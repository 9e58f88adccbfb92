"""Flight-recorder tracing (DESIGN.md §12).

A preallocated ring-buffer event log for the fused serving stack:

  * **Fixed capacity, allocation-free on the hot path.** Events live in
    preallocated parallel lists (timestamp, duration, interned name id,
    interned track id, up to four float args); recording an event is a
    handful of slot stores. When the ring is full the oldest events are
    overwritten — flight-recorder semantics — and ``dropped`` counts the
    casualties, so a reader always knows whether the window is complete.
  * **One clock domain, anchored to the profiler's.** Every timestamp is
    host ``time.perf_counter()`` (monotonic, sub-microsecond). There are
    deliberately NO in-jit timestamps: a device-side clock read would force
    a host sync (or a new output crossing the jit boundary), breaking the
    megastep's one-dispatch / int32-only-return contract — see DESIGN.md
    §12. ``anchor()`` ties the ring's clock to a running ``jax.profiler``
    session: it writes one ``obs.clock`` annotation into the profiler's
    trace and one ``obs.clock`` instant into the ring at the same moment,
    so a reader holding both maps every ring event onto the profiler's
    nanoseconds, beside the device's operations.
  * **Safe for concurrent writers.** The client thread (``submit``) and the
    dispatcher thread (engine step) record into one ring; each event claims
    its slot with one ``next()`` of an ``itertools.count``, which the
    interpreter lock makes atomic, so no two writers share a slot.
  * **Spans and instants.** A span is recorded at its END as a Chrome
    "complete" event (begin timestamp captured by the caller via
    ``now()``); an instant is a point event. Interning (``name()``,
    ``track()``) happens once at instrumentation-setup time, so the hot
    path never hashes strings.
  * **Exporter.** ``export_chrome`` writes Chrome trace-event JSON that
    loads in Perfetto / chrome://tracing — tracks are (pid, tid) pairs
    derived from the registered track groups (one process row per group:
    engine, engine rows, sessions, mlfq, sched) — with the clock anchor in
    ``otherData``. ``validate_chrome`` is the schema check CI runs against
    the exported artifact.

A disabled recorder (``TraceConfig(enabled=False)``, the default) keeps
the full API but drops every event before touching the buffer, so
instrumented code needs no branches beyond the recorder's own.
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["TraceConfig", "FlightRecorder", "validate_chrome"]

_INSTANT, _SPAN = 0, 1
_MAX_ARGS = 4
# name of the clock anchor, in the profiler's trace and in the ring alike
ANCHOR = "obs.clock"
# rounding room (µs) when checking that exported spans nest
_NEST_SLACK_US = 1e-3


@dataclasses.dataclass
class TraceConfig:
    """Gates the flight recorder. Off by default; when on, the overhead
    contract is <= 2% tokens/sec on the sched_live smoke (CI-gated via
    BENCH_obs.json)."""
    enabled: bool = False
    capacity: int = 1 << 16      # events; ~3 MB of ring at 44 B/event

    def __post_init__(self):
        if self.capacity < 16:
            raise ValueError(
                f"trace capacity {self.capacity} too small: the ring must "
                "hold at least 16 events (one megastep's worth with "
                "headroom) to be a usable flight recorder")
        if self.capacity > (1 << 24):
            raise ValueError(
                f"trace capacity {self.capacity} too large: the ring is "
                "preallocated host memory; cap it at 2^24 events (~700 MB)")


class FlightRecorder:
    """Preallocated ring-buffer trace log with drop-oldest semantics."""

    def __init__(self, config: Optional[TraceConfig] = None):
        self.config = config or TraceConfig()
        self.enabled = self.config.enabled
        n = self.config.capacity
        self.capacity = n
        # Hot-path ring: parallel preallocated Python lists. A plain list
        # slot store is ~2.5x cheaper than a numpy scalar assignment, and
        # every stored value is a reference the caller already holds
        # (floats from perf_counter()/args, interned small ints) — so the
        # emit path stays allocation-free. numpy views are rebuilt only at
        # export time in events().
        self._ts = [0.0] * n
        self._dur = [0.0] * n
        self._ph = [0] * n
        self._name = [0] * n
        self._track = [0] * n
        self._a0 = [0.0] * n
        self._a1 = [0.0] * n
        self._a2 = [0.0] * n
        self._a3 = [0.0] * n
        # the claim each slot holds (-1: never written); claims come from
        # one counter shared by every writer thread
        self._seq = [-1] * n
        self._claims = itertools.count()
        self._anchor_ts: Optional[float] = None
        # interning tables: id 0 is reserved for "unnamed"/"main" so a
        # disabled recorder can hand out 0 without registering anything
        self._names: List[Tuple[str, Tuple[str, ...]]] = [("event", ())]
        self._name_ids: Dict[str, int] = {"event": 0}
        self._tracks: List[Tuple[str, str]] = [("main", "main")]
        self._track_ids: Dict[Tuple[str, str], int] = {("main", "main"): 0}
        self._t0 = time.perf_counter()

    # ------------------------------------------------------ interning
    def name(self, label: str, arg_labels: Sequence[str] = ()) -> int:
        """Intern an event name (+ the labels its numeric args carry in
        exports). Call once at instrumentation-setup time."""
        nid = self._name_ids.get(label)
        if nid is None:
            nid = len(self._names)
            self._names.append((label, tuple(arg_labels)))
            self._name_ids[label] = nid
        return nid

    def track(self, label: str, group: str = "main") -> int:
        """Intern a display track. ``group`` becomes the Perfetto process
        row (pid); each track in it a thread row (tid)."""
        key = (group, label)
        tid = self._track_ids.get(key)
        if tid is None:
            tid = len(self._tracks)
            self._tracks.append((label, group))
            self._track_ids[key] = tid
        return tid

    # ------------------------------------------------------- recording
    @staticmethod
    def now() -> float:
        return time.perf_counter()

    def _emit(self, ph: int, name_id: int, track_id: int, ts: float,
              dur: float, a0: float, a1: float, a2: float, a3: float):
        k = next(self._claims)
        i = k % self.capacity
        self._ts[i] = ts
        self._dur[i] = dur
        self._ph[i] = ph
        self._name[i] = name_id
        self._track[i] = track_id
        self._a0[i] = a0
        self._a1[i] = a1
        self._a2[i] = a2
        self._a3[i] = a3
        self._seq[i] = k

    # instant/complete inline the _emit body: one less call frame per
    # event on the per-token hot path (the default-arg _clock binding
    # skips the time-module attribute lookup each call)
    def instant(self, name_id: int, track_id: int, a0: float = 0.0,
                a1: float = 0.0, a2: float = 0.0, a3: float = 0.0,
                _clock=time.perf_counter):
        if not self.enabled:
            return
        k = next(self._claims)
        i = k % self.capacity
        self._ts[i] = _clock()
        self._dur[i] = 0.0
        self._ph[i] = _INSTANT
        self._name[i] = name_id
        self._track[i] = track_id
        self._a0[i] = a0
        self._a1[i] = a1
        self._a2[i] = a2
        self._a3[i] = a3
        self._seq[i] = k

    def complete(self, name_id: int, track_id: int, t0: float,
                 a0: float = 0.0, a1: float = 0.0, a2: float = 0.0,
                 a3: float = 0.0, _clock=time.perf_counter):
        """Record a span that began at ``t0`` (from ``now()``) and ends
        now."""
        if not self.enabled:
            return
        dur = _clock() - t0
        k = next(self._claims)
        i = k % self.capacity
        self._ts[i] = t0
        self._dur[i] = dur if dur > 0.0 else 0.0
        self._ph[i] = _SPAN
        self._name[i] = name_id
        self._track[i] = track_id
        self._a0[i] = a0
        self._a1[i] = a1
        self._a2[i] = a2
        self._a3[i] = a3
        self._seq[i] = k

    def interval(self, name_id: int, track_id: int, t0: float, t1: float,
                 a0: float = 0.0, a1: float = 0.0, a2: float = 0.0,
                 a3: float = 0.0):
        """Record a span from ``t0`` to ``t1`` (both from ``now()``): for a
        phase whose end has already passed, such as the children a step
        emits once it knows it did work."""
        if self.enabled:
            self._emit(_SPAN, name_id, track_id, t0, max(t1 - t0, 0.0),
                       a0, a1, a2, a3)

    def anchor(self):
        """Tie the ring's clock to a running ``jax.profiler`` session: one
        ``obs.clock`` ``TraceAnnotation`` goes into the profiler's trace
        and an ``obs.clock`` instant at the ring-clock midpoint of that
        call into the ring. The offset between the two midpoints maps ring
        time onto the profiler's nanoseconds. Call it right after
        ``jax.profiler.start_trace``; nothing else of the recorder writes
        into the profiler's trace."""
        if not self.enabled:
            return
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(ANCHOR):
            pass
        t1 = time.perf_counter()
        self._anchor_ts = 0.5 * (t0 + t1)
        self._emit(_INSTANT, self.name(ANCHOR), self.track("clock", "obs"),
                   self._anchor_ts, 0.0, 0.0, 0.0, 0.0, 0.0)

    def span(self, label: str, track_label: str = "main",
             group: str = "main", **args):
        """Convenience context-manager span for non-hot-path callers (hot
        paths pre-intern and call ``complete`` directly)."""
        return _Span(self, self.name(label, tuple(args)),
                     self.track(track_label, group),
                     tuple(float(v) for v in args.values()))

    # ------------------------------------------------------ accounting
    # (export-time: each reads the slots' claims)
    @property
    def total(self) -> int:
        """Events ever recorded."""
        return max(self._seq) + 1

    @property
    def recorded(self) -> int:
        """Events currently held in the ring."""
        return min(self.total, self.capacity)

    @property
    def dropped(self) -> int:
        """Events overwritten by drop-oldest wraparound."""
        return max(0, self.total - self.capacity)

    def reset(self):
        self._seq = [-1] * self.capacity
        self._claims = itertools.count()
        self._anchor_ts = None
        self._t0 = time.perf_counter()

    # ------------------------------------------------------- exporting
    def events(self) -> List[dict]:
        """Decode the ring into dicts sorted by timestamp, a span before
        the spans it contains (the ring is not time-ordered: spans are
        recorded at their end). ``seq`` is the event's place in recording
        order. Allocation-heavy; export-time only."""
        idx = [i for i, k in enumerate(self._seq) if k >= 0]
        idx.sort(key=lambda i: (self._ts[i], -self._dur[i], self._seq[i]))
        av = (self._a0, self._a1, self._a2, self._a3)
        out = []
        for i in idx:
            name, labels = self._names[self._name[i]]
            tlabel, group = self._tracks[self._track[i]]
            args = {lab: av[j][i] for j, lab in enumerate(labels)}
            out.append({
                "name": name, "track": tlabel, "group": group,
                "ph": "X" if self._ph[i] == _SPAN else "i",
                "ts": self._ts[i], "dur": self._dur[i],
                "args": args, "seq": self._seq[i],
            })
        return out

    def _pids_tids(self) -> Dict[int, Tuple[int, int]]:
        """track id -> (pid, tid): pid per group, tid per track within."""
        groups: Dict[str, int] = {}
        per_group: Dict[str, int] = {}
        mapping = {}
        for tid_, (label, group) in enumerate(self._tracks):
            pid = groups.setdefault(group, len(groups) + 1)
            per_group[group] = per_group.get(group, 0) + 1
            mapping[tid_] = (pid, per_group[group])
        return mapping

    def chrome(self) -> dict:
        """Chrome trace-event JSON object (ts/dur in microseconds,
        relative to recorder start; metadata rows name each group/track)."""
        mapping = self._pids_tids()
        evs: List[dict] = []
        seen_groups = set()
        for tid_, (label, group) in enumerate(self._tracks):
            pid, tid = mapping[tid_]
            if group not in seen_groups:
                seen_groups.add(group)
                evs.append({"ph": "M", "name": "process_name", "pid": pid,
                            "tid": 0, "args": {"name": group}})
            evs.append({"ph": "M", "name": "thread_name", "pid": pid,
                        "tid": tid, "args": {"name": label}})
        for e in self.events():
            pid, tid = mapping[self._track_ids[(e["group"], e["track"])]]
            ev = {"name": e["name"], "ph": e["ph"], "pid": pid, "tid": tid,
                  "ts": (e["ts"] - self._t0) * 1e6, "cat": e["group"],
                  "args": e["args"]}
            if e["ph"] == "X":
                ev["dur"] = e["dur"] * 1e6
            else:
                ev["s"] = "t"
            evs.append(ev)
        other = {"dropped_events": self.dropped,
                 "clock_domain": "host perf_counter"}
        if self._anchor_ts is not None:
            # where the profiler's obs.clock annotation sits in this
            # file's timeline: shift the file by (the annotation's
            # midpoint - this) to overlay it on the profiler's capture
            other["clock_anchor_us"] = (self._anchor_ts - self._t0) * 1e6
        return {"traceEvents": evs, "displayTimeUnit": "ms",
                "otherData": other}

    def export_chrome(self, path: str):
        with open(path, "w") as f:
            json.dump(self.chrome(), f)


class _Span:
    __slots__ = ("rec", "name_id", "track_id", "args", "t0")

    def __init__(self, rec: FlightRecorder, name_id: int, track_id: int,
                 args: Tuple[float, ...]):
        self.rec, self.name_id, self.track_id = rec, name_id, track_id
        self.args = args

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        a = self.args + (0.0,) * (_MAX_ARGS - len(self.args))
        self.rec.complete(self.name_id, self.track_id, self.t0, *a[:4])
        return False


def validate_chrome(obj: dict) -> List[str]:
    """Schema check for an exported Chrome trace: returns a list of
    problems (empty = valid). Besides the fields and the time order, the
    spans of one track must nest: a span that starts inside another ends
    inside it too, or Perfetto draws the pair as nonsense. CI runs this
    against the sched_live trace artifact."""
    problems = []
    evs = obj.get("traceEvents")
    if not isinstance(evs, list) or not evs:
        return ["traceEvents missing or empty"]
    last_ts = -np.inf
    open_ends: Dict[Tuple[int, int], List[float]] = {}
    for i, e in enumerate(evs):
        ph = e.get("ph")
        if ph not in ("X", "i", "M"):
            problems.append(f"event {i}: unknown ph {ph!r}")
            continue
        if not isinstance(e.get("name"), str) or not e["name"]:
            problems.append(f"event {i}: missing name")
        if not isinstance(e.get("pid"), int) \
                or not isinstance(e.get("tid"), int):
            problems.append(f"event {i}: pid/tid must be ints")
        if ph == "M":
            continue
        ts = e.get("ts")
        if not isinstance(ts, (int, float)):
            problems.append(f"event {i}: non-numeric ts")
            continue
        if ts < last_ts:
            problems.append(f"event {i}: ts {ts} < previous {last_ts} "
                            "(export must be time-sorted)")
        last_ts = ts
        if ph != "X":
            continue
        if not isinstance(e.get("dur"), (int, float)) or e["dur"] < 0:
            problems.append(f"event {i}: X event needs dur >= 0")
            continue
        stack = open_ends.setdefault((e.get("pid"), e.get("tid")), [])
        while stack and stack[-1] <= ts + _NEST_SLACK_US:
            stack.pop()
        end = ts + e["dur"]
        if stack and end > stack[-1] + _NEST_SLACK_US:
            problems.append(f"event {i}: span {e['name']!r} overlaps the "
                            "span it starts in on its track")
        stack.append(end)
    return problems
