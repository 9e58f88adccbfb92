"""The program's own spans on the profiler's clock.

In a traced run the program's flight recorder (``repro.obs``) keeps host
spans on its own clock, and ``FlightRecorder.anchor()``, called right
after the profiler starts, writes one ``obs.clock`` annotation into the
profiler's trace and one ``obs.clock`` instant into the ring. ``extract``
reads the annotation and every device operation with its named-scope path
from the profiler's ``.xplane.pb``; ``map_ring`` moves the ring's events
onto the profiler's nanoseconds. Everything after that is arithmetic on
flat lists, which the tests pin on a small recorded fixture:

- a span's self time (its duration minus what its children cover);
- device operations put inside spans;
- the device's idle time named by the innermost program span open.

"Host spans" here are the host phases: the dispatcher's track (``sched.*``
and ``backend.*`` spans) and the engine's step track (``engine.*``).
Session, row and queue tracks describe a turn's life, not what the host is
doing, and are not used to name idle time.
"""
from __future__ import annotations

import bisect
import glob
import os
import sys
from collections import defaultdict
from typing import Callable, Dict, List, Optional

from bench import trace

ANCHOR = "obs.clock"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# the profiler keeps each live program's HLO in its metadata plane; an
# instruction's metadata ``op_name`` is its named-scope path
META_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
# the named scopes of the megastep (models/transformer.py,
# models/attention.py); ``attn`` is split one level further
TOP_SCOPES = ("embed", "attn", "mlp", "head")
ATTN_SCOPES = ("qkv", "kv_write", "paged", "out")
# the tracks of the host phases: the dispatcher's and the engine's step
HOST_TRACKS = ("dispatcher", "megastep")
NONE = "none"


def log(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _varint(b, i: int):
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b):
    """(field number, value) of one protobuf message: an int for varints,
    a memoryview slice for every other wire type."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire in (1, 5):
            ln = 8 if wire == 1 else 4
            v, i = b[i:i + ln], i + ln
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def scope_paths(xspace: bytes) -> Dict:
    """{(program, instruction name): op_name} of the HLO programs the
    profiler recorded in its metadata plane, each program under the name
    its ``XLA Modules`` events carry. Fields read (xplane.proto,
    hlo.proto): XSpace.planes 1; XPlane.name 2, .event_metadata 4 (map:
    key 1, value 2), .stat_metadata 5; XEventMetadata.name 2, .stats 5;
    XStat.metadata_id 1, .bytes_value 6; XStatMetadata.name 2; HloProto
    .hlo_module 1; HloModuleProto.computations 3;
    HloComputationProto.instructions 2; HloInstructionProto.name 1,
    .metadata 7; OpMetadata.op_name 2."""
    out: Dict = {}
    for f, plane in _fields(memoryview(xspace)):
        if f != 1:
            continue
        name, metas, stat_names = None, [], {}
        for pf, pv in _fields(plane):
            if pf == 2:
                name = _text(pv)
            elif pf == 4:
                metas.append(pv)
            elif pf == 5:
                entry = dict(_fields(pv))
                stat_names[entry.get(1)] = _text(
                    dict(_fields(entry.get(2, b""))).get(2, b""))
        if name != META_PLANE:
            continue
        for entry in metas:
            meta = dict(_fields(entry)).get(2, b"")
            program = None
            for ef, ev in _fields(meta):
                if ef == 2:
                    program = _text(ev)
                elif ef == 5:
                    stat = dict(_fields(ev))
                    if stat_names.get(stat.get(1)) == HLO_STAT \
                            and 6 in stat:
                        _hlo_paths(stat[6], program, out)
    return out


def _hlo_paths(hlo_proto, program: str, out: Dict):
    module = dict(_fields(hlo_proto)).get(1, b"")
    for mf, comp in _fields(module):
        if mf != 3:
            continue
        for cf, inst in _fields(comp):
            if cf != 2:
                continue
            iname = path = None
            for inf, iv in _fields(inst):
                if inf == 1:
                    iname = _text(iv)
                elif inf == 7:
                    path = _text(dict(_fields(iv)).get(2, b""))
            if iname and path:
                out[(program, iname)] = path


def extract(trace_dir: str) -> Dict:
    """{"anchors": [[start_ns, dur_ns], ...] (the host ``obs.clock``
    annotations), "device": [[name, start_ns, dur_ns, scope_path], ...]
    (the first TPU's ``XLA Ops``)} of the newest trace. An op's scope path
    is the ``op_name`` of its instruction in the program whose ``XLA
    Modules`` event contains it; "" where the trace does not say."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    with open(paths[-1], "rb") as f:
        raw = f.read()
    scopes = scope_paths(raw)
    pd = ProfileData.from_serialized_xspace(raw)
    anchors, device = [], []
    tpus = sorted((p for p in pd.planes if p.name.startswith("/device:TPU:")),
                  key=lambda p: p.name)
    for plane in tpus[:1]:
        lines = {line.name: line for line in plane.lines}
        programs = sorted((float(ev.start_ns), float(ev.end_ns), ev.name)
                          for ev in lines[MODULES_LINE].events) \
            if MODULES_LINE in lines else []
        starts = [p[0] for p in programs]
        for ev in (lines[OPS_LINE].events if OPS_LINE in lines else ()):
            name = ev.name.split(" = ")[0].lstrip("%")
            t = float(ev.start_ns)
            k = bisect.bisect_right(starts, t) - 1
            program = programs[k][2] if k >= 0 and t <= programs[k][1] \
                else None
            device.append([name, t, float(ev.duration_ns),
                           scopes.get((program, name), "")])
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == ANCHOR:
                    anchors.append([float(ev.start_ns),
                                    float(ev.duration_ns)])
    device.sort(key=lambda e: (e[1], -e[2]))
    anchors.sort()
    return {"anchors": anchors, "device": device}


def map_ring(ring: List[Dict], anchors: List, dropped: int,
             window_start_ns: float) -> Optional[List[Dict]]:
    """The ring's events (``FlightRecorder.events()``) with ``ts`` and
    ``dur`` in the profiler's nanoseconds. The first anchor of each side
    gives the offset; with two or more on each side, the first and last
    also give the rate. None, with a ``[bench]`` line, when either side
    has no anchor or when the ring dropped events that ended inside the
    window (the oldest event it kept ends after the window's start)."""
    ring_anchors = [e["ts"] for e in ring if e["name"] == ANCHOR]
    if not anchors or not ring_anchors:
        log(f"no {ANCHOR} anchor in the "
            f"{'profiler trace' if not anchors else 'ring'}: the program's "
            "spans cannot be put on the device's clock")
        return None
    mids = [s + 0.5 * d for s, d in anchors]
    rate = 1e9
    if len(mids) >= 2 and len(ring_anchors) >= 2 \
            and ring_anchors[-1] > ring_anchors[0]:
        rate = (mids[-1] - mids[0]) / (ring_anchors[-1] - ring_anchors[0])
    off = mids[0] - ring_anchors[0] * rate
    out = [dict(e, ts=e["ts"] * rate + off, dur=e["dur"] * rate)
           for e in ring]
    if dropped:
        oldest = min(out, key=lambda e: e["seq"])
        if oldest["ts"] + oldest["dur"] > window_start_ns:
            log(f"the ring dropped {dropped} events, some inside the traced "
                "window: its spans are not read")
            return None
    return out


def host_spans(spans: List[Dict]) -> List[Dict]:
    """The spans of the host phases (see the module's docstring)."""
    return [e for e in spans if e["ph"] == "X"
            and e["track"] in HOST_TRACKS]


def in_window(spans: List[Dict], name: str, lo: float, hi: float):
    """The spans called ``name`` that lie wholly in [lo, hi]."""
    return [e for e in spans if e["name"] == name and e["ph"] == "X"
            and lo <= e["ts"] and e["ts"] + e["dur"] <= hi]


def children(spans: List[Dict], parent: Dict,
             names=None) -> List[Dict]:
    """Spans on ``parent``'s track that lie inside it (``names``, if given,
    keeps those names only)."""
    s, t = parent["ts"], parent["ts"] + parent["dur"]
    return [e for e in spans if e is not parent and e["ph"] == "X"
            and e["track"] == parent["track"]
            and e["group"] == parent["group"]
            and (names is None or e["name"] in names)
            and s <= e["ts"] and e["ts"] + e["dur"] <= t]


def _union(ivs) -> float:
    tot, end = 0.0, float("-inf")
    for s, t in sorted(ivs):
        if t <= end:
            continue
        tot += t - max(s, end)
        end = t
    return tot


def self_ns(spans: List[Dict], span: Dict, names=None) -> float:
    """``span``'s duration minus what its children (``names``, if given)
    cover."""
    kids = children(spans, span, names)
    return span["dur"] - _union((e["ts"], e["ts"] + e["dur"]) for e in kids)


def ops_inside(leaves: List, span: Dict, match: Callable) -> float:
    """Device time of the operations of ``leaves`` (``trace.leaves``:
    sorted by start) for which ``match(name, long_name)`` that start inside
    ``span``. A step's operations run between its dispatch and its
    readback, so they lie inside its host span."""
    s, t = span["ts"], span["ts"] + span["dur"]
    i = bisect.bisect_left(leaves, s, key=lambda e: e[1])
    tot = 0.0
    while i < len(leaves) and leaves[i][1] <= t:
        e = leaves[i]
        if match(e[0], e[3]):
            tot += e[2]
        i += 1
    return tot


def _segments(spans: List[Dict], lo: float, hi: float):
    """[start, end, name] pieces of [lo, hi], each named by the innermost
    host span open in it (the one that began last), or ``none``."""
    hs = [e for e in host_spans(spans)
          if e["ts"] < hi and e["ts"] + e["dur"] > lo]
    cuts = sorted({lo, hi} | {min(max(x, lo), hi) for e in hs
                              for x in (e["ts"], e["ts"] + e["dur"])})
    starts = sorted(hs, key=lambda e: e["ts"])
    out, active, j = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        if b <= a:
            continue
        while j < len(starts) and starts[j]["ts"] <= a:
            active.append(starts[j])
            j += 1
        active = [e for e in active if e["ts"] + e["dur"] > a]
        inner = max(active, key=lambda e: (e["ts"], -e["dur"]),
                    default=None)
        out.append([a, b, inner["name"] if inner else NONE])
    return out


def _name_at(segs, t: float) -> str:
    i = bisect.bisect_right(segs, t, key=lambda g: g[1])
    return segs[i][2] if i < len(segs) and segs[i][0] <= t else NONE


def _idle(events: Dict, lo: float, hi: float):
    """The device's idle intervals in [lo, hi]."""
    busy = trace.busy_intervals(events, lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def idle_by_span(events: Dict, spans: List[Dict], lo: float,
                 hi: float) -> Dict[str, float]:
    """The device's idle time in [lo, hi], in seconds, summed by the
    innermost host span open at each instant (``none``: no host span)."""
    gaps = _idle(events, lo, hi)
    segs = _segments(spans, lo, hi)
    out: Dict[str, float] = defaultdict(float)
    k = 0
    for s, t in gaps:
        while k < len(segs) and segs[k][1] <= s:
            k += 1
        i = k
        while i < len(segs) and segs[i][0] < t:
            a, b = max(s, segs[i][0]), min(t, segs[i][1])
            if b > a:
                out[segs[i][2]] += (b - a) * 1e-9
            i += 1
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def idle_gaps(events: Dict, spans: List[Dict], lo: float, hi: float,
              n: int = 10):
    """The ``n`` longest idle gaps of the device in [lo, hi] (those of
    ``trace.idle_gaps``), in seconds, each named by the innermost host
    span open at its middle."""
    gaps = _idle(events, lo, hi)
    segs = _segments(spans, lo, hi)
    return [[_name_at(segs, 0.5 * (s + t)), (t - s) * 1e-9]
            for s, t in sorted(gaps, key=lambda g: g[0] - g[1])[:n]]


def scope_of(path: str) -> str:
    """The megastep scope an op's path names: ``embed``, ``attn/<part>``,
    ``mlp`` or ``head``; ``none`` outside them."""
    parts = path.split("/")
    for i, p in enumerate(parts):
        if p in TOP_SCOPES:
            if p == "attn" and parts[i + 1:i + 2] and \
                    parts[i + 1] in ATTN_SCOPES:
                return f"attn/{parts[i + 1]}"
            return p
    return NONE


def device_by_scope(device: List, lo: float, hi: float) -> Dict[str, float]:
    """Device time of the innermost operations in [lo, hi], in seconds, by
    the megastep scope each belongs to."""
    out: Dict[str, float] = defaultdict(float)
    for e in trace.leaves({"device": device}):
        s, t = max(e[1], lo), min(e[1] + e[2], hi)
        if t > s:
            out[scope_of(e[3])] += (t - s) * 1e-9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))
