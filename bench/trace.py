"""From a profiler trace to device busy time, kernel time and idle gaps.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
two flat lists, in nanoseconds on the profiler's clock: the operations
that ran on the cell's TPUs (each one's ``XLA Ops`` line), each tagged by
its chip, and the benchmark's own host annotations (``bench.*``).
Everything after that is plain arithmetic on those lists, which is what
the tests pin on small recorded traces. Busy time and kernel time are
sums over the chips, each chip's busy time the union of its own
operations; an operation without a chip tag is chip 0's.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"


def extract(trace_dir: str, chips: int = 1) -> Dict:
    """{"device": [[name, start_ns, dur_ns, hlo_op_long_name, chip], ...],
    "host": [[name, start_ns, dur_ns], ...]} of the newest trace, with the
    operations of its first ``chips`` TPUs."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    device, host = [], []
    tpus = sorted((p for p in pd.planes if p.name.startswith("/device:TPU:")),
                  key=lambda p: p.name)
    for chip, plane in enumerate(tpus[:chips]):
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                stats = dict(ev.stats)
                # the op's name is its HLO instruction text: keep the
                # instruction's own name, the part before " = "
                device.append([ev.name.split(" = ")[0].lstrip("%"),
                               float(ev.start_ns), float(ev.duration_ns),
                               str(stats.get("long_name", "")), chip])
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("bench."):
                    host.append([ev.name, float(ev.start_ns),
                                 float(ev.duration_ns)])
    if not device:
        import sys
        seen = [(p.name, [ln.name for ln in p.lines]) for p in pd.planes]
        print(f"[bench] no {OPS_LINE!r} events on a TPU plane; planes and "
              f"lines: {seen}", file=sys.stderr)
    device.sort(key=lambda e: (e[1], -e[2]))
    host.sort(key=lambda e: e[1])
    return {"device": device, "host": host}


def by_chip(events: Dict) -> Dict[int, Dict]:
    """The events with each chip's operations alone, by chip."""
    chips: Dict[int, List] = defaultdict(list)
    for e in events["device"]:
        chips[e[4] if len(e) > 4 else 0].append(e)
    return {c: {"device": ops, "host": events.get("host", [])}
            for c, ops in sorted(chips.items())}


def leaves(events: Dict) -> List:
    """Device operations that contain no other on their chip: a loop (the
    layer scan's ``while``) is listed beside the operations it runs, so
    only the innermost ones are counted by name."""
    out = []
    for one in by_chip(events).values():
        dev = sorted(one["device"], key=lambda e: (e[1], -e[2]))
        out += [e for e, nxt in zip(dev, dev[1:] + [None])
                if nxt is None or nxt[1] >= e[1] + e[2]]
    return sorted(out, key=lambda e: (e[1], -e[2]))


def window(events: Dict) -> Optional[Tuple[float, float]]:
    """The traced window: the benchmark's ``bench.window`` annotation."""
    for name, start, dur in events["host"]:
        if name == WINDOW:
            return start, start + dur
    return None


def _clip(events, lo: float, hi: float):
    for e in events:
        s, t = max(e[1], lo), min(e[1] + e[2], hi)
        if t > s:
            yield e, s, t


def busy_intervals(events: Dict, lo: float, hi: float) -> List[List[float]]:
    """Union of the device operations' intervals inside [lo, hi], of one
    chip's events."""
    out: List[List[float]] = []
    for _, s, t in sorted(_clip(events["device"], lo, hi),
                          key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return out


def busy_ns(events: Dict, lo: float, hi: float) -> float:
    """Busy time inside [lo, hi], summed over the chips."""
    return sum(sum(t - s for s, t in busy_intervals(one, lo, hi))
               for one in by_chip(events).values())


def op_ns(events: Dict, lo: float, hi: float, match) -> float:
    """Device time of the innermost operations for which
    ``match(name, long_name)``."""
    return sum(t - s for e, s, t in _clip(leaves(events), lo, hi)
               if match(e[0], e[3]))


def top_ops(events: Dict, lo: float, hi: float, n: int = 10):
    """The innermost device operations that took most time, by name, in
    seconds."""
    tot: Dict[str, float] = defaultdict(float)
    for e, s, t in _clip(leaves(events), lo, hi):
        tot[e[0]] += t - s
    return [[k, v * 1e-9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(events: Dict, lo: float, hi: float, n: int = 10):
    """The longest idle gaps of the device inside [lo, hi], in seconds,
    each named by the innermost benchmark annotation open at its middle
    (``host`` when none is: the dispatcher between calls). With several
    chips, each chip's gaps, the name followed by `` @chip<c>``."""
    chips = by_chip(events) or {0: events}
    gaps = []
    for c, one in chips.items():
        busy = busy_intervals(one, lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1], c)
                 for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    spans = [h for h in events["host"] if h[0] != WINDOW]
    out = []
    for s, t, c in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = 0.5 * (s + t)
        open_ = [h for h in spans if h[1] <= mid <= h[1] + h[2]]
        name = max(open_, key=lambda h: h[1])[0] if open_ else "host"
        out.append([name if len(chips) == 1 else f"{name} @chip{c}",
                    (t - s) * 1e-9])
    return out
