"""The program's spans on the profiler's clock (``bench/spans.py``) and the
per-layer metrics read from them, on the CPU: a small recorded fixture
(``data/spans_small.json``: the ring's events, the clock anchors and the
device operations of one decode and one prefill step of a traced
agent-tools run on the chip) and hand-made cases."""
import json
import os
import sys
from types import SimpleNamespace

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [REPO, os.path.join(REPO, "src")]

from bench import harness, spans, stats, trace  # noqa: E402

DATA = os.path.join(REPO, "tests", "bench", "data")
NEW = ["sched.queue_wait_p95_s", "sched.park_wait_p50_s", "sched.host_ms",
       "engine.host_ms", "paged_attn_ms.decode"]


def _read(name, ctx):
    return harness.metric_reader(name, (harness.BENCH,))(ctx)


def _span(name, ts, dur, track="dispatcher", group="sched", seq=0, **args):
    return {"name": name, "track": track, "group": group, "ph": "X",
            "ts": ts, "dur": dur, "args": args, "seq": seq}


def _instant(name, ts, track="Q0", group="mlfq", seq=0, **args):
    return {"name": name, "track": track, "group": group, "ph": "i",
            "ts": ts, "dur": 0.0, "args": args, "seq": seq}


def _ctx(spans_, device=(), lo=0.0, hi=1000.0):
    dev = [list(e) for e in device]
    return SimpleNamespace(spans=spans_, events={"device": dev, "host": []},
                           scopes=dev, trace_window=(lo, hi), trace=trace,
                           stats=stats)


# ------------------------------------------------------------- mapping
def test_map_ring_uses_the_anchors_offset_and_rate():
    ring = [_instant(spans.ANCHOR, 10.0, seq=0),
            _span("engine.pack", 10.5, 0.25, seq=1),
            _instant(spans.ANCHOR, 12.0, seq=2)]
    # the profiler's clock runs 1 ppm fast and starts elsewhere
    rate = 1e9 * (1 + 1e-6)
    anchors = [[1e6 - 500.0, 1000.0], [1e6 + 2.0 * rate - 500.0, 1000.0]]
    out = spans.map_ring(ring, anchors, dropped=0, window_start_ns=0.0)
    pack = next(e for e in out if e["name"] == "engine.pack")
    assert pack["ts"] == pytest.approx(1e6 + 0.5 * rate)
    assert pack["dur"] == pytest.approx(0.25 * rate)
    assert ring[1]["ts"] == 10.5                  # the input is untouched


def test_a_ring_without_an_anchor_reads_nothing():
    ring = [_span("engine.pack", 1.0, 0.1)]
    assert spans.map_ring(ring, [[0.0, 10.0]], 0, 0.0) is None
    ring.append(_instant(spans.ANCHOR, 1.0, seq=1))
    assert spans.map_ring(ring, [], 0, 0.0) is None


def test_a_ring_that_dropped_events_inside_the_window_reads_none():
    """Drop-oldest: what the ring lost was recorded before its oldest kept
    event. If that one ends after the window's start, events of the window
    may be gone, and every reader reads nothing."""
    ring = [_span("engine.megastep", 0.5, 0.2, seq=100, C=1),
            _instant(spans.ANCHOR, 1.0, seq=101)]
    anchors = [[1e9 - 5.0, 10.0]]                  # the same clock
    kept = spans.map_ring(ring, anchors, dropped=100, window_start_ns=0.8e9)
    assert kept is not None                       # lost before the window
    assert spans.map_ring(ring, anchors, dropped=100,
                          window_start_ns=0.6e9) is None
    ctx = _ctx(spans.map_ring(ring, anchors, 100, 0.6e9))
    assert all(_read(m, ctx) is None for m in NEW)


def test_readers_read_nothing_without_the_programs_spans():
    """A traced run of a program without the recorder's spans, or a
    harness that does not hand them over, leaves the metrics out."""
    ctx = SimpleNamespace(events={"device": [], "host": []},
                          trace_window=(0.0, 1.0), trace=trace, stats=stats)
    assert all(_read(m, ctx) is None for m in NEW)
    ctx.spans = None
    assert all(_read(m, ctx) is None for m in NEW)


# ---------------------------------------------------------- arithmetic
def test_self_time_subtracts_the_union_of_children():
    p = _span("sched.pass", 0.0, 100.0)
    kids = [_span("sched.schedule", 0.0, 30.0),
            _span("backend.begin_turn", 10.0, 10.0),   # inside schedule
            _span("sched.step", 30.0, 50.0),
            _span("sched.step", 50.0, 5.0, track="other")]
    evs = [p] + kids
    assert spans.self_ns(evs, p) == pytest.approx(20.0)
    assert spans.self_ns(evs, p, ("sched.step",)) == pytest.approx(50.0)


def test_idle_time_is_named_by_the_innermost_host_span():
    host = [_span("sched.pass", 0.0, 100.0),
            _span("sched.schedule", 0.0, 20.0),
            _span("sched.step", 20.0, 70.0),
            _span("engine.megastep", 20.0, 70.0, track="megastep",
                  group="engine", C=1),
            _span("engine.pack", 20.0, 10.0, track="megastep",
                  group="engine"),
            _span("engine.dispatch", 30.0, 5.0, track="megastep",
                  group="engine"),
            _span("engine.readback", 35.0, 55.0, track="megastep",
                  group="engine"),
            _span("sched.charge", 90.0, 10.0),
            # a turn's life, not a host phase: never names idle time
            _span("sched.queued", 0.0, 120.0, track="wait 0", group="mlfq")]
    device = [["fusion.1", 33.0, 20.0, ""], ["paged.1", 53.0, 30.0, ""]]
    ev = {"device": device, "host": []}
    idle = spans.idle_by_span(ev, host, 0.0, 120.0)
    assert idle == pytest.approx({
        "sched.schedule": 20e-9, "engine.pack": 10e-9,
        "engine.dispatch": 3e-9, "engine.readback": 7e-9,
        "sched.charge": 10e-9, "none": 20e-9})
    assert sum(idle.values()) * 1e9 == pytest.approx(
        120.0 - trace.busy_ns(ev, 0.0, 120.0))
    gaps = spans.idle_gaps(ev, host, 0.0, 120.0, n=2)
    assert gaps == [["none", pytest.approx(37e-9)],
                    ["sched.schedule", pytest.approx(33e-9)]]


def test_scope_paths_are_read_from_the_profilers_hlo(tmp_path):
    """The profiler keeps each live program's HLO in its metadata plane;
    ``scope_paths`` reads every instruction's named-scope path from it."""
    import glob

    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("mlp"):
            y = jnp.tanh(x @ x)
        with jax.named_scope("head"):
            return y.sum()

    x = jnp.ones((64, 64))
    f(x).block_until_ready()                 # compiled before the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    with open(path, "rb") as fh:
        paths = spans.scope_paths(fh.read())
    scopes = {spans.scope_of(p) for p in paths.values()}
    assert {"mlp", "head"} <= scopes
    # keyed by the program's name as its XLA Modules events carry it
    assert any(prog.startswith("jit_f(") for prog, _ in paths)


def test_device_time_by_scope():
    assert spans.scope_of(
        "jit(<lambda>)/while/body/closed_call/attn/kv_write/scatter") \
        == "attn/kv_write"
    assert spans.scope_of("jit(<lambda>)/head/reduce_and") == "head"
    assert spans.scope_of("jit(f)/while/body/closed_call/attn/add") == "attn"
    assert spans.scope_of("jit(<lambda>)/while/body/dynamic_slice") \
        == spans.NONE
    assert spans.scope_of("") == spans.NONE
    device = [["while.1", 0.0, 100.0, "jit(f)/while"],
              ["fusion.2", 0.0, 40.0, "jit(f)/while/body/mlp/dot"],
              ["paged.3", 40.0, 50.0, "jit(f)/while/body/attn/paged/x"],
              ["copy.4", 95.0, 10.0, ""]]
    assert spans.device_by_scope(device, 0.0, 100.0) == pytest.approx(
        {"attn/paged": 50e-9, "mlp": 40e-9, "none": 5e-9})


# ------------------------------------------------------------- readers
def _sched_case():
    """Two turns submitted in the window [100, 1100]: tid 1 admitted after
    40 ns and parked at 500 (resumed after 100), tid 2 never admitted;
    tid 3 parked at 900 and still waiting at the window's end."""
    return [
        _instant("sched.submitted", 100.0, tid=1, level=0),
        _span("sched.queued", 100.0, 40.0, track="wait 0", group="mlfq",
              tid=1, level=0, resumed=0, rid=7),
        _instant("sched.preempted", 500.0, tid=1, level=0, served_tokens=4),
        _span("sched.queued", 501.0, 100.0, track="wait 0", group="mlfq",
              tid=1, level=1, resumed=1, rid=7),
        _instant("sched.submitted", 700.0, tid=2, level=0),
        _instant("sched.kv_degraded", 900.0, track="faults", group="sched",
                 victim_tid=3, for_tid=2),
        _instant("sched.submitted", 50.0, tid=4, level=0),  # before window
    ]


def test_queue_and_park_waits_follow_the_wrappers_rule():
    ctx = _ctx(_sched_case(), lo=100.0, hi=1100.0)
    # waits 40 ns and 400 ns (censored at the window's end)
    assert _read("sched.queue_wait_p95_s", ctx) == pytest.approx(
        stats.quantile([40e-9, 400e-9], 0.95))
    # the episode that ended in the window (100 ns) and the open one
    # (200 ns so far)
    assert _read("sched.park_wait_p50_s", ctx) == pytest.approx(150e-9)


def test_host_ms_readers_subtract_the_device_wait():
    s = []
    for k, (c, read_ns) in enumerate([(1, 6e6), (64, 9e6)]):
        t = k * 20e6
        s += [_span("sched.pass", t, 15e6),
              _span("sched.step", t + 1e6, 12e6),
              _span("engine.megastep", t + 2e6, 10e6, track="megastep",
                    group="engine", C=c),
              _span("engine.readback", t + 3e6, read_ns, track="megastep",
                    group="engine")]
    device = [["paged_prefill_attention_bcd.9", 3.5e6, 5e6, ""],
              ["fusion.1", 8.6e6, 0.3e6, ""],
              ["paged_prefill_attention_bcd.6", 23.5e6, 7e6, ""]]
    ctx = _ctx(s, device, lo=0.0, hi=40e6)
    assert _read("sched.host_ms", ctx) == pytest.approx(3.0)
    assert _read("engine.host_ms", ctx) == pytest.approx(
        (4.0 + 1.0) / 2)
    assert _read("paged_attn_ms.decode", ctx) == pytest.approx(5.0)


# ------------------------------------------------------------- fixture
def _fixture():
    with open(os.path.join(DATA, "spans_small.json")) as f:
        return json.load(f)


def _fixture_ctx():
    fx = _fixture()
    lo, hi = fx["window"]
    mapped = spans.map_ring(fx["ring"], fx["anchors"], 0, lo)
    ops = [[n, s, d, ""] for n, s, d, _ in fx["device"]]
    return _ctx(mapped, ops, lo, hi), fx


def test_recorded_ops_lie_inside_their_megastep_spans():
    """On the chip's clocks, mapped through the anchors: every device op
    of the cut starts inside one of the two megastep spans, after its
    ``engine.dispatch`` began, and ends before its ``engine.readback``
    ended."""
    ctx, _ = _fixture_ctx()
    lo, hi = ctx.trace_window
    steps = spans.in_window(ctx.spans, "engine.megastep", lo, hi)
    assert sorted(s["args"]["C"] for s in steps) == [1, 128]
    for st in steps:
        phases = spans.children(ctx.spans, st)
        assert [k["name"] for k in phases][1:4] == [
            "engine.pack", "engine.dispatch", "engine.readback"]
        assert sum(k["dur"] for k in phases) <= st["dur"]
        d = spans.children(ctx.spans, st, ("engine.dispatch",))[0]
        r = spans.children(ctx.spans, st, ("engine.readback",))[0]
        ops = [e for e in ctx.events["device"]
               if st["ts"] <= e[1] <= st["ts"] + st["dur"]]
        assert ops
        assert min(e[1] for e in ops) > d["ts"]
        assert max(e[1] + e[2] for e in ops) < r["ts"] + r["dur"]
    assert all(any(s["ts"] <= e[1] <= s["ts"] + s["dur"] for s in steps)
               for e in ctx.events["device"])


def test_readers_on_the_recorded_fixture():
    ctx, _ = _fixture_ctx()
    # the decode step's kernel time and the megasteps' host time
    assert _read("paged_attn_ms.decode", ctx) == pytest.approx(920.4217180,
                                                             rel=1e-9)
    assert _read("engine.host_ms", ctx) == pytest.approx(7.7950788,
                                                       rel=1e-6)
    assert _read("sched.park_wait_p50_s", ctx) == pytest.approx(
        2.4144545, rel=1e-6)
    lo, hi = ctx.trace_window
    idle = spans.idle_by_span(ctx.events, ctx.spans, lo, hi)
    busy = trace.busy_ns(ctx.events, lo, hi)
    assert sum(idle.values()) * 1e9 == pytest.approx(hi - lo - busy)
    assert idle.get(spans.NONE, 0.0) < 0.01 * sum(idle.values())


def test_device_time_by_scope_on_the_recorded_fixture():
    _, fx = _fixture_ctx()
    lo, hi = fx["window"]
    by = spans.device_by_scope(fx["device"], lo, hi)
    busy = trace.busy_ns({"device": fx["device"]}, lo, hi) * 1e-9
    assert sum(by.values()) == pytest.approx(busy, rel=0.01)
    assert next(iter(by)) == "attn/paged" and by["attn/paged"] > 0.9 * busy
    assert {"mlp", "attn/qkv", "attn/kv_write", "attn/out", "head",
            "embed"} <= set(by)
