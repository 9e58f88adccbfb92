"""Observability tests (DESIGN.md §12): flight-recorder ring semantics
(wraparound, dropped accounting, span ordering), Chrome trace-event export
round-trip + schema validation, metrics registry (counters, gauges,
bounded-error histogram quantiles, bounded reservoir), and the fused-stack
lifecycle: a traced engine+AgentRM run must emit the full per-session span
sequence with zero drops while keeping the megastep at ONE jit dispatch."""
import json
import math

import numpy as np
import pytest

from repro.obs import (LATENCY_BUCKETS_S, FlightRecorder, MetricsRegistry,
                       Observability, TraceConfig, log_buckets,
                       validate_chrome)

# ------------------------------------------------------------------ ring


def _recorder(capacity=64):
    return FlightRecorder(TraceConfig(enabled=True, capacity=capacity))


def test_ring_wraparound_and_dropped_accounting():
    rec = _recorder(capacity=16)
    ev = rec.name("tick", ("i",))
    tr = rec.track("t")
    for i in range(40):
        rec.instant(ev, tr, float(i))
    assert rec.total == 40
    assert rec.recorded == 16
    assert rec.dropped == 24
    # drop-oldest: survivors are exactly the newest 16, in time order
    kept = [e["args"]["i"] for e in rec.events()]
    assert kept == list(map(float, range(24, 40)))


def test_ring_reset_clears_accounting():
    rec = _recorder(capacity=16)
    ev, tr = rec.name("x"), rec.track("t")
    for _ in range(20):
        rec.instant(ev, tr)
    rec.reset()
    assert rec.total == rec.recorded == rec.dropped == 0
    assert rec.events() == []


def test_disabled_recorder_records_nothing():
    rec = FlightRecorder(TraceConfig(enabled=False))
    assert not rec.enabled
    ev, tr = rec.name("x"), rec.track("t")
    rec.instant(ev, tr)
    rec.complete(ev, tr, rec.now())
    with rec.span("s"):
        pass
    assert rec.total == 0


def test_capacity_validation():
    with pytest.raises(ValueError, match="too small"):
        TraceConfig(enabled=True, capacity=8)
    with pytest.raises(ValueError, match="too large"):
        TraceConfig(enabled=True, capacity=1 << 25)


def test_concurrent_writers_lose_no_event():
    """Two threads write into one ring at once (the client thread's submit
    and the dispatcher's step do): every event comes back whole, once."""
    import sys
    import threading

    n = 20_000
    rec = _recorder(capacity=4 * n)
    ev = rec.name("w", ("writer", "i", "key"))
    tracks = [rec.track(f"writer {w}") for w in range(2)]

    def write(w):
        for i in range(n):
            if i % 2:
                rec.instant(ev, tracks[w], w, i, w * n + i)
            else:
                rec.complete(ev, tracks[w], rec.now(), w, i, w * n + i)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=write, args=(w,))
                   for w in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert rec.total == rec.recorded == 2 * n and rec.dropped == 0
    evs = rec.events()
    keys = set()
    for e in evs:
        a = e["args"]
        w, i = int(a["writer"]), int(a["i"])
        assert e["track"] == f"writer {w}"
        assert a["key"] == w * n + i
        assert e["ph"] == ("i" if i % 2 else "X")
        keys.add(a["key"])
    assert len(evs) == len(keys) == 2 * n
    assert sorted(e["seq"] for e in evs) == list(range(2 * n))


def _profiler_host_events(trace_dir):
    """(name, start_ns, dur_ns) of the host annotations in the newest
    profiler trace under ``trace_dir``."""
    import glob
    import os

    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                         "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.duration_ns) for e in line.events]
    return out


def test_anchor_maps_the_ring_onto_the_profiler_clock(tmp_path):
    """``anchor()`` writes ``obs.clock`` into the profiler's trace and the
    ring; the offset between the two maps a ring span onto the profiler's
    nanoseconds, where it lines up with a profiler annotation around it."""
    import time

    import jax

    rec = _recorder()
    probe = rec.name("probe")
    tr = rec.track("t")
    jax.profiler.start_trace(str(tmp_path))
    try:
        rec.anchor()
        time.sleep(0.02)
        with jax.profiler.TraceAnnotation("probe"):
            t0 = rec.now()
            time.sleep(0.005)
            rec.complete(probe, tr, t0)
    finally:
        jax.profiler.stop_trace()
    host = _profiler_host_events(str(tmp_path))
    (a_start, a_dur), = [(s, d) for n, s, d in host if n == "obs.clock"]
    (p_start, p_dur), = [(s, d) for n, s, d in host if n == "probe"]
    ring = {e["name"]: e for e in rec.events()}
    offset_ns = a_start + 0.5 * a_dur - ring["obs.clock"]["ts"] * 1e9
    span = ring["probe"]
    start = span["ts"] * 1e9 + offset_ns
    end = (span["ts"] + span["dur"]) * 1e9 + offset_ns
    assert abs(start - p_start) < 200e3
    assert abs(end - (p_start + p_dur)) < 200e3
    # the chrome export keeps the anchor's place in its own timeline
    chrome = rec.chrome()
    probe_us = next(e["ts"] for e in chrome["traceEvents"]
                    if e["name"] == "probe")
    assert probe_us - chrome["otherData"]["clock_anchor_us"] == \
        pytest.approx((span["ts"] - ring["obs.clock"]["ts"]) * 1e6)


def test_span_nesting_and_ordering():
    rec = _recorder()
    with rec.span("outer"):
        with rec.span("inner"):
            pass
        rec.instant(rec.name("mark"), rec.track("main"))
    evs = rec.events()
    # events() is time-sorted: outer began first, then inner, then mark
    assert [e["name"] for e in evs] == ["outer", "inner", "mark"]
    outer, inner = evs[0], evs[1]
    assert outer["ph"] == inner["ph"] == "X"
    # proper nesting: inner contained within outer
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-9


# ------------------------------------------------------- chrome export


def test_chrome_roundtrip_schema(tmp_path):
    rec = _recorder()
    ev = rec.name("work", ("n",))
    tr_a = rec.track("A", group="g1")
    tr_b = rec.track("B", group="g2")
    t0 = rec.now()
    rec.instant(ev, tr_a, 1.0)
    rec.complete(ev, tr_b, t0, 2.0)
    path = tmp_path / "trace.json"
    rec.export_chrome(str(path))
    obj = json.load(open(path))
    assert validate_chrome(obj) == []
    evs = obj["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    # one process_name per group (main, g1, g2) + one thread_name per track
    assert sum(e["name"] == "process_name" for e in meta) == 3
    assert {e["args"]["name"] for e in meta if e["name"] == "thread_name"} \
        >= {"A", "B"}
    data = [e for e in evs if e["ph"] != "M"]
    assert {e["ph"] for e in data} == {"X", "i"}
    # args survive the round trip under their interned labels
    assert any(e["args"].get("n") == 1.0 for e in data)
    assert obj["otherData"]["dropped_events"] == 0


def test_validate_chrome_catches_garbage():
    assert validate_chrome({}) != []
    assert validate_chrome({"traceEvents": []}) != []
    bad = {"traceEvents": [
        {"name": "a", "ph": "X", "pid": 1, "tid": 1, "ts": 5.0, "dur": -1}]}
    assert any("dur" in p for p in validate_chrome(bad))
    unsorted = {"traceEvents": [
        {"name": "a", "ph": "i", "pid": 1, "tid": 1, "ts": 5.0, "s": "t"},
        {"name": "b", "ph": "i", "pid": 1, "tid": 1, "ts": 1.0, "s": "t"}]}
    assert any("sorted" in p for p in validate_chrome(unsorted))
    # spans of one track nest; a partial overlap is refused, on another
    # track it is fine
    span = {"name": "a", "ph": "X", "pid": 1, "tid": 1}
    nested = {"traceEvents": [dict(span, ts=0.0, dur=10.0),
                              dict(span, ts=2.0, dur=8.0),
                              dict(span, ts=10.0, dur=1.0)]}
    assert validate_chrome(nested) == []
    crossed = {"traceEvents": [dict(span, ts=0.0, dur=10.0),
                               dict(span, ts=5.0, dur=10.0)]}
    assert any("overlaps" in p for p in validate_chrome(crossed))
    crossed["traceEvents"][1]["tid"] = 2
    assert validate_chrome(crossed) == []


# ----------------------------------------------------------- metrics


def test_counter_gauge_snapshot_reset():
    m = MetricsRegistry()
    c = m.counter("c")
    c.inc()
    c.inc(2)
    m.gauge("g").set(7)
    assert m.snapshot()["c"]["value"] == 3.0
    assert m.snapshot()["g"]["value"] == 7.0
    with pytest.raises(ValueError, match="already registered"):
        m.gauge("c")
    m.reset()
    assert m.snapshot()["c"]["value"] == 0.0
    assert "c" in m and m.get("missing") is None


def test_histogram_quantile_error_bound_vs_exact():
    """Bucket-path quantiles (no reservoir) must stay within the log-bucket
    relative error bound of the exact sample quantiles."""
    per_decade = 12
    bound = 10 ** (1 / per_decade) - 1          # ~21% for 12/decade
    rng = np.random.default_rng(0)
    xs = rng.lognormal(mean=-6.0, sigma=1.0, size=5000)
    h = MetricsRegistry().histogram(
        "lat", log_buckets(1e-5, 100.0, per_decade), reservoir=0)
    for v in xs:
        h.observe(float(v))
    assert not h.exact
    for q in (0.5, 0.95, 0.99):
        exact = float(np.percentile(xs, 100 * q))
        est = h.quantile(q)
        assert abs(est - exact) / exact <= bound, (q, est, exact)


def test_histogram_reservoir_exact_then_bounded():
    m = MetricsRegistry()
    h = m.histogram("r", LATENCY_BUCKETS_S, reservoir=128)
    for v in range(100):
        h.observe(float(v + 1))
    assert h.exact
    assert h.quantile(0.5) == pytest.approx(
        float(np.percentile(np.arange(1.0, 101.0), 50)))
    for v in range(10_000):
        h.observe(float(v % 97 + 1))
    assert not h.exact
    assert len(h.samples) == 128               # bounded memory
    assert h.count == 10_100


# ------------------------------------------- fused-stack lifecycle trace


@pytest.fixture(scope="module")
def traced_run():
    """One fused-budget engine+AgentRM run with tracing ON; returns the
    shared Observability plus run facts for the lifecycle assertions."""
    import jax

    from repro.configs import get_smoke_config
    from repro.core import AgentRM, AgentRMConfig
    from repro.models import build
    from repro.serving import PagedEngineBackend, PagedInferenceEngine

    cfg = get_smoke_config("gemma-2b").replace(remat=False)
    model = build(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    obs = Observability(trace=TraceConfig(enabled=True))
    eng = PagedInferenceEngine(cfg, params, num_blocks=65, block_size=8,
                               max_batch=4, max_len=96, prefill_chunk=16,
                               token_budget=32, obs=obs)
    eng.compile_buckets()
    backend = PagedEngineBackend(eng, max_new_tokens=6)
    rm = AgentRM(backend, AgentRMConfig(lanes=4, detect_after_s=300.0),
                 obs=obs)
    assert rm.obs is obs and eng.obs is obs     # one shared context
    try:
        handles = [rm.submit(f"agent{i}", f"lifecycle turn {i} " * 3)
                   for i in range(4)]
        outs = [h.result(timeout=300) for h in handles]
    finally:
        rm.shutdown()
    return obs, eng, outs


def test_traced_run_lifecycle_span_sequence(traced_run):
    obs, eng, outs = traced_run
    assert len(outs) == 4 and all(o.startswith("tok:") for o in outs)
    assert obs.recorder.dropped == 0            # default ring holds it all
    evs = obs.recorder.events()
    per_session = {}
    for e in evs:
        if e["group"] == "sessions":
            per_session.setdefault(e["track"], []).append(e["name"])
    assert len(per_session) == 4
    for track, names in per_session.items():
        # full lifecycle present on every session track (events() is
        # time-sorted, but X spans sort at their START timestamp, so the
        # session.queued wait-span can tie with the enqueued instant —
        # order is asserted over the instants, which are unambiguous)
        for required in ("session.enqueued", "session.queued",
                         "session.admitted", "session.prefill_chunk",
                         "session.token", "session.turn",
                         "session.finished"):
            assert required in names, (track, required)
        assert names.index("session.enqueued") \
            < names.index("session.admitted") \
            < names.index("session.token") \
            < names.index("session.finished")
    # scheduler-side instants landed on the mlfq tracks
    mlfq = [e["name"] for e in evs if e["group"] == "mlfq"]
    assert mlfq.count("sched.submitted") == 4
    assert mlfq.count("sched.admitted") == 4


def test_traced_run_megastep_spans_and_contract(traced_run):
    obs, eng, _ = traced_run
    steps = [e for e in obs.recorder.events()
             if e["name"] == "engine.megastep"]
    assert steps, "no megastep spans recorded"
    for e in steps:
        assert e["ph"] == "X" and e["dur"] >= 0
        assert e["args"]["rows"] >= 1
        assert e["args"]["tokens_real"] <= e["args"]["tokens_dispatched"]
    # tracing must not perturb the one-jitted-dispatch contract
    assert eng.step_stats()["jit_dispatches_per_step"] == 1.0


def test_traced_run_chrome_export_valid(traced_run, tmp_path):
    obs, _, _ = traced_run
    path = tmp_path / "lifecycle.json"
    obs.recorder.export_chrome(str(path))
    obj = json.load(open(path))
    assert validate_chrome(obj) == []
    names = {e["name"] for e in obj["traceEvents"] if e["ph"] != "M"}
    assert {"session.turn", "engine.megastep", "sched.admitted"} <= names


def test_traced_run_registry_unification(traced_run):
    """Engine stats surfaces and the registry are one derivation."""
    obs, eng, _ = traced_run
    m = obs.metrics
    assert m["engine.tokens_real"].value == eng.tokens_real
    assert m["engine.jit_dispatches"].value == eng.jit_dispatches
    st = eng.step_stats()
    assert st["trace_events_dropped"] == 0
    assert math.isclose(st["ttft_p95_s"],
                        m["engine.ttft_s"].quantile(0.95))
    eng.kv_stats()
    assert m["kv.blocks_total"].value == eng.cache.num_blocks - 1
    # monitor counters share the same store
    assert "rm.zombies_reaped" in m


def test_traced_run_queue_episodes_join_tid_and_rid(traced_run):
    """Each turn's first MLFQ queue episode is a ``sched.queued`` span
    carrying its tid and the rid ``begin_turn`` returned, so the wait from
    ``AgentRM.submit`` to the first token is read by joining it with the
    session's first ``session.token``."""
    obs, _, _ = traced_run
    evs = obs.recorder.events()
    queued = [e for e in evs if e["name"] == "sched.queued"]
    submitted = {e["args"]["tid"] for e in evs
                 if e["name"] == "sched.submitted"}
    assert {e["args"]["tid"] for e in queued} == submitted
    assert all(e["args"]["resumed"] == 0.0 for e in queued)
    first_tok = {}
    for e in evs:
        if e["name"] == "session.token":
            first_tok.setdefault(e["args"]["rid"], e["ts"])
    for e in queued:
        rid = e["args"]["rid"]
        assert e["track"].startswith("wait ")
        assert e["ts"] + e["dur"] <= first_tok[rid]
    assert "wait_s" not in next(
        e for e in evs if e["name"] == "sched.admitted")["args"]


def _children(evs, parent, names):
    """Spans named in ``names`` on ``parent``'s track, inside it."""
    end = parent["ts"] + parent["dur"] + 1e-9
    return [e for e in evs if e["name"] in names
            and e["track"] == parent["track"]
            and e["group"] == parent["group"]
            and parent["ts"] - 1e-9 <= e["ts"] and e["ts"] + e["dur"] <= end]


ENGINE_PHASES = ("engine.admit", "engine.pack", "engine.dispatch",
                 "engine.readback", "engine.retire")


def test_traced_run_megastep_phases_nest_in_the_step(traced_run):
    obs, eng, _ = traced_run
    evs = obs.recorder.events()
    steps = [e for e in evs if e["name"] == "engine.megastep"]
    assert len(steps) == eng.steps_dispatched
    for st in steps:
        kids = _children(evs, st, ENGINE_PHASES)
        assert [k["name"] for k in kids] == list(ENGINE_PHASES)
        assert sum(k["dur"] for k in kids) <= st["dur"] + 1e-9
        for a, b in zip(kids, kids[1:]):
            assert a["ts"] + a["dur"] <= b["ts"] + 1e-9


@pytest.fixture(scope="module")
def parked_run():
    """A traced fused run with more turns than lanes and a small quantum,
    so the dispatcher parks running turns for waiting ones."""
    import jax

    from repro.configs import get_smoke_config
    from repro.core import AgentRM, AgentRMConfig
    from repro.models import build
    from repro.serving import PagedEngineBackend, PagedInferenceEngine

    cfg = get_smoke_config("gemma-2b").replace(remat=False)
    params = build(cfg).init_params(jax.random.PRNGKey(0))
    obs = Observability(trace=TraceConfig(enabled=True))
    eng = PagedInferenceEngine(cfg, params, num_blocks=65, block_size=8,
                               max_batch=2, max_len=96, prefill_chunk=16,
                               token_budget=16, obs=obs)
    eng.compile_buckets()
    backend = PagedEngineBackend(eng, max_new_tokens=8)
    rm = AgentRM(backend, AgentRMConfig(lanes=2, detect_after_s=300.0,
                                        quantum_tokens=(2.0, 4.0, 8.0)),
                 obs=obs)
    try:
        handles = [rm.submit(f"agent{i}", f"parked turn {i} " * 2)
                   for i in range(4)]
        outs = [h.result(timeout=300) for h in handles]
    finally:
        rm.shutdown()
    return obs, outs


def test_parked_turn_gets_a_resumed_queue_episode(parked_run):
    obs, outs = parked_run
    assert len(outs) == 4
    evs = obs.recorder.events()
    parked = {e["args"]["tid"] for e in evs if e["name"] == "sched.preempted"}
    assert parked, "the run parked no turn"
    queued = [e for e in evs if e["name"] == "sched.queued"]
    for tid in parked:
        eps = [e for e in queued if e["args"]["tid"] == tid]
        assert [e["args"]["resumed"] for e in eps][:1] == [0.0]
        assert 1.0 in [e["args"]["resumed"] for e in eps[1:]]
        assert len({e["args"]["rid"] for e in eps}) == 1
    # a turn's episodes never overlap, and no wait track holds two at once
    for track in {e["track"] for e in queued}:
        on = [e for e in queued if e["track"] == track]
        for a, b in zip(on, on[1:]):
            assert a["ts"] + a["dur"] <= b["ts"] + 1e-9
    resumes = [e for e in evs if e["name"] == "backend.resume_turn"]
    parks = [e for e in evs if e["name"] == "backend.park_turn"]
    assert len(parks) >= len(resumes) >= 1


def test_dispatcher_pass_sections_nest(parked_run, tmp_path):
    obs, _ = parked_run
    evs = obs.recorder.events()
    passes = [e for e in evs if e["name"] == "sched.pass"]
    assert passes
    sections = ("sched.schedule", "sched.step", "sched.charge")
    for p in passes:
        kids = _children(evs, p, sections)
        assert [k["name"] for k in kids] == list(sections)
    calls = [e for e in evs if e["name"].startswith("backend.")]
    assert {e["name"] for e in calls} >= {
        "backend.begin_turn", "backend.park_turn", "backend.resume_turn",
        "backend.collect"}
    for c in calls:
        assert any(p["ts"] <= c["ts"] and c["ts"] + c["dur"]
                   <= p["ts"] + p["dur"] + 1e-9 for p in passes
                   if p["track"] == c["track"]), c
    path = tmp_path / "parked.json"
    obs.recorder.export_chrome(str(path))
    assert validate_chrome(json.load(open(path))) == []


def test_recorder_off_records_nothing_and_scopes_keep_tokens(monkeypatch):
    """With the recorder off an engine step records no event, and the
    megastep's named scopes leave its tokens as they were without them."""
    import contextlib

    import jax

    from repro.configs import get_smoke_config
    from repro.models import build
    from repro.serving import PagedInferenceEngine

    cfg = get_smoke_config("gemma-2b").replace(remat=False)
    params = build(cfg).init_params(jax.random.PRNGKey(1))
    prompts = [np.arange(3, 3 + n, dtype=np.int32) for n in (5, 11)]

    def tokens():
        eng = PagedInferenceEngine(cfg, params, num_blocks=33, block_size=8,
                                   max_batch=2, max_len=64,
                                   prefill_chunk=16)
        rids = [eng.submit(p, max_new_tokens=4) for p in prompts]
        fins = {r.rid: list(r.out_tokens) for r in eng.run_to_completion()}
        assert eng.obs.recorder.total == 0
        return [fins[r] for r in rids]

    with_scopes = tokens()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert tokens() == with_scopes
