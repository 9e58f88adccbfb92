"""Overload autopilot tests (DESIGN.md §16): windowed control signals,
live token-budget retuning inside the pre-traced bucket set, brownout-
ladder hysteresis, typed shed backpressure, AIMD coupling, and the
serve.py --turn-timeout expiry path the ladder must compose with."""
import time

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import AgentRM, AgentRMConfig
from repro.core.middleware import SteppableBackend, TurnCancelled
from repro.core.scheduler.ratelimit import AIMDController
from repro.models import build
from repro.obs import Observability
from repro.obs.metrics import LATENCY_BUCKETS_S, Histogram
from repro.serving import (AutopilotConfig, BackpressureError,
                           PagedEngineBackend, PagedInferenceEngine,
                           SLOAutopilot)
from repro.serving.errors import is_fatal


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("gemma-2b").replace(remat=False)
    model = build(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return cfg, params


def _paged(cfg, params, **kw):
    kw.setdefault("num_blocks", 33)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_len", 96)
    return PagedInferenceEngine(cfg, params, **kw)


# ------------------------------------------------- windowed control signals

def test_histogram_windowed_quantile_and_abstention():
    """The autopilot's signals are RECENT p95s: stale samples age out of
    the window, and an empty window abstains (None) instead of voting."""
    h = Histogram("x.itl_s", LATENCY_BUCKETS_S)
    for i in range(10):
        h.observe(0.010, now=100.0 + i * 0.1)
    assert h.windowed_count(5.0, now=101.0) == 10
    q = h.windowed_quantile(0.95, 5.0, now=101.0)
    assert q is not None and abs(q - 0.010) < 1e-9
    # a latency regression dominates the recent window even though the
    # all-time histogram is still mostly fast samples
    for i in range(10):
        h.observe(1.0, now=102.0 + i * 0.1)
    q = h.windowed_quantile(0.95, 1.2, now=103.0)
    assert q is not None and q > 0.5
    # everything aged out -> abstain, not zero
    assert h.windowed_quantile(0.95, 5.0, now=1000.0) is None
    assert h.windowed_count(5.0, now=1000.0) == 0
    h.reset()
    assert h.windowed_quantile(0.95, 1e9, now=103.0) is None


# ----------------------------------------------- live token-budget retuning

def test_set_token_budget_stays_within_pretraced_buckets(setup):
    cfg, params = setup
    eng = _paged(cfg, params, token_budget=32)
    assert eng.budget_rungs() == (8, 16, 32)
    eng.set_token_budget(8)
    assert eng.token_budget == 8 and eng.first_chunk_cap == 8
    assert eng.bucket_set == (1, 4, 8, 16, 32) or 8 in eng.bucket_set
    with pytest.raises(ValueError):
        eng.set_token_budget(24)            # not a pre-traced bucket
    with pytest.raises(ValueError):
        eng.set_token_budget(1)             # below the decode-first floor
    eng.set_token_budget(32)
    assert eng.token_budget == 32


def test_budget_swap_causes_no_recompiles(setup):
    """Retuning mid-run must keep every traced width inside the fixed
    pow2 bucket set — the zero-recompile contract of the tentpole."""
    cfg, params = setup
    eng = _paged(cfg, params, token_budget=32, prefill_chunk=16)
    eng.compile_buckets()
    eng.submit(np.arange(1, 20) % 50, max_new_tokens=4)
    eng.run_to_completion()
    eng.set_token_budget(8)
    eng.submit(np.arange(1, 30) % 50, max_new_tokens=4)
    eng.run_to_completion()
    eng.set_token_budget(16)
    eng.submit(np.arange(1, 12) % 50, max_new_tokens=4)
    eng.run_to_completion()
    st = eng.step_stats()
    assert set(st["trace_buckets"]) <= set(st["bucket_set"]), st


# ---------------------------------------------------- ladder + hysteresis

class _FakeEngine:
    """Just enough engine for the controller: a budget ladder and a name
    whose ttft/itl histograms the autopilot reads from the registry."""

    def __init__(self, name="engine", budget=32):
        self.name = name
        self.token_budget = budget
        self.max_batch = 4
        self.swaps = []

    def budget_rungs(self):
        return (8, 16, 32)

    def set_token_budget(self, b):
        assert b in self.budget_rungs()
        self.token_budget = b
        self.swaps.append(b)
        return b


class _FakeBackend:
    def __init__(self, eng):
        self.engine = eng


def _pilot(**cfg_kw):
    cfg_kw.setdefault("slo_ttft_p95_s", 1.0)
    cfg_kw.setdefault("slo_itl_p95_s", 0.1)
    cfg_kw.setdefault("min_samples", 3)
    cfg_kw.setdefault("queue_high", 10)
    cfg_kw.setdefault("breach_passes", 2)
    cfg_kw.setdefault("clear_passes", 3)
    cfg_kw.setdefault("check_interval_s", 0.0)
    obs = Observability()
    eng = _FakeEngine()
    ap = SLOAutopilot(AutopilotConfig(**cfg_kw), obs=obs)
    ap.bind(_FakeBackend(eng), aimd=AIMDController())
    return ap, eng, obs


def _feed(obs, name, suffix, v, now, n=6):
    h = obs.metrics.histogram(f"{name}.{suffix}", LATENCY_BUCKETS_S)
    for i in range(n):
        h.observe(v, now=now - i * 0.01)


def test_ladder_escalates_through_rungs_with_hysteresis():
    ap, eng, obs = _pilot()
    now = 100.0
    # one breach is not enough (breach_passes=2): no move yet
    _feed(obs, "engine", "itl_s", 5.0, now)
    assert ap.on_pass(now, queue_depth=0) is None
    assert ap.severity == 0 and ap.rung == 0
    # sustained breach walks the whole ladder: budget band first
    moves = []
    for k in range(1, 11):
        now += 0.1
        _feed(obs, "engine", "itl_s", 5.0, now)
        a = ap.on_pass(now, queue_depth=0)
        if a:
            moves.append(a)
    assert ap.severity == ap.max_severity == 5
    assert ap.rung == 4 and ap.shedding
    assert eng.swaps[:2] == [16, 8]         # one pre-traced bucket at a time
    assert any(m.startswith("escalate") for m in moves)
    # shed-rung breaches grow the client-facing retry backoff but must
    # NOT cut the internal admission multiplier (that would throttle the
    # queue->engine drain that relieves the overload)
    assert ap._aimd.slo_breaches > 0
    assert ap._aimd.shed_backoff_s > 0
    assert ap._aimd.multiplier == 1.0


def test_shed_rung_is_a_queue_cap_not_a_binary_valve():
    """At the shed rung, admissions are refused only while the queue
    already holds >= the floor (default queue_high // 2, min 2): the
    valve trims the excess, never the trickle that feeds the engine."""
    ap, eng, obs = _pilot()                  # queue_high=10 -> floor 5
    now = 300.0
    for _ in range(12):                      # drive to the shed rung
        now += 0.1
        _feed(obs, "engine", "itl_s", 5.0, now)
        ap.on_pass(now, queue_depth=0)
    assert ap.shedding
    assert not ap.should_shed(0)             # engine would starve
    assert not ap.should_shed(4)
    assert ap.should_shed(5)                 # backlog capped from here up
    assert ap.should_shed(50)
    # explicit floor overrides the derived one; 0 = binary valve
    ap.cfg.shed_queue_floor = 0
    assert ap.should_shed(0)
    # below the shed rung nothing sheds regardless of depth
    ap.severity = 0
    assert not ap.should_shed(50)


def test_queue_only_breach_keeps_budget_at_full():
    """The budget lever is signal-directed: a deep queue with healthy
    (or absent) latency signals climbs the ladder to the shed rung with
    the token budget untouched — smaller steps can't drain a queue, they
    just cut capacity exactly when demand exceeds it. A latency breach
    then cuts; clear_passes of sub-clear_frac latency restores."""
    ap, eng, obs = _pilot()
    now = 400.0
    for _ in range(12):
        now += 0.1
        ap.on_pass(now, queue_depth=50)      # queue breach, no latency
    assert ap.shedding and ap.severity == ap.max_severity
    assert not ap.latency_breached
    assert eng.swaps == []                   # budget never moved
    # latency joins the breach: cut engages at the current severity
    _feed(obs, "engine", "itl_s", 5.0, now)
    ap.on_pass(now + 0.1, queue_depth=50)
    assert ap.latency_breached
    assert eng.swaps == [8]                  # straight to the floor
    # latency clears (queue still deep): budget restores, shed persists
    now += 20.0                              # age out the breach samples
    for _ in range(3):                       # clear_passes=3
        now += 0.1
        _feed(obs, "engine", "itl_s", 0.001, now)
        ap.on_pass(now, queue_depth=50)
    assert not ap.latency_breached
    assert eng.swaps[-1] == 32
    assert ap.shedding                       # queue rung unaffected


def test_ladder_recovers_rung_by_rung_and_restores_budget():
    ap, eng, obs = _pilot()
    now = 200.0
    for _ in range(12):                      # drive to full severity
        now += 0.1
        _feed(obs, "engine", "itl_s", 5.0, now)
        ap.on_pass(now, queue_depth=0)
    assert ap.shedding
    eng.swaps.clear()
    now += 10.0          # age the breach samples out of the signal window
    # healthy signal must persist clear_passes times per relaxation, and
    # must be BELOW clear_frac * SLO (dual-threshold: no flapping)
    while ap.severity > 0:
        before = ap.severity
        for _ in range(3):
            now += 0.1
            _feed(obs, "engine", "itl_s", 0.001, now)
            ap.on_pass(now, queue_depth=0)
        assert ap.severity == before - 1    # exactly one rung per streak
    assert ap.rung == 0 and not ap.shedding
    assert eng.swaps[-1] == 32              # full budget restored last
    st = ap.stats()
    assert st["relaxations"] >= 5 and st["escalations"] >= 5


def test_ambiguous_signals_hold_position():
    """Between thresholds (above clear_frac*SLO, below SLO) the ladder
    neither escalates nor relaxes — and abstaining signals with work
    queued never count as healthy."""
    ap, eng, obs = _pilot()
    now = 300.0
    for _ in range(4):
        now += 0.1
        _feed(obs, "engine", "itl_s", 5.0, now)
        ap.on_pass(now, queue_depth=0)
    sev = ap.severity
    assert sev > 0
    now += 10.0          # age the breach samples out of the signal window
    for _ in range(10):                      # 0.09 is 90% of SLO: ambiguous
        now += 0.1
        _feed(obs, "engine", "itl_s", 0.09, now)
        ap.on_pass(now, queue_depth=0)
    assert ap.severity == sev
    # no latency samples at all + queued work: also not healthy
    for _ in range(10):
        now += 100.0
        ap.on_pass(now, queue_depth=3)
    assert ap.severity == sev


def test_retry_after_is_always_finite():
    ap, _, _ = _pilot(min_retry_after_s=0.05, max_retry_after_s=30.0)
    assert ap.retry_after(0.0) == 0.05
    assert ap.retry_after(4.2) == 4.2
    assert ap.retry_after(float("inf")) == 30.0
    assert ap.retry_after(float("nan")) == 0.05


# -------------------------------------------------- end-to-end: shed typed

def test_overloaded_rm_sheds_typed_backpressure(setup):
    """With unattainable SLOs the ladder deploys to the shed rung and NEW
    submissions fail with BackpressureError + finite retry_after_s, while
    already-admitted turns still complete (shed touches only the edge)."""
    cfg, params = setup
    eng = _paged(cfg, params, token_budget=32)
    eng.compile_buckets()
    # shed_queue_floor=0: this test drives one turn at a time, so the
    # queue is empty at submit — force the binary valve to probe the
    # typed-shed path itself (the bounded-queue floor is covered below)
    ap_cfg = AutopilotConfig(slo_ttft_p95_s=1e-4, slo_itl_p95_s=1e-5,
                             min_samples=1, breach_passes=1, clear_passes=99,
                             check_interval_s=0.0, queue_high=2,
                             shed_queue_floor=0)
    rm = AgentRM(PagedEngineBackend(eng, max_new_tokens=4),
                 AgentRMConfig(lanes=4, detect_after_s=60.0,
                               autopilot=ap_cfg))
    try:
        assert rm.autopilot is not None
        first = [rm.submit(f"a{i}", f"warm {i}") for i in range(4)]
        outs = [h.result(240) for h in first]
        assert all(o.startswith("tok:") for o in outs)
        # drive passes until the ladder reaches the shed rung
        deadline = time.monotonic() + 60
        shed_errors = []
        while time.monotonic() < deadline and len(shed_errors) < 3:
            h = rm.submit(f"b{len(shed_errors)}-{time.monotonic():.3f}",
                          "overload probe")
            try:
                h.result(240)
            except BackpressureError as e:
                shed_errors.append(e)
        assert len(shed_errors) >= 3, "autopilot never reached shed rung"
        for e in shed_errors:
            assert e.retry_after_s == e.retry_after_s   # not NaN
            assert 0.0 < e.retry_after_s <= 30.0
            assert not is_fatal(e)      # shed is backpressure, not teardown
        assert rm.autopilot.shedding
        m = rm.obs.metrics
        assert m.get("rm.admissions_shed").value >= 3
        # live retuning kept every traced width inside the fixed set
        st = eng.step_stats()
        assert set(st["trace_buckets"]) <= set(st["bucket_set"])
        assert eng.token_budget == eng.max_batch * 2 or \
            eng.token_budget in eng.bucket_set
    finally:
        rm.shutdown()


# ------------------------------------------------ serve.py CLI + timeouts

def test_serve_autopilot_flag_validation():
    from repro.launch.serve import main
    with pytest.raises(SystemExit, match="requires --paged"):
        main(["--arch", "gemma-2b", "--smoke", "--autopilot"])
    with pytest.raises(SystemExit, match="invalid SLO"):
        main(["--arch", "gemma-2b", "--smoke", "--paged", "--autopilot",
              "--slo-ttft-p95", "0"])
    with pytest.raises(SystemExit, match="invalid SLO"):
        main(["--arch", "gemma-2b", "--smoke", "--paged", "--autopilot",
              "--slo-itl-p95", "-1"])


class _SlowStepBackend(SteppableBackend):
    """Delegating wrapper that makes every engine step slow — a stand-in
    for the wedged turn serve.py's --turn-timeout guards against."""

    def __init__(self, inner, delay=0.25):
        self.inner = inner
        self.delay = delay

    @property
    def engine(self):
        return self.inner.engine

    @property
    def sessions(self):
        return self.inner.sessions

    @property
    def obs(self):
        return self.inner.obs

    def begin_turn(self, agent_id, context, prompt):
        return self.inner.begin_turn(agent_id, context, prompt)

    def session_busy(self, agent_id):
        return self.inner.session_busy(agent_id)

    def collect(self, rid):
        return self.inner.collect(rid)

    def park_turn(self, rid):
        self.inner.park_turn(rid)

    def resume_turn(self, rid):
        self.inner.resume_turn(rid)

    def abort_turn(self, rid):
        self.inner.abort_turn(rid)

    def can_admit(self, agent_id, prompt):
        return self.inner.can_admit(agent_id, prompt)

    def victim_parkable(self, rid):
        return self.inner.victim_parkable(rid)

    def step(self):
        time.sleep(self.delay)
        return self.inner.step()


def test_turn_timeout_expiry_frees_blocks_and_raises_typed(setup):
    """Regression for serve.py's --turn-timeout expiry path: result()
    times out, cancel() condemns the turn, the re-wait surfaces the typed
    TurnCancelled, and the aborted turn's KV blocks are RELEASED (not
    orphaned) so the engine ends the run with an empty allocator."""
    cfg, params = setup
    eng = _paged(cfg, params, token_budget=32)
    eng.compile_buckets()
    be = _SlowStepBackend(PagedEngineBackend(eng, max_new_tokens=32))
    rm = AgentRM(be, AgentRMConfig(lanes=2, detect_after_s=60.0))
    try:
        h = rm.submit("wedged", "this turn will out-live its deadline")
        # exactly what serve.py does on TimeoutError:
        with pytest.raises(TimeoutError):
            h.result(timeout=0.4)
        assert rm.cancel(h.turn.tid, reason="exceeded --turn-timeout")
        with pytest.raises(TurnCancelled):
            h.result(timeout=60)
        # the dispatcher applies the abort between steps: the turn leaves
        # the engine entirely (its retained session keeps only its
        # committed pages — that residency is accounted, not leaked)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and (eng.active or eng._queue):
            time.sleep(0.05)
        assert not eng.active and not eng._queue
        for rid in list(be.inner.sessions.values()):
            if rid in eng.reqs:
                eng.release(rid)
        assert eng.cache.allocator.num_used == 0, \
            "cancelled turn leaked KV blocks past its session residency"
    finally:
        rm.shutdown()
