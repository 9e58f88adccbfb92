"""Fused iteration-level scheduling tests: the token-quantum MLFQ contract,
park/resume bit-exactness, between-step reaping, block backpressure, chunked
prefill, prefix dedup, and typed engine errors through TurnHandle."""
import threading
import time

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import (AgentRM, AgentRMConfig, StepReport, SteppableBackend,
                        ZombieKilled)
from repro.core.scheduler import QueueClass, Turn, token_mlfq
from repro.models import build
from repro.serving import (EngineError, PagedEngineBackend,
                           PagedInferenceEngine)


# ------------------------------------------------- token-quantum contract

def _turn(agent="a", qc=QueueClass.INTERACTIVE):
    return Turn(agent_id=agent, arrival=0.0, service=0.0, queue_class=qc)


def test_token_quantum_demotion_ordering():
    """A turn that overran its level's token allotment is demoted on
    requeue: fresh interactive work passes it, and its next quantum is the
    lower level's (bigger) one."""
    pol = token_mlfq(quanta=(4, 8, 16), allotments=(8, 32, float("inf")))
    hog = _turn("hog")
    pol.enqueue(hog, 0.0)
    assert pol.dequeue(0.0) is hog
    assert pol.quantum_for(hog) == 4
    hog.executed += 9                    # decoded past the Q0 allotment
    pol.requeue(hog, 1.0)
    assert hog.demotions == 1 and pol.level_of(hog) == 1
    fresh = _turn("fresh")
    pol.enqueue(fresh, 1.0)
    assert pol.dequeue(1.0) is fresh     # Q0 beats the demoted hog
    assert pol.dequeue(1.0) is hog
    assert pol.quantum_for(hog) == 8     # Q1 quantum now applies


def test_token_mlfq_boost_is_wall_clock():
    """Boost stays time-based regardless of the token service unit: a
    background turn starved past starve_after is promoted to Q0 ahead of
    younger interactive arrivals."""
    pol = token_mlfq(quanta=(4, 8, 16), allotments=(8, 32, float("inf")),
                     boost_period=5.0, starve_after=10.0)
    bg = _turn("bg", qc=QueueClass.BACKGROUND)
    pol.enqueue(bg, 0.0)
    pol.on_tick(20.0)                    # bg waited 20s > starve_after
    ui = _turn("ui")
    pol.enqueue(ui, 20.0)
    first = pol.dequeue(20.0)
    assert first is bg and bg.boosted


# ---------------------------------------------------- engine-level fused

@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("gemma-2b").replace(remat=False)
    model = build(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return cfg, params


def _paged(cfg, params, **kw):
    kw.setdefault("num_blocks", 17)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_len", 96)
    return PagedInferenceEngine(cfg, params, **kw)


def test_park_resume_mid_decode_is_bit_exact(setup):
    """Preemption parks a sequence in place; resuming (even after its pages
    were swapped to host RAM) continues the decode bit-identically."""
    cfg, params = setup
    ref_eng = _paged(cfg, params)
    r = ref_eng.submit(np.arange(9) % 50, max_new_tokens=8, retain=True)
    ref_eng.run_to_completion()
    ref = ref_eng.reqs[r].out_tokens

    eng = _paged(cfg, params)
    rid = eng.submit(np.arange(9) % 50, max_new_tokens=8, retain=True)
    for _ in range(4):
        eng.step()
    eng.park(rid)
    assert eng.reqs[rid].state == "parked" and not eng.reqs[rid].done
    other = eng.submit((np.arange(12) + 3) % 50, max_new_tokens=3)
    eng.run_to_completion()              # drains `other`; rid stays parked
    assert other not in eng.reqs         # non-retained finished
    eng.hibernate(rid)                   # parked -> swapped under pressure
    assert eng.reqs[rid].state == "swapped"
    eng.resume(rid)
    eng.run_to_completion()
    assert eng.reqs[rid].out_tokens == ref


def test_abort_between_steps_leaves_batchmates_undisturbed(setup):
    """The reaper condemns one sequence; aborting it between steps must not
    change a single token of what its batchmates decode."""
    cfg, params = setup
    solo = _paged(cfg, params)
    s = solo.submit(np.arange(9) % 50, max_new_tokens=8, retain=True)
    solo.run_to_completion()
    ref = solo.reqs[s].out_tokens

    eng = _paged(cfg, params)
    victim = eng.submit((np.arange(6) + 11) % 50, max_new_tokens=8)
    mate = eng.submit(np.arange(9) % 50, max_new_tokens=8, retain=True)
    eng.step()
    eng.step()
    eng.abort_turn(victim)
    assert victim not in eng.reqs        # non-retained: fully dropped
    eng.run_to_completion()
    assert eng.reqs[mate].out_tokens == ref


def test_admission_backpressure_when_blocks_exhausted(setup):
    """Admission is head-of-line on free blocks: with the pool full of hot
    (unevictable) sequences, new work queues instead of erroring, and is
    admitted once blocks free up."""
    cfg, params = setup
    eng = _paged(cfg, params, num_blocks=7, block_size=8, max_batch=4,
                 max_len=40)
    a = eng.submit(np.arange(20) % 50, max_new_tokens=2)    # 3 pages hot
    b = eng.submit((np.arange(20) + 5) % 50, max_new_tokens=2)
    eng.step()
    assert len(eng.active) == 2          # 6/6 blocks hot
    c = eng.submit((np.arange(10) + 30) % 50, max_new_tokens=2)
    assert not eng.can_admit(10)         # nothing free, nothing cold
    done = {r.rid for r in eng.step()}
    assert eng.reqs[c].state == "queued"  # backpressured, not failed
    done |= {r.rid for r in eng.run_to_completion()}
    assert {a, b, c} <= done             # admitted once a/b freed blocks


def test_chunked_prefill_interleaves_with_decode(setup):
    """A long prompt prefills block-sized chunks per step while batchmates
    keep decoding — and the chunked path equals the one-shot path."""
    cfg, params = setup
    one = _paged(cfg, params, num_blocks=33, prefill_chunk=96)
    r1 = one.submit(np.arange(40) % 50, max_new_tokens=4)
    one.step()
    assert one.last_serviced[r1] == 40   # whole prompt in one chunk
    oneshot = {r.rid: r.out_tokens for r in one.run_to_completion()}[r1]

    # budget 9 = one decode token + one 8-token chunk per step
    eng = _paged(cfg, params, num_blocks=33, prefill_chunk=8, token_budget=9)
    short = eng.submit((np.arange(5) + 20) % 50, max_new_tokens=12)
    eng.step()                           # short: prefilled + first token
    long = eng.submit(np.arange(40) % 50, max_new_tokens=4)
    steps_interleaved = 0
    for _ in range(5):                   # 40 tokens / 8-chunk = 5 steps
        eng.step()
        if (eng.last_serviced.get(long) == 8
                and eng.last_serviced.get(short) == 1):
            steps_interleaved += 1
    assert steps_interleaved >= 4        # decode never stalled behind prefill
    done = {r.rid: r.out_tokens for r in eng.run_to_completion()}
    assert done.get(long, eng.reqs.get(long)) is not None
    long_tokens = done[long] if long in done else eng.reqs[long].out_tokens
    assert long_tokens == oneshot        # chunking never changes the model


def test_prefix_dedup_shares_blocks_and_reports_stats(setup):
    """Two sessions with the same prompt share its block-aligned prefix via
    refcounts; kv_stats reports hit rate and dedup ratio; divergent decode
    stays correct (COW protects the shared tail)."""
    cfg, params = setup
    eng = _paged(cfg, params, num_blocks=33)
    r1 = eng.submit(np.arange(24) % 50, max_new_tokens=3, retain=True)
    eng.run_to_completion()
    used_solo = eng.cache.allocator.num_used
    r2 = eng.submit(np.arange(24) % 50, max_new_tokens=3, retain=True)
    eng.run_to_completion()
    st = eng.kv_stats()
    assert st["blocks_deduped"] == 2          # 24 tokens @ blk 8 -> 2 full
    assert st["prefix_hit_rate"] == 0.5       # second lookup hit
    assert 0 < st["dedup_ratio"] <= 0.5
    # both sessions share physical blocks but decode identically
    assert eng.reqs[r1].out_tokens == eng.reqs[r2].out_tokens
    assert eng.reqs[r1].table.blocks[:2] == eng.reqs[r2].table.blocks[:2]
    assert eng.cache.allocator.num_used < 2 * used_solo
    # divergent extends COW away from the shared prefix without corruption
    eng.extend(r1, [3, 4], max_new_tokens=3)
    eng.extend(r2, [13, 14], max_new_tokens=3)
    eng.run_to_completion()
    assert len(eng.reqs[r1].out_tokens) == 3
    assert len(eng.reqs[r2].out_tokens) == 3
    # releasing one session must not invalidate the other's shared blocks
    eng.release(r2)
    eng.extend(r1, [5], max_new_tokens=2)
    eng.run_to_completion()
    assert len(eng.reqs[r1].out_tokens) == 2


def test_growth_oom_aborts_one_sequence_not_the_batch(setup):
    """When the pool cannot grow a sequence even after reclaim, that one
    sequence is aborted (reported in last_failures) and its batchmates keep
    decoding — memory pressure never fails the whole step."""
    cfg, params = setup
    eng = _paged(cfg, params, num_blocks=7, block_size=8, max_batch=2,
                 max_len=40)
    a = eng.submit(np.arange(24) % 50, max_new_tokens=8)
    b = eng.submit((np.arange(24) + 9) % 50, max_new_tokens=8)
    eng.step()                           # both prefilled: 6/6 blocks, hot
    failed, done = [], []
    for _ in range(20):
        done += [r.rid for r in eng.step()]
        failed += [rid for rid, _ in eng.last_failures]
        if not eng.active and not eng._queue:
            break
    assert len(failed) == 1              # exactly one casualty
    survivor = b if failed[0] == a else a
    assert survivor in done              # batchmate finished its turn
    assert failed[0] not in done
    assert eng.cache.allocator.num_used == 0   # nothing leaked


# ------------------------------------------------- middleware-level fused

def test_fused_middleware_runs_and_preempts(setup):
    """Real engine under the fused dispatcher: more agents than batch
    slots, tiny quanta so preemption fires, every turn completes, zero
    zombies, and the CLM records both sides of each turn."""
    cfg, params = setup
    eng = _paged(cfg, params, num_blocks=65, block_size=8, max_batch=2,
                 max_len=96, prefill_chunk=16)
    rm = AgentRM(PagedEngineBackend(eng, max_new_tokens=6),
                 AgentRMConfig(lanes=2, detect_after_s=60.0,
                               quantum_tokens=(3.0, 6.0, 12.0),
                               allotment_tokens=(6.0, 24.0, float("inf"))))
    try:
        hs = [rm.submit(f"agent{i}", f"question {i}") for i in range(4)]
        outs = [h.result(180) for h in hs]
        assert all(o.startswith("tok:") for o in outs)
        # preemption actually happened: some turn decoded over quantum and
        # was demoted (executed tokens exceed the Q0 allotment of 6)
        assert any(h.turn.demotions >= 1 for h in hs)
        assert rm.monitor.snapshot().zombies_reaped == 0
        assert len(rm.context_for("agent0").window()) == 2
    finally:
        rm.shutdown()


class _StallableBackend(SteppableBackend):
    """Scripted backend: decodes one token per step per turn, except rids
    in `stalled`, which stop being serviced (a wedged sequence)."""

    def __init__(self):
        self.turns = {}
        self.stalled = set()
        self._rid = 0

    def begin_turn(self, agent_id, context, prompt):
        self._rid += 1
        self.turns[self._rid] = {"agent": agent_id, "tokens": 0, "need": 40}
        return self._rid

    def step(self):
        rep = StepReport()
        time.sleep(0.005)
        for rid, t in list(self.turns.items()):
            if rid in self.stalled or t.get("parked"):
                continue
            t["tokens"] += 1
            rep.serviced[rid] = 1
            if t["tokens"] >= t["need"]:
                rep.finished.append(rid)
        return rep

    def collect(self, rid):
        return f"done:{self.turns[rid]['tokens']}"

    def park_turn(self, rid):
        self.turns[rid]["parked"] = True

    def resume_turn(self, rid):
        self.turns[rid].pop("parked", None)

    def abort_turn(self, rid):
        self.aborted = rid
        self.turns.pop(rid, None)

    def can_admit(self, agent_id, prompt):
        return True


def test_fused_reaper_aborts_stalled_turn_only():
    """A turn whose sequence stops being serviced is condemned by the
    reaper and aborted between steps; its batchmate is untouched."""
    be = _StallableBackend()
    rm = AgentRM(be, AgentRMConfig(
        lanes=2, detect_after_s=0.15, reaper_period_s=0.05,
        max_retries=1, recover_p=0.0, seed=0))
    try:
        h1 = rm.submit("stuck", "will hang")
        # wait until the turn is admitted, then wedge it
        t0 = time.monotonic()
        while not be.turns and time.monotonic() - t0 < 5:
            time.sleep(0.005)
        be.stalled.add(min(be.turns))
        h2 = rm.submit("fine", "runs normally")
        assert h2.result(10).startswith("done:")
        with pytest.raises(ZombieKilled):
            h1.result(10)
        assert be.aborted == 1               # the stalled rid, not the mate
        assert rm.monitor.snapshot().zombies_reaped == 1
    finally:
        rm.shutdown()


class _SessionBackend(SteppableBackend):
    """Scripted sessions: one in-flight turn per agent, park/resume, each
    turn needs `need` serviced tokens."""

    def __init__(self, need=10):
        self.turns = {}
        self.need = need
        self._rid = 0

    def begin_turn(self, agent_id, context, prompt):
        self._rid += 1
        self.turns[self._rid] = {"agent": agent_id, "tokens": 0,
                                 "done": False}
        return self._rid

    def session_busy(self, agent_id):
        return any(t["agent"] == agent_id and not t["done"]
                   for t in self.turns.values())

    def step(self):
        rep = StepReport()
        time.sleep(0.002)
        for rid, t in self.turns.items():
            if t["done"] or t.get("parked"):
                continue
            t["tokens"] += 1
            rep.serviced[rid] = 1
            if t["tokens"] >= self.need:
                t["done"] = True
                rep.finished.append(rid)
        return rep

    def collect(self, rid):
        return f"done:{self.turns[rid]['tokens']}"

    def park_turn(self, rid):
        self.turns[rid]["parked"] = True

    def resume_turn(self, rid):
        self.turns[rid].pop("parked", None)

    def abort_turn(self, rid):
        self.turns.pop(rid, None)

    def can_admit(self, agent_id, prompt):
        return True


def test_parked_demoted_turn_not_shadowed_by_own_successor():
    """Livelock regression (DESIGN.md §11): agent A's turn 1 is preempted
    mid-turn and demoted below Q0; A's turn 2 waits in Q0 with the session
    busy. The admission scan must hold the busy successor aside and fall
    through to resume the parked predecessor — NOT requeue the successor
    into Q0 where it shadows the predecessor until the starvation boost
    (starve_after here is far beyond the test timeout, so only the fix can
    make these turns finish)."""
    be = _SessionBackend(need=12)
    rm = AgentRM(be, AgentRMConfig(
        lanes=1, detect_after_s=60.0, seed=0,
        quantum_tokens=(4.0, 8.0, 16.0),
        allotment_tokens=(4.0, 16.0, float("inf")),
        boost_period_s=600.0, starve_after_s=600.0))
    try:
        t0 = time.monotonic()
        a1 = rm.submit("A", "turn 1")
        b1 = rm.submit("B", "turn 1")     # waiter -> quantum preemption
        a2 = rm.submit("A", "turn 2")     # busy-session successor in Q0
        b2 = rm.submit("B", "turn 2")
        for h in (a1, b1, a2, b2):
            assert h.result(30).startswith("done:")
        assert time.monotonic() - t0 < 20     # no 600 s boost involved
        assert a1.turn.demotions + b1.turn.demotions >= 1
        assert rm.monitor.snapshot().zombies_reaped == 0
    finally:
        rm.shutdown()


def test_engine_error_propagates_through_handle():
    """A typed EngineError raised by the backend surfaces in
    TurnHandle.result() instead of dying in a daemon thread."""

    class Exploding(SteppableBackend):
        def begin_turn(self, agent_id, context, prompt):
            return 1

        def step(self):
            raise EngineError("pool corrupted")

        def can_admit(self, agent_id, prompt):
            return True

    rm = AgentRM(Exploding(), AgentRMConfig(lanes=1))
    try:
        h = rm.submit("a", "boom")
        with pytest.raises(EngineError, match="pool corrupted"):
            h.result(10)
    finally:
        rm.shutdown()


def test_fused_backpressure_queues_when_engine_full(setup):
    """More agents than the engine can hold: can_admit gates MLFQ dequeue,
    everything completes eventually with zero zombies."""
    cfg, params = setup
    eng = _paged(cfg, params, num_blocks=9, block_size=8, max_batch=2,
                 max_len=64, prefill_chunk=16)
    rm = AgentRM(PagedEngineBackend(eng, max_new_tokens=3),
                 AgentRMConfig(lanes=2, detect_after_s=60.0))
    try:
        hs = [rm.submit(f"a{i}", f"prompt {i}" * 3) for i in range(5)]
        outs = [h.result(240) for h in hs]
        assert all(o.startswith("tok:") for o in outs)
        assert rm.monitor.snapshot().zombies_reaped == 0
    finally:
        rm.shutdown()
