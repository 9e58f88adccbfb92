"""Paged KV-cache subsystem tests: block allocator invariants, paged-vs-
dense attention parity (incl. the Pallas kernel in interpret mode), engine
hibernation round-trips, copy-on-write forks, and block-granular admission
(overcommit vs the dense engine at equal memory)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.paged_attention.kernel import paged_prefill_attention_bcd
from repro.kernels.paged_attention.ref import (gather_pages,
                                               paged_attention_ref)
from repro.models import build
from repro.serving import InferenceEngine, PagedInferenceEngine
from repro.serving.paging.allocator import (BlockAllocator, NULL_BLOCK,
                                            OutOfBlocksError, PageTable)

RNG = np.random.default_rng(7)


# --------------------------------------------------------------- allocator

def test_allocator_reserves_null_block_and_is_exhaustible():
    a = BlockAllocator(4)
    got = [a.alloc() for _ in range(3)]
    assert NULL_BLOCK not in got and sorted(got) == [1, 2, 3]
    with pytest.raises(OutOfBlocksError):
        a.alloc()
    a.release(got[0])
    assert a.num_free == 1 and a.alloc() == got[0]


def test_allocator_alloc_many_is_all_or_nothing():
    a = BlockAllocator(4)
    a.alloc()
    with pytest.raises(OutOfBlocksError):
        a.alloc_many(3)
    assert a.num_free == 2          # nothing leaked by the failed request


def test_allocator_refcounts_shared_blocks():
    a = BlockAllocator(4)
    bid = a.alloc()
    a.share(bid)
    assert a.is_shared(bid)
    assert not a.release(bid)       # still referenced by the sharer
    assert a.release(bid)           # last reference frees it
    assert a.num_free == 3


def test_page_table_padding_and_lookup():
    pt = PageTable(block_size=4, blocks=[5, 9], num_tokens=6)
    assert pt.block_of(0) == 5 and pt.block_of(5) == 9
    assert pt.padded(4) == [5, 9, NULL_BLOCK, NULL_BLOCK]
    with pytest.raises(AssertionError):
        pt.padded(1)


# ----------------------------------------------------------- kernel parity

def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-3


@pytest.mark.parametrize("b,hq,hkv,d,dv,blk,npages,lens", [
    (3, 4, 2, 32, 32, 16, 4, (37, 1, 64)),      # ragged, non-multiple of blk
    (2, 8, 1, 64, 64, 32, 3, (95, 17)),         # MQA, partial last page
    (1, 4, 4, 64, 32, 16, 2, (32,)),            # narrow V, exact multiple
    (2, 36, 4, 32, 32, 16, 40, (640, 1)),       # g 9, two KV blocks
    (2, 4, 2, 32, 32, 8, 70, (512, 560)),       # block edge, whole table
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_matches_dense_and_ref(b, hq, hkv, d, dv, blk,
                                               npages, lens, dtype):
    """The Pallas paged kernel (interpret mode) at decode width (C == 1) ==
    the paged jnp decode oracle == the dense decode oracle run on each
    sequence's gathered pages. The kernel pairs q head ``h`` with kv head
    ``h % hkv``; the dense oracle pairs kv-major, so its query heads are
    regrouped to the same pairing first."""
    nb = b * npages + 1
    q = jnp.asarray(RNG.standard_normal((b, hq, d)), dtype)
    k_pool = jnp.asarray(RNG.standard_normal((nb, hkv, blk, d)), dtype)
    v_pool = jnp.asarray(RNG.standard_normal((nb, hkv, blk, dv)), dtype)
    # shuffled, non-contiguous physical placement (never the null block)
    ids = RNG.permutation(np.arange(1, nb))[: b * npages].reshape(b, npages)
    pt = jnp.asarray(ids, jnp.int32)
    lens_v = jnp.asarray(lens, jnp.int32)

    out = paged_prefill_attention_bcd(q[:, None], k_pool, v_pool, lens_v - 1,
                                      jnp.ones_like(lens_v), pt,
                                      interpret=True)[:, 0]
    ref = paged_attention_ref(q, k_pool, v_pool, lens_v, pt)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))
    g = hq // hkv
    kv_major = q.reshape(b, g, hkv, d).swapaxes(1, 2).reshape(b, hq, d)
    kg = gather_pages(k_pool, pt).transpose(0, 2, 1, 3)
    vg = gather_pages(v_pool, pt).transpose(0, 2, 1, 3)
    for i in range(b):
        dense = decode_attention_ref(kv_major[i:i + 1], kg[i:i + 1],
                                     vg[i:i + 1], int(lens[i]))
        dense = dense.reshape(1, hkv, g, dv).swapaxes(1, 2).reshape(1, hq, dv)
        np.testing.assert_allclose(np.asarray(out[i:i + 1], np.float32),
                                   np.asarray(dense, np.float32),
                                   atol=1e-2, rtol=1e-2)


# ------------------------------------------------------------ engine tests

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


def test_pool_write_prefill_and_scatter_roundtrip(setup):
    """The jitted, donated bulk write paths: write_prefill scatters prefill
    KV into blocks (partial last page zero-padded) and gather returns the
    exact bytes; scatter rebinds host pages to fresh blocks identically."""
    from repro.serving.paging.pool import PagedKVCache
    cfg, params = setup
    cache = PagedKVCache(cfg, num_blocks=9, block_size=8)
    L, _, hkv, blk, hd = cache.k.shape
    plen = 13                                    # 2 pages, partial last
    rng = np.random.default_rng(3)
    k_pre = rng.standard_normal((L, plen, hkv, hd)).astype(np.float32)
    v_pre = rng.standard_normal((L, plen, hkv, hd)).astype(np.float32)
    pt = cache.alloc_table(plen)
    cache.write_prefill(pt, k_pre, v_pre)
    assert pt.num_tokens == plen
    kg, vg = cache.gather(pt)                    # (L, pages, hkv, blk, hd)
    flat = kg.transpose(0, 1, 3, 2, 4).reshape(L, -1, hkv, hd)
    want = np.asarray(jnp.asarray(k_pre, cache.k.dtype))  # pool precision
    np.testing.assert_array_equal(flat[:, :plen], want)
    assert (flat[:, plen:] == 0).all()                      # padded tail
    # swap-style roundtrip: host pages -> fresh device blocks, same bytes
    pt2 = cache.scatter(kg, vg, plen)
    assert pt2.blocks != pt.blocks or len(pt2.blocks) == 0
    kg2, vg2 = cache.gather(pt2)
    np.testing.assert_array_equal(kg, kg2)
    np.testing.assert_array_equal(vg, vg2)
    cache.free_table(pt)
    cache.free_table(pt2)
    assert cache.allocator.num_used == 0


def test_paged_engine_matches_dense_engine(setup):
    """Block-granular serving realises the same model: greedy decode through
    paged attention produces the dense engine's exact tokens."""
    cfg, params = setup
    dense = InferenceEngine(cfg, params, max_slots=2, max_len=96)
    paged = _paged(cfg, params)
    prompts = [np.arange(5 + 3 * i) % 50 for i in range(3)]
    drids = [dense.submit(p, max_new_tokens=5) for p in prompts]
    prids = [paged.submit(p, max_new_tokens=5) for p in prompts]
    ddone = {r.rid: r.out_tokens for r in dense.run_to_completion()}
    pdone = {r.rid: r.out_tokens for r in paged.run_to_completion()}
    for dr, pr in zip(drids, prids):
        assert ddone[dr] == pdone[pr]
    assert paged.kv_stats()["blocks_in_use"] == 0   # everything freed


def test_dense_engine_hibernation_roundtrip_is_exact(setup):
    """extract_slot -> restore_slot must be a bit-identical continuation."""
    cfg, params = setup
    base = InferenceEngine(cfg, params, max_slots=1, max_len=96)
    r0 = base.submit(np.arange(7) % 50, max_new_tokens=6)
    base.step()
    uninterrupted = {r.rid: r.out_tokens
                     for r in base.run_to_completion()}[r0]

    eng = InferenceEngine(cfg, params, max_slots=1, max_len=96)
    rid = eng.submit(np.arange(7) % 50, max_new_tokens=6)
    eng.step()
    req = eng.active[rid]
    payload, length = eng.extract_slot(req.slot)
    eng.restore_slot(req.slot, payload, length)
    resumed = {r.rid: r.out_tokens for r in eng.run_to_completion()}[rid]
    assert resumed == uninterrupted


def test_paged_hibernate_wake_roundtrip_is_exact(setup):
    """The page-swap hibernation path: pages leave the device entirely, come
    back under different block ids, and decode continues bit-identically."""
    cfg, params = setup
    a = _paged(cfg, params)
    ra = a.submit(np.arange(9) % 50, max_new_tokens=5, retain=True)
    a.run_to_completion()
    a.extend(ra, [7, 8, 9], max_new_tokens=5)
    a.run_to_completion()
    uninterrupted = a.reqs[ra].out_tokens

    b = _paged(cfg, params)
    rb = b.submit(np.arange(9) % 50, max_new_tokens=5, retain=True)
    b.run_to_completion()
    before = b.cache.gather(b.reqs[rb].table)
    b.hibernate(rb)
    assert b.kv_stats()["blocks_in_use"] == 0       # O(pages) swap-out
    assert b.kv_stats()["swapped_sessions"] == 1
    b.wake(rb)
    after = b.cache.gather(b.reqs[rb].table)
    for x, y in zip(before, after):
        assert (x == y).all()                       # bytes identical
    b.hibernate(rb)                                 # extend straight from swap
    b.extend(rb, [7, 8, 9], max_new_tokens=5)
    b.run_to_completion()
    assert b.reqs[rb].out_tokens == uninterrupted


def test_fork_shares_pages_copy_on_write(setup):
    """fork() costs zero blocks; divergent appends COW the shared tail so
    the parent's continuation is unchanged by the clone's writes."""
    cfg, params = setup
    eng = _paged(cfg, params)
    rid = eng.submit(np.arange(9) % 50, max_new_tokens=5, retain=True)
    eng.run_to_completion()
    used = eng.cache.allocator.num_used
    clone = eng.fork(rid)
    assert eng.cache.allocator.num_used == used     # zero-copy fork
    eng.extend(rid, [3, 4], max_new_tokens=4)
    eng.extend(clone, [13, 14], max_new_tokens=4)
    eng.run_to_completion()
    forked_parent = eng.reqs[rid].out_tokens

    solo = _paged(cfg, params)
    srid = solo.submit(np.arange(9) % 50, max_new_tokens=5, retain=True)
    solo.run_to_completion()
    solo.extend(srid, [3, 4], max_new_tokens=4)
    solo.run_to_completion()
    assert solo.reqs[srid].out_tokens == forked_parent


def test_paged_overcommit_beats_dense_admission(setup):
    """With the same KV byte budget the paged engine holds concurrent live
    context the dense engine's slot-granular admission cannot reach."""
    cfg, params = setup
    max_slots, max_len = 2, 96
    # identical token capacity: dense = max_slots*max_len = 192 positions
    paged = _paged(cfg, params, num_blocks=25, block_size=8, max_batch=8,
                   max_len=max_len)
    assert (paged.cache.num_blocks - 1) * paged.cache.block_size \
        == max_slots * max_len
    prompts = [np.arange(14 + i) % 50 for i in range(8)]
    for p in prompts:
        paged.submit(p, max_new_tokens=4)
    paged.step()
    live = paged.kv_stats()["live_context_tokens"]
    # dense can run at most `max_slots` of these concurrently
    dense_live_cap = max_slots * (max(len(p) for p in prompts) + 4)
    assert len(paged.active) == 8
    assert live > dense_live_cap
    paged.run_to_completion()


def test_reclaim_swaps_cold_sessions_under_pressure(setup):
    """Demand paging: when fresh work needs blocks, LRU cold (parked)
    sessions are evicted to host RAM automatically — and survive it."""
    cfg, params = setup
    eng = _paged(cfg, params, num_blocks=9, block_size=8, max_batch=2,
                 max_len=64)
    r1 = eng.submit(np.arange(20) % 50, max_new_tokens=4, retain=True)
    eng.run_to_completion()
    assert eng.reqs[r1].state == "parked"
    # 3 pages held by r1, 8 total; this grows to 6 pages -> must evict r1
    # (offset prompt: a shared prefix would be deduped and dodge the pressure)
    r2 = eng.submit((np.arange(40) + 7) % 50, max_new_tokens=4)
    eng.run_to_completion()
    assert eng.swap.stats()["swaps_out"] >= 1
    assert eng.reqs[r1].state == "swapped"
    # the evicted session still continues exactly
    eng.extend(r1, [5], max_new_tokens=3)
    eng.run_to_completion()
    assert len(eng.reqs[r1].out_tokens) == 3


def test_extend_overflow_is_rejected_upfront(setup):
    """A turn that cannot fit in max_len must fail at extend(), not corrupt
    the decode step mid-flight."""
    cfg, params = setup
    eng = _paged(cfg, params, num_blocks=17, block_size=8, max_len=32)
    rid = eng.submit(np.arange(20) % 50, max_new_tokens=4, retain=True)
    eng.run_to_completion()
    with pytest.raises(ValueError, match="overflows max_len"):
        eng.extend(rid, np.arange(10), max_new_tokens=4)
    eng.hibernate(rid)
    with pytest.raises(ValueError, match="overflows max_len"):
        eng.extend(rid, np.arange(10), max_new_tokens=4)   # swapped too
    eng.extend(rid, [1, 2], max_new_tokens=3)              # this one fits
    eng.run_to_completion()
    assert len(eng.reqs[rid].out_tokens) >= 1


def test_release_and_abort_in_any_state(setup):
    """release() / abort_turn() must leave the engine consistent from every
    lifecycle state (queued, active, parked, swapped)."""
    cfg, params = setup
    eng = _paged(cfg, params)
    # active: release one mid-decode, the other finishes normally
    r1 = eng.submit(np.arange(6) % 50, max_new_tokens=6)
    r2 = eng.submit(np.arange(8) % 50, max_new_tokens=6)
    eng.step()
    eng.release(r1)
    assert r1 not in eng.active and len(eng.free_slots) == eng.max_batch - 1
    done = {r.rid for r in eng.run_to_completion()}
    assert r2 in done and eng.cache.allocator.num_used == 0
    # queued: never admitted, abort drops it cleanly
    r3 = eng.submit(np.arange(5) % 50, max_new_tokens=2)
    eng.abort_turn(r3)
    assert r3 not in eng.reqs and not eng._queue
    # active retained: abort parks the session and the next turn extends it
    r4 = eng.submit(np.arange(6) % 50, max_new_tokens=8, retain=True)
    eng.step()
    eng.abort_turn(r4)
    assert eng.reqs[r4].state == "parked" and not eng.active
    eng.extend(r4, [3], max_new_tokens=2)
    eng.run_to_completion()
    assert len(eng.reqs[r4].out_tokens) == 2
    # swapped: release drops the host pages too
    eng.hibernate(r4)
    eng.release(r4)
    assert len(eng.swap.store) == 0 and eng.cache.allocator.num_used == 0


def test_backend_abort_leaves_session_extendable(setup):
    """An aborted turn (zombie reap) must not wedge the agent's retained
    session — the next turn extends it normally (fused session API)."""
    from repro.serving import PagedEngineBackend
    cfg, params = setup
    eng = _paged(cfg, params, num_blocks=33, max_batch=2)
    be = PagedEngineBackend(eng, max_new_tokens=3)
    rid = be.begin_turn("a", "", "hello")
    while rid not in [f for f in _drain(be)]:
        pass
    out1 = be.collect(rid)
    assert out1.startswith("tok:")
    # second turn reaped mid-decode: abort between steps
    rid2 = be.begin_turn("a", "", "again")
    be.step()
    be.abort_turn(rid2)
    assert eng.reqs[be.sessions["a"]].state == "parked"
    rid3 = be.begin_turn("a", "", "once more")
    while rid3 not in [f for f in _drain(be)]:
        pass
    assert be.collect(rid3).startswith("tok:")
    # a fresh agent aborted before admission is fully dropped
    rid4 = be.begin_turn("b", "", "hi")
    be.abort_turn(rid4)
    assert rid4 not in eng.reqs


def _drain(be):
    return be.step().finished


def test_serialized_backend_reap_and_engine_error(setup):
    """The legacy lock-per-turn baseline keeps the old reap contract, and a
    turn the engine cannot finish raises a typed EngineError (not a bare
    assert in a daemon thread)."""
    import threading
    from repro.core.middleware import ZombieKilled
    from repro.serving import EngineError, SerializedPagedBackend
    cfg, params = setup
    eng = _paged(cfg, params, num_blocks=33, max_batch=2)
    be = SerializedPagedBackend(eng, max_new_tokens=3)
    ok = threading.Event()           # never set
    dead = threading.Event()
    dead.set()
    out1 = be.generate("a", "", "hello", lambda: None, ok)
    assert out1.startswith("tok:")
    with pytest.raises(ZombieKilled):
        be.generate("a", "", "again", lambda: None, dead)
    assert eng.reqs[be.sessions["a"]].state == "parked"
    out2 = be.generate("a", "", "again", lambda: None, ok)
    assert out2.startswith("tok:")
    # a fresh agent reaped on its very first turn is fully dropped
    with pytest.raises(ZombieKilled):
        be.generate("b", "", "hi", lambda: None, dead)
    assert "b" not in be.sessions
    # a stepping engine that never finishes the turn -> typed error
    eng.step, real = (lambda: []), eng.step
    try:
        with pytest.raises(EngineError, match="failed to finish turn"):
            be.generate("a", "", "stuck", lambda: None, ok)
    finally:
        eng.step = real


def test_middleware_hibernates_paged_sessions(setup):
    """CLM tier transition -> engine page swap through AgentRM."""
    from repro.core import AgentRM, AgentRMConfig
    from repro.serving import PagedEngineBackend
    cfg, params = setup
    eng = _paged(cfg, params, num_blocks=33, max_batch=4)
    rm = AgentRM(PagedEngineBackend(eng, max_new_tokens=3),
                 AgentRMConfig(lanes=2, detect_after_s=60.0))
    try:
        out1 = rm.submit("alice", "first question").result(180)
        assert out1.startswith("tok:")
        rm.hibernate_agent("alice")
        st = eng.kv_stats()
        assert st["swapped_sessions"] == 1 and st["swap_bytes_out"] > 0
        rm.wake_agent("alice")
        out2 = rm.submit("alice", "second question").result(180)
        assert out2.startswith("tok:")
        assert eng.kv_stats()["swaps_in"] == 1
        # the session's KV survived the round-trip and kept growing
        rid = rm.backend.sessions["alice"]
        assert eng.reqs[rid].num_tokens > 0
    finally:
        rm.shutdown()
