"""Megastep tests: chunked-prefill Pallas kernel parity (bitwise vs the
gathered-view oracle, fp32 tolerance vs the quadratic jnp oracle), the
one-dispatch-per-iteration engine contract, megastep-vs-legacy token parity
at f32 compute, and prefix-dedup interactions (hibernate/wake re-indexing,
index invalidation when the owner is retired mid-batch)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.kernels.paged_attention.kernel import paged_prefill_attention_bcd
from repro.kernels.paged_attention.ref import (
    paged_prefill_attention_gathered_oracle, paged_prefill_attention_ref)
from repro.models import build
from repro.serving import PagedInferenceEngine

RNG = np.random.default_rng(11)

BLOCK_SIZE = 8
PREFILL_CHUNK = 16


# ----------------------------------------------------------- kernel parity

def _mixed_case(b, C, hq, hkv, d, dv, blk, npages, seed):
    rng = np.random.default_rng(seed)
    nb = b * npages + 1
    q = jnp.asarray(rng.standard_normal((b, C, hq, d)), jnp.float32)
    k_pool = jnp.asarray(rng.standard_normal((nb, hkv, blk, d)), jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal((nb, hkv, blk, dv)), jnp.float32)
    # shuffled, non-contiguous physical placement (never the null block)
    ids = rng.permutation(np.arange(1, nb))[: b * npages].reshape(b, npages)
    pt = jnp.asarray(ids, jnp.int32)
    # ragged: decode-like rows (valid 1), partial chunks, inactive rows (0)
    valids = rng.integers(0, C + 1, size=b)
    valids[0] = C
    if b > 1:
        valids[1] = min(1, C)
    cache = rng.integers(0, (npages - 1) * blk, size=b)
    cache = np.minimum(cache, npages * blk - C)   # chunk stays in-table
    return (q, k_pool, v_pool, jnp.asarray(cache, jnp.int32),
            jnp.asarray(valids, jnp.int32), pt)


@pytest.mark.parametrize("C", [1, BLOCK_SIZE, PREFILL_CHUNK])
@pytest.mark.parametrize("b,hq,hkv,d,dv,npages", [
    (3, 4, 2, 32, 32, 4),       # GQA, narrow table
    (2, 8, 1, 64, 32, 3),       # MQA, narrow V
])
def test_chunked_prefill_kernel_parity(C, b, hq, hkv, d, dv, npages):
    """Interpret-mode chunked-prefill kernel == the gathered-view oracle
    (the SAME online-softmax program over a jnp-gathered contiguous view)
    **bit for bit** — so the page-table scalar-prefetch walk provably
    changes nothing — and == the independent quadratic jnp oracle at fp32
    tolerance, across chunk widths {1, block, chunk} and ragged valids."""
    case = _mixed_case(b, C, hq, hkv, d, dv, BLOCK_SIZE, npages,
                       seed=C * 100 + b)
    out = paged_prefill_attention_bcd(*case, interpret=True)
    oracle = paged_prefill_attention_gathered_oracle(*case)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(oracle))
    ref = paged_prefill_attention_ref(*case)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("C", [24, 64])
def test_budget_width_kernel_parity_ragged_valids(C):
    """The token-budget buckets instantiate the same kernel at widths far
    beyond the old fixed chunk. Sweep wide C with fully ragged per-row
    valids (inactive 0, decode-like 1, partial, full) and pin bitwise
    parity against the gathered-view oracle plus fp32 agreement with the
    quadratic ref — the masking generalizes, the page walk doesn't care."""
    b, hq, hkv, d, dv, npages = 4, 4, 2, 32, 32, 10
    rng = np.random.default_rng(C)
    nb = b * npages + 1
    q = jnp.asarray(rng.standard_normal((b, C, hq, d)), jnp.float32)
    k_pool = jnp.asarray(rng.standard_normal((nb, hkv, BLOCK_SIZE, d)),
                         jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal((nb, hkv, BLOCK_SIZE, dv)),
                         jnp.float32)
    ids = rng.permutation(np.arange(1, nb))[: b * npages].reshape(b, npages)
    pt = jnp.asarray(ids, jnp.int32)
    valids = np.asarray([0, 1, C // 3, C], np.int32)       # fully ragged
    cache = np.asarray([0, 5, BLOCK_SIZE + 3,
                        npages * BLOCK_SIZE - C], np.int32)
    case = (q, k_pool, v_pool, jnp.asarray(cache), jnp.asarray(valids), pt)
    out = paged_prefill_attention_bcd(*case, interpret=True)
    oracle = paged_prefill_attention_gathered_oracle(*case)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(oracle))
    ref = paged_prefill_attention_ref(*case)
    live = np.asarray(valids)[:, None] > np.arange(C)[None, :]
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(ref)[live],
                               atol=1e-5, rtol=1e-5)


def test_chunked_prefill_kernel_is_deterministic():
    """Two interpret runs over identical inputs are bit-identical (the
    megastep's bit-exact park/resume contract rests on this)."""
    case = _mixed_case(2, PREFILL_CHUNK, 4, 2, 32, 32, BLOCK_SIZE, 4, seed=5)
    a = paged_prefill_attention_bcd(*case, interpret=True)
    b = paged_prefill_attention_bcd(*case, interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_chunked_prefill_width1_equals_decode_semantics():
    """A C == 1 chunk row is exactly a decode step: parity against the
    existing paged decode oracle on the same pools."""
    from repro.kernels.paged_attention.ref import paged_attention_ref
    b, hq, hkv, d, dv, npages = 3, 4, 2, 32, 32, 4
    q, k_pool, v_pool, cache, valids, pt = _mixed_case(
        b, 1, hq, hkv, d, dv, BLOCK_SIZE, npages, seed=9)
    valids = jnp.ones((b,), jnp.int32)
    out = paged_prefill_attention_bcd(q, k_pool, v_pool, cache, valids, pt,
                                      interpret=True)
    # decode oracle: one query at position cache_len, kv_len = cache_len + 1
    ref = paged_attention_ref(q[:, 0], k_pool, v_pool, cache + 1, pt)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_kernel_pairs_heads_g_major():
    """The kernel pairs q head ``h`` with kv head ``h % hkv`` — the model's
    ``gqa_mode="tiled"`` pairing, which the full path, the jnp reference
    and the TP head permutation all use. At hkv 2, g 2 the other pairing
    (``h // g``) is a different model, and the kernel must not match it."""
    case = _mixed_case(3, PREFILL_CHUNK, 4, 2, 32, 32, BLOCK_SIZE, 4,
                       seed=21)
    out = np.asarray(paged_prefill_attention_bcd(*case, interpret=True))
    g_major = np.asarray(paged_prefill_attention_ref(*case,
                                                     pairing="g_major"))
    kv_major = np.asarray(paged_prefill_attention_ref(*case,
                                                      pairing="kv_major"))
    live = np.asarray(case[4])[:, None] > np.arange(PREFILL_CHUNK)[None, :]
    np.testing.assert_allclose(out[live], g_major[live], atol=1e-5,
                               rtol=1e-5)
    assert np.abs(out[live] - kv_major[live]).max() > 1e-2


def test_mixed_step_kernel_matches_reference_path_at_f32():
    """One megastep layer through the Pallas kernel (interpret mode) agrees
    with the jnp reference path the engine runs on a CPU: same attention
    output rows and bit-identical pool writes, at f32 on a GQA model with
    hkv 2, g 2 and ragged rows (decode, partial chunk, inactive)."""
    from repro.models.attention import gqa_mixed_step_paged, init_gqa
    cfg = get_smoke_config("chatglm3-6b").replace(compute_dtype="float32")
    hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    assert cfg.n_heads // hkv == 2 and hkv == 2
    rng = np.random.default_rng(4)
    params = init_gqa(jax.random.PRNGKey(4), cfg)
    B, C, npages = 3, PREFILL_CHUNK, 4
    nb = B * npages + 1
    x = jnp.asarray(rng.standard_normal((B, C, cfg.d_model)), jnp.float32)
    k_pool = jnp.asarray(rng.standard_normal((nb, hkv, BLOCK_SIZE, hd)),
                         jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal((nb, hkv, BLOCK_SIZE, hd)),
                         jnp.float32)
    ids = rng.permutation(np.arange(1, nb))[: B * npages].reshape(B, npages)
    tables = jnp.asarray(ids, jnp.int32)
    cache_lens = jnp.asarray([9, 13, 0], jnp.int32)
    valids = jnp.asarray([C, 1, 0], jnp.int32)

    def run(kernel):
        return gqa_mixed_step_paged(params, x, k_pool, v_pool, tables,
                                    cache_lens, valids, cfg,
                                    paged_kernel=kernel, interpret=kernel)

    out_k, (kk, vk) = run(True)
    out_r, (kr, vr) = run(False)
    live = np.asarray(valids)[:, None] > np.arange(C)[None, :]
    np.testing.assert_allclose(np.asarray(out_k)[live],
                               np.asarray(out_r)[live], atol=1e-4, rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(kk), np.asarray(kr))
    np.testing.assert_array_equal(np.asarray(vk), np.asarray(vr))


# ------------------------------------------------------------ engine level

@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("gemma-2b").replace(remat=False)
    model = build(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return cfg, params


def _paged(cfg, params, **kw):
    kw.setdefault("num_blocks", 33)
    kw.setdefault("block_size", BLOCK_SIZE)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_len", 96)
    kw.setdefault("prefill_chunk", PREFILL_CHUNK)
    return PagedInferenceEngine(cfg, params, **kw)


def test_megastep_is_one_jit_dispatch_per_iteration(setup):
    """The tentpole contract: a mixed prefill/decode workload (fresh
    prompts, extends, decodes interleaving) runs at exactly ONE jitted
    dispatch per work-doing engine iteration; the legacy loop costs
    1 + n_prefilling."""
    cfg, params = setup
    eng = _paged(cfg, params)
    rids = [eng.submit(np.arange(20 + 3 * i) % 50, max_new_tokens=4,
                       retain=True) for i in range(3)]
    eng.run_to_completion()
    for r in rids:
        eng.extend(r, np.arange(10) % 50, max_new_tokens=4)
    eng.run_to_completion()
    assert eng.jit_dispatches == eng.steps_dispatched > 0
    assert eng.jit_dispatches_per_step == 1.0

    legacy = _paged(cfg, params, megastep=False)
    for i in range(3):
        legacy.submit(np.arange(20 + 3 * i) % 50, max_new_tokens=4)
    legacy.run_to_completion()
    assert legacy.jit_dispatches_per_step > 1.0


def test_megastep_matches_legacy_tokens_at_f32(setup):
    """At float32 compute the megastep and the PR 2 per-sequence loop are
    the same model: identical greedy tokens across a mixed multi-turn run.
    (At bf16 compute the fused batch shapes round differently — megastep
    self-consistency is what the park/resume suite pins there.)"""
    cfg, _ = setup
    cfg32 = cfg.replace(compute_dtype="float32")
    params32 = build(cfg32).init_params(jax.random.PRNGKey(0))

    def run(megastep):
        eng = _paged(cfg32, params32, megastep=megastep, prefill_chunk=8)
        rids = [eng.submit(np.arange(5 + 7 * i) % 50, max_new_tokens=6,
                           retain=True) for i in range(3)]
        eng.run_to_completion()
        for r in rids:
            eng.extend(r, [3, 4, 5], max_new_tokens=4)
        eng.run_to_completion()
        return {r: eng.reqs[r].out_tokens for r in rids}

    assert run(True) == run(False)


def test_prefix_dedup_survives_hibernate_wake(setup):
    """A fresh prompt that block-aligns with a hibernated-then-woken
    session's prefix must still adopt shared blocks: wake() re-registers
    the rebound blocks (hibernation freed the originals, purging their
    index entries)."""
    cfg, params = setup
    eng = _paged(cfg, params)
    prompt = np.arange(24) % 50
    r1 = eng.submit(prompt, max_new_tokens=3, retain=True)
    eng.run_to_completion()
    indexed = eng.kv_stats()["prefix_blocks_indexed"]
    assert indexed > 0
    eng.hibernate(r1)
    assert eng.kv_stats()["prefix_blocks_indexed"] == 0   # entries purged
    eng.wake(r1)
    assert eng.kv_stats()["prefix_blocks_indexed"] == indexed  # re-registered
    r2 = eng.submit(prompt, max_new_tokens=3, retain=True)
    eng.step()
    # 24 tokens @ blk 8 -> the 2 full prompt-prefix blocks are shared
    assert eng.reqs[r2].table.blocks[:2] == eng.reqs[r1].table.blocks[:2]
    assert eng.kv_stats()["blocks_deduped"] >= 2
    eng.run_to_completion()
    # the adopter decodes the same continuation the owner did
    assert eng.reqs[r2].out_tokens == eng.reqs[r1].out_tokens


def test_prefix_index_invalidated_when_owner_retired_mid_batch(setup):
    """Releasing the prefix owner mid-batch must not break its adopter
    (refcounts keep the shared blocks alive) — and once the last holder
    retires, the index entries die with the blocks: a later identical
    prompt misses the index yet still decodes identically."""
    cfg, params = setup
    eng = _paged(cfg, params)
    prompt = np.arange(24) % 50
    r1 = eng.submit(prompt, max_new_tokens=3, retain=True)
    eng.run_to_completion()
    ref_tokens = eng.reqs[r1].out_tokens[:]
    r2 = eng.submit(prompt, max_new_tokens=3)
    eng.step()                               # r2 active, prefix adopted
    assert eng.reqs[r2].table.blocks[:2] == eng.reqs[r1].table.blocks[:2]
    eng.release(r1)                          # owner retired mid-batch
    done = {r.rid for r in eng.run_to_completion()}
    assert r2 in done                        # adopter untouched by the free
    assert eng.reqs.get(r2) is None or eng.reqs[r2].done
    # r2 (non-retained) freed the last refs -> index must be empty now
    st = eng.kv_stats()
    assert st["prefix_blocks_indexed"] == 0
    assert eng.cache.allocator.num_used == 0
    # a third identical prompt misses (no stale block ids) but decodes
    # the exact same continuation from scratch
    hits_before = eng.cache.prefix_hits
    r3 = eng.submit(prompt, max_new_tokens=3)
    eng.run_to_completion()
    assert eng.cache.prefix_hits == hits_before   # miss, not a stale hit
    assert eng.reqs.get(r3) is None               # ran to completion, freed
    # compare against the owner's reference continuation on a fresh engine
    # (same prompt, same params -> same greedy tokens)
    eng2 = _paged(cfg, params)
    r5 = eng2.submit(prompt, max_new_tokens=3, retain=True)
    eng2.run_to_completion()
    assert eng2.reqs[r5].out_tokens == ref_tokens
