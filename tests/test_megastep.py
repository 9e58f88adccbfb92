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
from repro.kernels.paged_attention.kernel import (
    kernel_plan, paged_prefill_attention_bcd, paged_prefill_attention_stacked)
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
    hkv 2, g 2 and ragged rows (decode, partial chunk, inactive), writing
    and reading the middle layer of a three-layer pool stack."""
    from repro.models.attention import gqa_mixed_step_paged, init_gqa
    cfg = get_smoke_config("chatglm3-6b").replace(compute_dtype="float32")
    hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    assert cfg.n_heads // hkv == 2 and hkv == 2
    rng = np.random.default_rng(4)
    params = init_gqa(jax.random.PRNGKey(4), cfg)
    B, C, npages = 3, PREFILL_CHUNK, 4
    nb = B * npages + 1
    x = jnp.asarray(rng.standard_normal((B, C, cfg.d_model)), jnp.float32)
    k_pool = jnp.asarray(rng.standard_normal((3, nb, hkv, BLOCK_SIZE, hd)),
                         jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal((3, nb, hkv, BLOCK_SIZE, hd)),
                         jnp.float32)
    ids = rng.permutation(np.arange(1, nb))[: B * npages].reshape(B, npages)
    tables = jnp.asarray(ids, jnp.int32)
    cache_lens = jnp.asarray([9, 13, 0], jnp.int32)
    valids = jnp.asarray([C, 1, 0], jnp.int32)

    def run(kernel):
        return gqa_mixed_step_paged(params, x, k_pool, v_pool, 1, tables,
                                    cache_lens, valids, cfg,
                                    paged_kernel=kernel, interpret=kernel)

    out_k, (kk, vk) = run(True)
    out_r, (kr, vr) = run(False)
    live = np.asarray(valids)[:, None] > np.arange(C)[None, :]
    np.testing.assert_allclose(np.asarray(out_k)[live],
                               np.asarray(out_r)[live], atol=1e-4, rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(kk), np.asarray(kr))
    np.testing.assert_array_equal(np.asarray(vk), np.asarray(vr))


@pytest.mark.parametrize("layer", [0, 2])
def test_stacked_pool_kernel_reads_its_layer_bitwise(layer):
    """The kernel over the stacked (L, nb, hkv, blk, d) pool at layer
    ``layer`` (the first and the last of three) equals, bit for bit, the
    single-layer call on ``pool[layer]``: the page copies read
    ``(layer, page)`` and nothing of any other layer."""
    q, k_pool, v_pool, cache, valids, pt = _mixed_case(
        3, PREFILL_CHUNK, 4, 2, 32, 32, BLOCK_SIZE, 4, seed=31)
    rng = np.random.default_rng(32)
    k_stack = jnp.asarray(rng.standard_normal((3,) + k_pool.shape),
                          jnp.float32).at[layer].set(k_pool)
    v_stack = jnp.asarray(rng.standard_normal((3,) + v_pool.shape),
                          jnp.float32).at[layer].set(v_pool)
    out = paged_prefill_attention_stacked(q, k_stack, v_stack, layer, cache,
                                          valids, pt, interpret=True)
    one = paged_prefill_attention_bcd(q, k_pool, v_pool, cache, valids, pt,
                                      interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(one))


def _layer_sliced_megastep(params, pools, tokens, cache_lens, valids, tables,
                           cfg):
    """``mixed_step_paged`` composed layer by layer the way it scanned
    before it carried the pools: each layer's pool slice rides the scan as
    xs, is written as a stack of one, and is restacked as ys."""
    from repro.models import transformer as tr
    from repro.models.attention import gqa_mixed_step_paged
    from repro.models.layers import cast_floats, rms_norm
    params = cast_floats(params, cfg.compute_dtype)
    x = tr._embed(params, tokens, cfg)

    def body(h, xs):
        lp, kp, vp = xs
        hh = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
        a, (kp, vp) = gqa_mixed_step_paged(
            lp["attn"], hh, kp[None], vp[None], 0, tables, cache_lens,
            valids, cfg)
        h = h + a
        m, _, _ = tr._mlp_or_moe(lp, h, cfg)
        return h + m, (kp[0], vp[0])

    x, (k, v) = jax.lax.scan(body, x, (params["layers"], pools["k"],
                                       pools["v"]))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    rows = jnp.arange(x.shape[0])
    last = jnp.clip(valids - 1, 0, x.shape[1] - 1)
    logits = tr._unembed(params, x[rows, last], cfg)
    ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    ids = jnp.where(jnp.all(jnp.isfinite(logits), axis=-1), ids, -1)
    return ids, {"k": k, "v": v}


def test_megastep_with_carried_pools_equals_layer_sliced_composition():
    """The megastep, whose layer scan carries the whole pool stacks and
    writes them in place, gives the same ids and the same pools, bit for
    bit, as a layer-by-layer composition over each layer's pool slice, on
    a row mix of a full chunk, a partial chunk, a decode row and an
    inactive row over filled pools: every layer's writes land in its own
    slice and no other layer changes."""
    from repro.models import transformer as tr
    cfg = get_smoke_config("chatglm3-6b")
    params = build(cfg).init_params(jax.random.PRNGKey(3))
    B, C, npages = 4, PREFILL_CHUNK, 4
    nb = B * npages + 1
    rng = np.random.default_rng(8)
    pools = tr.init_paged_pools(cfg, nb, BLOCK_SIZE)
    pools = {n: jnp.asarray(rng.standard_normal(p.shape), p.dtype)
             for n, p in pools.items()}
    ids = rng.permutation(np.arange(1, nb))[: B * npages].reshape(B, npages)
    tables = jnp.asarray(ids, jnp.int32)
    cache_lens = jnp.asarray([0, 9, 21, 0], jnp.int32)
    valids = jnp.asarray([C, 5, 1, 0], jnp.int32)
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, (B, C)), jnp.int32)
    args = (params, pools, tokens, cache_lens, valids, tables)
    got_ids, got = jax.jit(
        lambda *a: tr.mixed_step_paged(*a, cfg))(*args)
    want_ids, want = jax.jit(
        lambda *a: _layer_sliced_megastep(*a, cfg))(*args)
    np.testing.assert_array_equal(np.asarray(got_ids), np.asarray(want_ids))
    for n in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(got[n]),
                                      np.asarray(want[n]))
        assert not np.array_equal(np.asarray(got[n]), np.asarray(pools[n]))


# --------------------------------------------------- kernel tiling limits

# Each case crosses a boundary of the kernel's plan (``kernel_plan``):
# several KV blocks with a table that is not a whole number of blocks, a
# context ending exactly on a block edge, g = 9 at hkv 4, blk 8 and 16,
# C in {1, 8, 64} and widths with several query tiles, ragged valids
# (inactive 0, decode 1, partial, full) and bf16 q and pool.
TILING_CASES = {
    # P = 64 pages of 8: 70 pages is one block and 6 pages; row 2 reaches
    # into the second block, row 1 stops just short of its edge
    "blk8-table-not-whole-blocks": dict(
        hq=4, hkv=2, blk=8, npages=70, C=8,
        cache=[0, 503, 551, 200], valids=[0, 1, 8, 5]),
    # P = 32 pages of 16: rows end exactly on the block edge (512), one
    # past it, one short of it
    "blk16-block-edge": dict(
        hq=8, hkv=2, blk=16, npages=40, C=8,
        cache=[0, 504, 512, 510], valids=[0, 8, 1, 1]),
    "g9-hkv4-decode": dict(
        hq=36, hkv=4, blk=16, npages=40, C=1,
        cache=[0, 0, 299, 639], valids=[0, 1, 1, 1]),
    # several query tiles, the later ones live in one row only
    "g9-hkv4-C64-query-tiles": dict(
        hq=36, hkv=4, blk=16, npages=40, C=64,
        cache=[0, 33, 100, 300], valids=[0, 1, 20, 64]),
    # several query tiles, tiles past valids skipped, a chunk walking two
    # blocks
    "g9-blk8-C128-query-tiles": dict(
        hq=36, hkv=4, blk=8, npages=70, C=128,
        cache=[0, 4, 300, 400], valids=[0, 1, 40, 128]),
    # chatglm3-6b's group: g = 16 at hkv 2
    "g16-C8": dict(
        hq=32, hkv=2, blk=16, npages=40, C=8,
        cache=[0, 7, 200, 600], valids=[0, 1, 3, 8]),
    "bf16-g16-decode": dict(
        hq=32, hkv=2, blk=16, npages=40, C=1, dtype=jnp.bfloat16,
        cache=[0, 20, 511, 639], valids=[0, 1, 1, 1]),
    "bf16-g9-C64": dict(
        hq=36, hkv=4, blk=16, npages=40, C=64, dtype=jnp.bfloat16,
        cache=[0, 3, 450, 576], valids=[0, 1, 30, 64]),
}


def _tiling_case(hq, hkv, blk, npages, C, cache, valids, dtype=jnp.float32,
                 d=32, seed=0):
    rng = np.random.default_rng(seed)
    b = len(cache)
    nb = b * npages + 1
    q = jnp.asarray(rng.standard_normal((b, C, hq, d)), dtype)
    k_pool = jnp.asarray(rng.standard_normal((nb, hkv, blk, d)), dtype)
    v_pool = jnp.asarray(rng.standard_normal((nb, hkv, blk, d)), dtype)
    ids = rng.permutation(np.arange(1, nb))[: b * npages].reshape(b, npages)
    return (q, k_pool, v_pool, jnp.asarray(cache, jnp.int32),
            jnp.asarray(valids, jnp.int32), jnp.asarray(ids, jnp.int32))


@pytest.mark.parametrize("name", list(TILING_CASES))
def test_kernel_parity_across_tiling_boundaries(name):
    """At every tiling boundary the interpret-mode kernel matches the
    gathered-view twin bit for bit and the quadratic jnp oracle on every
    real query position (f32 at 1e-5, bf16 at bf16 tolerance); query
    tiles wholly past a row's valid width (other than its first) are
    zeros, the walk having skipped them."""
    spec = TILING_CASES[name]
    case = _tiling_case(**spec)
    q, k_pool = case[0], case[1]
    b, C, hq, d = q.shape
    out = np.asarray(paged_prefill_attention_bcd(*case, interpret=True),
                     np.float32)
    oracle = np.asarray(paged_prefill_attention_gathered_oracle(*case),
                        np.float32)
    np.testing.assert_array_equal(out, oracle)
    ref = np.asarray(paged_prefill_attention_ref(*case), np.float32)
    valids = np.asarray(spec["valids"])
    live = valids[:, None] > np.arange(C)[None, :]
    tol = 2e-2 if q.dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(out[live], ref[live], atol=tol, rtol=tol)
    plan = kernel_plan(C=C, hq=hq, hkv=spec["hkv"], blk=spec["blk"], d=d,
                       dv=d, npages=spec["npages"], q_dtype=q.dtype,
                       kv_dtype=k_pool.dtype)
    tile = np.arange(C) // plan.tile_positions
    skipped = (tile[None, :] > 0) & (
        tile[None, :] * plan.tile_positions >= valids[:, None])
    assert not out[skipped].any()


def test_kernel_never_reads_pages_past_the_live_length():
    """Table entries past a row's live pages name a block full of NaN: the
    walk copies no page past the live length (and zeroes V positions it
    did not copy), so every output stays finite and equal to the result
    over a clean pool."""
    spec = TILING_CASES["g9-blk8-C128-query-tiles"]
    q, k_pool, v_pool, cache, valids, pt = _tiling_case(**spec)
    blk = spec["blk"]
    live_pages = -(-(np.asarray(cache) + np.asarray(valids)) // blk)
    live_pages = np.maximum(live_pages, 1)
    dead = np.arange(pt.shape[1])[None, :] >= live_pages[:, None]
    poisoned = jnp.where(jnp.asarray(dead), 0, pt)       # block 0: NaN
    nan_k = k_pool.at[0].set(jnp.nan)
    nan_v = v_pool.at[0].set(jnp.nan)
    out = paged_prefill_attention_bcd(q, nan_k, nan_v, cache, valids,
                                      poisoned, interpret=True)
    clean = paged_prefill_attention_bcd(q, k_pool, v_pool, cache, valids,
                                        poisoned, interpret=True)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_array_equal(np.asarray(out), np.asarray(clean))


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


def test_engine_records_kernel_plan_per_compiled_bucket(setup):
    """An engine on the kernel path records, for every bucket it compiles,
    the tiling the kernel runs there: the plan of the shapes the megastep
    hands the kernel. The CPU engine runs the jnp reference and records
    none; flipping the flag after construction records the plans without
    changing the attention path the engine already traced."""
    cfg, params = setup
    eng = _paged(cfg, params, token_budget=16)
    eng.compile_buckets()
    assert eng.kernel_plans == {}
    eng.paged_kernel = True
    eng.compiled_buckets.clear()
    eng.compile_buckets()
    assert set(eng.kernel_plans) == set(eng.bucket_set)
    hd = cfg.resolved_head_dim
    for C, plan in eng.kernel_plans.items():
        assert plan == kernel_plan(
            C=C, hq=cfg.n_heads, hkv=cfg.n_kv_heads, blk=BLOCK_SIZE, d=hd,
            dv=hd, npages=eng.max_pages, q_dtype=cfg.compute_dtype,
            kv_dtype=eng.cache.k.dtype)


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
