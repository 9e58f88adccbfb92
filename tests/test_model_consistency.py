"""Deep correctness: decode paths must agree with full-sequence forwards,
MoE dispatch variants must agree with each other, and MLA's absorbed decode
must match its uncompressed formulation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import build

ATOL = 2e-2   # bf16 compute


def _greedy_forward_last(model, params, tokens):
    logits, _ = model.forward(params, {"tokens": tokens,
                                       "labels": tokens})
    return np.asarray(logits[:, -1], np.float32)


@pytest.mark.parametrize("arch", ["gemma-2b", "chatglm3-6b",
                                  "deepseek-v2-lite-16b", "mamba2-370m",
                                  "zamba2-7b"])
def test_decode_matches_full_forward(arch):
    """Feeding tokens one-by-one through decode_step must produce the same
    final-position logits as one full forward pass (KV-cache / SSM-state /
    MLA-absorption / head-pairing correctness). fp32 compute so any
    mismatch is a real bug, not rounding."""
    import dataclasses
    cfg = get_smoke_config(arch).replace(remat=False,
                                         compute_dtype="float32")
    if cfg.moe is not None:
        # decode batches are tiny: per-batch capacity differs from the full
        # forward unless routing is effectively dropless
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                  capacity_factor=8.0))
    model = build(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    b, s = 2, 16          # divisible by the smoke SSD chunk (8)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                                cfg.vocab_size)
    ref = _greedy_forward_last(model, params, tokens)

    state = model.init_decode_state(b, s + 4)
    for t in range(s):
        logits, state = model.decode_step(params, state, tokens[:, t:t + 1],
                                          jnp.int32(t))
    got = np.asarray(logits[:, 0], np.float32)
    np.testing.assert_allclose(got, ref, atol=2e-3, rtol=2e-3)


def test_moe_sort_matches_einsum_dispatch():
    """With ample capacity both dispatch strategies route identically."""
    import dataclasses
    cfg = get_smoke_config("llama4-scout-17b-a16e").replace(remat=False)
    cfg_big_cap = cfg.replace(moe=dataclasses.replace(
        cfg.moe, capacity_factor=4.0))
    from repro.models.moe import apply_moe, init_moe
    params = init_moe(jax.random.PRNGKey(0), cfg_big_cap)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model),
                          jnp.float32)
    cfg_e = cfg_big_cap.replace(moe=dataclasses.replace(
        cfg_big_cap.moe, dispatch="einsum"))
    cfg_s = cfg_big_cap.replace(moe=dataclasses.replace(
        cfg_big_cap.moe, dispatch="sort"))
    out_e, aux_e, _ = apply_moe(params, x, cfg_e)
    out_s, aux_s, _ = apply_moe(params, x, cfg_s)
    np.testing.assert_allclose(np.asarray(out_e, np.float32),
                               np.asarray(out_s, np.float32),
                               atol=1e-4, rtol=1e-3)
    assert float(aux_e) == pytest.approx(float(aux_s))


def test_gqa_tiled_matches_g_major_grouped():
    """The tiled-KV layout must equal grouped attention with g_major
    pairing (h % hkv) — the invariant that keeps prefill (tiled) and
    decode (grouped cache read) realizing the same model."""
    from repro.models.layers import simple_attention, tile_kv
    rng = np.random.default_rng(0)
    b, s, hq, hkv, d = 2, 16, 8, 2, 16
    q = jnp.asarray(rng.standard_normal((b, s, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    kt, vt = tile_kv(q, k, v)
    tiled = simple_attention(q, kt, vt, causal=True)
    g_major = simple_attention(q, k, v, causal=True, pairing="g_major")
    np.testing.assert_allclose(np.asarray(tiled), np.asarray(g_major),
                               atol=1e-5, rtol=1e-5)
    # and kv_major is a genuinely different pairing (different model)
    kv_major = simple_attention(q, k, v, causal=True)
    assert np.abs(np.asarray(kv_major) - np.asarray(tiled)).max() > 1e-3


def test_elastic_remesh_checkpoint(tmp_path):
    """Save under one mesh, restore+re-place under another; training step
    still runs and params are numerically identical."""
    import jax.sharding as jsh
    from repro.checkpoint import Checkpointer
    from repro.distributed.elastic import elastic_restore
    cfg = get_smoke_config("gemma-2b").replace(remat=False)
    model = build(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    ck = Checkpointer(str(tmp_path))
    ck.save(1, params)
    mesh = jax.make_mesh((1, 1), ("data", "model"),    # "different" mesh
                         axis_types=(jsh.AxisType.Auto,) * 2)
    placed, step, _ = elastic_restore(cfg, ck, params, mesh)
    assert step == 1
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(placed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # still usable for compute after re-placement
    logits, _ = model.forward(placed, {"tokens": jnp.ones((1, 8), jnp.int32),
                                       "labels": jnp.ones((1, 8), jnp.int32)})
    assert np.isfinite(np.asarray(logits)).all()
