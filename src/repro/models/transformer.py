"""Decoder-only LM assembly for dense / MoE / MLA / SSM / VLM families.

Layers are *scanned* (params stacked on a leading axis) so the HLO contains a
single traced layer regardless of depth — essential for 95-layer dry-run
compiles. Heterogeneous leading layers (deepseek-v2-lite's dense layer 0) are
kept unstacked.

API (functions returned by ``repro.models.model.build``):
  init_params(rng)                                  -> params
  forward(params, batch)                            -> logits over text posns
  loss(params, batch)                               -> (scalar, metrics)
  init_decode_state(batch, max_len)                 -> state pytree
  prefill(params, batch, state)                     -> (logits_last, state)
  decode_step(params, state, token, cache_len)      -> (logits, state)
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssd as ssd_mod
from repro.models.layers import (_init, apply_mlp, cast_floats,
                                 cross_entropy_loss, init_mlp, rms_norm)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_attn(key, cfg: ModelConfig, dtype):
    if cfg.mla is not None:
        return attn_mod.init_mla(key, cfg, dtype)
    return attn_mod.init_gqa(key, cfg, dtype)


def _init_layer(key, cfg: ModelConfig, *, dense_ff: int = 0, dtype=jnp.float32):
    """One transformer layer; dense_ff>0 forces a dense MLP of that width."""
    k1, k2 = jax.random.split(key)
    p = {
        "attn_norm": jnp.zeros((cfg.d_model,), dtype),
        "mlp_norm": jnp.zeros((cfg.d_model,), dtype),
        "attn": _init_attn(k1, cfg, dtype),
    }
    if cfg.family == "ssm":
        raise AssertionError("ssm handled by init_mamba stack")
    if dense_ff or cfg.moe is None:
        p["mlp"] = init_mlp(k2, cfg.d_model, dense_ff or cfg.d_ff, cfg.act, dtype)
    else:
        p["moe"] = moe_mod.init_moe(k2, cfg, dtype)
    return p


def init_params(rng, cfg: ModelConfig) -> Dict:
    dtype = jnp.dtype(cfg.param_dtype)
    keys = jax.random.split(rng, 8)
    p: Dict = {
        "embed": _init(keys[0], (cfg.vocab_size, cfg.d_model), scale=0.02,
                       dtype=dtype),
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = _init(keys[1], (cfg.d_model, cfg.vocab_size),
                             dtype=dtype)
    if cfg.family == "ssm":
        n = cfg.n_layers
        lkeys = jax.random.split(keys[2], n)
        layer = jax.vmap(lambda k: {
            "norm": jnp.zeros((cfg.d_model,), dtype),
            "mamba": ssd_mod.init_mamba(k, cfg, dtype)})
        p["layers"] = layer(lkeys)
        return p
    n_scan = cfg.n_layers - cfg.first_dense_layers
    lkeys = jax.random.split(keys[2], n_scan)
    p["layers"] = jax.vmap(
        lambda k: _init_layer(k, cfg, dtype=dtype))(lkeys)
    if cfg.first_dense_layers:
        assert cfg.first_dense_layers == 1
        p["dense0"] = _init_layer(keys[3], cfg,
                                  dense_ff=cfg.first_dense_d_ff, dtype=dtype)
    return p


# ---------------------------------------------------------------------------
# forward (train / prefill, full sequence)
# ---------------------------------------------------------------------------

def _attn_full(lp, x, cfg, return_kv=False):
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    if cfg.mla is not None:
        return attn_mod.mla_full(lp["attn"], h, cfg, return_kv=return_kv)
    return attn_mod.gqa_full(lp["attn"], h, cfg, return_kv=return_kv)


def _mlp_or_moe(lp, x, cfg):
    h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    if "moe" in lp:
        out, aux, z = moe_mod.apply_moe(lp["moe"], h, cfg)
    else:
        out, aux, z = apply_mlp(lp["mlp"], h, cfg.act), 0.0, 0.0
    return out, aux, z


def remat_wrap(body, cfg):
    """Per-layer remat with a selectable policy: "full" recomputes the whole
    layer in backward; "dots" saves matmul outputs (no MXU recompute) at the
    price of activation memory — §Perf iteration knob."""
    if not cfg.remat:
        return body
    policy = (None if cfg.remat_policy == "full"
              else jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(body, policy=policy)


def _layer_full(x, lp, cfg, return_kv=False):
    if return_kv:
        a, kv = _attn_full(lp, x, cfg, return_kv=True)
    else:
        a, kv = _attn_full(lp, x, cfg), None
    x = x + a
    m, aux, z = _mlp_or_moe(lp, x, cfg)
    x = x + m
    return x, (jnp.asarray(aux, jnp.float32), jnp.asarray(z, jnp.float32)), kv


def _embed(params, tokens, cfg):
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.compute_dtype)
    if cfg.tie_embeddings:  # gemma scales tied embeddings
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


def _unembed(params, h, cfg):
    w = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    return h.astype(jnp.float32) @ w.astype(jnp.float32)


def _assemble_input(params, batch, cfg):
    x = _embed(params, batch["tokens"], cfg)
    if cfg.family == "vlm":
        patch = batch["patch_embeds"].astype(x.dtype)
        x = jnp.concatenate([patch, x], axis=1)
    return x


def forward(params, batch, cfg: ModelConfig):
    """-> (logits over text positions (b, s_text, V) f32, aux_metrics)."""
    params = cast_floats(params, cfg.compute_dtype)
    x = _assemble_input(params, batch, cfg)

    if cfg.family == "ssm":
        def body(h, lp):
            y, _ = ssd_mod.mamba_full(
                lp["mamba"], rms_norm(h, lp["norm"], cfg.norm_eps), cfg)
            return h + y, None
        body = remat_wrap(body, cfg)
        x, _ = jax.lax.scan(body, x, params["layers"])
        aux = (jnp.float32(0.0), jnp.float32(0.0))
    else:
        if cfg.first_dense_layers:
            x, _, _ = _layer_full(x, params["dense0"], cfg)

        def body(h, lp):
            h, aux, _ = _layer_full(h, lp, cfg)
            return h, aux
        body = remat_wrap(body, cfg)
        x, auxs = jax.lax.scan(body, x, params["layers"])
        aux = (jnp.sum(auxs[0]), jnp.sum(auxs[1]))

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.family == "vlm":
        x = x[:, cfg.n_image_tokens:]
    logits = _unembed(params, x, cfg)
    return logits, {"moe_aux": aux[0], "moe_z": aux[1]}


def loss(params, batch, cfg: ModelConfig):
    logits, metrics = forward(params, batch, cfg)
    ce = cross_entropy_loss(logits, batch["labels"])
    total = ce + 0.01 * metrics["moe_aux"] + 1e-3 * metrics["moe_z"]
    metrics = dict(metrics, ce=ce)
    return total, metrics


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def init_decode_state(cfg: ModelConfig, batch: int, max_len: int) -> Dict:
    ct = jnp.dtype(cfg.kv_cache_dtype or cfg.compute_dtype)
    n_scan = cfg.n_layers - cfg.first_dense_layers
    st: Dict = {}
    if cfg.family == "ssm":
        m = cfg.ssm
        d_in = m.expand * cfg.d_model
        h = d_in // m.head_dim
        conv_dim = d_in + 2 * m.n_groups * m.d_state
        st["conv"] = jnp.zeros(
            (cfg.n_layers, batch, m.conv_kernel - 1, conv_dim), ct)
        st["ssm"] = jnp.zeros(
            (cfg.n_layers, batch, m.n_groups, h // m.n_groups, m.d_state,
             m.head_dim), jnp.float32)
        return st
    if cfg.mla is not None:
        r, rd = cfg.mla.kv_lora_rank, cfg.mla.qk_rope_head_dim
        st["ckv"] = jnp.zeros((n_scan, batch, max_len, r), ct)
        st["krope"] = jnp.zeros((n_scan, batch, max_len, rd), ct)
        if cfg.first_dense_layers:
            st["ckv0"] = jnp.zeros((batch, max_len, r), ct)
            st["krope0"] = jnp.zeros((batch, max_len, rd), ct)
        return st
    hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    st["k"] = jnp.zeros((n_scan, batch, max_len, hkv, hd), ct)
    st["v"] = jnp.zeros((n_scan, batch, max_len, hkv, hd), ct)
    return st


def _layer_decode(lp, x, ks, cache_len, cfg):
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    if cfg.mla is not None:
        a, (ckv, krope) = attn_mod.mla_decode(
            lp["attn"], h, ks[0], ks[1], cache_len, cfg)
        new_ks = (ckv, krope)
    else:
        a, (ck, cv) = attn_mod.gqa_decode(
            lp["attn"], h, ks[0], ks[1], cache_len, cfg)
        new_ks = (ck, cv)
    x = x + a
    m, _, _ = _mlp_or_moe(lp, x, cfg)
    return x + m, new_ks


def decode_step(params, state: Dict, token, cache_len, cfg: ModelConfig):
    """token (b, 1) -> (logits (b, 1, V) f32, new state)."""
    params = cast_floats(params, cfg.compute_dtype)
    x = _embed(params, token, cfg)

    if cfg.family == "ssm":
        def body(h, xs):
            lp, conv, ssm = xs
            y, (conv, ssm) = ssd_mod.mamba_decode(
                lp["mamba"], rms_norm(h, lp["norm"], cfg.norm_eps),
                (conv, ssm), cfg)
            return h + y, (conv, ssm)
        x, (conv, ssm) = jax.lax.scan(
            body, x, (params["layers"], state["conv"], state["ssm"]))
        state = dict(state, conv=conv, ssm=ssm)
    elif cfg.mla is not None:
        if cfg.first_dense_layers:
            x, (ckv0, krope0) = _layer_decode(
                params["dense0"], x, (state["ckv0"], state["krope0"]),
                cache_len, cfg)
            state = dict(state, ckv0=ckv0, krope0=krope0)

        def body(h, xs):
            lp, ckv, krope = xs
            h, (ckv, krope) = _layer_decode(lp, h, (ckv, krope), cache_len, cfg)
            return h, (ckv, krope)
        x, (ckv, krope) = jax.lax.scan(
            body, x, (params["layers"], state["ckv"], state["krope"]))
        state = dict(state, ckv=ckv, krope=krope)
    else:
        def body(h, xs):
            lp, ck, cv = xs
            h, (ck, cv) = _layer_decode(lp, h, (ck, cv), cache_len, cfg)
            return h, (ck, cv)
        x, (k, v) = jax.lax.scan(
            body, x, (params["layers"], state["k"], state["v"]))
        state = dict(state, k=k, v=v)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return _unembed(params, x, cfg), state


def init_paged_pools(cfg: ModelConfig, num_blocks: int,
                     block_size: int) -> Dict:
    """Pooled paged KV state for the GQA family: per-layer block pools of
    shape (L, num_blocks, hkv, blk, hd). Block ids are shared across layers
    (every layer stores the same token positions in the same block id), so
    one page table per sequence serves the whole stack. Each page is
    head-major, so the paged kernel reads one (blk, hd) slab per kv head.
    The megastep (``mixed_step_paged``) carries each pool as ONE buffer
    through its layer scan and writes it in place at (layer, block, head,
    offset); no layer's slice is ever cut out or restacked."""
    assert cfg.family in ("dense", "moe", "vlm") and cfg.mla is None, \
        "paged KV pools target the decoder-only GQA family"
    assert cfg.first_dense_layers == 0, \
        "paged decode does not support heterogeneous leading layers yet"
    ct = jnp.dtype(cfg.kv_cache_dtype or cfg.compute_dtype)
    hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    shape = (cfg.n_layers, num_blocks, hkv, block_size, hd)
    return {"k": jnp.zeros(shape, ct), "v": jnp.zeros(shape, ct)}


def decode_step_paged(params, pools: Dict, token, cache_len, page_tables,
                      cfg: ModelConfig, *, paged_kernel: bool = False):
    """Paged analogue of ``decode_step``: token (b, 1), cache_len (b,) int32
    lengths before this token, page_tables (b, npages) int32.
    ``paged_kernel`` selects the Pallas attention kernel (see
    ``mixed_step_paged``). Returns (logits (b, 1, V) f32, updated pools)."""
    params = cast_floats(params, cfg.compute_dtype)
    x = _embed(params, token, cfg)

    def body(h, xs):
        lp, kp, vp = xs
        hh = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
        a, (kp, vp) = attn_mod.gqa_decode_paged(
            lp["attn"], hh, kp, vp, page_tables, cache_len, cfg,
            paged_kernel=paged_kernel)
        h = h + a
        m, _, _ = _mlp_or_moe(lp, h, cfg)
        return h + m, (kp, vp)

    x, (k, v) = jax.lax.scan(body, x, (params["layers"], pools["k"],
                                       pools["v"]))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return _unembed(params, x, cfg), {"k": k, "v": v}


def prefill_chunk_paged(params, pools: Dict, tokens, cache_len, valid,
                        page_table, cfg: ModelConfig, *,
                        paged_kernel: bool = False):
    """Chunked prefill for one sequence over the paged pools (Sarathi-style
    admission: a long prompt enters the batch ``C`` tokens per engine step
    instead of blocking it). tokens: (1, C) int32 (null-padded to the fixed
    chunk width), cache_len/valid: scalar int32, page_table: (npages,) int32.
    Returns (logits (1, C, V) f32 — caller reads position ``valid - 1``,
    updated pools). The chunk's K/V is written into the sequence's pages, so
    after the call the cache holds positions [0, cache_len + valid)."""
    params = cast_floats(params, cfg.compute_dtype)
    x = _embed(params, tokens, cfg)

    def body(h, xs):
        lp, kp, vp = xs
        hh = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
        a, (kp, vp) = attn_mod.gqa_prefill_chunk_paged(
            lp["attn"], hh, kp, vp, page_table, cache_len, valid, cfg,
            paged_kernel=paged_kernel)
        h = h + a
        m, _, _ = _mlp_or_moe(lp, h, cfg)
        return h + m, (kp, vp)

    x, (k, v) = jax.lax.scan(body, x, (params["layers"], pools["k"],
                                       pools["v"]))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return _unembed(params, x, cfg), {"k": k, "v": v}


def mixed_step_paged(params, pools: Dict, tokens, cache_lens, valids,
                     page_tables, cfg: ModelConfig, poison_mask=None, *,
                     paged_kernel: bool = False, axis_name=None):
    """The megastep forward: ONE jitted call advances the whole mixed batch
    one engine iteration — decode rows are width-1 prefill rows (Sarathi
    batch fusion over the paged pools).

    tokens: (B, C) int32 — row b carries ``valids[b]`` real tokens (decode:
    the last sampled token at column 0; prefill: the next prompt chunk),
    null-padded to the dispatch width. C itself carries no semantics beyond
    "wide enough": the engine's token-budget packer picks it per step from
    a bounded pow2 bucket set over the ragged per-row widths, and every
    per-row quantity (RoPE positions, causal masking, K/V scatter targets,
    which column is unembedded) is driven by ``valids``/``cache_lens``, so
    the same function serves any bucket — wider C only adds masked padding
    columns. cache_lens/valids: (B,) int32; page_tables: (B, npages) int32,
    null-padded. Greedy sampling happens INSIDE the jit: only the last
    valid position of each row is unembedded and argmaxed, so a single
    (B,) int32 vector crosses to host per step instead of (B, vocab)
    logits. Returns (next_token_ids (B,) int32, updated pools). Inactive
    rows (valids == 0) produce garbage ids the caller ignores; their K/V
    writes land in the reserved null block.

    ``paged_kernel`` runs attention through the Pallas paged kernel; the
    engine sets it from the platform of its device (the kernel on a TPU,
    the jnp gather reference on a CPU).

    The pools ride the layer scan as its carry, not as xs/ys: each
    (L, num_blocks, hkv, blk, hd) stack stays one buffer in its own layout
    through all L layers, layer ``l`` writes its K/V into it in place and
    its attention reads ``(l, page)`` of it, and the carried stacks are
    returned. Scanned as xs/ys instead, every layer's slice would be cut
    out, relaid and restacked and both stacks copied after the loop, on
    every step (DESIGN.md §10).

    ``axis_name`` is set when this body runs inside the sharded megastep's
    shard_map (DESIGN.md §13): ``cfg`` then carries per-shard head counts,
    ``pools`` is this shard's KV-head slice, and each layer's attention
    output is psum'd over the axis — after which the residual stream is
    replicated again, so the final unembed + argmax are computed
    identically on every shard with no further collective."""
    params = cast_floats(params, cfg.compute_dtype)
    # named scopes (embed, attn/qkv, attn/kv_write, attn/paged, attn/out,
    # mlp, head) name the device ops in a profiler trace; they change op
    # metadata only, not the computation
    with jax.named_scope("embed"):
        x = _embed(params, tokens, cfg)

    def body(carry, xs):
        h, kp, vp = carry
        lp, layer = xs
        with jax.named_scope("attn"):
            with jax.named_scope("qkv"):
                hh = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
            a, (kp, vp) = attn_mod.gqa_mixed_step_paged(
                lp["attn"], hh, kp, vp, layer, page_tables, cache_lens,
                valids, cfg, paged_kernel=paged_kernel, axis_name=axis_name)
        h = h + a
        with jax.named_scope("mlp"):
            m, _, _ = _mlp_or_moe(lp, h, cfg)
        return (h + m, kp, vp), None

    layers = jnp.arange(pools["k"].shape[0], dtype=jnp.int32)
    (x, k, v), _ = jax.lax.scan(body, (x, pools["k"], pools["v"]),
                                (params["layers"], layers))
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        rows = jnp.arange(x.shape[0])
        last = jnp.clip(jnp.asarray(valids) - 1, 0, x.shape[1] - 1)
        logits = _unembed(params, x[rows, last], cfg)  # (B, V): last valid
        if poison_mask is not None:
            # Seeded chaos injection point: poison a row's logits AFTER its
            # K/V writes so the damage is confined to this row's sampled
            # token. With an all-False mask the where is a bitwise no-op,
            # keeping the faults-disabled dispatch identical to an
            # uninstrumented one.
            logits = jnp.where(poison_mask[:, None], jnp.float32(jnp.nan),
                               logits)
        # In-jit per-row finiteness check (blast-radius = 1 row): a
        # non-finite logits row — injected or genuine — reports the -1
        # sentinel instead of an argmax over garbage, so the host fails
        # exactly that row's turn while batchmates' tokens stay bitwise
        # identical to a fault-free step. Still only one (B,) int32 vector
        # crosses to host.
        row_ok = jnp.all(jnp.isfinite(logits), axis=-1)
        ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        ids = jnp.where(row_ok, ids, jnp.int32(-1))
    return ids, {"k": k, "v": v}


def prefill(params, batch, cfg: ModelConfig, state: Optional[Dict] = None,
            max_len: Optional[int] = None):
    """Full-sequence prefill; returns (last-position logits, filled state).

    For the dry-run prefill shape we only need logits; state fill-in is used
    by the serving engine (repro.serving.engine) for prefill->decode handoff.
    """
    if state is None:
        logits, _ = forward(params, batch, cfg)
        return logits[:, -1:], None
    # serving path: run layers individually collecting KV — implemented via
    # the same scan but returning per-layer kv stacks.
    params = cast_floats(params, cfg.compute_dtype)
    x = _assemble_input(params, batch, cfg)
    if cfg.family == "ssm":
        def body(h, xs):
            lp, conv, ssm = xs
            y, (cs, ss) = ssd_mod.mamba_full(
                lp["mamba"], rms_norm(h, lp["norm"], cfg.norm_eps), cfg)
            return h + y, (cs, ss)
        x, (conv, ssm) = jax.lax.scan(
            body, x, (params["layers"], state["conv"], state["ssm"]))
        state = dict(state, conv=conv, ssm=ssm)
    else:
        s = x.shape[1]
        if cfg.first_dense_layers:
            x, _, kv0 = _layer_full(x, params["dense0"], cfg, return_kv=True)
            if cfg.mla is not None:
                state = dict(state,
                             ckv0=_fill(state["ckv0"], kv0[0]),
                             krope0=_fill(state["krope0"], kv0[1]))

        def body(h, lp):
            h, _, kv = _layer_full(h, lp, cfg, return_kv=True)
            return h, kv
        x, kvs = jax.lax.scan(body, x, params["layers"])
        if cfg.mla is not None:
            state = dict(state, ckv=_fill(state["ckv"], kvs[0], stacked=True),
                         krope=_fill(state["krope"], kvs[1], stacked=True))
        else:
            state = dict(state, k=_fill(state["k"], kvs[0], stacked=True),
                         v=_fill(state["v"], kvs[1], stacked=True))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.family == "vlm":
        x = x[:, -1:]
        logits = _unembed(params, x, cfg)
    else:
        logits = _unembed(params, x[:, -1:], cfg)
    return logits, state


def _fill(cache, new, stacked=False):
    """Write prefill K/V into position 0.. of a max_len cache."""
    axis = 2 if stacked else 1
    new = new.astype(cache.dtype)
    idx = [0] * cache.ndim
    return jax.lax.dynamic_update_slice(cache, new, tuple(idx))
