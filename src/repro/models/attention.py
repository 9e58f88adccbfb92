"""Attention blocks: GQA/MQA/MHA with RoPE, and DeepSeek-V2 MLA.

Two execution modes per block:
  * full   — train / prefill over (b, s) tokens; returns new KV for caching.
  * decode — one query token against a cache at dynamic length ``cache_len``.

MLA caches the *compressed* latent (c_kv, k_rope) and uses the matrix-
absorption trick at decode, which is the whole point of MLA (KV bytes
independent of n_heads).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import MLAConfig, ModelConfig
from repro.models.layers import _init, apply_rope, attention, rope_tables, simple_attention


# ----------------------------- GQA ----------------------------------------

def init_gqa(key, cfg: ModelConfig, dtype=jnp.float32):
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": _init(ks[0], (d, hq * hd), dtype=dtype),
        "wk": _init(ks[1], (d, hkv * hd), dtype=dtype),
        "wv": _init(ks[2], (d, hkv * hd), dtype=dtype),
        "wo": _init(ks[3], (hq * hd, d), dtype=dtype),
    }


def gqa_full(params, x, cfg: ModelConfig, *, causal=True, positions=None,
             window: int = 0, return_kv=False):
    b, s, _ = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = (x @ params["wq"]).reshape(b, s, hq, hd)
    k = (x @ params["wk"]).reshape(b, s, hkv, hd)
    v = (x @ params["wv"]).reshape(b, s, hkv, hd)
    if cfg.rotary_pct > 0:
        pos = positions if positions is not None else jnp.arange(s)
        rot = int(hd * cfg.rotary_pct)
        cos, sin = rope_tables(pos, rot, cfg.rope_theta)
        q = apply_rope(q, cos, sin, cfg.rotary_pct)
        k = apply_rope(k, cos, sin, cfg.rotary_pct)
    o = attention(q, k, v, causal=causal, use_pallas=cfg.use_pallas,
                  window=window, gqa_mode=cfg.gqa_mode,
                  q_block=cfg.attn_q_block, kv_block=cfg.attn_kv_block,
                  f32_inputs=cfg.attn_f32_inputs)
    out = o.reshape(b, s, hq * hd) @ params["wo"]
    return (out, (k, v)) if return_kv else out


def gqa_cross(params, x, kv, cfg: ModelConfig):
    """Cross-attention: kv = (k, v) precomputed from the encoder."""
    b, s, _ = x.shape
    hq, hd = cfg.n_heads, cfg.resolved_head_dim
    q = (x @ params["wq"]).reshape(b, s, hq, hd)
    k, v = kv
    o = attention(q, k, v, causal=False, use_pallas=cfg.use_pallas,
                  gqa_mode=cfg.gqa_mode, q_block=cfg.attn_q_block,
                  kv_block=cfg.attn_kv_block)
    return o.reshape(b, s, hq * hd) @ params["wo"]


def gqa_decode(params, x, cache_k, cache_v, cache_len, cfg: ModelConfig,
               window: int = 0):
    """x: (b, 1, d); cache_k/v: (b, S, hkv, hd); returns out + updated cache.

    cache_len may be a scalar (dry-run / lockstep decode) or a (b,) vector
    (continuous batching — per-slot cache depths)."""
    b = x.shape[0]
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    cache_len = jnp.asarray(cache_len)
    per_slot = cache_len.ndim == 1
    q = (x @ params["wq"]).reshape(b, 1, hq, hd)
    k = (x @ params["wk"]).reshape(b, 1, hkv, hd)
    v = (x @ params["wv"]).reshape(b, 1, hkv, hd)
    if cfg.rotary_pct > 0:
        pos = cache_len.reshape(b, 1) if per_slot else \
            jnp.full((1,), cache_len)
        rot = int(hd * cfg.rotary_pct)
        cos, sin = rope_tables(pos, rot, cfg.rope_theta)
        q = apply_rope(q, cos, sin, cfg.rotary_pct)
        k = apply_rope(k, cos, sin, cfg.rotary_pct)
    if per_slot:
        rows = jnp.arange(b)
        cache_k = cache_k.at[rows, cache_len].set(
            k[:, 0].astype(cache_k.dtype))
        cache_v = cache_v.at[rows, cache_len].set(
            v[:, 0].astype(cache_v.dtype))
    else:
        cache_k = jax.lax.dynamic_update_slice_in_dim(
            cache_k, k.astype(cache_k.dtype), cache_len, axis=1)
        cache_v = jax.lax.dynamic_update_slice_in_dim(
            cache_v, v.astype(cache_v.dtype), cache_len, axis=1)
    if cfg.use_pallas and not per_slot:
        from repro.kernels.decode_attention import ops as da
        o = da.decode_attention(q, cache_k, cache_v, kv_len=cache_len + 1,
                                q_offset_for_window=(cache_len, window))
    else:
        # NOTE: never tile the KV cache at decode — measured 8x cache
        # materialization + 6x collectives (EXPERIMENTS.md §Perf C2);
        # grouped attention reads the hkv-wide cache directly, with the
        # head pairing matched to the full path's layout.
        pairing = "g_major" if cfg.gqa_mode == "tiled" else "kv_major"
        o = simple_attention(q, cache_k.astype(q.dtype),
                             cache_v.astype(q.dtype),
                             causal=False, kv_len=cache_len + 1,
                             window=window, f32_inputs=cfg.attn_f32_inputs,
                             pairing=pairing)
    out = o.reshape(b, 1, hq * hd) @ params["wo"]
    return out, (cache_k, cache_v)


def paged_kernel_attention(q, k_pools, v_pools, layer, cache_lens, valids,
                           page_tables, cfg: ModelConfig, *,
                           interpret: bool = False):
    """The Pallas paged kernel for (B, C) query rows over layer ``layer``
    of the (L, nb, hkv, blk, hd) pools (one layer's pool is ``pool[None]``
    at layer 0). It pairs q head ``h`` with kv head ``h % hkv``, the
    pairing of ``gqa_mode="tiled"``; a model pairing heads the other way
    would silently become a different model, so that combination is
    refused."""
    if cfg.gqa_mode != "tiled":
        raise ValueError(
            f"the paged kernel pairs heads g-major (gqa_mode='tiled'); "
            f"{cfg.name} has gqa_mode={cfg.gqa_mode!r}")
    from repro.kernels.paged_attention import ops as pa
    return pa.paged_prefill_attention(q, k_pools, v_pools, layer, cache_lens,
                                      valids, page_tables,
                                      interpret=interpret)


def gqa_decode_paged(params, x, k_pool, v_pool, page_tables, cache_len,
                     cfg: ModelConfig, *, paged_kernel: bool = False,
                     interpret: bool = False):
    """Paged-cache decode: one token per sequence against pooled KV blocks.

    x: (b, 1, d); k_pool/v_pool: (num_blocks, hkv, blk, hd) — one layer's
    slice of the shared block pool; page_tables: (b, npages) int32 block ids
    in position order (entries beyond the live length must be valid ids —
    the engine pads with the reserved null block 0); cache_len: (b,) int32
    per-sequence lengths *before* this token.

    The new token's K/V is scattered into block ``page_tables[b, len//blk]``
    at offset ``len % blk``; rows whose page table is all-null (inactive
    decode slots) harmlessly write to the null block. Attention then runs
    either through the Pallas paged kernel (``paged_kernel``: page-table
    scalar prefetch, no contiguous cache copy) or a gather-based jnp path.
    """
    b = x.shape[0]
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    blk = k_pool.shape[2]
    cache_len = jnp.asarray(cache_len)
    q = (x @ params["wq"]).reshape(b, 1, hq, hd)
    k = (x @ params["wk"]).reshape(b, 1, hkv, hd)
    v = (x @ params["wv"]).reshape(b, 1, hkv, hd)
    if cfg.rotary_pct > 0:
        pos = cache_len.reshape(b, 1)
        rot = int(hd * cfg.rotary_pct)
        cos, sin = rope_tables(pos, rot, cfg.rope_theta)
        q = apply_rope(q, cos, sin, cfg.rotary_pct)
        k = apply_rope(k, cos, sin, cfg.rotary_pct)
    rows = jnp.arange(b)
    bids = page_tables[rows, cache_len // blk]
    offs = cache_len % blk
    k_pool = k_pool.at[bids, :, offs].set(k[:, 0].astype(k_pool.dtype))
    v_pool = v_pool.at[bids, :, offs].set(v[:, 0].astype(v_pool.dtype))
    if paged_kernel:
        o = paged_kernel_attention(q, k_pool[None], v_pool[None], 0,
                                   cache_len, jnp.ones_like(cache_len),
                                   page_tables, cfg, interpret=interpret)
    else:
        from repro.kernels.paged_attention.ref import gather_pages
        kg = gather_pages(k_pool, page_tables).astype(q.dtype)
        vg = gather_pages(v_pool, page_tables).astype(q.dtype)
        pairing = "g_major" if cfg.gqa_mode == "tiled" else "kv_major"
        o = simple_attention(q, kg, vg, causal=False, kv_len=cache_len + 1,
                             f32_inputs=cfg.attn_f32_inputs, pairing=pairing)
    out = o.reshape(b, 1, hq * hd) @ params["wo"]
    return out, (k_pool, v_pool)


def gqa_prefill_chunk_paged(params, x, k_pool, v_pool, page_table, cache_len,
                            valid, cfg: ModelConfig, *,
                            paged_kernel: bool = False):
    """Sarathi-style chunked prefill against a paged cache: a fixed-width
    window of ``C`` prompt tokens for ONE sequence is processed in a single
    call, attending causally within the chunk and fully over the sequence's
    already-written pages.

    x: (1, C, d) chunk embeddings; k_pool/v_pool: (num_blocks, hkv, blk, hd)
    one layer's pool slice; page_table: (npages,) int32 block ids in position
    order (null-padded); cache_len: scalar int32 tokens already resident
    *before* this chunk; valid: scalar int32 — how many of the C positions
    are real (the tail of a prompt rarely fills the chunk width; padding
    rows write to the reserved null block 0 and are masked out of
    attention, so one traced shape serves every chunk).
    """
    b, C, _ = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    blk = k_pool.shape[2]
    npages = page_table.shape[0]
    cache_len = jnp.asarray(cache_len)
    valid = jnp.asarray(valid)
    pos = cache_len + jnp.arange(C)
    q = (x @ params["wq"]).reshape(b, C, hq, hd)
    k = (x @ params["wk"]).reshape(b, C, hkv, hd)
    v = (x @ params["wv"]).reshape(b, C, hkv, hd)
    if cfg.rotary_pct > 0:
        rot = int(hd * cfg.rotary_pct)
        cos, sin = rope_tables(pos, rot, cfg.rope_theta)
        q = apply_rope(q, cos, sin, cfg.rotary_pct)
        k = apply_rope(k, cos, sin, cfg.rotary_pct)
    # scatter the chunk's K/V into the sequence's blocks; padding positions
    # (and any position past the table) land in null block 0
    live = jnp.arange(C) < valid
    page_idx = jnp.clip(pos // blk, 0, npages - 1)
    bids = jnp.where(live, page_table[page_idx], 0)
    offs = pos % blk
    k_pool = k_pool.at[bids, :, offs].set(k[0].astype(k_pool.dtype))
    v_pool = v_pool.at[bids, :, offs].set(v[0].astype(v_pool.dtype))
    if paged_kernel:
        o = paged_kernel_attention(q, k_pool[None], v_pool[None], 0,
                                   cache_len[None], valid[None],
                                   page_table[None], cfg)
    else:
        from repro.kernels.paged_attention.ref import gather_pages
        kg = gather_pages(k_pool, page_table[None]).astype(q.dtype)
        vg = gather_pages(v_pool, page_table[None]).astype(q.dtype)
        pairing = "g_major" if cfg.gqa_mode == "tiled" else "kv_major"
        o = simple_attention(q, kg, vg, causal=True, q_offset=cache_len,
                             kv_len=cache_len + valid,
                             f32_inputs=cfg.attn_f32_inputs, pairing=pairing)
    out = o.reshape(b, C, hq * hd) @ params["wo"]
    return out, (k_pool, v_pool)


def gqa_mixed_step_paged(params, x, k_pool, v_pool, layer, page_tables,
                         cache_lens, valids, cfg: ModelConfig, *,
                         paged_kernel: bool = False, interpret: bool = False,
                         axis_name: Optional[str] = None):
    """One fused Sarathi megastep row set: every row of the ``(B, C)``
    batch is a prefill chunk — decode rows simply carry ``valids == 1`` —
    so ONE call writes every row's K/V into its pages and attends causally
    over chunk + resident history.

    x: (B, C, d) embeddings (token padding beyond ``valids`` is garbage the
    caller discards); k_pool/v_pool: (L, num_blocks, hkv, blk, hd) — the
    WHOLE layer stack, which the megastep carries through its layer scan;
    this layer's K/V is written into it in place at ``layer`` and the
    attention reads ``layer`` of it, so no layer's slice is cut out,
    relaid or restacked; layer: scalar int32 (traced: the scan's layer
    index); page_tables: (B, npages) int32, null-padded; cache_lens:
    (B,) int32 tokens resident *before* this step; valids: (B,) int32 real
    tokens per row (0 = inactive slot; its writes land in the null block and
    its outputs are discarded). C is whatever trace bucket the engine's
    token-budget packer chose for this step ({1, 8, 16, ..., budget}):
    the RoPE positions, scatter targets and attention mask below are all
    computed from ``cache_lens``/``valids`` per row, never from C, so rows
    of different real widths coexist in one dispatch and a wider bucket
    only adds masked padding columns. Per-row isolation is the page table
    itself: a row only reads/writes its own blocks, so batching rows into
    one dispatch cannot change any row's math. ``paged_kernel`` runs the
    attention through the Pallas kernel (the engine sets it on a TPU);
    otherwise the jnp gather reference computes the same rows.

    Under the sharded megastep (DESIGN.md §13) this runs INSIDE shard_map
    with per-shard views: ``cfg`` carries the LOCAL head counts
    (``n_heads/tp``, ``n_kv_heads/tp``), the pools are this shard's KV-head
    slice, and ``axis_name`` names the mesh axis to ``psum`` the attention
    output over — the one collective per layer, placed after the local
    ``o @ wo`` partial so only a (B, C, d) activation is reduced. With
    ``axis_name=None`` (single device) the math is untouched.
    """
    b, C, _ = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    blk = k_pool.shape[3]
    npages = page_tables.shape[1]
    cache_lens = jnp.asarray(cache_lens)
    valids = jnp.asarray(valids)
    pos = cache_lens[:, None] + jnp.arange(C)[None, :]        # (B, C)
    # the named scopes label the device ops of a profiler trace
    with jax.named_scope("qkv"):
        q = (x @ params["wq"]).reshape(b, C, hq, hd)
        k = (x @ params["wk"]).reshape(b, C, hkv, hd)
        v = (x @ params["wv"]).reshape(b, C, hkv, hd)
        if cfg.rotary_pct > 0:
            rot = int(hd * cfg.rotary_pct)
            cos, sin = rope_tables(pos, rot, cfg.rope_theta)
            q = apply_rope(q, cos, sin, cfg.rotary_pct)
            k = apply_rope(k, cos, sin, cfg.rotary_pct)
    # scatter every row's chunk into its own blocks of this layer; padding
    # positions (and inactive rows) aim at the reserved null block 0. Every
    # axis but the minor one is indexed (the head axis too), so each update
    # is one hd-wide row and XLA keeps the pool's own layout; a ``:`` on
    # the head axis makes the window (hkv, hd) and XLA relays the whole
    # stack around the scatter, inside the layer loop (DESIGN.md §10)
    with jax.named_scope("kv_write"):
        live = jnp.arange(C)[None, :] < valids[:, None]
        page_idx = jnp.clip(pos // blk, 0, npages - 1)
        bids = jnp.where(live,
                         jnp.take_along_axis(page_tables, page_idx, axis=1),
                         0)
        offs = pos % blk
        at = (layer, bids[..., None], jnp.arange(hkv)[None, None, :],
              offs[..., None])
        k_pool = k_pool.at[at].set(k.astype(k_pool.dtype))
        v_pool = v_pool.at[at].set(v.astype(v_pool.dtype))
    with jax.named_scope("paged"):
        if paged_kernel:
            o = paged_kernel_attention(q, k_pool, v_pool, layer, cache_lens,
                                       valids, page_tables, cfg,
                                       interpret=interpret)
        else:
            from repro.kernels.paged_attention.ref import \
                paged_prefill_attention_ref
            pairing = "g_major" if cfg.gqa_mode == "tiled" else "kv_major"
            o = paged_prefill_attention_ref(q, k_pool[layer], v_pool[layer],
                                            cache_lens, valids, page_tables,
                                            pairing=pairing)
    with jax.named_scope("out"):
        out = o.reshape(b, C, hq * hd) @ params["wo"]
        if axis_name is not None:
            # each shard contributed its head slice through its wo rows;
            # the sum over shards completes the (B, C, d) attention output
            # and re-replicates the residual stream on every shard
            out = jax.lax.psum(out, axis_name)
    return out, (k_pool, v_pool)


def gqa_decode_ring(params, x, cache_k, cache_v, cache_len, cfg: ModelConfig):
    """Sliding-window decode against a ring-buffer cache (zamba2 long ctx).

    cache size == window; entry for absolute position p lives at p % W.
    Once the ring is full every slot is a valid (in-window) key.
    """
    b = x.shape[0]
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    w = cache_k.shape[1]
    q = (x @ params["wq"]).reshape(b, 1, hq, hd)
    k = (x @ params["wk"]).reshape(b, 1, hkv, hd)
    v = (x @ params["wv"]).reshape(b, 1, hkv, hd)
    if cfg.rotary_pct > 0:
        pos = jnp.full((1,), cache_len)
        rot = int(hd * cfg.rotary_pct)
        cos, sin = rope_tables(pos, rot, cfg.rope_theta)
        q = apply_rope(q, cos, sin, cfg.rotary_pct)
        k = apply_rope(k, cos, sin, cfg.rotary_pct)
    write = jnp.mod(cache_len, w)
    cache_k = jax.lax.dynamic_update_slice_in_dim(
        cache_k, k.astype(cache_k.dtype), write, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(
        cache_v, v.astype(cache_v.dtype), write, axis=1)
    kv_len = jnp.minimum(cache_len + 1, w)
    pairing = "g_major" if cfg.gqa_mode == "tiled" else "kv_major"
    o = simple_attention(q, cache_k.astype(q.dtype), cache_v.astype(q.dtype),
                         causal=False, kv_len=kv_len, pairing=pairing)
    out = o.reshape(b, 1, hq * hd) @ params["wo"]
    return out, (cache_k, cache_v)


# ----------------------------- MLA ----------------------------------------

def init_mla(key, cfg: ModelConfig, dtype=jnp.float32):
    m: MLAConfig = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 5)
    return {
        "wq": _init(ks[0], (d, h * qk), dtype=dtype),
        # joint down-projection: [c_kv (rank) | k_rope (rope_dim)]
        "w_dkv": _init(ks[1], (d, m.kv_lora_rank + m.qk_rope_head_dim), dtype=dtype),
        "kv_norm": jnp.zeros((m.kv_lora_rank,), dtype=dtype),
        # up-projections from the latent: per head [k_nope | v]
        "w_uk": _init(ks[2], (m.kv_lora_rank, h * m.qk_nope_head_dim), dtype=dtype),
        "w_uv": _init(ks[3], (m.kv_lora_rank, h * m.v_head_dim), dtype=dtype),
        "wo": _init(ks[4], (h * m.v_head_dim, d), dtype=dtype),
    }


def _mla_qkv(params, x, cfg: ModelConfig, positions):
    from repro.models.layers import rms_norm
    m: MLAConfig = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    q = (x @ params["wq"]).reshape(b, s, h, qk)
    q_nope, q_rope = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    dkv = x @ params["w_dkv"]
    c_kv = rms_norm(dkv[..., : m.kv_lora_rank], params["kv_norm"], cfg.norm_eps)
    k_rope = dkv[..., m.kv_lora_rank:]                    # (b, s, rope) MQA-like
    cos, sin = rope_tables(positions, m.qk_rope_head_dim, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    k_rope = apply_rope(k_rope[..., None, :], cos, sin)[..., 0, :]
    return q_nope, q_rope, c_kv, k_rope


def mla_full(params, x, cfg: ModelConfig, *, positions=None, return_kv=False):
    """Training/prefill path: decompress K/V per head (standard formulation)."""
    m: MLAConfig = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    pos = positions if positions is not None else jnp.arange(s)
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(params, x, cfg, pos)
    k_nope = (c_kv @ params["w_uk"]).reshape(b, s, h, m.qk_nope_head_dim)
    v = (c_kv @ params["w_uv"]).reshape(b, s, h, m.v_head_dim)
    # assemble full-width q/k: [nope | rope(shared k)]
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, m.qk_rope_head_dim))],
        axis=-1)
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    # value head_dim (v) differs from qk head_dim — the generic attention path
    # supports dv != dqk (blocked online-softmax at long seq).
    o = attention(q_full, k_full, v, causal=True, scale=scale,
                  use_pallas=cfg.use_pallas, q_block=cfg.attn_q_block,
                  kv_block=cfg.attn_kv_block)
    out = o.reshape(b, s, h * m.v_head_dim) @ params["wo"]
    return (out, (c_kv, k_rope)) if return_kv else out


def mla_decode(params, x, cache_ckv, cache_krope, cache_len, cfg: ModelConfig):
    """Absorbed decode: scores in latent space, cache holds (c_kv, k_rope).

    cache_ckv: (b, S, rank); cache_krope: (b, S, rope_dim).
    """
    m: MLAConfig = cfg.mla
    b = x.shape[0]
    h = cfg.n_heads
    pos = jnp.full((1,), cache_len)
    q_nope, q_rope, c_kv_new, k_rope_new = _mla_qkv(params, x, cfg, pos)
    cache_ckv = jax.lax.dynamic_update_slice_in_dim(
        cache_ckv, c_kv_new.astype(cache_ckv.dtype), cache_len, axis=1)
    cache_krope = jax.lax.dynamic_update_slice_in_dim(
        cache_krope, k_rope_new.astype(cache_krope.dtype), cache_len, axis=1)
    # absorb W_uk into q: q_lat (b,1,h,rank)
    w_uk = params["w_uk"].reshape(m.kv_lora_rank, h, m.qk_nope_head_dim)
    q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope, w_uk)
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    s_lat = jnp.einsum("bqhr,bkr->bhqk", q_lat.astype(jnp.float32),
                       cache_ckv.astype(jnp.float32))
    s_rope = jnp.einsum("bqhd,bkd->bhqk", q_rope.astype(jnp.float32),
                        cache_krope.astype(jnp.float32))
    s = (s_lat + s_rope) * scale
    kpos = jnp.arange(cache_ckv.shape[1])
    s = jnp.where((kpos < cache_len + 1)[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    # output in latent space, then up-project through W_uv (absorbed into wo)
    o_lat = jnp.einsum("bhqk,bkr->bqhr", p.astype(cache_ckv.dtype), cache_ckv)
    w_uv = params["w_uv"].reshape(m.kv_lora_rank, h, m.v_head_dim)
    o = jnp.einsum("bqhr,rhd->bqhd", o_lat, w_uv)
    out = o.reshape(b, 1, h * m.v_head_dim) @ params["wo"]
    return out, (cache_ckv, cache_krope)
