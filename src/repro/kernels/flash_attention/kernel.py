"""Pallas TPU flash-attention (FlashAttention-2 schedule, VMEM-tiled).

TPU adaptation: KV tiles stream HBM->VMEM under BlockSpec control; the
(bq x d) @ (d x bk) score matmul and the (bq x bk) @ (bk x dv) PV matmul both
land on the MXU (tile sizes are multiples of 128 on the lane dim); the
online-softmax running stats (m, l) and the f32 accumulator live in VMEM
scratch across the sequential kv grid dimension.

Grid: (b, hq, nq, nk) with dimension_semantics (parallel x3, arbitrary) —
the last axis iterates KV tiles in order, which is what makes the scratch
carry valid.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, blk_q: int, blk_k: int, nk: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = (not causal) or (ki * blk_k <= qi * blk_q + blk_q - 1)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)       # (blk_q, d)
        k = k_ref[0, 0].astype(jnp.float32)       # (blk_k, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qi * blk_q + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 0)
            kpos = ki * blk_k + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1)
        v = v_ref[0, 0].astype(jnp.float32)       # (blk_k, dv)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr[:, None] + pv
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _fini():
        denom = jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "blk_q",
                                             "blk_k", "interpret"))
def flash_attention_bhsd(q, k, v, *, causal: bool = True, scale=None,
                         blk_q: int = 256, blk_k: int = 256,
                         interpret: bool = False):
    """q: (b, hq, sq, d); k: (b, hkv, skv, d); v: (b, hkv, skv, dv)."""
    b, hq, sq, d = q.shape
    hkv, skv, dv = k.shape[1], k.shape[2], v.shape[-1]
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    blk_q = min(blk_q, sq)
    blk_k = min(blk_k, skv)
    assert sq % blk_q == 0 and skv % blk_k == 0, (sq, skv, blk_q, blk_k)
    nq, nk = sq // blk_q, skv // blk_k

    grid = (b, hq, nq, nk)
    kern = functools.partial(_kernel, scale=scale, causal=causal,
                             blk_q=blk_q, blk_k=blk_k, nk=nk)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, blk_k, d),
                         lambda b_, h, i, j, g=g: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, blk_k, dv),
                         lambda b_, h, i, j, g=g: (b_, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, blk_q, dv),
                               lambda b_, h, i, j: (b_, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q,), jnp.float32),
            pltpu.VMEM((blk_q,), jnp.float32),
            pltpu.VMEM((blk_q, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
