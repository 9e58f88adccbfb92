"""Pallas TPU decode attention: flash-decoding-style sequential split-K.

One query token per (batch, head); the KV cache streams through VMEM in
blk_k tiles along the sequential grid axis while online-softmax stats carry
in scratch. The dynamic valid length (kv_len) arrives via scalar prefetch
so tiles fully beyond it are skipped (@pl.when) — decode cost is
O(kv_len), not O(cache_size).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, blk_k: int, nk: int):
    ki = pl.program_id(2)
    kv_len = len_ref[0]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(ki * blk_k < kv_len)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)           # (1, d)
        k = k_ref[0, 0].astype(jnp.float32)           # (blk_k, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = ki * blk_k + jax.lax.broadcasted_iota(jnp.int32, (1, blk_k), 1)
        s = jnp.where(kpos < kv_len, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1)
        v = v_ref[0, 0].astype(jnp.float32)           # (blk_k, dv)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _fini():
        denom = jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "blk_k", "interpret"))
def decode_attention_bhd(q, k, v, kv_len, *, scale=None, blk_k: int = 512,
                         interpret: bool = False):
    """q: (b, hq, d); k: (b, hkv, S, d); v: (b, hkv, S, dv); kv_len scalar."""
    b, hq, d = q.shape
    hkv, s, dv = k.shape[1], k.shape[2], v.shape[-1]
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    blk_k = min(blk_k, s)
    assert s % blk_k == 0
    nk = s // blk_k
    q4 = q.reshape(b, hq, 1, d)
    kern = functools.partial(_kernel, scale=scale, blk_k=blk_k, nk=nk)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hq, nk),
            in_specs=[
                pl.BlockSpec((1, 1, 1, d), lambda b_, h, j, sref: (b_, h, 0, 0)),
                pl.BlockSpec((1, 1, blk_k, d),
                             lambda b_, h, j, sref, g=g: (b_, h // g, j, 0)),
                pl.BlockSpec((1, 1, blk_k, dv),
                             lambda b_, h, j, sref, g=g: (b_, h // g, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, 1, dv),
                                   lambda b_, h, j, sref: (b_, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((1,), jnp.float32),
                pltpu.VMEM((1,), jnp.float32),
                pltpu.VMEM((1, dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, 1, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(kv_len, jnp.int32).reshape(1), q4, k, v)
    return out.reshape(b, hq, dv)
