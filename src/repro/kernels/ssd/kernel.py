"""Pallas TPU kernel for the Mamba-2 SSD chunked scan.

TPU adaptation of the paper's GPU algorithm (arXiv:2405.21060): the
warp-level parallel scan becomes per-chunk dense (L x L) matmuls on the MXU
plus a cheap inter-chunk state recurrence carried in VMEM scratch across the
sequential chunk axis of the grid. Per (batch, head) program:

  intra:  y_diag = (tril(C B^T) * decay * dt) @ x          — two MXU matmuls
  inter:  y_off  = exp(cum) * (C @ state)                  — one MXU matmul
  carry:  state  = exp(cum_L) * state + (B * w)^T @ x      — one MXU matmul

Grid: (b, h, nc), dimension_semantics (parallel, parallel, arbitrary).
A (per-head decay rates) rides in via scalar prefetch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, state_ref, st_scr, *,
            chunk: int, nc: int):
    hi = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        st_scr[...] = jnp.zeros_like(st_scr)

    a = a_ref[hi]                                   # scalar decay rate (<0)
    x = x_ref[0, 0].astype(jnp.float32)             # (L, p)
    dt = dt_ref[0, 0].astype(jnp.float32)           # (L,)
    bmat = b_ref[0, 0].astype(jnp.float32)          # (L, n)
    cmat = c_ref[0, 0].astype(jnp.float32)          # (L, n)

    da = dt * a                                     # (L,)
    cum = jnp.cumsum(da)                            # inclusive
    seg = cum[:, None] - cum[None, :]               # (L, L)
    tril = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.exp(jnp.where(tril, seg, -1e30))

    cb = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (L, L)
    m = cb * decay * dt[None, :]
    y = jax.lax.dot_general(m, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (L, p)

    # inter-chunk: contribution of the carried state
    state = st_scr[...]                             # (n, p)
    y_off = jax.lax.dot_general(cmat, state, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y = y + jnp.exp(cum)[:, None] * y_off
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: S' = exp(cum_L) * S + B^T @ (w * x)
    w = jnp.exp(cum[-1] - cum) * dt                 # (L,)
    upd = jax.lax.dot_general(bmat, w[:, None] * x,
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (n, p)
    st_scr[...] = jnp.exp(cum[-1]) * state + upd

    @pl.when(ci == nc - 1)
    def _fini():
        state_ref[0, 0] = st_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_bhsd(x, dt, A, B, C, *, chunk: int = 256, interpret: bool = False):
    """x: (b, h, s, p); dt: (b, h, s); A: (h,); B, C: (b, g, s, n) with the
    group dim pre-broadcast is NOT required — index_map picks h // hg.
    Returns (y (b, h, s, p), final_state (b, h, n, p))."""
    b, h, s, p = x.shape
    g, n = B.shape[1], B.shape[3]
    hg = h // g
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk
    kern = functools.partial(_kernel, chunk=chunk, nc=nc)
    y, state = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h, nc),
            in_specs=[
                pl.BlockSpec((1, 1, chunk, p),
                             lambda b_, h_, c, aref: (b_, h_, c, 0)),
                pl.BlockSpec((1, 1, chunk),
                             lambda b_, h_, c, aref: (b_, h_, c)),
                pl.BlockSpec((1, 1, chunk, n),
                             lambda b_, h_, c, aref, hg=hg: (b_, h_ // hg, c, 0)),
                pl.BlockSpec((1, 1, chunk, n),
                             lambda b_, h_, c, aref, hg=hg: (b_, h_ // hg, c, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, chunk, p),
                             lambda b_, h_, c, aref: (b_, h_, c, 0)),
                pl.BlockSpec((1, 1, n, p),
                             lambda b_, h_, c, aref: (b_, h_, 0, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, n, p), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(A.astype(jnp.float32), x, dt, B, C)
    return y, state
