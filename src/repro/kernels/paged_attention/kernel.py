"""Pallas TPU paged attention: page-table-indirected split-K.

The kernel shares the structure of ``kernels/decode_attention`` (online-
softmax stats carried in VMEM scratch along a sequential grid axis) — but
the KV cache is *paged*: keys/values live in a pooled
``(num_blocks, hkv, blk, d)`` array shared by all sequences, and each
sequence owns an int32 page table naming its blocks in position order.
Head-major pages make each grid step's K/V tile one ``(blk, d)`` slab whose
last two dims equal the pool's, which Mosaic's tiling rule accepts for
every ``hkv`` (a ``(1, d)`` slice over an ``(hkv, d)`` tail is refused
whenever hkv > 1).

``paged_prefill_attention_bcd`` is chunked prefill (Sarathi): a ``(C, d)``
query tile per (batch, head) with a ``(C, blk)`` causal mask against each
page, per-row ``cache_len`` offsets and ragged ``valid`` widths. Decode
rows are its C == 1 special case, which is what lets the engine fuse
prefill chunks and decode tokens into ONE jitted megastep. C is not baked
into the program logic — the mask and page walk are driven entirely by the
per-row scalars — so the engine's token-budget packer can instantiate the
same kernel at any width from its bounded pow2 bucket set ({1, 8, 16, ...,
budget}); each bucket is one traced shape, and rows of different real
widths share one dispatch.

Query head ``h`` reads kv head ``h % hkv`` (g-major), the pairing of the
model's full path under ``gqa_mode="tiled"`` and of the TP head
permutation in ``distributed/sharding.py``.

Both the per-sequence valid lengths and the page tables arrive via scalar
prefetch, so the BlockSpec index maps can compute each grid step's HBM block
address *before* the body runs: step (b, h, j) DMAs pool block
``page_table[b, j]`` — a hardware-paced gather, no materialised contiguous
copy of the cache. Pages fully beyond ``lens[b]`` are skipped with
``@pl.when`` so decode cost stays O(kv_len) per sequence, and the partial
last page is masked inside the online softmax. ``interpret=True`` runs the
same kernel on CPU for tests; ``paged_prefill_attention_contig`` runs the
same chunked-prefill program over a pre-gathered contiguous view, which is
the bitwise oracle the parity tests pin the page walk against.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
SUBLANE = 8       # f32 sublane width: minimum chunk tile along the q axis
# both matmuls run on f32 operands at full f32 precision, on the chip as in
# interpret mode: the default TPU precision would round them to one bf16
# pass, a lower precision than the f32 online softmax the kernel computes
_F32 = jax.lax.Precision.HIGHEST


def _prefill_kernel(lens_ref, off_ref, pt_ref, q_ref, k_ref, v_ref, o_ref,
                    m_scr, l_scr, acc_scr, *, scale: float, blk: int,
                    npages: int, C: int):
    """Chunked-prefill body: a (C, d) query tile per (batch, head) walks the
    sequence's pages with a (C, blk) causal mask per page. Decode is the
    C == 1 special case, so one kernel serves the whole megastep."""
    bi = pl.program_id(0)
    pi = pl.program_id(2)
    off = off_ref[bi]                               # tokens cached pre-chunk
    # clamp so an inactive row (kv_len 0) still attends one (null) position
    # instead of producing a 0/0 NaN that would poison later pool reads
    kv_len = jnp.maximum(lens_ref[bi], 1)

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(pi * blk < kv_len)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)           # (C, d)
        k = k_ref[0, 0].astype(jnp.float32)           # (blk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=_F32,
                                preferred_element_type=jnp.float32) * scale
        kpos = pi * blk + jax.lax.broadcasted_iota(jnp.int32, (C, blk), 1)
        qpos = off + jax.lax.broadcasted_iota(jnp.int32, (C, blk), 0)
        s = jnp.where((kpos <= qpos) & (kpos < kv_len), s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1)
        v = v_ref[0, 0].astype(jnp.float32)           # (blk, dv)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), precision=_F32,
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(pi == npages - 1)
    def _fini():
        denom = jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def _prefill_call(q, k_src, v_src, cache_lens, valids, page_tables, *,
                  scale, blk: int, k_map, v_map, interpret: bool):
    """Shared scaffolding for the chunked-prefill kernel and its gathered-
    view twin: everything except the k/v index maps lives HERE, so the two
    traced programs are structurally guaranteed to be 'the same except the
    indirection' — which is what makes their bit-for-bit parity a test of
    the page walk rather than of float associativity."""
    b, C, hq, d = q.shape
    dv = v_src.shape[-1]
    npages = page_tables.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    kv_lens = jnp.asarray(cache_lens, jnp.int32) + jnp.asarray(valids,
                                                               jnp.int32)
    # pad the chunk axis to the f32 sublane width: narrower tiles would be
    # padded by the TPU tiling anyway, and a fixed sublane-aligned width is
    # what keeps interpret-mode runs reproducible for C == 1 (decode rows)
    # — sub-tile shapes take different reduction paths
    want = -(-C // SUBLANE) * SUBLANE
    if want != C:
        q = jnp.pad(q, ((0, 0), (0, want - C), (0, 0), (0, 0)))
    q4 = q.transpose(0, 2, 1, 3)
    kern = functools.partial(_prefill_kernel, scale=scale, blk=blk,
                             npages=npages, C=want)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, hq, npages),
            in_specs=[
                pl.BlockSpec((1, 1, want, d),
                             lambda b_, h, j, lens_, off_, pt: (b_, h, 0, 0)),
                pl.BlockSpec((1, 1, blk, d), k_map),
                pl.BlockSpec((1, 1, blk, dv), v_map),
            ],
            out_specs=pl.BlockSpec((1, 1, want, dv),
                                   lambda b_, h, j, lens_, off_, pt:
                                   (b_, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((want,), jnp.float32),
                pltpu.VMEM((want,), jnp.float32),
                pltpu.VMEM((want, dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, want, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(kv_lens.reshape(b), jnp.asarray(cache_lens, jnp.int32).reshape(b),
      jnp.asarray(page_tables, jnp.int32), q4, k_src, v_src)
    return out.transpose(0, 2, 1, 3)[:, :C]


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_prefill_attention_bcd(q, k_pool, v_pool, cache_lens, valids,
                                page_tables, *, scale=None,
                                interpret: bool = False):
    """Chunked-prefill paged attention over a batch of mixed-width rows.

    q: (b, C, hq, d) — row ``i`` holds a chunk of ``valids[i]`` real query
    tokens at absolute positions ``cache_lens[i] + [0, C)`` (decode rows are
    C-padded width-1 chunks); k_pool: (nb, hkv, blk, d); v_pool:
    (nb, hkv, blk, dv); cache_lens/valids: (b,) int32; page_tables:
    (b, npages) int32 block ids in position order (entries beyond the live
    length must be valid ids, e.g. the null block 0). Each row attends
    causally within its chunk and fully over its already-resident pages;
    q head ``h`` reads kv head ``h % hkv``. Rows/positions beyond
    ``valids`` produce garbage the caller discards. Returns (b, C, hq, dv)."""
    hkv, blk = k_pool.shape[1], k_pool.shape[2]
    return _prefill_call(
        q, k_pool, v_pool, cache_lens, valids, page_tables,
        scale=scale, blk=blk, interpret=interpret,
        k_map=lambda b_, h, j, lens_, off_, pt: (pt[b_, j], h % hkv, 0, 0),
        v_map=lambda b_, h, j, lens_, off_, pt: (pt[b_, j], h % hkv, 0, 0))


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_prefill_attention_contig(q, k_contig, v_contig, cache_lens, valids,
                                   page_tables, *, scale=None,
                                   interpret: bool = False):
    """Gathered-view twin of ``paged_prefill_attention_bcd``: the SAME kernel
    body over a contiguous per-sequence (b, hkv, npages*blk, d) view (e.g.
    ``ref.gather_pages`` with its head axis moved ahead of positions), with
    plain sliced index maps instead of the page-table walk. Because the two
    traced programs share ``_prefill_call`` and differ only in the k/v index
    maps, an interpret-mode run must match the paged kernel **bit for bit**
    — this is the oracle the parity CI pins the page-table scalar-prefetch
    machinery against (the quadratic jnp oracle in ``ref`` checks the math
    itself, at fp32 tolerance)."""
    hkv = k_contig.shape[1]
    blk = k_contig.shape[2] // page_tables.shape[1]
    return _prefill_call(
        q, k_contig, v_contig, cache_lens, valids, page_tables,
        scale=scale, blk=blk, interpret=interpret,
        k_map=lambda b_, h, j, lens_, off_, pt: (b_, h % hkv, j, 0),
        v_map=lambda b_, h, j, lens_, off_, pt: (b_, h % hkv, j, 0))
