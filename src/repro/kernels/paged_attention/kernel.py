"""Pallas TPU paged attention: GQA-group query tiles walking multi-page KV
blocks up to the live length.

The KV cache is *paged*: keys/values live in a pooled ``(L, num_blocks,
hkv, blk, d)`` stack shared by all sequences, and each sequence owns an
int32 page table naming its blocks in position order (the same ids in
every layer). A page of every kv head, ``(hkv, blk, d)``, is one
contiguous slab of the pool, and the kernel copies it from
``(layer, page)``: the layer arrives as a scalar-prefetch operand, so the
megastep hands the kernel the whole stack it carries through its layer
scan, never a layer's slice. ``paged_prefill_attention_bcd`` takes one
layer's pool as a stack of one.

``paged_prefill_attention_stacked`` is chunked prefill (Sarathi): row ``b``
holds ``valids[b]`` real query tokens at absolute positions
``cache_lens[b] + [0, C)``. Decode rows are its C == 1 special case, which
is what lets the engine fuse prefill chunks and decode tokens into ONE
jitted megastep at any width of its bounded bucket set.

The grid is ``(row, query tile)``; one grid step does the work of every
kv head's GQA group for ``TC`` chunk positions over the row's live pages:

- **Head-group query tiles.** Query head ``h`` reads kv head ``h % hkv``
  (g-major: the model's ``gqa_mode="tiled"`` pairing and the TP head
  permutation's), so kv head ``j`` serves heads ``j, j + hkv, ...``. The
  wrapper folds those ``g = hq / hkv`` heads and the tile's ``TC`` chunk
  positions into one row axis per kv head, ``row = head_in_group * TC +
  position_in_tile``: a decode tile is the ``g`` real rows of each group,
  not one real row in each of ``hq`` padded tiles.
- **Multi-page KV blocks.** The pools stay in HBM (``pl.ANY``); the body
  gathers ``P`` pages per block, one async copy per page (all kv heads of
  it, so each page is read once per (row, query tile)), into a
  double-buffered ``(2, P, hkv, blk, d)`` VMEM buffer: block ``i + 1``
  loads while block ``i`` computes. Pages are scattered in the pool, which
  a BlockSpec (contiguous blocks only) cannot fetch.
- **A walk bounded by the live length.** A ``fori_loop`` walks only the
  blocks below ``min(kv_len, the tile's last causal position + 1)`` and
  copies only the pages below that bound; query tiles wholly past
  ``valids[b]`` (other than the first) do no copy and no math and write
  zeros, which the caller discards.

The tile sizes come from the input shapes alone (``kernel_plan``): one
algorithm, parameters chosen so that the buffers stay inside Mosaic's
scoped VMEM on v5e.

Numerics: the online softmax and its accumulators are f32. With bf16 ``q``
and pool (the chip), ``QK^T`` takes the bf16 operands straight to the MXU
with f32 accumulation (the products are exact in f32); otherwise both run
at f32 ``HIGHEST``. ``PV`` multiplies f32 probabilities by the f32 upcast
of V at ``HIGHEST``. Keys past the bound are masked; V positions whose page
was not copied are zeroed, so a stale VMEM slot never reaches the output.

``interpret=True`` runs the same kernel on CPU for tests;
``paged_prefill_attention_contig`` runs the same body over a pre-gathered
contiguous view — the two differ only in the address each page copy reads
— which is the bitwise oracle the parity tests pin the page walk against.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# the default precision on the chip would round f32 operands to one bf16
# pass, a lower precision than the f32 online softmax the kernel computes
_F32 = jax.lax.Precision.HIGHEST

# Tile limits (kernel_plan): query rows of one grid step over all kv heads,
# key positions per block, and elements of one (rows, keys) f32 score tile.
# Mosaic's default scoped VMEM on TPU v5e is 16 MiB; the plan's estimate
# must stay under it.
ROWS_PER_TILE = 1024
KEYS_PER_BLOCK = 512
SCORE_ELEMS = 1 << 18
SCOPED_VMEM_BYTES = 16 * 2**20


@dataclasses.dataclass(frozen=True)
class KernelPlan:
    """The kernel's tiling at one set of input shapes (``kernel_plan``)."""
    tile_positions: int     # chunk positions per query tile (TC)
    rows: int               # query rows per kv head: g * TC, sublane-padded
    query_tiles: int        # query tiles per row: ceil(C / TC)
    pages_per_block: int    # pages gathered per KV block (P)
    vmem_bytes: int         # estimated VMEM of one grid step


def _pow2_floor(n: int) -> int:
    return 1 << (max(int(n), 1).bit_length() - 1)


def kernel_plan(*, C: int, hq: int, hkv: int, blk: int, d: int, dv: int,
                npages: int, q_dtype, kv_dtype) -> KernelPlan:
    """The tiling for queries (b, C, hq, d) over a (nb, hkv, blk, d|dv) pool
    with ``npages`` table entries per row: a pure function of the shapes.

    A query tile holds every query head for ``TC`` chunk positions (a power
    of two, at most ``ROWS_PER_TILE / hq`` and at most C); each kv head's
    ``g * TC`` rows are padded to the sublane tile of ``q``'s dtype. A KV
    block holds ``P`` pages: as many as fill ``KEYS_PER_BLOCK`` positions,
    fewer where one head's (rows, keys) score tile would pass
    ``SCORE_ELEMS``, never more than the table."""
    g = hq // hkv
    q_size = jnp.dtype(q_dtype).itemsize
    kv_size = jnp.dtype(kv_dtype).itemsize
    sublane = 32 // q_size                       # 8 for f32, 16 for bf16
    tc = min(C, _pow2_floor(ROWS_PER_TILE // hq))
    rows = -(-g * tc // sublane) * sublane
    keys = min(KEYS_PER_BLOCK, _pow2_floor(SCORE_ELEMS // rows))
    pages = max(1, min(npages, keys // blk))
    kb = pages * blk
    vmem = (2 * hkv * rows * (d + dv) * q_size        # q in, o out, 2 bufs
            + 2 * hkv * kb * (d + dv) * kv_size       # K/V blocks, 2 slots
            + hkv * rows * dv * 4                     # acc
            + 2 * max(hkv, 8) * rows * 4              # m, l
            + 4 * rows * kb * 4                       # scores, probs, masks
            + kb * (d + dv) * 4)                      # f32 K, V of a head
    return KernelPlan(tile_positions=tc, rows=rows, query_tiles=-(-C // tc),
                      pages_per_block=pages, vmem_bytes=vmem)


def _prefill_kernel(lens_ref, off_ref, val_ref, pt_ref, layer_ref, q_ref,
                    k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, m_scr, l_scr,
                    acc_scr, *, page_at, scale: float, blk: int, pages: int,
                    tc: int, npages: int):
    """One grid step: the (hkv, rows, d) query tile of one (row, query tile)
    walks the row's live KV blocks with an online softmax per kv head."""
    b, t = pl.program_id(0), pl.program_id(1)
    layer = layer_ref[0]
    kb = pages * blk
    hkv, rows, d = q_ref.shape
    dv = v_buf.shape[-1]
    off = off_ref[b]                                # tokens cached pre-chunk
    # clamp so an inactive row (kv_len 0) still attends one (null) position
    # instead of producing a 0/0 NaN that would poison later pool reads
    kv_len = jnp.maximum(lens_ref[b], 1)
    # every key this tile can see lies below n_keys: the live length, the
    # tile's last causal position, and the table
    n_keys = jnp.minimum(jnp.minimum(kv_len, off + (t + 1) * tc),
                         npages * blk)
    live_pages = (n_keys + blk - 1) // blk
    n_blocks = (live_pages + pages - 1) // pages

    def copy_pages(i, slot, src, buf, sem, start: bool):
        n = jnp.minimum(pages, live_pages - i * pages)

        def one(p, c):
            cp = pltpu.make_async_copy(
                page_at(src, layer, pt_ref, b, i * pages + p),
                buf.at[slot, p], sems.at[sem, slot])
            cp.start() if start else cp.wait()
            return c

        if start:
            jax.lax.fori_loop(0, n, one, 0)
            return

        # the page copies of a block all signal one semaphore, so a whole
        # block is awaited at once; a partial (last) block page by page
        @pl.when(n == pages)
        def _whole():
            pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                                  sems.at[sem, slot]).wait()

        @pl.when(n < pages)
        def _partial():
            jax.lax.fori_loop(0, n, one, 0)

    def fetch(i, slot):
        copy_pages(i, slot, k_hbm, k_buf, 0, True)
        copy_pages(i, slot, v_hbm, v_buf, 1, True)

    def block(i, c):
        slot = i % 2

        @pl.when(i + 1 < n_blocks)
        def _prefetch():
            fetch(i + 1, 1 - slot)

        kpos = i * kb + jax.lax.broadcasted_iota(jnp.int32, (rows, kb), 1)
        qpos = off + t * tc + jax.lax.rem(
            jax.lax.broadcasted_iota(jnp.int32, (rows, kb), 0), tc)
        causal = (kpos <= qpos) & (kpos < kv_len)
        # positions past n_keys are masked out of p; their pages may not
        # have been copied, so V there is zeroed rather than stale VMEM
        v_live = i * kb + jax.lax.broadcasted_iota(jnp.int32, (kb, dv),
                                                   0) < n_keys
        copy_pages(i, slot, k_hbm, k_buf, 0, False)
        copy_pages(i, slot, v_hbm, v_buf, 1, False)
        for j in range(hkv):
            q = q_ref[j]
            k = k_buf[slot, :, j].reshape(kb, d)
            if q.dtype == k.dtype == jnp.bfloat16:
                s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)
            else:
                s = jax.lax.dot_general(
                    q.astype(jnp.float32), k.astype(jnp.float32),
                    (((1,), (1,)), ((), ())), precision=_F32,
                    preferred_element_type=jnp.float32)
            s = jnp.where(causal, s * scale, NEG_INF)
            m_prev = m_scr[j]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
            p = jnp.exp(s - m_new[:, None])
            corr = jnp.exp(m_prev - m_new)
            l_scr[j] = l_scr[j] * corr + jnp.sum(p, axis=1)
            m_scr[j] = m_new
            v = jnp.where(v_live,
                          v_buf[slot, :, j].reshape(kb, dv).astype(
                              jnp.float32), 0.0)
            acc_scr[j] = acc_scr[j] * corr[:, None] + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())), precision=_F32,
                preferred_element_type=jnp.float32)
        return c

    @pl.when((t == 0) | (t * tc < val_ref[b]))
    def _walk():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        fetch(0, 0)
        jax.lax.fori_loop(0, n_blocks, block, 0)
        denom = jnp.maximum(l_scr[...], 1e-30)[:, :, None]
        o_ref[...] = (acc_scr[...] / denom).astype(o_ref.dtype)

    @pl.when((t > 0) & (t * tc >= val_ref[b]))
    def _past_valid():
        o_ref[...] = jnp.zeros_like(o_ref)


def _prefill_call(q, k_src, v_src, layer, cache_lens, valids, page_tables,
                  *, scale, hkv: int, blk: int, page_at, interpret: bool):
    """Shared scaffolding for the chunked-prefill kernel and its gathered-
    view twin: everything except ``page_at`` (the HBM address a page copy
    reads) lives HERE, so the two traced programs are structurally 'the
    same except the indirection' — which is what makes their bit-for-bit
    parity a test of the page walk rather than of float associativity.

    For the paged kernel ``k_src``/``v_src`` are the whole layer stack
    ``(L, nb, hkv, blk, d|dv)`` that the megastep carries through its
    layer scan and writes in place; ``layer`` (a scalar-prefetch operand)
    picks the layer, so no layer's slice is ever cut out of the pool."""
    b, C, hq, d = q.shape
    dv = v_src.shape[-1]
    npages = page_tables.shape[1]
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    plan = kernel_plan(C=C, hq=hq, hkv=hkv, blk=blk, d=d, dv=dv,
                       npages=npages, q_dtype=q.dtype, kv_dtype=k_src.dtype)
    tc, rows, nq = plan.tile_positions, plan.rows, plan.query_tiles
    cache_lens = jnp.asarray(cache_lens, jnp.int32).reshape(b)
    valids = jnp.asarray(valids, jnp.int32).reshape(b)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    # (b, C, hq, d) -> (b, nq, hkv, rows, d): q head h = gi * hkv + j goes
    # to kv head j's rows of its tile at gi * tc + (position mod tc)
    q = jnp.pad(q, ((0, 0), (0, nq * tc - C), (0, 0), (0, 0)))
    q = q.reshape(b, nq, tc, g, hkv, d).transpose(0, 1, 4, 3, 2, 5)
    q = jnp.pad(q.reshape(b, nq, hkv, g * tc, d),
                ((0, 0), (0, 0), (0, 0), (0, rows - g * tc), (0, 0)))
    kern = functools.partial(_prefill_kernel, page_at=page_at, scale=scale,
                             blk=blk, pages=plan.pages_per_block, tc=tc,
                             npages=npages)
    tile = lambda b_, t, *_: (b_, t, 0, 0, 0)   # noqa: E731
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(b, nq),
            in_specs=[
                pl.BlockSpec((None, None, hkv, rows, d), tile),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, None, hkv, rows, dv), tile),
            scratch_shapes=[
                pltpu.VMEM((2, plan.pages_per_block, hkv, blk, d),
                           k_src.dtype),
                pltpu.VMEM((2, plan.pages_per_block, hkv, blk, dv),
                           v_src.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((hkv, rows), jnp.float32),
                pltpu.VMEM((hkv, rows), jnp.float32),
                pltpu.VMEM((hkv, rows, dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, nq, hkv, rows, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(cache_lens + valids, cache_lens, valids,
      jnp.asarray(page_tables, jnp.int32), layer, q, k_src, v_src)
    out = out[:, :, :, :g * tc].reshape(b, nq, hkv, g, tc, dv)
    out = out.transpose(0, 1, 4, 3, 2, 5).reshape(b, nq * tc, hq, dv)
    return out[:, :C]


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_prefill_attention_stacked(q, k_pools, v_pools, layer, cache_lens,
                                    valids, page_tables, *, scale=None,
                                    interpret: bool = False):
    """Chunked-prefill paged attention over a batch of mixed-width rows,
    reading layer ``layer`` of the stacked pools.

    q: (b, C, hq, d) — row ``i`` holds a chunk of ``valids[i]`` real query
    tokens at absolute positions ``cache_lens[i] + [0, C)`` (decode rows are
    C-padded width-1 chunks); k_pools: (L, nb, hkv, blk, d); v_pools:
    (L, nb, hkv, blk, dv); layer: scalar int32; cache_lens/valids: (b,)
    int32; page_tables: (b, npages) int32 block ids in position order. A
    page copy reads ``pool[layer, page_tables[row, page]]`` straight from
    HBM. Each row attends causally within its chunk and fully over its
    already-resident pages; q head ``h`` reads kv head ``h % hkv``.
    Positions beyond ``valids`` produce garbage the caller discards (zeros
    in query tiles wholly past it). Returns (b, C, hq, dv)."""
    hkv, blk = k_pools.shape[2], k_pools.shape[3]
    return _prefill_call(
        q, k_pools, v_pools, layer, cache_lens, valids, page_tables,
        scale=scale, hkv=hkv, blk=blk, interpret=interpret,
        page_at=lambda src, lyr, pt, b_, page: src.at[lyr, pt[b_, page]])


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_prefill_attention_bcd(q, k_pool, v_pool, cache_lens, valids,
                                page_tables, *, scale=None,
                                interpret: bool = False):
    """``paged_prefill_attention_stacked`` over one layer's pool
    (nb, hkv, blk, d|dv): the pool as a stack of one, at layer 0."""
    return paged_prefill_attention_stacked(
        q, k_pool[None], v_pool[None], 0, cache_lens, valids, page_tables,
        scale=scale, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_prefill_attention_contig(q, k_contig, v_contig, cache_lens, valids,
                                   page_tables, *, scale=None,
                                   interpret: bool = False):
    """Gathered-view twin of ``paged_prefill_attention_bcd``: the SAME kernel
    body over a contiguous per-sequence (b, hkv, npages*blk, d) view (e.g.
    ``ref.gather_pages`` with its head axis moved ahead of positions), whose
    page copies read page ``p`` of row ``b`` in place instead of through
    the page table. Because the two traced programs share ``_prefill_call``
    and differ only in that address, an interpret-mode run must match the
    paged kernel **bit for bit** — this is the oracle the parity CI pins the
    page-table walk against (the quadratic jnp oracle in ``ref`` checks the
    math itself, at fp32 tolerance)."""
    b, hkv, positions = k_contig.shape[:3]
    npages = page_tables.shape[1]
    blk = positions // npages

    def pages(x):       # (b, hkv, npages*blk, d) -> (b, npages, hkv, blk, d)
        return x.reshape(b, hkv, npages, blk, x.shape[-1]).swapaxes(1, 2)

    return _prefill_call(
        q, pages(k_contig), pages(v_contig), 0, cache_lens, valids,
        page_tables, scale=scale, hkv=hkv, blk=blk, interpret=interpret,
        page_at=lambda src, lyr, pt, b_, page: src.at[b_, page])
