"""Pure-jnp oracles for paged attention.

``paged_attention_ref`` (decode) and ``paged_prefill_attention_ref``
(chunked prefill / megastep rows) gather each sequence's pages in table
order — materialising the contiguous view the kernels avoid — then run a
masked softmax with per-sequence offsets and valid lengths.
``paged_prefill_attention_ref`` is the path the paged engine runs on a
CPU device (the kernel runs on a TPU). Like the
kernels, they take the chunk axis C from the input shape and the ragged
per-row real widths from ``valids`` — the oracles stay in lockstep with
the kernels across every token-budget trace bucket, which is what the
ragged-width parity tests sweep.

``paged_prefill_attention_gathered_oracle`` runs the kernel's own online-
softmax program over the jnp-gathered contiguous view (same traced ops,
no page-table indirection), so interpret-mode kernel runs can be asserted
bit-identical against it — isolating page-walk bugs from float
associativity."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def gather_pages(pool, page_tables):
    """pool: (nb, hkv, blk, d); page_tables: (b, npages) ->
    (b, npages*blk, hkv, d) contiguous per-sequence view (position order)."""
    b, npages = page_tables.shape
    hkv, blk, d = pool.shape[1:]
    return pool[page_tables].transpose(0, 1, 3, 2, 4).reshape(
        b, npages * blk, hkv, d)


def paged_attention_ref(q, k_pool, v_pool, lens, page_tables, *, scale=None):
    """Decode oracle. q: (b, hq, d); pools: (nb, hkv, blk, d|dv); lens: (b,)
    int32; page_tables: (b, npages) int32. Q head ``h`` reads kv head
    ``h % hkv`` (g-major, the kernel's pairing). Returns (b, hq, dv)."""
    b, hq, d = q.shape
    hkv, dv = k_pool.shape[1], v_pool.shape[-1]
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    k = gather_pages(k_pool, page_tables).transpose(0, 2, 1, 3)  # (b,hkv,S,d)
    v = gather_pages(v_pool, page_tables).transpose(0, 2, 1, 3)
    s = k.shape[2]
    qg = q.reshape(b, g, hkv, d)
    sc = jnp.einsum("bghd,bhkd->bghk", qg.astype(jnp.float32),
                    k.astype(jnp.float32)) * scale
    mask = jnp.arange(s)[None] < jnp.asarray(lens)[:, None]     # (b, S)
    sc = jnp.where(mask[:, None, None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bghk,bhkd->bghd", p.astype(jnp.float32),
                   v.astype(jnp.float32))
    return o.reshape(b, hq, dv).astype(q.dtype)


def _mixed_mask(C, S, cache_lens, valids):
    """(b, C, S) bool mask for mixed prefill/decode rows: position ``i`` of
    row ``b`` attends causally up to ``cache_lens[b] + i`` and never past the
    row's written length, clamped to >= 1 so inactive rows (kv_len 0) keep a
    single (null, discarded) key instead of an empty softmax."""
    cache_lens = jnp.asarray(cache_lens, jnp.int32)
    valids = jnp.asarray(valids, jnp.int32)
    kpos = jnp.arange(S)[None, None, :]
    qpos = cache_lens[:, None, None] + jnp.arange(C)[None, :, None]
    kv_len = jnp.maximum(cache_lens + valids, 1)[:, None, None]
    return (kpos <= qpos) & (kpos < kv_len)


def paged_prefill_attention_ref(q, k_pool, v_pool, cache_lens, valids,
                                page_tables, *, scale=None,
                                pairing: str = "g_major"):
    """Batched gather-based oracle for chunked-prefill paged attention.

    q: (b, C, hq, d); pools: (nb, hkv, blk, d|dv); cache_lens/valids: (b,)
    int32; page_tables: (b, npages) int32. Same row semantics as the kernel
    (see ``kernel.paged_prefill_attention_bcd``). ``pairing`` selects which
    kv head q-head h reads — "g_major" (h % hkv: the kernel's pairing, and
    what full paths running gqa_mode="tiled" realize) or "kv_major"
    (h // g, gqa_mode="grouped").
    Returns (b, C, hq, dv). Safe-softmax throughout: fully-padded rows
    produce finite garbage, never NaN, so discarded rows cannot poison the
    pool on the next scatter."""
    b, C, hq, d = q.shape
    hkv, dv = k_pool.shape[1], v_pool.shape[-1]
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    k = gather_pages(k_pool, page_tables)            # (b, S, hkv, d)
    v = gather_pages(v_pool, page_tables)
    S = k.shape[1]
    if pairing == "g_major":
        qg = q.reshape(b, C, g, hkv, d).swapaxes(2, 3)
    else:
        qg = q.reshape(b, C, hkv, g, d)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = _mixed_mask(C, S, cache_lens, valids)     # (b, C, S)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(jnp.float32),
                   v.astype(jnp.float32))
    if pairing == "g_major":
        o = o.swapaxes(2, 3)
    return o.reshape(b, C, hq, dv).astype(q.dtype)


def paged_prefill_attention_gathered_oracle(q, k_pool, v_pool, cache_lens,
                                            valids, page_tables, *,
                                            scale=None):
    """Bitwise oracle for the chunked-prefill kernel: jnp-gather each
    sequence's pages into the contiguous view the kernel's page walk avoids,
    then run the SAME online-softmax program over it (via
    ``kernel.paged_prefill_attention_contig``, interpret mode). The two
    traced programs are identical except for the page-table indirection, so
    the paged kernel must match this bit for bit — any diff is a page-walk
    bug, never float associativity. (The quadratic ``..._ref`` above is the
    independent check of the math, at fp32 tolerance.)"""
    from repro.kernels.paged_attention.kernel import \
        paged_prefill_attention_contig
    kg = gather_pages(k_pool, page_tables).transpose(0, 2, 1, 3)
    vg = gather_pages(v_pool, page_tables).transpose(0, 2, 1, 3)
    return paged_prefill_attention_contig(q, kg, vg, cache_lens, valids,
                                          page_tables, scale=scale,
                                          interpret=True)
