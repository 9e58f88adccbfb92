"""PagedKVCache: the pooled physical KV store + page-table bookkeeping.

One ``(L, num_blocks, hkv, blk, hd)`` array per tensor (K and V) backs every
sequence; block ids are shared across layers, so a single page table per
sequence maps its token positions for the whole stack. This is the layer
that owns the bytes: the BlockAllocator decides *which* block, this class
moves data — prefill scatter, copy-on-write duplication, and the host<->
device page transfers the swap tier is built on.

Two extras over a plain pool:

  * Bulk writes (``write_prefill``/``scatter``) run under ``jax.jit`` with
    the pool buffers donated, so swap-in and prefill update the pool
    in place instead of re-materialising the full ``(L, num_blocks, ...)``
    arrays outside jit per call. Block-id rows are padded to power-of-two
    widths (padding aimed at the null block) to bound retraces.
  * A prompt-prefix index: full, block-aligned prompt prefixes are hashed
    across sessions, so a new session whose prompt starts with an indexed
    prefix *adopts* those blocks through the existing refcount/COW
    machinery instead of recomputing and rewriting them (vLLM-style
    automatic prefix caching). Dedup counters feed ``kv_stats``.

Under a ``tp`` mesh (DESIGN.md §13) the pools are placed with their KV-head
axis sharded — each device holds the SAME block ids for its own head
slice, so the allocator, page tables and prefix index are completely
mesh-oblivious. Host transfers stay mesh-shape-agnostic: ``gather``
assembles full-``hkv`` pages on the host (a session hibernated at TP=2
wakes at TP=4 unchanged) and ``scatter`` re-shards them on the way in.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed.sharding import kv_pool_pspec
from repro.models import transformer as tr
from repro.serving.paging.allocator import NULL_BLOCK, BlockAllocator, PageTable


@partial(jax.jit, donate_argnums=(0, 1))
def _pool_put(k, v, bids, k_pages, v_pages):
    """Scatter page-shaped updates into donated pools.

    k/v: (L, nb, hkv, blk, hd); bids: (P,) int32 (NULL_BLOCK-padded);
    k_pages/v_pages: (L, P, hkv, blk, hd) with zeros in padding rows — the
    padding writes land in the reserved null block, which exists exactly to
    absorb masked writes."""
    return k.at[:, bids].set(k_pages), v.at[:, bids].set(v_pages)


def _pow2_pad(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


class PagedKVCache:
    """Pooled paged KV storage for the decoder-only GQA family."""

    def __init__(self, cfg: ModelConfig, num_blocks: int, block_size: int,
                 mesh=None, device=None):
        self.cfg = cfg
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.mesh = mesh
        # the pools are built where they live: head-sharded over the mesh
        # (page-shaped updates in _put_pages are placed the same way, so
        # the donated scatter never needs a cross-device reshard), or
        # committed to the engine's device, so later pool writes run there
        # and not on the default device
        if mesh is not None:
            sh = jax.sharding.NamedSharding(mesh, kv_pool_pspec())
            self._page_sharding = sh
        else:
            sh = (jax.sharding.SingleDeviceSharding(device)
                  if device is not None else None)
            self._page_sharding = None
        pools = jax.jit(partial(tr.init_paged_pools, cfg, num_blocks,
                                block_size), out_shardings=sh)()
        self.k: jax.Array = pools["k"]
        self.v: jax.Array = pools["v"]
        self.allocator = BlockAllocator(num_blocks)
        L, _, hkv, blk, hd = self.k.shape
        self.block_bytes = 2 * L * blk * hkv * hd * self.k.dtype.itemsize
        # ---- prompt-prefix dedup index -----------------------------------
        # key = the raw bytes of a block-aligned prompt prefix; value = the
        # block id holding that prefix's *last* block. Entries are dropped
        # the moment their block's refcount reaches zero, so a hit is always
        # a live block.
        self._prefix_index: Dict[bytes, int] = {}
        self._prefix_of: Dict[int, bytes] = {}
        self.prefix_lookups = 0
        self.prefix_hits = 0
        self.prompt_blocks_shared = 0
        self.prompt_blocks_fresh = 0

    # ------------------------------------------------------------- pools
    def pools(self) -> Dict:
        """The pool pytree handed to (and returned by) the jitted paged
        decode step; write the result back via ``set_pools``."""
        return {"k": self.k, "v": self.v}

    def set_pools(self, pools: Dict):
        self.k, self.v = pools["k"], pools["v"]

    # ------------------------------------------------------------- sizes
    def pages_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.block_size)

    @property
    def bytes_in_use(self) -> int:
        return self.allocator.num_used * self.block_bytes

    @property
    def bytes_total(self) -> int:
        return (self.num_blocks - 1) * self.block_bytes

    # ----------------------------------------------------------- tables
    def alloc_table(self, n_tokens: int) -> PageTable:
        blocks = self.allocator.alloc_many(self.pages_for(n_tokens))
        return PageTable(self.block_size, blocks, 0)

    def free_table(self, pt: PageTable):
        for bid in pt.blocks:
            self._release_block(bid)
        pt.blocks = []
        pt.num_tokens = 0

    def fork(self, pt: PageTable) -> PageTable:
        """Share every block with a new sequence (prefix sharing / agent
        fork). O(pages) bookkeeping, zero bytes copied — divergent writes
        trigger copy-on-write in ``ensure_capacity``."""
        for bid in pt.blocks:
            self.allocator.share(bid)
        return PageTable(pt.block_size, list(pt.blocks), pt.num_tokens)

    def _release_block(self, bid: int):
        """Drop one reference; purge the prefix index if the block died."""
        if self.allocator.release(bid):
            key = self._prefix_of.pop(bid, None)
            if key is not None:
                self._prefix_index.pop(key, None)

    # -------------------------------------------------- prefix dedup
    def adopt_prefix(self, tokens) -> List[int]:
        """Longest indexed block-aligned *strict* prefix of ``tokens``:
        returns the block ids (refcounts already bumped) so the caller can
        seed a page table with them and skip recomputing their KV. Capped at
        ``len(tokens) - 1`` positions — the final prompt token is always
        recomputed, because its logits are what seed generation."""
        self.prefix_lookups += 1
        toks = np.ascontiguousarray(np.asarray(tokens, np.int32).reshape(-1))
        shared: List[int] = []
        k = 1
        while k * self.block_size <= len(toks) - 1:
            bid = self._prefix_index.get(toks[: k * self.block_size].tobytes())
            if bid is None:
                break
            self.allocator.share(bid)
            shared.append(bid)
            k += 1
        eligible = max(0, (len(toks) - 1) // self.block_size)
        self.prompt_blocks_shared += len(shared)
        self.prompt_blocks_fresh += eligible - len(shared)
        if shared:
            self.prefix_hits += 1
        return shared

    def prefix_match_blocks(self, tokens) -> int:
        """Side-effect-free probe: how many block-aligned *strict*-prefix
        blocks of ``tokens`` this pool already indexes. No refcount bumps
        and no lookup/hit counter movement — this is placement scoring
        (fleet prefix affinity), not adoption; a later ``adopt_prefix``
        does the real sharing."""
        toks = np.ascontiguousarray(np.asarray(tokens, np.int32).reshape(-1))
        k = 0
        while (k + 1) * self.block_size <= len(toks) - 1:
            key = toks[: (k + 1) * self.block_size].tobytes()
            if key not in self._prefix_index:
                break
            k += 1
        return k

    def register_prefix(self, tokens, pt: PageTable, upto_tokens: int):
        """Index ``pt``'s full blocks whose contents are exactly the first
        ``upto_tokens`` positions of ``tokens`` (prompt-only blocks; call as
        prefill chunks land). Idempotent; first writer wins."""
        toks = np.ascontiguousarray(np.asarray(tokens, np.int32).reshape(-1))
        n = min(int(upto_tokens), len(toks))
        for k in range(1, n // self.block_size + 1):
            bid = pt.blocks[k - 1]
            if bid in self._prefix_of:
                continue
            key = toks[: k * self.block_size].tobytes()
            if key not in self._prefix_index:
                self._prefix_index[key] = bid
                self._prefix_of[bid] = key

    def prefix_stats(self) -> Dict[str, float]:
        shared, fresh = self.prompt_blocks_shared, self.prompt_blocks_fresh
        return {
            "prefix_lookups": self.prefix_lookups,
            "prefix_hits": self.prefix_hits,
            "prefix_hit_rate": self.prefix_hits / max(1, self.prefix_lookups),
            "prefix_blocks_indexed": len(self._prefix_index),
            "blocks_deduped": shared,
            "dedup_ratio": shared / max(1, shared + fresh),
        }

    # ------------------------------------------------------ write paths
    def ensure_capacity(self, pt: PageTable, n_tokens: int):
        """Make the next write (token positions up to ``n_tokens``) safe:
        grow the table block-by-block and copy-on-write a shared tail block
        so appends never mutate another sequence's data."""
        if n_tokens > pt.num_tokens and pt.num_tokens < pt.capacity:
            # the block being appended into must be exclusively owned
            self._unshare(pt, pt.num_tokens // self.block_size)
        while pt.capacity < n_tokens:
            pt.blocks.append(self.allocator.alloc())

    def _unshare(self, pt: PageTable, page_idx: int):
        bid = pt.blocks[page_idx]
        if not self.allocator.is_shared(bid):
            return
        new = self.allocator.alloc()
        self.k = self.k.at[:, new].set(self.k[:, bid])
        self.v = self.v.at[:, new].set(self.v[:, bid])
        self._release_block(bid)
        pt.blocks[page_idx] = new

    def _put_pages(self, bids: np.ndarray, k_pages, v_pages):
        """Jitted, donated bulk page write: pad the page axis to a power of
        two (padding rows -> null block, zero data) and scatter."""
        pages = len(bids)
        width = _pow2_pad(max(pages, 1))
        row = np.full((width,), NULL_BLOCK, np.int32)
        row[:pages] = bids
        if width != pages:
            pad = [(0, 0), (0, width - pages)] + \
                [(0, 0)] * (k_pages.ndim - 2)
            k_pages = jnp.pad(k_pages, pad)
            v_pages = jnp.pad(v_pages, pad)
        k_pages = jnp.asarray(k_pages, self.k.dtype)
        v_pages = jnp.asarray(v_pages, self.v.dtype)
        if self._page_sharding is not None:
            # pages share the pool's (..., hkv, blk, hd) trailing layout, so
            # the same head-sharded spec applies; committing them here
            # keeps the donated scatter a pure per-shard write
            k_pages = jax.device_put(k_pages, self._page_sharding)
            v_pages = jax.device_put(v_pages, self._page_sharding)
        self.k, self.v = _pool_put(
            self.k, self.v, jnp.asarray(row), k_pages, v_pages)

    def write_prefill(self, pt: PageTable, k_pre, v_pre):
        """Scatter prefill KV (L, plen, hkv, hd) into the sequence's blocks
        in one batched, jitted update (the last partial page is zero-padded,
        pages are laid out head-major, the pool buffers are donated)."""
        L, plen = k_pre.shape[0], k_pre.shape[1]
        self.ensure_capacity(pt, plen)
        pages = self.pages_for(plen)
        pad = pages * self.block_size - plen
        bids = np.asarray(pt.blocks[:pages], np.int32)

        def paged(pre):
            pre = jnp.asarray(pre)
            if pad:
                pre = jnp.pad(pre, ((0, 0), (0, pad), (0, 0), (0, 0)))
            return pre.reshape(L, pages, self.block_size,
                               *pre.shape[2:]).transpose(0, 1, 3, 2, 4)

        self._put_pages(bids, paged(k_pre), paged(v_pre))
        pt.num_tokens = plen

    # ------------------------------------------------- swap (host pages)
    def gather(self, pt: PageTable) -> Tuple[np.ndarray, np.ndarray]:
        """Copy a sequence's live pages to host memory (L, pages, hkv, blk,
        hd) — O(dirty pages), not O(max_len)."""
        bids = np.asarray(pt.blocks, np.int32)
        return np.asarray(self.k[:, bids]), np.asarray(self.v[:, bids])

    def gather_range(self, pt: PageTable, lo: int,
                     hi: int) -> Tuple[np.ndarray, np.ndarray]:
        """Copy pages [lo, hi) of a sequence to host memory — the unit of
        fluid migration. Full pages of a live session are content-frozen
        (decode only appends past ``num_tokens``; COW ``_unshare`` swaps
        the *tail* block id, never rewrites a full block), so streaming
        them by index while the session keeps decoding is race-free."""
        bids = np.asarray(pt.blocks[lo:hi], np.int32)
        return np.asarray(self.k[:, bids]), np.asarray(self.v[:, bids])

    def scatter(self, k_pages: np.ndarray, v_pages: np.ndarray,
                num_tokens: int) -> PageTable:
        """Rebind host pages to freshly allocated device blocks (swap-in),
        through the same donated jit write as prefill."""
        pages = k_pages.shape[1]
        blocks = self.allocator.alloc_many(pages)
        self._put_pages(np.asarray(blocks, np.int32), k_pages, v_pages)
        return PageTable(self.block_size, blocks, num_tokens)
