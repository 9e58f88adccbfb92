"""Public wrapper for paged attention: model-layout queries against the
pooled block cache (num_blocks, hkv, blk, d) + per-sequence page tables. The
pool layout is the allocator's native layout, so no transpose or gather of
the cache happens on the hot path — the kernel's index maps do the page
walk. ``paged_prefill_attention`` is the chunked-prefill form the megastep
uses; decode rows are its C == 1 special case (the chunk axis C is whatever
pow2 trace bucket the engine's token-budget packer selected — per-row
``valids`` carry the ragged real widths)."""
from __future__ import annotations

from repro.kernels.paged_attention.kernel import paged_prefill_attention_bcd


def paged_prefill_attention(q, k_pool, v_pool, cache_lens, valids,
                            page_tables, *, scale=None,
                            interpret: bool = False):
    """q: (b, C, hq, d) mixed prefill/decode rows (see the kernel docstring);
    cache_lens/valids: (b,) int32; page_tables: (b, npages) int32.
    Returns (b, C, hq, dv)."""
    return paged_prefill_attention_bcd(q, k_pool, v_pool, cache_lens, valids,
                                       page_tables, scale=scale,
                                       interpret=interpret)
