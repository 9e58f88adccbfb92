"""Operations and bytes of the served work, counted from live shapes.

A step is a list of rows ``(cache_len, q)``: ``q`` real query tokens
written after ``cache_len`` resident ones. Nothing here looks at pages
walked, grid steps or padded widths, so the counts are the same whatever
implements the kernel and however wide the step was dispatched.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

Row = Tuple[int, int]


def attn_flops_row(d: Dict, cache_len: int, q: int) -> int:
    """One layer's attention for one row: QK^T and PV over the causal
    pairs, ``kv = cache_len + q`` live positions; each pair costs
    ``2 * head_dim`` per matmul per query head."""
    kv = cache_len + q
    pairs = q * kv - q * (q - 1) // 2
    return 4 * d["n_heads"] * d["head_dim"] * pairs


def attn_bytes_row(d: Dict, cache_len: int, q: int, kv_itemsize: int = 2,
                   act_itemsize: int = 2) -> int:
    """One layer's attention for one row: the K and V of every live
    position, read once per kv head, plus the queries read and the output
    written."""
    kv = cache_len + q
    hd = d["head_dim"]
    return (2 * d["n_kv_heads"] * hd * kv * kv_itemsize
            + 2 * q * d["n_heads"] * hd * act_itemsize)


def paged_attn(d: Dict, rows: Iterable[Row]) -> Tuple[int, int]:
    """(FLOPs, bytes) of the paged-attention kernel over a step's rows,
    all layers."""
    f = b = 0
    for cache_len, q in rows:
        f += attn_flops_row(d, cache_len, q)
        b += attn_bytes_row(d, cache_len, q)
    return d["n_layers"] * f, d["n_layers"] * b


def matmul_params(d: Dict) -> int:
    """Weights of the per-token projections, all layers, without the
    embedding table and the LM head."""
    D, hq, hkv, hd = d["d_model"], d["n_heads"], d["n_kv_heads"], d["head_dim"]
    attn = D * (hq + 2 * hkv) * hd + hq * hd * D
    mlp = (3 if d["mlp"] == "swiglu" else 2) * D * d["d_ff"]
    return d["n_layers"] * (attn + mlp)


def model_flops(d: Dict, rows: Iterable[Row]) -> int:
    """Model FLOPs of a step: 2 per weight per real token, attention over
    the causal pairs, and the LM head once per row (the megastep unembeds
    only the position it samples from)."""
    per_tok = 2 * matmul_params(d)
    head = 2 * d["d_model"] * d["vocab_size"]
    total = 0
    for cache_len, q in rows:
        total += (q * per_tok + head
                  + d["n_layers"] * attn_flops_row(d, cache_len, q))
    return total


def least_time(flops: float, nbytes: float, peaks: Dict) -> Tuple[float, str]:
    """The roofline: the larger of compute and memory time, and which."""
    tc = flops / peaks["bf16_flops"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "flops") if tc >= tm else (tm, "bytes")
