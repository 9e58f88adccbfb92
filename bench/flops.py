"""The roofline that the work counts are held against.

The counts themselves belong to the cell's architecture module
(``archs/<name>.py``: ``paged_attn`` and ``model_flops``). A step is a
list of rows ``(cache_len, q)``: ``q`` real query tokens written after
``cache_len`` resident ones. The counts look at live shapes only, never at
pages walked, grid steps or padded widths, so they are the same whatever
implements the kernel and however wide the step was dispatched.
"""
from __future__ import annotations

from types import SimpleNamespace
from typing import Dict, Tuple


def least_time(flops: float, nbytes: float, peaks: Dict) -> Tuple[float, str]:
    """The roofline: the larger of compute and memory time, and which."""
    tc = flops / peaks["bf16_flops"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "flops") if tc >= tm else (tm, "bytes")


def counts(arch) -> SimpleNamespace:
    """What the metric readers see as ``ctx.flops``: the architecture's
    ``paged_attn`` and ``model_flops``, and ``least_time``."""
    return SimpleNamespace(paged_attn=arch.paged_attn,
                           model_flops=arch.model_flops,
                           least_time=least_time)
