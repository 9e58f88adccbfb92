"""How the device trace names the kernels the per-layer metrics read."""
from __future__ import annotations

import re

# the Pallas paged-attention kernel (kernels/paged_attention/kernel.py):
# its body is ``_prefill_kernel``, lowered as a Mosaic custom call
PAGED_ATTN = re.compile(r"_prefill_kernel|paged_prefill_attention")


def is_paged_attn(name: str, long_name: str) -> bool:
    return bool(PAGED_ATTN.search(name) or PAGED_ATTN.search(long_name))
