"""Bytes one ``paged_decode`` call *needs*: the keys and values of every
running sequence's true context, read once.  The query and the output are
one row per head and are left out, as are the block tables.  Per position and
head the kernel does a (1, d) x (d, 1) dot twice: under one operation per
byte read, so the call is memory-bound and the roofline is bytes over the
chip's memory bandwidth."""
from __future__ import annotations

from typing import Sequence


def needed_bytes(context_lens: Sequence[int], kv_bytes_per_token: int) -> int:
    """``kv_bytes_per_token`` is one layer's: 2 (K and V) x heads x head_dim
    x the pool's item size."""
    return sum(context_lens) * kv_bytes_per_token
