"""What the ``gqa_decode`` calls of one decode step *need*: grouped-query
attention of one query row a sequence over its cached K and V rows, in every
attention layer.

Bytes: K and V of every position a row attends, read once a K/V head and
layer whatever the number of query heads that share it: ``ctx`` positions in
a full layer, ``min(ctx, window)`` in a window layer, ``2 * kv_heads *
head_dim`` values of the pool's item size a position (4,096 B at the
published widths in bfloat16).  Queries, outputs, tables and the gate are a
row a head and are left out.

Operations: per query head and attended position ``2 * head_dim`` for the
score and ``2 * head_dim`` for the weighted sum: ``4 * head_dim``, times the
layer's query heads (48 on full layers, 64 on window layers).

6 or 8 query heads a K/V head make 12 or 16 operations a byte: far under the
chip's 240, so the calls are bound by memory; the larger of the two times is
taken all the same.
"""
from __future__ import annotations

from typing import Sequence


def attended(context_lens: Sequence[int], window: int) -> int:
    """Positions the rows attend in one layer (``window`` 0: all)."""
    return sum(min(c, window) if window else c for c in context_lens)


def needed_bytes(context_lens: Sequence[int], model: dict) -> int:
    row = 2 * model["kv_heads"] * model["head_dim"] * model["cache_item_bytes"]
    return row * (model["full_layers"] * attended(context_lens, 0)
                  + model["window_layers"]
                  * attended(context_lens, model["window"]))


def needed_flops(context_lens: Sequence[int], model: dict) -> int:
    d = model["head_dim"]
    return 4 * d * (
        model["full_layers"] * model["heads_full"]
        * attended(context_lens, 0)
        + model["window_layers"] * model["heads_window"]
        * attended(context_lens, model["window"]))


def least_seconds(context_lens: Sequence[int], model: dict,
                  peaks: dict) -> float:
    """The least time one decode step's calls (every attention layer) can
    take on a chip of ``peaks``."""
    return max(needed_bytes(context_lens, model) / peaks["hbm_bytes_per_s"],
               needed_flops(context_lens, model) / peaks["flops_per_s_bf16"])
