"""What ``gqa_prefill`` calls *need*: causal (windowed) grouped-query
attention of whole prompts.

Operations: per query head and UNMASKED (query, key) pair of real tokens ``2 *
head_dim`` for the score and ``2 * head_dim`` for the weighted sum: ``4 *
head_dim`` (512 at the published widths).  A prompt of ``n`` tokens has ``n (n
+ 1) / 2`` such pairs in a full layer and, in a window layer, ``w (w + 1) / 2
+ (n - w) window`` with ``w = min(n, window)``; the engine sums them over its
calls by layer kind (``gqa_prefill_pairs_full`` / ``_window``, a head).
Masked pairs inside a visited block are what the implementation computes and
not what the attention needs: they are not counted.

Bytes: q and o of every query head and k and v of every K/V head, each real
token once a layer, at the matmul operands' item size.

A prompt past a few hundred tokens is bound by the MXU; the larger of the two
times is taken all the same.
"""
from __future__ import annotations


def needed_flops(pairs_full: int, pairs_window: int, model: dict) -> int:
    return 4 * model["head_dim"] * (model["heads_full"] * pairs_full
                                    + model["heads_window"] * pairs_window)


def needed_bytes(tokens: int, calls: int, model: dict) -> int:
    """``tokens``: the real tokens summed over calls (a call a layer)."""
    layers = model["full_layers"] + model["window_layers"]
    heads = (model["full_layers"] * model["heads_full"]
             + model["window_layers"] * model["heads_window"]) / layers
    return int(tokens * 2 * (heads + model["kv_heads"]) * model["head_dim"]
               * model["item_bytes"])


def least_seconds(counts: dict, model: dict, peaks: dict) -> float:
    """The least time the calls ``counts`` sums (the engine's
    ``gqa_prefill_*`` counters over some calls) can take on a chip of
    ``peaks``."""
    return max(
        needed_flops(counts["gqa_prefill_pairs_full"],
                     counts["gqa_prefill_pairs_window"], model)
        / peaks["flops_per_s_bf16"],
        needed_bytes(counts["gqa_prefill_tokens"],
                     counts["gqa_prefill_calls"], model)
        / peaks["hbm_bytes_per_s"])
