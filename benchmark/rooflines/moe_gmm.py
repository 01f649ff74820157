"""What the routed experts of one layer *need* in one program call (the two
``moe_gmm`` kernel calls of a layer together: gate and up in one, down in
the other).

Bytes: the three matrices (``hidden x width`` twice, ``width x hidden``) of
every expert THAT RECEIVED A TOKEN, read once; an expert with no token needs
nothing.  The tokens' own rows are small beside them (a decode step: 1,024
rows of 2,048 against 9 MB an expert) and are left out.

Operations: every routed token passes the three matrices of its expert: ``3 *
2 * hidden * width`` a token and expert.

A routed row does two operations a weight, one a byte of bfloat16, and the
chip does 240 in the time it reads a byte: below 240 rows an expert the call
is bound by the weights it streams (a decode step has 4 rows an expert, a
4096-token prefill 128), above by the MXU.  The larger of the two times is
the least the call can take.
"""
from __future__ import annotations

from typing import Sequence


def needed_bytes(tokens_per_expert: Sequence[int], hidden: int, width: int,
                 item_bytes: int) -> int:
    touched = sum(1 for n in tokens_per_expert if n > 0)
    return touched * 3 * hidden * width * item_bytes


def needed_flops(tokens_per_expert: Sequence[int], hidden: int,
                 width: int) -> int:
    return int(sum(tokens_per_expert)) * 3 * 2 * hidden * width


def least_seconds(tokens_per_expert: Sequence[int], model: dict,
                  peaks: dict) -> float:
    """The least time one layer's routed experts can take in one call."""
    return max(
        needed_bytes(tokens_per_expert, model["hidden"],
                     model["expert_width"], model["item_bytes"])
        / peaks["hbm_bytes_per_s"],
        needed_flops(tokens_per_expert, model["hidden"],
                     model["expert_width"]) / peaks["flops_per_s_bf16"])
