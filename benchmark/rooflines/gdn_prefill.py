"""What the ``gdn_prefill`` calls *need*: the gated delta rule with one decay
a head (Gated DeltaNet, arXiv:2412.06464) over the real tokens of one prompt
from an empty state, in one linear layer.

Operations: the RECURRENCE's, ``7 d_k d_v`` a token and head (see
``gdn_decode.py``), so that the share reads the same work whatever chunking
implements it: a chunked kernel does more arithmetic than this (its pairwise
products, its triangular inverse) on the MXU, and none of that is needed.

Bytes: a token's ``q`` and ``k`` (``d_k`` each) and ``v`` (``d_v``) read and
its output (``d_v``) written, float32 as the program holds them (the decay
and the write strength are one value a head and are left out), and the final
state written once a call and head (an empty state is not read).  Padded rows
of a prompt's bucket need nothing.

``7 d_k d_v / (4 (2 d_k + 2 d_v))`` = 56 operations a byte at 96 x 192: under
the chip's 240, so by this count the call is bound by the rows it streams;
the larger of the two times is taken all the same.
"""
from __future__ import annotations


def needed_bytes(tokens: int, calls: int, heads: int, dk: int, dv: int,
                 item_bytes: int, state_item_bytes: int) -> int:
    return tokens * heads * (2 * dk + 2 * dv) * item_bytes \
        + calls * heads * dk * dv * state_item_bytes


def needed_flops(tokens: int, heads: int, dk: int, dv: int) -> int:
    return 7 * tokens * heads * dk * dv


def least_seconds(tokens: int, calls: int, model: dict, peaks: dict) -> float:
    """The least time ``calls`` calls over ``tokens`` real tokens in all can
    take on a chip of ``peaks``."""
    heads, dk, dv = model["gdn_heads"], model["gdn_key_dim"], \
        model["gdn_value_dim"]
    return max(
        needed_bytes(tokens, calls, heads, dk, dv, model["gdn_item_bytes"],
                     model["state_item_bytes"]) / peaks["hbm_bytes_per_s"],
        needed_flops(tokens, heads, dk, dv) / peaks["flops_per_s_bf16"])
