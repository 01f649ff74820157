"""What one ``kda_prefill`` call *needs*: the gated delta rule over the real
tokens of one prompt from an empty state, in one KDA layer.

Operations: the RECURRENCE's, ``7 d_k d_v`` a token and head (see
``kda_decode.py``), so that the share reads the same work whatever chunking
implements it: the chunked kernel does more arithmetic than this (its
pairwise decays, its triangular inverse) on the MXU, and none of that is
needed.

Bytes: a token's ``q``, ``k``, decay (``d_k`` each) and ``v`` (``d_v``) read
and its output (``d_v``) written, float32 as the program holds them, and the
final state written once a call and head (an empty state is not read).
Padded rows of a prompt's bucket need nothing.

About ``7 d_k d_v / (4 (3 d_k + 2 d_v))`` = 45 operations a byte at heads of
128: under the chip's 240, so by this count the call is bound by the rows it
streams; the larger of the two times is taken all the same.
"""
from __future__ import annotations


def needed_bytes(tokens: int, calls: int, heads: int, dk: int, dv: int,
                 item_bytes: int, state_item_bytes: int) -> int:
    return tokens * heads * (3 * dk + 2 * dv) * item_bytes \
        + calls * heads * dk * dv * state_item_bytes


def needed_flops(tokens: int, heads: int, dk: int, dv: int) -> int:
    return 7 * tokens * heads * dk * dv


def least_seconds(tokens: int, calls: int, model: dict, peaks: dict) -> float:
    """The least time ``calls`` calls over ``tokens`` real tokens in all can
    take on a chip of ``peaks``."""
    heads, d = model["kda_heads"], model["kda_head_dim"]
    return max(
        needed_bytes(tokens, calls, heads, d, d, model["kda_item_bytes"],
                     model["state_item_bytes"]) / peaks["hbm_bytes_per_s"],
        needed_flops(tokens, heads, d, d) / peaks["flops_per_s_bf16"])
