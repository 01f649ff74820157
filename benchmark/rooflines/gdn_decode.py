"""What the ``gdn_decode`` calls *need*: one token of the gated delta rule
with one decay a head (Gated DeltaNet, arXiv:2412.06464) for each live
sequence of a decode step, in one linear layer.

Bytes: a sequence's state, ``heads x d_k x d_v`` values of ``state_item_bytes``
(float32), read once and written once: the mathematics' count, with nothing a
layout may pad (2 x 2,211,840 B a sequence and layer at 30 x 96 x 192).  The
token's own ``q``, ``k``, ``v``, decay and output are a row a head (under a
hundredth of the state) and are left out.  Padded rows of a batch bucket move
the padding's slot and need nothing.

Operations: the RECURRENCE's, whatever implements it: a head's token decays
the state (``d_k d_v``), reads it against ``k`` (``2 d_k d_v``), writes the
outer product back (``2 d_k d_v``) and reads it against ``q`` (``2 d_k
d_v``): ``7 d_k d_v``.

Seven operations for eight bytes: far under the chip's 240 a byte, so the call
is bound by memory; the larger of the two times is taken all the same.
"""
from __future__ import annotations


def needed_bytes(sequences: int, heads: int, dk: int, dv: int,
                 state_item_bytes: int) -> int:
    return 2 * sequences * heads * dk * dv * state_item_bytes


def needed_flops(sequences: int, heads: int, dk: int, dv: int) -> int:
    return 7 * sequences * heads * dk * dv


def least_seconds(sequences: int, model: dict, peaks: dict) -> float:
    """The least time the calls that took ``sequences`` live sequences (over
    however many calls and layers) can take on a chip of ``peaks``."""
    heads, dk, dv = model["gdn_heads"], model["gdn_key_dim"], \
        model["gdn_value_dim"]
    return max(
        needed_bytes(sequences, heads, dk, dv, model["state_item_bytes"])
        / peaks["hbm_bytes_per_s"],
        needed_flops(sequences, heads, dk, dv) / peaks["flops_per_s_bf16"])
