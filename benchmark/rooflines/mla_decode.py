"""What one ``mla_decode`` call *needs*: absorbed latent attention of one
query row a sequence over its cached latent rows.

Bytes: every running sequence's true context, one latent row a position read
once: ``latent_values`` (512 + 64 = 576 at the published widths) times the
pool's item size.  The pool stores rows of 640 lanes (the chip's tiling pads
576 to 640 whatever is declared); the 64 lanes of padding are what the
implementation moves and not what the algorithm needs, so they are not
counted: a share of 90 % is then the most this layout can reach.  Queries,
outputs and block tables are a row a head and are left out.

Operations: per head and position ``2 * latent_values`` for the score (the
512 latent lanes and the 64 rotary lanes) and ``2 * kv_lora_rank`` for the
weighted sum of the latent rows: 2 * 576 + 2 * 512 = 2,176 at the published
widths.

About 60 operations a byte: under the chip's 240, so the call is bound by
memory; the larger of the two times is taken all the same.
"""
from __future__ import annotations

from typing import Sequence


def needed_bytes(context_lens: Sequence[int], latent_values: int,
                 item_bytes: int) -> int:
    return sum(context_lens) * latent_values * item_bytes


def needed_flops(context_lens: Sequence[int], heads: int,
                 latent_values: int, kv_lora_rank: int) -> int:
    return sum(context_lens) * heads * 2 * (latent_values + kv_lora_rank)


def least_seconds(context_lens: Sequence[int], model: dict,
                  peaks: dict) -> float:
    """The least time one call (one layer) can take on a chip of ``peaks``."""
    return max(
        needed_bytes(context_lens, model["latent_values"],
                     model["cache_item_bytes"]) / peaks["hbm_bytes_per_s"],
        needed_flops(context_lens, model["heads"], model["latent_values"],
                     model["kv_lora_rank"]) / peaks["flops_per_s_bf16"])
