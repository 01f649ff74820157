"""Kimi-Linear's three program forms, held to a digest taken on the commit
before the block builder learned the grouped-query mixers (PR 41's parent,
97fbb62), as ``tests/test_joyai_program_digest.py`` holds JoyAI's.

The accepted ``kimi-linear-48b-a3b`` cell runs these programs.  A later PR
that reaches into ``build_mla_program``, ``_MB`` or the forms' plumbing
(``open_form``, ``embed_rows``, ``close_form``) moves them only by changing
what this digest covers: every op's type, slots, var names and attributes,
every var's shape, type and persistence, and the feed and fetch names,
greedy, bfloat16 weights and latent pool, at the widths of
``benchmark/configs/kimi-linear-48b-a3b.json``.  Where that is meant, take the
new digest from the changed tree with ``python
tests/test_kimi_program_digest.py`` and say so in CHANGES.md.
"""
import json
import os

import pytest

from paddle_tpu.inference.mla_decoder import MLADecoderConfig
from test_gpt2_program_digest import program_digest

MODES = ("reference", "prefill", "decode")
HERE = os.path.dirname(os.path.abspath(__file__))

AT_KIMI_WIDTHS = {
    "reference":
        "e3b4a67e8e761a615f40395fc85802f14889586d05fe6f121ee1075400e5f7b3",
    "prefill":
        "eaa759436cc0ba556a0f793e9f27c1553b606279b33f8c82e2e180cc36f6eef5",
    "decode":
        "c0edcf37717fd767ab636b57604eeceef29428e264e4d7b31e57833ccf3e49c3",
}


def kimi() -> MLADecoderConfig:
    with open(os.path.join(HERE, "..", "benchmark", "configs",
                           "kimi-linear-48b-a3b.json")) as f:
        size = json.load(f)
    return MLADecoderConfig.from_source(
        size, max_seq_len=size["deployment"]["max_context"],
        weights_dtype=size["weights_dtype"])


def digest(mode: str) -> str:
    kw = {} if mode == "reference" else {"kv_dtype": "bfloat16"}
    return program_digest(*kimi().build_program(mode, **kw))


@pytest.mark.parametrize("mode", MODES)
def test_form_is_the_parents(mode):
    assert digest(mode) == AT_KIMI_WIDTHS[mode]


if __name__ == "__main__":
    print(json.dumps({m: digest(m) for m in MODES}, indent=4))
