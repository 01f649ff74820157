"""Olmo-Hybrid-7B's three program forms, held to a digest taken on the commit
before the shared block-and-form builder moved out of ``mla_decoder.py`` and
the engine's file (PR 50's parent, 42c71ce), as
``tests/test_laguna_program_digest.py`` holds Laguna-XS.2's.

The accepted ``olmo-hybrid-7b`` cell runs these programs.  A later PR that
reaches into ``build_gqa_program``, ``GQADecoderConfig`` or the shared builder
of ``decoder_program.py`` (``build_form``, ``block``, ``open_form``,
``embed_rows``, ``close_form``) moves them only by changing what this digest
covers: every op's type, slots, var names and attributes, every var's shape,
type and persistence, and the feed and fetch names, greedy, bfloat16 weights
and K/V pools, at the widths of ``benchmark/configs/olmo-hybrid-7b.json``.
Where that is meant, take the new digest from the changed tree with ``python
tests/test_olmo_program_digest.py`` and say so in CHANGES.md.
"""
import json
import os

import pytest

from paddle_tpu.inference.gqa_decoder import GQADecoderConfig
from test_gpt2_program_digest import program_digest

MODES = ("reference", "prefill", "decode")
HERE = os.path.dirname(os.path.abspath(__file__))

AT_OLMO_WIDTHS = {
    "reference":
        "2b43f75afcd33efcfaaeec306aa45015a1b50cd48bf6da19423bfb6ce2d588a0",
    "prefill":
        "09d70340a7c32e485115f4d14ef26d103f2a20754b99d157f1646e260d163653",
    "decode":
        "84791a63958d9db06c7e81d8fc82da2895bd60d449e1353ec3a3d3fb2ad9ff62",
}


def olmo() -> GQADecoderConfig:
    with open(os.path.join(HERE, "..", "benchmark", "configs",
                           "olmo-hybrid-7b.json")) as f:
        size = json.load(f)
    return GQADecoderConfig.from_source(
        size, max_seq_len=size["deployment"]["max_context"],
        weights_dtype=size["weights_dtype"])


def digest(mode: str) -> str:
    kw = {} if mode == "reference" else {"kv_dtype": "bfloat16"}
    return program_digest(*olmo().build_program(mode, **kw))


@pytest.mark.parametrize("mode", MODES)
def test_form_is_the_parents(mode):
    assert digest(mode) == AT_OLMO_WIDTHS[mode]


if __name__ == "__main__":
    print(json.dumps({m: digest(m) for m in MODES}, indent=4))
