"""Laguna-XS.2's three program forms, held to a digest taken on the commit
before the grouped-query description learned a recurrent layer kind (PR 44's
parent, 069257a), as ``tests/test_kimi_program_digest.py`` holds Kimi-Linear's.

The accepted ``laguna-xs2`` cell runs these programs.  A later PR that reaches
into ``build_gqa_program``, ``GQADecoderConfig``, ``_MB`` or the forms'
plumbing (``open_form``, ``embed_rows``, ``close_form``) moves them only by
changing what this digest covers: every op's type, slots, var names and
attributes, every var's shape, type and persistence, and the feed and fetch
names, greedy, bfloat16 weights and K/V pools, at the widths of
``benchmark/configs/laguna-xs2.json``.  Where that is meant, take the new
digest from the changed tree with ``python
tests/test_laguna_program_digest.py`` and say so in CHANGES.md.
"""
import json
import os

import pytest

from paddle_tpu.inference.gqa_decoder import GQADecoderConfig
from test_gpt2_program_digest import program_digest

MODES = ("reference", "prefill", "decode")
HERE = os.path.dirname(os.path.abspath(__file__))

AT_LAGUNA_WIDTHS = {
    "reference":
        "5b399ea6a1f0cdb1b50c22250d329747ae91c851e8f088fde23025063d32aadb",
    "prefill":
        "1eb75e42700ea61045fbd2b826b7ccfc96abaa180ce06631288afe35635ad846",
    "decode":
        "ccc91b77bef0d90c22a40a5bbbcc3e497dc45c00eeb03b588492ae9790a8acea",
}


def laguna() -> GQADecoderConfig:
    with open(os.path.join(HERE, "..", "benchmark", "configs",
                           "laguna-xs2.json")) as f:
        size = json.load(f)
    return GQADecoderConfig.from_source(
        size, max_seq_len=size["deployment"]["max_context"],
        weights_dtype=size["weights_dtype"])


def digest(mode: str) -> str:
    kw = {} if mode == "reference" else {"kv_dtype": "bfloat16"}
    return program_digest(*laguna().build_program(mode, **kw))


@pytest.mark.parametrize("mode", MODES)
def test_form_is_the_parents(mode):
    assert digest(mode) == AT_LAGUNA_WIDTHS[mode]


if __name__ == "__main__":
    print(json.dumps({m: digest(m) for m in MODES}, indent=4))
