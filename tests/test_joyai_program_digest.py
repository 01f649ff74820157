"""JoyAI-LLM-Flash's five program forms, held to a digest taken from the
commit before the MLA decoder's description learned a second mixer (PR 36's
parent, 964063c).

The accepted ``joyai-llm-flash`` cell runs these programs.  A later model PR
that reaches into ``build_mla_program`` or its block builder moves them only
by changing what this digest covers (as ``tests/test_gpt2_program_digest.py``
for GPT-2): every op's type, slots, var names and attributes, every var's
shape, type and persistence, and the feed and fetch names, greedy, bfloat16
weights and pool, at the widths of ``benchmark/configs/joyai-llm-flash.json``
with its MTP block.  Where that is meant, take the new digest from the
changed tree with ``python tests/test_joyai_program_digest.py`` and say so in
CHANGES.md; where it is not, the change has a fault.
"""
import json
import os

import pytest

from paddle_tpu.inference.mla_decoder import MLADecoderConfig
from test_gpt2_program_digest import program_digest

MODES = ("reference", "prefill", "decode", "verify", "mtp")
HERE = os.path.dirname(os.path.abspath(__file__))

# PR 39: every op of the forms says which part of the model it serves (attr
# ``part``; before, only the four op types below carried one).  The forms are
# held twice: as they are, and with the attributes that PR added taken off
# again, to the digests of the commit before the MLA decoder's description
# learned a second mixer: nothing but ``part`` attributes moved them.
HAD_A_PART = ("matmul_f32acc", "rms_norm", "rope_interleaved", "swiglu")

BEFORE_EVERY_OP_HAD_A_PART = {
    "reference":
        "d5ea938dce63561c665fcb5ff37ed1a8a3bd2fb77a491e69e5f59b7c55859cc3",
    "prefill":
        "025d40f4ce7e2a11f8143a920429b6ca5641b8165adfac4fc8dc6cf2abfbfd9b",
    "decode":
        "6b9c080ea6198f0413e56cc2758031da71a6e8d884c2d69e54f195deb02efdf1",
    "verify":
        "8979ee670057f29cbb5b860d17950c853ae1aaf4ff14ddfb6a4fc801b84878a9",
    "mtp":
        "002978704c07622cbd49889a1a0b3ff3b83d852740aea42d8d2cbf14e2d9d676",
}


AT_JOYAI_WIDTHS = {
    "reference":
        "c2e63b7232f2efa07fe5e2721a3a1ef003af87b706b4924a59d38bfc822e94a8",
    "prefill":
        "e50ea756a9f10c9d0468e12e314432fde54da0d81c758aedd20c03dc32e49392",
    "decode":
        "7ce2b1a02c9575b0dc3596b9ed05448999e1a3b97deafbd46d6771f3ca73c6bf",
    "verify":
        "71804c6730bcfa3b96913140abd03169a3ed8ca6d99d4f2aa1e1967fa645516c",
    "mtp":
        "9439666cb77271e7ac6ae9e4ace97345e38127a2ccd6cd1bc7bc154ee7ad4ee3",
}


def joyai() -> MLADecoderConfig:
    with open(os.path.join(HERE, "..", "benchmark", "configs",
                           "joyai-llm-flash.json")) as f:
        size = json.load(f)
    return MLADecoderConfig.from_source(
        size, max_seq_len=size["deployment"]["max_context"],
        weights_dtype=size["weights_dtype"], mtp_layers=1)


def digest(mode: str, added_parts: bool = True) -> str:
    kw = {} if mode == "reference" else {"kv_dtype": "bfloat16"}
    prog, feeds, fetches = joyai().build_program(mode, **kw)
    if not added_parts:
        for op in prog.global_block().ops:
            if op.type not in HAD_A_PART:
                op.attrs.pop("part", None)
    return program_digest(prog, feeds, fetches)


@pytest.mark.parametrize("mode", MODES)
def test_form_is_the_parents(mode):
    assert digest(mode) == AT_JOYAI_WIDTHS[mode]


@pytest.mark.parametrize("mode", MODES)
def test_form_moved_by_part_attributes_alone(mode):
    """Every op carries a part, and without the ones PR 39 added the form
    is to the byte the one the accepted cell ran before."""
    prog = joyai().build_program(
        mode, **({} if mode == "reference" else {"kv_dtype": "bfloat16"}))[0]
    assert all(op.attrs.get("part") for op in prog.global_block().ops)
    assert digest(mode, added_parts=False) == BEFORE_EVERY_OP_HAD_A_PART[mode]


if __name__ == "__main__":
    print(json.dumps({m: digest(m) for m in MODES}, indent=4))
