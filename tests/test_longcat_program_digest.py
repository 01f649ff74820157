"""LongCat-Flash-Chat's four program forms, held to a digest taken at the end
of the PR that brought them (PR 51).

The ``longcat-flash-chat`` cell runs these programs.  A later PR that reaches
into ``build_mla_program`` or the shared block builder
(``decoder_program._MB.shortcut_pair``) moves them only by changing what this
digest covers (as ``tests/test_gpt2_program_digest.py`` for GPT-2): every
op's type, slots, var names and attributes, every var's shape, type and
persistence, and the feed and fetch names, greedy, bfloat16 weights and pools,
at the widths of ``benchmark/configs/longcat-flash-chat.json``.  Where that is
meant, take the new digest from the changed tree with ``python
tests/test_longcat_program_digest.py`` and say so in CHANGES.md; where it is
not, the change has a fault.
"""
import json
import os

import pytest

from paddle_tpu.inference.mla_decoder import MLADecoderConfig
from test_gpt2_program_digest import program_digest

MODES = ("reference", "prefill", "decode", "verify")
HERE = os.path.dirname(os.path.abspath(__file__))

AT_LONGCAT_WIDTHS = {
    "reference":
        "bc27e421cd0e3e8b58b1e94179985cf4944972b35c7cc962d787d9329a478aea",
    "prefill":
        "000bbdbf9bb291de1b2b2032b4fa19da73312a13d0743ec55eaef43391414bf1",
    "decode":
        "c31d17fea88c5cd2f104e79894aca5fc54b3446de792d9398c04857a950e5716",
    "verify":
        "4bb30abfa294e723993d59fd92a54da9c15bd24bcf4044270d83c88cab941579",
}


def longcat() -> MLADecoderConfig:
    with open(os.path.join(HERE, "..", "benchmark", "configs",
                           "longcat-flash-chat.json")) as f:
        size = json.load(f)
    return MLADecoderConfig.from_source(
        size, max_seq_len=size["deployment"]["max_context"],
        weights_dtype=size["weights_dtype"])


def digest(mode: str) -> str:
    kw = {} if mode == "reference" else {"kv_dtype": "bfloat16"}
    return program_digest(*longcat().build_program(mode, **kw))


@pytest.mark.parametrize("mode", MODES)
def test_form_is_the_one_the_cell_was_admitted_with(mode):
    assert digest(mode) == AT_LONGCAT_WIDTHS[mode]


@pytest.mark.parametrize("mode", MODES)
def test_every_op_says_its_part_and_a_layer_is_two_sub_blocks(mode):
    prog = longcat().build_program(
        mode, **({} if mode == "reference" else {"kv_dtype": "bfloat16"}))[0]
    ops = prog.global_block().ops
    assert all(op.attrs.get("part") for op in ops)
    kinds = [op.type for op in ops]
    attention = "mla_prefill_attention" if mode in ("reference", "prefill") \
        else "mla_paged_attention"
    # 4 layers: 8 attentions, 8 dense halves (3 matmuls + a swiglu each), 4
    # routers and expert layers, no shared expert
    assert kinds.count(attention) == 8
    assert kinds.count("swiglu") == 8
    assert kinds.count("moe_router") == kinds.count("moe_experts") == 4
    router = next(op for op in ops if op.type == "moe_router")
    assert router.attrs["scoring_func"] == "softmax"
    assert router.attrs["norm_topk_prob"] is False
    assert router.attrs["top_k"] == 12
    experts = next(op for op in ops if op.type == "moe_experts")
    assert experts.attrs["routed_experts"] == 512
    assert not [v for v in prog.global_block().vars if "shared" in v]


if __name__ == "__main__":
    print(json.dumps({m: digest(m) for m in MODES}, indent=4))
