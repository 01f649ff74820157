"""The five GPT-2 program forms, held to a digest taken from the commit
before the engine learned a second decoder (PR 32's parent, 2c1a7c3).

The accepted ``gpt2-small`` cells run these programs.  A later model PR that
reaches into ``build_decoder_program``, the block builder or the engine's
seam moves them only by changing what this digest covers: every op's type,
slots, var names and attributes, every var's shape, type and persistence,
and the feed and fetch names, greedy, float32.  Where that is meant, take the
new digest from the changed tree with the function below and say so in
CHANGES.md; where it is not, the change has a fault.
"""
import hashlib
import json

import pytest

from paddle_tpu.inference.gpt2_decoder import build_decoder_program
from paddle_tpu.inference.serving import DecoderConfig, ServingEngine

MODES = ("reference", "prefill", "decode", "chunk", "verify")
GPT2_SMALL = DecoderConfig(vocab_size=50257, hidden=768, num_heads=12,
                           num_layers=12, max_seq_len=1024)

# `python tests/test_gpt2_program_digest.py`, run with this file copied into
# a `git archive` of the parent commit, printed these
BUILDER_AT_GPT2_SMALL = {
    "reference":
        "3f0a648941de6fa4716f99a1fa076cd830ee5eeb55197b7207c078900e08a04f",
    "prefill":
        "fc26d29be458179f0b21f8e2b38d856e74cdb590b18d26c8ec9617b8bc35e6c8",
    "decode":
        "52ff262bd3ba8f27384af2bc0322b28ac6fdb5e9b08b721cd366ecb4a2e3a58d",
    "chunk":
        "d58e8910e62af9d9914c9baafc26a7dd379d88d7c6d00c28a519772467c426d0",
    "verify":
        "b8ce6597ed178ff17c24d7e3af7e624fedda4ee2a26bfa9978350257d03f02c6",
}
ENGINE_AT_DEFAULT = {
    "reference":
        "48edb8a70d1aaf80da2b071f9a51480a071edeb41c25a478e0d9455a23d04fa7",
    "prefill":
        "933f68193468c5dae474ad0cb87c96735e9feb1b9e20dfc423d03ec2b2755a73",
    "decode":
        "8d5a3c1c4a20b875908907d626d5ab79b89ec3d91af1b323e59d8e7bf28273aa",
    "chunk":
        "c341087c4a519469116f71b912dcc10bed33d29942b044bc1fcccac67ae8f87a",
    "verify":
        "4dfa23fdb13f291bd873c7319606267cfeac2be5bc97cf50218202a475bbdabb",
}


def _plain(v):
    if isinstance(v, (list, tuple)):
        return [_plain(x) for x in v]
    if isinstance(v, (bool, int, float, str)) or v is None:
        return v
    return repr(v)


def program_digest(prog, feeds, fetches) -> str:
    block = prog.global_block()
    ops = [[op.type, sorted((k, list(v)) for k, v in op.inputs.items()),
            sorted((k, list(v)) for k, v in op.outputs.items()),
            sorted((k, _plain(v)) for k, v in op.attrs.items()
                   if k != "op_callstack")]     # where it was built from
           for op in block.ops]
    vars_ = sorted([name, _plain(getattr(v, "shape", None)),
                    str(getattr(v, "dtype", None)),
                    bool(getattr(v, "persistable", False))]
                   for name, v in block.vars.items())
    text = json.dumps([ops, vars_, list(feeds), list(fetches)],
                      sort_keys=True)
    return hashlib.sha256(text.encode()).hexdigest()


def engine_forms():
    eng = ServingEngine(cfg=DecoderConfig(), num_pages=8, page_size=8)
    core = eng.core
    return {
        "reference": (core.ref_prog, core.ref_feeds, core.ref_fetch),
        "prefill": (core.prefill_prog, core.prefill_feeds,
                    core.prefill_fetch),
        "decode": (core.decode_prog, core.decode_feeds, core.decode_fetch),
        "chunk": core.chunk_prog_parts,
        "verify": core.verify_prog_parts,
    }


@pytest.mark.parametrize("mode", MODES)
def test_builder_form_is_the_parents(mode):
    got = program_digest(*build_decoder_program(GPT2_SMALL, mode))
    assert got == BUILDER_AT_GPT2_SMALL[mode]
    # and the seam hands the engine the same program
    assert program_digest(*GPT2_SMALL.build_program(mode)) == got


@pytest.fixture(scope="module")
def forms():
    return engine_forms()


@pytest.mark.parametrize("mode", MODES)
def test_engine_form_is_the_parents(forms, mode):
    """As the engine holds them: built through the seam, the attention
    fusion pass applied to the reference and prefill forms."""
    assert program_digest(*forms[mode]) == ENGINE_AT_DEFAULT[mode]


if __name__ == "__main__":
    print(json.dumps({
        "BUILDER_AT_GPT2_SMALL": {m: program_digest(
            *build_decoder_program(GPT2_SMALL, m)) for m in MODES},
        "ENGINE_AT_DEFAULT": {m: program_digest(*f)
                              for m, f in engine_forms().items()}},
        indent=4))
