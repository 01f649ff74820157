"""The model side of the serving seam, as ``decoder_program.py`` writes it
down: the five served descriptions answer ``ServedModel`` whole, every form
they build carries one ``FormExtras`` that names vars of that program, the
engine reads nothing of a description the protocol does not name, and no model
module imports the engine's (the arrows point one way).
"""
import ast
import os

import pytest

from paddle_tpu.inference import decoder_program, serving
from paddle_tpu.inference.decoder_program import FormExtras, ServedModel
from paddle_tpu.inference.gqa_decoder import GQADecoderConfig
from paddle_tpu.inference.mla_decoder import MLADecoderConfig
from test_gpt2_program_digest import GPT2_SMALL
from test_joyai_program_digest import joyai
from test_kimi_program_digest import kimi
from test_laguna_program_digest import laguna
from test_olmo_program_digest import olmo

INFERENCE = os.path.dirname(os.path.abspath(decoder_program.__file__))
SERVING = ("reference", "prefill", "decode")

#: name -> (the description at its cell's widths, the forms it builds)
MODELS = {
    "gpt2-small": (lambda: GPT2_SMALL, SERVING + ("chunk", "verify")),
    "joyai-llm-flash": (joyai, SERVING + ("verify", "mtp")),
    "kimi-linear-48b-a3b": (kimi, SERVING),
    "laguna-xs2": (laguna, SERVING),
    "olmo-hybrid-7b": (olmo, SERVING),
}
FORMS = [(name, mode) for name, (_, modes) in MODELS.items()
         for mode in modes]


@pytest.mark.parametrize("name", MODELS)
def test_description_answers_the_protocol(name):
    cfg = MODELS[name][0]()
    assert isinstance(cfg, ServedModel)
    pools = cfg.cache_pool_names()
    specs = cfg.state_pool_specs(4)
    assert isinstance(specs, dict)
    for pool, (shape, dtype) in specs.items():
        # a slot a sequence and the padding's
        assert isinstance(pool, str) and pool not in pools
        assert shape[0] == 5 and all(isinstance(d, int) for d in shape)
        assert dtype == "float32"
    window = cfg.window_pool_names()
    assert isinstance(window, list) and set(window) <= set(pools)
    # what the engine does with the two answers
    assert bool(specs) == (name in ("kimi-linear-48b-a3b", "olmo-hybrid-7b"))
    assert bool(window) == (name == "laguna-xs2")
    assert bool(cfg.kv_cache_config(8, 16, "bfloat16").window) == bool(window)


@pytest.mark.parametrize("name,mode", FORMS)
def test_form_carries_one_record_that_names_its_vars(name, mode):
    cfg = MODELS[name][0]()
    kw = {} if mode == "reference" else {"kv_dtype": "bfloat16"}
    prog, feeds, fetches = cfg.build_program(mode, **kw)
    offers = prog._form_extras
    assert isinstance(offers, FormExtras)
    block = prog.global_block()
    for field, value in offers._asdict().items():
        if field in ("kernel_stats", "live_walk_pages"):
            assert value is None or callable(value)
        elif value is not None:
            assert block.has_var(value), (field, value)
    # the one attribute, and nothing beside it
    assert not [a for a in vars(prog) if a.startswith("_srv_")]
    if name == "gpt2-small" or mode == "mtp":
        assert offers.logits and offers == FormExtras(logits=offers.logits)
    else:
        assert offers.logits and offers.hidden and offers.score
        assert (offers.kernel_stats is None) == (mode == "reference")
        routed = name != "olmo-hybrid-7b"
        assert bool(offers.counts) == bool(offers.routes) == routed
    assert all(block.has_var(n) for n in list(feeds) + list(fetches))
    # one table width is offered by the decode form of a description whose
    # decode kernel walks live chunks, and by no other form
    assert (offers.live_walk_pages is not None) == (
        mode == "decode" and name != "gpt2-small")


#: name -> the page size of the model's cell
PAGES = {"joyai-llm-flash": 16, "kimi-linear-48b-a3b": 16, "laguna-xs2": 16,
         "olmo-hybrid-7b": 16}


@pytest.mark.parametrize("name", PAGES)
def test_decode_form_offers_a_width_where_its_kernel_engages(name,
                                                             monkeypatch):
    """``live_walk_pages`` answers by the predicate ``kernel_stats`` uses:
    the kernels' widest table where the decode kernel runs (here: in the
    interpreter), None on the CPU without it (the gather fallback pays for
    every column) and for a page the kernel does not take."""
    from paddle_tpu.ops import gqa_kernels, mla_kernels

    cfg = MODELS[name][0]()
    offer = cfg.build_program("decode", kv_dtype="bfloat16")[0] \
        ._form_extras.live_walk_pages
    kvc = cfg.kv_cache_config(64, PAGES[name], "bfloat16")
    assert offer(kvc) is None                         # the CPU: no kernel
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    kernels = mla_kernels if isinstance(cfg, MLADecoderConfig) \
        else gqa_kernels
    assert offer(kvc) == kernels.DECODE_TABLE_PAGES == 1024
    if kernels is mla_kernels:                        # a page of 4 rows
        assert offer(cfg.kv_cache_config(64, 4, "bfloat16")) is None


def imports_of(path):
    """(module, name) of every import in the file, at any depth."""
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                yield a.name, None
        elif isinstance(node, ast.ImportFrom):
            for a in node.names:
                yield node.module or "", a.name


@pytest.mark.parametrize("module,banned", [
    ("decoder_program", ("serving", "gpt2_decoder", "mla_decoder",
                         "gqa_decoder")),
    ("gpt2_decoder", ("serving",)),
    ("mla_decoder", ("serving",)),
    ("gqa_decoder", ("serving", "mla_decoder")),
])
def test_no_model_module_imports_the_engine(module, banned):
    for source, name in imports_of(os.path.join(INFERENCE, module + ".py")):
        parts = set(source.split(".")) | {name}
        assert not parts & set(banned), (module, source, name)


@pytest.mark.parametrize("cls", [MLADecoderConfig, GQADecoderConfig])
def test_state_pool_specs_is_the_class_s_own(cls):
    """The benchmark's runners ``delattr`` it from the class to take a
    model's state away and then look with ``hasattr``: an inherited default
    would break both."""
    assert "state_pool_specs" in vars(cls)
    assert "window_pool_names" in vars(cls)


def test_the_engine_reads_what_the_protocol_names():
    """Every ``cfg.<name>`` in the engine's module is a member of
    ``ServedModel`` (``init_weights`` aside: GPT-2's convenience, asked only
    where no weights are given)."""
    with open(serving.__file__) as f:
        tree = ast.parse(f.read())
    asked = {node.attr for node in ast.walk(tree)
             if isinstance(node, ast.Attribute) and (
                 (isinstance(node.value, ast.Name) and node.value.id == "cfg")
                 or (isinstance(node.value, ast.Attribute)
                     and node.value.attr == "cfg"))}
    assert asked - {"init_weights"} <= set(ServedModel.__protocol_attrs__)
    assert not [n for n in ast.walk(tree) if isinstance(n, ast.Call)
                and getattr(n.func, "id", "") == "getattr"
                and getattr(n.args[0], "id", "") == "cfg"]
