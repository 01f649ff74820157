"""Tensor-parallel serving decode (r24): the decoder + paged KV pool
sharded over the ``mp`` mesh axis, priced as a plan axis.

Oracles:
* the partition rules the engine derives from the generic constructors
  (parallel/tensor_parallel.py attention_head_rules / megatron_mlp_rules
  / embedding_rules) EQUAL hand-written Megatron specs — pinned so a
  refactor of either side is caught;
* ``build_decoder_program(..., tp=1)`` is byte-identical to the
  unsharded builder for every program form (the flag-off baseline);
* ``serving_tp_pass`` inserts exactly 2 collectives per block + 3
  model-level (embed all-gather, logits split + reduce), all carrying
  the dedicated serving ring — and only ops the registry knows;
* tp in {2, 4} greedy decode is TOKEN-IDENTICAL to tp=1 on a seeded
  trace, including prefix-cache, chunked prefill, spec-decode, and the
  quantized KV dtypes;
* a fixed per-device ``kv_budget_mb`` buys exactly tp x more pages
  (the capacity headline) at UNCHANGED per-device pool residency, and
  the static planner's tp division reproduces the engine census for
  both the kv_pool class and the decoder weights;
* infeasible degrees fail loud at construction (engine guard and the
  kernel's GQA grouping guard);
* the plan searcher enumerates the tp axis: with a budget the tp=1
  footprint exceeds, tp=1 candidates are rejected BEFORE compile and a
  finite-feasible tp>1 plan is chosen, priced with the collective term.
"""
import numpy as np
import pytest

from paddle_tpu.framework import unique_name
from paddle_tpu.framework.ir import get_pass
from paddle_tpu.inference.gpt2_decoder import (
    build_decoder_program, decoder_tp_rules, validate_tp_degree,
)
from paddle_tpu.inference.serving import (
    SERVING_TP_AXIS, SERVING_TP_RING_ID, DecoderConfig, Request,
    ServingEngine,
)
from paddle_tpu.utils import flags as F

CFG = DecoderConfig(vocab_size=64, hidden=32, num_heads=4, num_layers=2,
                    max_seq_len=128)


def make_engine(tp=1, **kw):
    kw.setdefault("num_pages", 32)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_batch", 4)
    kw.setdefault("token_budget", 64)
    kw.setdefault("prefill_bucket_min", 8)
    return ServingEngine(kw.pop("cfg", CFG), tp=tp, **kw)


def run_trace(tp, flags=None, **kw):
    """Seeded 4-request trace (two share a prefix) -> event tuples."""
    F.set_flags(flags or {})
    try:
        eng = make_engine(tp=tp, **kw)
        rng = np.random.default_rng(0)
        for i in range(4):
            prompt = rng.integers(1, CFG.vocab_size,
                                  size=5 + 3 * i).tolist()
            if i >= 2:
                prompt = [9] * 8 + prompt
            eng.submit(Request(req_id=f"r{i}", prompt=prompt,
                               max_new_tokens=8))
        events = []
        while eng.has_work():
            events += eng.step()
        return [(e.req_id, e.token, e.finished) for e in events]
    finally:
        F.set_flags({"FLAGS_kv_prefix_cache": 0,
                     "FLAGS_prefill_chunk_tokens": 0})


# ==========================================================================
# partition rules: derived == hand-written Megatron specs (pinned)
# ==========================================================================
def test_decoder_tp_rules_match_hand_specs():
    ax = SERVING_TP_AXIS
    hand = {
        # attention: Q/K/V column-parallel (heads split), out-proj
        # row-parallel — attention_head_rules
        r"dec_l\d+_wq": (None, ax),
        r"dec_l\d+_wk": (None, ax),
        r"dec_l\d+_wv": (None, ax),
        r"dec_l\d+_wo": (ax, None),
        # MLP: up column-parallel, down row-parallel — megatron_mlp_rules
        r"dec_l\d+_w1": (None, ax),
        r"dec_l\d+_w2": (ax, None),
        # embeddings hidden-sharded (positional follows the token table
        # so the embed sum stays local) — embedding_rules(mode="hidden")
        "dec_embed": (None, ax),
        "dec_pos_embed": (None, ax),
        # paged KV pools split on kv_heads (pool layout
        # (kv_heads, pages, page_size, head_dim))
        r"kv_[kv]_\d+": (ax, None, None, None),
    }
    assert decoder_tp_rules(CFG) == hand
    assert decoder_tp_rules(CFG, kv_dtype="int8") == {
        **hand, r"kv_[kv]_scale_\d+": (ax, None)}
    # LayerNorm params are replicated: no rule may match them
    import re
    for pat in decoder_tp_rules(CFG, kv_dtype="int8"):
        for name in ("dec_l0_ln1_scale", "dec_l0_ln2_bias",
                     "dec_lnf_scale"):
            assert not (name == pat or re.fullmatch(pat, name))


def test_rules_compose_from_generic_constructors():
    """The engine's rule set is EXACTLY the union of the generic
    constructors' outputs — nothing hand-patched besides the pos-embed
    rider and the KV pools."""
    from paddle_tpu.parallel.tensor_parallel import (
        attention_head_rules, embedding_rules, megatron_mlp_rules)

    composed = {}
    composed.update(attention_head_rules(
        r"dec_l\d+_wq", r"dec_l\d+_wk", r"dec_l\d+_wv", r"dec_l\d+_wo",
        axis=SERVING_TP_AXIS))
    composed.update(megatron_mlp_rules(
        [r"dec_l\d+_w1", r"dec_l\d+_w2"], axis=SERVING_TP_AXIS))
    composed.update(embedding_rules("dec_embed", axis=SERVING_TP_AXIS,
                                    mode="hidden"))
    composed = {k: tuple(v) for k, v in composed.items()}
    derived = decoder_tp_rules(CFG)
    extras = set(derived) - set(composed)
    assert extras == {"dec_pos_embed", r"kv_[kv]_\d+"}
    for k, v in composed.items():
        assert derived[k] == v


# ==========================================================================
# tp=1 baseline: byte-identical programs, no mesh, no collectives
# ==========================================================================
@pytest.mark.parametrize("mode", ["reference", "prefill", "decode",
                                  "chunk", "verify"])
def test_tp1_builder_byte_identical(mode):
    def build(**kw):
        unique_name.switch()
        return build_decoder_program(CFG, mode, **kw)[0] \
            .serialize_to_string()

    assert build() == build(tp=1)


def test_tp1_engine_is_legacy_path():
    eng = make_engine(tp=1)
    assert eng.core.tp == 1 and eng.core.tp_mesh is None
    for prog in (eng.core.prefill_prog, eng.core.decode_prog):
        assert not [op for op in prog.global_block().ops
                    if op.type.startswith("c_")]
    assert int(F.flag("serving_tp", 1)) == 1  # flag default stays off


# ==========================================================================
# serving_tp_pass: structure + ring
# ==========================================================================
def test_serving_tp_pass_structure():
    from collections import Counter

    from paddle_tpu.ops.registry import OPS

    prog = build_decoder_program(CFG, "decode", tp=2)[0]
    p = get_pass("serving_tp_pass")
    p.ring_id = SERVING_TP_RING_ID
    p.apply(prog)
    # 2 per block (o-proj + ff2 allreduce) + 3 model-level (embed
    # all-gather, logits split, logits allreduce)
    assert p.inserted_count == 2 * CFG.num_layers + 3
    c = Counter(op.type for op in prog.global_block().ops)
    assert c["c_concat"] == 1
    assert c["c_split"] == 1
    assert c["c_allreduce_sum"] == 2 * CFG.num_layers + 1
    for op in prog.global_block().ops:
        assert op.type in OPS, f"pass inserted unregistered op {op.type}"
        if op.type in ("c_concat", "c_split", "c_allreduce_sum"):
            assert op.attrs["ring_id"] == SERVING_TP_RING_ID


# ==========================================================================
# token identity: tp in {2, 4} == tp=1, every serving feature
# ==========================================================================
@pytest.mark.parametrize("feature,kw", [
    ("plain", {}),
    ("prefix_cache", {"flags": {"FLAGS_kv_prefix_cache": 1}}),
    ("chunked_prefill", {"flags": {"FLAGS_prefill_chunk_tokens": 16}}),
    ("spec_decode", {"spec_k": 2}),
    ("kv_int8", {"kv_dtype": "int8"}),
    ("kv_bf16", {"kv_dtype": "bfloat16"}),
])
def test_tp_token_identity(feature, kw):
    base = run_trace(1, **kw)
    assert base, "trace produced no events"
    assert run_trace(2, **kw) == base
    if feature == "plain":  # tp=4 once; the mechanism is degree-blind
        assert run_trace(4, **kw) == base


def test_tp_matches_greedy_reference():
    eng = make_engine(tp=2)
    prompt = [5, 17, 3, 9, 22]
    out = eng.generate([prompt], max_new_tokens=6)[0]
    assert out == eng.core.greedy_reference(prompt, 6)


# ==========================================================================
# capacity + memory: tp x pages at fixed per-device budget
# ==========================================================================
def test_capacity_scales_tp_x_at_fixed_budget():
    pages, resident = {}, {}
    for tp in (1, 2, 4):
        eng = make_engine(tp=tp, kv_budget_mb=1.0)
        pages[tp] = eng.core.kv_config.num_pages
        resident[tp] = eng.core.kv_pool_resident_bytes()
    assert pages[2] == 2 * pages[1]
    assert pages[4] == 4 * pages[1]
    # per-device residency is UNCHANGED: the budget is per device
    assert resident[2] == resident[1] and resident[4] == resident[1]


@pytest.mark.parametrize("kv_dtype", ["float32", "bfloat16", "int8"])
def test_planner_tp_division_reconciles_with_census(kv_dtype):
    from paddle_tpu.framework import memory_plan as mp
    from paddle_tpu.inference.gpt2_decoder import init_decoder_weights
    from paddle_tpu.inference.serving import _EngineCore

    cfg = DecoderConfig(vocab_size=32, hidden=16, num_heads=2,
                        num_layers=2, max_seq_len=32)
    core = _EngineCore(cfg, init_decoder_weights(cfg), page_size=4,
                       kv_dtype=kv_dtype, kv_budget_mb=0.03125, tp=2)
    plan = mp.plan_memory(core.decode_prog, feed_names=core.decode_feeds,
                          fetch_names=core.decode_fetch, scope=core.scope,
                          tp=core.tp, tp_rules=core._tp_rules)
    assert int(plan.resident_by_class["kv_pool"]) == \
        core.kv_pool_resident_bytes()
    modeled_w = sum(v["dev_bytes"] for v in plan.per_var.values()
                    if v["class"] == "state")
    assert int(modeled_w) == int(core.memory_stats()["weight_bytes"])


# ==========================================================================
# guards: infeasible degrees fail loud at construction
# ==========================================================================
def test_tp_degree_guard():
    bad = DecoderConfig(vocab_size=64, hidden=30, num_heads=3,
                        num_layers=1, max_seq_len=64)
    with pytest.raises(ValueError, match="does not divide"):
        make_engine(cfg=bad, tp=2)
    with pytest.raises(ValueError, match="num_heads=3"):
        validate_tp_degree(bad, 2)
    with pytest.raises(ValueError, match="serving_tp must be >= 1"):
        validate_tp_degree(CFG, -1)
    validate_tp_degree(CFG, 0)  # 0 == unset == 1 (the flag default)
    validate_tp_degree(CFG, 1)  # always feasible
    validate_tp_degree(CFG, 4)


def test_gqa_group_guard():
    from paddle_tpu.ops.pallas_kernels import _gqa_group

    assert _gqa_group(8, 2) == 4
    with pytest.raises(ValueError, match="GQA grouping"):
        _gqa_group(3, 2)
    with pytest.raises(ValueError, match="tensor-parallel"):
        _gqa_group(4, 0)


# ==========================================================================
# plan search: tp as a priced axis with pre-compile feasibility gating
# ==========================================================================
def test_plan_search_enumerates_and_prices_tp():
    from paddle_tpu.parallel.plan_search import search_plan

    cfg = DecoderConfig(vocab_size=256, hidden=256, num_heads=8,
                        num_layers=4, max_seq_len=128)
    prog, feeds, fetches = build_decoder_program(cfg, "decode")[:3]
    prog._tp_candidates = (2, 4)
    prog._tp_rule_set = decoder_tp_rules(cfg)
    prog._tp_extra_resident = {"kv_k_0": 32 << 20, "kv_v_0": 32 << 20}
    F.set_flags({"FLAGS_hbm_budget_mb": 40})  # tp=1 peak > 40 MB
    try:
        plan, report = search_plan(prog, feeds, fetches, ndev=1,
                                   use_shard_map=False, strict=False)
    finally:
        F.set_flags({"FLAGS_hbm_budget_mb": 0})
    assert plan.tp in (2, 4)
    assert not report["infeasible"]
    assert report["n_rejected"] > 0
    by_tp = {}
    for c in report["candidates"]:
        by_tp.setdefault(c["tp"], c)
    # every tp=1 row was rejected BEFORE compile on modeled peak
    assert all("rejected before compile" in (c["rejected"] or "")
               for c in report["candidates"] if c["tp"] == 1)
    # the TP collective term is priced (nonzero) and peaks scale down
    assert by_tp[2]["tp_comm_s"] > 0 and by_tp[4]["tp_comm_s"] > 0
    assert by_tp[4]["modeled_peak_mb"] < by_tp[2]["modeled_peak_mb"] \
        < by_tp[1]["modeled_peak_mb"]
    # the chosen plan round-trips tp through flag overrides
    assert plan.flag_overrides().get("serving_tp") == plan.tp
    assert plan.as_dict()["tp"] == plan.tp


def test_plan_tp_not_enumerated_without_opt_in():
    """Programs that never declare _tp_candidates keep the legacy
    candidate space (tp never looks free on non-TP-able programs)."""
    from paddle_tpu.parallel.plan_search import enumerate_candidates

    prog = build_decoder_program(CFG, "decode")[0]
    assert all(p.tp == 1 for p in
               enumerate_candidates(prog, ndev=1, use_shard_map=False))
