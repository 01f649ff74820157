"""The shortcut-connected latent-attention decoder with zero-computation
experts (``MLADecoderConfig(shortcut=True, ...)``, LongCat-Flash) against its
plain reference (benchmark/reference/longcat-flash-chat.py), at a small size
on the CPU: logits of prefill then decode through the paged latent pools (two
a layer), the softmax router, the identity term, the share of the experts,
the two scales on the low-rank streams, the counters, and what the
description refuses.
"""
import dataclasses
import importlib.util
import os

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.mla_decoder import (MLADecoderConfig,
                                              init_mla_weights)
from paddle_tpu.inference.serving import Request, ServingEngine
from paddle_tpu.ops import mla_kernels, mla_ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_reference():
    path = os.path.join(ROOT, "benchmark", "reference",
                        "longcat-flash-chat.py")
    spec = importlib.util.spec_from_file_location("ref_longcat", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load_reference()

# 2 layers = 4 sub-layers; 8 routed + 4 identity experts, top-3, experts 0-1
# held (a quarter share); both scales on; scaling 6, nothing normalised
TINY = MLADecoderConfig(
    vocab_size=128, hidden=64, num_heads=4, num_layers=2, first_k_dense=0,
    intermediate=96, moe_intermediate=32, n_routed_experts=8,
    n_shared_experts=0, num_experts_per_tok=3, q_lora_rank=32,
    kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rope_theta=1e7, rms_norm_eps=1e-5, routed_scaling_factor=6.0,
    norm_topk_prob=False, experts_held=2, shortcut=True,
    router_scoring="softmax", zero_experts=4, scale_q_lora=True,
    scale_kv_lora=True)
PROMPT_LENS = (5, 8, 9, 17, 30)       # page_size 8: under, at, over, 2+, 3+


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")


def make_engine(cfg=TINY, dtype="float32", seed=0, **kw):
    cfg = dataclasses.replace(cfg, weights_dtype=dtype)
    weights = init_mla_weights(cfg, seed)
    kw.setdefault("num_pages", 64)
    eng = ServingEngine(cfg=cfg, weights=weights, kv_dtype=dtype, page_size=8,
                        max_batch=4, token_budget=128, **kw)
    eng.core.keep_scores = True
    return eng, cfg, weights


def prompts_of(seed, lens=PROMPT_LENS, vocab=128):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, size=n).tolist() for n in lens]


def serve(eng, prompts, want=10):
    reqs = [Request(i, p, want) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    return reqs


def served_against_reference(eng, cfg, weights, reqs, **control):
    """Worst |engine - reference| of a served token's logit or its row's
    log-sum-exp, the reference routed as the engine was; the worst slack."""
    worst, slack = 0.0, 0.0
    for r in reqs:
        got, routes = eng.core.served_scores(r.req_id)
        assert len(got) == len(r.out_tokens)
        ref = REF.served_token_scores(weights, cfg.source_config(), r.prompt,
                                      r.out_tokens, routes, **control)
        assert ref["finite"]
        worst = max(worst, float(np.abs(got[:, 0] - ref["logit"]).max()),
                    float(np.abs(got[:, 1] - ref["lse"]).max()))
        slack = max(slack, float(ref["slack"].max()))
    return worst, slack


# -- the served path --------------------------------------------------------
# float32: summation order alone.  bfloat16: as tests/test_mla_decoder.py
@pytest.mark.parametrize("dtype,tol", [("float32", 3e-4), ("bfloat16", 8e-2)])
def test_prefill_then_decode_logits_match_reference(dtype, tol):
    eng, cfg, weights = make_engine(dtype=dtype)
    reqs = serve(eng, prompts_of(1), 12)
    assert eng.stats["preempted"] == 0
    worst, slack = served_against_reference(eng, cfg, weights, reqs)
    assert worst <= tol, worst
    # in units of 1 / 12 outputs: the engine's choice is the reference's to
    # within what the precision moves a score
    assert slack <= (1e-4 if dtype == "float32" else 5e-2), slack


@pytest.mark.parametrize("drop", ["routed", "identity"])
def test_a_dropped_term_of_the_expert_layer_is_seen(drop):
    """The reference without the held experts' sum, or without the identity
    term, is another model: the comparison reads tenths."""
    eng, cfg, weights = make_engine()
    reqs = serve(eng, prompts_of(2, lens=(9, 17)), 8)
    worst, _ = served_against_reference(eng, cfg, weights, reqs, drop=drop)
    assert worst > 0.05, worst


@pytest.mark.parametrize("n", [1, 7, 16, 33])
def test_reference_form_logits_match_reference(n):
    eng, cfg, weights = make_engine()
    seq = prompts_of(3, lens=(n,))[0]
    got = eng.core.reference_logits(seq)
    want = REF.logits_all_positions(weights, seq, cfg.source_config())[-1]
    np.testing.assert_allclose(got, want, atol=3e-4)


def test_preempted_and_resumed_requests_match_reference():
    # 12 pages of 8: four prompts of 17-20 tokens fit, their decodes do not
    eng, cfg, weights = make_engine(num_pages=12)
    reqs = serve(eng, prompts_of(2, lens=(17, 18, 19, 20)), 14)
    assert eng.stats["preempted"] > 0
    assert all(len(r.out_tokens) == 14 for r in reqs)
    worst, _ = served_against_reference(eng, cfg, weights, reqs)
    assert worst <= 3e-4, worst


def test_engine_through_the_kernels_matches_reference(interpreted):
    """Lanes of 128 so that every kernel of the cell engages (interpreted):
    ``mla_decode``, ``latent_append``, ``moe_gmm``, and with a prompt's worth
    of rows ``moe_rows_in`` / ``moe_combine``."""
    cfg = dataclasses.replace(
        TINY, hidden=128, moe_intermediate=128, intermediate=128,
        kv_lora_rank=96, qk_rope_head_dim=32, num_layers=1)
    assert mla_kernels.moe_rows_engage(30 * 3, 2, 128)
    eng, cfg, weights = make_engine(cfg)
    reqs = serve(eng, prompts_of(5, lens=(30, 9)), 5)
    worst, _ = served_against_reference(eng, cfg, weights, reqs)
    assert worst <= 5e-4, worst


def test_pipelined_steps_serve_the_plain_engines_tokens():
    plain, _, _ = make_engine()
    piped, _, _ = make_engine(pipeline=2)
    a = serve(plain, prompts_of(6))
    b = serve(piped, prompts_of(6))
    assert [r.out_tokens for r in a] == [r.out_tokens for r in b]


# -- the description ----------------------------------------------------------
def test_two_latent_pools_a_layer_and_the_layers_own_experts():
    cfg = TINY
    assert cfg.mla_layers == [0, 1, 2, 3]
    assert cfg.cache_pool_names() == [f"kv_lat_{i}" for i in range(4)]
    assert cfg.kv_cache_config(8, 8, "float32").num_layers == 4
    assert cfg.kv_token_bytes("bfloat16") == 4 * 24 * 2
    specs = cfg.param_specs()
    for j in range(4):            # every sub-layer: attention + dense half
        assert specs[f"dec_l{j}_wq_b"] == (32, 4 * 24)
        assert specs[f"dec_l{j}_w_gate"] == (64, 96)
    for j in (0, 2):              # a layer's experts under its first
        assert specs[f"dec_l{j}_router"] == (64, 12)
        assert specs[f"dec_l{j}_router_bias"] == (12,)
        assert specs[f"dec_l{j}_experts_gate"] == (2, 64, 32)
    assert "dec_l1_router" not in specs and "dec_l3_experts_up" not in specs
    # no shared expert: no (hidden, 0) matrices
    assert not [n for n in specs if "shared" in n]
    assert not [n for n, s in specs.items() if 0 in s]


def test_source_config_round_trips_under_the_sources_names():
    src = TINY.source_config()
    assert src["num_layers"] == 2 and src["ffn_hidden_size"] == 96
    assert src["expert_ffn_hidden_size"] == 32 and src["moe_topk"] == 3
    assert src["zero_expert_num"] == 4 and src["n_routed_experts"] == 2
    assert src["router_experts"] == 8
    assert MLADecoderConfig.from_source(src, max_seq_len=256) == TINY


def test_every_op_of_a_form_says_its_part():
    for mode in ("reference", "prefill", "decode", "verify"):
        prog = TINY.build_program(mode)[0]
        parts = [op.attrs.get("part") for op in prog.global_block().ops]
        assert all(parts)
        assert {"mla_part", "dense_ffn", "moe_part", "head", "embed"} \
            == set(parts)


@pytest.mark.parametrize("kw,match", [
    (dict(router_scoring="sigmoid"), "softmax"),
    (dict(mixers=("kda", "mla")), "shortcut"),
    (dict(mtp_layers=1), "shortcut"),
    (dict(first_k_dense=1), "shortcut"),
    (dict(router_scoring="tanh"), "sigmoid"),
])
def test_description_refuses_what_is_not_published(kw, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(TINY, **kw).validate()


def test_zero_expert_type_other_than_identity_is_refused():
    with pytest.raises(ValueError, match="identity"):
        MLADecoderConfig.from_source(
            dict(TINY.source_config(), zero_expert_type="constant"))


def test_the_two_scales_multiply_the_normed_low_rank_streams():
    """Without either scale the logits, on the SAME weights, are another
    model's: the reference, told the same, follows."""
    seq = prompts_of(7, lens=(12,))[0]
    eng, cfg, weights = make_engine()
    with_scales = eng.core.reference_logits(seq)
    for off in ("scale_q_lora", "scale_kv_lora"):
        cfg2 = dataclasses.replace(cfg, **{off: False})
        eng2 = ServingEngine(cfg=cfg2, weights=weights, kv_dtype="float32",
                             page_size=8, num_pages=64, max_batch=4,
                             token_budget=128)
        got = eng2.core.reference_logits(seq)
        assert float(np.abs(got - with_scales).max()) > 1e-2
        want = REF.logits_all_positions(weights, seq,
                                        cfg2.source_config())[-1]
        np.testing.assert_allclose(got, want, atol=3e-4)


def test_seeds_draw_the_scaled_streams_matrices_over_the_hidden_size():
    from paddle_tpu.inference.mla_decoder import seed_fan_in

    assert seed_fan_in(TINY, "dec_l0_wq_b", (32, 96)) == 64.0
    assert seed_fan_in(TINY, "dec_l0_wkv_b", (16, 128)) == 64.0
    assert seed_fan_in(TINY, "dec_l0_wo", (64, 64)) == 64.0
    plain = dataclasses.replace(TINY, scale_q_lora=False)
    assert seed_fan_in(plain, "dec_l0_wq_b", (32, 96)) == 32.0
    w = init_mla_weights(TINY, 0)
    assert abs(float(np.std(w["dec_l1_wq_b"])) * 8 - 1.0) < 0.1
    assert abs(float(np.std(w["dec_l0_router_bias"])) * 120 - 1.0) < 0.5


# -- the router ---------------------------------------------------------------
def _expert_layer(seed=0, n=24, h=64, f=32, routed=8, zero=4, k=3):
    r = np.random.RandomState(seed)
    w = {"router": r.randn(h, routed + zero) / 8,
         "router_bias": 0.01 * r.randn(routed + zero),
         "experts_gate": r.randn(routed, h, f) / 8,
         "experts_up": r.randn(routed, h, f) / 8,
         "experts_down": r.randn(routed, f, h) / 6}
    w = {name: jnp.asarray(v, jnp.float32) for name, v in w.items()}
    cfg = {"moe_topk": k, "n_routed_experts": routed,
           "router_experts": routed, "zero_expert_num": zero,
           "routed_scaling_factor": 6.0}
    return jnp.asarray(r.randn(n, h), jnp.float32), w, cfg


def test_softmax_router_is_the_references():
    x, w, cfg = _expert_layer()
    idx, weight = mla_ops.route(x, w["router"], w["router_bias"], 3, 6.0,
                                False, "softmax")
    s = np.asarray(jnp.exp(x @ w["router"]))
    s = s / s.sum(axis=1, keepdims=True)          # over all 12 outputs
    want = np.argsort(-(s + np.asarray(w["router_bias"])), axis=1)[:, :3]
    np.testing.assert_array_equal(np.sort(idx, axis=1), np.sort(want, axis=1))
    # the weight is 6 x the score itself: no bias in it, nothing normalised
    np.testing.assert_allclose(weight, 6.0 * np.take_along_axis(
        s, np.asarray(idx), axis=1), rtol=1e-5)
    assert not np.allclose(np.asarray(weight).sum(axis=1), 6.0)


def test_router_bias_moves_the_choice_and_not_the_weight():
    x, w, _ = _expert_layer(1)
    free = mla_ops.route(x, w["router"], jnp.zeros(12), 3, 6.0, False,
                         "softmax")
    push = jnp.zeros(12).at[11].set(10.0)         # an identity expert
    idx, weight = mla_ops.route(x, w["router"], push, 3, 6.0, False,
                                "softmax")
    assert bool((idx == 11).any(axis=1).all())
    assert not bool((free[0] == 11).any(axis=1).all())
    s = np.asarray(jnp.exp(x @ w["router"]))
    s = s / s.sum(axis=1, keepdims=True)
    np.testing.assert_allclose(weight, 6.0 * np.take_along_axis(
        s, np.asarray(idx), axis=1), rtol=1e-5)


def test_an_unknown_scoring_function_is_refused():
    x, w, _ = _expert_layer()
    with pytest.raises(ValueError, match="sigmoid or softmax"):
        mla_ops.route(x, w["router"], w["router_bias"], 3, 6.0, False, "tanh")


# -- the identity term and the share ----------------------------------------
def test_identity_experts_return_the_row_times_their_weight():
    x, w, _ = _expert_layer(2)
    n = x.shape[0]
    idx = jnp.tile(jnp.asarray([[8, 10, 11]], jnp.int32), (n, 1))
    weight = jnp.asarray(np.random.RandomState(0).rand(n, 3), jnp.float32)
    y, counts = mla_ops.experts_forward(
        x, idx, weight, w["experts_gate"], w["experts_up"],
        w["experts_down"], routed=8)
    assert int(counts.sum()) == 0
    np.testing.assert_allclose(y, weight.sum(axis=1, keepdims=True) * x,
                               rtol=1e-6)
    # a padded row has no identity term either
    valid = jnp.arange(n) < 20
    y, _ = mla_ops.experts_forward(
        x, idx, weight, w["experts_gate"], w["experts_up"],
        w["experts_down"], valid, routed=8)
    np.testing.assert_array_equal(np.asarray(y[20:]), 0.0)


def _shares(x, w, cfg, valid=None):
    """Whole layer by the reference; the four shares' routed parts by the
    program (chip c sees the routed experts rotated so that its own come
    first, the identity outputs where they are), and the identity part
    once."""
    whole = REF._moe(x, w, cfg, None, None, None)[0]
    idx, weight = mla_ops.route(x, w["router"], w["router_bias"], 3, 6.0,
                                False, "softmax")
    total, identity = 0.0, None
    for lo in range(0, 8, 2):
        turn = jnp.where(idx < 8, (idx - lo) % 8, idx)
        held = [w[n][lo:lo + 2]
                for n in ("experts_gate", "experts_up", "experts_down")]
        part, counts = mla_ops.experts_forward(x, turn, weight, *held, valid,
                                               share=True)
        both, counts2 = mla_ops.experts_forward(x, turn, weight, *held,
                                                valid, share=True, routed=8)
        np.testing.assert_array_equal(counts, counts2)
        live = idx if valid is None else idx[np.asarray(valid)]
        assert int(counts.sum()) == int(((live >= lo) & (live < lo + 2)).sum())
        # every chip computes the same identity part, whatever it holds
        if identity is None:
            identity = both - part
        np.testing.assert_allclose(both - part, identity, atol=1e-5)
        total = total + part
    return whole, total + identity, idx


def test_four_shares_and_the_identity_part_once_are_the_uncut_layer():
    x, w, cfg = _expert_layer(3)
    whole, summed, idx = _shares(x, w, cfg)
    assert bool((idx >= 8).any()) and bool((idx < 8).any())
    np.testing.assert_allclose(summed, whole, atol=2e-5)
    # and the reference's own share of chip 0 is the program's
    held = {n: (v[:2] if n.startswith("experts_") else v)
            for n, v in w.items()}
    ref_part = REF._moe(x, held, dict(cfg, n_routed_experts=2), None, None,
                        None)[0]
    weight = mla_ops.route(x, w["router"], w["router_bias"], 3, 6.0, False,
                           "softmax")[1]
    part, _ = mla_ops.experts_forward(
        x, idx, weight, held["experts_gate"], held["experts_up"],
        held["experts_down"], share=True, routed=8)
    np.testing.assert_allclose(part, ref_part, atol=2e-5)


def test_four_shares_by_the_kernels_are_the_uncut_layer(interpreted):
    x, w, cfg = _expert_layer(4, n=80, h=128, f=128)
    assert mla_kernels.moe_rows_engage(80 * 3, 2, 128)
    valid = jnp.arange(80) < 67
    whole, summed, _ = _shares(x, w, cfg, valid)
    np.testing.assert_allclose(summed[:67], np.asarray(whole)[:67], atol=3e-5)
    np.testing.assert_array_equal(np.asarray(summed[67:]), 0.0)


def test_every_expert_held_and_no_identity_is_the_path_before_bit_for_bit():
    x, w, _ = _expert_layer(5, zero=0)
    idx, weight = mla_ops.route(x, w["router"], w["router_bias"], 3, 6.0,
                                False, "softmax")
    args = (x, idx, weight, w["experts_gate"], w["experts_up"],
            w["experts_down"])
    y0, c0 = mla_ops.experts_forward(*args)
    y1, c1 = mla_ops.experts_forward(*args, routed=8)
    np.testing.assert_array_equal(y0, y1)
    np.testing.assert_array_equal(c0, c1)


# -- the counters ------------------------------------------------------------
def test_engine_counts_choices_on_held_and_identity_experts():
    eng, cfg, _ = make_engine()
    serve(eng, prompts_of(4, lens=(20, 9)), 4)
    moe = eng.core.moe_stats
    layers, k = cfg.num_layers, cfg.num_experts_per_tok
    for phase, rows in (("prefill", 29), ("decode", 2 * 3)):
        st = moe[phase]
        assert st["choices_all"] == rows * layers * k
        assert 0 < st["choices_identity"] < st["choices_all"]
        assert 0 < st["choices_held"] < st["choices_all"]
        assert st["choices_held"] + st["choices_identity"] \
            <= st["choices_all"]
    assert moe["prefill"]["layer_steps"] == 2 * layers
    # a row is absent only where none of its 3 choices is held OR identity
    assert moe["prefill"]["rows_all_absent"] < 0.3 * 29 * layers


def test_a_model_without_identity_experts_offers_no_choices():
    prog = MLADecoderConfig().build_program("decode")[0]
    assert prog._form_extras.choices is None
    assert TINY.build_program("decode")[0]._form_extras.choices \
        == "moe_choices"
