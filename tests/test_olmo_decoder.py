"""The Olmo-Hybrid-shaped decoder (``GQADecoderConfig`` with ``linear``
layers: Gated DeltaNet mixers with a rectangular state and one decay a head
beside full multi-head layers, dense throughout, the norms on the outputs)
against its plain reference (benchmark/reference/olmo-hybrid-7b.py), at a
small size on the CPU: the two kernels against the token-by-token recurrence,
logits (not tokens) of prefill then decode through the state slots and the
K/V pages, the cache manager with both, and what the engine refuses.
"""
import dataclasses
import importlib.util
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.gqa_decoder import (GQADecoderConfig, Rope,
                                              init_gqa_weights)
from paddle_tpu.inference.kv_cache import KVCacheConfig, PagedKVCache
from paddle_tpu.inference.serving import Request, ServingEngine
from paddle_tpu.ops import kda_kernels as kk

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    path = os.path.join(ROOT, "benchmark", "reference", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "ref_" + name.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("olmo-hybrid-7b")

# Olmo-Hybrid's shape: two periods of (linear, linear, linear, full); the
# linear layers' heads 24 x 48 (1 to 2, neither a multiple of the chip's 128
# lanes) and six of them, the full layers' four heads of 16 over as many K/V
# heads, no rotary, no gate, no expert layer
TINY = GQADecoderConfig(
    vocab_size=128, hidden=64, num_layers=8,
    mixers=("linear", "linear", "linear", "full") * 2,
    heads_full=4, heads_window=4, num_kv_heads=4, head_dim=16, window=0,
    gate=False, rope_full=Rope(lanes=0), rope_window=Rope(lanes=0),
    first_k_dense=8, intermediate=128, n_routed_experts=0,
    n_shared_experts=0, num_experts_per_tok=0,
    linear_heads=6, linear_key_dim=24, linear_value_dim=48,
    linear_neg_eigval=True, norm_after=True, qk_norm=True, max_seq_len=256)
PROMPT_LENS = (3, 8, 9, 13, 40)


def make_engine(cfg=TINY, dtype="float32", seed=0, **kw):
    cfg = dataclasses.replace(cfg, weights_dtype=dtype)
    weights = init_gqa_weights(cfg, seed)
    kw.setdefault("num_pages", 64)
    kw.setdefault("max_batch", 4)
    kw.setdefault("prefill_bucket_min", 8)
    kw.setdefault("token_budget", 256)
    eng = ServingEngine(cfg=cfg, weights=weights, kv_dtype=dtype, page_size=4,
                        **kw)
    eng.core.keep_scores = True
    return eng, cfg, weights


def prompts_of(seed, lens=PROMPT_LENS, vocab=128):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, size=n).tolist() for n in lens]


def served_against_reference(eng, cfg, weights, reqs):
    """Worst |engine - reference| of a served logit or log-sum-exp."""
    worst = 0.0
    for r in reqs:
        got, routes = eng.core.served_scores(r.req_id)
        assert routes is None and len(got) == len(r.out_tokens)
        ref = REF.served_token_scores(weights, cfg.source_config(), r.prompt,
                                      r.out_tokens)
        assert ref["finite"]
        worst = max(worst, float(np.abs(got[:, 0] - ref["logit"]).max()),
                    float(np.abs(got[:, 1] - ref["lse"]).max()))
    return worst


def serve(eng, prompts, want=12):
    reqs = [Request(i, p, want) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    assert all(len(r.out_tokens) == want for r in reqs)
    return reqs


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")


# -- the kernels against the recurrence ----------------------------------------
def inputs(t, heads, dk, dv, decay, seed=0, beta=(1.6, 2.0)):
    """Normalised ``q`` and ``k``, ``v``, one log-decay a head with rates up
    to ``decay`` (0: none at all) and a write strength near 2."""
    r = np.random.RandomState(seed)
    q, k = (r.randn(t, heads, dk).astype(np.float32) for _ in range(2))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * dk ** 0.5
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = r.randn(t, heads, dv).astype(np.float32)
    g = -np.exp(r.uniform(np.log(1e-3), np.log(decay), (t, heads))) \
        .astype(np.float32) if decay else np.zeros((t, heads), np.float32)
    b = r.uniform(*beta, (t, heads)).astype(np.float32)
    return tuple(jnp.asarray(x) for x in (q, k, v, g, b))


def recurrence(q, k, v, g, b, state=None):
    heads, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    return kk.kda_recurrence(
        q, k, v, jnp.broadcast_to(g[..., None], q.shape), b,
        jnp.zeros((heads, dk, dv)) if state is None else state)


def near(got, want, rel):
    return float(jnp.abs(got - want).max()) <= rel * float(jnp.abs(want).max())


# a prompt inside one chunk, one that ends inside its second, whole chunks
@pytest.mark.parametrize("t", [5, 37, 128, 200, 256])
@pytest.mark.parametrize("decay", [0.0, 0.05, 5.0],
                         ids=["none", "slow", "strong"])
@pytest.mark.parametrize("heads,dk,dv", [(6, 24, 48), (10, 24, 48),
                                         (2, 128, 128)])
def test_chunked_prefill_is_the_recurrence(interpreted, t, decay, heads, dk,
                                           dv):
    """At a rectangular state that fills no lane tile (6 and 10 heads: the
    factors of the published 30) and at an aligned square one, ``beta`` in
    [1.6, 2]: the output and the state within the rounding of the kernel's
    bfloat16 operands (the recurrence is float32 throughout)."""
    args = inputs(t, heads, dk, dv, decay, seed=t)
    o, s = kk.gdn_prefill(*args)
    o_ref, s_ref = recurrence(*args)
    assert o.shape == (t, heads, dv) and s.shape == (heads, dk, dv)
    assert near(o, o_ref, 2e-2) and near(s, s_ref, 2e-2)


def test_chunked_prefill_at_the_published_head_size(interpreted):
    args = inputs(150, 6, 96, 192, 0.1, seed=3)
    assert kk.gdn_prefill_grid(150, 6) == (128, 6, (1, 2))
    assert kk.gdn_prefill_grid(2048, 30) == (128, 6, (5, 16))
    o, s = kk.gdn_prefill(*args)
    o_ref, s_ref = recurrence(*args)
    assert near(o, o_ref, 2e-2) and near(s, s_ref, 2e-2)


def test_rows_past_the_prompt_leave_the_state_alone(interpreted):
    """A prompt of 45 in a bucket of 64: the padded rows carry ``g = 0`` and
    ``beta = 0`` and the state is that of the 45."""
    q, k, v, g, b = inputs(64, 6, 24, 48, 0.5, seed=5)
    live = (jnp.arange(64) < 45)[:, None]
    _, s = kk.gdn_prefill(q, k, v, jnp.where(live, g, 0.0),
                          jnp.where(live, b, 0.0))
    _, s_ref = recurrence(q[:45], k[:45], v[:45], g[:45], b[:45])
    assert near(s, s_ref, 2e-2)
    # and to the last bit whatever the padded rows hold
    _, again = kk.gdn_prefill(jnp.where(live[..., None], q, 7.0), k,
                              jnp.where(live[..., None], v, -3.0),
                              jnp.where(live, g, 0.0),
                              jnp.where(live, b, 0.0))
    assert bool((again == s).all())


def test_without_the_kernel_the_recurrence_serves():
    """Off the chip and not interpreted: the ``jnp`` recurrence, exactly."""
    args = inputs(33, 6, 24, 48, 0.5, seed=6)
    assert not kk.gdn_engages(6, 24, 48)
    o, s = kk.gdn_prefill(*args)
    o_ref, s_ref = recurrence(*args)
    assert bool((o == o_ref).all()) and bool((s == s_ref).all())


@pytest.mark.parametrize("heads,dk,dv,pack", [(6, 24, 48, 2), (10, 24, 48, 2),
                                              (30, 96, 192, 2),
                                              (2, 128, 128, 1),
                                              (3, 24, 48, 1)])
def test_the_pool_lays_heads_side_by_side(heads, dk, dv, pack):
    """Two heads share a row of lanes where one does not fill whole tiles and
    the heads pair up; packing is a permutation."""
    assert kk.gdn_pack(heads, dv) == pack
    assert kk.gdn_state_shape(heads, dk, dv) == (heads // pack, dk, pack * dv)
    s = jnp.asarray(np.random.RandomState(0).randn(3, heads, dk, dv)
                    .astype(np.float32))
    packed = kk.gdn_pack_states(s)
    assert packed.shape == (3,) + kk.gdn_state_shape(heads, dk, dv)
    assert bool((kk.gdn_unpack_states(packed, heads) == s).all())
    if pack == 2:     # head 1 lies beside head 0, on the lanes past d_v
        assert bool((packed[:, 0, :, dv:] == s[:, 1]).all())


def test_the_kernels_engage_at_the_published_sizes(monkeypatch):
    """On the chip: 96 x 192 in pairs, 128 x 128; not a lone 192."""
    monkeypatch.setattr(kk, "_use_pallas", lambda: True)
    monkeypatch.setattr(kk, "_interpret", lambda: False)
    assert kk.gdn_engages(30, 96, 192) and kk.gdn_engages(32, 128, 128)
    assert not kk.gdn_engages(15, 96, 192)
    assert not kk.gdn_engages(6, 24, 48)


@pytest.mark.parametrize("heads,dk,dv", [(6, 24, 48), (10, 24, 48),
                                         (2, 128, 128)])
@pytest.mark.parametrize("decay", [0.0, 5.0], ids=["none", "strong"])
def test_decode_kernel_rewrites_its_slots_and_no_other(interpreted, heads, dk,
                                                       dv, decay):
    """Five rows, two of them padding (the pad slot, ``g = 0``, ``beta =
    0``): the slots named are stepped as the recurrence steps them, every
    other slot and the padding's are bit for bit what they were."""
    q, k, v, g, b = inputs(5, heads, dk, dv, decay, seed=7)
    g, b = g.at[3:].set(0.0), b.at[3:].set(0.0)
    pool = jnp.asarray(np.random.RandomState(1).randn(
        8, *kk.gdn_state_shape(heads, dk, dv)).astype(np.float32))
    slots = jnp.asarray([3, 0, 6, 7, 7], jnp.int32)
    o, new = kk.gdn_decode(pool, slots, q, k, v, g, b)
    for row, slot in enumerate((3, 0, 6)):
        o_ref, s_ref = recurrence(
            q[row:row + 1], k[row:row + 1], v[row:row + 1], g[row:row + 1],
            b[row:row + 1], kk.gdn_unpack_states(pool[slot], heads))
        assert near(o[row], o_ref[0], 1e-5)
        assert near(kk.gdn_unpack_states(new[slot], heads), s_ref, 1e-5)
    for slot in (1, 2, 4, 5, 7):
        assert bool((new[slot] == pool[slot]).all())


def test_a_decode_step_continues_a_prefill(interpreted):
    """The state a prompt's prefill leaves, stepped by decode, is the state
    of the longer prompt."""
    q, k, v, g, b = inputs(41, 6, 24, 48, 0.5, seed=8)
    _, s40 = kk.gdn_prefill(q[:40], k[:40], v[:40], g[:40], b[:40])
    pool = jnp.zeros((3,) + kk.gdn_state_shape(6, 24, 48)) \
        .at[1].set(kk.gdn_pack_states(s40))
    o, pool = kk.gdn_decode(pool, jnp.asarray([1], jnp.int32), q[40:],
                            k[40:], v[40:], g[40:], b[40:])
    o_ref, s_ref = recurrence(q, k, v, g, b)
    assert near(o[0], o_ref[40], 2e-2)
    assert near(kk.gdn_unpack_states(pool[1], 6), s_ref, 2e-2)


# -- the model through the engine against the plain reference -------------------
@pytest.mark.parametrize("dtype,tol", [("float32", 3e-4), ("bfloat16", 8e-2)])
def test_prefill_then_decode_logits_match_reference(dtype, tol):
    """Prompts of five lengths, twelve tokens each: every served token's
    logit and log-sum-exp, as the prefill and decode programs computed them
    through the state slots and the K/V pages, against the reference's full
    forward pass on the same seeded weights."""
    eng, cfg, weights = make_engine(dtype=dtype)
    reqs = serve(eng, prompts_of(1))
    assert served_against_reference(eng, cfg, weights, reqs) <= tol


def test_engine_through_the_kernels_matches_reference(interpreted):
    """The same with both pairs of kernels interpreted (``gdn_*`` on the six
    linear layers, ``gqa_*`` on the two full ones), a prompt past a chunk
    among them; the engine counts what they took."""
    eng, cfg, weights = make_engine()
    reqs = serve(eng, prompts_of(1, lens=PROMPT_LENS + (150,)))
    assert served_against_reference(eng, cfg, weights, reqs) <= 5e-2
    k = eng.stats["kernels"]
    tokens, prompts = sum(PROMPT_LENS) + 150, len(PROMPT_LENS) + 1
    assert k["prefill"]["gdn_prefill_calls"] == 6 * prompts
    assert k["prefill"]["gdn_prefill_tokens"] == 6 * tokens
    # buckets of 8, 8, 16, 16, 64 and 256 tokens: one chunk each, and two
    assert k["prefill"]["gdn_prefill_chunks"] == 6 * 7
    assert k["prefill"]["gqa_prefill_calls"] == 2 * prompts
    steps = k["decode"]["gdn_decode_calls"] // 6
    assert k["decode"]["gqa_decode_calls"] == 2 * steps
    assert k["decode"]["gdn_decode_sequences"] == 6 * 11 * prompts
    assert k["decode"]["gqa_decode_sequences"] == 2 * 11 * prompts


@pytest.mark.parametrize("n", [1, 7, 8, 9, 33])
def test_reference_form_logits_match_reference(n):
    eng, cfg, weights = make_engine()
    seq = prompts_of(n, lens=(n,))[0]
    want = np.asarray(REF.logits_all_positions(
        weights, seq, cfg.source_config()))[-1]
    np.testing.assert_allclose(eng.core.reference_logits(seq), want,
                               atol=3e-4)


def test_the_lower_reference_is_another_answer():
    """The reading the benchmark's limits must refuse: weights and K/V rows
    through float8_e4m3fn, the state through bfloat16 after every token."""
    eng, cfg, weights = make_engine()
    seq = prompts_of(6, lens=(40,))[0]
    src = cfg.source_config()
    plain = np.asarray(REF.logits_all_positions(weights, seq, src))
    lower = np.asarray(REF.logits_all_positions(weights, seq, src,
                                                lower="float8_e4m3fn"))
    assert float(np.abs(plain - lower).max()) > 0.05


def test_a_stale_state_would_be_seen():
    """A prompt's prefill rewrites its whole slot (pools spoiled before it
    change nothing); pools spoiled after it move the logits."""
    eng, cfg, weights = make_engine()
    prompt = prompts_of(9, lens=(13,))[0]

    def spoil():
        for name in eng.core._state_specs:
            eng.core.scope.set(name, eng.core.scope.get(name) + 0.5)

    spoil()
    a = Request("a", prompt, 5)
    eng.submit(a)
    eng.run_to_completion()
    assert served_against_reference(eng, cfg, weights, [a]) <= 3e-4
    b = Request("b", prompt, 5)
    eng.submit(b)
    eng.step()
    spoil()
    eng.run_to_completion()
    assert served_against_reference(eng, cfg, weights, [b]) > 1e-2


@pytest.mark.parametrize("depth", [1, 2])
def test_pipelined_steps_and_preemption_serve_the_plain_engines_tokens(depth):
    """A page pool so small that sequences are preempted and resumed: slot
    and pages both come back, the resumed prefill rebuilds the state, and the
    tokens are the roomy engine's."""
    prompts = prompts_of(4, lens=(9, 13, 30, 17))
    plain = [r.out_tokens for r in serve(make_engine()[0], prompts, 14)]
    eng, cfg, weights = make_engine(num_pages=22, pipeline=depth)
    reqs = serve(eng, prompts, 14)
    assert [r.out_tokens for r in reqs] == plain
    assert eng.stats["preempted"] > 0
    stats = eng.kv.stats()
    assert stats["state_slots"]["freed_by_preemption"] \
        == eng.stats["preempted"]
    assert stats["state_slots"]["in_use"] == 0 and stats["pages_in_use"] == 0


def test_a_batch_of_two_hands_each_freed_slot_on():
    eng, cfg, weights = make_engine(max_batch=2)
    reqs = [Request(i, p, n) for i, (p, n) in enumerate(zip(
        prompts_of(3, lens=(9, 21, 12, 30, 7)), (3, 9, 6, 4, 8)))]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    slots = eng.kv.stats()["state_slots"]
    assert slots["total"] == 2 and slots["peak"] == 2
    assert served_against_reference(eng, cfg, weights, reqs) <= 3e-4


# -- the description ---------------------------------------------------------------
def test_pools_are_pages_for_the_full_layers_and_slots_for_the_linear():
    eng, cfg, _ = make_engine()
    assert cfg.cache_pool_names() == ["kv_k_3", "kv_v_3", "kv_k_7", "kv_v_7"]
    assert eng.core.kv_config.num_layers == 2
    assert eng.core.kv_config.state_slots == 4
    assert not eng.core.kv_config.window
    assert eng.core.scope.get("kv_k_3").shape == (4, 64, 4, 16)
    assert eng.core.scope.get("gdn_state_0").shape == (5, 3, 24, 96)
    assert eng.core.scope.get("gdn_conv_0").shape == (5, 3, 6 * 96)
    assert sorted(cfg.state_pool_specs(4)) == sorted(
        f"gdn_{kind}_{i}" for i in (0, 1, 2, 4, 5, 6)
        for kind in ("state", "conv"))
    assert cfg.kv_token_bytes("float32") == 2 * 2 * 4 * 16 * 4
    assert cfg.state_slot_bytes() == 6 * (6 * 24 * 48 + 3 * 6 * 96) * 4
    for mode in ("prefill", "decode"):
        assert "state_slots" in cfg.build_program(mode)[1]
    assert "state_slots" not in cfg.build_program("reference")[1]


def test_a_dense_decoder_carries_no_counts_and_no_routes():
    for mode in ("reference", "prefill", "decode"):
        prog = TINY.build_program(mode)[0]
        offers = prog._form_extras
        assert offers.counts is None and offers.routes is None
        assert offers.absent is None and offers.routes_all is None
        kinds = {op.type for op in prog.global_block().ops}
        assert "gdn_mixer" in kinds and not kinds & {"moe_router",
                                                     "moe_experts",
                                                     "rope_half"}
    assert not [n for n in TINY.param_specs() if "router" in n
                or "expert" in n or n.endswith("wg")]


def test_every_op_of_a_layer_names_its_part():
    """The linear mixer's ops under ``gdn_part``, the full layers' under
    ``attn_full``, every feed-forward under ``dense_ffn``."""
    prog = TINY.build_program("decode", kv_dtype="float32")[0]
    parts = {}
    for op in prog.global_block().ops:
        parts.setdefault(op.attrs.get("part"), set()).add(op.type)
    assert set(parts) == {"embed", "gdn_part", "attn_full", "dense_ffn",
                          "head"}
    assert "gdn_mixer" in parts["gdn_part"]
    assert "gqa_paged_attention" in parts["attn_full"]
    assert "swiglu" in parts["dense_ffn"]


@pytest.mark.parametrize("kw,match", [
    (dict(tp=2), "tensor-parallel"),
    (dict(kv_dtype="int8"), "int8"),
    (dict(prefix_cache=True), "chunk"),
    (dict(prefill_chunk=16), "chunk"),
    (dict(spec_k=2), "recurrent state"),
])
def test_description_refuses_what_is_not_built(kw, match):
    with pytest.raises(ValueError, match=match):
        TINY.validate(**kw)


@pytest.mark.parametrize("change,match", [
    (dict(mixers=("linear",) * 8), "full-attention layer"),
    (dict(linear_heads=0), "linear_heads"),
    (dict(first_k_dense=4), "norm_after"),
    (dict(mixers=("linear", "kda") * 4), "'linear'"),
])
def test_description_refuses_a_bad_layer_list(change, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(TINY, **change).validate()


def test_engine_refuses_at_construction():
    for kw, match in ((dict(prefix_cache=True), "prefix"),
                      (dict(spec_k=2), "speculative"),
                      (dict(prefill_chunk=16), "chunk")):
        with pytest.raises(ValueError, match=match):
            make_engine(**kw)
    with pytest.raises(ValueError, match="no 'chunk' form"):
        TINY.build_program("chunk")


def test_source_config_round_trips():
    src = TINY.source_config()
    assert src["layer_types"][:4] == ["linear_attention"] * 3 \
        + ["full_attention"]
    assert src["rope_parameters"] == {"rope_theta": None}
    assert GQADecoderConfig.from_source(src, max_seq_len=256) == TINY


def test_the_published_configuration_is_2436_million_parameters():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "olmo-hybrid-7b.json")) as f:
        size = json.load(f)
    cfg = GQADecoderConfig.from_source(
        size, max_seq_len=size["deployment"]["max_context"],
        weights_dtype=size["weights_dtype"])
    specs = cfg.param_specs()
    count = sum(int(np.prod(s)) for s in specs.values())
    assert count == 2 * 832_520_436 + 770_703_360 + 3_840 == 2_435_748_072
    linear = sum(int(np.prod(s)) for n, s in specs.items()
                 if n.startswith("dec_l0_"))
    full = sum(int(np.prod(s)) for n, s in specs.items()
               if n.startswith("dec_l3_"))
    assert (linear, full) == (215_570_172, 185_809_920)
    assert cfg.mixers == ("linear", "linear", "linear", "full") * 2
    assert (cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim) \
        == (30, 96, 192) and cfg.linear_neg_eigval
    assert cfg.head_dim == 128 and cfg.num_kv_heads == 30
    assert cfg.norm_after and cfg.qk_norm and not cfg.gate
    assert cfg.rope_full.lanes == 0 and cfg.first_k_dense == 8
    # 30 x 96 x 192 float32 of state, nothing padded, and 3 x 11,520 of tail
    assert cfg.state_slot_bytes() == 6 * (2_211_840 + 138_240)
    assert cfg.state_pool_specs(128)["gdn_state_0"][0] == (129, 15, 96, 384)
    assert cfg.kv_token_bytes("bfloat16") == 30_720


# -- the cache manager with slots and K/V pages together ------------------------
def cache(pages=8, slots=2):
    return PagedKVCache(KVCacheConfig(num_pages=pages, page_size=4,
                                      num_kv_heads=4, head_dim=16,
                                      num_layers=2, state_slots=slots))


def test_admission_waits_for_a_slot_and_for_pages():
    kv = cache()
    assert kv.append_tokens("a", 9) is not None          # 3 pages, slot 0
    assert kv.append_tokens("b", 8) is not None          # 2 pages, slot 1
    assert (kv.state_slot("a"), kv.state_slot("b")) == (0, 1)
    # pages are free (3), slots are not: refused, nothing mutated
    assert kv.num_free_pages == 3 and not kv.can_append("c", 1)
    assert kv.append_tokens("c", 1) is None and "c" not in kv.live_sequences()
    kv.free_sequence("b")
    # a slot is free, pages are not (5 free, 6 asked): refused, the slot kept
    assert kv.append_tokens("c", 21) is None
    assert kv.state_slots_in_use == 1 and kv.num_free_pages == 5
    assert kv.append_tokens("c", 20) is not None and kv.state_slot("c") == 1
    # a live sequence grows by pages alone, and is refused for want of one
    assert kv.append_tokens("a", 4) is None
    assert kv.state_slot("a") == 0


def test_release_and_preemption_return_slot_and_pages():
    kv = cache()
    kv.append_tokens("a", 9)
    kv.append_tokens("b", 8)
    kv.free_sequence("a", preempted=True)
    assert kv.num_free_pages == 6 and kv.state_slots_in_use == 1
    assert kv.append_tokens("c", 3) is not None and kv.state_slot("c") == 0
    kv.free_sequence("b")
    kv.free_sequence("c")
    stats = kv.stats()
    assert stats["pages_in_use"] == 0
    assert stats["state_slots"] == {"total": 2, "in_use": 0, "peak": 2,
                                    "freed_by_preemption": 1}


def test_a_cache_with_slots_and_pages_refuses_sharing_and_roll_back():
    with pytest.raises(ValueError, match="prefix"):
        PagedKVCache(cache().config, prefix_cache=True)
    kv = cache()
    kv.append_tokens("a", 6)
    with pytest.raises(ValueError, match="roll"):
        kv.truncate_tokens("a", 2)
