"""The hybrid decoder (``MLADecoderConfig`` with ``mixers``: gated delta-rule
KDA layers beside latent-attention layers, an expert layer that holds a share
of its experts) against its plain reference
(benchmark/reference/kimi-linear-48b-a3b.py), at a small size on the CPU:
the chunked KDA prefill against the recurrence, logits (not tokens) of
prefill then decode through the state slots and the paged latent cache,
padding, preemption and slot reuse, pipelined steps, the share of the
experts, what the engine refuses, and the cache manager's slots.
"""
import dataclasses
import importlib.util
import os

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.kv_cache import KVCacheConfig, PagedKVCache
from paddle_tpu.inference.mla_decoder import (MLADecoderConfig,
                                              init_mla_weights)
from paddle_tpu.inference.serving import Request, ServingEngine
from paddle_tpu.ops import kda_kernels, mla_ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    path = os.path.join(ROOT, "benchmark", "reference", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "ref_" + name.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("kimi-linear-48b-a3b")

# two periods of (KDA, KDA, KDA, MLA), the first layer's FFN dense, 8 experts
# of which 4 are held, top-2, no query low-rank, NoPE: Kimi-Linear's shape
TINY = MLADecoderConfig(
    vocab_size=128, hidden=64, num_heads=4, num_layers=8, first_k_dense=1,
    intermediate=128, moe_intermediate=32, n_routed_experts=8,
    experts_held=4, num_experts_per_tok=2, q_lora_rank=0, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, rope=False,
    rms_norm_eps=1e-5, routed_scaling_factor=2.446,
    mixers=("kda", "kda", "kda", "mla") * 2, kda_heads=4, kda_head_dim=16,
    kda_gate_rank=16, max_seq_len=256)
PROMPT_LENS = (5, 8, 9, 17, 30)


def make_engine(cfg=TINY, dtype="float32", seed=0, **kw):
    cfg = dataclasses.replace(cfg, weights_dtype=dtype)
    weights = init_mla_weights(cfg, seed)
    kw.setdefault("num_pages", 64)
    kw.setdefault("max_batch", 4)
    eng = ServingEngine(cfg=cfg, weights=weights, kv_dtype=dtype, page_size=8,
                        token_budget=128, **kw)
    eng.core.keep_scores = True
    return eng, cfg, weights


def prompts_of(seed, lens=PROMPT_LENS, vocab=128):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, size=n).tolist() for n in lens]


def served_against_reference(eng, cfg, weights, reqs, prompt_rows=False):
    """Worst |engine - reference| of a served logit or log-sum-exp, and the
    worst routing slack; ``prompt_rows``: the reference follows the engine's
    routing on the prompts' rows too (as the benchmark's runner has it)."""
    worst, slack = 0.0, 0.0
    for r in reqs:
        got, routes = eng.core.served_scores(r.req_id)
        assert len(got) == len(r.out_tokens)
        ref = REF.served_token_scores(
            weights, cfg.source_config(), r.prompt, r.out_tokens, routes,
            prompt_routes=eng.core.prompt_routes(r.req_id)
            if prompt_rows else None)
        assert ref["finite"]
        worst = max(worst, float(np.abs(got[:, 0] - ref["logit"]).max()),
                    float(np.abs(got[:, 1] - ref["lse"]).max()))
        slack = max(slack, float(ref["slack"].max(initial=0.0)))
    return worst, slack


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")


# -- the chunked prefill against the recurrence -------------------------------
def kda_case(t, heads, d, decay, seed=0):
    """``(qkv, g, beta)`` as the kernel takes them (the convolution's outputs
    before the l2 norm) and ``(q, k, v)`` as the recurrence does."""
    r = np.random.RandomState(seed)
    qkv = jnp.asarray(r.randn(t, 3 * heads * d), jnp.float32)
    g = jnp.asarray(-decay * np.abs(r.randn(t, heads, d)), jnp.float32)
    beta = jnp.asarray(1 / (1 + np.exp(-r.randn(t, heads))), jnp.float32)
    return (qkv, g, beta), kda_kernels.normalised_heads(qkv, heads, 1e-6)


# the chunk is 128: lengths under, at, just over, at two and between; decay
# from gentle to a state wiped every token (g near -20: exp(-G) of the
# textbook form would overflow float32 within a chunk)
@pytest.mark.parametrize("t", [5, 16, 128, 129, 256, 300])
@pytest.mark.parametrize("decay", [0.05, 3.0, 20.0])
def test_chunked_prefill_is_the_recurrence(interpreted, t, decay):
    (qkv, g, beta), (q, k, v) = kda_case(t, 2, 16, decay, seed=t)
    want_o, want_s = kda_kernels.kda_recurrence(
        q, k, v, g, beta, jnp.zeros((2, 16, 16)))
    got_o, got_s = kda_kernels.kda_prefill(qkv, g, beta, 2)
    assert bool(jnp.isfinite(got_o).all()) and bool(jnp.isfinite(got_s).all())
    # matmul operands are bfloat16: 2^-9 relative an operand
    np.testing.assert_allclose(got_o, want_o, atol=6e-3)
    np.testing.assert_allclose(got_s, want_s, atol=2e-2)


def test_chunked_prefill_at_the_published_head_size(interpreted):
    (qkv, g, beta), (q, k, v) = kda_case(260, 2, 128, 0.5)
    want_o, want_s = kda_kernels.kda_recurrence(
        q, k, v, g, beta, jnp.zeros((2, 128, 128)))
    got_o, got_s = kda_kernels.kda_prefill(qkv, g, beta, 2)
    np.testing.assert_allclose(got_o, want_o, atol=2e-3)
    np.testing.assert_allclose(got_s, want_s, atol=2e-2)


def test_rows_past_the_prompt_leave_the_state_alone(interpreted):
    (qkv, g, beta), _ = kda_case(256, 2, 16, 0.5)
    live = jnp.arange(256) < 137
    _, want = kda_kernels.kda_prefill(qkv[:137], g[:137], beta[:137], 2)
    _, got = kda_kernels.kda_prefill(
        qkv, jnp.where(live[:, None, None], g, 0.0),
        jnp.where(live[:, None], beta, 0.0), 2)
    # bfloat16 operands apart; a padded row that wrote or decayed anything
    # would move the state by tenths
    np.testing.assert_allclose(got, want, atol=6e-3)


# a grid step takes a group of heads: 1, 2, 4 and 8 heads are one group, 3
# fall to one head a step; under, at and over a chunk; the three decays above
@pytest.mark.parametrize("heads", [1, 2, 3, 4, 8])
@pytest.mark.parametrize("t", [5, 128, 300])
@pytest.mark.parametrize("decay", [0.05, 3.0, 20.0])
def test_a_group_of_heads_is_one_head_a_step_bit_for_bit(
        interpreted, heads, t, decay):
    (qkv, g, beta), _ = kda_case(t, heads, 16, decay, seed=t + heads)
    chunk, group, grid = kda_kernels.prefill_grid(t, heads, 16)
    assert group == (1 if heads == 3 else heads)
    assert grid == (heads // group, -(-t // chunk))
    call = kda_kernels._kda_prefill_call.__wrapped__
    want_o, want_s = call(qkv, g, beta, heads=heads, chunk=chunk, group=1,
                          l2_eps=1e-6)
    got_o, got_s = call(qkv, g, beta, heads=heads, chunk=chunk, group=group,
                        l2_eps=1e-6)
    np.testing.assert_array_equal(got_o, want_o)
    np.testing.assert_array_equal(got_s, want_s)


def test_the_wrapper_takes_the_group_of_prefill_grid(interpreted):
    (qkv, g, beta), _ = kda_case(200, 4, 16, 0.5)
    want_o, want_s = kda_kernels._kda_prefill_call.__wrapped__(
        qkv, g, beta, heads=4, chunk=128, group=1, l2_eps=1e-6)
    got_o, got_s = kda_kernels.kda_prefill(qkv, g, beta, 4)
    np.testing.assert_array_equal(got_o, want_o)
    np.testing.assert_array_equal(got_s, want_s)


@pytest.mark.parametrize("t,heads,d,want", [
    (8192, 32, 128, (128, 4, (8, 64))),     # the cell's buckets
    (4096, 32, 128, (128, 4, (8, 32))),
    (2048, 32, 128, (128, 4, (8, 16))),
    (16, 32, 128, (16, 8, (4, 1))),         # a prompt under a chunk
    (300, 3, 16, (128, 1, (3, 3))),         # no group divides three heads
    (300, 12, 128, (128, 4, (3, 3))),
    (300, 8, 16, (128, 8, (1, 3))),         # narrow heads: all eight
    (8192, 32, 256, (128, 2, (16, 64))),    # wider heads: VMEM sets the group
])
def test_prefill_grid_comes_from_the_shapes(t, heads, d, want):
    assert kda_kernels.prefill_grid(t, heads, d) == want


def test_hybrid_walk_counts_the_grid_the_wrapper_uses():
    from paddle_tpu.inference.mla_decoder import _hybrid_walk

    cfg = dataclasses.replace(TINY, kda_heads=32, kda_head_dim=128)
    feed = {"tokens": np.zeros((1, 8192), np.int32),
            "last_index": np.asarray([4999], np.int32)}
    kda = len(cfg.kda_layers)
    assert _hybrid_walk(feed, None, mode="prefill", cfg=cfg) == {
        "kda_prefill_calls": kda, "kda_prefill_tokens": 5000 * kda,
        "kda_prefill_grid_steps": 8 * 64 * kda}


def test_decode_kernel_rewrites_its_slots_and_no_other(interpreted):
    r = np.random.RandomState(1)
    pool = jnp.asarray(r.randn(6, 4, 16, 16), jnp.float32)
    slots = jnp.asarray([3, 0, 5, 5], jnp.int32)       # two padded rows
    (_, g, beta), (q, k, v) = kda_case(4, 4, 16, 1.0)
    g, beta = g.at[2:].set(0.0), beta.at[2:].set(0.0)
    want_o, want_pool = kda_kernels.kda_decode_reference(
        pool, slots, q, k, v, g, beta)
    got_o, got_pool = kda_kernels.kda_decode(pool, slots, q, k, v, g, beta)
    np.testing.assert_allclose(got_o[:2], want_o[:2], atol=1e-5)
    np.testing.assert_allclose(got_pool, want_pool, atol=1e-5)
    # slots no row names, and the padding's, are what they were
    for untouched in (1, 2, 4, 5):
        np.testing.assert_array_equal(got_pool[untouched], pool[untouched])


def test_short_conv_step_continues_the_sequence():
    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(9, 12), jnp.float32)
    w = jnp.asarray(r.randn(12, 4), jnp.float32)
    whole = kda_kernels.short_conv(x, w)
    tail = kda_kernels.short_conv_tail(x[:8], 7, 4)
    np.testing.assert_array_equal(tail, x[5:8])
    y, new_tail = kda_kernels.short_conv_step(tail[None], x[8:9], w)
    np.testing.assert_allclose(y[0], whole[8], atol=1e-6)
    np.testing.assert_array_equal(new_tail[0], x[6:9])
    # a prompt shorter than the taps: zeros before the sequence
    np.testing.assert_array_equal(
        kda_kernels.short_conv_tail(x[:8], 0, 4),
        jnp.concatenate([jnp.zeros((2, 12)), x[:1]]))


# -- through the engine ---------------------------------------------------------
@pytest.mark.parametrize("dtype,tol", [("float32", 3e-4), ("bfloat16", 8e-2)])
def test_prefill_then_decode_logits_match_reference(dtype, tol):
    eng, cfg, weights = make_engine(dtype=dtype)
    reqs = [Request(i, p, 12) for i, p in enumerate(prompts_of(1))]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    assert eng.stats["preempted"] == 0
    worst, slack = served_against_reference(eng, cfg, weights, reqs)
    assert worst <= tol, worst
    assert slack <= (1e-5 if dtype == "float32" else 2e-2), slack
    slots = eng.kv.stats()["state_slots"]
    assert slots == {"total": 4, "in_use": 0, "peak": 4,
                     "freed_by_preemption": 0}


def test_engine_through_the_kernels_matches_reference(interpreted):
    """The KDA kernels' bodies under the engine (the other kernels want
    lanes of 128).  Every FFN dense: a bfloat16 operand's rounding in a
    prompt row would else flip a tiny router's choice there now and then,
    which the reference, routing the prompt's rows alone, does not follow."""
    eng, cfg, weights = make_engine(
        dataclasses.replace(TINY, first_k_dense=TINY.num_layers))
    reqs = [Request(i, p, 6) for i, p in
            enumerate(prompts_of(5, lens=(9, 70, 33)))]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    worst, _ = served_against_reference(eng, cfg, weights, reqs)
    assert worst <= 2e-2, worst          # the kernels' bfloat16 operands
    kernels = eng.stats["kernels"]
    kda = len(cfg.kda_layers)
    # buckets of 16, 128 and 64 rows: each under a chunk, four heads a step
    assert kernels["prefill"] == {"kda_prefill_calls": 3 * kda,
                                  "kda_prefill_tokens": (9 + 70 + 33) * kda,
                                  "kda_prefill_grid_steps": 3 * kda}
    assert kernels["decode"]["kda_decode_calls"] == 5 * kda
    assert kernels["decode"]["kda_decode_sequences"] == 15 * kda


def test_prompt_rows_routed_as_the_engine_was_take_the_flips_away(
        interpreted):
    """bfloat16 weights, the KDA kernels' bfloat16 operands and a tiny
    router: a prompt row's expert flips now and then, and its neighbours
    carry that into the first served rows.  Routed as the engine was on the
    prompt's rows too (each choice held to the reference's own scores by the
    slack), the comparison is continuous again."""
    eng, cfg, weights = make_engine(dtype="bfloat16")
    reqs = [Request(i, p, 6) for i, p in
            enumerate(prompts_of(5, lens=(9, 70, 33, 50, 21)))]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    rows = eng.core.prompt_routes(0)
    assert rows.shape[0] == cfg.num_layers - cfg.first_k_dense
    assert rows.shape[1] >= 9 and rows.shape[2] == cfg.num_experts_per_tok
    alone, _ = served_against_reference(eng, cfg, weights, reqs)
    followed, slack = served_against_reference(eng, cfg, weights, reqs,
                                               prompt_rows=True)
    assert followed <= 8e-2, followed
    assert followed <= alone
    assert slack <= 5e-2, slack


@pytest.mark.parametrize("n", [1, 7, 16, 33])
def test_reference_form_logits_match_reference(n):
    eng, cfg, weights = _shared_engine()
    seq = prompts_of(3, lens=(n,))[0]
    got = eng.core.reference_logits(seq)
    want = np.asarray(REF.logits_all_positions(
        weights, seq, cfg.source_config()))[-1]
    np.testing.assert_allclose(got, want, atol=3e-4)


_ENGINE = {}


def _shared_engine():
    if "e" not in _ENGINE:
        _ENGINE["e"] = make_engine()
    return _ENGINE["e"]


def _pools(eng):
    return {n: np.array(eng.core.scope.get(n))
            for n in eng.core._state_specs}


def test_a_padded_prompt_writes_its_slot_and_no_other():
    """A prompt of 19 runs in a bucket of 32: the slot holds the state after
    token 19 and the tail of tokens 17-19, whatever the 13 padded rows were;
    all-padding feeds (the warm-up's) write the padding's slot alone."""
    eng, cfg, weights = make_engine()
    core = eng.core
    before = _pools(eng)
    pad = core.kv_config.pad_slot
    core._run(core.prefill_prog, {
        "tokens": np.full((1, 32), 7, np.int32),
        "positions": np.arange(32, dtype=np.int32)[None],
        "slot_mapping": np.full(32, pad, np.int32),
        "last_index": np.zeros(1, np.int32),
        "state_slots": np.full(1, core.kv_config.pad_state_slot, np.int32)},
        core.prefill_fetch, "warm")
    core._run(core.decode_prog, {
        "tokens": np.full(4, 7, np.int32), "positions": np.zeros(4, np.int32),
        "block_tables": np.zeros((4, 1), np.int32),
        "context_lens": np.ones(4, np.int32),
        "slot_mapping": np.full(4, pad, np.int32),
        "state_slots": np.full(4, core.kv_config.pad_state_slot, np.int32)},
        core.decode_fetch, "warm")
    after = _pools(eng)
    for name in before:
        np.testing.assert_array_equal(after[name][:-1], before[name][:-1])
        if "state" in name:          # beta = 0, g = 0: zero stays zero
            assert not after[name][-1].any()
    # the state a real prompt leaves is that of its real tokens alone: the
    # same prompt in a bucket of 32 and as the first 19 rows of a longer one
    prompt = prompts_of(7, lens=(19,))[0]
    assert core.prefill_job(Request("a", prompt, 5)) is not None
    slot = eng.kv.state_slot("a")
    name = f"kda_state_{cfg.kda_layers[0]}"
    held = np.array(core.scope.get(name))[slot]
    x = jnp.asarray(np.asarray(weights["dec_embed"])[prompt], jnp.float32)
    # layer 0's own inputs through the op's parts, no padding anywhere
    from paddle_tpu.ops import kda_ops
    w = {slot_: jnp.asarray(weights[f"dec_l0_{nm}"])
         for nm, slot_ in __import__(
             "paddle_tpu.inference.mla_decoder",
             fromlist=["_KDA_SLOTS"])._KDA_SLOTS.items()}
    xn = x * jnp.reciprocal(jnp.sqrt(jnp.mean(x * x, -1, keepdims=True)
                                     + cfg.rms_norm_eps))
    pre, g, beta = kda_ops.kda_inputs(xn, w, cfg.kda_heads, cfg.kda_head_dim)
    import jax
    q, k, v = kda_kernels.normalised_heads(
        jax.nn.silu(kda_kernels.short_conv(pre, w["Conv"])), cfg.kda_heads,
        1e-6)
    _, want = kda_kernels.kda_recurrence(
        q, k, v, g, beta,
        jnp.zeros((cfg.kda_heads, cfg.kda_head_dim, cfg.kda_head_dim)))
    np.testing.assert_allclose(held, want, atol=1e-5)
    np.testing.assert_allclose(
        np.array(core.scope.get("kda_conv_0"))[slot], pre[16:19], atol=1e-6)


def test_a_padded_decode_batch_leaves_the_other_slots_alone():
    eng, cfg, weights = make_engine()
    reqs = [Request(i, p, 8) for i, p in
            enumerate(prompts_of(8, lens=(6, 11, 20)))]      # 3 rows of 4
    for r in reqs:
        eng.submit(r)
    eng.step()
    free = [s for s in range(4)
            if s not in {eng.kv.state_slot(r.req_id) for r in reqs}]
    before = _pools(eng)
    eng.step()
    after = _pools(eng)
    for name in before:
        for s in free:
            np.testing.assert_array_equal(after[name][s], before[name][s])
        if "state" in name:
            assert not after[name][-1].any()


def test_preempted_resumed_and_reused_slots_match_reference():
    """Four prompts on 12 pages: their decodes outgrow the pool, the youngest
    is preempted (its slot freed) and resumed later by a prefill that
    rebuilds its state; then a batch of two (so two slots) for five
    requests, so every finish hands its slot to a newcomer.  A state left
    over from the slot's last owner, or from before the preemption, would
    move the logits by tenths."""
    eng, cfg, weights = make_engine(num_pages=12, max_batch=4)
    reqs = [Request(i, p, 14) for i, p in
            enumerate(prompts_of(2, lens=(17, 18, 19, 20)))]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    assert eng.stats["preempted"] > 0
    slots = eng.kv.stats()["state_slots"]
    assert slots["freed_by_preemption"] == eng.stats["preempted"]
    assert slots["in_use"] == 0
    assert all(len(r.out_tokens) == 14 for r in reqs)
    worst, _ = served_against_reference(eng, cfg, weights, reqs)
    assert worst <= 3e-4, worst

    eng, cfg, weights = make_engine(max_batch=2)
    reqs = [Request(i, p, n) for i, (p, n) in enumerate(zip(
        prompts_of(3, lens=(9, 21, 12, 30, 7)), (3, 9, 6, 4, 8)))]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    slots = eng.kv.stats()["state_slots"]
    assert slots["total"] == 2 and slots["peak"] == 2
    assert eng.stats["admitted"] == 5 and eng.stats["preempted"] == 0
    worst, _ = served_against_reference(eng, cfg, weights, reqs)
    assert worst <= 3e-4, worst


def test_a_stale_state_would_be_seen():
    """The control of the test above: the same engine with its slot pools
    spoiled before a prompt's prefill serves the same logits (the prefill
    rewrites the whole slot), and spoiled after it does not."""
    eng, cfg, weights = make_engine()
    prompt = prompts_of(9, lens=(13,))[0]

    def spoil():
        for name in eng.core._state_specs:
            eng.core.scope.set(name, eng.core.scope.get(name) + 0.5)

    spoil()
    a = Request("a", prompt, 5)
    eng.submit(a)
    eng.run_to_completion()
    clean, _ = served_against_reference(eng, cfg, weights, [a])
    assert clean <= 3e-4
    b = Request("b", prompt, 5)
    eng.submit(b)
    eng.step()
    spoil()
    eng.run_to_completion()
    stale, _ = served_against_reference(eng, cfg, weights, [b])
    assert stale > 1e-2, stale


WORK = [(5, 1), (8, 2), (9, 6), (17, 11), (30, 4), (3, 9), (12, 1), (21, 7)]


@pytest.mark.parametrize("num_pages", [64, 9], ids=["roomy", "preempting"])
@pytest.mark.parametrize("depth", [1, 2])
def test_pipelined_steps_serve_the_plain_engines_tokens(num_pages, depth):
    def serve(pipeline):
        eng, *_ = make_engine(num_pages=num_pages, max_batch=3,
                              pipeline=pipeline)
        rng = np.random.RandomState(0)
        reqs = [Request(i, rng.randint(0, 128, size=n).tolist(), want)
                for i, (n, want) in enumerate(WORK)]
        for r in reqs:
            eng.submit(r)
        steps = 0
        while eng.has_work():
            eng.step(float(steps))
            steps += 1
            assert steps < 500
        return eng, reqs

    plain, a = serve(0)
    piped, b = serve(depth)
    assert [r.out_tokens for r in a] == [r.out_tokens for r in b]
    assert all(len(r.out_tokens) == r.max_new_tokens for r in b)
    assert plain.stats == piped.stats
    assert (plain.stats["preempted"] > 0) == (num_pages == 9)
    assert plain.kv.stats() == piped.kv.stats()
    assert piped.kv.stats()["state_slots"]["in_use"] == 0


# -- the share of the experts ---------------------------------------------------
def _expert_layer(seed=0, n=24, h=64, f=32, experts=8, k=2):
    r = np.random.RandomState(seed)
    w = {"router": r.randn(h, experts) / 8, "router_bias":
         0.01 * r.randn(experts), "experts_gate": r.randn(experts, h, f) / 8,
         "experts_up": r.randn(experts, h, f) / 8,
         "experts_down": r.randn(experts, f, h) / 6,
         "shared_gate": r.randn(h, f) / 8, "shared_up": r.randn(h, f) / 8,
         "shared_down": r.randn(f, h) / 6}
    w = {name: jnp.asarray(v, jnp.float32) for name, v in w.items()}
    return jnp.asarray(r.randn(n, h), jnp.float32), w, k


# a configuration whose expert layer holds a share: its reference, the keys
# its ``_moe`` reads, its scaling factor and how that ``_moe`` is called
SHARED_LAYERS = {
    "kimi-linear-48b-a3b": (
        REF, lambda k: {"num_experts_per_token": k, "moe_renormalize": True,
                        "routed_scaling_factor": 2.446}, 2.446,
        lambda ref, x, w, cfg: ref._moe(x, w, "", cfg, None, None)),
    "laguna-xs2": (
        _load("laguna-xs2"),
        lambda k: {"num_experts_per_tok": k,
                   "moe_routed_scaling_factor": 2.5}, 2.5,
        lambda ref, x, w, cfg: ref._moe(x, w, cfg, None, None)),
}


@pytest.mark.parametrize("name", sorted(SHARED_LAYERS))
def test_four_shares_and_the_shared_expert_once_are_the_uncut_layer(name):
    ref, keys, scaling, moe = SHARED_LAYERS[name]
    x, w, k = _expert_layer()
    cfg = keys(k)
    whole, _, _ = moe(ref, x, w, cfg)
    shared = ref._swiglu(x, w["shared_gate"], w["shared_up"],
                         w["shared_down"], None)
    idx, weight = mla_ops.route(x, w["router"], w["router_bias"], k, scaling,
                                True)
    total = shared
    for lo in range(0, 8, 2):        # four chips, two experts each
        # a share's experts are the FIRST of those the router ranges over:
        # chip c sees the experts rotated so that its own come first
        turn = (idx - lo) % 8
        part, counts = mla_ops.experts_forward(
            x, turn, weight, w["experts_gate"][lo:lo + 2],
            w["experts_up"][lo:lo + 2], w["experts_down"][lo:lo + 2],
            share=True)
        assert int(counts.sum()) == int(((idx >= lo) & (idx < lo + 2)).sum())
        total = total + part
        # and the reference's share of chip 0 is the engine's
        if lo == 0:
            held = {n: (v[:2] if n.startswith("experts_") else v)
                    for n, v in w.items()}
            ref_part, _, _ = moe(ref, x, held, cfg)
            np.testing.assert_allclose(part + shared, ref_part, atol=1e-5)
    np.testing.assert_allclose(total, whole, atol=1e-5)


@pytest.mark.parametrize("name", sorted(SHARED_LAYERS))
def test_four_shares_by_the_kernels_are_the_uncut_layer(interpreted, name):
    """The same sum with lanes of 128 and a prompt's worth of rows, so that
    every share's rows go in by ``moe_rows_in`` and out by ``moe_combine``:
    three choices in four are another share's on each, and a padded tail
    routes nowhere on any."""
    from paddle_tpu.ops import mla_kernels

    ref, keys, scaling, moe = SHARED_LAYERS[name]
    x, w, k = _expert_layer(2, n=80, h=128, f=128)
    whole, _, _ = moe(ref, x, w, keys(k))
    shared = ref._swiglu(x, w["shared_gate"], w["shared_up"],
                         w["shared_down"], None)
    idx, weight = mla_ops.route(x, w["router"], w["router_bias"], k, scaling,
                                True)
    assert mla_kernels.moe_rows_engage(80 * k, 2, 128)
    valid = jnp.arange(80) < 67
    total = shared
    for lo in range(0, 8, 2):
        part, counts = mla_ops.experts_forward(
            x, (idx - lo) % 8, weight, w["experts_gate"][lo:lo + 2],
            w["experts_up"][lo:lo + 2], w["experts_down"][lo:lo + 2],
            valid, share=True)
        assert int(counts.sum()) == int(
            ((idx[:67] >= lo) & (idx[:67] < lo + 2)).sum())
        np.testing.assert_array_equal(np.asarray(part[67:]), 0.0)
        total = total + part
    np.testing.assert_allclose(total[:67], np.asarray(whole)[:67], atol=2e-5)


def test_every_expert_held_is_the_uncut_path_bit_for_bit():
    x, w, k = _expert_layer(1)
    idx, weight = mla_ops.route(x, w["router"], w["router_bias"], k, 2.5,
                                True)
    args = (x, idx, weight, w["experts_gate"], w["experts_up"],
            w["experts_down"])
    valid = jnp.arange(x.shape[0]) < 20
    for v in (None, valid):
        y0, c0 = mla_ops.experts_forward(*args, v)
        y1, c1 = mla_ops.experts_forward(*args, v, share=True)
        np.testing.assert_array_equal(y0, y1)
        np.testing.assert_array_equal(c0, c1)


def test_engine_counts_the_held_experts_and_the_rows_with_none():
    eng, cfg, weights = make_engine()
    for i, p in enumerate(prompts_of(4, lens=(20, 9))):
        eng.submit(Request(i, p, 4))
    eng.run_to_completion()
    moe = eng.core.moe_stats
    layers = cfg.num_layers - cfg.first_k_dense
    assert moe["prefill"]["layer_steps"] == 2 * layers
    assert moe["prefill"]["experts_touched"] <= 2 * layers * cfg.experts_held
    # top-2 of 8 with 4 held: a row has none of its experts here 3 times in 14
    rows = 29 * layers
    assert 0.05 * rows < moe["prefill"]["rows_all_absent"] < 0.45 * rows
    assert "rows_all_absent" in moe["decode"]


def test_engine_counts_the_rows_its_share_moved(interpreted):
    """``moe_rows_sorted`` / ``moe_rows_moved`` by phase against a brute
    count on a share (4 of 8 experts held, top-2, lanes of 128 so that the
    kernels take the prompts' calls): a prefill sorts its bucket's rows
    times ``k`` a layer and moves the choices of its real rows that name an
    expert held here, counted from the routes the prefill itself reports; a
    decode step's handful of rows goes by XLA's ``take``, all of them."""
    eng, cfg, _ = make_engine(
        dataclasses.replace(TINY, hidden=128, moe_intermediate=128))
    lens = (70, 100, 40)
    for i, p in enumerate(prompts_of(4, lens=lens)):
        eng.submit(Request(i, p, 4))
    eng.run_to_completion()
    eng.core.moe_stats
    layers = cfg.num_layers - cfg.first_k_dense
    k = cfg.num_experts_per_tok
    pre, dec = (eng.stats["kernels"][ph] for ph in ("prefill", "decode"))
    assert pre["moe_rows_sorted"] == (128 + 128 + 64) * k * layers
    moved = 0
    for i, n in enumerate(lens):
        routes = eng.core.prompt_routes(i)          # (layers, rows, k)
        assert routes.shape[0] == layers
        moved += int((routes[:, :n] < cfg.experts_held).sum())
    assert pre["moe_rows_moved"] == moved
    # top-2 of 8 with 4 held: about half the real rows' choices, and none of
    # the buckets' padding
    assert 0.3 * sum(lens) * k * layers < moved < 0.7 * sum(lens) * k * layers
    assert dec["moe_rows_moved"] == dec["moe_rows_sorted"] > 0
    assert dec["moe_rows_sorted"] % (k * layers) == 0


# -- what the engine refuses for this model ------------------------------------
@pytest.mark.parametrize("kw,match", [
    ({"prefix_cache": True}, "prefix"),
    ({"prefill_chunk": 16}, "chunk"),
    ({"kv_dtype": "int8"}, "int8"),
    ({"tp": 2}, "tensor-parallel"),
    ({"spec_k": 2}, "speculative"),
])
def test_engine_refuses_what_a_state_cannot_be_served_with(kw, match):
    with pytest.raises(ValueError, match=match):
        ServingEngine(cfg=TINY, weights=init_mla_weights(TINY, 0),
                      **{"kv_dtype": "float32", "num_pages": 16,
                         "page_size": 8, **kw})


def test_description_refuses_a_drafter_and_a_bad_layer_list():
    with pytest.raises(ValueError, match="speculative"):
        dataclasses.replace(TINY, mtp_layers=1).validate()
    with pytest.raises(ValueError, match="mixers"):
        dataclasses.replace(TINY, mixers=("kda", "mla")).validate()
    with pytest.raises(ValueError, match="verify"):
        TINY.build_program("verify")


def test_every_mixer_mla_is_the_plain_description():
    plain = MLADecoderConfig()
    named = dataclasses.replace(plain, mixers=("mla",) * plain.num_layers)
    assert named.param_specs() == plain.param_specs()
    assert named.cache_pool_names() == plain.cache_pool_names()
    assert not named.state_pool_specs(4)
    from test_gpt2_program_digest import program_digest
    for mode in ("reference", "prefill", "decode", "verify"):
        assert program_digest(*named.build_program(mode)) == \
            program_digest(*plain.build_program(mode))


def test_pools_are_rows_for_the_mla_layers_and_slots_for_the_kda_layers():
    eng, cfg, _ = _shared_engine()
    assert cfg.cache_pool_names() == ["kv_lat_3", "kv_lat_7"]
    assert eng.core.scope.get("kv_lat_3").shape == (1, 64, 8, 24)
    assert eng.core.scope.get("kda_state_0").shape == (5, 4, 16, 16)
    assert eng.core.scope.get("kda_conv_0").shape == (5, 3, 192)
    assert cfg.kv_token_bytes("float32") == 2 * 24 * 4
    assert cfg.state_slot_bytes() == 6 * (4 * 16 * 16 + 3 * 192) * 4
    # the published widths: 2.10 MB of state and 147 KB of tail a layer
    wide = dataclasses.replace(cfg, kda_heads=32, kda_head_dim=128)
    assert wide.state_slot_bytes() == 6 * (2097152 + 147456)


def test_source_config_round_trips():
    assert MLADecoderConfig.from_source(
        TINY.source_config(), max_seq_len=256) == TINY


# -- the cache manager's slots --------------------------------------------------
def test_a_sequence_owns_a_slot_from_its_first_pages_to_its_last():
    kv = PagedKVCache(KVCacheConfig(num_pages=16, page_size=4,
                                    num_kv_heads=1, head_dim=8,
                                    state_slots=2))
    assert kv.append_tokens("a", 5) is not None
    assert kv.append_tokens("b", 3) is not None
    assert (kv.state_slot("a"), kv.state_slot("b")) == (0, 1)
    # pages are free, slots are not: backpressure, nothing mutated
    assert not kv.can_append("c", 1)
    assert kv.append_tokens("c", 1) is None and "c" not in kv.live_sequences()
    assert kv.append_tokens("a", 1) is not None      # a live one still grows
    kv.free_sequence("a", preempted=True)
    assert kv.append_tokens("c", 1) is not None and kv.state_slot("c") == 0
    kv.free_sequence("b")
    kv.free_sequence("c")
    assert kv.stats()["state_slots"] == {
        "total": 2, "in_use": 0, "peak": 2, "freed_by_preemption": 1}
    assert kv.config.pad_state_slot == 2


def test_a_cache_with_slots_refuses_sharing_and_roll_back():
    cfg = KVCacheConfig(num_pages=8, page_size=4, num_kv_heads=1, head_dim=8,
                        state_slots=2)
    with pytest.raises(ValueError, match="prefix"):
        PagedKVCache(cfg, prefix_cache=True)
    kv = PagedKVCache(cfg)
    kv.append_tokens("a", 6)
    with pytest.raises(ValueError, match="roll"):
        kv.truncate_tokens("a", 2)
    assert "state_slots" not in PagedKVCache(
        dataclasses.replace(cfg, state_slots=0)).stats()
