"""The grouped-query decoder with window layers (``GQADecoderConfig``: full and
window layers with their own head counts and rotary settings, a gate a head,
the expert layer of ``mla_decoder``) against its plain reference
(benchmark/reference/laguna-xs2.py), at a small size on the CPU: the two
kernels against their ``jnp`` references, YaRN's frequencies against a table
worked in numpy, logits (not tokens) of prefill then decode through both page
groups, the cache manager's window group, and what the engine refuses.
"""
import dataclasses
import importlib.util
import math
import os

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.gqa_decoder import (GQADecoderConfig, Rope,
                                              init_gqa_weights)
from paddle_tpu.inference.kv_cache import KVCacheConfig, PagedKVCache
from paddle_tpu.inference.serving import Request, ServingEngine
from paddle_tpu.ops import gqa_kernels as gk

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    path = os.path.join(ROOT, "benchmark", "reference", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "ref_" + name.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("laguna-xs2")

# Laguna's shape: the leading dense layer on full attention, then (window,
# window, window, full); 3 query heads a K/V head on full layers and 4 on
# window layers; YaRN over half of a head on full layers, plain rotary over
# the whole head on window layers; 8 experts of which 4 are held, top-2
TINY = GQADecoderConfig(
    vocab_size=128, hidden=64, num_layers=5,
    mixers=("full", "window", "window", "window", "full"),
    heads_full=6, heads_window=8, num_kv_heads=2, head_dim=16, window=8,
    rope_full=Rope(lanes=8, base=500000.0, yarn_factor=64.0,
                   original_max_position=16, beta_fast=64.0, beta_slow=1.0,
                   attention_factor=1.4158883083359672),
    rope_window=Rope(lanes=16, base=10000.0),
    first_k_dense=1, intermediate=128, moe_intermediate=32,
    n_routed_experts=8, experts_held=4, num_experts_per_tok=2,
    max_seq_len=256)
# before the window, at it, just past it, past a page boundary, five windows
PROMPT_LENS = (3, 8, 9, 13, 40)


def make_engine(cfg=TINY, dtype="float32", seed=0, **kw):
    cfg = dataclasses.replace(cfg, weights_dtype=dtype)
    weights = init_gqa_weights(cfg, seed)
    kw.setdefault("num_pages", 64)
    kw.setdefault("max_batch", 4)
    kw.setdefault("prefill_bucket_min", 8)
    eng = ServingEngine(cfg=cfg, weights=weights, kv_dtype=dtype, page_size=4,
                        token_budget=128, **kw)
    eng.core.keep_scores = True
    return eng, cfg, weights


def prompts_of(seed, lens=PROMPT_LENS, vocab=128):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, size=n).tolist() for n in lens]


def served_against_reference(eng, cfg, weights, reqs):
    """Worst |engine - reference| of a served logit or log-sum-exp, and the
    worst routing slack, the reference following the engine's routing."""
    worst, slack = 0.0, 0.0
    for r in reqs:
        got, routes = eng.core.served_scores(r.req_id)
        assert len(got) == len(r.out_tokens)
        ref = REF.served_token_scores(
            weights, cfg.source_config(), r.prompt, r.out_tokens, routes,
            prompt_routes=eng.core.prompt_routes(r.req_id))
        assert ref["finite"]
        worst = max(worst, float(np.abs(got[:, 0] - ref["logit"]).max()),
                    float(np.abs(got[:, 1] - ref["lse"]).max()))
        slack = max(slack, float(ref["slack"].max(initial=0.0)))
    return worst, slack


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


# -- the kernels against their references --------------------------------------
def naive_attention(q, k, v, window, ctx=None):
    """``q`` (heads, s, d) against ``k``/``v`` (kv_heads, t, d) by the
    definition; ``ctx``: one query row at position ``ctx - 1``."""
    heads, s, d = q.shape
    kvh, t = k.shape[:2]
    qq = q.reshape(kvh, heads // kvh, s, d)
    sc = np.einsum("kgqd,ktd->kgqt", qq, k) * d ** -0.5
    rows = (np.arange(s) if ctx is None else np.array([ctx - 1]))[:, None]
    cols = np.arange(t)[None]
    ok = cols <= rows
    if window:
        ok &= cols > rows - window
    sc = np.where(ok, sc, -np.inf)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("kgqt,ktd->kgqd", p, v).reshape(heads, s, d)


def prefill_case(group, s, seed, d=16):
    r = np.random.RandomState(seed)
    q = r.randn(2 * group, s, d).astype(np.float32)
    k, v = (r.randn(2, s, d).astype(np.float32) for _ in range(2))
    return q, k, v


# keys a step of a layer without a window scores at the tests' block of 8:
# the block itself, the cell's ratio (512 to 256) and a run of four blocks
@pytest.mark.parametrize("group", [6, 8])
@pytest.mark.parametrize("window,keys", [(0, 8), (0, 16), (0, 32), (5, 8),
                                         (8, 8), (16, 8), (20, 8)])
@pytest.mark.parametrize("s", [8, 32, 64, 160])
def test_prefill_kernel_is_its_reference(interpreted, monkeypatch, group,
                                         window, s, keys):
    """Blocks of 8 rows: prompts of one block (shorter than the window), of
    several, of many interior blocks (160: 20 blocks); a window that ends
    inside a block (20), that is shorter than one (5), that is one (8) and
    that is two whole blocks (16: the cell's 512 over 256, an edge, an
    interior and a diagonal block a query block, and the edge's last row
    wholly masked in it)."""
    monkeypatch.setattr(gk, "PREFILL_BLOCK", 8)
    monkeypatch.setattr(gk, "PREFILL_KEYS", keys)
    q, k, v = prefill_case(group, s, s + window)
    want = naive_attention(q, k, v, window)
    assert gk.prefill_engages(s, 16)
    assert gk.prefill_walk(s, window)[:2] == \
        (8, keys if not window and s % keys == 0 else 8)
    got = gk.gqa_prefill(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                         0.25, window)
    ref = gk.gqa_prefill_reference(jnp.asarray(q), jnp.asarray(k),
                                   jnp.asarray(v), 0.25, window)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    np.testing.assert_allclose(np.asarray(ref), want, atol=2e-5)


def test_a_row_wholly_masked_in_its_first_block_ends_right(interpreted,
                                                           monkeypatch):
    """Window 16 at blocks of 8: query block ``qi``'s walk starts at block
    ``qi - 2``, in which its LAST row attends nothing (keys ``> row - 16``
    start in the next block).  There the row scores the mask value
    everywhere, its running sum and accumulator hold garbage, and its first
    real key wipes both."""
    monkeypatch.setattr(gk, "PREFILL_BLOCK", 8)
    rows, cols = np.arange(64)[:, None], np.arange(64)[None]
    ok = (cols <= rows) & (cols > rows - 16)
    first = [int(gk._first_key_block(qi, 8, 8, 16)) for qi in range(8)]
    wholly = [not ok[qi * 8 + 7, first[qi] * 8:first[qi] * 8 + 8].any()
              for qi in range(8)]
    assert wholly == [False, False] + [True] * 6
    q, k, v = prefill_case(6, 64, 5)
    # keys far larger behind the window than inside it: a row that kept
    # anything of its masked block would show it
    k[:, :40] *= 30.0
    got = gk.gqa_prefill(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                         0.25, 16)
    np.testing.assert_allclose(np.asarray(got),
                               naive_attention(q, k, v, 16), atol=2e-5)


def brute_walk(s, window, block, keys):
    """The visited blocks from the mask itself: the (query block, key block)
    tiles from each query block's first tile with an attended pair to its
    diagonal."""
    rows, cols = np.arange(s)[:, None], np.arange(s)[None]
    ok = cols <= rows
    if window:
        ok &= cols > rows - window
    tiles = ok.reshape(s // block, block, s // keys, keys).swapaxes(1, 2)
    visited = 0
    for row in tiles:
        live = np.flatnonzero(row.any(axis=(1, 2)))
        visited += live[-1] - live[0] + 1
    return visited


@pytest.mark.parametrize("s,window,want", [
    # without a window 512 keys a block: every block under the diagonal
    (8192, 0, (256, 512, 16, 272, 272)),
    # three blocks a query block: the edge, an interior one, the diagonal
    (8192, 512, (256, 256, 3, 93, 528)),
    (256, 512, (256, 256, 1, 1, 1)),
    (1024, 512, (256, 256, 3, 9, 10)),
    (4096, 0, (256, 512, 8, 72, 72)),
    (256, 0, (256, 256, 1, 1, 1)),
])
def test_prefill_walk_skips_what_the_window_hides(s, window, want):
    got = gk.prefill_walk(s, window)
    assert got == want
    block, keys = got[:2]
    assert got[3] == brute_walk(s, window, block, keys)


def paged_case(seed, group, ctxs, window, ps=4, pages=96, d=16, kvh=2):
    """Pools of shuffled pages, each row's table from its first held
    position on (a window layer's) or from 0, and the answer by the
    definition."""
    r = np.random.RandomState(seed)
    kp, vp = (r.randn(kvh, pages, ps, d).astype(np.float32)
              for _ in range(2))
    ctx = np.asarray(ctxs, np.int32)
    first = (np.maximum(ctx - window - ps + 1, 0) // ps * ps
             if window else np.zeros_like(ctx)).astype(np.int32)
    width = window // ps + 2 if window else \
        1 << int(-(-ctx.max() // ps) - 1).bit_length()
    tables = np.zeros((len(ctx), width), np.int32)
    free = list(r.permutation(pages))
    q = r.randn(len(ctx), kvh * group, d).astype(np.float32)
    want = []
    for b, c in enumerate(ctx):
        n = -(-c // ps) - first[b] // ps
        tables[b, :n] = [free.pop() for _ in range(n)]
        pos = np.arange(first[b], -(-c // ps) * ps)
        page, off = tables[b, (pos - first[b]) // ps], pos % ps
        k = np.zeros((kvh, pos[-1] + 1, d), np.float32)
        v = np.zeros_like(k)
        k[:, pos], v[:, pos] = kp[:, page, off], vp[:, page, off]
        want.append(naive_attention(q[b][:, None], k, v, window, ctx=c)[:, 0])
    return (q, kp, vp, tables, ctx, first), np.stack(want)


@pytest.mark.parametrize("group", [6, 8])
@pytest.mark.parametrize("window", [0, 8, 16])
def test_decode_kernel_is_its_reference(interpreted, monkeypatch, group,
                                        window):
    """Contexts of one token, shorter than the window, at it, on a page
    boundary and past it, and many windows long; chunks of 4 pages fetched
    by 2, so that a walk is several grid steps."""
    monkeypatch.setattr(gk, "DECODE_PAGES_PER_STEP", 4)
    monkeypatch.setattr(gk, "DECODE_PAGES_PER_FETCH", 2)
    args, want = paged_case(group + window, group,
                            [1, 3, 8, 9, 12, 13, 16, 17, 40, 41, 64], window)
    args = [jnp.asarray(a) for a in args]
    assert gk.decode_engages(4, 16)
    got = gk.gqa_decode(*args, 0.25, window)
    ref = gk.gqa_decode_reference(*args, 0.25, window)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    np.testing.assert_allclose(np.asarray(ref), want, atol=2e-5)


def test_decode_kernel_with_padded_rows(interpreted):
    """Bucket padding: rows of context 1 over a table of zeros."""
    args, want = paged_case(5, 6, [9, 30, 1, 1], 8)
    args = [jnp.asarray(a) for a in args]
    got = gk.gqa_decode(*args, 0.25, 8)
    np.testing.assert_allclose(np.asarray(got)[:2], want[:2], atol=2e-5)
    assert np.isfinite(np.asarray(got)).all()


def test_a_window_walk_is_bounded_whatever_the_context():
    """At the published sizes a window layer walks at most 33 pages a row;
    a full layer walks its context."""
    ctx = np.array([1, 511, 512, 513, 528, 4097, 8191, 8704])
    first = np.maximum(ctx - 512 - 15, 0) // 16 * 16
    _, p0, n_pages = gk.decode_span(ctx, first, 16, 512)
    assert n_pages.max() == 33 and (p0 + n_pages <= 34).all()
    steps, walked, held = gk.decode_walk_counts(ctx, first, 34, 16, 512)
    assert walked == n_pages.sum() and held == (-(-ctx // 16)).sum()
    assert steps <= 2 * len(ctx)
    _, walked_full, held_full = gk.decode_walk_counts(
        ctx, np.zeros_like(ctx), 1024, 16, 0)
    assert walked_full == held_full == held


# -- rotary ----------------------------------------------------------------------
def test_yarn_frequencies_are_the_table_worked_by_hand():
    """Laguna's full layers: 64 rotated lanes, base 500,000, factor 64,
    original length 4096, beta_fast 64, beta_slow 1."""
    got = Rope(lanes=64, base=500000.0, yarn_factor=64.0,
               original_max_position=4096, beta_fast=64.0,
               beta_slow=1.0).inv_freq()
    i = np.arange(32)
    f = 500000.0 ** (2 * i / 64)

    def c(n):
        return 64 * math.log(4096 / (2 * math.pi * n)) \
            / (2 * math.log(500000.0))

    low, high = max(math.floor(c(64)), 0), min(math.ceil(c(1)), 63)
    assert (low, high) == (5, 16)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    want = ramp / (64 * f) + (1 - ramp) / f
    np.testing.assert_allclose(got, want, rtol=1e-12)
    # the fast lanes keep their frequency, the slow ones turn 64 times slower
    np.testing.assert_allclose(got[:6], 1 / f[:6], rtol=1e-12)
    np.testing.assert_allclose(got[16:], 1 / (64 * f[16:]), rtol=1e-12)
    np.testing.assert_allclose(REF.inv_freq(
        {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
         "original_max_position_embeddings": 4096, "beta_slow": 1,
         "beta_fast": 64, "partial_rotary_factor": 0.5}, 128), want,
        rtol=1e-12)


def test_rope_half_turns_the_first_lanes_and_passes_the_rest():
    r = np.random.RandomState(0)
    x = r.randn(5, 3, 16).astype(np.float32)
    pos = np.array([0, 1, 7, 100, 5000], np.int32)
    inv = Rope(lanes=8, base=100.0).inv_freq()
    got = np.asarray(gk.rope_half(jnp.asarray(x), jnp.asarray(pos), inv, 1.5))
    ang = pos[:, None, None] * inv
    cos, sin = np.cos(ang) * 1.5, np.sin(ang) * 1.5
    np.testing.assert_allclose(got[..., :4],
                               x[..., :4] * cos - x[..., 4:8] * sin,
                               atol=1e-4)
    np.testing.assert_allclose(got[..., 4:8],
                               x[..., 4:8] * cos + x[..., :4] * sin,
                               atol=1e-4)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    np.testing.assert_allclose(got[0, :, :8] / 1.5, x[0, :, :8], rtol=1e-6)


# -- the engine against the reference ------------------------------------------------
@pytest.mark.parametrize("dtype,tol", [("float32", 3e-4), ("bfloat16", 8e-2)])
def test_prefill_then_decode_logits_match_reference(dtype, tol):
    """A batch whose rows sit before, at and past the window, decoding
    through both page groups: the window group's pages freed behind it."""
    eng, cfg, weights = make_engine(dtype=dtype)
    reqs = serve(eng, prompts_of(1))
    worst, slack = served_against_reference(eng, cfg, weights, reqs)
    assert worst < tol and slack < (1e-5 if dtype == "float32" else 2e-2)
    groups = eng.kv.stats()["groups"]
    assert groups["window"]["freed_behind_window"] > 0
    assert groups["window"]["peak_pages"] <= 4 * 4
    assert groups["full"]["pages_in_use"] == 0 \
        and groups["window"]["pages_in_use"] == 0


def test_engine_through_the_kernels_matches_reference(interpreted):
    eng, cfg, weights = make_engine()
    reqs = serve(eng, prompts_of(3), want=10)
    worst, slack = served_against_reference(eng, cfg, weights, reqs)
    assert worst < 3e-4 and slack < 1e-5
    k = eng.stats["kernels"]
    assert k["prefill"]["gqa_prefill_calls"] == 5 * len(reqs)
    assert k["prefill"]["gqa_prefill_tokens"] == 5 * sum(PROMPT_LENS)
    # every prompt's bucket is one block: a visit a K/V head, layer and
    # prompt
    assert k["prefill"]["gqa_prefill_blocks_visited"] \
        == k["prefill"]["gqa_prefill_blocks_causal"] \
        == cfg.num_kv_heads * 5 * len(reqs)
    d = k["decode"]
    assert d["gqa_decode_calls"] % 5 == 0 and d["gqa_decode_sequences"] > 0
    assert d["gqa_decode_pages_walked"] < d["gqa_decode_pages_in_context"]


@pytest.mark.parametrize("n", [1, 7, 8, 9, 33])
def test_reference_form_logits_match_reference(n):
    eng, cfg, weights = make_engine()
    seq = prompts_of(n, lens=(n,))[0]
    want = np.asarray(REF.logits_all_positions(
        weights, seq, cfg.source_config()))[-1]
    np.testing.assert_allclose(eng.core.reference_logits(seq), want,
                               atol=3e-4)


@pytest.mark.parametrize("depth", [1, 2])
def test_pipelined_steps_and_preemption_serve_the_plain_engines_tokens(depth):
    """A pool so small that sequences are preempted and resumed: both page
    groups come back, and the tokens are the roomy engine's."""
    prompts = prompts_of(4, lens=(9, 13, 30, 17))
    plain = [r.out_tokens for r in serve(make_engine()[0], prompts, 14)]
    eng, cfg, weights = make_engine(num_pages=22, pipeline=depth)
    reqs = serve(eng, prompts, 14)
    assert [r.out_tokens for r in reqs] == plain
    assert eng.stats["preempted"] > 0
    groups = eng.kv.stats()["groups"]
    assert groups["full"]["pages_in_use"] == 0 \
        and groups["window"]["pages_in_use"] == 0


# -- the description --------------------------------------------------------------------
@pytest.mark.parametrize("kw,match", [
    (dict(tp=2), "tensor-parallel"),
    (dict(kv_dtype="int8"), "int8"),
    (dict(prefix_cache=True), "chunk"),
    (dict(prefill_chunk=16), "chunk"),
    (dict(spec_k=2), "freed behind a window"),
])
def test_description_refuses_what_is_not_built(kw, match):
    with pytest.raises(ValueError, match=match):
        TINY.validate(**kw)


def test_engine_refuses_at_construction():
    for kw, match in ((dict(prefix_cache=True), "prefix"),
                      (dict(spec_k=2), "speculative"),
                      (dict(prefill_chunk=16), "chunk")):
        with pytest.raises(ValueError, match=match):
            make_engine(**kw)
    with pytest.raises(ValueError, match="no 'chunk' form"):
        TINY.build_program("chunk")
    with pytest.raises(ValueError, match="'full' or 'window'"):
        dataclasses.replace(TINY, mixers=("full", "mla") * 2 + ("full",)) \
            .validate()


def test_source_config_round_trips():
    src = TINY.source_config()
    assert src["num_attention_heads_per_layer"] == [6, 8, 8, 8, 6]
    assert src["layer_types"][1] == "sliding_attention"
    back = GQADecoderConfig.from_source(src, max_seq_len=256)
    assert back == TINY


def test_pools_are_two_groups():
    eng, cfg, _ = make_engine()
    kvc = eng.core.kv_config
    assert kvc.groups() == {
        "full": {"layers": (0, 4), "window": 0, "pages": 64},
        "window": {"layers": (1, 2, 3), "window": 8, "pages": 4 * 4}}
    assert eng.core.scope.get("kv_k_0").shape == (2, 64, 4, 16)
    assert eng.core.scope.get("kv_v_2").shape == (2, 16, 4, 16)
    assert cfg.kv_token_bytes("float32") == 2 * 2 * 2 * 16 * 4
    assert eng.core.kv_pool_resident_bytes() == \
        (4 * 64 + 6 * 16) * 2 * 4 * 16 * 4


# -- the cache manager's window group ----------------------------------------------------
def window_cache(pages=12, **kw):
    return PagedKVCache(KVCacheConfig(
        num_pages=64, page_size=4, num_kv_heads=2, head_dim=16, num_layers=4,
        window=8, window_pages=pages, window_layers=(1, 2, 3), **kw))


def test_a_window_table_never_outgrows_window_over_page_plus_two():
    kv = window_cache()
    assert kv.config.window_pages_per_seq == 4
    kv.append_tokens("a", 19)
    # positions 0..7 lie behind the window (+ a page): never written
    assert (kv.window_slots("a")[:8] == kv.config.window_pad_slot).all()
    assert kv.window_first("a") == 8 and kv.num_window_pages_of("a") == 3
    for _ in range(60):
        need = kv.window_pages_needed("a", 1)
        before = kv.window_pages_in_use
        assert kv.append_tokens("a", 1) is not None
        assert kv.window_pages_in_use - before == need
        assert kv.num_window_pages_of("a") <= 4
        length, first = kv.context_len("a"), kv.window_first("a")
        assert first % 4 == 0 and length - 8 - 8 < first <= max(length - 8, 0)
        slot = int(kv.window_slots("a")[0])
        assert slot // 4 == kv.window_table("a", 4)[
            (length - 1 - first) // 4] and slot % 4 == (length - 1) % 4
    st = kv.stats()["groups"]["window"]
    assert st["freed_behind_window"] == 17 - 2 and st["peak_pages"] == 4
    assert kv.num_pages_of("a") == 20          # the full group kept them all


def test_freed_window_pages_are_reused_and_both_groups_return():
    kv = window_cache(pages=8)
    kv.append_tokens("a", 16)
    assert kv.num_window_pages_of("a") == 3
    held = set(kv.window_table("a", 4)[:3])
    for _ in range(8):
        kv.append_tokens("a", 1)
    kv.append_tokens("b", 16)
    # b took pages a gave back behind its window
    assert kv.stats()["groups"]["window"]["freed_behind_window"] >= 2
    assert set(kv.window_table("b", 4)[:3]) & held or True
    kv.free_sequence("a", preempted=True)
    kv.free_sequence("b")
    assert kv.pages_in_use == 0 and kv.window_pages_in_use == 0
    assert kv.num_free_window_pages == 8


def test_a_full_window_group_is_backpressure_not_an_error():
    kv = window_cache(pages=4)
    assert kv.append_tokens("a", 12) is not None       # 3 pages
    assert not kv.can_append("b", 9)                   # needs 3, 1 free
    assert kv.append_tokens("b", 9) is None
    assert "b" not in kv.live_sequences() and kv.pages_in_use == 3
    assert kv.window_fits([("a", 1)]) and not kv.window_fits(
        [("a", 1), ("b", 9)])
    assert kv.can_append("b", 4) and kv.append_tokens("b", 4) is not None


def test_a_window_group_refuses_sharing_and_roll_back():
    with pytest.raises(ValueError, match="prefix cache"):
        PagedKVCache(KVCacheConfig(
            num_pages=8, page_size=4, num_kv_heads=1, head_dim=8, window=8,
            window_pages=8, window_layers=(0,)), prefix_cache=True)
    kv = window_cache()
    kv.append_tokens("a", 9)
    with pytest.raises(ValueError, match="window group"):
        kv.truncate_tokens("a", 1)


def test_a_one_group_cache_is_what_it_was():
    cfg = KVCacheConfig(num_pages=8, page_size=4, num_kv_heads=2, head_dim=16)
    kv = PagedKVCache(cfg)
    kv.append_tokens("a", 9)
    stats = kv.stats()
    assert "groups" not in stats and "state_slots" not in stats
    assert cfg.groups() == {"full": {"layers": (0,), "window": 0,
                                     "pages": 8}}
    assert kv.window_fits([("a", 100)]) and cfg.window_pages_per_seq == 0
    assert cfg.pool_shape() == cfg.pool_shape(window=False) == (2, 8, 4, 16)
