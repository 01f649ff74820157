"""The latent-attention / sparse-expert decoder (inference/mla_decoder.py)
against its plain reference (benchmark/reference/joyai-llm-flash.py), at a
small size on the CPU: logits (not tokens) of prefill then decode through the
paged latent cache, the two attention forms against each other, the rotary
embedding, the router's properties, the MTP module and its drafter, and the
kernels' bodies in the interpreter.
"""
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.mla_decoder import (MLADecoderConfig, MTPDrafter,
                                              init_mla_weights)
from paddle_tpu.inference.serving import Request, ServingEngine
from paddle_tpu.ops import mla_kernels, mla_ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_reference():
    path = os.path.join(ROOT, "benchmark", "reference", "joyai-llm-flash.py")
    spec = importlib.util.spec_from_file_location("ref_joyai", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load_reference()


def ref_cfg(cfg: MLADecoderConfig) -> dict:
    """The configuration under the source's key names, as the reference
    reads it."""
    return cfg.source_config()


# hidden 64, 4 heads, q_lora 32, kv_lora 16, nope 16, rope 8, v 16, 8 experts
# top-2, 1 dense + 2 expert layers, vocabulary 128: the dataclass's defaults
TINY = MLADecoderConfig()
PROMPT_LENS = (5, 8, 9, 17, 30)       # page_size 8: under, at, over, 2+, 3+


def make_engine(cfg, dtype="float32", seed=0, **kw):
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


def served_against_reference(eng, cfg, weights, reqs):
    """For every served token of ``reqs``: |engine - reference| of its logit
    and of its row's log-sum-exp, the reference routed as the engine was;
    and the worst routing slack."""
    worst, slack = 0.0, 0.0
    for r in reqs:
        got, routes = eng.core.served_scores(r.req_id)
        assert len(got) == len(r.out_tokens)
        ref = REF.served_token_scores(weights, ref_cfg(cfg), r.prompt,
                                      r.out_tokens, routes)
        assert ref["finite"]
        worst = max(worst, float(np.abs(got[:, 0] - ref["logit"]).max()),
                    float(np.abs(got[:, 1] - ref["lse"]).max()))
        slack = max(slack, float(ref["slack"].max()))
    return worst, slack


# float32: the two differ by summation order alone.  bfloat16: weights and
# latent rows hold 8 bits of mantissa (a rounding of 2^-9 relative a matmul
# operand), so logits of unit scale move by about 1e-2; 8e-2 leaves room for
# the worst row of a test's few hundred and is a fifth of what a wrong page,
# a missing term or one mis-routed expert moves them by (0.3 and more)
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-4), ("bfloat16", 8e-2)])
def test_prefill_then_decode_logits_match_reference(dtype, tol):
    eng, cfg, weights = make_engine(TINY, dtype)
    reqs = [Request(i, p, 12) for i, p in enumerate(prompts_of(1))]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    assert eng.stats["preempted"] == 0
    worst, slack = served_against_reference(eng, cfg, weights, reqs)
    assert worst <= tol, worst
    # the engine's choice of experts is the reference's, to within what
    # the precision moves a score
    assert slack <= (1e-5 if dtype == "float32" else 2e-2), slack


def test_preempted_and_resumed_requests_match_reference():
    # 12 pages of 8: four prompts of 17-20 tokens fit, their decodes do not
    eng, cfg, weights = make_engine(TINY, num_pages=12)
    reqs = [Request(i, p, 14) for i, p in
            enumerate(prompts_of(2, lens=(17, 18, 19, 20)))]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    assert eng.stats["preempted"] > 0
    assert all(len(r.out_tokens) == 14 for r in reqs)
    worst, _ = served_against_reference(eng, cfg, weights, reqs)
    assert worst <= 2e-4, worst


@pytest.mark.parametrize("n", [1, 7, 16, 33])
def test_reference_form_logits_match_reference(n):
    """The full-sequence program form (the export form) against the
    reference's full forward pass, every logit of the last row."""
    eng, cfg, weights = _shared_engine()
    seq = prompts_of(3, lens=(n,))[0]
    got = eng.core.reference_logits(seq)
    want = np.asarray(REF.logits_all_positions(weights, seq, ref_cfg(cfg)))[-1]
    np.testing.assert_allclose(got, want, atol=2e-4)


_ENGINE = {}


def _shared_engine():
    if "e" not in _ENGINE:
        _ENGINE["e"] = make_engine(TINY)
    return _ENGINE["e"]


def test_generate_is_the_reference_forms_greedy_decode():
    eng, _cfg, _w = _shared_engine()
    prompts = prompts_of(4, lens=(6, 21))
    outs = eng.generate(prompts, 8)
    assert outs == [eng.core.greedy_reference(p, 8) for p in prompts]


# -- the two attention forms ---------------------------------------------
@pytest.mark.parametrize("ctx", [1, 8, 13, 40])
def test_absorbed_attention_is_expanded_attention(ctx):
    rng = np.random.RandomState(ctx)
    heads, dn, dr, dv, rank, ps = 4, 16, 8, 16, 16, 8
    q_nope = jnp.asarray(rng.randn(ctx, heads, dn), jnp.float32)
    q_rope = jnp.asarray(rng.randn(ctx, heads, dr), jnp.float32)
    c_kv = jnp.asarray(rng.randn(ctx, rank), jnp.float32)
    k_r = jnp.asarray(rng.randn(ctx, dr), jnp.float32)
    w_kvb = jnp.asarray(rng.randn(rank, heads * (dn + dv)) / 4, jnp.float32)
    scale = (dn + dr) ** -0.5
    want = mla_ops.mla_expanded_attention(q_nope, q_rope, c_kv, k_r, w_kvb,
                                          dv, scale)[-1]
    # the same rows through a paged pool, pages in a shuffled order
    n_pages = -(-ctx // ps)
    table = rng.permutation(n_pages + 3)[:n_pages].astype(np.int32)
    slots = jnp.asarray(table[np.arange(ctx) // ps] * ps
                        + np.arange(ctx) % ps, jnp.int32)
    pool = jnp.zeros((1, n_pages + 3, ps, rank + dr), jnp.float32)
    pool = mla_kernels.latent_append(
        pool, jnp.concatenate([c_kv, k_r], axis=-1), slots)
    got = mla_ops.mla_absorbed_attention(
        q_nope[-1:], q_rope[-1:], pool, jnp.asarray(table)[None],
        jnp.asarray([ctx], jnp.int32), w_kvb, dv, scale)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# -- rotary embedding --------------------------------------------------------
def test_rope_rotates_interleaved_pairs_at_an_offset():
    rng = np.random.RandomState(0)
    x = rng.randn(5, 2, 8).astype(np.float32)
    pos = np.arange(5) + 1000
    theta = 32e6
    got = np.asarray(mla_ops.rope_interleaved(jnp.asarray(x),
                                              jnp.asarray(pos), theta))
    want = np.empty_like(x)
    for i in range(4):
        ang = pos * theta ** (-2.0 * i / 8)
        a, b = x[..., 2 * i], x[..., 2 * i + 1]
        c, s = np.cos(ang)[:, None], np.sin(ang)[:, None]
        want[..., 2 * i], want[..., 2 * i + 1] = a * c - b * s, a * s + b * c
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_rope_scores_depend_on_the_distance_alone():
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(1, 1, 8), jnp.float32)
    k = jnp.asarray(rng.randn(1, 1, 8), jnp.float32)

    def score(pq, pk):
        return float(jnp.sum(
            mla_ops.rope_interleaved(q, jnp.asarray([pq]), 1e4)
            * mla_ops.rope_interleaved(k, jnp.asarray([pk]), 1e4)))

    assert abs(score(7, 3) - score(107, 103)) < 1e-4
    assert abs(score(7, 3) - score(7, 4)) > 1e-3


# -- the router ------------------------------------------------------------
def _route(x, gate, bias, k=2, scaling=2.5, norm=True):
    idx, w = mla_ops.route(jnp.asarray(x), jnp.asarray(gate),
                           jnp.asarray(bias), k, scaling, norm)
    return np.asarray(idx), np.asarray(w)


def test_router_bias_moves_the_choice_and_not_the_weight():
    rng = np.random.RandomState(0)
    x = rng.randn(6, 16).astype(np.float32)
    gate = (rng.randn(16, 8) / 4).astype(np.float32)
    idx0, _ = _route(x, gate, np.zeros(8, np.float32), norm=False,
                     scaling=1.0)
    bias = np.zeros(8, np.float32)
    bias[5] = 10.0                            # expert 5 now wins everywhere
    idx, w = _route(x, gate, bias, norm=False, scaling=1.0)
    assert (idx == 5).any(axis=1).all() and not (idx0 == 5).any(axis=1).all()
    scores = 1 / (1 + np.exp(-(x @ gate)))
    np.testing.assert_allclose(w, np.take_along_axis(scores, idx, 1),
                               atol=1e-6)     # the score, without the bias


def test_router_normalises_then_scales():
    rng = np.random.RandomState(1)
    x = rng.randn(5, 16).astype(np.float32)
    gate = (rng.randn(16, 8) / 4).astype(np.float32)
    _, w = _route(x, gate, np.zeros(8, np.float32), scaling=2.5)
    np.testing.assert_allclose(w.sum(axis=1), 2.5, atol=1e-5)


def _experts(n, experts=8, h=16, f=8, seed=0):
    rng = np.random.RandomState(seed)
    return (rng.randn(n, h).astype(np.float32),
            (rng.randn(experts, h, f) / 4).astype(np.float32),
            (rng.randn(experts, h, f) / 4).astype(np.float32),
            (rng.randn(experts, f, h) / 3).astype(np.float32))


def _dense_experts(x, idx, w, wg, wu, wd):
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        for e, we in zip(idx[t], w[t]):
            g, u = x[t] @ wg[e], x[t] @ wu[e]
            out[t] += we * ((g / (1 + np.exp(-g)) * u) @ wd[e])
    return out


def test_every_token_to_the_same_experts_drops_none():
    x, wg, wu, wd = _experts(40)
    idx = np.tile(np.array([[1, 6]], np.int32), (40, 1))
    w = np.full((40, 2), 1.25, np.float32)
    y, counts = mla_ops.experts_forward(*map(jnp.asarray,
                                             (x, idx, w, wg, wu, wd)))
    assert np.asarray(counts).tolist() == [0, 40, 0, 0, 0, 0, 40, 0]
    np.testing.assert_allclose(np.asarray(y),
                               _dense_experts(x, idx, w, wg, wu, wd),
                               atol=1e-4)


def test_an_expert_with_no_token_gives_no_nan_and_padding_routes_nowhere():
    x, wg, wu, wd = _experts(6, seed=3)
    wg[4] = np.nan                            # expert 4 receives nothing
    idx = np.array([[0, 1], [2, 3], [5, 6], [7, 0], [4, 4], [1, 2]], np.int32)
    w = np.full((6, 2), 0.5, np.float32)
    valid = np.array([1, 1, 1, 1, 0, 1], bool)   # the row sent to 4 is padding
    y, counts = mla_ops.experts_forward(
        *map(jnp.asarray, (x, idx, w, wg, wu, wd)), jnp.asarray(valid))
    y = np.asarray(y)
    assert np.isfinite(y).all() and np.asarray(counts)[4] == 0
    assert int(np.asarray(counts).sum()) == 10
    np.testing.assert_array_equal(y[4], 0.0)
    keep = [0, 1, 2, 3, 5]
    np.testing.assert_allclose(
        y[keep], _dense_experts(x[keep], idx[keep], w[keep],
                                np.nan_to_num(wg), wu, wd), atol=1e-4)


def test_engine_counts_experts_by_phase():
    eng, _cfg, _w = make_engine(TINY)
    eng.generate(prompts_of(5, lens=(9, 12)), 4)
    st = eng.core.moe_stats
    assert st["prefill"]["layer_steps"] == 2 * 2      # 2 prompts x 2 layers
    assert st["decode"]["layer_steps"] == 3 * 2       # 3 decode steps
    # a step of two rows, top-2: at most four experts a layer
    assert 0 < st["decode"]["experts_touched"] <= 4 * 3 * 2
    assert st["decode"]["expert_load_max_over_mean"] >= \
        st["decode"]["layer_steps"]


# -- multi-token prediction --------------------------------------------------
MTP = MLADecoderConfig(mtp_layers=1)


def test_mtp_logits_match_reference():
    eng, cfg, weights = make_engine(MTP, spec_k=1, proposer=MTPDrafter())
    drafter = eng.proposer
    prompt = prompts_of(6, lens=(19,))[0]
    req = Request(0, prompt, 4)
    eng.submit(req)
    eng.step()                                # prefill, then one verify
    first = req.out_tokens[0]
    # the drafter's rows over the prompt, again, keeping their logits
    job_hidden = np.asarray(REF.hidden_states(
        weights, jnp.asarray(prompt, jnp.int32), ref_cfg(cfg))[0])
    want = np.asarray(REF.mtp_logits_all_positions(
        weights, prompt + [first], ref_cfg(cfg)))
    eng2, _, _ = make_engine(MTP, spec_k=1, proposer=MTPDrafter())
    r2 = Request(0, prompt, 4)
    eng2.submit(r2)
    eng2.core.kv.append_tokens(0, len(prompt), tokens=prompt)
    eng2.proposer.after_prefill(r2, job_hidden, first, keep_logits=True)
    np.testing.assert_allclose(eng2.proposer.last_logits, want, atol=3e-4)
    assert drafter.propose(req, 1) != [] or len(req.out_tokens) >= 4


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_mtp_drafter_leaves_greedy_tokens_unchanged(seed):
    prompts = prompts_of(10 + seed, lens=(5, 16, 23))
    plain, _, _ = make_engine(MTP, seed=seed)
    spec, _, _ = make_engine(MTP, seed=seed, spec_k=1, proposer=MTPDrafter())
    want = plain.generate(prompts, 10)
    got = spec.generate(prompts, 10)
    assert got == want
    assert spec.stats["spec_proposed"] > 0


def test_mtp_weights_load_only_with_the_module():
    from paddle_tpu.inference.mla_decoder import mla_param_specs

    assert not any(n.startswith("mtp_") for n in mla_param_specs(TINY))
    assert "mtp_proj" in mla_param_specs(MTP)
    assert len(MTP.cache_pool_names()) == len(TINY.cache_pool_names()) + 1


# -- what the engine refuses for this model ----------------------------------
@pytest.mark.parametrize("kw,match", [
    ({"prefix_cache": True}, "chunk"),
    ({"prefill_chunk": 16}, "chunk"),
    ({"kv_dtype": "int8"}, "int8"),
    ({"tp": 2}, "tensor-parallel"),
])
def test_engine_refuses_what_the_model_has_no_form_for(kw, match):
    cfg = TINY
    with pytest.raises(ValueError, match=match):
        ServingEngine(cfg=cfg, weights=init_mla_weights(cfg, 0),
                      **{"kv_dtype": "float32", "num_pages": 16,
                         "page_size": 8, **kw})


def test_pool_is_one_latent_row_a_token_and_layer():
    eng, cfg, _ = _shared_engine()
    pool = eng.core.scope.get("kv_lat_0")
    assert pool.shape == (1, 64, 8, cfg.kv_lora_rank + cfg.qk_rope_head_dim)
    wide = MLADecoderConfig(kv_lora_rank=512, qk_rope_head_dim=64)
    assert wide.latent_width == 576 and wide.latent_row == 640
    assert wide.kv_cache_config(4, 16, "bfloat16").pool_shape() == \
        (1, 4, 16, 640)


# -- the kernels' bodies, in the interpreter ---------------------------------
@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")


def _decode_case(lens, width, pages, tables, reps):
    """Operands of one ``mla_decode`` call: ``lens`` the rows' contexts,
    tables ``width`` pages wide over a pool of ``pages`` (``random``:
    drawn with repeats, so rows share pages; ``own``: every table's pages
    its own; ``reps`` consecutive rows read one table)."""
    rng = np.random.RandomState(len(lens))
    n, heads, rank, rope, ps = len(lens), 8, 16, 8, 8
    pool = rng.randn(1, pages, ps, rank + rope).astype(np.float32)
    q_lat = jnp.asarray(rng.randn(n, heads, rank), jnp.float32)
    q_rope = jnp.asarray(rng.randn(n, heads, rope), jnp.float32)
    if tables == "own":
        need = [-(-max(lens[t * reps:(t + 1) * reps]) // ps)
                for t in range(n // reps)]
        assert sum(need) <= pages and max(need) <= width
        order, at = rng.permutation(pages), 0
        tables = np.zeros((n // reps, width), np.int32)
        for t, k in enumerate(need):
            tables[t, :k] = order[at:at + k]
            at += k
    else:
        tables = rng.randint(0, pages, (n // reps, width)).astype(np.int32)
    return q_lat, q_rope, pool, tables, np.asarray(lens, np.int32)


# (contexts, table width, pool pages, pages a grid step, tables, rows a
# table)
DECODE_CASES = {
    "one_chunk_a_row": ((5, 70, 96), 12, 40, None, "random", 1),
    "one_token": ((1,), 1, 40, None, "random", 1),
    "two_rows": ((64, 33), 8, 40, None, "random", 1),
    # chunks of 32 tokens: 1, 3, 1, 3, 1 live of 3 a row
    "ragged_with_padded_rows": ((5, 70, 1, 96, 1), 12, 40, 4, "own", 1),
    # 10 pages are 3 chunks of 4, the table padded to 12
    "width_not_whole_chunks": ((70, 9, 80), 10, 40, 4, "own", 1),
    # 5 pages each of a pool of 15, every page of it some row's: 2 chunks a
    # row, 6 steps where 15 // 4 + 3 rows bound what exclusive pages give
    "pool_full_of_exclusive_pages": ((33, 40, 36), 16, 15, 4, "own", 1),
    "room_in_the_pool": ((33, 8, 70, 1), 16, 24, 4, "own", 1),
    # rows that share 8 pages walk 15 chunks, past any bound the pool gives
    "rows_share_pages": ((100, 120, 128, 90), 16, 8, 4, "random", 1),
    # every row walks its whole table: the list is as long as it can be
    "every_chunk_live": ((128, 128, 127), 16, 8, 4, "random", 1),
    # a verify call: three rows a table, contexts one apart
    "verify_rows_share_a_table": ((30, 31, 32, 70, 71, 72), 12, 40, 4, "own",
                                  3),
    # chunks of 6 pages fetched by groups of 3, a table of 14
    "groups_and_a_table_that_do_not_divide": ((100, 17, 49, 90), 14, 40, 6,
                                              "own", 1),
    "verify_four_rows_a_table": ((30, 31, 32, 33, 70, 71, 72, 73), 16, 16, 2,
                                 "own", 4),
}


def _chunks_of(monkeypatch, step):
    """Chunks of ``step`` pages fetched by groups of half as many (the
    kernel's own sizes where ``step`` is None)."""
    if step:
        monkeypatch.setattr(mla_kernels, "DECODE_PAGES_PER_STEP", step)
        monkeypatch.setattr(mla_kernels, "DECODE_PAGES_PER_FETCH",
                            max(step // 2, 1))


@pytest.mark.parametrize("case", list(DECODE_CASES))
def test_mla_decode_kernel(interpreted, monkeypatch, case):
    lens, width, pages, step, how, reps = DECODE_CASES[case]
    _chunks_of(monkeypatch, step)
    q_lat, q_rope, pool, tables, ctx = _decode_case(lens, width, pages, how,
                                                    reps)
    got = mla_kernels.mla_decode(q_lat, q_rope, jnp.asarray(pool),
                                 jnp.asarray(tables), jnp.asarray(ctx), 0.2)
    want = mla_kernels.mla_decode_reference(
        q_lat, q_rope, jnp.asarray(pool),
        jnp.asarray(np.repeat(tables, reps, axis=0)), jnp.asarray(ctx), 0.2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # the grid is the chunks that hold context, of those the tables span
    steps, spanned = mla_kernels.decode_walk_counts(ctx, width, 8)
    pages_a_step, n_chunks = mla_kernels.decode_chunks(width)
    assert spanned == len(lens) * n_chunks
    assert steps == sum(min(max(-(-n // (8 * pages_a_step)), 1), n_chunks)
                        for n in lens) <= spanned


@pytest.mark.parametrize("case", ["ragged_with_padded_rows",
                                  "width_not_whole_chunks",
                                  "pool_full_of_exclusive_pages",
                                  "verify_rows_share_a_table"])
def test_mla_decode_kernel_reads_no_row_past_a_context(interpreted,
                                                       monkeypatch, case):
    """Every pool row no context reaches holds NaN (whole pages, and the
    rest of each table's last page): the result is the finite one the
    reference gives over a pool with zeros there."""
    lens, width, pages, step, how, reps = DECODE_CASES[case]
    _chunks_of(monkeypatch, step)
    q_lat, q_rope, pool, tables, ctx = _decode_case(lens, width, pages, how,
                                                    reps)
    live = np.zeros(pool.shape[1] * pool.shape[2], bool)
    for b, n in enumerate(lens):
        live[(tables[b // reps][:, None] * 8 + np.arange(8)).reshape(-1)[:n]] \
            = True
    live = live.reshape(pool.shape[1:3])
    clean = pool.copy()
    pool[0][~live], clean[0][~live] = np.nan, 0.0
    got = mla_kernels.mla_decode(q_lat, q_rope, jnp.asarray(pool),
                                 jnp.asarray(tables), jnp.asarray(ctx), 0.2)
    want = mla_kernels.mla_decode_reference(
        q_lat, q_rope, jnp.asarray(clean),
        jnp.asarray(np.repeat(tables, reps, axis=0)), jnp.asarray(ctx), 0.2)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("lens,tokens,n_chunks", [
    ((5, 70, 1, 96, 1), 32, 3),              # ragged, padded rows
    ((33, 40, 36), 32, 2),                   # the list is as long as it gets
    ((0, 300, 64), 32, 4),                   # no context: one visit; over
                                             # the table: the table's chunks
    ((256,) * 4, 256, 1),                    # a chunk a row
])
def test_decode_work_list(lens, tokens, n_chunks):
    ctx = np.asarray(lens, np.int32)
    visits = len(lens) * n_chunks
    row, chunk, n_live = map(np.asarray, mla_kernels.decode_work_list(
        jnp.asarray(ctx), tokens, n_chunks))
    assert row.shape == chunk.shape == (visits,)
    per_row = np.clip(-(-ctx // tokens), 1, n_chunks)
    assert n_live == per_row.sum() <= visits
    # every live (row, chunk) once, rows in order, a row's chunks ascending
    want = [(b, i) for b, k in enumerate(per_row) for i in range(k)]
    assert list(zip(row[:n_live], chunk[:n_live])) == want
    # nothing past n_live: the rest repeat the last real visit
    assert (row[n_live:] == row[n_live - 1]).all()
    assert (chunk[n_live:] == chunk[n_live - 1]).all()
    assert (per_row == mla_kernels.live_chunks(ctx, tokens, n_chunks)).all()


def test_mla_decode_kernel_ignores_what_lies_past_the_context(interpreted):
    rng = np.random.RandomState(0)
    pool = np.asarray(rng.randn(1, 6, 8, 24), np.float32)
    pool[0, 3:] = np.nan                      # pages never written
    tables = jnp.asarray([[0, 1, 2, 3, 4, 5, 3, 4, 5, 3, 4, 5]], jnp.int32)
    q_lat = jnp.asarray(rng.randn(1, 8, 16), jnp.float32)
    q_rope = jnp.asarray(rng.randn(1, 8, 8), jnp.float32)
    out = mla_kernels.mla_decode(q_lat, q_rope, jnp.asarray(pool), tables,
                                 jnp.asarray([20], jnp.int32), 0.2)
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("s", [128, 1024, 1536])
def test_mla_prefill_kernel(interpreted, s):
    """One block, whole blocks, and blocks the diagonal cuts."""
    rng = np.random.RandomState(s)
    heads, dn, dr, dv = 4, 16, 8, 16
    q_nope, k_nope = (jnp.asarray(rng.randn(heads, s, dn), jnp.float32)
                      for _ in range(2))
    q_rope = jnp.asarray(rng.randn(heads, s, dr), jnp.float32)
    k_r = jnp.asarray(rng.randn(s, dr), jnp.float32)
    v = jnp.asarray(rng.randn(heads, s, dv), jnp.float32)
    got = mla_kernels.mla_prefill(q_nope, q_rope, k_nope, k_r, v, 0.2)
    want = mla_kernels.mla_prefill_reference(q_nope, q_rope, k_nope, k_r, v,
                                             0.2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_latent_append_kernel(interpreted):
    rng = np.random.RandomState(0)
    pool = jnp.asarray(rng.randn(1, 10, 8, 24), jnp.float32)
    rows = jnp.asarray(rng.randn(7, 24), jnp.float32)
    slots = jnp.asarray([3, 8, 9, 80, 17, 2, 79], jnp.int32)   # 80: the pad
    got = mla_kernels.latent_append(pool, rows, slots)
    want = mla_kernels.latent_append_reference(pool, rows, slots)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# (sizes of the 8 groups, rows no expert owns, n): the first four are a
# decode step's (a handful of rows an expert: small tiles, column blocks);
# the rest a prefill's (``rows >= 16 * experts``: one grid step a visit at
# the whole width, the experts' matrices fetched one expert ahead)
GMM_CASES = {
    "decode-mixed": ((0, 5, 40, 0, 3, 0, 17, 1), 14, 256),
    "decode-empty": ((0, 0, 0, 0, 0, 0, 0, 0), 14, 256),
    "decode-one-expert": ((66, 0, 0, 0, 0, 0, 0, 0), 14, 256),
    "two-large-groups": ((300, 1, 0, 200, 0, 0, 11, 0), 14, 256),
    # every group of a tile's size and every one astride a boundary
    "every-group-straddles": ((64, 128, 128, 128, 128, 128, 128, 192), 0,
                              256),
    "every-group-exactly-a-tile": ((128,) * 8, 0, 256),
    # groups of one row and of one to three tiles, none on a boundary
    "ones-and-several-tiles": ((130, 1, 384, 1, 129, 255, 1, 260), 0, 256),
    "empty-between-full": ((256, 0, 0, 128, 0, 384, 0, 128), 0, 256),
    "one-expert-owns-all": ((0, 0, 0, 1000, 0, 0, 0, 0), 0, 256),
    # five whole tiles after the groups that no visit reaches
    "tail-of-unvisited-tiles": ((90, 200, 0, 130, 7, 0, 300, 40), 640, 256),
    # widths that are no power of two; 640: 512, 384, 256 do not divide it
    "width-384": ((150, 0, 129, 3, 0, 260, 0, 64), 30, 384),
    "width-no-block-divides": ((150, 0, 129, 3, 0, 260, 0, 64), 30, 640),
    # one chip's share: three rows in four are another chip's
    "share-three-quarters-unowned": ((70, 130, 0, 128, 55, 1, 60, 68), 1536,
                                     256),
    # groups of 256 rows and more: the tile of 256 in pieces of 128
    "tile-256": ((300, 0, 513, 256, 255, 1, 700, 30), 45, 256),
    "prefill-empty": ((0, 0, 0, 0, 0, 0, 0, 0), 256, 256),
}


@pytest.mark.parametrize("case", list(GMM_CASES))
@pytest.mark.parametrize("gated", [True, False])
@pytest.mark.parametrize("ahead", [True, False])
def test_moe_gmm_kernel(interpreted, monkeypatch, case, gated, ahead):
    """The rows the experts own against the reference; the rows past them
    are unspecified.  ``ahead`` off: matrices too large for two experts'
    worth of VMEM, so a prefill call keeps the pipeline's fetches and
    column blocks."""
    sizes, tail, n = GMM_CASES[case]
    if not ahead:
        monkeypatch.setattr(mla_kernels, "GMM_AHEAD_MAX_ELEMENTS", 0)
    rng = np.random.RandomState(sum(sizes))
    rows, k = sum(sizes) + tail, 128
    want_tm = 256 if rows >= 2048 else 128 if rows >= 128 else None
    tm, _, took = mla_kernels.gmm_schedule(rows, 8, k, n, 1 + gated)
    assert took == (ahead and rows >= 128)
    assert want_tm is None or tm == want_tm
    x = jnp.asarray(rng.randn(rows, k), jnp.float32)
    ws = tuple(jnp.asarray(rng.randn(8, k, n) / 12, jnp.float32)
               for _ in range(2 if gated else 1))
    group_sizes = jnp.asarray(sizes, jnp.int32)
    got = mla_kernels.moe_gmm(x, ws, group_sizes, gated)
    want = mla_kernels.moe_gmm_reference(x, ws, group_sizes, gated)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got)[:sum(sizes)],
                               np.asarray(want)[:sum(sizes)], atol=1e-4)
    # what is left of the last tile a visit reached was blanked as the
    # tile was opened
    reached = -(-sum(sizes) // tm) * tm
    assert (np.asarray(got)[sum(sizes):reached] == 0).all()


def test_moe_gmm_kernel_bfloat16_operands_float32_sums(interpreted):
    """Operands as served (bfloat16), sums in float32, the gated output in
    the weights' type and the down projection's float32: against the same
    products of the bfloat16 values taken in float32."""
    rng = np.random.RandomState(5)
    sizes = (100, 28, 0, 300, 129, 1, 66, 16)
    rows, k, f = sum(sizes) + 128, 256, 384
    x = jnp.asarray(rng.randn(rows, k), jnp.bfloat16)
    wg, wu = (jnp.asarray(rng.randn(8, k, f) / 16, jnp.bfloat16)
              for _ in range(2))
    wd = jnp.asarray(rng.randn(8, f, k) / 16, jnp.bfloat16)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    assert mla_kernels.gmm_schedule(rows, 8, k, f, 2).ahead
    hmid = mla_kernels.moe_gmm(x, (wg, wu), group_sizes, True,
                               out_dtype=jnp.bfloat16)
    assert hmid.dtype == jnp.bfloat16
    want = mla_kernels.moe_gmm_reference(x, (wg, wu), group_sizes, True)
    own = sum(sizes)
    np.testing.assert_allclose(
        np.asarray(hmid.astype(jnp.float32))[:own], np.asarray(want)[:own],
        rtol=2 ** -7, atol=2e-3)
    ys = mla_kernels.moe_gmm(hmid, (wd,), group_sizes, False)
    assert ys.dtype == jnp.float32
    want = mla_kernels.moe_gmm_reference(hmid, (wd,), group_sizes, False)
    np.testing.assert_allclose(np.asarray(ys)[:own], np.asarray(want)[:own],
                               rtol=1e-5, atol=1e-4)


def _experts_reference(x, idx, weight, w_gate, w_up, w_down, valid):
    """Every token times every expert, the choices summed: float64."""
    x, wg, wu, wd = (np.asarray(a, np.float64)
                     for a in (x, w_gate, w_up, w_down))
    g = np.einsum("nh,ehf->nef", x, wg)
    h = g / (1 + np.exp(-g)) * np.einsum("nh,ehf->nef", x, wu)
    per = np.einsum("nef,efh->neh", h, wd)              # (n, experts, h)
    y = np.zeros_like(x)
    for t in range(x.shape[0]):
        for j in range(idx.shape[1]):
            e = int(idx[t, j])
            if e < wg.shape[0] and (valid is None or valid[t]):
                y[t] += float(weight[t, j]) * per[t, e]
    return y


def _gmm_with_nan_past_the_groups(x, ws, sizes, **kw):
    """``moe_gmm`` with every row it owes nobody filled with NaN, the worst
    a buffer can hold (rows flat or apart)."""
    out = mla_kernels.moe_gmm(x, ws, sizes, **kw)
    keep = jnp.arange(out.shape[0]) < jnp.sum(sizes)
    return jnp.where(keep.reshape((-1,) + (1,) * (out.ndim - 1)), out,
                     jnp.nan)


@pytest.mark.parametrize("share", [False, True])
@pytest.mark.parametrize("padded", [False, True])
def test_experts_forward_masks_what_moe_gmm_leaves_unwritten(
        interpreted, monkeypatch, share, padded):
    """Prefill sizes through the kernel: the rows of padding and of another
    chip's experts sort past the groups, ``moe_gmm`` leaves their tiles
    unwritten (NaN here, the worst a buffer can hold: every row past the
    groups, those of the last visited tile too) and the combine selects
    them away: no NaN reaches a token's sum, nor the down call's owned
    rows."""
    monkeypatch.setattr(mla_ops, "moe_gmm", _gmm_with_nan_past_the_groups)
    rng = np.random.RandomState(3 + share)
    n, k, held, routed, h, f = 96, 4, 8, 32 if share else 8, 128, 128
    x = jnp.asarray(rng.randn(n, h), jnp.float32)
    idx = np.stack([rng.choice(routed, k, replace=False) for _ in range(n)])
    weight = rng.rand(n, k).astype(np.float32)
    valid = np.arange(n) < 61 if padded else None
    wg, wu = (jnp.asarray(rng.randn(held, h, f) / 12, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.randn(held, f, h) / 12, jnp.float32)
    assert mla_kernels.gmm_schedule(n * k, held, h, f, 2).ahead
    y, counts = mla_ops.experts_forward(
        x, jnp.asarray(idx, jnp.int32), jnp.asarray(weight), wg, wu, wd,
        None if valid is None else jnp.asarray(valid), share)
    live = idx[valid] if padded else idx
    np.testing.assert_array_equal(
        np.asarray(counts), np.bincount(live[live < held], minlength=held))
    want = _experts_reference(x, idx, weight, wg, wu, wd, valid)
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-4)


def _take_and_einsum_path(x, idx, weight, w_gate, w_up, w_down, valid, share):
    """The dispatch and combine as they were before ``moe_rows_in`` and
    ``moe_combine``: XLA's ``take`` of all ``n * k`` rows in, the grouped
    matmuls' oracle, ``take`` of all rows back, the select and the einsum."""
    n, k = idx.shape
    experts = w_gate.shape[0]
    flat = idx.reshape(-1)
    if share:
        flat = jnp.where(flat < experts, flat, experts)
    if valid is not None:
        flat = jnp.where(jnp.repeat(valid, k), flat, experts)
    order = jnp.argsort(flat)
    counts = jnp.bincount(flat, length=experts + 1)[:experts]
    xs = jnp.take(x, order // k, axis=0)
    hmid = mla_kernels.moe_gmm_reference(xs, (w_gate, w_up), counts, True,
                                         w_gate.dtype)
    ys = mla_kernels.moe_gmm_reference(hmid, (w_down,), counts, False)
    y = jnp.take(ys, jnp.argsort(order), axis=0).reshape(n, k, -1)
    y = jnp.where((flat < experts).reshape(n, k, 1), y, 0.0)
    w = weight if valid is None else jnp.where(valid[:, None], weight, 0.0)
    return jnp.einsum("nkh,nk->nh", y, w), counts


# name -> (tokens, k, experts held, experts routed over, real tokens or None,
#          experts that receive nothing, by the kernels)
ROW_CASES = {
    "every-expert-held": (96, 4, 8, 8, None, (), True),
    "a-share-most-rows-absent": (96, 4, 8, 32, None, (), True),
    "padded-rows": (96, 4, 8, 8, 61, (), True),
    "a-share-with-padded-rows": (96, 4, 8, 32, 61, (), True),
    "an-expert-with-no-token": (96, 4, 8, 8, None, (0, 5), True),
    "no-row-owned-at-all": (64, 2, 8, 32, None, tuple(range(8)), True),
    "rows-no-multiple-of-a-tile": (203, 2, 8, 16, 150, (3,), True),
    "more-tokens-than-a-step-sums": (300, 2, 8, 16, None, (), True),
    "a-decode-step": (12, 4, 8, 32, None, (), False),
}


def _row_case(name):
    n, k, held, routed, real, empty, _ = ROW_CASES[name]
    rng = np.random.RandomState(len(name))
    h = f = 128
    take = [e for e in range(routed) if e not in empty]
    idx = np.stack([rng.choice(take, k, replace=False) for _ in range(n)])
    # a token none of whose experts are held, where the layer is a share
    if routed > held:
        idx[5] = np.arange(held, held + k)
    x = jnp.asarray(rng.randn(n, h), jnp.float32)
    weight = jnp.asarray(rng.rand(n, k), jnp.float32)
    wg, wu = (jnp.asarray(rng.randn(held, h, f) / 12, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.randn(held, f, h) / 12, jnp.float32)
    valid = None if real is None else jnp.arange(n) < real
    return (x, jnp.asarray(idx, jnp.int32), weight, wg, wu, wd, valid,
            routed > held)


@pytest.mark.parametrize("name", sorted(ROW_CASES))
def test_experts_forward_moves_the_owned_rows_alone(interpreted, monkeypatch,
                                                    name):
    """``moe_rows_in`` and ``moe_combine`` in ``experts_forward`` against
    the path they replace (XLA's ``take`` both ways, the select and the
    einsum over ``moe_gmm_reference``) and against every token times every
    expert in float64, with every row ``moe_gmm`` owes nobody filled with
    NaN: the kernels read none of them.  A decode step's handful of rows
    keeps the ``take``."""
    monkeypatch.setattr(mla_ops, "moe_gmm", _gmm_with_nan_past_the_groups)
    args = _row_case(name)
    n, k = args[1].shape
    assert mla_kernels.moe_rows_engage(n * k, 8, 128) == ROW_CASES[name][-1]
    y, counts = mla_ops.experts_forward(*args)
    was, counts_were = _take_and_einsum_path(*args)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(counts_were))
    for e in ROW_CASES[name][5]:
        assert int(counts[e]) == 0
    y = np.asarray(y)
    assert y.shape == (n, 128) and np.isfinite(y).all()
    # the same k products a token in float32; only the order of their sum
    np.testing.assert_allclose(y, np.asarray(was), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        y, _experts_reference(*(np.asarray(a) if a is not None else None
                                for a in args[:7])), atol=2e-4)
    if args[7]:
        np.testing.assert_array_equal(y[5], 0.0)     # none of its experts here


@pytest.mark.parametrize("name", sorted(n for n, c in ROW_CASES.items()
                                        if c[-1]))
def test_rows_in_and_combine_against_their_oracles(interpreted, name):
    """Each kernel alone against its jnp oracle on the rows that are owed:
    ``moe_rows_in`` row for row the bits of ``x`` in the weights' type, and
    nothing specified past ``total``; ``moe_combine`` over a ``ys`` that is
    NaN past ``total`` (and, a row apart, the last owned row exactly)."""
    x, idx, weight, wg, _, _, valid, share = _row_case(name)
    n, k = idx.shape
    flat = idx.reshape(-1)
    flat = jnp.where(flat < 8, flat, 8)
    if valid is not None:
        flat = jnp.where(jnp.repeat(valid, k), flat, 8)
    order = jnp.argsort(flat).astype(jnp.int32)
    total = jnp.sum(flat < 8)
    owned = int(total)
    xs = mla_kernels.moe_rows_in(x, order, total, k, jnp.bfloat16)
    assert xs.shape == (n * k, 128) and xs.dtype == jnp.bfloat16
    want = mla_kernels.moe_rows_in_reference(x, order, total, k, jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(xs[:owned], np.float32),
                                  np.asarray(want[:owned], np.float32))
    rng = np.random.RandomState(owned)
    ys = rng.randn(n * k, 128).astype(np.float32)
    ys[owned:] = np.nan
    y = mla_kernels.moe_combine(jnp.asarray(ys)[:, None, :], order, total,
                                weight)
    want = mla_kernels.moe_combine_reference(jnp.asarray(ys), order, total,
                                             weight)
    assert np.isfinite(np.asarray(y)).all()
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5,
                               atol=1e-6)


@pytest.mark.parametrize("name", sorted(n for n, c in ROW_CASES.items()
                                        if c[-1]))
def test_combine_work_list_holds_the_owned_choices_alone(name):
    """What ``moe_combine`` is told to copy, against a brute list: every
    owned choice once, in the tokens' order, under the grid step of its
    token and at its place there; no entry for a choice that is not owned
    (a row ``moe_gmm`` never wrote), so no step has more entries than its
    buffer has rows -- also where the tokens are no multiple of a step's."""
    _, idx, _, _, _, _, valid, _ = _row_case(name)
    n, k = idx.shape
    flat = np.where(np.asarray(idx).reshape(-1) < 8,
                    np.asarray(idx).reshape(-1), 8)
    if valid is not None:
        flat = np.where(np.repeat(np.asarray(valid), k), flat, 8)
    order = np.argsort(flat, kind="stable").astype(np.int32)
    owned = int((flat < 8).sum())
    (src, place, start), tokens, steps = mla_kernels.combine_work_list(
        jnp.asarray(order), jnp.int32(owned), n, k)
    src, place, start = (np.asarray(a) for a in (src, place, start))
    width = k * tokens
    assert steps * tokens >= n > (steps - 1) * tokens
    assert start.shape == (steps + 1,) and start[0] == 0
    assert start[-1] == owned
    assert (np.diff(start) <= width).all()
    step_of = np.repeat(np.arange(steps), np.diff(start))
    choice = step_of * width + place[:owned]
    # the owned choices, each once, in the tokens' order
    np.testing.assert_array_equal(choice, np.sort(order[:owned]))
    # beside each the sorted row that holds its product
    assert (src[:owned] < owned).all()
    np.testing.assert_array_equal(order[src[:owned]], choice)


# sha256 of the float32 bytes of the rows the experts own, as the kernel of
# commit 0167326 (before PR 38 touched ``mla_kernels.py``) returned them
DECODE_GMM_DOWN_SHA256 = \
    "b80def7a9001d00be91ea9b968605abb18a7e005030150de566cc0c82fc650ad"
DECODE_GMM_GATED_SHA256 = \
    "3238d5e9e0f48becf5b506bd2fb3b50df66c10c5d1cab74ea046058bb125d555"


def _fixed_decode_gmm(gated):
    """A decode step's sizes (80 rows over 8 experts: small tiles) in small
    whole numbers over eighths: every product and every sum is exact in
    float32, so a matmul's bits do not depend on the order of its sums."""
    rng = np.random.RandomState(38)
    sizes = np.array((0, 5, 40, 0, 3, 0, 17, 1), np.int32)
    rows, k, n = int(sizes.sum()) + 14, 128, 256
    x = jnp.asarray(rng.randint(-3, 4, (rows, k)), jnp.bfloat16)
    ws = tuple(jnp.asarray(rng.randint(-2, 3, (8, k, n)) / 8.0, jnp.bfloat16)
               for _ in range(2 if gated else 1))
    return x, ws, jnp.asarray(sizes)


@pytest.mark.parametrize("gated", [False, True])
def test_moe_gmm_decode_branch_is_bit_for_bit_the_parents(interpreted, gated):
    """The decode-size branch (``rows < 16 * experts``) on fixed inputs:
    the down call's bits against the parent's, recorded before the kernel
    was touched.  The gated call's depend on the machine's ``exp`` besides:
    held bit for bit to ``silu(g) * u`` of the exact products, and to the
    record where this machine computes that as the recording one did."""
    import hashlib

    x, ws, sizes = _fixed_decode_gmm(gated)
    assert x.shape[0] < 16 * sizes.shape[0]
    out = mla_kernels.moe_gmm(x, ws, sizes, gated, out_dtype=jnp.bfloat16
                              if gated else jnp.float32)
    owned = np.asarray(out.astype(jnp.float32))[:int(sizes.sum())]
    said = hashlib.sha256(owned.tobytes()).hexdigest()
    if not gated:
        assert said == DECODE_GMM_DOWN_SHA256
        return
    owner = np.repeat(np.arange(8), np.asarray(sizes))
    xs = x[:owner.size].astype(jnp.float32)
    g, u = (jnp.einsum("mk,mkn->mn", xs, w[owner].astype(jnp.float32))
            for w in ws)
    want = np.asarray((g * jax.nn.sigmoid(g) * u).astype(jnp.bfloat16)
                      .astype(jnp.float32))
    np.testing.assert_array_equal(owned, want)
    if hashlib.sha256(want.tobytes()).hexdigest() == DECODE_GMM_GATED_SHA256:
        assert said == DECODE_GMM_GATED_SHA256


def test_engine_through_the_kernels_matches_reference(interpreted):
    """Widths the kernels engage at (heads 8, lanes of 128), bfloat16 as
    served: prefill, decode and the append run their kernel bodies."""
    cfg = MLADecoderConfig(hidden=128, num_heads=8, moe_intermediate=128,
                           intermediate=256, num_layers=2)
    eng, cfg, weights = make_engine(cfg, "bfloat16")
    reqs = [Request(i, p, 5) for i, p in
            enumerate(prompts_of(7, lens=(9, 20)))]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    worst, _ = served_against_reference(eng, cfg, weights, reqs)
    assert worst <= 8e-2, worst


@pytest.mark.parametrize("rows,experts", [
    (32768, 256),       # JoyAI's 4096-token prefill: a tile an expert
    (16384, 256),       # its 2048 bucket
    (65536, 64),        # Kimi's share: a quarter of the rows, tiles of 256
    (1024, 256),        # JoyAI's decode step: small tiles
    (1024, 64),         # Kimi's decode step: the tile of 128
])
def test_gmm_walk_counts_against_a_brute_count(rows, experts):
    """``moe_gmm_row_tiles`` / ``moe_gmm_visits`` as the engine counts them,
    against every (tile, expert) pair tried one by one and against the work
    list the kernel is given."""
    rng = np.random.RandomState(rows + experts)
    owned = rows // 4 if experts == 64 else rows - rows // 3
    sizes = rng.multinomial(owned, np.ones(experts) / experts)
    sizes[rng.choice(experts, experts // 8, replace=False)] = 0
    tm = mla_kernels.gmm_schedule(rows, experts, 128, 128, 1).tm
    assert tm == (32 if rows < 16 * experts else
                  256 if rows >= 256 * experts else 128)
    ends = np.cumsum(sizes)
    tiles = -(-rows // tm)
    shared = [(t, e) for t in range(tiles) for e in range(experts)
              if sizes[e] and max(t * tm, ends[e] - sizes[e])
              < min((t + 1) * tm, ends[e])]
    row_tiles, visits = mla_kernels.gmm_walk_counts(sizes, rows)
    assert visits == len(shared)
    assert row_tiles == len({t for t, _ in shared})
    assert row_tiles <= visits <= row_tiles + (sizes > 0).sum() - 1
    lists = mla_kernels._gmm_work_list(jnp.asarray(sizes, jnp.int32), tm,
                                       tiles)
    group, tile, _, n_visits, nxt, slot = (np.asarray(a) for a in lists)
    assert int(n_visits[0]) == visits
    assert list(zip(tile[:visits], group[:visits])) == \
        sorted(shared, key=lambda p: (p[1], p[0]))
    # an expert's weights wait in the slot the visit before it did not use
    turn = np.flatnonzero(np.diff(group[:visits])) + 1
    assert (slot[turn] != slot[turn - 1]).all()
    assert (nxt[turn - 1] == group[turn]).all() and nxt[visits - 1] == -1
    assert mla_kernels.gmm_walk_counts(np.zeros(experts), rows) == (0, 0)


def test_engine_counts_what_moe_gmm_walks(interpreted):
    """``moe_stats`` folds each call's tokens per expert into what its
    grouped matmuls walked, by phase, in ``eng.stats`` and the registry:
    two calls an expert layer; a decoder whose kernel does not engage and
    GPT-2 say nothing."""
    from paddle_tpu.utils import telemetry as tm

    cfg = MLADecoderConfig(hidden=128, num_heads=8, moe_intermediate=128,
                           intermediate=256, num_layers=2)
    eng, cfg, _ = make_engine(cfg, "bfloat16")
    eng.generate(prompts_of(3, lens=(70, 100)), 4)
    assert "prefill" not in eng.stats["kernels"]      # nothing read yet
    assert "moe_gmm_calls" not in eng.stats["kernels"]["decode"]
    moe = eng.core.moe_stats
    walk = eng.stats["kernels"]
    expert_layers = cfg.num_layers - cfg.first_k_dense
    for phase in ("prefill", "decode"):
        st = walk[phase]
        assert st["moe_gmm_calls"] >= 2 * moe[phase]["layer_steps"] > 0
        assert st["moe_gmm_calls"] % (2 * expert_layers) == 0
        assert 0 < st["moe_gmm_row_tiles"] <= st["moe_gmm_visits"]
    # a prompt's bucket of 128 tokens x 2 choices is 32 rows an expert, two
    # tiles of 128 that the 8 experts' groups share: 2 + 8 - 1 visits at most
    pre = walk["prefill"]
    assert pre["moe_gmm_visits"] <= pre["moe_gmm_calls"] * 9
    assert pre["moe_gmm_row_tiles"] <= pre["moe_gmm_calls"] * 2
    said = tm.snapshot()["moe_gmm_visits"]["series"]
    assert any(s["labels"] == {"phase": "prefill"}
               and s["value"] >= pre["moe_gmm_visits"] for s in said)
    # read again: nothing is counted twice
    before = {ph: dict(st) for ph, st in walk.items()}
    eng.core.moe_stats
    assert eng.stats["kernels"] == before
    plain, _, _ = make_engine(TINY)                   # 4 heads, width 32
    plain.generate(prompts_of(3, lens=(9,)), 2)
    plain.core.moe_stats
    assert plain.stats["kernels"] == {}


def test_engine_counts_what_mla_decode_walks(interpreted):
    """The decode form says from its feed what its kernels walk, by phase,
    in ``eng.stats`` and the registry; a form whose kernel does not engage
    (4 heads) says nothing."""
    from paddle_tpu.utils import telemetry as tm

    cfg = MLADecoderConfig(hidden=128, num_heads=8, moe_intermediate=128,
                           intermediate=256, num_layers=2)
    eng, cfg, _ = make_engine(cfg, "bfloat16")
    eng.generate(prompts_of(3, lens=(9, 20)), 4)
    walk = eng.stats["kernels"]
    assert set(walk) == {"decode"} and walk is eng.core.kernel_stats
    assert set(walk["decode"]) == {"mla_decode_calls", "mla_decode_grid_steps",
                                   "mla_decode_table_chunks"}
    st, steps = walk["decode"], eng.stats["decode_steps"]
    assert st["mla_decode_calls"] == cfg.num_layers * steps
    # contexts of 10..24 tokens: a chunk a row, two rows a step, and tables
    # one chunk wide
    assert st["mla_decode_grid_steps"] == st["mla_decode_table_chunks"] \
        == 2 * st["mla_decode_calls"]
    said = tm.snapshot()["mla_decode_grid_steps"]["series"]
    assert any(s["labels"] == {"phase": "decode"}
               and s["value"] >= st["mla_decode_grid_steps"] for s in said)
    plain, _, _ = make_engine(TINY)
    plain.generate(prompts_of(3, lens=(9,)), 2)
    assert plain.stats["kernels"] == {}
