"""One block-table width for a decode form whose kernels walk live chunks.

``mla_decode`` and ``gqa_decode`` run a grid exactly as long as a work list
of the chunks that hold context, so a table column past a row's context is
int32 in SMEM and never a step or a fetch.  Such a form says so
(``FormExtras.live_walk_pages``) and the engine then feeds it ONE table width
(``_EngineCore.decode_table_floor``): one program a batch bucket where a
width a bucket of contexts was several.  Held here, on the CPU with the
kernels' bodies in the interpreter: the kernels' output and walk do not
change with the width; the engine's tokens are the bucketed engine's, through
one ``(padded batch, table width)`` a batch bucket; a narrower table fed
straight to ``_run`` (a warm-up that enumerates widths) compiles nothing
more; a form that offers no width is bucketed as before; and past the width a
form offers the contexts' own buckets come back.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference import gqa_decoder, mla_decoder
from paddle_tpu.inference.gpt2_decoder import DecoderConfig
from paddle_tpu.inference.mla_decoder import MLADecoderConfig
from paddle_tpu.inference.serving import Request, ServingEngine
from paddle_tpu.ops import gqa_kernels, mla_kernels
from test_gqa_decoder import TINY as GQA_TINY
from test_gqa_decoder import make_engine as make_gqa_engine
from test_gqa_decoder import paged_case
from test_hybrid_decoder import TINY as HYBRID_TINY
from test_hybrid_decoder import make_engine as make_hybrid_engine
from test_mla_decoder import make_engine as make_mla_engine


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")


def widened(tables, times):
    """``tables`` ``times`` as wide, the new columns page 0 (what
    ``PagedKVCache.block_table`` pads with)."""
    return np.pad(tables, ((0, 0), (0, (times - 1) * tables.shape[1])))


# -- (a) the kernels: the same output and the same walk under a wider table --
@pytest.mark.parametrize("lens", [(5, 70, 1, 96, 1), (128, 128, 127),
                                  (33, 8, 70, 1)])
def test_mla_decode_is_bit_identical_under_a_wider_table(interpreted,
                                                         monkeypatch, lens):
    """Chunks of 4 pages over tables 16, 32 and 64 pages wide: the rows'
    chunks that hold context are the same list, so the output is the same
    bits, the grid the same steps; only what the tables span grows."""
    monkeypatch.setattr(mla_kernels, "DECODE_PAGES_PER_STEP", 4)
    monkeypatch.setattr(mla_kernels, "DECODE_PAGES_PER_FETCH", 2)
    rng = np.random.RandomState(len(lens))
    n, heads, rank, rope, ps, pages, width = len(lens), 8, 16, 8, 8, 40, 16
    pool = jnp.asarray(rng.randn(1, pages, ps, rank + rope), jnp.float32)
    q_lat = jnp.asarray(rng.randn(n, heads, rank), jnp.float32)
    q_rope = jnp.asarray(rng.randn(n, heads, rope), jnp.float32)
    tables = rng.randint(0, pages, (n, width)).astype(np.int32)
    ctx = np.asarray(lens, np.int32)
    outs, walks = [], []
    for times in (1, 2, 4):
        wide = widened(tables, times)
        outs.append(np.asarray(mla_kernels.mla_decode(
            q_lat, q_rope, pool, jnp.asarray(wide), jnp.asarray(ctx), 0.2)))
        walks.append(mla_kernels.decode_walk_counts(ctx, wide.shape[1], ps))
        # the list the kernel's grid is as long as
        n_live = mla_kernels.decode_work_list(
            jnp.asarray(ctx), 4 * ps, wide.shape[1] // 4)[2]
        assert int(n_live) == walks[-1][0]
    assert np.array_equal(outs[0], outs[1]) \
        and np.array_equal(outs[0], outs[2])
    steps, spanned = zip(*walks)
    assert steps[0] == steps[1] == steps[2]
    assert spanned == (spanned[0], 2 * spanned[0], 4 * spanned[0])


@pytest.mark.parametrize("window", [0, 8, 16])
def test_gqa_decode_is_bit_identical_under_a_wider_table(interpreted,
                                                         monkeypatch, window):
    """A full layer's table (64 pages here) and a window layer's (the
    window's pages and two), 1, 2 and 4 times as wide: the same bits, the
    same steps and pages walked."""
    monkeypatch.setattr(gqa_kernels, "DECODE_PAGES_PER_STEP", 4)
    monkeypatch.setattr(gqa_kernels, "DECODE_PAGES_PER_FETCH", 2)
    (q, kp, vp, tables, ctx, first), want = paged_case(
        7 + window, 6, [1, 3, 8, 9, 13, 17, 40, 41, 64, 200], window,
        pages=160)
    outs, walks = [], []
    for times in (1, 2, 4):
        wide = widened(tables, times)
        outs.append(np.asarray(gqa_kernels.gqa_decode(
            *(jnp.asarray(a) for a in (q, kp, vp, wide, ctx, first)), 0.25,
            window)))
        walks.append(gqa_kernels.decode_walk_counts(
            ctx, first, wide.shape[1], 4, window))
    np.testing.assert_allclose(outs[0], want, atol=2e-5)
    assert np.array_equal(outs[0], outs[1]) \
        and np.array_equal(outs[0], outs[2])
    assert walks[0] == walks[1] == walks[2]


# -- (b) the engine: one width a batch bucket, the bucketed engine's tokens --
MLA_8_HEADS = MLADecoderConfig(hidden=128, num_heads=8, moe_intermediate=128,
                               intermediate=256, num_layers=2, max_seq_len=96)


def mla_engine(**kw):
    return make_mla_engine(MLA_8_HEADS, **kw)[0]          # pages of 8 rows


def gqa_engine(**kw):
    cfg = dataclasses.replace(GQA_TINY, max_seq_len=64)   # pages of 4 rows
    return make_gqa_engine(cfg, **kw)[0]


def hybrid_engine(**kw):
    cfg = dataclasses.replace(HYBRID_TINY, num_heads=8, max_seq_len=96,
                              num_layers=4, mixers=("kda", "kda", "kda",
                                                    "mla"))
    return make_hybrid_engine(cfg, **kw)[0]               # pages of 8 rows


#: name -> (engine, the model module whose ``_live_walk`` its decode form
#: offers, the pages of ``max_seq_len``)
ENGINES = {"mla": (mla_engine, mla_decoder, 12),
           "gqa_window": (gqa_engine, gqa_decoder, 16),
           "hybrid": (hybrid_engine, mla_decoder, 12)}


def bucketed(monkeypatch, module):
    """From here on ``module``'s decode forms offer no width: the engine
    built next buckets its tables by the contexts, as every engine did."""
    monkeypatch.setattr(module, "_live_walk", lambda kv_config, cfg: None)


def serve_spread(eng, top, want=8):
    """Contexts from under one page to ``top`` (``max_seq_len``) tokens, in
    one engine: two short prompts that outlast the others (the batch's
    longest context shrinks as it goes), a middling one and one that ends at
    the model's longest context."""
    rng = np.random.RandomState(11)
    reqs = [Request(i, rng.randint(0, 128, size=n).tolist(), m)
            for i, (n, m) in enumerate(((2, 3 * want), (3, 3 * want),
                                        (top // 3, want),
                                        (top - want, want)))]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    assert all(len(r.out_tokens) == r.max_new_tokens for r in reqs)
    return [r.out_tokens for r in reqs]


def shapes_fed(monkeypatch, eng):
    """Record every decode feed's (padded batch, table width) as the
    executor is given it."""
    seen, run = [], eng.core.exe.run

    def spy(prog, feed=None, **kw):
        if prog is eng.core.decode_prog:
            seen.append(feed["block_tables"].shape)
        return run(prog, feed=feed, **kw)

    monkeypatch.setattr(eng.core.exe, "run", spy)
    return seen


@pytest.mark.parametrize("name,pipeline", [
    ("mla", 0), ("mla", 2), ("gqa_window", 0), ("gqa_window", 2),
    ("hybrid", 2)])
def test_engine_feeds_one_width_and_serves_the_bucketed_tokens(
        interpreted, monkeypatch, name, pipeline):
    make, module, pages = ENGINES[name]
    top = _pow2(pages)
    eng = make(pipeline=pipeline)
    page = eng.core.kv_config.page_size
    assert eng.core.decode_table_floor == top
    fed = shapes_fed(monkeypatch, eng)
    tokens = serve_spread(eng, pages * page)
    # ONE (padded batch, table width) a batch bucket
    assert {w for _, w in fed} == {top}
    assert len(set(fed)) == len({b for b, _ in fed}) \
        == eng.stats["decode_feed_shapes"]["decode"]
    walked = dict(eng.stats["kernels"]["decode"])

    bucketed(monkeypatch, module)
    plain = make(pipeline=pipeline)
    assert plain.core.decode_table_floor == 0
    fed_plain = shapes_fed(monkeypatch, plain)
    assert serve_spread(plain, pages * page) == tokens
    # the contexts' own buckets, several a batch bucket, none wider
    assert len({w for _, w in fed_plain}) > 2
    assert max(w for _, w in fed_plain) == top
    assert plain.stats["decode_feed_shapes"]["decode"] \
        == len(set(fed_plain)) > len(set(fed))
    # nothing more is walked: the same steps over the same pages, under
    # tables that span more
    plain_walked = plain.stats["kernels"]["decode"]
    for key in ("mla_decode_grid_steps", "gqa_decode_pages_walked",
                "gqa_decode_pages_in_context", "kda_decode_sequences"):
        assert walked.get(key) == plain_walked.get(key), key
    if "mla_decode_table_chunks" in walked:
        assert walked["mla_decode_table_chunks"] \
            >= plain_walked["mla_decode_table_chunks"]


def _pow2(n):
    return 1 << (n - 1).bit_length()


# -- (c) a warm-up that enumerates widths runs one program ---------------------
def decode_feed(core, b, w):
    """A decode feed of padding rows, as the benchmark's warm-ups build it."""
    kvc = core.kv_config
    feed = {"tokens": np.zeros(b, np.int32),
            "positions": np.zeros(b, np.int32),
            "block_tables": np.zeros((b, w), np.int32),
            "context_lens": np.ones(b, np.int32),
            "slot_mapping": np.full(b, kvc.pad_slot, np.int32)}
    if kvc.window:
        feed.update(
            window_slot_mapping=np.full(b, kvc.window_pad_slot, np.int32),
            window_tables=np.zeros((b, kvc.window_pages_per_seq), np.int32),
            window_first=np.zeros(b, np.int32))
    return feed


@pytest.mark.parametrize("name", ["mla", "gqa_window"])
def test_a_narrower_table_fed_to_run_compiles_nothing_more(interpreted,
                                                           monkeypatch, name):
    monkeypatch.setattr(mla_kernels, "DECODE_PAGES_PER_STEP", 4)
    monkeypatch.setattr(mla_kernels, "DECODE_PAGES_PER_FETCH", 2)
    make, _, pages = ENGINES[name]
    core = make().core
    top = _pow2(pages)

    def warm(b, w):
        np.asarray(core._run(core.decode_prog, decode_feed(core, b, w),
                             core.decode_fetch, "warm")[0])
        return len(core.exe._cache)

    # the first shape of a form compiles twice (its first call of all leaves
    # the RNG state in the scope in another type), as the warm-ups know
    warm(2, 1)
    compiled = warm(2, 1)
    for w in (2, 4, top // 2, top):
        assert warm(2, w) == compiled, w
    assert warm(4, 2) == compiled + 1                  # a batch bucket more
    assert warm(4, top) == compiled + 1
    assert core.decode_feed_shapes == {"warm": 2}
    # what the form says of its kernels reads the feed as run: every call's
    # tables span 16 pages, four chunks a row, whatever width it was fed
    if name == "mla":
        walk = core.kernel_stats["warm"]
        rows = 6 * 2 + 2 * 4                     # the calls above, by batch
        assert walk["mla_decode_table_chunks"] \
            == MLA_8_HEADS.num_layers * rows * (top // 4)
        assert walk["mla_decode_grid_steps"] == MLA_8_HEADS.num_layers * rows


# -- (d) a form that offers no width is bucketed as ever ---------------------------
def gpt2_engine():
    cfg = DecoderConfig(vocab_size=64, hidden=32, num_heads=4, num_layers=2,
                        max_seq_len=64)
    return ServingEngine(cfg=cfg, num_pages=64, page_size=4, max_batch=4,
                         token_budget=128)


def no_kernel_engine():
    return mla_engine()            # run without the interpreter: no kernel


@pytest.mark.parametrize("make", [gpt2_engine, no_kernel_engine])
def test_a_form_that_offers_no_width_is_bucketed_as_before(monkeypatch, make):
    """GPT-2's decode form (``paged_decode`` pays for every column) and an
    MLA form on the CPU without the interpreter (the gather fallback does
    too): the widths fed are the powers of two of the contexts' pages, and a
    table fed to ``_run`` is run as fed."""
    eng = make()
    core, page = eng.core, eng.core.kv_config.page_size
    assert core.decode_table_floor == 0
    fed, run = [], core.exe.run

    def spy(prog, feed=None, **kw):
        if prog is core.decode_prog:
            fed.append((feed["block_tables"].shape,
                        int(feed["context_lens"].max())))
        return run(prog, feed=feed, **kw)

    monkeypatch.setattr(core.exe, "run", spy)
    reqs = [Request(i, [1 + i] * n, 6) for i, n in enumerate((2, 9, 30))]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    # every width is the bucket of the step's most pages: the parent's rule
    assert all(w == _pow2(-(-ctx // page)) for (_, w), ctx in fed)
    widths = {w for (_, w), _ in fed}
    assert len(widths) > 1 and max(widths) == _pow2(-(-36 // page))
    assert eng.stats["decode_feed_shapes"]["decode"] \
        == len({shape for shape, _ in fed})
    feed = {"tokens": np.zeros(2, np.int32),
            "positions": np.zeros(2, np.int32),
            "block_tables": np.zeros((2, 2), np.int32),
            "context_lens": np.ones(2, np.int32),
            "slot_mapping": np.full(2, core.kv_config.pad_slot, np.int32)}
    del fed[:]
    core._run(core.decode_prog, feed, core.decode_fetch, "warm")
    assert fed == [((2, 2), 1)]
    assert core.decode_feed_shapes["warm"] == 1


# -- (e) past the width a form offers: today's buckets -------------------------------
def test_beyond_the_offered_width_the_contexts_buckets_return(interpreted,
                                                              monkeypatch):
    """The kernels' constant cut to 4 pages under a model of 12: contexts up
    to 4 pages run one width, longer ones the bucket of their own pages."""
    monkeypatch.setattr(mla_kernels, "DECODE_TABLE_PAGES", 4)
    eng = mla_engine()
    page = eng.core.kv_config.page_size
    assert eng.core.decode_table_floor == 4
    fed = shapes_fed(monkeypatch, eng)
    tokens = serve_spread(eng, 12 * page)
    assert {w for _, w in fed} == {4, 8, 16}
    monkeypatch.undo()
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    whole = mla_engine()
    assert whole.core.decode_table_floor == 16
    assert serve_spread(whole, 12 * page) == tokens

