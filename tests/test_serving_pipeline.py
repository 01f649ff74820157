"""``ServingEngine(pipeline=k)``: the steps of ``_step`` with no call's
tokens read before the calls of the next ``k`` steps are dispatched.  The
schedule and every token are those of the plain engine; the events come ``k``
steps later.
"""
import dataclasses

import jax
import numpy as np
import pytest

from paddle_tpu.inference.mla_decoder import (MLADecoderConfig,
                                              init_mla_weights)
from paddle_tpu.inference.serving import (DecoderConfig, Request,
                                          ServingEngine)
from paddle_tpu.inference.spec_decode import SamplingParams

GPT = DecoderConfig(vocab_size=64, hidden=32, num_layers=2, num_heads=2,
                    max_seq_len=96)
MLA = MLADecoderConfig()
# page_size 8: prompts under, at and over a page; outputs of one token (the
# prefill's own), two, and many
WORK = [(5, 1), (8, 2), (9, 6), (17, 11), (30, 4), (3, 9), (12, 1), (21, 7)]


def engine(cfg, pipeline, **kw):
    weights = init_mla_weights(cfg, 0) if isinstance(cfg, MLADecoderConfig) \
        else cfg.init_weights(0)
    kw.setdefault("num_pages", 64)
    kw.setdefault("max_batch", 3)
    return ServingEngine(cfg=cfg, weights=weights, page_size=8,
                         token_budget=64, pipeline=pipeline, **kw)


def requests(vocab, work=WORK, seed=0):
    rng = np.random.RandomState(seed)
    return [Request(i, rng.randint(0, vocab, size=n).tolist(), want)
            for i, (n, want) in enumerate(work)]


def serve(eng, reqs):
    """Every step's events, in order, until nothing is left."""
    for r in reqs:
        eng.submit(r)
    steps = []
    while eng.has_work():
        steps.append(eng.step(float(len(steps))))
        assert len(steps) < 500
    return steps


@pytest.mark.parametrize("cfg", [GPT, MLA], ids=["gpt2", "mla"])
@pytest.mark.parametrize("num_pages", [64, 9], ids=["roomy", "preempting"])
@pytest.mark.parametrize("depth", [1, 2, 5])
def test_pipelined_steps_serve_the_same_tokens_by_the_same_schedule(
        cfg, num_pages, depth):
    vocab = cfg.vocab_size
    plain, piped = engine(cfg, 0, num_pages=num_pages), \
        engine(cfg, depth, num_pages=num_pages)
    a, b = requests(vocab), requests(vocab)
    steps_a, steps_b = serve(plain, a), serve(piped, b)
    assert [r.out_tokens for r in a] == [r.out_tokens for r in b]
    assert all(len(r.out_tokens) == r.max_new_tokens for r in b)
    assert plain.stats == piped.stats
    assert (plain.stats["preempted"] > 0) == (num_pages == 9)
    assert plain.kv.stats()["peak_pages"] == piped.kv.stats()["peak_pages"]
    assert piped.kv.stats()["pages_in_use"] == 0
    assert sorted(piped._free_lanes) == list(range(piped.max_batch))
    # the same events, a request's in the same order, none lost or doubled
    def by_req(steps):
        out = {}
        for evs in steps:
            for e in evs:
                out.setdefault(e.req_id, []).append((e.token, e.finished))
        return out
    assert by_req(steps_a) == by_req(steps_b)
    assert all(r.finished_at is not None for r in b)


@pytest.mark.parametrize("depth,counts", [(True, [0, 4, 4]), (2, [0, 0, 8])])
def test_a_steps_tokens_are_delivered_depth_steps_later(depth, counts):
    eng = engine(GPT, depth)
    reqs = requests(GPT.vocab_size, [(6, 4), (7, 4)])
    steps = serve(eng, reqs)
    # step 0 dispatches two prefills and a decode and reads nothing.  One
    # deep: step 1 reads those four tokens; step 2 dispatches the last
    # decode, reads step 1's two tokens and, nothing being left to dispatch,
    # its own two.  Two deep: step 2 reads all eight
    assert [len(s) for s in steps] == counts
    assert steps[-1][-1].finished and not eng.has_work()
    assert eng.stats["decode_steps"] == 3


def test_tokens_stay_on_the_device_between_calls():
    eng = engine(GPT, True)
    for r in requests(GPT.vocab_size, [(6, 5), (9, 5)]):
        eng.submit(r)
    eng.step(0.0)
    assert isinstance(eng.core.board, jax.Array)
    assert all(isinstance(toks, jax.Array)
               for calls in eng._in_flight for toks, _ in calls)
    assert all(st.req.out_tokens == [] for st in eng.running)
    lanes = [st.lane for st in eng.running]
    assert len(set(lanes)) == 2 and all(0 <= n < eng.max_batch for n in lanes)


def test_mla_served_scores_are_those_of_the_plain_engine():
    plain, piped = engine(MLA, False), engine(MLA, True)
    plain.core.keep_scores = piped.core.keep_scores = True
    a, b = requests(MLA.vocab_size), requests(MLA.vocab_size)
    serve(plain, a), serve(piped, b)
    for ra, rb in zip(a, b):
        sa, routes_a = plain.core.served_scores(ra.req_id)
        sb, routes_b = piped.core.served_scores(rb.req_id)
        np.testing.assert_array_equal(sa, sb)
        np.testing.assert_array_equal(routes_a, routes_b)


@pytest.mark.parametrize("kw,match", [
    (dict(sampling=SamplingParams(temperature=0.7)), "sampled"),
    (dict(spec_k=2), "speculative"),
    (dict(prefill_chunk=16), "chunked"),
    (dict(prefix_cache=True), "prefix cache"),
    (dict(admission_policy="slo_aware"), "admission policy"),
    (dict(eos=3), "EOS"),
])
def test_pipeline_refuses_what_makes_the_schedule_depend_on_tokens(kw, match):
    cfg = GPT
    if "eos" in kw:
        cfg, kw = dataclasses.replace(GPT, eos_id=kw["eos"]), {}
    with pytest.raises(ValueError, match=match):
        engine(cfg, True, **kw)
