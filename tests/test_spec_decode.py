"""Speculative decoding + in-program sampling (r21).

Oracles:
* GREEDY spec-decode is **token-identical** to the monolithic baseline
  (exact-argmax acceptance) — including under preemption, chunked
  prefill and prefix-cache hits in the same trace — while issuing
  strictly fewer decode program calls whenever acceptance > 0;
* zero acceptance (NullProposer) degrades to EXACTLY the baseline:
  same event stream, same step count, same budget accounting;
* the verify program's per-row logits match the reference program's
  logits for the same prefix (the chunk-body drift guard);
* KV truncation (the reject rollback) is refcount/chain/index-correct
  at the allocator, for within-page and cross-page truncates;
* sampled decode: seeded traces replay bit-identically, RNG lanes are
  resume-invariant (pure functions of position, recomputed after
  preemption), and ``top_k=1`` sampling is token-identical to greedy
  end to end (spec + preemption included) — the whole sampled
  machinery under an ULP-robust head.  FREE sampling is deliberately
  NOT pinned token-identical across program forms: the
  prefill/decode/verify compositions differ at FP-ulp level, and
  ``jax.random.categorical`` can flip at nucleus/top-k filter
  boundaries where argmax cannot;
* ``admission.lost_work_cost`` counts only ACCEPTED tokens (rejected
  drafts were never emitted);
* both flags OFF are byte-identical to the r20 engine (event streams +
  stats + counters pinned), and ``loadgen.poisson_trace`` with
  ``repeat_frac=0`` draws the exact pre-r21 trace.
"""
import dataclasses

import numpy as np
import pytest

from paddle_tpu.inference.admission import lost_work_cost
from paddle_tpu.inference.kv_cache import KVCacheConfig, PagedKVCache
from paddle_tpu.inference.serving import (DecoderConfig, Request,
                                          SamplingParams, ServingEngine,
                                          _EngineCore, _pow2_bucket)
from paddle_tpu.inference.spec_decode import (NGramProposer, NullProposer,
                                              Proposer, get_proposer,
                                              rng_lane)
from paddle_tpu.ops import registry as op_registry
from paddle_tpu.utils import chaos
from paddle_tpu.utils import flags as _flags
from paddle_tpu.utils import telemetry, tracing

CFG = DecoderConfig(vocab_size=64, hidden=32, num_heads=4, num_layers=2,
                    max_seq_len=128)


@pytest.fixture(autouse=True)
def _fresh():
    saved = dict(_flags._flags)
    telemetry.registry().clear()
    tracing.reset()
    chaos.reset()
    yield
    tracing.reset()
    telemetry.registry().clear()
    _flags._flags.clear()
    _flags._flags.update(saved)
    telemetry.reset_slo()
    chaos.reset()


def make_engine(**kw):
    kw.setdefault("num_pages", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_batch", 4)
    kw.setdefault("token_budget", 64)
    kw.setdefault("prefill_bucket_min", 8)
    kw.setdefault("seed", 3)
    return ServingEngine(kw.pop("cfg", CFG), **kw)


def _prompts(seed=0, n=6, vocab=64, lo=4, hi=12):
    rng = np.random.RandomState(seed)
    return [list(map(int, rng.randint(0, vocab, size=rng.randint(lo, hi))))
            for _ in range(n)]


_GREEDY = {}


def greedy_prompts():
    return _prompts(seed=0, n=5)


def greedy_baseline():
    """Canonical greedy baseline (default engine, ``greedy_prompts``,
    max_new 10), computed once per process — pure token lists, safe to
    share across tests (the per-test fixture resets everything else)."""
    if "out" not in _GREEDY:
        eng = make_engine()
        _GREEDY["out"] = eng.generate(greedy_prompts(), max_new_tokens=10)
        _GREEDY["decode_steps"] = eng.stats["decode_steps"]
    return _GREEDY["out"]


def _event_stream(eng, prompts, max_new):
    reqs = [Request(i, list(p), max_new) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    events = []
    while eng.has_work():
        events.extend((e.req_id, e.token, e.finished) for e in eng.step())
    return events, eng.stats.copy()


class OracleProposer(Proposer):
    """Drafts the request's own true greedy continuation — every draft
    token verifies, so acceptance is total (the upper-bound fixture)."""

    def __init__(self, continuations):
        self.continuations = continuations  # req_id -> full greedy output

    def propose(self, req, k):
        cont = self.continuations[req.req_id]
        return cont[len(req.out_tokens):len(req.out_tokens) + k]


# ==========================================================================
# proposers + RNG lanes (pure host-side units)
# ==========================================================================
def test_rng_lane_pure_stable_and_distinct():
    assert rng_lane(3, "r1", 17) == rng_lane(3, "r1", 17)
    lanes = {rng_lane(3, "r1", p) for p in range(64)}
    assert len(lanes) == 64                        # positions separate
    assert rng_lane(3, "r1", 5) != rng_lane(3, "r2", 5)   # requests too
    assert rng_lane(3, "r1", 5) != rng_lane(4, "r1", 5)   # and seeds
    assert all(0 <= v < 2 ** 31 for v in lanes)    # int32-feedable


def test_ngram_proposer_prompt_lookup():
    req = Request("a", [1, 2, 3, 9, 9, 1, 2, 3], 8)
    # suffix [1,2,3] recurs at the front; its continuation is proposed
    assert NGramProposer().propose(req, 2) == [9, 9]
    assert NGramProposer().propose(req, 4) == [9, 9, 1, 2]
    # history extends into out_tokens
    req2 = Request("b", [7, 8], 8)
    req2.out_tokens = [5, 7, 8]
    assert NGramProposer().propose(req2, 3) == [5, 7, 8]
    # no recurrence -> no draft; k=0 -> no draft
    assert NGramProposer().propose(Request("c", [1, 2, 3, 4], 8), 3) == []
    assert NGramProposer().propose(req, 0) == []
    assert NullProposer().propose(req, 4) == []
    assert isinstance(get_proposer("ngram", max_n=2), NGramProposer)
    with pytest.raises(ValueError):
        get_proposer("nope")
    with pytest.raises(ValueError):
        NGramProposer(max_n=0)


# ==========================================================================
# the sample_token op
# ==========================================================================
def _sample(logits, seeds, **attrs):
    a = {"temperature": 1.0, "top_k": 0, "top_p": 1.0}
    a.update(attrs)
    out = op_registry.eager_call(
        "sample_token",
        {"Logits": [np.asarray(logits, np.float32)],
         "Seeds": [np.asarray(seeds, np.int32)]},
        a, {"Out": 1})
    return np.asarray(out["Out"][0])


def test_sample_token_greedy_degenerates_to_argmax():
    rng = np.random.RandomState(0)
    logits = rng.randn(5, 16).astype(np.float32)
    got = _sample(logits, np.arange(5), temperature=0.0)
    np.testing.assert_array_equal(got, np.argmax(logits, axis=-1))


def test_sample_token_respects_topk_topp_support():
    rng = np.random.RandomState(1)
    logits = rng.randn(8, 32).astype(np.float32)
    seeds = np.arange(100, 108)
    # top-k: every draw must land in each row's k largest logits
    got = _sample(logits, seeds, top_k=4)
    for i, t in enumerate(got):
        assert t in np.argsort(logits[i])[-4:]
    # top-p: every draw must land in the row's nucleus set
    got = _sample(logits, seeds, top_p=0.5)
    for i, t in enumerate(got):
        order = np.argsort(-logits[i])
        probs = np.exp(logits[i][order] - logits[i].max())
        probs /= probs.sum()
        cum = np.cumsum(probs)
        nucleus = order[:int(np.searchsorted(cum, 0.5) + 1)]
        assert t in nucleus
    # deterministic in the lanes; different lanes decorrelate
    again = _sample(logits, seeds, top_p=0.5)
    np.testing.assert_array_equal(got, again)
    same_row = np.tile(logits[:1], (8, 1))
    draws = _sample(same_row, np.arange(8) * 977, temperature=2.0)
    assert len(set(draws.tolist())) > 1


# ==========================================================================
# greedy spec-decode: the token-identity oracle
# ==========================================================================
def test_greedy_spec_token_identical_and_fewer_calls():
    prompts = greedy_prompts()
    base_out = greedy_baseline()
    spec = make_engine(spec_k=4)
    spec_out = spec.generate(prompts, max_new_tokens=10)
    assert spec_out == base_out
    # and the baseline equals the one-at-a-time reference (so spec
    # output transitively matches the full-recompute oracle)
    ref = [spec.core.greedy_reference(p, 10) for p in prompts]
    assert spec_out == ref
    assert spec.stats["spec_accepted"] > 0
    assert spec.stats["decode_steps"] < _GREEDY["decode_steps"]
    # telemetry mirrors the stats
    snap = telemetry.snapshot()
    assert snap["spec_proposed_total"]["series"][0]["value"] == \
        spec.stats["spec_proposed"]
    assert snap["spec_accepted_total"]["series"][0]["value"] == \
        spec.stats["spec_accepted"]
    rate = snap["spec_accept_rate"]["series"][0]["value"]
    assert rate == pytest.approx(spec.stats["spec_accepted"]
                                 / spec.stats["spec_proposed"])


def test_greedy_spec_identity_under_preemption():
    prompts = greedy_prompts()
    base_out = greedy_baseline()
    spec = make_engine(spec_k=4, num_pages=8, page_size=4)  # tight pool
    spec_out = spec.generate(prompts, max_new_tokens=10)
    assert spec.stats["preempted"] > 0
    assert spec_out == base_out


def test_greedy_spec_identity_with_prefix_cache_and_chunked_prefill():
    rng = np.random.RandomState(5)
    shared = list(map(int, rng.randint(0, 64, size=20)))
    prompts = [shared + p for p in _prompts(seed=6, n=3, lo=3, hi=8)] \
        + _prompts(seed=7, n=2)
    base = make_engine()
    base_out = base.generate(prompts, max_new_tokens=8)
    spec = make_engine(spec_k=4, prefix_cache=True, prefill_chunk=8)
    spec_out = spec.generate(prompts, max_new_tokens=8)
    assert spec.stats["prefill_hit_tokens"] > 0   # cache hits in-trace
    assert spec.stats["prefill_chunks"] > len(prompts)  # chunking too
    assert spec.stats["spec_accepted"] > 0
    assert spec_out == base_out


def test_oracle_proposer_full_acceptance():
    prompts = greedy_prompts()[:3]
    base_out = greedy_baseline()[:3]
    conts = {i: list(o) for i, o in enumerate(base_out)}
    spec = make_engine(spec_k=4, proposer=OracleProposer(conts))
    spec_out = spec.generate(prompts, max_new_tokens=10)
    assert spec_out == base_out
    assert spec.stats["spec_accepted"] == spec.stats["spec_proposed"] > 0


def test_zero_accept_is_exactly_baseline():
    prompts = greedy_prompts()
    base = make_engine()
    a = _event_stream(base, prompts, 8)
    null = make_engine(spec_k=4, proposer=NullProposer())
    b = _event_stream(null, prompts, 8)
    # identical event stream, step count and token accounting — the
    # only difference allowed is the (zero) spec counters themselves, and
    # which form the steps ran (``decode_feed_shapes`` counts the decode
    # form's feeds: a spec engine's steps are verify calls)
    assert b[0] == a[0]
    for k in set(a[1]) - {"decode_feed_shapes"}:
        assert b[1][k] == a[1][k], k
    assert a[1]["decode_feed_shapes"] and not b[1]["decode_feed_shapes"]
    assert null._spec_debt == 0


def test_eos_mid_draft_stops_exactly_like_baseline():
    prompts = greedy_prompts()
    probe_out = greedy_baseline()
    # pick an EOS that fires mid-stream for at least one request
    eos = next(o[2] for o in probe_out if len(o) > 3)
    cfg = dataclasses.replace(CFG, eos_id=int(eos))
    base = make_engine(cfg=cfg)
    base_out = base.generate(prompts, max_new_tokens=10)
    assert any(o[-1] == eos and len(o) < 10 for o in base_out)
    spec = make_engine(cfg=cfg, spec_k=4)
    spec_out = spec.generate(prompts, max_new_tokens=10)
    assert spec_out == base_out


def test_spec_budget_charges_accepted_plus_one():
    prompts = greedy_prompts()
    spec = make_engine(spec_k=4)
    out = spec.generate(prompts, max_new_tokens=10)
    assert out == greedy_baseline()
    # every decode token was charged: emitted = prefill-emitted (one
    # per admission) + decode-emitted, and the carried debt is settled
    assert spec._spec_debt == 0
    assert spec.stats["decode_tokens"] == \
        sum(len(o) for o in out) - spec.stats["admitted"]
    # a verify call can never emit more than token_budget tokens: the
    # debt mechanism keeps the budget an invariant across steps
    tight = make_engine(spec_k=4, token_budget=16, max_batch=2)
    tight_out = tight.generate(prompts, max_new_tokens=10)
    assert tight_out == out
    assert tight._spec_debt == 0


# ==========================================================================
# verify program == reference program (logits parity)
# ==========================================================================
def test_verify_logits_match_reference():
    prompts = _prompts(seed=8, n=3)
    eng = make_engine(spec_k=3)
    core = eng.core
    rec = {}
    orig_vb = core.verify_batch
    orig_run = core.exe.run

    def vb(items):
        if "logits" not in rec and any(d for _, d in items):
            rec["ctx"] = [(list(st.req.prompt) + list(st.req.out_tokens),
                           list(d)) for st, d in items]

            def shim(prog, feed=None, fetch_list=None, scope=None):
                out = orig_run(prog, feed=feed, fetch_list=fetch_list,
                               scope=scope)
                # re-fetch the logits under the same feed (the KV
                # append rewrites identical values into the same slots)
                rec["logits"] = np.asarray(orig_run(
                    prog, feed=feed, fetch_list=[prog._form_extras.logits],
                    scope=scope)[0])
                rec["S"] = _pow2_bucket(max(1 + len(d) for _, d in items))
                core.exe.run = orig_run
                return out

            core.exe.run = shim
        return orig_vb(items)

    core.verify_batch = vb
    eng.generate(prompts, max_new_tokens=8)
    assert "logits" in rec, "no verify call carried a draft"

    S = rec["S"]
    logits = rec["logits"]
    for i, (prefix, draft) in enumerate(rec["ctx"]):
        for j in range(len(draft) + 1):
            got = logits[i * S + j]
            want = core.reference_logits(prefix + draft[:j])
            np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


# ==========================================================================
# KV truncation (the reject rollback) at the allocator
# ==========================================================================
def _kv(num_pages=8, page_size=4, **kw):
    return PagedKVCache(KVCacheConfig(num_pages=num_pages,
                                      page_size=page_size,
                                      num_kv_heads=1, head_dim=8), **kw)


def test_truncate_within_page():
    kv = _kv(prefix_cache=True)
    toks = list(range(100, 106))                  # 1 full page + 2 tail
    kv.append_tokens("A", 6, tokens=toks)
    pages = list(kv._seqs["A"].pages)
    kv.truncate_tokens("A", 1)
    assert kv.context_len("A") == 5
    assert kv._seqs["A"].pages == pages           # same pages kept
    assert kv._seqs["A"].tokens == toks[:5]
    # the stale 2-token tail entry is gone; the kept 1-token tail is
    # re-registered, so a 5-token prefix still hits but the dropped
    # 6th token does NOT
    hit, _ = kv.match_prefix(toks[:5] + [1, 2])
    assert hit == 5
    # appends resume over the truncated slots
    s = kv.append_tokens("A", 1, tokens=[55])
    assert s.tolist() == [pages[-1] * 4 + 1]


def test_truncate_cross_page_reclaims_and_rechains():
    kv = _kv(prefix_cache=True)
    toks = list(range(10))                        # 2 full + 2-token tail
    kv.append_tokens("A", 10, tokens=toks)
    free0 = kv.free_count
    kv.truncate_tokens("A", 4)                    # back to 6 tokens
    assert kv.context_len("A") == 6
    assert kv.free_count == free0 + 1             # tail page released
    assert kv._seqs["A"].tokens == toks[:6]
    # the kept page (tokens 4..7 written, only 4..5 counted) is demoted
    # from the full-page index to a 2-token partial, which breaks the
    # digest chain to the parked third page: the long prefix no longer
    # hits, the truncated 6-token prefix does — pinned semantics
    hit, _ = kv.match_prefix(toks)
    assert hit == 6
    # refcounted sharing: a shared tail page is never popped from under
    # the sharer
    kv2 = _kv(prefix_cache=True)
    t2 = list(range(50, 59))                      # 2 full + 1 tail
    kv2.append_tokens("X", 9, tokens=t2)
    hit, pages = kv2.match_prefix(t2)
    kv2.acquire_prefix("Y", t2, pages)
    assert kv2.refcount(pages[-1]) == 2
    kv2.truncate_tokens("Y", 1)                   # Y backs off the tail
    assert kv2.refcount(pages[-1]) == 1           # X keeps it
    assert kv2.context_len("X") == 9


def test_truncate_without_prefix_cache_plain_rewind():
    kv = _kv()                                    # cache off (default)
    kv.append_tokens("A", 10)
    free0 = kv.free_count
    kv.truncate_tokens("A", 5)
    assert kv.context_len("A") == 5
    assert kv.free_count == free0 + 1
    with pytest.raises(ValueError):
        kv.truncate_tokens("A", 6)
    kv.truncate_tokens("A", 0)                    # no-op guard
    assert kv.context_len("A") == 5


# ==========================================================================
# lost work counts accepted tokens only
# ==========================================================================
def test_lost_work_cost_counts_accepted_tokens_and_span_attrs():
    _flags.set_flags({"trace_requests": 1})
    prompts = greedy_prompts()[:2]
    eng = make_engine(spec_k=4)
    reqs = [Request(i, list(p), 10) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    for _ in range(3):
        if eng.has_work():
            eng.step()
    ran = [st.req for st in eng.running]
    assert ran, "need a running request mid-trace"
    for req in ran:
        # traced cost == prompt + emitted tokens (the untraced truth):
        # rejected draft tokens are NOT lost work
        assert lost_work_cost(req) == len(req.prompt) + len(req.out_tokens)
    eng.run_to_completion()
    # spec-path decode_step spans carry the proposed/accepted attrs...
    spans = [s for t in tracing.store().finished_traces()
             for s in t.spans if s.name == "decode_step"]
    assert spans and all("proposed" in s.attrs and "accepted" in s.attrs
                         for s in spans)
    # ...and flag-off spans carry NEITHER (byte-identical span schema)
    tracing.reset()
    base = make_engine()
    base.generate(prompts, max_new_tokens=4)
    spans = [s for t in tracing.store().finished_traces()
             for s in t.spans if s.name == "decode_step"]
    assert spans and not any("proposed" in s.attrs or "accepted" in s.attrs
                             for s in spans)


# ==========================================================================
# sampled decode: replay determinism + resume-invariant lanes
# ==========================================================================
SP = SamplingParams(temperature=0.8, top_k=20, top_p=0.95)


def test_sampled_replay_is_bit_identical():
    prompts = greedy_prompts()

    def run(spec_k):
        eng = make_engine(sampling=SP, spec_k=spec_k)
        return eng.generate(prompts, max_new_tokens=10), eng.stats

    a, b = run(0), run(0)
    assert a == b
    assert run(4) == run(4)
    # the sampled stream differs from greedy (the knob really engages)
    assert a[0] != greedy_baseline()


def test_sampled_topk1_token_identical_to_greedy_everywhere():
    # top_k=1 keeps only the argmax token, so the categorical draw is
    # lane-independent — the full sampled machinery (per-slot lane
    # feeds, sample_token head in every program form, verify-row
    # lanes) under an ULP-robust head must reproduce greedy exactly,
    # spec + preemption + truncation included
    prompts = greedy_prompts()
    k1 = SamplingParams(temperature=0.7, top_k=1)
    greedy = greedy_baseline()
    assert make_engine(sampling=k1).generate(
        prompts, max_new_tokens=10) == greedy
    spec = make_engine(sampling=k1, spec_k=4)
    assert spec.generate(prompts, max_new_tokens=10) == greedy
    assert spec.stats["spec_accepted"] > 0
    tight = make_engine(sampling=k1, spec_k=4, num_pages=8, page_size=4)
    assert tight.generate(prompts, max_new_tokens=10) == greedy
    assert tight.stats["preempted"] > 0


def test_rng_lanes_resume_invariant(monkeypatch):
    prompts = greedy_prompts()
    orig = _EngineCore._lane

    def capture():
        lanes = {}

        def rec(self, req, offset=0):
            v = orig(self, req, offset)
            pos = len(req.prompt) + len(req.out_tokens) + offset
            lanes.setdefault((req.req_id, pos), set()).add(v)
            return v

        monkeypatch.setattr(_EngineCore, "_lane", rec)
        return lanes

    l1 = capture()
    make_engine(sampling=SP, spec_k=4).generate(prompts, max_new_tokens=10)
    l2 = capture()
    eng = make_engine(sampling=SP, spec_k=4, num_pages=8, page_size=4)
    eng.generate(prompts, max_new_tokens=10)
    assert eng.stats["preempted"] > 0
    # one lane per (request, position) within a run, equal across the
    # uncontended and the preempted run on every shared position, and
    # exactly the pure function of (seed, req_id, position)
    for lanes in (l1, l2):
        assert lanes and all(len(v) == 1 for v in lanes.values())
    for key in set(l1) & set(l2):
        assert l1[key] == l2[key]
        rid, pos = key
        assert l1[key] == {rng_lane(3, rid, pos)}


# ==========================================================================
# flags + defaults: byte-identity with everything off
# ==========================================================================
def test_flags_off_byte_identical_to_r20():
    prompts = _prompts(seed=11, n=4)

    def run(**kw):
        telemetry.registry().clear()
        eng = make_engine(num_pages=6, page_size=4, token_budget=32, **kw)
        ev = _event_stream(eng, prompts, 5)
        snap = telemetry.snapshot()
        counters = {k: v["series"][0]["value"] for k, v in snap.items()
                    if (k.startswith("serving_") or k.startswith("spec_"))
                    and v["type"] == "counter" and not v["labels"]}
        return ev, counters

    a = run()                                      # flag defaults
    b = run(spec_k=0, sampling=None)               # explicit off
    assert a == b
    assert a[0][1]["preempted"] >= 1               # the schedule bites
    assert a[0][1]["spec_proposed"] == 0
    assert a[0][1]["spec_accepted"] == 0
    assert not any(k.startswith("spec_") for k in a[1])


def test_flags_arm_spec_and_sampling():
    _flags.set_flags({"spec_decode_k": 2, "sample_temperature": 0.5})
    eng = make_engine()
    assert eng.spec_k == 2
    assert isinstance(eng.proposer, NGramProposer)
    assert eng.sampling is not None \
        and eng.sampling.temperature == pytest.approx(0.5)
    eng2 = make_engine(spec_k=0, sampling=SamplingParams())
    assert eng2.spec_k == 0 and eng2.sampling is None


def test_repeat_frac_off_is_bit_identical():
    from paddle_tpu.utils.loadgen import poisson_trace

    kw = dict(num_requests=12, rate=30.0, vocab_size=64, seed=9)
    a = poisson_trace(**kw)
    b = poisson_trace(repeat_frac=0.0, **kw)
    assert [(e.req_id, e.arrival, e.prompt, e.max_new_tokens) for e in a] \
        == [(e.req_id, e.arrival, e.prompt, e.max_new_tokens) for e in b]
    # armed: arrivals/lengths untouched (derived seed), prompts become
    # self-similar, and the whole thing is deterministic
    c = poisson_trace(repeat_frac=0.6, **kw)
    d = poisson_trace(repeat_frac=0.6, **kw)
    assert [(e.arrival, len(e.prompt), e.max_new_tokens) for e in c] \
        == [(e.arrival, len(e.prompt), e.max_new_tokens) for e in a]
    assert [e.prompt for e in c] != [e.prompt for e in a]
    assert [e.prompt for e in c] == [e.prompt for e in d]
