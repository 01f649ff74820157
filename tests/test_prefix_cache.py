"""Copy-on-write KV prefix caching + chunked prefill (r19).

Oracles:
* CoW semantics at the allocator: full pages are immutable-once-full
  and indexed under a chained content digest; a write into a SHARED
  partial page forks it (the writer gets a private copy, every other
  sharer keeps the frozen original); frees decrement refcounts and
  reclaim ONLY at zero; refcount-0 cached pages evict in a
  deterministic seeded order;
* token identity is non-negotiable: prefix-hit decode output is
  byte-identical to a cold run, chunked prefill is token-identical to
  monolithic prefill (EOS and bucketing edges included), and shared-
  then-diverging suffixes produce exactly the cold outputs;
* prefix hit under preemption/resume: a preempted request's re-prefill
  hits its own earlier pages (the eviction kept them cached);
* both features OFF are byte-identical to the r18 engine (event
  streams + scheduler stats + KV counters pinned);
* chunked prefill bounds the per-step prefill work by the chunk budget
  (vs the full prompt length today) and serves prompts larger than the
  token budget;
* chaos ``pool_spike`` under CoW: seizure never touches a page a live
  sequence maps (a live shared prefix survives a spike) and release is
  refcount-correct — pinned with two engines under one schedule;
* the memory planner's ``kv_pool`` block and the engine's distinct-page
  accounting count shared pages ONCE.
"""
import numpy as np
import pytest

from paddle_tpu.inference.admission import lost_work_cost
from paddle_tpu.inference.kv_cache import KVCacheConfig, PagedKVCache
from paddle_tpu.inference.serving import (DecoderConfig, Request,
                                          ServingEngine)
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
    kw.setdefault("num_pages", 32)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_batch", 4)
    kw.setdefault("token_budget", 64)
    kw.setdefault("prefill_bucket_min", 8)
    return ServingEngine(kw.pop("cfg", CFG), **kw)


def _kv(num_pages=8, page_size=4, **kw):
    return PagedKVCache(KVCacheConfig(num_pages=num_pages,
                                      page_size=page_size,
                                      num_kv_heads=1, head_dim=8), **kw)


def _prompts(seed=7, n=4, vocab=64, lens=(5, 11, 6, 14)):
    rng = np.random.RandomState(seed)
    return [list(map(int, rng.randint(0, vocab, size=ln)))
            for ln in lens[:n]]


# ==========================================================================
# allocator: CoW semantics
# ==========================================================================
def test_full_pages_index_and_partial_share_forks_on_write():
    kv = _kv(prefix_cache=True)
    toks = list(range(100, 110))              # 2 full pages + 2-token tail
    kv.append_tokens("A", 10, tokens=toks)
    hit, pages = kv.match_prefix(toks + [1, 2])
    assert hit == 10 and pages == [0, 1, 2]   # full, full, partial tail
    kv.acquire_prefix("B", toks, pages)
    assert kv.refcount(2) == 2
    # B's first write into the shared partial page forks it
    slots = kv.append_tokens("B", 2, tokens=[1, 2])
    assert slots is not None
    forks = kv.take_forks()
    assert forks == [(2, 3, 2)]               # src, private copy, kept slots
    assert kv.refcount(2) == 1 and kv.refcount(3) == 1
    assert kv.stats()["prefix_cache"]["forked_pages"] == 1
    # A's original page content is frozen: A keeps appending into it
    # exclusively (no fork needed — refcount is back to 1)
    s = kv.append_tokens("A", 1, tokens=[55])
    assert s.tolist() == [10] and kv.take_forks() == []


def test_writer_side_fork_when_original_owner_appends():
    kv = _kv(prefix_cache=True)
    toks = list(range(9))                     # 2 full pages + 1-token tail
    kv.append_tokens("A", 9, tokens=toks)
    hit, pages = kv.match_prefix(toks + [40, 41])
    assert hit == 9
    kv.acquire_prefix("B", toks, pages)
    # now A (the ORIGINAL owner) writes first: A must fork, B keeps
    # the frozen page — fork-on-first-write is writer-symmetric
    kv.append_tokens("A", 1, tokens=[77])
    (src, dst, used), = kv.take_forks()
    assert used == 1 and kv.refcount(src) == 1 and kv.refcount(dst) == 1
    assert dst in kv._seqs["A"].pages and src in kv._seqs["B"].pages


def test_refcount_zero_only_reclaim():
    kv = _kv(prefix_cache=True)
    toks = list(range(8))                     # exactly 2 full pages
    kv.append_tokens("A", 8, tokens=toks)
    hit, pages = kv.match_prefix(toks + [9])
    kv.acquire_prefix("B", toks[:hit], pages)
    assert kv.refcount(0) == 2
    kv.free_sequence("A")
    # B still maps the pages: nothing reclaimed, nothing cached-free
    assert kv.refcount(0) == 1 and kv.pages_in_use == 2
    assert kv.stats()["prefix_cache"]["cached_pages"] == 0
    kv.free_sequence("B")
    # refcount zero: indexed pages park as evictable cache entries
    assert kv.pages_in_use == 0
    assert kv.stats()["prefix_cache"]["cached_pages"] == 2
    # and they still serve hits until evicted
    assert kv.match_prefix(toks)[0] == 8


def test_seeded_eviction_order_is_deterministic():
    def run():
        kv = _kv(num_pages=4, page_size=4, prefix_cache=True, seed=3)
        events = []
        for i in range(6):                    # 6 distinct 1-page prompts
            toks = [100 + i] * 4
            kv.append_tokens(f"s{i}", 4, tokens=toks)
            kv.free_sequence(f"s{i}")         # park as cached
            events.append(("round", i, kv.stats()["prefix_cache"]
                           ["evicted_pages"], sorted(kv._cached_free)))
        return events, kv.stats()

    a, b = run(), run()
    assert a == b                             # replay bit-identical
    assert a[1]["prefix_cache"]["evicted_pages"] >= 2  # eviction real
    # evicted entries left the index: their prompts miss, recent hit
    kv = _kv(num_pages=4, page_size=4, prefix_cache=True, seed=3)
    for i in range(6):
        kv.append_tokens(f"s{i}", 4, tokens=[100 + i] * 4)
        kv.free_sequence(f"s{i}")
    assert kv.match_prefix([105] * 4 + [0])[0] == 4     # newest cached
    assert kv.match_prefix([100] * 4 + [0])[0] == 0     # oldest evicted


def test_opaque_sequences_never_index():
    kv = _kv(prefix_cache=True)
    kv.append_tokens("spike", 4)              # tokens unknown -> opaque
    kv.free_sequence("spike")
    assert kv.stats()["prefix_cache"]["cached_pages"] == 0
    assert kv.num_free_pages == 8             # straight back to the pool


def test_flag_off_allocator_unchanged():
    kv = _kv(prefix_cache=False)
    kv.append_tokens("a", 9, tokens=list(range(9)))
    kv.free_sequence("a")
    assert kv.match_prefix(list(range(9)))[0] == 0
    st = kv.stats()["prefix_cache"]
    assert not st["enabled"] and st["hit_tokens"] == 0
    assert kv.num_free_pages == 8 and kv.free_count == 3


# ==========================================================================
# engine: token identity (the non-negotiable oracle)
# ==========================================================================
def test_prefix_hit_decode_byte_identical_to_cold():
    rng = np.random.RandomState(11)
    prefix = list(map(int, rng.randint(0, 64, size=20)))
    prompts = [prefix + list(map(int, rng.randint(0, 64, size=n)))
               for n in (5, 3, 9, 1)]
    cold = make_engine()
    oracle = [cold.core.greedy_reference(p, 6) for p in prompts]
    warm = make_engine(prefix_cache=True)
    outs = warm.generate(prompts, max_new_tokens=6)
    assert outs == oracle
    st = warm.kv.stats()["prefix_cache"]
    assert st["hit_tokens"] > 0
    assert warm.stats["prefill_hit_tokens"] > 0
    assert warm.stats["prefill_tokens"] \
        < sum(len(p) for p in prompts)        # work actually skipped
    assert warm.kv.pages_in_use == 0          # everything released


def test_shared_then_diverging_suffix_fork_parity():
    # a NON-page-aligned shared prefix where request A's prompt IS the
    # prefix: B and C share A's partial tail page and fork on their
    # first (diverging) write — outputs must still match the cold
    # oracle exactly.  All three are admitted in the same step, before
    # A decodes into its tail, so the partial entry is pure prompt.
    rng = np.random.RandomState(5)
    prefix = list(map(int, rng.randint(0, 64, size=13)))   # 1 full + 5 tail
    prompts = [list(prefix)] + \
        [prefix + [int(t), int(u)]
         for t, u in rng.randint(0, 64, size=(2, 2))]
    cold = make_engine()
    oracle = [cold.core.greedy_reference(p, 5) for p in prompts]
    eng = make_engine(prefix_cache=True)
    reqs = [Request(i, list(p), 5) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    assert [r.out_tokens for r in reqs] == oracle
    assert eng.kv.stats()["prefix_cache"]["forked_pages"] >= 1
    assert reqs[1]._prefix_hit == 13          # full + partial tail hit


@pytest.mark.parametrize("chunk,lens", [
    (8, (16, 17, 5)),         # page/bucket-aligned, off-by-one, short
    (4, (12, 31, 8)),         # budget not a divisor, odd length
])
def test_chunked_prefill_token_identical_to_monolithic(chunk, lens):
    prompts = _prompts(seed=3, n=3, lens=lens)
    mono = make_engine()
    oracle = [mono.core.greedy_reference(p, 5) for p in prompts]
    assert mono.generate(prompts, max_new_tokens=5) == oracle
    eng = make_engine(prefill_chunk=chunk)
    outs = eng.generate(prompts, max_new_tokens=5)
    assert outs == oracle
    assert eng.stats["prefill_chunks"] > len(prompts)  # chunking engaged


def test_chunked_prefill_eos_edge():
    # pick an eos the greedy model emits (the r12 probe trick), then
    # re-serve chunked: generation must stop at the same token
    probe = make_engine()
    prompts = _prompts(seed=3, n=2, lens=(17, 12))
    free_run = probe.generate(prompts, max_new_tokens=6)
    eos = free_run[0][2]
    cfg = DecoderConfig(**{**CFG.to_dict(), "eos_id": int(eos)})
    mono = make_engine(cfg=cfg)
    oracle = [mono.core.greedy_reference(p, 6) for p in prompts]
    eng = make_engine(cfg=cfg, prefill_chunk=8, prefix_cache=True)
    outs = eng.generate(prompts, max_new_tokens=6)
    assert outs == oracle
    assert outs[0][-1] == eos and len(outs[0]) <= 3


def test_long_prompt_over_token_budget_served_and_gap_bounded():
    rng = np.random.RandomState(9)
    longp = list(map(int, rng.randint(0, 64, size=80)))
    # over the 32-token budget: rejected without chunking...
    plain = make_engine(token_budget=32, num_pages=64)
    with pytest.raises(ValueError):
        plain.submit(Request(0, list(longp), 4))
    # ...served with it, one budget-sized slice per step
    eng = make_engine(prefill_chunk=16, token_budget=32, num_pages=64)
    outs = eng.generate([longp], max_new_tokens=4)
    assert outs == [eng.core.greedy_reference(longp, 4)]
    assert eng.stats["max_prefill_step_tokens"] <= 16
    assert eng.stats["prefill_chunks"] == 5


def test_decode_never_stalls_behind_chunked_prefill():
    """With decoders running, a long prompt's arrival must not produce
    a decode-free step: every chunking step still emits decode tokens,
    and the per-step prefill work stays within the chunk budget."""
    rng = np.random.RandomState(2)
    longp = list(map(int, rng.randint(0, 64, size=60)))

    def drive(chunk):
        eng = make_engine(prefill_chunk=chunk, token_budget=128,
                          num_pages=64)
        for i in range(2):
            eng.submit(Request(i, _prompts(seed=i, n=1, lens=(4,))[0], 30))
        eng.step()
        eng.step()
        eng.stats["max_prefill_step_tokens"] = 0
        eng.submit(Request("long", list(longp), 4))
        chunk_steps = decode_starved_steps = 0
        while eng.has_work():
            evs = eng.step()
            if eng._prefill_job is not None:
                chunk_steps += 1
                if not any(e.req_id in (0, 1) for e in evs):
                    decode_starved_steps += 1
        return eng, chunk_steps, decode_starved_steps

    eng, chunk_steps, starved = drive(16)
    assert chunk_steps >= 2                   # chunking really spanned steps
    assert starved == 0                       # decode emitted every step
    assert eng.stats["max_prefill_step_tokens"] <= 16
    # vs monolithic: the whole prompt lands in one step
    mono, _, _ = drive(0)
    assert mono.stats["max_prefill_step_tokens"] == len(longp)


# ==========================================================================
# determinism + preemption/resume
# ==========================================================================
def _event_stream(eng, prompts, max_new):
    reqs = [Request(i, list(p), max_new) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    events = []
    while eng.has_work():
        events.extend((e.req_id, e.token, e.finished) for e in eng.step())
    return events, eng.stats.copy(), eng.kv.stats()


def test_features_on_scheduler_determinism():
    rng = np.random.RandomState(13)
    prefix = list(map(int, rng.randint(0, 64, size=12)))
    prompts = [prefix + list(map(int, rng.randint(0, 64, size=n)))
               for n in (3, 9, 5, 7)] + _prompts(seed=1, n=2)

    def run():
        eng = make_engine(num_pages=8, page_size=4, prefix_cache=True,
                          prefill_chunk=8)
        return _event_stream(eng, prompts, 5)

    a, b = run(), run()
    assert a == b
    # the pool is tight enough that eviction (and possibly preemption)
    # really fired — determinism under cache churn, not just cold paths
    assert a[2]["prefix_cache"]["evicted_pages"] > 0 \
        or a[1]["preempted"] > 0


def test_flags_off_byte_identical_to_r18_schedule():
    prompts = _prompts(seed=11)

    def run(**kw):
        telemetry.registry().clear()
        eng = make_engine(num_pages=6, page_size=4, **kw)
        ev = _event_stream(eng, prompts, 5)
        snap = telemetry.snapshot()
        counters = {k: v["series"][0]["value"] for k, v in snap.items()
                    if k.startswith("serving_") and v["type"] == "counter"
                    and not v["labels"]}
        return ev, counters

    a = run()                                  # flag defaults (both off)
    b = run(prefix_cache=False, prefill_chunk=0)
    assert a == b
    assert a[0][1]["preempted"] >= 1           # the schedule really bites
    assert a[0][1]["prefill_hit_tokens"] == 0
    assert a[0][1]["prefill_chunks"] == 0


def test_resume_after_preemption_hits_own_pages():
    # tight pool forces preemption; with the cache on, the victim's
    # freed prompt pages stay indexed, so its re-prefill is a hit
    prompts = _prompts(seed=9)
    eng = make_engine(num_pages=6, page_size=4, prefix_cache=True)
    reqs = [Request(i, list(p), 5) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    events = []
    while eng.has_work():
        events.extend(eng.step())
    assert eng.stats["preempted"] >= 1
    assert eng.stats["prefill_hit_tokens"] > 0  # resumes hit the cache
    # and output still matches the cold oracle
    cold = make_engine()
    oracle = [cold.core.greedy_reference(p, 5) for p in prompts]
    assert [r.out_tokens for r in reqs] == oracle


def test_lost_work_cost_is_shared_page_aware():
    _flags.set_flags({"trace_requests": 1})
    rng = np.random.RandomState(4)
    prefix = list(map(int, rng.randint(0, 64, size=16)))
    p1 = prefix + [1, 2, 3]
    p2 = prefix + [4, 5]
    eng = make_engine(prefix_cache=True)
    reqs = [Request(i, p, 6) for i, p in enumerate([p1, p2])]
    for r in reqs:
        eng.submit(r)
    eng.step(1.0)
    hit = reqs[1]._prefix_hit
    assert hit == 16
    for st in eng.running:
        want = (len(st.req.prompt) - st.req._prefix_hit
                + len(st.req.out_tokens))
        assert lost_work_cost(st.req) == want   # traced == untraced
    # the high-hit request is the cheaper preemption victim
    costs = [lost_work_cost(st.req) for st in eng.running]
    assert costs[1] < costs[0]
    eng.run_to_completion(2.0)


def test_slo_tracker_reports_prefix_hit_ratio():
    rng = np.random.RandomState(8)
    prefix = list(map(int, rng.randint(0, 64, size=16)))
    prompts = [prefix + list(map(int, rng.randint(0, 64, size=4)))
               for _ in range(3)]
    telemetry.slo_tracker().configure(ttft_s=None, token_s=None)
    eng = make_engine(prefix_cache=True)
    eng.generate(prompts, max_new_tokens=3)
    rep = telemetry.slo_tracker().report()
    assert rep["prefix_hit_ratio"] > 0.4
    assert "prefix_hit_ratio" in eng.slo_hint()


# ==========================================================================
# chaos pool_spike under CoW (two engines, one schedule)
# ==========================================================================
def test_pool_spike_never_seizes_live_shared_prefix():
    _flags.set_flags({"chaos": "pool_spike=10@2:3"})
    chaos.reset()
    rng = np.random.RandomState(6)
    prefix = list(map(int, rng.randint(0, 64, size=16)))
    a = make_engine(prefix_cache=True)
    b = make_engine(prefix_cache=True)
    # engine A: two live requests sharing the prefix
    r1 = Request("r1", prefix + [1, 2, 3], 8)
    r2 = Request("r2", prefix + [4, 5], 8)
    a.submit(r1)
    a.step(1.0)                     # r1 admitted; spike not armed yet
    a.submit(r2)
    shared_before = [p for p in a.kv._refs if a.kv.refcount(p) >= 1]
    a.step(2.0)                     # r2 admitted AND the spike fires
    kinds = {s["labels"]["kind"]: s["value"]
             for s in telemetry.snapshot()["chaos_injections_total"]
             ["series"]}
    assert kinds.get("pool_spike", 0) >= 1
    # every page a live sequence maps survived the seizure
    for p in shared_before:
        assert a.kv.refcount(p) >= 1
    assert any(a.kv.refcount(p) > 1 for p in a.kv._seqs["r1"].pages)
    # engine B under the SAME schedule: its spike seizes from ITS pool
    for t in range(1, 7):
        b.step(float(t))
    assert b.kv.pages_in_use == 0   # B's release was refcount-correct
    assert b.kv.num_free_pages == 32
    # drive A to completion: output identical to a chaos-free cold run
    while a.has_work():
        a.step(3.0)
    _flags.set_flags({"chaos": ""})
    chaos.reset()
    cold = make_engine()
    assert r1.out_tokens == cold.core.greedy_reference(r1.prompt, 8)
    assert r2.out_tokens == cold.core.greedy_reference(r2.prompt, 8)
    assert a.kv.pages_in_use == 0   # A fully released its own seizure


# ==========================================================================
# memory planner reconciliation: shared pages counted once
# ==========================================================================
def test_kv_pool_block_counts_shared_pages_once():
    from paddle_tpu.framework import memory_plan as mp
    from paddle_tpu.inference.gpt2_decoder import init_decoder_weights
    from paddle_tpu.inference.serving import _EngineCore

    cfg = DecoderConfig(vocab_size=32, hidden=16, num_heads=2,
                        num_layers=2, max_seq_len=64)
    core = _EngineCore(cfg, init_decoder_weights(cfg), num_pages=16,
                       page_size=4, prefix_cache=True)
    toks = list(range(8))
    core.kv.append_tokens("A", 8, tokens=toks)
    hit, pages = core.kv.match_prefix(toks + [9])
    core.kv.acquire_prefix("B", toks[:hit], pages)
    assert core.kv.refcount(0) == 2           # genuinely shared
    assert core.kv.pages_in_use == 2          # ...but counted once
    plan = mp.plan_memory(core.decode_prog,
                          feed_names=core.decode_feeds,
                          fetch_names=core.decode_fetch,
                          scope=core.scope)
    # the modeled kv_pool block is the FIXED pool: sharing inside it
    # never double-counts — modeled bytes == the engine's resident view
    assert plan.resident_by_class["kv_pool"] == \
        core.kv_pool_resident_bytes()
    ms = core.memory_stats()
    assert ms["kv_pool_resident_bytes"] == core.kv_pool_resident_bytes()
    assert ms["kv_pool_peak_pages"] == 2
    assert ms["prefix_cache"]["shared_pages"] == 2
