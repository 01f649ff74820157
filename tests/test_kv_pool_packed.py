"""A KV pool stored with rows that fill the 128 lanes.

Where head_dim is under the lanes a pool is stored ``(kv_heads,
num_pages, page_size * head_dim / 128, 128)``, ``t = 128 / head_dim``
tokens of a page side by side in a row: a row-major bitcast of the
logical ``(kv_heads, num_pages, page_size, head_dim)``, which stays what
the allocator, the slots and the references mean.  Three things are
held here, all on the CPU with the real kernel bodies interpreted:

* the rule (``KVCacheConfig.pool_shape``): shapes and the storage type
  alone decide, and where the rule does not hold the stored shape is the
  logical one;
* ``paged_decode`` on a stored pool against ``paged_attention_reference``
  on the LOGICAL pool: ragged contexts with odd and even tails, a
  context ending mid-row, padded table entries, GQA, ``t`` = 1, 2, 4;
* an engine at head_dim 64 and pages of 16 (GPT-2-small's geometry, the
  packed condition engaged) through every program form that touches a
  pool — prefill, decode, chunked prefill, spec-decode verify, a CoW
  fork — token-identical to the reference program, which has no pool.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.inference.kv_cache import KVCacheConfig, PagedKVCache
from paddle_tpu.inference.serving import (DecoderConfig, Proposer, Request,
                                          ServingEngine)
from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.utils import chaos
from paddle_tpu.utils import flags as _flags
from paddle_tpu.utils import telemetry, tracing


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


# ==========================================================================
# the rule
# ==========================================================================
@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("head_dim,page_size,stored,t", [
    (64, 16, (8, 128), 2),        # GPT-2-small: a page is one (8, 128) tile
    (32, 32, (8, 128), 4),
    (64, 32, (16, 128), 2),
    (16, 64, (8, 128), 8),
    (128, 16, (16, 128), 1),      # lane-full already: the logical shape
    (256, 16, (16, 256), 1),
    (64, 8, (8, 64), 1),          # half a tile a page: stays logical
    (96, 16, (16, 96), 1),        # 96 does not divide the lanes
    (8, 8, (8, 8), 1),            # the tiny test models
])
def test_stored_shape_follows_from_shapes_alone(head_dim, page_size, stored,
                                                t, dtype):
    cfg = KVCacheConfig(num_pages=24, page_size=page_size, num_kv_heads=3,
                        head_dim=head_dim, dtype=dtype)
    assert cfg.tokens_per_row == t
    assert cfg.pool_shape() == (3, 24) + stored
    pool = cfg.make_pool()
    assert pool.shape == cfg.pool_shape() and pool.dtype == np.dtype(dtype)
    # the same bytes as the logical pool, and the slots still count tokens
    assert pool.size == 3 * 24 * page_size * head_dim
    assert cfg.pad_slot == 24 * page_size
    st = PagedKVCache(cfg).stats()
    assert st["pool_stored_shape"] == list(cfg.pool_shape())
    assert st["pool_tokens_per_row"] == t


def test_tokens_per_row_gauge_says_whether_the_packing_engaged():
    kv = PagedKVCache(KVCacheConfig(num_pages=4, page_size=16,
                                    num_kv_heads=1, head_dim=64))
    kv.append_tokens("a", 3)
    snap = telemetry.snapshot()
    assert snap["kv_pool_tokens_per_row"]["series"][0]["value"] == 2


# ==========================================================================
# paged_decode on a stored pool
# ==========================================================================
def _stored(logical, t):
    h, p, ps, d = logical.shape
    return jnp.asarray(logical.reshape(h, p, ps // t, d * t))


# (head_dim, page_size, t)
GEOMETRY = {"t2-d64-page16": (64, 16, 2), "t4-d32-page32": (32, 32, 4),
            "t1-d128-page8": (128, 8, 1), "t1-d16-page8": (16, 8, 1)}
# context lengths over a table of 4 pages, in units the case scales by
# its page: (pages, tokens past them)
CONTEXTS = {
    "one-token": [(0, 1)] * 3,
    "odd-and-even-tails": [(0, 5), (1, 6), (2, 3)],
    "ends-mid-row": [(0, 3), (1, 1), (3, 7)],        # t does not divide it
    "full-pages-and-padded-entries": [(1, 0), (4, 0), (2, 0)],
}


@pytest.mark.parametrize("group", [1, 2])
@pytest.mark.parametrize("contexts", list(CONTEXTS))
@pytest.mark.parametrize("geometry", list(GEOMETRY))
def test_paged_decode_on_the_stored_pool_matches_the_reference(
        geometry, contexts, group, monkeypatch):
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    d, ps, t = GEOMETRY[geometry]
    hkv, pages, width = 2, 9, 4
    rng = np.random.RandomState(len(geometry) + len(contexts))
    k = rng.randn(hkv, pages, ps, d).astype(np.float32)
    v = rng.randn(hkv, pages, ps, d).astype(np.float32)
    cl = np.array([p * ps + r for p, r in CONTEXTS[contexts]], np.int32)
    q = jnp.asarray(rng.randn(len(cl), hkv * group, d).astype(np.float32))
    bt = rng.randint(1, pages, size=(len(cl), width)).astype(np.int32)
    for b, n in enumerate(cl):            # entries past the context: page 0
        bt[b, -(-int(n) // ps):] = 0
    bt, cl = jnp.asarray(bt), jnp.asarray(cl)
    want = pk.paged_attention_reference(q, jnp.asarray(k), jnp.asarray(v),
                                        bt, cl)
    ks, vs = _stored(k, t), _stored(v, t)
    assert ks.shape == KVCacheConfig(pages, ps, hkv, d).pool_shape()
    got = pk.paged_attention(q, ks, vs, bt, cl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    # the gather fallback reads the stored pool too: bit for bit what it
    # makes of the logical one
    np.testing.assert_array_equal(
        np.asarray(pk.paged_attention_reference(q, ks, vs, bt, cl)),
        np.asarray(want))


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("geometry", ["t2-d64-page16", "t4-d32-page32"])
def test_paged_decode_on_a_stored_quantized_pool(geometry, kv_dtype,
                                                 monkeypatch):
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    d, ps, t = GEOMETRY[geometry]
    hkv, pages = 2, 6
    rng = np.random.RandomState(4)
    if kv_dtype == "int8":
        k = rng.randint(-127, 128, (hkv, pages, ps, d)).astype(np.int8)
        v = rng.randint(-127, 128, (hkv, pages, ps, d)).astype(np.int8)
        scales = dict(
            k_scale=jnp.asarray(rng.uniform(.5, 2, (hkv, pages)), jnp.float32),
            v_scale=jnp.asarray(rng.uniform(.5, 2, (hkv, pages)), jnp.float32))
    else:
        k = np.asarray(jnp.asarray(rng.randn(hkv, pages, ps, d), kv_dtype))
        v = np.asarray(jnp.asarray(rng.randn(hkv, pages, ps, d), kv_dtype))
        scales = {}
    q = jnp.asarray(rng.randn(3, 2 * hkv, d).astype(np.float32))
    bt = jnp.asarray(rng.randint(0, pages, (3, 3)).astype(np.int32))
    cl = jnp.asarray(np.array([1, ps + 5, 3 * ps], np.int32))
    want = pk.paged_attention_reference(q, jnp.asarray(k), jnp.asarray(v),
                                        bt, cl, **scales)
    got = pk.paged_attention(q, _stored(k, t), _stored(v, t), bt, cl,
                             **scales)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


# ==========================================================================
# the engine, every program form that touches a pool
# ==========================================================================
# GPT-2-small's pool geometry at a width the CPU can serve
CFG = DecoderConfig(vocab_size=64, hidden=128, num_heads=2, num_layers=2,
                    max_seq_len=128)


def make_engine(**kw):
    kw.setdefault("num_pages", 32)
    kw.setdefault("page_size", 16)
    kw.setdefault("max_batch", 4)
    kw.setdefault("token_budget", 64)
    kw.setdefault("prefill_bucket_min", 8)
    return ServingEngine(CFG, **kw)


def _prompts(lens, seed=7):
    rng = np.random.RandomState(seed)
    return [list(map(int, rng.randint(0, 64, size=n))) for n in lens]


class _OracleProposer(Proposer):
    """Drafts the request's own greedy continuation, so every verify call
    carries a full draft and all of it is accepted."""

    def __init__(self, continuations):
        self.continuations = continuations

    def propose(self, req, k):
        cont = self.continuations[req.req_id]
        return cont[len(req.out_tokens):len(req.out_tokens) + k]


def _shared_prefix_prompts():
    # request 0's prompt IS the prefix, 16 + 5 tokens: the others share its
    # partial tail page and fork it on their first write
    rng = np.random.RandomState(5)
    prefix = list(map(int, rng.randint(0, 64, size=21)))
    return [list(prefix)] + [prefix + [int(a), int(b)]
                             for a, b in rng.randint(0, 64, size=(2, 2))]


FORMS = {
    # prompts over one, two and three pages, tails odd and even
    "prefill+decode": (dict(), lambda: _prompts((3, 17, 38, 32))),
    "chunk": (dict(prefill_chunk=8), lambda: _prompts((16, 37, 5), seed=3)),
    "verify": (dict(spec_k=3), lambda: _prompts((5, 19, 9), seed=1)),
    "cow-fork": (dict(prefix_cache=True), _shared_prefix_prompts),
}


@pytest.mark.parametrize("kernels", ["interpreted", "jnp"])
@pytest.mark.parametrize("form", list(FORMS))
def test_engine_on_stored_pools_is_token_identical(form, kernels,
                                                   monkeypatch):
    if kernels == "interpreted":
        monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("PT_PALLAS_INTERPRET", raising=False)
    kw, prompts = FORMS[form]
    prompts = prompts()
    new = 6
    cold = make_engine()
    assert cold.core.kv_config.pool_shape() == (2, 32, 8, 128)
    assert cold.core.scope.get("kv_k_0").shape == (2, 32, 8, 128)
    oracle = [cold.core.greedy_reference(p, new) for p in prompts]
    if form == "verify":
        kw = dict(kw, proposer=_OracleProposer(dict(enumerate(oracle))))
    eng = make_engine(**kw)
    reqs = [Request(i, list(p), new) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    assert [r.out_tokens for r in reqs] == oracle
    if form == "chunk":
        assert eng.stats["prefill_chunks"] > len(prompts)
    elif form == "verify":
        assert eng.stats["spec_accepted"] > 0
    elif form == "cow-fork":
        assert eng.kv.stats()["prefix_cache"]["forked_pages"] >= 1
    # a program leaves a pool in the shape it found it
    assert eng.core.scope.get("kv_v_1").shape == (2, 32, 8, 128)
    assert eng.kv.stats()["pool_tokens_per_row"] == 2


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_engine_on_stored_quantized_pools(kv_dtype, monkeypatch):
    """bfloat16 and int8 pools at the packed geometry, through chunked
    prefill (the gather + ``kv_dequant`` read) and decode: the kernels
    serve what the jnp path serves, and int8's scale pools keep their
    per-page shape."""
    prompts = _prompts((16, 37, 5), seed=3)
    outs = {}
    for kernels in ("jnp", "interpreted"):
        if kernels == "interpreted":
            monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
        else:
            monkeypatch.delenv("PT_PALLAS_INTERPRET", raising=False)
        eng = make_engine(kv_dtype=kv_dtype, prefill_chunk=8)
        pool = eng.core.scope.get("kv_k_0")
        assert pool.shape == (2, 32, 8, 128) and pool.dtype == kv_dtype
        if kv_dtype == "int8":
            assert eng.core.scope.get("kv_k_scale_0").shape == (2, 32)
        outs[kernels] = eng.generate(prompts, max_new_tokens=6)
        assert eng.stats["prefill_chunks"] > len(prompts)
    assert outs["interpreted"] == outs["jnp"]
    if kv_dtype == "bfloat16":
        # no near-tie in this seeded model: bfloat16 serves float32's tokens
        assert outs["jnp"] == make_engine().generate(prompts,
                                                     max_new_tokens=6)
