"""Quantized KV page pool (r22): bf16/int8 storage + per-page scales,
f32 attention accumulation.

Oracles:
* ``FLAGS_kv_cache_dtype`` default OFF is **byte-identical**: the
  default-flags engine and an explicit ``float32`` engine produce the
  same StepEvent streams under the same logical clock, and the default
  decode program contains no scale vars and no ``kv_dequant`` ops;
* int8 roundtrip error is bounded by half a quantization step
  (``scale / 254``) per element; bf16 by one mantissa ulp (2^-8
  relative);
* ``_quant_scatter`` page-scale rules hold: reset-on-open zeroes a
  recycled page and restarts its scale, mid-page appends never lower a
  scale (monotone), a growing scale requants the touched page's old
  slots within one quantization step, and UNTOUCHED pages are
  bit-stable; the allocator's pad sentinel drops the write entirely;
* CoW forks copy quantized pages AND their scales verbatim (a fork
  never requantizes), so prefix-cache hits are token-identical to cold
  runs within a dtype;
* within-dtype identity: chunked prefill == monolithic prefill and
  greedy spec-decode == baseline for bf16 and int8 (the truncate /
  re-append path keeps surviving slots' dequantized values);
* the Pallas decode kernel (interpret mode) matches the dense
  reference for f32, bf16 and int8+scales pools;
* a fixed byte budget buys exactly 2x pages at bf16 and 4x at int8,
  the static planner's ``kv_pool`` class reconciles with the runtime
  census for all three dtypes, and ``stats()`` / telemetry gauges
  surface dtype, scale bytes and effective capacity (quantized only);
* chaos ``pool_spike`` allocator rules are dtype-independent.
"""
import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from paddle_tpu.framework import memory_plan as mp
from paddle_tpu.inference.kv_cache import KVCacheConfig, PagedKVCache
from paddle_tpu.inference.gpt2_decoder import init_decoder_weights
from paddle_tpu.inference.serving import (DecoderConfig, Request,
                                          ServingEngine, _EngineCore,
                                          _fork_copy_fn)
from paddle_tpu.ops import paged_ops
from paddle_tpu.ops import pallas_kernels as pk
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


def make_engine(**kw):
    kw.setdefault("num_pages", 32)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_batch", 4)
    kw.setdefault("token_budget", 256)
    kw.setdefault("prefill_bucket_min", 8)
    return ServingEngine(kw.pop("cfg", CFG), **kw)


def prompts_seed7():
    rng = np.random.RandomState(7)
    return [list(map(int, rng.randint(0, 64, size=ln)))
            for ln in (3, 11, 6, 14)]


def drive(eng, prompts, max_new=6):
    """Submit everything, step on a logical clock, return the full
    StepEvent stream (frozen dataclasses — directly comparable)."""
    for i, p in enumerate(prompts):
        eng.submit(Request(i, list(p), max_new))
    events, t = [], 0.0
    while eng.waiting or eng.running or eng._prefill_job is not None:
        t += 1.0
        events.extend(eng.step(t))
    return events


# ==========================================================================
# quantization roundtrip bounds
# ==========================================================================
def _scatter(pool, scales, new, slots, page_size):
    kq, ks = paged_ops._quant_scatter(
        jnp.asarray(pool), jnp.asarray(scales),
        jnp.asarray(new, jnp.float32), jnp.asarray(slots, jnp.int32),
        page_size)
    return np.asarray(kq), np.asarray(ks)


def _deq(pool, scales):
    return (pool.astype(np.float32)
            * scales[:, :, None, None] / paged_ops.INT8_QMAX)


def test_int8_roundtrip_half_step_bound():
    rng = np.random.RandomState(0)
    n_kv, n_pages, ps, d = 2, 4, 8, 16
    pool = np.zeros((n_kv, n_pages, ps, d), np.int8)
    scales = np.zeros((n_kv, n_pages), np.float32)
    # fill two full pages, starting at offset 0 (fresh pages)
    new = rng.randn(n_kv, 2 * ps, d).astype(np.float32) * 3.0
    slots = np.arange(2 * ps, dtype=np.int32)          # pages 0 and 1
    q, s = _scatter(pool, scales, new, slots, ps)
    # per-(head, page) scale is the absmax of what landed there
    want = np.abs(new).reshape(n_kv, 2, ps * d).max(axis=2)
    np.testing.assert_allclose(s[:, :2], want, rtol=1e-6)
    assert (s[:, 2:] == 0).all()
    got = _deq(q, s)[:, :2].reshape(n_kv, 2 * ps, d)
    step = s[:, :2, None].repeat(ps, 2).reshape(n_kv, 2 * ps) \
        / paged_ops.INT8_QMAX
    assert (np.abs(got - new) <= step[..., None] / 2 + 1e-6).all()


def test_bf16_pool_roundtrip_one_ulp():
    rng = np.random.RandomState(1)
    n_kv, n_pages, ps, d = 2, 4, 8, 16
    pool = jnp.zeros((n_kv, n_pages, ps, d), jnp.bfloat16)
    new = rng.randn(ps, n_kv, d).astype(np.float32) * 5.0  # (tokens, kv, d)
    out = op_registry.eager_call(
        "kv_cache_append",
        {"K": [jnp.asarray(new)], "V": [jnp.asarray(new)],
         "SlotMapping": [jnp.arange(ps, dtype=jnp.int32)],
         "KCache": [pool], "VCache": [pool]},
        {}, {"KCacheOut": 1, "VCacheOut": 1})
    got = np.asarray(out["KCacheOut"][0][:, 0].astype(jnp.float32))
    want = new.transpose(1, 0, 2)
    assert (np.abs(got - want) <= np.abs(want) * 2.0 ** -8 + 1e-7).all()
    # and the stored bits are EXACTLY the bf16 cast (no extra rounding)
    np.testing.assert_array_equal(
        np.asarray(out["KCacheOut"][0][:, 0]),
        np.asarray(jnp.asarray(want).astype(jnp.bfloat16)))


# ==========================================================================
# _quant_scatter page-scale rules
# ==========================================================================
def test_quant_scatter_reset_monotone_requant_rules():
    rng = np.random.RandomState(2)
    n_kv, n_pages, ps, d = 1, 4, 4, 8
    pool = np.zeros((n_kv, n_pages, ps, d), np.int8)
    scales = np.zeros((n_kv, n_pages), np.float32)
    # seed page 1 fully with magnitude-2 content
    base = rng.randn(n_kv, ps, d).astype(np.float32)
    base *= 2.0 / np.abs(base).max()
    pool, scales = _scatter(pool, scales, base,
                            np.arange(ps, dtype=np.int32) + ps, ps)
    assert scales[0, 1] == pytest.approx(2.0)
    kept_bits = pool[:, 1].copy()
    untouched = pool[:, [0, 2, 3]].copy()

    # (a) mid-page append with SMALLER values: scale monotone (held),
    # previously written slots bit-stable
    small = rng.randn(n_kv, 1, d).astype(np.float32) * 0.1
    p2, s2 = _scatter(pool, scales, small,
                      np.array([ps + 2], np.int32), ps)
    assert s2[0, 1] == pytest.approx(2.0)
    np.testing.assert_array_equal(p2[:, 1, [0, 1, 3]],
                                  kept_bits[:, [0, 1, 3]])
    np.testing.assert_array_equal(p2[:, [0, 2, 3]], untouched)

    # (b) mid-page append with a LARGER value: scale grows, the page's
    # old slots requant — dequantized values move at most one step of
    # the NEW scale
    big = np.full((n_kv, 1, d), 5.0, np.float32)
    p3, s3 = _scatter(pool, scales, big, np.array([ps + 3], np.int32), ps)
    assert s3[0, 1] == pytest.approx(5.0)
    old = _deq(pool, scales)[:, 1, :3]
    new = _deq(p3, s3)[:, 1, :3]
    assert np.abs(new - old).max() <= 5.0 / paged_ops.INT8_QMAX + 1e-6
    np.testing.assert_array_equal(p3[:, [0, 2, 3]], untouched)

    # (c) reset-on-open: a write at page offset 0 recycles the page —
    # stale slots zero, scale restarts at THIS write's absmax
    tiny = np.full((n_kv, 1, d), 0.25, np.float32)
    p4, s4 = _scatter(pool, scales, tiny, np.array([ps], np.int32), ps)
    assert s4[0, 1] == pytest.approx(0.25)
    assert (p4[:, 1, 1:] == 0).all()
    np.testing.assert_allclose(_deq(p4, s4)[:, 1, 0], 0.25, atol=2e-3)

    # (d) the allocator's pad sentinel (num_pages * page_size) is a
    # complete no-op: bits and scales unchanged
    p5, s5 = _scatter(pool, scales, big,
                      np.array([n_pages * ps], np.int32), ps)
    np.testing.assert_array_equal(p5, pool)
    np.testing.assert_array_equal(s5, scales)


# ==========================================================================
# CoW forks copy pages + scales verbatim
# ==========================================================================
def test_fork_copy_is_bitwise_for_int8_pools_and_scales():
    rng = np.random.RandomState(3)
    pool = jnp.asarray(rng.randint(-127, 128, size=(2, 6, 4, 8)
                                   ).astype(np.int8))
    scales = jnp.asarray(np.abs(rng.randn(2, 6)).astype(np.float32))
    want_page = np.asarray(pool[:, 1])
    want_scale = np.asarray(scales[:, 1])
    fn = _fork_copy_fn()
    pool2 = fn(pool, np.int32(1), np.int32(4))
    scales2 = fn(scales, np.int32(1), np.int32(4))
    np.testing.assert_array_equal(np.asarray(pool2[:, 4]), want_page)
    np.testing.assert_array_equal(np.asarray(scales2[:, 4]), want_scale)


def test_prefix_hit_identical_to_cold_int8():
    shared = list(range(1, 17))
    ps = [shared + [20, 21], shared + [30, 31, 32]]
    cold = make_engine(kv_dtype="int8").generate(ps, max_new_tokens=5)
    eng = make_engine(kv_dtype="int8", prefix_cache=True)
    warm = eng.generate(ps, max_new_tokens=5)
    assert warm == cold
    st = eng.kv.stats()["prefix_cache"]
    assert st["hit_tokens"] > 0 or st["shared_acquires"] > 0


# ==========================================================================
# within-dtype identity: chunked == monolithic, spec == baseline
# ==========================================================================
@pytest.mark.parametrize("dtype", ["bfloat16", "int8"])
def test_within_dtype_identity_oracles(dtype):
    ps = prompts_seed7()
    mono = make_engine(kv_dtype=dtype).generate(ps, max_new_tokens=6)
    chunk = make_engine(kv_dtype=dtype, prefill_chunk=4).generate(
        ps, max_new_tokens=6)
    assert chunk == mono
    spec = make_engine(kv_dtype=dtype, spec_k=3)
    assert spec.generate(ps, max_new_tokens=6) == mono
    # the reject rollback ran against the quantized pool: the truncate /
    # re-append path must not have perturbed surviving tokens
    assert spec.kv.pages_in_use == 0


# ==========================================================================
# default OFF is byte-identical
# ==========================================================================
def test_default_flags_byte_identical_to_explicit_float32():
    ps = prompts_seed7()
    ev_default = drive(make_engine(), ps)
    ev_f32 = drive(make_engine(kv_dtype="float32"), ps)
    assert ev_default == ev_f32


def test_default_decode_program_has_no_quant_machinery():
    eng = make_engine()
    assert eng.kv_dtype == "float32"
    blk = eng.core.decode_prog.global_block()
    assert not any(n.startswith(("kv_k_scale_", "kv_v_scale_"))
                   for n in blk.vars)
    assert not any(op.type == "kv_dequant" for op in blk.ops)
    i8 = make_engine(kv_dtype="int8")
    blk8 = i8.core.decode_prog.global_block()
    assert any(n.startswith("kv_k_scale_") for n in blk8.vars)


def test_flag_routes_and_bad_dtype_raises():
    _flags.set_flags({"kv_cache_dtype": "int8"})
    eng = make_engine()
    assert eng.kv_dtype == "int8"
    assert eng.kv.stats()["dtype"] == "int8"
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        make_engine(kv_dtype="fp4")


# ==========================================================================
# Pallas decode kernel parity (interpret mode)
# ==========================================================================
def test_pallas_decode_parity_quantized(monkeypatch):
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    rng = np.random.RandomState(2)
    b, hq, hkv, d, bs, p, w = 3, 4, 2, 16, 8, 6, 2
    q = jnp.asarray(rng.randn(b, hq, d).astype(np.float32))
    bt = jnp.asarray(rng.choice(p, size=(b, w)).astype(np.int32))
    cl = jnp.asarray(np.array([3, 16, 9], np.int32))
    # int8 + scales
    kp = jnp.asarray((rng.randn(hkv, p, bs, d) * 20).astype(np.int8))
    vp = jnp.asarray((rng.randn(hkv, p, bs, d) * 20).astype(np.int8))
    ks = jnp.asarray(np.abs(rng.randn(hkv, p)).astype(np.float32) + 0.1)
    vs = jnp.asarray(np.abs(rng.randn(hkv, p)).astype(np.float32) + 0.1)
    ref = pk.paged_attention_reference(q, kp, vp, bt, cl,
                                       k_scale=ks, v_scale=vs)
    ker = pk._paged_decode_call(q, kp, vp, bt, cl, d ** -0.5,
                                k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               atol=5e-5)
    # bf16 (no scales)
    bk = jnp.asarray(rng.randn(hkv, p, bs, d).astype(np.float32)
                     ).astype(jnp.bfloat16)
    bv = jnp.asarray(rng.randn(hkv, p, bs, d).astype(np.float32)
                     ).astype(jnp.bfloat16)
    ref_b = pk.paged_attention_reference(q, bk, bv, bt, cl)
    ker_b = pk._paged_decode_call(q, bk, bv, bt, cl, d ** -0.5)
    np.testing.assert_allclose(np.asarray(ker_b), np.asarray(ref_b),
                               atol=5e-5)
    # f32 control under the same interpreter
    ref_f = pk.paged_attention_reference(
        q, kp.astype(jnp.float32), vp.astype(jnp.float32), bt, cl)
    ker_f = pk._paged_decode_call(
        q, kp.astype(jnp.float32), vp.astype(jnp.float32), bt, cl,
        d ** -0.5)
    np.testing.assert_allclose(np.asarray(ker_f), np.asarray(ref_f),
                               atol=5e-4)


# ==========================================================================
# budget-derived capacity + planner/census reconciliation
# ==========================================================================
def test_budget_buys_exact_2x_and_4x_pages():
    n = {}
    for dt in ("float32", "bfloat16", "int8"):
        eng = make_engine(kv_dtype=dt, kv_budget_mb=1.0)
        n[dt] = eng.core.kv_config.num_pages
    assert n["bfloat16"] == 2 * n["float32"]
    assert n["int8"] == 4 * n["float32"]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
def test_planner_kv_pool_matches_runtime_census(dtype):
    cfg = DecoderConfig(vocab_size=32, hidden=16, num_heads=2,
                        num_layers=2, max_seq_len=32)
    core = _EngineCore(cfg, init_decoder_weights(cfg), num_pages=16,
                       page_size=4, kv_dtype=dtype)
    plan = mp.plan_memory(core.decode_prog, feed_names=core.decode_feeds,
                          fetch_names=core.decode_fetch, scope=core.scope)
    assert plan.resident_by_class["kv_pool"] == \
        core.kv_pool_resident_bytes()
    ms = core.memory_stats()
    assert ms["kv_pool_dtype"] == dtype
    itemsize = np.dtype(dtype).itemsize
    # 2 sides x 2 layers x (2 heads x 16 pages x 4 slots x head_dim 8)
    base = 4 * 2 * 16 * 4 * 8 * itemsize
    scale = (4 * 2 * 16 * 4) if dtype == "int8" else 0
    assert ms["kv_pool_scale_bytes"] == scale
    assert core.kv_pool_resident_bytes() == base + scale
    assert ms["kv_pool_capacity_tokens"] == 16 * 4


# ==========================================================================
# stats + telemetry gauges
# ==========================================================================
def test_stats_and_gauges_quantized_only():
    eng = make_engine(kv_dtype="int8")
    eng.generate(prompts_seed7()[:2], max_new_tokens=3)
    st = eng.kv.stats()
    assert st["dtype"] == "int8"
    assert st["scale_bytes"] == 4 * 32 * 4          # heads * pages * f32
    assert st["effective_capacity_tokens"] == 32 * 8
    snap = telemetry.snapshot()
    assert snap["kv_quant_scale_bytes"]["series"][0]["value"] == st[
        "scale_bytes"]
    assert snap["kv_quant_capacity_tokens"]["series"][0]["value"] == \
        st["effective_capacity_tokens"]
    telemetry.registry().clear()
    f32 = make_engine()
    f32.generate(prompts_seed7()[:1], max_new_tokens=2)
    snap = telemetry.snapshot()
    assert "kv_quant_scale_bytes" not in snap
    assert "kv_quant_capacity_tokens" not in snap


# ==========================================================================
# allocator semantics are dtype-independent
# ==========================================================================
def test_truncate_tokens_on_int8_config():
    kv = PagedKVCache(KVCacheConfig(num_pages=8, page_size=4,
                                    num_kv_heads=2, head_dim=8,
                                    dtype="int8"))
    kv.append_tokens("s", 10)                       # 3 pages
    assert kv.pages_in_use == 3
    kv.truncate_tokens("s", 3)                      # back to 7 -> 2 pages
    assert kv.pages_in_use == 2
    kv.free_sequence("s")
    assert kv.pages_in_use == 0


def test_chaos_pool_spike_with_int8_engine():
    _flags.set_flags({"chaos": "pool_spike=4@2:3"})
    chaos.reset()
    eng = make_engine(kv_dtype="int8")
    assert eng.kv.num_free_pages == 32
    eng.step(1.0)
    assert eng.kv.num_free_pages == 32
    eng.step(2.0)
    assert eng.kv.num_free_pages == 28
    eng.step(3.0)
    eng.step(4.0)
    assert eng.kv.num_free_pages == 28
    eng.step(5.0)
    assert eng.kv.num_free_pages == 32
