"""Paged-KV serving ops: the decode path of the serving runtime.

Three ops, shared by the continuous-batching engine
(inference/serving.py) over the pools the paged allocator
(inference/kv_cache.py) manages:

* ``kv_cache_append`` — write this step's new K/V vectors into the
  preallocated device pools at allocator-assigned flat slots.  In-place
  on the pool vars (output name == input name, the registry's in-place
  convention).  What makes that true on the chip is two aliases: the
  executor donates a var that a program both reads and writes, and the
  append kernel (ops/pallas_kernels.py ``kv_append``) aliases its pool
  operand onto its output and moves only the blocks it writes, in the
  layout the chip holds the pool in — the append reads and writes no
  whole pool.  An XLA scatter here cost two pool-sized layout copies per
  pool per call.
* ``paged_attention`` — each decode query gathers K/V through its block
  table at its true length (ops/pallas_kernels.py: Pallas kernel on
  TPU, gather fallback on CPU with identical semantics).
* ``kv_dequant`` — cast gathered pages back to f32 (int8: also apply
  the gathered per-(kv_head, page) scales), so the chunk / spec-verify
  dense-attention forms accumulate in full precision regardless of the
  storage dtype.

A pool reaches these ops AS STORED (``KVCacheConfig.pool_shape``):
logically ``(kv_heads, num_pages, page_size, head_dim)``; where head_dim
is under the 128 lanes and a page is whole tiles, ``(kv_heads,
num_pages, page_size * head_dim / 128, 128)``, a row-major bitcast with
``128 / head_dim`` tokens side by side in a row.  The chip's compiler
holds that shape row-major by its own choice, and both kernels work on
it there: no program copies, reshapes or transposes a whole pool
(``chip_smoke.py`` reads the compiled prefill and decode programs and
refuses one that does).  head_dim comes from K / Q, never from the
pool; page numbers address axis 1 in either form; whatever needs
``(tokens, head_dim)`` rows reshapes GATHERED pages.

Quantized storage (``FLAGS_kv_cache_dtype``): bf16 pools need no extra
state — the existing ``astype(pool.dtype)`` on write and a cast on read
cover it.  int8 pools carry a per-(kv_head, page) absmax scale pool
(optional KScale/VScale slots).  The write path keeps scales
semantically exact under the allocator's page lifecycle:

* **reset-on-open** — the allocator only ever starts writing a page at
  slot offset 0 (CoW forks keep > 0 slots, truncate keeps partial
  pages), so a write at ``slot % page_size == 0`` marks the page
  recycled: its old scale is treated as 0 and its stale content is
  requantized by ratio 0 (zeroed).
* **monotone scale** — a page's scale only ever grows while the page is
  live (``new_scale = max(old_scale, absmax(new values))``), so
  already-written slots are never re-quantized destructively; when the
  scale does grow, the touched page's existing content is requantized
  once by ``round(q * old/new)`` in the same program.
* quantize: ``q = clip(round(x / scale * 127), -127, 127)``; dequant:
  ``x = q * scale / 127``.

All three are serving-only (``no_grad``): the KV cache is inference
state, not a differentiable activation.
"""
from __future__ import annotations

import jax.numpy as jnp

from .registry import op
from .pallas_kernels import kv_append as _kv_append_impl
from .pallas_kernels import paged_attention as _paged_attention_impl

INT8_QMAX = 127.0


def _quant_scatter(pool, scales, new, slots, page_size):
    """Scatter ``new`` (kv_heads, tokens, d) f32 into the int8 ``pool``
    at flat ``slots`` with per-(kv_head, page) ``scales``, returning
    ``(pool', scales')``.  Implements reset-on-open + monotone scale +
    touched-page requant (module docstring); pad-sentinel slots drop
    out of every scatter (mode='drop') and gather via a clipped index
    whose result is then dropped too."""
    n_kv, n_pages = pool.shape[:2]
    pages = slots // page_size                      # sentinel -> n_pages (OOB)
    safe_pages = jnp.minimum(pages, n_pages - 1)    # gather-safe alias
    # reset-on-open: any slot at page offset 0 recycles its page
    opens = (slots % page_size == 0).astype(jnp.float32)
    open_vec = jnp.zeros((n_pages,), jnp.float32).at[pages].max(
        opens, mode="drop")
    old_eff = scales * (1.0 - open_vec)[None, :]    # (n_kv, n_pages)
    # monotone per-(head, page) scale: absmax of this step's values,
    # folded in by scatter-max (duplicate slots in one page combine)
    new_abs = jnp.abs(new).max(axis=2)              # (n_kv, tokens)
    page_max = jnp.zeros((n_kv, n_pages), jnp.float32).at[:, pages].max(
        new_abs, mode="drop")
    new_scales = jnp.maximum(old_eff, page_max)
    # requant the touched pages' existing content under the new scale
    # (ratio 1 when unchanged -> exact; ratio 0 on reset -> zeroed).
    # Duplicate page gathers read the SAME original content and write
    # identical requants, so scatter order cannot matter.
    ratio = jnp.where(new_scales > 0, old_eff / jnp.where(
        new_scales > 0, new_scales, 1.0), 1.0)      # (n_kv, n_pages)
    old_pages = jnp.take(pool, safe_pages, axis=1).astype(jnp.float32)
    requant = jnp.round(
        old_pages * jnp.take(ratio, safe_pages, axis=1)[..., None, None]
    ).astype(pool.dtype)
    pool = pool.at[:, pages].set(requant, mode="drop")
    # quantize this step's values with their page's (new) scale
    slot_scale = jnp.take(new_scales, safe_pages, axis=1)  # (n_kv, tokens)
    denom = jnp.where(slot_scale > 0, slot_scale, 1.0)
    q = jnp.clip(jnp.round(new / denom[..., None] * INT8_QMAX),
                 -INT8_QMAX, INT8_QMAX).astype(pool.dtype)
    # the rows take the same write as every other storage type
    (pool,) = _kv_append_impl((pool,), (q.transpose(1, 0, 2),), slots)
    return pool, new_scales


@op("kv_cache_append", no_grad=True,
    spec_hint={"optional_inputs": ["KScale", "VScale"]})
def _kv_cache_append(ctx):
    """Inputs: K/V ``(num_tokens, kv_heads, head_dim)`` — this step's new
    keys/values (decode: one per sequence; prefill: one per prompt
    token); SlotMapping ``(num_tokens,)`` int32 flat pool slots
    (``page_id * page_size + offset``) from the allocator — an
    out-of-range slot (``num_pages * page_size``, the allocator's pad
    sentinel) drops the write, so bucket-padded positions never touch
    the pool; KCache/VCache pools as stored (module docstring);
    optional KScale/VScale ``(kv_heads, num_pages)``
    f32 scale pools (int8 storage only).  Outputs KCacheOut/VCacheOut
    (+ KScaleOut/VScaleOut when scales are present) alias the pool vars
    (in-place update)."""
    k = ctx.in_("K")
    v = ctx.in_("V")
    slots = ctx.in_("SlotMapping").astype(jnp.int32)
    k_pool = ctx.in_("KCache")
    v_pool = ctx.in_("VCache")
    # a stored page is (rows, width) whatever the tokens a row
    page_size = k_pool.shape[2] * k_pool.shape[3] // k.shape[-1]

    if ctx.has_input("KScale"):
        kq, ks = _quant_scatter(
            k_pool, ctx.in_("KScale"),
            k.astype(jnp.float32).transpose(1, 0, 2), slots, page_size)
        vq, vs = _quant_scatter(
            v_pool, ctx.in_("VScale"),
            v.astype(jnp.float32).transpose(1, 0, 2), slots, page_size)
        ctx.set_out("KCacheOut", kq)
        ctx.set_out("VCacheOut", vq)
        ctx.set_out("KScaleOut", ks)
        ctx.set_out("VScaleOut", vs)
        return

    # K and V in one call: one kernel a layer
    k_pool, v_pool = _kv_append_impl(
        (k_pool, v_pool),
        (k.astype(k_pool.dtype), v.astype(v_pool.dtype)), slots)
    ctx.set_out("KCacheOut", k_pool)
    ctx.set_out("VCacheOut", v_pool)


@op("paged_attention", no_grad=True,
    spec_hint={"optional_inputs": ["KScale", "VScale"]})
def _paged_attention(ctx):
    """Inputs: Q ``(num_seqs, q_heads, head_dim)`` (one decode token per
    sequence), KCache/VCache pools, BlockTables ``(num_seqs,
    pages_per_seq)`` int32 (bucketed to the longest ACTIVE sequence —
    never the model max; pad rows/entries with page 0), ContextLens
    ``(num_seqs,)`` int32 true lengths including the current token;
    optional KScale/VScale ``(kv_heads, num_pages)`` f32 per-page
    scales (int8 pools — K/V dequantize inline, attention accumulates
    in f32).  Attr: scale (0 -> 1/sqrt(head_dim)).  Out: ``(num_seqs,
    q_heads, head_dim)``."""
    q = ctx.in_("Q")
    k_pool = ctx.in_("KCache")
    v_pool = ctx.in_("VCache")
    tables = ctx.in_("BlockTables").astype(jnp.int32)
    lens = ctx.in_("ContextLens").astype(jnp.int32)
    scale = ctx.attr("scale", 0.0) or None
    k_scale = ctx.in_("KScale") if ctx.has_input("KScale") else None
    v_scale = ctx.in_("VScale") if ctx.has_input("VScale") else None
    ctx.set_out("Out", _paged_attention_impl(q, k_pool, v_pool, tables,
                                             lens, scale,
                                             k_scale=k_scale,
                                             v_scale=v_scale))


@op("kv_dequant", no_grad=True,
    spec_hint={"optional_inputs": ["Scale"]})
def _kv_dequant(ctx):
    """Cast gathered KV pages back to f32 for dense attention (the
    chunk / spec-verify forms).  X is the pool gather result in the
    storage dtype; optional Scale is the SAME gather applied to the
    per-(kv_head, page) scale pool — its shape must be a leading-axes
    prefix of X's (trailing page_size/head_dim axes broadcast).  Out is
    f32: ``X * Scale / 127`` (int8) or a plain cast otherwise."""
    x = ctx.in_("X").astype(jnp.float32)
    if ctx.has_input("Scale"):
        s = ctx.in_("Scale").astype(jnp.float32)
        s = s.reshape(s.shape + (1,) * (x.ndim - s.ndim))
        x = x * s / INT8_QMAX
    ctx.set_out("Out", x)
