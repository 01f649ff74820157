"""Lowerings of the grouped-query decoder with window layers
(inference/gqa_decoder.py): what its attention needs beyond the ops of
``mla_ops.py`` (norms, projections, the expert layer) and ``paged_ops.py``
(``kv_cache_append``).

* ``rope_half`` — rotary embedding in the half-rotated form over the first
  lanes of a head, the frequency table an attribute: plain rotary over the
  whole head and YaRN over half of it are one op.
* ``gqa_prefill_attention`` — causal (windowed) grouped-query attention of
  one whole prompt (``gqa_prefill``), the output gated a head.
* ``gqa_paged_attention`` — one row a sequence over the paged K/V pools
  (``gqa_decode``), the walk starting at the row's first held position, the
  output gated a head.

All serving-only (``no_grad``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .gqa_kernels import gqa_decode, gqa_prefill, rope_half
from .registry import op


@op("rope_half", no_grad=True)
def _rope_half(ctx):
    """X ``(..., heads, d)``, Positions ``(...)`` int32; attrs inv_freq (the
    rotated lanes' frequencies, half as many as lanes turn) and factor (on
    cos and sin)."""
    ctx.set_out("Out", rope_half(
        ctx.in_("X"), ctx.in_("Positions"), list(ctx.attr("inv_freq")),
        float(ctx.attr("factor", 1.0))))


def _gated(o, gate):
    """``o`` (n, heads, d) float32 times ``sigmoid(gate)`` (n, heads), a
    value a head, flattened to (n, heads * d): fused by XLA into the read of
    the kernel's output that the output projection makes anyway."""
    n, heads, d = o.shape
    if gate is not None:
        o = o * jax.nn.sigmoid(gate.astype(jnp.float32))[..., None]
    return o.reshape(n, heads * d)


@op("gqa_prefill_attention", no_grad=True,
    spec_hint={"optional_inputs": ["Gate"]})
def _gqa_prefill_attention(ctx):
    """Q ``(s, heads, d)``, K/V ``(s, kv_heads, d)`` after rotary, optional
    Gate ``(s, heads)`` (before its sigmoid) -> Out ``(s, heads * d)``.  The
    mask is built from the rows' order (positions ascend along a prompt).
    Attrs: scale, window (0: none)."""
    q, k, v = ctx.in_("Q"), ctx.in_("K"), ctx.in_("V")
    with jax.named_scope("gqa_attention"):
        o = gqa_prefill(q.astype(k.dtype).transpose(1, 0, 2),
                        k.transpose(1, 0, 2),
                        v.transpose(1, 0, 2), float(ctx.attr("scale")),
                        int(ctx.attr("window", 0)))
        ctx.set_out("Out", _gated(
            o.transpose(1, 0, 2),
            ctx.in_("Gate") if ctx.has_input("Gate") else None))


@op("gqa_paged_attention", no_grad=True,
    spec_hint={"optional_inputs": ["Gate", "First"]})
def _gqa_paged_attention(ctx):
    """Q ``(n, heads, d)``, KCache/VCache ``(kv_heads, pages, page_size,
    d)``, BlockTables ``(n, w)``, ContextLens ``(n,)`` (the row's own token
    included, already appended), optional First ``(n,)`` (the position of the
    tables' entry 0: a window layer's table holds the pages from there on;
    absent: 0), optional Gate ``(n, heads)`` -> Out ``(n, heads * d)``.
    Attrs: scale, window (0: none)."""
    lens = ctx.in_("ContextLens").astype(jnp.int32)
    first = ctx.in_("First").astype(jnp.int32) if ctx.has_input("First") \
        else jnp.zeros_like(lens)
    o = gqa_decode(ctx.in_("Q"), ctx.in_("KCache"), ctx.in_("VCache"),
                   ctx.in_("BlockTables").astype(jnp.int32), lens, first,
                   float(ctx.attr("scale")), int(ctx.attr("window", 0)))
    with jax.named_scope("gqa_attention"):
        ctx.set_out("Out", _gated(
            o, ctx.in_("Gate") if ctx.has_input("Gate") else None))
