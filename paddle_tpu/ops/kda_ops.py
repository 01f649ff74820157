"""Lowering of the gated delta-rule mixer (Kimi Delta Attention) of the
hybrid decoder (inference/mla_decoder.py): ``kda_mixer``, the whole mixer
from the block's normed rows to the rows ``W_o`` takes, in three forms.

Per token and head (arXiv:2510.26692 section 3; ``d_k = d_v = head_dim``)::

    [q | k | v] = SiLU(conv4(x W_qkv))          causal, depthwise, no bias
    q = l2norm(q) d_k^-1/2,  k = l2norm(k)      per head
    g = -exp(A_log) softplus(W_fb (W_fa x) + dt_bias)   per head and channel
    beta = sigmoid(x W_beta)                    per head
    S = Diag(exp(g)) S;  S += beta k (v - S^T k)^T;  o = S^T q
    out = RMSNorm_head(o; gamma) * sigmoid(W_gb (W_ga x))

* ``reference`` — one whole prompt, nothing kept.
* ``prefill`` — one whole prompt from an empty state (``kda_prefill``); the
  state after the last REAL token and the last ``taps - 1`` inputs of the
  convolution go to the sequence's slot of the two pools.  Rows past the
  prompt (``Valid`` false) decay nothing and write nothing.
* ``decode`` — one token a row against its slot (``kda_decode``, the pool
  rewritten in place); a padded row carries the pad slot and touches that
  alone.

Projections take operands in the weights' type and accumulate in float32;
the convolution, norms, gates, decay and state are float32.  Serving-only.

``gdn_mixer`` is the same rule as Gated DeltaNet writes it (arXiv:2412.06464;
the ``linear_attention`` layers of ``inference/gqa_decoder.py``), in the same
three forms over the same convolution, normalisation and slot pools, through
``gdn_prefill`` / ``gdn_decode``::

    [q | k | v | z] = x W_qkvz;  [b | a] = x W_ba
    [q | k | v] = SiLU(conv4([q | k | v]))      d_k for q and k, d_v for v
    q = l2norm(q) d_k^-1/2,  k = l2norm(k)      per head
    g = -exp(A_log) softplus(a + dt_bias)       ONE value a head
    beta = sigmoid(b), or 2 sigmoid(b)          (``neg_eigval``)
    S = exp(g) S;  S += beta k (v - S^T k)^T;  o = S^T q     S (d_k, d_v)
    out = RMSNorm_head(o; gamma) * SiLU(z)

Its state pool holds ``kda_kernels.gdn_state_shape`` a slot: ``d_k`` on
sublanes under the lanes of as many heads as fill whole tiles.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .kda_kernels import (gdn_decode, gdn_pack_states, gdn_prefill,
                          kda_decode, kda_prefill, normalised_heads,
                          short_conv, short_conv_step, short_conv_tail)
from .mla_ops import _mm
from .registry import op

#: the weights a KDA mixer takes, by input slot
WEIGHT_SLOTS = ("WQKV", "Conv", "WFA", "WFB", "ALog", "DtBias", "WBeta",
                "WGA", "WGB", "ONormScale")


def kda_inputs(x, w, heads: int, dk: int):
    """The rows' projections that need no neighbour: the convolution's
    inputs ``(n, 3 heads d_k)``, the log-decay ``g (n, heads, d_k)`` and the
    write strength ``beta (n, heads)``."""
    f32 = jnp.float32
    pre = _mm(x, w["WQKV"])
    dt = _mm(_mm(x, w["WFA"]), w["WFB"]) + w["DtBias"].astype(f32)
    g = -jnp.exp(w["ALog"].astype(f32))[:, None] \
        * jax.nn.softplus(dt).reshape(-1, heads, dk)
    beta = jax.nn.sigmoid(_mm(x, w["WBeta"]))
    return pre, g, beta


def kda_output(o, x, w, eps: float):
    """``o`` (n, heads, d_v): the per-head RMSNorm under the sigmoid gate,
    ``(n, heads d_v)``."""
    f32 = jnp.float32
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) \
        * w["ONormScale"].astype(f32)
    gate = jax.nn.sigmoid(_mm(_mm(x, w["WGA"]), w["WGB"]))
    return o.reshape(o.shape[0], -1) * gate


@op("kda_mixer", no_grad=True, spec_hint={"optional_inputs": [
    "Valid", "LastIndex", "StateSlots", "State", "ConvState"]})
def _kda_mixer(ctx):
    """X ``(n, hidden)`` and the weights of :data:`WEIGHT_SLOTS` -> Out
    ``(n, heads * head_dim)``.  Attrs: mode (reference | prefill | decode),
    heads, head_dim, epsilon (the output norm's), l2_epsilon.  The caching
    modes take Valid ``(n,)``, StateSlots (``(1,)`` for a prompt, ``(n,)``
    for a decode batch), State ``(slots + 1, heads, d_k, d_v)`` and
    ConvState ``(slots + 1, taps - 1, 3 heads d_k)``, both rewritten in
    place (StateOut, ConvStateOut); prefill takes LastIndex ``(1,)``."""
    mode = ctx.attr("mode", "reference")
    heads, dk = int(ctx.attr("heads")), int(ctx.attr("head_dim"))
    x = ctx.in_("X")
    w = {slot: ctx.in_(slot) for slot in WEIGHT_SLOTS}
    taps = w["Conv"].shape[1]
    l2_eps = float(ctx.attr("l2_epsilon", 1e-6))
    pre, g, beta = kda_inputs(x, w, heads, dk)
    if ctx.has_input("Valid"):
        live = ctx.in_("Valid") != 0
        g = jnp.where(live[:, None, None], g, 0.0)
        beta = jnp.where(live[:, None], beta, 0.0)
    if mode == "decode":
        slots = ctx.in_("StateSlots").astype(jnp.int32)
        tails = ctx.in_("ConvState")
        conv, tail = short_conv_step(tails[slots], pre, w["Conv"])
        q, k, v = normalised_heads(jax.nn.silu(conv), heads, l2_eps)
        o, state = kda_decode(ctx.in_("State"), slots, q, k, v, g, beta)
        ctx.set_out("StateOut", state)
        ctx.set_out("ConvStateOut", tails.at[slots].set(tail))
    else:
        o, state = kda_prefill(
            jax.nn.silu(short_conv(pre, w["Conv"])), g, beta, heads,
            l2_eps)
        if mode == "prefill":
            _keep_prompt(ctx, state, pre, taps)
    ctx.set_out("Out", kda_output(o, x, w, ctx.attr("epsilon", 1e-5)))


def _keep_prompt(ctx, state, pre, taps: int):
    """A prompt's prefill into its slot of the two pools: the state after its
    last real token and the convolution's inputs that end there."""
    slot = ctx.in_("StateSlots").astype(jnp.int32)[0]
    last = ctx.in_("LastIndex").astype(jnp.int32)[0]
    ctx.set_out("StateOut", lax.dynamic_update_index_in_dim(
        ctx.in_("State"), state, slot, 0))
    ctx.set_out("ConvStateOut", lax.dynamic_update_index_in_dim(
        ctx.in_("ConvState"), short_conv_tail(pre, last, taps), slot, 0))


#: the weights a Gated DeltaNet mixer takes, by input slot
GDN_WEIGHT_SLOTS = ("WQKVZ", "WBA", "Conv", "ALog", "DtBias", "ONormScale")


def gdn_inputs(x, w, heads: int, dk: int, dv: int, neg_eigval: bool):
    """The rows' projections that need no neighbour: the convolution's
    inputs ``(n, heads (2 d_k + d_v))``, the output gate's ``z (n, heads
    d_v)``, the log-decay ``g (n, heads)`` and the write strength ``beta (n,
    heads)``, up to 2 with ``neg_eigval``."""
    f32 = jnp.float32
    pre, z = jnp.split(_mm(x, w["WQKVZ"]), [heads * (2 * dk + dv)], axis=-1)
    b, a = jnp.split(_mm(x, w["WBA"]), 2, axis=-1)
    g = -jnp.exp(w["ALog"].astype(f32)) \
        * jax.nn.softplus(a + w["DtBias"].astype(f32))
    beta = jax.nn.sigmoid(b) * (2.0 if neg_eigval else 1.0)
    return pre, z, g, beta


def gdn_output(o, z, scale, eps: float):
    """``o`` (n, heads, d_v): the per-head RMSNorm times ``SiLU(z)``, ``(n,
    heads d_v)``."""
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)
    return o.reshape(o.shape[0], -1) * jax.nn.silu(z)


@op("gdn_mixer", no_grad=True, spec_hint={"optional_inputs": [
    "Valid", "LastIndex", "StateSlots", "State", "ConvState"]})
def _gdn_mixer(ctx):
    """X ``(n, hidden)`` and the weights of :data:`GDN_WEIGHT_SLOTS` -> Out
    ``(n, heads * value_dim)``.  Attrs: mode (reference | prefill | decode),
    heads, key_dim, value_dim, neg_eigval, epsilon (the output norm's),
    l2_epsilon.  The caching modes take what ``kda_mixer``'s do, State
    ``(slots + 1,) + gdn_state_shape`` and ConvState ``(slots + 1, taps - 1,
    heads (2 d_k + d_v))``."""
    mode = ctx.attr("mode", "reference")
    heads, dk, dv = (int(ctx.attr(a)) for a in ("heads", "key_dim",
                                                "value_dim"))
    x = ctx.in_("X")
    w = {slot: ctx.in_(slot) for slot in GDN_WEIGHT_SLOTS}
    taps = w["Conv"].shape[1]
    l2_eps = float(ctx.attr("l2_epsilon", 1e-6))
    pre, z, g, beta = gdn_inputs(x, w, heads, dk, dv,
                                 bool(ctx.attr("neg_eigval", False)))
    if ctx.has_input("Valid"):
        live = (ctx.in_("Valid") != 0)[:, None]
        g, beta = jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0)
    if mode == "decode":
        slots = ctx.in_("StateSlots").astype(jnp.int32)
        tails = ctx.in_("ConvState")
        conv, tail = short_conv_step(tails[slots], pre, w["Conv"])
        q, k, v = normalised_heads(jax.nn.silu(conv), heads, l2_eps, dk)
        o, state = gdn_decode(ctx.in_("State"), slots, q, k, v, g, beta)
        ctx.set_out("StateOut", state)
        ctx.set_out("ConvStateOut", tails.at[slots].set(tail))
    else:
        q, k, v = normalised_heads(
            jax.nn.silu(short_conv(pre, w["Conv"])), heads, l2_eps, dk)
        o, state = gdn_prefill(q, k, v, g, beta)
        if mode == "prefill":
            _keep_prompt(ctx, gdn_pack_states(state), pre, taps)
    ctx.set_out("Out", gdn_output(o, z, w["ONormScale"],
                                  ctx.attr("epsilon", 1e-6)))
