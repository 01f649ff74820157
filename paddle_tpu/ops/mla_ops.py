"""Lowerings of the latent-attention / sparse-expert decoder
(inference/mla_decoder.py): the ops a DeepSeek-V3-shaped block needs that
the GPT-2-shaped one does not.

* ``rms_norm`` — ``x * rsqrt(mean(x^2) + eps) * scale``, statistics in
  float32.
* ``rope_interleaved`` — rotary embedding over the pairs ``(2i, 2i + 1)``
  of the last axis, at the positions fed.
* ``swiglu`` — ``silu(gate) * up``.
* ``matmul_f32acc`` — a projection with its types stated: operands in the
  weight's type (bfloat16 in a served model), accumulation and result in
  float32, which ``matmul`` on two bfloat16 operands does not promise.
* ``moe_router`` — sigmoid scores, or a softmax over every output, the
  top-k of ``score + bias``, weights from the scores without the bias,
  normalised where the model says so, and scaled.
* ``moe_experts`` — the routed experts' SwiGLU over the tokens each expert
  received (``moe_gmm``), no capacity and no dropped token, the identity
  term of a model with zero-computation experts, and the count of tokens
  per expert.
* ``mla_prefill_attention`` — expanded latent attention of one whole
  prompt: ``W_kvb`` widens the latent rows to per-head keys and values,
  causal softmax by blocks (``mla_prefill``).
* ``mla_paged_attention`` — absorbed attention of one row a sequence over
  the paged latent pool (``mla_decode``).
* ``latent_cache_append`` — a token's ``[c_kv | k_r]`` row into the pool.

All serving-only (``no_grad``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .mla_kernels import (latent_append, mla_decode, mla_prefill, moe_combine,
                          moe_combine_reference, moe_gmm, moe_rows_engage,
                          moe_rows_in, moe_rows_in_reference)
from .registry import op


def _mm(x, w):
    """``x @ w``: operands in ``w``'s type, accumulated and returned in
    float32."""
    return jnp.matmul(x.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


@op("matmul_f32acc", no_grad=True)
def _matmul_f32acc(ctx):
    """X ``(..., k)`` times Y ``(k, n)``: operands in Y's type (bfloat16 in
    a served model), accumulation and Out in float32."""
    ctx.set_out("Out", _mm(ctx.in_("X"), ctx.in_("Y")))


@op("rms_norm", no_grad=True)
def _rms_norm(ctx):
    x = ctx.in_("X")
    eps = ctx.attr("epsilon", 1e-6)
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    ctx.set_out("Y", (y * ctx.in_("Scale").astype(jnp.float32))
                .astype(x.dtype))


def rope_interleaved(x, positions, theta: float):
    """``x`` (..., heads, d) rotated at ``positions`` (...): the pair
    ``(x[2i], x[2i+1])`` turns by ``pos * theta^(-2i/d)``.  Computed in
    float32."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[..., None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    a, b = x32[..., 0], x32[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


@op("rope_interleaved", no_grad=True)
def _rope_interleaved(ctx):
    """X ``(..., heads, d)``, Positions ``(...)`` int32; attr theta."""
    ctx.set_out("Out", rope_interleaved(
        ctx.in_("X"), ctx.in_("Positions"), ctx.attr("theta", 10000.0)))


@op("swiglu", no_grad=True)
def _swiglu(ctx):
    g = ctx.in_("Gate").astype(jnp.float32)
    u = ctx.in_("Up")
    ctx.set_out("Out", (jax.nn.silu(g) * u.astype(jnp.float32))
                .astype(u.dtype))


def route(x, w_gate, bias, top_k: int, scaling: float, normalize: bool,
          scoring: str = "sigmoid"):
    """``noaux_tc`` with one group, in float32: the scores are ``sigmoid(x @
    w_gate)``, each output alone, or (``scoring`` ``"softmax"``) a softmax
    over ALL of ``w_gate``'s outputs, the zero-computation experts' after the
    routed ones'; the chosen experts are the top-k of ``score + bias``; their
    weights are the scores themselves without the bias, normalised to sum 1
    where ``normalize``, and scaled.  Returns ``(idx (n, k) int32, weight (n,
    k) float32)``."""
    if scoring not in ("sigmoid", "softmax"):
        raise ValueError(f"the router scores by sigmoid or softmax: "
                         f"{scoring!r}")
    with jax.named_scope("moe_router"):
        z = jnp.matmul(x.astype(jnp.float32), w_gate.astype(jnp.float32),
                       precision=lax.Precision.HIGHEST)
        s = jax.nn.sigmoid(z) if scoring == "sigmoid" \
            else jax.nn.softmax(z, axis=-1)
        _, idx = lax.top_k(s + bias.astype(jnp.float32), top_k)
        w = jnp.take_along_axis(s, idx, axis=-1)
        if normalize:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return idx.astype(jnp.int32), w * scaling


@op("moe_router", no_grad=True)
def _moe_router(ctx):
    """X ``(n, hidden)``, Gate ``(hidden, experts)``, Bias ``(experts,)``
    -> Idx ``(n, k)`` int32, Weight ``(n, k)`` float32.  Attrs: top_k,
    routed_scaling_factor, norm_topk_prob, scoring_func (sigmoid where
    absent)."""
    idx, w = route(ctx.in_("X"), ctx.in_("Gate"), ctx.in_("Bias"),
                   int(ctx.attr("top_k", 1)),
                   float(ctx.attr("routed_scaling_factor", 1.0)),
                   bool(ctx.attr("norm_topk_prob", True)),
                   str(ctx.attr("scoring_func", "sigmoid")))
    ctx.set_out("Idx", idx)
    ctx.set_out("Weight", w)


def experts_forward(x, idx, weight, w_gate, w_up, w_down, valid=None,
                    share: bool = False, routed=None):
    """The routed experts over the tokens each received.  ``x`` (n, h);
    ``idx``/``weight`` (n, k); expert weights ``(experts, h, f)`` twice and
    ``(experts, f, h)``; ``valid`` (n,) bool, rows that are padding route
    nowhere.  ``share``: the weights are experts ``0 .. experts - 1`` of a
    layer that routes over more (one chip's share of an expert-parallel
    layer): a choice past them is another chip's and adds nothing here, its
    weight is spent all the same.  ``routed``: how many of the router's
    outputs are experts with weights, here or elsewhere; a choice at or past
    it is a zero-computation expert, the identity, which has no weights and
    so acts here, on this chip's own tokens, whatever experts are held: the
    row gains ``x`` times the sum of such choices' weights.  Returns ``(y
    (n, h), counts (experts,) int32)``."""
    n, k = idx.shape
    experts = w_gate.shape[0]
    by_kernel = moe_rows_engage(n * k, experts, x.shape[1])
    with jax.named_scope("moe_dispatch"):
        flat = idx.reshape(-1)
        if share or routed is not None:
            flat = jnp.where(flat < experts, flat, experts)
        if valid is not None:
            flat = jnp.where(jnp.repeat(valid, k), flat, experts)
        order = jnp.argsort(flat).astype(jnp.int32)
        # a compare and a sum: ``bincount`` is a scatter, half a
        # millisecond of the chip's time at 65,536 choices
        counts = jnp.sum(
            flat[None, :] == jnp.arange(experts, dtype=flat.dtype)[:, None],
            axis=1, dtype=jnp.int32)
        total = jnp.sum(counts)
        xs = (moe_rows_in if by_kernel else moe_rows_in_reference)(
            x, order, total, k, w_gate.dtype)
    # rows past the groups (padding, another chip's experts: three rows in
    # four of a share) are left unwritten by both calls: the combine's
    # kernel never reads them, and XLA's gather, where a call keeps it,
    # selects them away
    hmid = moe_gmm(xs, (w_gate, w_up), counts, gated=True,
                   out_dtype=w_gate.dtype)
    ys = moe_gmm(hmid, (w_down,), counts, gated=False,
                 out_dtype=jnp.float32, rows_apart=by_kernel)
    with jax.named_scope("moe_combine"):
        w = weight if valid is None else \
            jnp.where(valid[:, None], weight, 0.0)
        y = (moe_combine if by_kernel else moe_combine_reference)(
            ys, order, total, w)
    if routed is not None:
        with jax.named_scope("moe_identity"):
            own = jnp.sum(jnp.where(idx >= routed, w, 0.0), axis=-1)
            y = y + own[:, None] * x.astype(jnp.float32)
    return y, counts


@op("moe_experts", no_grad=True, spec_hint={"optional_inputs": ["Valid"]})
def _moe_experts(ctx):
    """X ``(n, hidden)``, Idx/Weight from ``moe_router``, WGate/WUp
    ``(experts, hidden, f)``, WDown ``(experts, f, hidden)``, optional
    Valid ``(n,)`` (non-zero: a real token) -> Out ``(n, hidden)``, Counts
    ``(experts,)`` int32 tokens each expert received.  With the output
    Absent (a scalar: rows with no expert here) the weights are a SHARE of
    the layer's experts, the first ``experts`` of those Idx ranges over.
    With the attr ``routed_experts`` the outputs of the router at and past
    it are zero-computation experts: Out holds their identity term, and the
    output Choices ``(3,)`` int32 counts the real tokens' choices: on the
    experts held, on identity experts, and all of them."""
    valid = ctx.in_("Valid") != 0 if ctx.has_input("Valid") else None
    share = ctx.has_output("Absent")
    routed = ctx.attr("routed_experts", None)
    routed = None if routed is None else int(routed)
    idx = ctx.in_("Idx")
    y, counts = experts_forward(
        ctx.in_("X"), idx, ctx.in_("Weight"), ctx.in_("WGate"),
        ctx.in_("WUp"), ctx.in_("WDown"), valid, share, routed)
    ctx.set_out("Out", y)
    ctx.set_out("Counts", counts)
    live = jnp.ones(idx.shape[:1], bool) if valid is None else valid
    if share:
        # the rows (real tokens) none of whose experts are held here: an
        # identity expert is held by every chip
        held = idx < counts.shape[0]
        if routed is not None:
            held = held | (idx >= routed)
        ctx.set_out("Absent", jnp.sum(~jnp.any(held, axis=-1) & live)
                    .astype(jnp.int32))
    if ctx.has_output("Choices"):
        identity = jnp.sum((idx >= routed) & live[:, None])
        ctx.set_out("Choices", jnp.stack(
            [jnp.sum(counts), identity, jnp.sum(live) * idx.shape[1]])
            .astype(jnp.int32))


def mla_expanded_attention(q_nope, q_rope, c_kv, k_r, w_kvb, v_dim: int,
                           scale: float):
    """Expanded causal latent attention of one sequence.  ``q_nope`` (s,
    heads, dn), ``q_rope`` (s, heads, dr) after RoPE, ``c_kv`` (s, r)
    after its norm, ``k_r`` (s, dr) after RoPE, ``w_kvb`` (r, heads * (dn +
    dv)): ``W_kvb`` widens the latent rows to per-head keys and values and
    ``mla_prefill`` attends.  Matmul operands in ``w_kvb``'s type, softmax
    in float32.  Returns (s, heads * dv) float32."""
    s, heads, dn = q_nope.shape
    cd = w_kvb.dtype
    with jax.named_scope("mla_attention"):
        kv = _mm(c_kv, w_kvb).astype(cd).reshape(s, heads, dn + v_dim)
        kv = kv.transpose(1, 0, 2)                       # heads first
        o = mla_prefill(q_nope.astype(cd).transpose(1, 0, 2),
                        q_rope.astype(cd).transpose(1, 0, 2),
                        kv[..., :dn], k_r.astype(cd), kv[..., dn:], scale)
        return o.transpose(1, 0, 2).reshape(s, heads * v_dim)


@op("mla_prefill_attention", no_grad=True)
def _mla_prefill_attention(ctx):
    """QNope ``(s, heads, dn)``, QRope ``(s, heads, dr)``, CKV ``(s, r)``,
    KRope ``(s, dr)``, WKVB ``(r, heads * (dn + dv))`` -> Out ``(s, heads *
    dv)``.  The causal mask is built here from the rows' order (positions
    ascend along a prompt).  Attrs: v_head_dim, scale."""
    ctx.set_out("Out", mla_expanded_attention(
        ctx.in_("QNope"), ctx.in_("QRope"), ctx.in_("CKV"),
        ctx.in_("KRope"), ctx.in_("WKVB"), int(ctx.attr("v_head_dim", 0)),
        float(ctx.attr("scale", 1.0))))


def mla_absorbed_attention(q_nope, q_rope, pool, block_tables, context_lens,
                           w_kvb, v_dim: int, scale: float):
    """Absorbed latent attention of one query row a sequence over the
    paged pool: ``q_lat = q_nope W_kvb^K``, ``mla_decode``, ``o = o_lat
    W_kvb^V``.  ``q_nope`` (n, heads, dn), ``q_rope`` (n, heads, dr);
    ``block_tables`` may hold fewer rows than ``n`` (a verify call: one
    table a sequence, ``n / rows`` consecutive rows each).  Returns (n,
    heads * dv) float32."""
    n, heads, dn = q_nope.shape
    rank, cd = w_kvb.shape[0], w_kvb.dtype
    w = w_kvb.reshape(rank, heads, dn + v_dim)
    with jax.named_scope("mla_absorb"):
        q_lat = jnp.einsum("nhd,rhd->nhr", q_nope.astype(cd), w[..., :dn],
                           preferred_element_type=jnp.float32)
    o_lat = mla_decode(q_lat, q_rope, pool, block_tables, context_lens,
                       scale)
    with jax.named_scope("mla_absorb"):
        o = jnp.einsum("nhr,rhd->nhd", o_lat.astype(cd), w[..., dn:],
                       preferred_element_type=jnp.float32)
    return o.reshape(n, heads * v_dim)


@op("mla_paged_attention", no_grad=True)
def _mla_paged_attention(ctx):
    """QNope ``(n, heads, dn)``, QRope ``(n, heads, dr)``, Cache (the
    latent pool as stored), BlockTables ``(n, w)``, ContextLens ``(n,)``
    (the row's own token included, already appended), WKVB -> Out ``(n,
    heads * dv)``.  Attrs: v_head_dim, scale."""
    ctx.set_out("Out", mla_absorbed_attention(
        ctx.in_("QNope"), ctx.in_("QRope"), ctx.in_("Cache"),
        ctx.in_("BlockTables").astype(jnp.int32),
        ctx.in_("ContextLens").astype(jnp.int32), ctx.in_("WKVB"),
        int(ctx.attr("v_head_dim")), float(ctx.attr("scale"))))


@op("latent_cache_append", no_grad=True)
def _latent_cache_append(ctx):
    """CKV ``(tokens, r)`` (after its norm), KRope ``(tokens, dr)`` (after
    RoPE), SlotMapping ``(tokens,)``, Cache -> CacheOut (the pool var, in
    place): the row ``[c_kv | k_r]`` at each token's slot; the pad slot
    drops its row."""
    pool = ctx.in_("Cache")
    rows = jnp.concatenate([ctx.in_("CKV"), ctx.in_("KRope")], axis=-1)
    ctx.set_out("CacheOut", latent_append(
        pool, rows.astype(pool.dtype), ctx.in_("SlotMapping")))


@op("slot_is_live", no_grad=True)
def _slot_is_live(ctx):
    """SlotMapping ``(tokens,)``, Cache (a pool as stored: ``(heads, pages,
    rows, width)``, a token a row) -> Out ``(tokens,)`` bool: the slot lies
    in the pool, so the row is a real token and not bucket padding (whose
    slot is the pad sentinel, the first past the pool)."""
    slots, pool = ctx.in_("SlotMapping"), ctx.in_("Cache")
    ctx.set_out("Out", (slots >= 0) & (slots < pool.shape[1] * pool.shape[2]))


@op("token_score", no_grad=True)
def _token_score(ctx):
    """Logits ``(rows, vocab)`` float32, Token ``(rows,)`` -> Out ``(rows,
    2)`` float32: the token's own logit and the row's log-sum-exp, what a
    check of the served logits needs of a row without fetching the row."""
    logits = ctx.in_("Logits").astype(jnp.float32)
    tok = ctx.in_("Token").astype(jnp.int32).reshape(-1)
    own = jnp.take_along_axis(logits, tok[:, None], axis=-1)[:, 0]
    ctx.set_out("Out", jnp.stack(
        [own, jax.nn.logsumexp(logits, axis=-1)], axis=-1))
