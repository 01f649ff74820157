"""Plain reference of the ``kimi-linear-48b-a3b`` configuration: the forward
pass of Kimi-Linear's hybrid decoder in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
chunking, no batching, experts one at a time, the delta rule one token at a
time (``lax.scan``).

Per layer ``h = x + Mixer(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``, RMSNorm
eps from the configuration, no biases, no position embedding, untied head.
``linear_attn_config`` (1-based lists, as published) says which layers are
KDA and which MLA.

- KDA (arXiv:2510.26692 section 3; ``H`` heads of ``d = head_dim``): ``[q~ |
  k~ | v~] = x W_qkv``; each channel through a causal convolution of 4 taps
  (``u_t = SiLU(sum_j w[c, j] u~_{t-3+j})``, zeros before the sequence, no
  bias); ``q = l2norm(q) d^-1/2``, ``k = l2norm(k)`` per head (``x
  rsqrt(sum x^2 + 1e-6)``); log-decay per head and channel ``g = -exp(A_log)
  softplus(W_fb (W_fa x) + dt_bias)``; write strength ``beta = sigmoid(x
  W_beta)`` per head; state ``S`` (d, d) a head from zero: ``S' = Diag(exp g)
  S``, ``S = S' + beta k (v - S'^T k)^T``, ``o = S^T q``; ``out = [RMSNorm_head
  (o; gamma) * sigmoid(W_gb (W_ga x))] W_o``.
- MLA: ``q = x W_q`` per head ``[q_nope | q_r]`` (no low-rank query, no query
  norm); ``[c_kv | k_r] = x W_kva``, ``c_kv = RMSNorm(c_kv)``; NO rotary
  (``mla_use_nope``): ``q_r``, ``k_r`` are 64 more key lanes all heads share;
  ``[k_nope | v] = c_kv W_kvb``; ``score = (q_nope . k_nope + q_r . k_r) (dn +
  dr)^-0.5``, causal softmax, ``out = concat_h(P v) W_o``.
- FFN: a SwiGLU of width ``intermediate_size`` in the first
  ``first_k_dense_replace`` layers; after them ``s = sigmoid(x W_r)`` over ALL
  the layer's experts (the router's width: 256), the top-k of ``s + b``, their
  weights ``s`` at the chosen (without ``b``) normalised to 1 over ALL chosen
  and scaled by ``routed_scaling_factor``; ``y = sum_{e chosen and held} w_e
  SwiGLU_e(x) + SwiGLU_shared(x)``.

**The share.**  The weights handed in are one chip's share of an
expert-parallel layer: experts ``0 .. held - 1`` (``held`` is the expert
stacks' leading size, the router's width says how many the layer has) and the
vocabulary rows the embedding and head hold.  A chosen expert that is not
held adds nothing here (another chip computes it) and its weight is spent all
the same; logits, arg-max and log-sum-exp are over the rows held.  Given every
expert this is the uncut layer: the four shares' routed parts plus the shared
expert once add up to it (tests/test_hybrid_decoder.py).

Independent of ``paddle_tpu``: it takes the weights by the names the program
gives them, in whatever type they are held (bfloat16 as served), and widens
each block to float32 as it uses it.

**Routing.**  As ``joyai-llm-flash.py``: top-k routing is discontinuous, so
a caller may pass the experts the served model chose (``routes`` for the
served rows); the reference checks each choice against its own scores
(``slack``: a wrong router fails here), then follows it.  Here the rows of
the PROMPT may be given too (``prompt_routes``): in this model a row's
neighbours reach it undiluted (the convolution's four taps, the channels of a
state that decay within a few tokens), so an expert flipped on the prompt's
last rows by a served precision's rounding moves the first served logits by
a tenth, where attention over thousands of rows would have diluted it.  Every
row given is held to ``slack``; rows not given are routed here alone.

``lower`` names a type (``float8_e4m3fn``) that every weight block and every
latent row is rounded through before it is widened, and with it the KDA state
is rounded through bfloat16 after every token (the state is float32 as
served: its nearest precision below): the reading the comparison must
refuse.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32


def _wide(w, lower=None):
    """A weight block in float32, through ``lower`` where that is asked."""
    if lower is not None:
        w = w.astype(jnp.dtype(lower))
    return w.astype(F32)


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rope(x, positions, theta):
    """``x`` (s, ..., d): pairs ``(2i, 2i + 1)`` turned by ``pos *
    theta^(-2i / d)``."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions.astype(F32).reshape((-1,) + (1,) * (x.ndim - 2) + (1,)) \
        * inv
    pairs = x.reshape(x.shape[:-1] + (d // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      a * jnp.sin(ang) + b * jnp.cos(ang)],
                     axis=-1).reshape(x.shape)


def _swiglu(x, gate, up, down, lower):
    return (jax.nn.silu(x @ _wide(gate, lower)) * (x @ _wide(up, lower))) \
        @ _wide(down, lower)


def _mla(x, w, p, cfg, positions, lower):
    s = x.shape[0]
    heads = cfg["num_attention_heads"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    q = (x @ _wide(w[p + "wq"], lower)).reshape(s, heads, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    kv_a = x @ _wide(w[p + "wkv_a"], lower)
    c_kv = _rms_norm(kv_a[:, :rank], w[p + "kv_norm_scale"], eps)
    k_r = kv_a[:, rank:]
    if not cfg["mla_use_nope"]:
        q_rope = _rope(q_rope, positions, cfg["rope_theta"])
        k_r = _rope(k_r, positions, cfg["rope_theta"])
    if lower is not None:                    # the cache's rows, rounded
        c_kv, k_r = _wide(c_kv, lower), _wide(k_r, lower)
    w_kvb = _wide(w[p + "wkv_b"], lower).reshape(rank, heads, dn + dv)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scale = (dn + dr) ** -0.5

    def one_head(args):
        qn, qr, wb = args                    # (s, dn), (s, dr), (rank, dn+dv)
        kv = c_kv @ wb
        sc = (qn @ kv[:, :dn].T + qr @ k_r.T) * scale
        prob = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return prob @ kv[:, dn:]

    out = lax.map(one_head, (q_nope.transpose(1, 0, 2),
                             q_rope.transpose(1, 0, 2),
                             w_kvb.transpose(1, 0, 2)))      # (heads, s, dv)
    return out.transpose(1, 0, 2).reshape(s, heads * dv) \
        @ _wide(w[p + "wo"], lower)


def _kda(x, w, p, cfg, lower):
    """The KDA mixer over one sequence ``x`` (s, hidden), the delta rule one
    token at a time."""
    s = x.shape[0]
    heads, d = cfg["kda_heads"], cfg["kda_head_dim"]
    pre = x @ _wide(w[p + "kda_wqkv"], lower)
    taps = _wide(w[p + "kda_conv"], lower)                 # (channels, 4)
    n = taps.shape[1]
    padded = jnp.concatenate([jnp.zeros((n - 1, pre.shape[1]), F32), pre])
    conv = jax.nn.silu(sum(padded[j:j + s] * taps[:, j] for j in range(n)))
    q, k, v = (t.reshape(s, heads, d) for t in jnp.split(conv, 3, axis=-1))

    def l2norm(t):
        return t * lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True)
                             + cfg["kda_l2_eps"])

    q, k = l2norm(q) * d ** -0.5, l2norm(k)
    dt = (x @ _wide(w[p + "kda_wfa"], lower)) @ _wide(w[p + "kda_wfb"],
                                                     lower) \
        + w[p + "kda_dt_bias"].astype(F32)
    g = -jnp.exp(w[p + "kda_a_log"].astype(F32))[:, None] \
        * jax.nn.softplus(dt).reshape(s, heads, d)
    beta = jax.nn.sigmoid(x @ _wide(w[p + "kda_wbeta"], lower))

    def token(state, at):
        qt, kt, vt, gt, bt = at
        state = jnp.exp(gt)[..., None] * state
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", state, kt))
        state = state + kt[..., None] * u[:, None, :]
        if lower is not None:                # the state, rounded
            state = state.astype(jnp.bfloat16).astype(F32)
        return state, jnp.einsum("hkv,hk->hv", state, qt)

    _, o = lax.scan(token, jnp.zeros((heads, d, d), F32),
                    (q, k, v, g, beta))
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                      + cfg["rms_norm_eps"]) \
        * w[p + "kda_onorm_scale"].astype(F32)
    gate = jax.nn.sigmoid((x @ _wide(w[p + "kda_wga"], lower))
                          @ _wide(w[p + "kda_wgb"], lower))
    return (o.reshape(s, heads * d) * gate) @ _wide(w[p + "wo"], lower)


def _moe(x, w, p, cfg, routes, lower):
    """Returns ``(y, margin, slack)``: per row the gap between this
    reference's k-th and (k+1)-th biased scores, and how far the worst of
    the experts in ``routes`` lies below its k-th (0 where the row is routed
    here)."""
    k = cfg["num_experts_per_token"]
    experts = w[p + "router"].shape[1]           # all the layer's experts
    held = w[p + "experts_gate"].shape[0]        # those of this share
    scores = jax.nn.sigmoid(x @ _wide(w[p + "router"], lower))
    biased = scores + w[p + "router_bias"].astype(F32)
    top, own = lax.top_k(biased, k + 1)
    margin = top[:, k - 1] - top[:, k]
    chosen, slack = own[:, :k], jnp.zeros(x.shape[0], F32)
    if routes is not None:
        given = routes[:, 0] >= 0
        safe = jnp.clip(routes, 0, experts - 1)
        got = jnp.take_along_axis(biased, safe, axis=-1)
        slack = jnp.where(given, jnp.maximum(
            top[:, k - 1] - jnp.min(got, axis=-1), 0.0), 0.0)
        chosen = jnp.where(given[:, None], safe, chosen)
    weight = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg["moe_renormalize"]:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    weight = weight * cfg["routed_scaling_factor"]
    dense = jnp.zeros((x.shape[0], experts), F32).at[
        jnp.arange(x.shape[0])[:, None], chosen].add(weight)

    stacks = [jnp.asarray(w[p + n]) for n in
              ("experts_gate", "experts_up", "experts_down")]

    def one_expert(e, y):
        out = _swiglu(x, *(lax.dynamic_index_in_dim(s, e, keepdims=False)
                           for s in stacks), lower)
        return y + lax.dynamic_index_in_dim(dense, e, 1) * out

    y = lax.fori_loop(0, held, one_expert, jnp.zeros_like(x))
    y = y + _swiglu(x, w[p + "shared_gate"], w[p + "shared_up"],
                    w[p + "shared_down"], lower)
    return y, margin, slack


def _block(x, w, cfg, positions, kda, moe, routes, lower):
    """One block; ``w`` holds the layer's weights without their prefix."""
    eps = cfg["rms_norm_eps"]
    xn = _rms_norm(x, w["attn_norm_scale"], eps)
    h = x + (_kda(xn, w, "", cfg, lower) if kda
             else _mla(xn, w, "", cfg, positions, lower))
    hn = _rms_norm(h, w["ffn_norm_scale"], eps)
    if not moe:
        zero = jnp.zeros(x.shape[0], F32)
        return h + _swiglu(hn, w["w_gate"], w["w_up"], w["w_down"],
                           lower), zero, zero
    y, margin, slack = _moe(hn, w, "", cfg, routes, lower)
    return h + y, margin, slack


_compiled = {}


def _frozen(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (bool, int, float))))


def _settled(cfg) -> dict:
    """The configuration file's numbers plus what the mixers need of its
    ``linear_attn_config`` and ``assumed`` groups, flat."""
    out = {k: v for k, v in cfg.items() if isinstance(v, (bool, int, float))}
    lin = cfg["linear_attn_config"]
    out.update(kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
               kda_l2_eps=cfg.get("kda_l2_eps", 1e-6))
    out.setdefault("mla_use_nope", True)
    return out


def is_kda(cfg, i: int) -> bool:
    """Layer ``i`` (0-based) by the published 1-based lists."""
    return i + 1 in cfg["linear_attn_config"]["kda_layers"]


def _block_fn(cfg, kda: bool, moe: bool, routed: bool, lower):
    """One jitted block per kind of layer: the layers of a kind share a
    compilation, and so do the sequences of one padded length."""
    key = ("block", _frozen(cfg), kda, moe, routed, lower)
    if key not in _compiled:
        frozen = dict(cfg)

        def run(x, w, positions, routes):
            with jax.default_matmul_precision("highest"):
                return _block(x, w, frozen, positions, kda, moe,
                              routes if routed else None, lower)

        _compiled[key] = jax.jit(run)
    return _compiled[key]


def _layer(weights, i):
    p = f"dec_l{i}_"
    return {k[len(p):]: v for k, v in weights.items() if k.startswith(p)}


def hidden_states(weights, tokens, cfg, routes=None, lower=None):
    """``tokens`` (s,) -> the last block's output before the final norm (s,
    hidden), and per expert layer the rows' routing ``margin`` and ``slack``
    (expert layers, s).  ``routes`` (expert layers, s, k) int32: the experts
    a served model chose, -1 in rows this reference routes itself."""
    tokens = jnp.asarray(tokens, jnp.int32)
    positions = jnp.arange(tokens.shape[0])
    x = _wide(jnp.asarray(weights["dec_embed"])[tokens], lower)
    margins, slacks, at = [], [], 0
    none = jnp.zeros((0,), jnp.int32)
    flat = _settled(cfg)
    for i in range(cfg["num_hidden_layers"]):
        moe = i >= cfg["first_k_dense_replace"]
        routed = moe and routes is not None
        x, margin, slack = _block_fn(flat, is_kda(cfg, i), moe, routed,
                                     lower)(
            x, _layer(weights, i), positions, routes[at] if routed else none)
        if moe:
            margins.append(margin)
            slacks.append(slack)
            at += 1
    if not margins:                          # no expert layer at all
        none = jnp.zeros((0, tokens.shape[0]), F32)
        return x, none, none
    return x, jnp.stack(margins), jnp.stack(slacks)


def logits_of(weights, hidden, cfg, norm="dec_norm_scale", lower=None,
              slab: int = 16384):
    """Rows of hidden state -> (rows, vocab) float32 logits, the head widened
    a slab of the vocabulary at a time."""
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(hidden, weights[norm], cfg["rms_norm_eps"])
        head = weights["dec_head"]
        vocab = head.shape[1]
        return jnp.concatenate(
            [x @ _wide(head[:, lo:lo + slab], lower)
             for lo in range(0, vocab, slab)], axis=-1)


def logits_all_positions(weights, tokens, cfg, lower=None):
    """``tokens`` (s,) -> (s, vocab): the next-token logits after every
    position (the small sizes of the tests)."""
    hidden, _, _ = hidden_states(weights, tokens, cfg, lower=lower)
    return logits_of(weights, hidden, cfg, lower=lower)


def _row_scores(cfg, lower):
    """Jitted: the rows' own-token logit, log-sum-exp and maximum."""
    key = ("rows", _frozen(cfg), lower)
    if key not in _compiled:
        frozen = _settled(cfg)

        def run(head, hidden, nxt):
            logits = logits_of(head, hidden, frozen, lower=lower)
            own = jnp.take_along_axis(logits, nxt[:, None], axis=-1)[:, 0]
            return (own, jax.nn.logsumexp(logits, axis=-1),
                    jnp.max(logits, axis=-1), jnp.isfinite(logits).all())

        _compiled[key] = jax.jit(run)
    return _compiled[key]


def served_token_scores(weights, cfg, prompt, served, routes=None,
                        pad_to: int = 0, lower=None, prompt_routes=None):
    """Teacher-forced over prompt + served, padded on the right to
    ``pad_to`` (causal attention leaves the real rows untouched).  For each
    served token: its reference logit, the row's log-sum-exp and maximum, the
    row's routing margin and the slack of the ``routes`` given for it, per
    expert layer.  ``routes`` (served, expert layers, k) or None.  The rows
    scored are padded to a power of two, so the sequences of a sample share
    their compilations.  Returns a dict of numpy arrays and ``finite``."""
    n, m = len(prompt), len(served)
    rows_routed = None
    size = max(pad_to, n + m)
    seq = np.zeros(size, np.int32)
    seq[:n + m] = list(prompt) + list(served)
    rows = np.arange(n - 1, n - 1 + m)
    moe_layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    full = None
    if routes is not None:
        full = np.full((moe_layers, size, cfg["num_experts_per_token"]), -1,
                       np.int32)
        full[:, rows] = np.asarray(routes, np.int32).transpose(1, 0, 2)
        if prompt_routes is not None:        # (expert layers, >= n, k)
            full[:, :n - 1] = np.asarray(prompt_routes, np.int32)[:, :n - 1]
            rows_routed = np.arange(n - 1 + m)
        full = jnp.asarray(full)
    hidden, margin, slack = hidden_states(weights, seq, cfg, full, lower)
    padded = np.full(1 << max(m - 1, 0).bit_length(), rows[-1])
    padded[:m] = rows
    own, lse, top, finite = _row_scores(cfg, lower)(
        {k: weights[k] for k in ("dec_head", "dec_norm_scale")},
        hidden[padded], jnp.asarray(seq[np.minimum(padded + 1, size - 1)]))
    return {"logit": np.asarray(own)[:m], "lse": np.asarray(lse)[:m],
            "max": np.asarray(top)[:m],
            "margin": np.asarray(margin)[:, rows].T,
            "slack": np.asarray(slack)[:, rows if rows_routed is None
                                       else rows_routed].T,
            "finite": bool(finite)}
