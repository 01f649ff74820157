"""Plain reference of the ``laguna-xs2`` configuration: the forward pass of
Laguna-XS.2's decoder in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching, experts one at a time; attention by blocks of query rows against
the keys they can see, so that 8.7 k positions fit.

Per layer ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``, RMSNorm
eps from the configuration, no biases, untied head, a final RMSNorm.

- ``Attn`` of layer ``l`` with ``H_l = num_attention_heads_per_layer[l]``
  query heads over ``num_key_value_heads`` K/V heads of ``head_dim``: ``q = x
  W_q``, ``k = x W_k``, ``v = x W_v``; rotary on q and k by the layer's kind
  (``layer_types[l]``, ``rope_parameters``); ``s_ij = q_i . k_j /
  sqrt(head_dim)`` for ``j <= i`` and, on a ``sliding_attention`` layer, ``j >
  i - sliding_window``; softmax; head ``h`` reads K/V head ``h // group``;
  ``g = sigmoid(x W_g)`` one value a head (``gating``); ``Attn = concat_h(g_h
  o_h) W_o``.
- Rotary, half-rotated (``rotate_half``: lane ``i`` pairs with lane ``i +
  r/2`` inside the first ``r = head_dim * partial_rotary_factor`` lanes, the
  rest pass through).  ``default``: ``inv_freq_i = theta^(-2i/r)``.  ``yarn``:
  with ``f_i = theta^(2i/r)``, ``c(n) = r ln(orig / (2 pi n)) / (2 ln
  theta)``, ``low = max(floor(c(beta_fast)), 0)``, ``high = min(ceil(c(
  beta_slow)), r - 1)``, ``ramp_i = clip((i - low) / (high - low), 0, 1)``:
  ``inv_freq_i = ramp_i / (factor f_i) + (1 - ramp_i) / f_i``; cos and sin
  times ``attention_factor``.
- FFN: a SwiGLU of width ``intermediate_size`` where ``mlp_layer_types[l]``
  is ``dense``; else ``s = sigmoid(x W_r)`` over ALL the layer's experts (the
  router's width), the top-k of ``s + b``, their weights ``s`` at the chosen
  (without ``b``) normalised to 1 over all chosen and scaled by
  ``moe_routed_scaling_factor``; ``y = sum_{e chosen and held} w_e
  SwiGLU_e(x) + SwiGLU_shared(x)``.

**The share**, **routing** (``routes`` for the served rows, ``prompt_routes``
for the prompt's, every choice given held to ``slack`` and then followed) and
the independence of ``paddle_tpu`` are ``kimi-linear-48b-a3b.py``'s: the
weights handed in are experts ``0 .. held - 1`` of each layer and the
vocabulary rows held; given every expert this is the uncut layer.

``lower`` names a type (``float8_e4m3fn``) that every weight block and every
K and V row (after rotary: what the cache holds) is rounded through before it
is widened: the reading the comparison must refuse.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
Q_BLOCK = 1024


def _wide(w, lower=None):
    """A weight block in float32, through ``lower`` where that is asked."""
    if lower is not None:
        w = w.astype(jnp.dtype(lower))
    return w.astype(F32)


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


def inv_freq(p: dict, head_dim: int) -> np.ndarray:
    """One kind's rotary frequencies (float64) from its ``rope_parameters``
    entry: ``head_dim * partial_rotary_factor / 2`` of them."""
    r = int(round(head_dim * p.get("partial_rotary_factor", 1.0)))
    i = np.arange(r // 2, dtype=np.float64)
    f = float(p["rope_theta"]) ** (2.0 * i / r)
    if p.get("rope_type", "default") != "yarn":
        return 1.0 / f

    def c(turns):
        return r * math.log(p["original_max_position_embeddings"]
                            / (2.0 * math.pi * turns)) \
            / (2.0 * math.log(p["rope_theta"]))

    low = max(math.floor(c(p["beta_fast"])), 0)
    high = min(math.ceil(c(p["beta_slow"])), r - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return ramp / (p["factor"] * f) + (1.0 - ramp) / f


def _rope(x, positions, inv, factor):
    """``x`` (s, heads, d), half-rotated over the first ``2 * len(inv)``
    lanes."""
    half = inv.shape[0]
    ang = positions.astype(F32)[:, None, None] * inv
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    a, b = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., 2 * half:]], axis=-1)


def _swiglu(x, gate, up, down, lower):
    return (jax.nn.silu(x @ _wide(gate, lower)) * (x @ _wide(up, lower))) \
        @ _wide(down, lower)


def _attention(x, w, cfg, positions, kind, lower):
    """One layer's attention over one sequence ``x`` (s, hidden)."""
    s = x.shape[0]
    kvh, d = cfg["num_key_value_heads"], cfg["head_dim"]
    heads = w["wq"].shape[1] // d
    group = heads // kvh
    window = cfg["sliding_window"] if kind == "sliding_attention" else 0
    inv, factor = cfg["rope"][kind]
    inv = jnp.asarray(inv, F32)
    q = _rope((x @ _wide(w["wq"], lower)).reshape(s, heads, d), positions,
              inv, factor)
    k = _rope((x @ _wide(w["wk"], lower)).reshape(s, kvh, d), positions,
              inv, factor)
    v = (x @ _wide(w["wv"], lower)).reshape(s, kvh, d)
    if lower is not None:                    # the cache's rows, rounded
        k, v = _wide(k, lower), _wide(v, lower)
    q = q.reshape(s, kvh, group, d).transpose(1, 2, 0, 3)   # (kvh, g, s, d)
    k, v = k.transpose(1, 0, 2), v.transpose(1, 0, 2)       # (kvh, s, d)
    scale = d ** -0.5

    def one_kv_head(args):
        qh, kh, vh = args                    # (g, s, d), (s, d), (s, d)
        out = []
        for lo in range(0, s, Q_BLOCK):
            hi = min(lo + Q_BLOCK, s)
            k0 = max(0, lo - window + 1) if window else 0
            sc = jnp.einsum("gqd,td->gqt", qh[:, lo:hi], kh[k0:hi]) * scale
            rows = lo + jnp.arange(hi - lo)[:, None]
            cols = k0 + jnp.arange(hi - k0)[None, :]
            ok = cols <= rows
            if window:
                ok &= cols > rows - window
            prob = jax.nn.softmax(jnp.where(ok, sc, -jnp.inf), axis=-1)
            out.append(jnp.einsum("gqt,td->gqd", prob, vh[k0:hi]))
        return jnp.concatenate(out, axis=1)

    o = lax.map(one_kv_head, (q, k, v))                     # (kvh, g, s, d)
    o = o.transpose(2, 0, 1, 3).reshape(s, heads, d)
    if cfg["gating"]:
        o = o * jax.nn.sigmoid(x @ _wide(w["wg"], lower))[..., None]
    return o.reshape(s, heads * d) @ _wide(w["wo"], lower)


def _moe(x, w, cfg, routes, lower):
    """Returns ``(y, margin, slack)``: per row the gap between this
    reference's k-th and (k+1)-th biased scores, and how far the worst of
    the experts in ``routes`` lies below its k-th (0 where the row is routed
    here)."""
    k = cfg["num_experts_per_tok"]
    experts = w["router"].shape[1]           # all the layer's experts
    held = w["experts_gate"].shape[0]        # those of this share
    scores = jax.nn.sigmoid(x @ _wide(w["router"], lower))
    biased = scores + w["router_bias"].astype(F32)
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
    weight = weight / jnp.sum(weight, axis=-1, keepdims=True) \
        * cfg["moe_routed_scaling_factor"]
    dense = jnp.zeros((x.shape[0], experts), F32).at[
        jnp.arange(x.shape[0])[:, None], chosen].add(weight)
    stacks = [jnp.asarray(w[n]) for n in
              ("experts_gate", "experts_up", "experts_down")]

    def one_expert(e, y):
        out = _swiglu(x, *(lax.dynamic_index_in_dim(s, e, keepdims=False)
                           for s in stacks), lower)
        return y + lax.dynamic_index_in_dim(dense, e, 1) * out

    y = lax.fori_loop(0, held, one_expert, jnp.zeros_like(x))
    y = y + _swiglu(x, w["shared_gate"], w["shared_up"], w["shared_down"],
                    lower)
    return y, margin, slack


def _block(x, w, cfg, positions, kind, moe, routes, lower):
    """One block; ``w`` holds the layer's weights without their prefix."""
    eps = cfg["rms_norm_eps"]
    h = x + _attention(_rms_norm(x, w["attn_norm_scale"], eps), w, cfg,
                       positions, kind, lower)
    hn = _rms_norm(h, w["ffn_norm_scale"], eps)
    if not moe:
        zero = jnp.zeros(x.shape[0], F32)
        return h + _swiglu(hn, w["w_gate"], w["w_up"], w["w_down"],
                           lower), zero, zero
    y, margin, slack = _moe(hn, w, cfg, routes, lower)
    return h + y, margin, slack


_compiled = {}


def _settled(cfg) -> dict:
    """The configuration file's numbers plus each kind's rotary table and
    factor, flat and hashable by :func:`_frozen`."""
    out = {k: v for k, v in cfg.items() if isinstance(v, (bool, int, float))}
    ropes = cfg["rope_parameters"]
    out["rope"] = {
        kind: (tuple(float(v) for v in inv_freq(ropes[kind],
                                                cfg["head_dim"])),
               float(ropes[kind].get("attention_factor", 1.0))
               if ropes[kind].get("rope_type") == "yarn" else 1.0)
        for kind in ("full_attention", "sliding_attention")}
    return out


def _frozen(cfg):
    return tuple(sorted((k, tuple(sorted(v.items())) if isinstance(v, dict)
                         else v) for k, v in cfg.items()))


def _block_fn(cfg, kind: str, moe: bool, routed: bool, lower):
    """One jitted block per kind of layer: the layers of a kind share a
    compilation, and so do the sequences of one padded length."""
    key = ("block", _frozen(cfg), kind, moe, routed, lower)
    if key not in _compiled:
        frozen = dict(cfg)

        def run(x, w, positions, routes):
            with jax.default_matmul_precision("highest"):
                return _block(x, w, frozen, positions, kind, moe,
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
        moe = cfg["mlp_layer_types"][i] != "dense"
        routed = moe and routes is not None
        x, margin, slack = _block_fn(flat, cfg["layer_types"][i], moe, routed,
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


def logits_of(weights, hidden, cfg, lower=None, slab: int = 16384):
    """Rows of hidden state -> (rows, vocab) float32 logits, the head widened
    a slab of the vocabulary at a time."""
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(hidden, weights["dec_norm_scale"], cfg["rms_norm_eps"])
        head = weights["dec_head"]
        return jnp.concatenate(
            [x @ _wide(head[:, lo:lo + slab], lower)
             for lo in range(0, head.shape[1], slab)], axis=-1)


def logits_all_positions(weights, tokens, cfg, lower=None):
    """``tokens`` (s,) -> (s, vocab): the next-token logits after every
    position (the small sizes of the tests)."""
    hidden, _, _ = hidden_states(weights, tokens, cfg, lower=lower)
    return logits_of(weights, hidden, cfg, lower=lower)


def _row_scores(cfg, lower):
    """Jitted: the rows' own-token logit, log-sum-exp and maximum."""
    key = ("rows", cfg["rms_norm_eps"], lower)
    if key not in _compiled:
        eps = {"rms_norm_eps": cfg["rms_norm_eps"]}

        def run(head, hidden, nxt):
            logits = logits_of(head, hidden, eps, lower=lower)
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
    expert layer.  ``routes`` (served, expert layers, k) or None;
    ``prompt_routes`` (expert layers, >= prompt rows, k): the prompt's rows
    are then held to ``slack`` and followed too.  Returns a dict of numpy
    arrays and ``finite``."""
    n, m = len(prompt), len(served)
    rows_routed = None
    size = max(pad_to, n + m)
    seq = np.zeros(size, np.int32)
    seq[:n + m] = list(prompt) + list(served)
    rows = np.arange(n - 1, n - 1 + m)
    k = cfg["num_experts_per_tok"]
    moe_layers = sum(t != "dense" for t in
                     cfg["mlp_layer_types"][:cfg["num_hidden_layers"]])
    full = None
    if routes is not None:
        full = np.full((moe_layers, size, k), -1, np.int32)
        full[:, rows] = np.asarray(routes, np.int32).transpose(1, 0, 2)
        if prompt_routes is not None:
            full[:, :n - 1] = np.asarray(prompt_routes, np.int32)[:, :n - 1]
            rows_routed = np.arange(n - 1 + m)
        full = jnp.asarray(full)
    hidden, margin, slack = hidden_states(weights, seq, cfg, full, lower)
    padded = np.full(1 << max(m - 1, 0).bit_length(), rows[-1])
    padded[:m] = rows
    own, lse, top, finite = _row_scores(cfg, lower)(
        {key: weights[key] for key in ("dec_head", "dec_norm_scale")},
        hidden[padded], jnp.asarray(seq[np.minimum(padded + 1, size - 1)]))
    return {"logit": np.asarray(own)[:m], "lse": np.asarray(lse)[:m],
            "max": np.asarray(top)[:m],
            "margin": np.asarray(margin)[:, rows].T,
            "slack": np.asarray(slack)[:, rows if rows_routed is None
                                       else rows_routed].T,
            "finite": bool(finite)}
