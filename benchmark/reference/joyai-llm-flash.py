"""Plain reference of the ``joyai-llm-flash`` configuration: the forward pass
of a DeepSeek-V3-shaped decoder in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching, experts one at a time.

Equations (arXiv:2405.04434 section 2.1 for the latent attention,
arXiv:2412.19437 sections 2.1-2.2 for the router without auxiliary loss and
multi-token prediction), per layer ``h = x + MLA(RMSNorm(x))``, ``y = h +
FFN(RMSNorm(h))``, RMSNorm eps from the configuration, no biases, untied
head:

- MLA: ``c_q = RMSNorm(x W_qa)``; ``[q_nope | q_rope] = c_q W_qb`` per head;
  ``[c_kv | k_r] = x W_kva``, ``c_kv = RMSNorm(c_kv)``; ``q_rope``, ``k_r``
  rotated over interleaved pairs ``(2i, 2i + 1)`` at ``theta``, ``k_r`` one
  vector shared by all heads; ``[k_nope | v] = c_kv W_kvb`` per head; ``score
  = (q_nope . k_nope + q_rope . k_r) * (dn + dr)^-0.5``, causal softmax,
  ``out = concat_h(P v) W_o``.
- FFN: a SwiGLU of width ``intermediate_size`` in the first
  ``first_k_dense_replace`` layers; after them ``s = sigmoid(x W_g)``, the
  experts chosen are the top-k of ``s + b``, their weights ``s`` at the
  chosen experts (without ``b``), normalised to 1 and scaled by
  ``routed_scaling_factor``; ``y = sum_e w_e SwiGLU_e(x) + SwiGLU_shared(x)``.
  No capacity, no dropped token.
- MTP (eq. 21-23): ``h' = W_p [RMSNorm(h_i) ; RMSNorm(Emb(t_{i+1}))]``, one
  block as above, the model's head over ``RMSNorm(.)``: logits for
  ``t_{i+2}``.  ``h_i`` is the last block's output BEFORE the final norm, and
  the hidden state comes first in the concatenation (both as the paper
  writes them; the configuration file lists them under ``assumed``).

Independent of ``paddle_tpu``: it takes the weights by the names the program
gives them, in whatever type they are held (bfloat16 as served), and widens
each block to float32 as it uses it, so that it fits beside them on one
chip: one head's scores, one expert's matrices, one slab of the vocabulary
at a time.

**Routing of the served rows.**  Top-k routing is discontinuous: where the
8th and 9th of 256 scores lie closer than the rounding of the served
precision moves them, the served model and this one pick different experts
and their logits differ by a tenth, whichever is right.  So a caller may pass
the experts the served model chose for the rows it checks (``routes``).  For
those rows this reference first checks the choice (every chosen expert's
biased score within ``slack`` of its own k-th: a wrong router fails here),
then computes with the chosen experts and its OWN scores as weights; the
logits are then a continuous function of the inputs and are compared
tightly.  Every other row is routed by this reference alone.

``lower`` names a type (``float8_e4m3fn``) that every weight block and every
latent row is rounded through before it is widened: the reading of the
nearest precision below the served one, which the comparison must refuse.
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
    c_q = _rms_norm(x @ _wide(w[p + "wq_a"], lower), w[p + "q_norm_scale"],
                    eps)
    q = (c_q @ _wide(w[p + "wq_b"], lower)).reshape(s, heads, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], positions,
                                        cfg["rope_theta"])
    kv_a = x @ _wide(w[p + "wkv_a"], lower)
    c_kv = _rms_norm(kv_a[:, :rank], w[p + "kv_norm_scale"], eps)
    k_r = _rope(kv_a[:, rank:], positions, cfg["rope_theta"])
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


def _moe(x, w, p, cfg, routes, lower):
    """Returns ``(y, margin, slack)``: per row the gap between this
    reference's k-th and (k+1)-th biased scores, and how far the worst of
    the experts in ``routes`` lies below its k-th (0 where the row is routed
    here)."""
    k, experts = cfg["num_experts_per_tok"], cfg["n_routed_experts"]
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
    if cfg["norm_topk_prob"]:
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

    y = lax.fori_loop(0, experts, one_expert, jnp.zeros_like(x))
    y = y + _swiglu(x, w[p + "shared_gate"], w[p + "shared_up"],
                    w[p + "shared_down"], lower)
    return y, margin, slack


def _block(x, w, cfg, positions, moe, routes, lower):
    """One block; ``w`` holds the layer's weights without their prefix."""
    eps = cfg["rms_norm_eps"]
    h = x + _mla(_rms_norm(x, w["attn_norm_scale"], eps), w, "", cfg,
                 positions, lower)
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


def _block_fn(cfg, moe: bool, routed: bool, lower):
    """One jitted block per kind of layer: the expert layers of a model
    share a compilation, and so do the sequences of one padded length."""
    key = ("block", _frozen(cfg), moe, routed, lower)
    if key not in _compiled:
        frozen = dict(cfg)

        def run(x, w, positions, routes):
            with jax.default_matmul_precision("highest"):
                return _block(x, w, frozen, positions, moe,
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
    for i in range(cfg["num_hidden_layers"]):
        moe = i >= cfg["first_k_dense_replace"]
        routed = moe and routes is not None
        x, margin, slack = _block_fn(cfg, moe, routed, lower)(
            x, _layer(weights, i), positions, routes[at] if routed else none)
        if moe:
            margins.append(margin)
            slacks.append(slack)
            at += 1
    return x, jnp.stack(margins), jnp.stack(slacks)


def mtp_hidden(weights, hidden, next_tokens, cfg, lower=None):
    """The MTP block over a sequence: ``hidden`` (s, h) the main model's
    rows, ``next_tokens`` (s,) the token after each.  Returns the block's
    output (s, h), whose normed rows the model's head reads."""
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        emb = _wide(jnp.asarray(weights["dec_embed"])[next_tokens], lower)
        both = jnp.concatenate(
            [_rms_norm(hidden, weights["mtp_hnorm_scale"], eps),
             _rms_norm(emb, weights["mtp_enorm_scale"], eps)], axis=-1)
        x = both @ _wide(weights["mtp_proj"], lower)
    x, _, _ = _block_fn(cfg, True, False, lower)(
        x, _layer(weights, cfg["num_hidden_layers"]),
        jnp.arange(hidden.shape[0]), jnp.zeros((0,), jnp.int32))
    return x


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


def mtp_logits_all_positions(weights, tokens, cfg):
    """``tokens`` (s + 1,): position i's main hidden state with token i + 1
    -> (s, vocab) logits for token i + 2."""
    tokens = jnp.asarray(tokens, jnp.int32)
    hidden, _, _ = hidden_states(weights, tokens[:-1], cfg)
    return logits_of(weights, mtp_hidden(weights, hidden, tokens[1:], cfg),
                     cfg, norm="mtp_norm_scale")


def _row_scores(cfg, lower):
    """Jitted: the rows' own-token logit, log-sum-exp and maximum."""
    key = ("rows", _frozen(cfg), lower)
    if key not in _compiled:
        frozen = dict(cfg)

        def run(head, hidden, nxt):
            logits = logits_of(head, hidden, frozen, lower=lower)
            own = jnp.take_along_axis(logits, nxt[:, None], axis=-1)[:, 0]
            return (own, jax.nn.logsumexp(logits, axis=-1),
                    jnp.max(logits, axis=-1), jnp.isfinite(logits).all())

        _compiled[key] = jax.jit(run)
    return _compiled[key]


def served_token_scores(weights, cfg, prompt, served, routes=None,
                        pad_to: int = 0, lower=None):
    """Teacher-forced over prompt + served, padded on the right to
    ``pad_to`` (causal attention leaves the real rows untouched).  For each
    served token: its reference logit, the row's log-sum-exp and maximum, the
    row's routing margin and the slack of the ``routes`` given for it, per
    expert layer.  ``routes`` (served, expert layers, k) or None.  The rows
    scored are padded to a power of two, so the sequences of a sample share
    their compilations.  Returns a dict of numpy arrays and ``finite``."""
    n, m = len(prompt), len(served)
    size = max(pad_to, n + m)
    seq = np.zeros(size, np.int32)
    seq[:n + m] = list(prompt) + list(served)
    rows = np.arange(n - 1, n - 1 + m)
    moe_layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    full = None
    if routes is not None:
        full = np.full((moe_layers, size, cfg["num_experts_per_tok"]), -1,
                       np.int32)
        full[:, rows] = np.asarray(routes, np.int32).transpose(1, 0, 2)
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
            "slack": np.asarray(slack)[:, rows].T, "finite": bool(finite)}
