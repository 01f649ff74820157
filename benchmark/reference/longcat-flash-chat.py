"""Plain reference of the ``longcat-flash-chat`` configuration: the forward
pass of a shortcut-connected latent-attention decoder with zero-computation
experts (LongCat-Flash, arXiv:2509.01322) in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching, experts one at a time.

One "layer" ``l`` of the source's ``num_layers`` holds sub-layers ``2l`` and
``2l + 1``, each a latent attention and a dense SwiGLU with weights and norms
of its own, and one expert layer on a shortcut across them (``x`` the
residual stream, RMSNorm eps from the configuration, no biases, untied
head)::

    h1 = x  + MLA_0(RMSNorm(x))            n1 = RMSNorm(h1)
    m  = MoE(n1)                                     # the shortcut: read here
    h2 = h1 + SwiGLU_0(n1)                           # width ffn_hidden_size
    h3 = h2 + MLA_1(RMSNorm(h2))           n3 = RMSNorm(h3)
    y  = h3 + SwiGLU_1(n3) + m                       # ... added here

- MLA: ``c_q = RMSNorm(x W_qa) * sqrt(hidden / q_lora_rank)``; ``[q_nope |
  q_rope] = c_q W_qb`` per head; ``[c_kv | k_r] = x W_kva``, ``c_kv =
  RMSNorm(c_kv) * sqrt(hidden / kv_lora_rank)``; ``[k_nope | v] = c_kv W_kvb``
  per head; ``q_rope``, ``k_r`` rotated over interleaved pairs ``(2i, 2i +
  1)`` at ``theta`` (``k_r`` is NOT scaled and is shared by the heads);
  ``score = (q_nope . k_nope + q_rope . k_r) * (dn + dr)^-0.5``, causal
  softmax, ``out = concat_h(P v) W_o``.  The two constants are the config's
  ``mla_scale_q_lora`` / ``mla_scale_kv_lora``.
- MoE: ``s = softmax(n1 W_r)`` over ALL the router's outputs, the
  ``router_experts`` routed experts and then ``zero_expert_num`` identity
  experts; chosen = top-``moe_topk`` of ``s + b``; weight ``w_e =
  routed_scaling_factor * s_e`` (no normalisation); ``m = sum_{e routed} w_e
  SwiGLU_e(n1) + (sum_{e identity} w_e) * n1``.  No shared expert, no
  capacity, no dropped token.

**The share.**  ``n_routed_experts`` is what the weights hold: experts ``0 ..
n_routed_experts - 1`` of the ``router_experts`` the router scores (one chip's
share of an expert-parallel layer).  A choice on a routed expert that is not
held adds nothing here and its weight is spent all the same; an identity
expert has no weights and acts on this chip's tokens whatever is held.  The
vocabulary is the slice the embedding and the head hold.

Independent of ``paddle_tpu``: it takes the weights by the names the program
gives them (``dec_l{j}_...`` of SUB-layer ``j``; the experts of layer ``l``
under sub-layer ``2l``), in whatever type they are held (bfloat16 as served),
and widens each block to float32 as it uses it, so that it fits beside them
on one chip: one head's scores, one expert's matrices, a slab of a dense
SwiGLU's width and of the vocabulary at a time.

**Routing of the served rows**, as ``joyai-llm-flash.py``: top-k routing is
discontinuous, so a caller may pass the experts the served model chose for
the rows it checks (``routes``).  For those rows this reference first checks
the choice (every chosen output's biased score within ``slack`` of its own
k-th), then computes with the chosen outputs and its OWN scores as weights.
``margin`` and ``slack`` are in units of the uniform score ``1 / outputs``
(a softmax over 768 outputs has scores of order 1/768: a slack in score
units would be smaller than any limit worth writing).

Controls, each a reading the comparison must refuse: ``lower`` names a type
(``float8_e4m3fn``) that every weight block and every latent row is rounded
through before it is widened; ``drop`` leaves a term of the expert layer out,
``"routed"`` the held experts' sum or ``"identity"`` the identity term.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
DENSE_SLAB = 2048          # columns of a dense SwiGLU widened at a time


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


def _dense_swiglu(x, gate, up, down, lower):
    """A SwiGLU of any width, a slab of its columns widened at a time (the
    sum over the slabs of the slab's own SwiGLU)."""
    width = gate.shape[1]
    slab = DENSE_SLAB if width % DENSE_SLAB == 0 else width

    def one(j, y):
        return y + _swiglu(
            x, lax.dynamic_slice_in_dim(gate, j * slab, slab, 1),
            lax.dynamic_slice_in_dim(up, j * slab, slab, 1),
            lax.dynamic_slice_in_dim(down, j * slab, slab, 0), lower)

    return lax.fori_loop(0, width // slab, one, jnp.zeros_like(x))


def _mla(x, w, cfg, positions, lower):
    s, hidden = x.shape
    heads = cfg["num_attention_heads"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    c_q = _rms_norm(x @ _wide(w["wq_a"], lower), w["q_norm_scale"], eps)
    if cfg["mla_scale_q_lora"]:
        c_q = c_q * (hidden / cfg["q_lora_rank"]) ** 0.5
    q = (c_q @ _wide(w["wq_b"], lower)).reshape(s, heads, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], positions,
                                        cfg["rope_theta"])
    kv_a = x @ _wide(w["wkv_a"], lower)
    c_kv = _rms_norm(kv_a[:, :rank], w["kv_norm_scale"], eps)
    if cfg["mla_scale_kv_lora"]:
        c_kv = c_kv * (hidden / rank) ** 0.5
    k_r = _rope(kv_a[:, rank:], positions, cfg["rope_theta"])
    if lower is not None:                    # the cache's rows, rounded
        c_kv, k_r = _wide(c_kv, lower), _wide(k_r, lower)
    w_kvb = _wide(w["wkv_b"], lower).reshape(rank, heads, dn + dv)
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
        @ _wide(w["wo"], lower)


def _moe(x, w, cfg, routes, lower, drop):
    """Returns ``(m, margin, slack)``: per row the gap between this
    reference's k-th and (k+1)-th biased scores, and how far the worst of
    the outputs in ``routes`` lies below its k-th (0 where the row is routed
    here), both in units of ``1 / outputs``."""
    k = cfg["moe_topk"]
    routed = cfg.get("router_experts", cfg["n_routed_experts"])
    outputs = routed + cfg["zero_expert_num"]
    held = w["experts_gate"].shape[0]
    scores = jax.nn.softmax(x @ _wide(w["router"], lower), axis=-1)
    biased = scores + w["router_bias"].astype(F32)
    top, own = lax.top_k(biased, k + 1)
    margin = (top[:, k - 1] - top[:, k]) * outputs
    chosen, slack = own[:, :k], jnp.zeros(x.shape[0], F32)
    if routes is not None:
        given = routes[:, 0] >= 0
        safe = jnp.clip(routes, 0, outputs - 1)
        got = jnp.take_along_axis(biased, safe, axis=-1)
        slack = jnp.where(given, jnp.maximum(
            top[:, k - 1] - jnp.min(got, axis=-1), 0.0), 0.0) * outputs
        chosen = jnp.where(given[:, None], safe, chosen)
    weight = jnp.take_along_axis(scores, chosen, axis=-1) \
        * cfg["routed_scaling_factor"]                 # never normalised
    dense = jnp.zeros((x.shape[0], outputs), F32).at[
        jnp.arange(x.shape[0])[:, None], chosen].add(weight)

    stacks = [jnp.asarray(w[n]) for n in
              ("experts_gate", "experts_up", "experts_down")]

    def one_expert(e, y):
        out = _swiglu(x, *(lax.dynamic_index_in_dim(s, e, keepdims=False)
                           for s in stacks), lower)
        return y + lax.dynamic_index_in_dim(dense, e, 1) * out

    m = jnp.zeros_like(x)
    if drop != "routed":       # outputs held .. routed - 1: another chip's
        m = lax.fori_loop(0, held, one_expert, m)
    if drop != "identity":
        m = m + jnp.sum(dense[:, routed:], axis=-1, keepdims=True) * x
    return m, margin, slack


def _layer(x, first, second, cfg, positions, routes, lower, drop):
    """One shortcut-connected layer; ``first`` / ``second`` hold the two
    sub-layers' weights without their prefix."""
    eps = cfg["rms_norm_eps"]

    def dense(n, w):
        return _dense_swiglu(n, w["w_gate"], w["w_up"], w["w_down"], lower)

    h1 = x + _mla(_rms_norm(x, first["attn_norm_scale"], eps), first, cfg,
                  positions, lower)
    n1 = _rms_norm(h1, first["ffn_norm_scale"], eps)
    m, margin, slack = _moe(n1, first, cfg, routes, lower, drop)
    h2 = h1 + dense(n1, first)
    h3 = h2 + _mla(_rms_norm(h2, second["attn_norm_scale"], eps), second,
                   cfg, positions, lower)
    n3 = _rms_norm(h3, second["ffn_norm_scale"], eps)
    return h3 + dense(n3, second) + m, margin, slack


_compiled = {}


def _frozen(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (bool, int, float))))


def _layer_fn(cfg, routed: bool, lower, drop):
    """One jitted layer: the layers of a model share a compilation, and so
    do the sequences of one padded length."""
    key = ("layer", _frozen(cfg), routed, lower, drop)
    if key not in _compiled:
        frozen = dict(cfg)

        def run(x, first, second, positions, routes):
            with jax.default_matmul_precision("highest"):
                return _layer(x, first, second, frozen, positions,
                              routes if routed else None, lower, drop)

        _compiled[key] = jax.jit(run)
    return _compiled[key]


def _sub_layer(weights, j):
    p = f"dec_l{j}_"
    return {k[len(p):]: v for k, v in weights.items() if k.startswith(p)}


def hidden_states(weights, tokens, cfg, routes=None, lower=None, drop=None):
    """``tokens`` (s,) -> the last layer's output before the final norm (s,
    hidden), and per expert layer the rows' routing ``margin`` and ``slack``
    (layers, s).  ``routes`` (layers, s, k) int32: the outputs a served model
    chose, -1 in rows this reference routes itself."""
    tokens = jnp.asarray(tokens, jnp.int32)
    positions = jnp.arange(tokens.shape[0])
    x = _wide(jnp.asarray(weights["dec_embed"])[tokens], lower)
    margins, slacks = [], []
    none = jnp.zeros((0,), jnp.int32)
    routed = routes is not None
    for l in range(cfg["num_layers"]):
        x, margin, slack = _layer_fn(cfg, routed, lower, drop)(
            x, _sub_layer(weights, 2 * l), _sub_layer(weights, 2 * l + 1),
            positions, routes[l] if routed else none)
        margins.append(margin)
        slacks.append(slack)
    return x, jnp.stack(margins), jnp.stack(slacks)


def logits_of(weights, hidden, cfg, lower=None, slab: int = 16384):
    """Rows of hidden state -> (rows, vocab) float32 logits, the head widened
    a slab of the vocabulary at a time."""
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(hidden, weights["dec_norm_scale"], cfg["rms_norm_eps"])
        head = weights["dec_head"]
        vocab = head.shape[1]
        return jnp.concatenate(
            [x @ _wide(head[:, lo:lo + slab], lower)
             for lo in range(0, vocab, slab)], axis=-1)


def logits_all_positions(weights, tokens, cfg, lower=None, drop=None):
    """``tokens`` (s,) -> (s, vocab): the next-token logits after every
    position (the small sizes of the tests)."""
    hidden, _, _ = hidden_states(weights, tokens, cfg, lower=lower, drop=drop)
    return logits_of(weights, hidden, cfg, lower=lower)


def expert_layer(weights, l, x, cfg):
    """Layer ``l``'s expert layer alone over the normed rows ``x`` (rows,
    hidden), routed by this reference: what the share test sums."""
    with jax.default_matmul_precision("highest"):
        return _moe(jnp.asarray(x, F32), _sub_layer(weights, 2 * l), cfg,
                    None, None, None)[0]


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
                        pad_to: int = 0, lower=None, drop=None):
    """Teacher-forced over prompt + served, padded on the right to
    ``pad_to`` (causal attention leaves the real rows untouched).  For each
    served token: its reference logit, the row's log-sum-exp and maximum, the
    row's routing margin and the slack of the ``routes`` given for it, per
    expert layer.  ``routes`` (served, layers, k) or None.  The rows scored
    are padded to a power of two, so the sequences of a sample share their
    compilations.  Returns a dict of numpy arrays and ``finite``."""
    n, m = len(prompt), len(served)
    size = max(pad_to, n + m)
    seq = np.zeros(size, np.int32)
    seq[:n + m] = list(prompt) + list(served)
    rows = np.arange(n - 1, n - 1 + m)
    full = None
    if routes is not None:
        full = np.full((cfg["num_layers"], size, cfg["moe_topk"]), -1,
                       np.int32)
        full[:, rows] = np.asarray(routes, np.int32).transpose(1, 0, 2)
        full = jnp.asarray(full)
    hidden, margin, slack = hidden_states(weights, seq, cfg, full, lower,
                                          drop)
    padded = np.full(1 << max(m - 1, 0).bit_length(), rows[-1])
    padded[:m] = rows
    own, lse, top, finite = _row_scores(cfg, lower)(
        {k: weights[k] for k in ("dec_head", "dec_norm_scale")},
        hidden[padded], jnp.asarray(seq[np.minimum(padded + 1, size - 1)]))
    return {"logit": np.asarray(own)[:m], "lse": np.asarray(lse)[:m],
            "max": np.asarray(top)[:m],
            "margin": np.asarray(margin)[:, rows].T,
            "slack": np.asarray(slack)[:, rows].T, "finite": bool(finite)}
