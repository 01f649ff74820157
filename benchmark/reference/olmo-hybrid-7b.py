"""Plain reference of the ``olmo-hybrid-7b`` configuration: the forward pass
of Olmo-Hybrid's decoder in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
chunking, no batching, attention a head at a time, the delta rule one token
at a time (``lax.scan``).

Per layer, the norms on the OUTPUTS (the Olmo family since OLMo 2,
arXiv:2501.00656): ``h = x + RMSNorm(Mixer(x))``, ``y = h + RMSNorm(SwiGLU
(h))``, RMSNorm eps from the configuration, no biases, no position embedding,
untied head, a final RMSNorm.  ``layer_types`` says which layers are
``linear_attention`` and which ``full_attention``; every feed-forward is a
dense SwiGLU of ``intermediate_size``.

- ``linear_attention`` (Gated DeltaNet, arXiv:2412.06464; ``H =
  linear_num_value_heads`` heads, ``d_k = linear_key_head_dim``, ``d_v =
  linear_value_head_dim``): ``[q~ | k~ | v~ | z] = x W_qkvz``, ``[b | a] = x
  W_ba``; each channel of ``[q~ | k~ | v~]`` through a causal convolution of
  ``linear_conv_kernel_dim`` taps (``u_t = SiLU(sum_j w[c, j] u~_{t-3+j})``,
  zeros before the sequence, no bias); ``q = l2norm(q) d_k^-1/2``, ``k =
  l2norm(k)`` per head (``x rsqrt(sum x^2 + 1e-6)``); ``beta = sigmoid(b)``,
  twice that with ``linear_allow_neg_eigval``; ``g = -exp(A_log) softplus(a +
  dt_bias)``, ONE value a head; state ``S`` (d_k, d_v) a head from zero: ``S'
  = exp(g) S``, ``S = S' + k (beta (v - S'^T k))^T``, ``o = S^T q``; ``out =
  [RMSNorm_head(o; gamma) * SiLU(z)] W_o``.
- ``full_attention``: ``q = RMSNorm(x W_q)``, ``k = RMSNorm(x W_k)`` over the
  whole projection before the heads are split, ``v = x W_v``;
  ``num_attention_heads`` query heads over ``num_key_value_heads`` K/V heads
  of ``hidden_size / num_attention_heads``; NO rotary (``rope_parameters``'
  ``rope_theta`` is null); ``score = q . k head_dim^-1/2``, causal softmax,
  ``out = concat_h(P v) W_o``.

Independent of ``paddle_tpu``: it takes the weights by the names the program
gives them, in whatever type they are held (bfloat16 as served), and widens
each block to float32 as it uses it.  The model routes nothing: the
``routes`` a caller passes are ignored, and the ``margin`` and ``slack`` the
serving comparison reads of an expert decoder's reference are one column of
``inf`` and of 0 (no choice was near, none was followed).

``lower`` names a type (``float8_e4m3fn``) that every weight block and every
K and V row is rounded through before it is widened, and with it the delta
rule's state is rounded through bfloat16 after every token (the state is
float32 as served: its nearest precision below): the reading the comparison
must refuse.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
L2_EPS = 1e-6


def _wide(w, lower=None):
    """A weight block in float32, through ``lower`` where that is asked."""
    if lower is not None:
        w = w.astype(jnp.dtype(lower))
    return w.astype(F32)


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


def _swiglu(x, gate, up, down, lower):
    return (jax.nn.silu(x @ _wide(gate, lower)) * (x @ _wide(up, lower))) \
        @ _wide(down, lower)


def _attention(x, w, cfg, lower):
    """One full layer's attention over one sequence ``x`` (s, hidden)."""
    s = x.shape[0]
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, eps = cfg["hidden_size"] // heads, cfg["rms_norm_eps"]
    q = _rms_norm(x @ _wide(w["wq"], lower), w["q_norm_scale"], eps)
    k = _rms_norm(x @ _wide(w["wk"], lower), w["k_norm_scale"], eps)
    v = x @ _wide(w["wv"], lower)
    if lower is not None:                    # the cache's rows, rounded
        k, v = _wide(k, lower), _wide(v, lower)
    q = q.reshape(s, heads, d).transpose(1, 0, 2)
    k = jnp.repeat(k.reshape(s, kvh, d), heads // kvh, axis=1) \
        .transpose(1, 0, 2)
    v = jnp.repeat(v.reshape(s, kvh, d), heads // kvh, axis=1) \
        .transpose(1, 0, 2)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def one_head(args):
        qh, kh, vh = args                    # (s, d) each
        sc = (qh @ kh.T) * d ** -0.5
        return jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1) @ vh

    out = lax.map(one_head, (q, k, v))                       # (heads, s, d)
    return out.transpose(1, 0, 2).reshape(s, heads * d) \
        @ _wide(w["wo"], lower)


def _linear(x, w, cfg, lower):
    """The Gated DeltaNet mixer over one sequence ``x`` (s, hidden), the
    delta rule one token at a time."""
    s = x.shape[0]
    heads = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    pre = x @ _wide(w["gdn_wqkvz"], lower)
    pre, z = pre[:, :heads * (2 * dk + dv)], pre[:, heads * (2 * dk + dv):]
    taps = _wide(w["gdn_conv"], lower)                     # (channels, taps)
    n = taps.shape[1]
    padded = jnp.concatenate([jnp.zeros((n - 1, pre.shape[1]), F32), pre])
    conv = jax.nn.silu(sum(padded[j:j + s] * taps[:, j] for j in range(n)))
    q = conv[:, :heads * dk].reshape(s, heads, dk)
    k = conv[:, heads * dk:2 * heads * dk].reshape(s, heads, dk)
    v = conv[:, 2 * heads * dk:].reshape(s, heads, dv)

    def l2norm(t):
        return t * lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + L2_EPS)

    q, k = l2norm(q) * dk ** -0.5, l2norm(k)
    ba = x @ _wide(w["gdn_wba"], lower)
    beta = jax.nn.sigmoid(ba[:, :heads]) \
        * (2.0 if cfg["linear_allow_neg_eigval"] else 1.0)
    g = -jnp.exp(w["gdn_a_log"].astype(F32)) \
        * jax.nn.softplus(ba[:, heads:] + w["gdn_dt_bias"].astype(F32))

    def token(state, at):
        qt, kt, vt, gt, bt = at
        state = jnp.exp(gt)[:, None, None] * state
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", state, kt))
        state = state + kt[..., None] * u[:, None, :]
        if lower is not None:                # the state, rounded
            state = state.astype(jnp.bfloat16).astype(F32)
        return state, jnp.einsum("hkv,hk->hv", state, qt)

    _, o = lax.scan(token, jnp.zeros((heads, dk, dv), F32),
                    (q, k, v, g, beta))
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                      + cfg["rms_norm_eps"]) \
        * w["gdn_onorm_scale"].astype(F32)
    return (o.reshape(s, heads * dv) * jax.nn.silu(z)) \
        @ _wide(w["wo"], lower)


def _block(x, w, cfg, kind, lower):
    """One block; ``w`` holds the layer's weights without their prefix."""
    eps = cfg["rms_norm_eps"]
    mixed = _linear(x, w, cfg, lower) if kind == "linear_attention" \
        else _attention(x, w, cfg, lower)
    h = x + _rms_norm(mixed, w["attn_norm_scale"], eps)
    return h + _rms_norm(
        _swiglu(h, w["w_gate"], w["w_up"], w["w_down"], lower),
        w["ffn_norm_scale"], eps)


_compiled = {}


def _settled(cfg) -> dict:
    """The configuration file's numbers, flat."""
    return {k: v for k, v in cfg.items() if isinstance(v, (bool, int, float))}


def _frozen(cfg):
    return tuple(sorted(cfg.items()))


def _block_fn(cfg, kind: str, lower):
    """One jitted block per kind of layer: the layers of a kind share a
    compilation, and so do the sequences of one padded length."""
    key = ("block", _frozen(cfg), kind, lower)
    if key not in _compiled:
        frozen = dict(cfg)

        def run(x, w):
            with jax.default_matmul_precision("highest"):
                return _block(x, w, frozen, kind, lower)

        _compiled[key] = jax.jit(run)
    return _compiled[key]


def _layer(weights, i):
    p = f"dec_l{i}_"
    return {k[len(p):]: v for k, v in weights.items() if k.startswith(p)}


def hidden_states(weights, tokens, cfg, lower=None):
    """``tokens`` (s,) -> the last block's output before the final norm (s,
    hidden)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x = _wide(jnp.asarray(weights["dec_embed"])[tokens], lower)
    flat = _settled(cfg)
    for i in range(cfg["num_hidden_layers"]):
        x = _block_fn(flat, cfg["layer_types"][i], lower)(
            x, _layer(weights, i))
    return x


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
    return logits_of(weights, hidden_states(weights, tokens, cfg, lower),
                     cfg, lower=lower)


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
    ``pad_to`` (causal attention and a recurrence that runs forward leave the
    real rows untouched).  For each served token: its reference logit, the
    row's log-sum-exp and maximum; ``margin`` and ``slack`` as the docstring
    says (the model routes nothing, ``routes`` and ``prompt_routes`` are
    ignored).  The rows scored are padded to a power of two, so the
    sequences of a sample share their compilations.  Returns a dict of numpy
    arrays and ``finite``."""
    del routes, prompt_routes
    n, m = len(prompt), len(served)
    size = max(pad_to, n + m)
    seq = np.zeros(size, np.int32)
    seq[:n + m] = list(prompt) + list(served)
    rows = np.arange(n - 1, n - 1 + m)
    hidden = hidden_states(weights, seq, cfg, lower)
    padded = np.full(1 << max(m - 1, 0).bit_length(), rows[-1])
    padded[:m] = rows
    own, lse, top, finite = _row_scores(cfg, lower)(
        {key: weights[key] for key in ("dec_head", "dec_norm_scale")},
        hidden[padded], jnp.asarray(seq[np.minimum(padded + 1, size - 1)]))
    return {"logit": np.asarray(own)[:m], "lse": np.asarray(lse)[:m],
            "max": np.asarray(top)[:m],
            "margin": np.full((m, 1), np.inf, np.float32),
            "slack": np.zeros((m, 1), np.float32),
            "finite": bool(finite)}
