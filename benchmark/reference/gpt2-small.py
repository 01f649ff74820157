"""Plain reference of the ``gpt2-small`` configuration: the decoder's forward
pass in float32 ``jax.numpy``, no kernels, no cache, no batching.

It implements what the program states (``DecoderConfig``'s block), which is
GPT-2 (Radford et al. 2019; ``openai-community/gpt2``) with two departures
listed in the configuration file: no bias on the attention and feed-forward
projections, and the exact (erf) GELU where GPT-2 has the tanh form.  Shared
with GPT-2: pre-LayerNorm blocks (eps 1e-5), learned positions, causal
softmax attention scaled by head_dim**-0.5, a 4x feed-forward, a final
LayerNorm, and logits through the transposed token embedding.

Independent of ``paddle_tpu``: it takes the weights by the names the program
gives them and nothing else.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _layer_norm(x, scale, bias, eps=1e-5):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def logits_all_positions(weights, tokens, num_layers: int, num_heads: int):
    """``tokens`` (S,) int32 -> (S, vocab) float32: the next-token logits
    after every position, each attending to itself and what precedes it."""
    with jax.default_matmul_precision("highest"):
        s = tokens.shape[0]
        x = weights["dec_embed"][tokens] + weights["dec_pos_embed"][:s]
        hidden = x.shape[-1]
        d = hidden // num_heads
        causal = jnp.tril(jnp.ones((s, s), bool))
        for i in range(num_layers):
            p = f"dec_l{i}_"
            h = _layer_norm(x, weights[p + "ln1_scale"],
                            weights[p + "ln1_bias"])
            q = (h @ weights[p + "wq"]).reshape(s, num_heads, d)
            k = (h @ weights[p + "wk"]).reshape(s, num_heads, d)
            v = (h @ weights[p + "wv"]).reshape(s, num_heads, d)
            scores = jnp.einsum("qhd,khd->hqk", q, k) * d ** -0.5
            scores = jnp.where(causal[None], scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1)
            att = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, hidden)
            x = x + att @ weights[p + "wo"]
            h = _layer_norm(x, weights[p + "ln2_scale"],
                            weights[p + "ln2_bias"])
            x = x + jax.nn.gelu(h @ weights[p + "w1"],
                                approximate=False) @ weights[p + "w2"]
        x = _layer_norm(x, weights["dec_lnf_scale"], weights["dec_lnf_bias"])
        return x @ weights["dec_embed"].T


def served_token_gaps(weights, prompt, served, num_layers, num_heads,
                      pad_to: int):
    """Teacher-forced: for every served token, how far its reference logit
    lies below the reference maximum at that position (0.0: the reference's
    own argmax), and whether every logit was finite.  One jitted forward pass
    over prompt + served, padded on the right to ``pad_to`` (causal attention
    leaves the real positions untouched)."""
    import numpy as np

    seq = np.zeros(pad_to, np.int32)
    n, m = len(prompt), len(served)
    seq[:n + m] = list(prompt) + list(served)
    gaps, finite = _jitted(num_layers, num_heads)(weights, jnp.asarray(seq))
    return np.asarray(gaps)[n - 1:n - 1 + m], bool(finite)


def _gap_to_next(weights, tokens, num_layers, num_heads):
    """Per position i: max(logits[i]) - logits[i, tokens[i+1]]."""
    logits = logits_all_positions(weights, tokens, num_layers, num_heads)
    nxt = jnp.roll(tokens, -1)
    picked = jnp.take_along_axis(logits, nxt[:, None], axis=-1)[:, 0]
    return jnp.max(logits, axis=-1) - picked, jnp.isfinite(logits).all()


_cache = {}


def _jitted(num_layers, num_heads):
    key = (num_layers, num_heads)
    if key not in _cache:
        _cache[key] = jax.jit(lambda w, t: _gap_to_next(
            w, t, num_layers, num_heads))
    return _cache[key]
