"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference has no long-context machinery (SURVEY.md §2.6: sequence
parallel ❌ absent — its longest-sequence support is LoD ragged batching,
lod_tensor.h:104).  This module is the beyond-parity capability layer the
build plan adds natively (SURVEY.md §7 phase 9): the sequence axis is
sharded over a mesh axis and attention runs either as

* **ring attention** (`ring_attention`): K/V blocks rotate around the
  ring with ``lax.ppermute`` while each device streams
  flash-attention-style softmax accumulation over its local queries —
  memory per device is O(seq/devices), communication rides ICI and
  overlaps with the per-block matmuls.
* **Ulysses** (`ulysses_attention`): two ``lax.all_to_all`` collectives
  re-shard sequence↔heads so every device runs full-sequence attention
  on a head slice — cheaper at moderate sequence lengths when
  heads % devices == 0.

Both are differentiable (scan/ppermute/all_to_all have transpose rules),
so ``jax.grad`` yields the corresponding backward communication schedule.
Layout convention: [batch, seq, heads, head_dim], sequence sharded.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

_NEG_INF = -1e30


def _block_attn_update(q, k, v, m, l, o, scale, qpos, kpos, causal):
    """One streaming-softmax step over a K/V block.

    q: [b, lq, h, d]; k, v: [b, lk, h, d]; m, l: [b, h, lq]; o like q
    (accumulated in [b, lq, h, d]).
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        mask = kpos[None, None, None, :] <= qpos[None, None, :, None]
        s = jnp.where(mask, s, _NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    corr = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = l * corr + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    o_new = o * jnp.transpose(corr, (0, 2, 1))[..., None] + pv
    return m_new, l_new, o_new


def ring_attention(q, k, v, mesh, axis: str = "sp", causal: bool = False,
                   scale: float = None):
    """Exact attention over a sequence sharded on ``axis``.

    q, k, v: [batch, seq, heads, head_dim] global arrays (or host arrays);
    seq must divide by the axis size.  Returns attention output with the
    same global shape, sequence-sharded on ``axis``.
    """
    n_shards = mesh.shape[axis]
    b, seq, h, d = q.shape
    assert seq % n_shards == 0, (seq, n_shards)
    lq = seq // n_shards
    scale = (1.0 / d ** 0.5) if scale is None else scale
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(None, axis, None, None),) * 3,
        out_specs=P(None, axis, None, None),
        check_vma=False,
    )
    def run(ql, kl, vl):
        i = lax.axis_index(axis)
        qpos = i * lq + jnp.arange(lq)
        m0 = jnp.full((b, h, lq), _NEG_INF, ql.dtype)
        l0 = jnp.zeros((b, h, lq), ql.dtype)
        o0 = jnp.zeros_like(ql)

        def body(carry, t):
            kc, vc, m, l, o = carry
            src = (i - t) % n_shards  # which global block kc currently is
            kpos = src * lq + jnp.arange(lq)
            m, l, o = _block_attn_update(ql, kc, vc, m, l, o, scale,
                                         qpos, kpos, causal)
            kc = lax.ppermute(kc, axis, perm)
            vc = lax.ppermute(vc, axis, perm)
            return (kc, vc, m, l, o), None

        (kc, vc, m, l, o), _ = lax.scan(
            body, (kl, vl, m0, l0, o0), jnp.arange(n_shards)
        )
        l = jnp.where(l == 0.0, 1.0, l)
        return o / jnp.transpose(l, (0, 2, 1))[..., None]

    return run(q, k, v)


def ulysses_attention(q, k, v, mesh, axis: str = "sp",
                      causal: bool = False, scale: float = None):
    """All-to-all sequence parallelism (Ulysses): re-shard seq→heads,
    run full attention on a head slice, re-shard back.  Requires
    heads % mesh.shape[axis] == 0."""
    n_shards = mesh.shape[axis]
    b, seq, h, d = q.shape
    assert h % n_shards == 0, (h, n_shards)
    assert seq % n_shards == 0, (seq, n_shards)
    scale = (1.0 / d ** 0.5) if scale is None else scale

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(None, axis, None, None),) * 3,
        out_specs=P(None, axis, None, None),
        check_vma=False,
    )
    def run(ql, kl, vl):
        # [b, seq/s, h, d] -> [b, seq, h/s, d]
        qg = lax.all_to_all(ql, axis, split_axis=2, concat_axis=1, tiled=True)
        kg = lax.all_to_all(kl, axis, split_axis=2, concat_axis=1, tiled=True)
        vg = lax.all_to_all(vl, axis, split_axis=2, concat_axis=1, tiled=True)
        o = _dense_attn(qg, kg, vg, scale, causal)
        # [b, seq, h/s, d] -> [b, seq/s, h, d]
        return lax.all_to_all(o, axis, split_axis=1, concat_axis=2, tiled=True)

    return run(q, k, v)


def _dense_attn(q, k, v, scale, causal):
    """Shared dense attention core (scale → causal mask → softmax → pv)."""
    seq = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        pos = jnp.arange(seq)
        s = jnp.where(pos[None, None, None, :] <= pos[None, None, :, None],
                      s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def reference_attention(q, k, v, causal: bool = False, scale: float = None):
    """Dense single-device oracle for tests/benchmarks."""
    d = q.shape[-1]
    scale = (1.0 / d ** 0.5) if scale is None else scale
    return _dense_attn(q, k, v, scale, causal)
