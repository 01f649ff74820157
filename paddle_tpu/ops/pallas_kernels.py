"""Hand-written Pallas TPU kernels for the hot fused ops.

The reference ships hand-fused CUDA kernels for exactly these spots
(reference: paddle/fluid/operators/fused/multihead_matmul_op.cu,
fused/fused_bn_activation_op.cu, operators/math/bert_encoder_functor.cu);
on TPU the only ones XLA does not already fuse well are the
memory-bound attention inner loop, so we implement flash attention
(forward + backward) as Pallas kernels and let XLA handle the rest.

Kernel design (see /opt/skills/guides/pallas_guide.md):
* Q/K/V laid out ``(batch, heads, seq, head_dim)``; grid is
  ``(b, h, q_blocks, kv_blocks)`` with the kv axis innermost so the TPU's
  sequential grid walk accumulates the online softmax in VMEM scratch.
* Row statistics (running max / sum) are kept lane-broadcast at width
  128 (the TPU lane count) so every store is tile-aligned.
* head_dim is passed through un-padded: Mosaic accepts a block whose
  last dim equals the full array dim (it pads lanes internally), and
  measurement showed explicit zero-padding to 128 buys nothing.
  head_dim must be a multiple of 8 (sublane) — anything else falls back.
* The backward pass recomputes S = QK^T per block from the saved
  log-sum-exp (the flash-attention trick), with separate kernels for
  dQ (kv innermost) and dK/dV (q innermost).

CPU fallback: a numerically identical jnp composition (used under
``interpret``-less CPU execution and as the test oracle).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..framework.place import is_compiled_with_tpu

LANES = 128
DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


def _interpret() -> bool:
    """Run kernels in interpreter mode (CPU testing of the real kernel)."""
    return os.environ.get("PT_PALLAS_INTERPRET", "0") == "1"


def _use_pallas() -> bool:
    return _interpret() or is_compiled_with_tpu()


def own_jit(call):
    """A kernel's jitted wrapper, which the layers of a program share (a
    kernel's trace is Python time a shape and layer otherwise); interpreted
    (tests, a rehearsal) the plain function, traced into its caller: a jit
    of its own hides the kernel's name from the lowered text, where a
    rehearsal looks for it."""
    return call.__wrapped__ if _interpret() else call


def _pick_block(seq: int, candidates=(512, 256, 128)) -> int | None:
    env = os.environ.get("PT_FLASH_BLOCK")
    if env:
        # tuning knob: accept only a supported block (>=128, the kernel's
        # lane-broadcast row-stat width); anything else falls through to
        # the default ladder instead of handing Mosaic a bad BlockSpec
        try:
            b = int(env)
        except ValueError:
            b = 0
        if b >= 128 and seq % b == 0:
            return b
    for c in candidates:
        if seq % c == 0:
            return c
    return None


# ==========================================================================
# Reference (jnp) implementation — the oracle and the fallback
# ==========================================================================
def attention_reference(q, k, v, bias=None, causal=False, scale=1.0,
                        dropout_rate=0.0, dropout_seed=None):
    """Dense attention: the flash kernel's oracle AND the general-bias
    fallback.  bias: additive — padding shapes ((b,kv), (b,1,kv),
    (b,1,1,kv)) or a full attention matrix broadcastable to
    (b, h, q, kv).  dropout_rate applies upscale-in-train probs dropout
    (note: the mask stream differs from the Pallas kernel's — dropout is
    stochastic, only the distribution is contractual)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        if is_padding_bias(bias):
            b2 = _normalize_bias(bias)
            s = s + b2[:, None, None, :].astype(s.dtype)
        else:
            s = s + bias.astype(s.dtype)  # (b,1,q,kv) / (b,h,q,kv)
    if causal:
        qlen, klen = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((qlen, klen), bool))
        s = jnp.where(mask, s, DEFAULT_MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    if dropout_rate > 0.0:
        key = jax.random.key(
            jnp.asarray(dropout_seed, jnp.float32).reshape(()).astype(
                jnp.int32))
        keep = jax.random.bernoulli(key, 1.0 - dropout_rate, p.shape)
        p = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - dropout_rate))
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def is_padding_bias(bias) -> bool:
    """True for the per-key padding shapes the flash kernel handles."""
    if bias.ndim == 2:
        return True
    if bias.ndim == 3 and bias.shape[1] == 1:
        return True
    if bias.ndim == 4 and bias.shape[1] == 1 and bias.shape[2] == 1:
        return True
    return False


def _normalize_bias(bias):
    """Accept (b, kv), (b,1,1,kv) or (b,1,kv); return (b, kv)."""
    if bias.ndim == 2:
        return bias
    if bias.ndim == 4 and bias.shape[1] == 1 and bias.shape[2] == 1:
        return bias[:, 0, 0, :]
    if bias.ndim == 3 and bias.shape[1] == 1:
        return bias[:, 0, :]
    raise ValueError(f"unsupported attention bias shape {bias.shape}")


# ==========================================================================
# Forward kernel
# ==========================================================================
def _dropout_keep(seed_ref, shape, rate, iq, ik, n_q, n_kv):
    """Deterministic per-block keep mask: the PRNG is seeded from
    (step seed, flattened (batch, head, q-block, kv-block) index), so
    the backward kernels regenerate the exact forward mask from the same
    coordinates — nothing is stored (the flash-attention treatment of
    attention-probs dropout).  Mosaic supports at most two seed values,
    hence the flat block index."""
    flat = ((pl.program_id(0) * pl.num_programs(1) + pl.program_id(1))
            * n_q + iq) * n_kv + ik
    pltpu.prng_seed(seed_ref[0].astype(jnp.int32), flat)
    bits = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    thresh = jnp.uint32(min(int(rate * (2 ** 32)), 2 ** 32 - 1))
    return bits >= thresh


def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, seed_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, block_q, block_k,
                n_kv, dropout_rate=0.0):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    q = q_ref[0, 0]                                   # (bq, d)
    k = k_ref[0, 0]                                   # (bk, d)
    v = v_ref[0, 0]
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    if bias_ref is not None:
        s = s + bias_ref[0].astype(jnp.float32)       # (1, bk) broadcasts
    if causal:
        qi = pl.program_id(2)
        rows = qi * block_q + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = ki * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(rows >= cols, s, DEFAULT_MASK_VALUE)

    m_prev = m_scr[...]                               # (bq, 128) lane-bcast
    l_prev = l_scr[...]
    m_cur = jnp.max(s, axis=1, keepdims=True)         # (bq, 1)
    m_next = jnp.maximum(m_prev, m_cur)               # (bq, 128)
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.exp(s - m_next[:, :1])                    # (bq, bk)
    # softmax normalization uses the UNDROPPED p (dropout applies after
    # softmax); only the value accumulation sees the mask
    l_next = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    if dropout_rate > 0.0:
        keep = _dropout_keep(seed_ref, p.shape, dropout_rate,
                             pl.program_id(2), ki, pl.num_programs(2), n_kv)
        pd = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - dropout_rate))
    else:
        pd = p
    acc_scr[...] = acc_scr[...] * alpha[:, :1] + lax.dot_general(
        pd.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_next
    l_scr[...] = l_next

    @pl.when(ki == n_kv - 1)
    def _done():
        l_fin = l_scr[...]
        l_safe = jnp.where(l_fin == 0.0, 1.0, l_fin)
        o_ref[0, 0] = (acc_scr[...] / l_safe[:, :1]).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[...] + jnp.log(l_safe)


def _fwd_single_block_kernel(q_ref, k_ref, v_ref, bias_ref, seed_ref,
                             o_ref, lse_ref, *, scale, causal,
                             dropout_rate=0.0):
    """Single-block forward (nq == nk == 1): the whole softmax row is in
    VMEM, so the online-softmax scratch accumulation (m/l/acc updates +
    @pl.when epilogues) reduces to one direct softmax."""
    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    if bias_ref is not None:
        s = s + bias_ref[0].astype(jnp.float32)
    if causal:
        rows = lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(rows >= cols, s, DEFAULT_MASK_VALUE)
    m = jnp.max(s, axis=1, keepdims=True)              # (bq, 1)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=1, keepdims=True)
    if dropout_rate > 0.0:
        keep = _dropout_keep(seed_ref, p.shape, dropout_rate, 0, 0, 1, 1)
        pd = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - dropout_rate))
    else:
        pd = p
    l_safe = jnp.where(l == 0.0, 1.0, l)
    acc = lax.dot_general(pd.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32)
    o_ref[0, 0] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[0, 0] = jnp.broadcast_to(m + jnp.log(l_safe), lse_ref.shape[2:])


def _wrap_optional(body, n_lead, has_bias, has_seed):
    """Adapter: positional refs -> body(..., bias_ref/seed_ref or None).
    Keeps the kernel bodies single-sourced across the 4 bias x dropout
    variants."""

    def kernel(*refs):
        i = n_lead
        lead = list(refs[:n_lead])
        bias_ref = refs[i] if has_bias else None
        i += 1 if has_bias else 0
        seed_ref = refs[i] if has_seed else None
        i += 1 if has_seed else 0
        body(*lead, bias_ref, seed_ref, *refs[i:])

    return kernel


def _seed_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _flash_fwd(q, k, v, bias, scale, causal, block_q, block_k,
               dropout_rate=0.0, seed=None):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    nq, nk = sq // block_q, sk // block_k
    if nq == 1 and nk == 1 and os.environ.get("PT_FLASH_FUSED_BWD",
                                              "1") != "0":
        # single-block: direct softmax, no online-softmax scratch (the
        # same gate as the fused backward so one env var A/Bs both)
        def _blk(ib, ih):
            return (ib, ih, 0, 0)

        in_specs = [
            pl.BlockSpec((1, 1, block_q, d), _blk),
            pl.BlockSpec((1, 1, block_k, d), _blk),
            pl.BlockSpec((1, 1, block_k, d), _blk),
        ]
        args = [q, k, v]
        if bias is not None:
            in_specs.append(pl.BlockSpec((1, 1, block_k),
                                         lambda ib, ih: (ib, 0, 0)))
            args.append(bias[:, None, :])
        if dropout_rate > 0.0:
            in_specs.append(_seed_spec())
            args.append(seed)
        return pl.pallas_call(
            _wrap_optional(
                functools.partial(_fwd_single_block_kernel, scale=scale,
                                  causal=causal,
                                  dropout_rate=dropout_rate),
                3, bias is not None, dropout_rate > 0.0),
            name="flash_fwd_single",
            grid=(b, h),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, 1, block_q, d), _blk),
                pl.BlockSpec((1, 1, block_q, LANES), _blk),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
                jax.ShapeDtypeStruct((b, h, sq, LANES), jnp.float32),
            ],
            interpret=_interpret(),
        )(*args)
    grid = (b, h, nq, nk)

    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        pl.BlockSpec((1, 1, block_k, d), lambda ib, ih, iq, ik: (ib, ih, ik, 0)),
        pl.BlockSpec((1, 1, block_k, d), lambda ib, ih, iq, ik: (ib, ih, ik, 0)),
    ]
    args = [q, k, v]
    if bias is not None:
        in_specs.append(
            pl.BlockSpec((1, 1, block_k),
                         lambda ib, ih, iq, ik: (ib, 0, ik)))
        args.append(bias[:, None, :])
    if dropout_rate > 0.0:
        in_specs.append(_seed_spec())
        args.append(seed)
    kernel = _wrap_optional(
        functools.partial(_fwd_kernel_body, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, n_kv=nk,
                          dropout_rate=dropout_rate),
        3, bias is not None, dropout_rate > 0.0)
    out, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, block_q, LANES),
                         lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=_interpret(),
    )(*args)
    return out, lse


def _fwd_kernel_body(q_ref, k_ref, v_ref, bias_ref, seed_ref, o_ref, lse_ref,
                     m_scr, l_scr, acc_scr, **kw):
    _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, seed_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, **kw)


# ==========================================================================
# Backward kernels
# ==========================================================================
def _bwd_softmax_terms(q, k, v, do, lse, delta, bias_ref, seed_ref, *,
                       scale, causal, row0, col0, drop_coords,
                       dropout_rate):
    """Shared backward math: recompute S from the saved lse, regenerate
    the dropout mask, and return (pd, ds) — the two matrices every
    backward kernel contracts from.  drop_coords = (iq, ik, n_q, n_kv)
    in FORWARD block coordinates (the mask stream contract)."""
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    if bias_ref is not None:
        s = s + bias_ref[0].astype(jnp.float32)
    if causal:
        rows = row0 + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = col0 + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(rows >= cols, s, DEFAULT_MASK_VALUE)
    p = jnp.exp(s - lse[:, :1])
    dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)
    if dropout_rate > 0.0:
        # dS = P*(M*dPD/keep - delta): delta = rowsum(dO*O) is already
        # the dropped-path rowsum (O = PD@V), so only dp needs the mask
        iq, ik, n_q, n_kv = drop_coords
        keep = _dropout_keep(seed_ref, p.shape, dropout_rate, iq, ik,
                             n_q, n_kv)
        inv = 1.0 / (1.0 - dropout_rate)
        pd = jnp.where(keep, p, 0.0) * inv
        dp = jnp.where(keep, dp, 0.0) * inv
    else:
        pd = p
    ds = p * (dp - delta[:, :1]) * scale
    return pd, ds


def _bwd_dq_kernel(q_ref, k_ref, do_ref, lse_ref, delta_ref, bias_ref,
                   seed_ref, v_ref, dq_ref, dq_scr, *, scale, causal,
                   block_q, block_k, n_kv, dropout_rate=0.0):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    q = q_ref[0, 0]
    k = k_ref[0, 0]
    qi = pl.program_id(2)
    _, ds = _bwd_softmax_terms(
        q, k, v_ref[0, 0], do_ref[0, 0], lse_ref[0, 0], delta_ref[0, 0],
        bias_ref, seed_ref, scale=scale, causal=causal,
        row0=qi * block_q, col0=ki * block_k,
        drop_coords=(qi, ki, pl.num_programs(2), n_kv),
        dropout_rate=dropout_rate)
    dq_scr[...] += lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ki == n_kv - 1)
    def _done():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    bias_ref, seed_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                    scale, causal, block_q, block_k, n_q, dropout_rate=0.0):
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    q = q_ref[0, 0]                                   # (bq, d)
    do = do_ref[0, 0]
    ik = pl.program_id(2)
    # seed coordinates MUST be (seed, b, h, q-block, kv-block) — the
    # same order as the forward, though this grid iterates kv outer
    pd, ds = _bwd_softmax_terms(
        q, k_ref[0, 0], v_ref[0, 0], do, lse_ref[0, 0], delta_ref[0, 0],
        bias_ref, seed_ref, scale=scale, causal=causal,
        row0=qi * block_q, col0=ik * block_k,
        drop_coords=(qi, ik, n_q, pl.num_programs(2)),
        dropout_rate=dropout_rate)
    # dV += PD^T dO   (contract over bq)
    dv_scr[...] += lax.dot_general(
        pd.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    # dK += dS^T Q   (contract over bq)
    dk_scr[...] += lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(qi == n_q - 1)
    def _done():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      bias_ref, seed_ref, dq_ref, dk_ref, dv_ref, *,
                      scale, causal, dropout_rate=0.0):
    """Single-block backward (nq == nk == 1): S is computed ONCE and all
    three grads come out of the same invocation — the two-kernel split
    exists only because multi-block dq wants kv-innermost accumulation
    while dk/dv want q-innermost; with one block per axis there is
    nothing to accumulate.  Saves 2 of the 7 backward matmuls and a
    second read of q/k/v/do/lse/delta (measured on v5e: the dominant
    seq-512 BERT shape)."""
    q = q_ref[0, 0]
    k = k_ref[0, 0]
    do = do_ref[0, 0]
    pd, ds = _bwd_softmax_terms(
        q, k, v_ref[0, 0], do, lse_ref[0, 0], delta_ref[0, 0],
        bias_ref, seed_ref, scale=scale, causal=causal, row0=0, col0=0,
        drop_coords=(0, 0, 1, 1), dropout_rate=dropout_rate)
    dq_ref[0, 0] = lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dq_ref.dtype)
    dv_ref[0, 0] = lax.dot_general(
        pd.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dv_ref.dtype)
    dk_ref[0, 0] = lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dk_ref.dtype)


def _flash_bwd_fused(q, k, v, bias, lse, do, delta, scale, causal,
                     block_q, block_k, dropout_rate, seed):
    b, h = q.shape[0], q.shape[1]
    d = q.shape[3]
    has_drop = dropout_rate > 0.0

    def _q_idx(ib, ih):
        return (ib, ih, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), _q_idx),       # q
        pl.BlockSpec((1, 1, block_k, d), _q_idx),       # k
        pl.BlockSpec((1, 1, block_k, d), _q_idx),       # v
        pl.BlockSpec((1, 1, block_q, d), _q_idx),       # do
        pl.BlockSpec((1, 1, block_q, LANES), _q_idx),   # lse
        pl.BlockSpec((1, 1, block_q, LANES), _q_idx),   # delta
    ]
    args = [q, k, v, do, lse, delta]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, 1, block_k),
                                     lambda ib, ih: (ib, 0, 0)))
        args.append(bias[:, None, :])
    if has_drop:
        in_specs.append(_seed_spec())
        args.append(seed)
    return pl.pallas_call(
        _wrap_optional(
            functools.partial(_bwd_fused_kernel, scale=scale, causal=causal,
                              dropout_rate=dropout_rate),
            6, bias is not None, has_drop),
        name="flash_bwd_fused",
        grid=(b, h),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), _q_idx),
            pl.BlockSpec((1, 1, block_k, d), _q_idx),
            pl.BlockSpec((1, 1, block_k, d), _q_idx),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        interpret=_interpret(),
    )(*args)


def _flash_bwd(q, k, v, bias, o, lse, do, scale, causal, block_q, block_k,
               dropout_rate=0.0, seed=None):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    nq, nk = sq // block_q, sk // block_k
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (b, h, sq, LANES))
    has_drop = dropout_rate > 0.0

    if nq == 1 and nk == 1 and os.environ.get("PT_FLASH_FUSED_BWD",
                                              "1") != "0":
        return _flash_bwd_fused(q, k, v, bias, lse, do, delta, scale,
                                causal, block_q, block_k, dropout_rate,
                                seed)

    # --- dQ: grid (b, h, nq, nk), kv innermost ---------------------------
    def _q_idx(ib, ih, iq, ik):
        return (ib, ih, iq, 0)

    def _kv_idx(ib, ih, iq, ik):
        return (ib, ih, ik, 0)

    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), _q_idx),       # q
        pl.BlockSpec((1, 1, block_k, d), _kv_idx),      # k
        pl.BlockSpec((1, 1, block_q, d), _q_idx),       # do
        pl.BlockSpec((1, 1, block_q, LANES), _q_idx),   # lse
        pl.BlockSpec((1, 1, block_q, LANES), _q_idx),   # delta
    ]
    args = [q, k, do, lse, delta]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, 1, block_k),
                                     lambda ib, ih, iq, ik: (ib, 0, ik)))
        args.append(bias[:, None, :])
    if has_drop:
        in_specs.append(_seed_spec())
        args.append(seed)
    in_specs.append(pl.BlockSpec((1, 1, block_k, d), _kv_idx))  # v
    args.append(v)
    dq = pl.pallas_call(
        _wrap_optional(
            functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                              block_q=block_q, block_k=block_k, n_kv=nk,
                              dropout_rate=dropout_rate),
            5, bias is not None, has_drop),
        name="flash_bwd_dq",
        grid=(b, h, nq, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, block_q, d), _q_idx),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=_interpret(),
    )(*args)

    # --- dK/dV: grid (b, h, nk, nq), q innermost -------------------------
    def _q_idx2(ib, ih, ik, iq):
        return (ib, ih, iq, 0)

    def _kv_idx2(ib, ih, ik, iq):
        return (ib, ih, ik, 0)

    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), _q_idx2),      # q
        pl.BlockSpec((1, 1, block_k, d), _kv_idx2),     # k
        pl.BlockSpec((1, 1, block_k, d), _kv_idx2),     # v
        pl.BlockSpec((1, 1, block_q, d), _q_idx2),      # do
        pl.BlockSpec((1, 1, block_q, LANES), _q_idx2),  # lse
        pl.BlockSpec((1, 1, block_q, LANES), _q_idx2),  # delta
    ]
    args = [q, k, v, do, lse, delta]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, 1, block_k),
                                     lambda ib, ih, ik, iq: (ib, 0, ik)))
        args.append(bias[:, None, :])
    if has_drop:
        in_specs.append(_seed_spec())
        args.append(seed)
    dk, dv = pl.pallas_call(
        _wrap_optional(
            functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                              block_q=block_q, block_k=block_k, n_q=nq,
                              dropout_rate=dropout_rate),
            6, bias is not None, has_drop),
        name="flash_bwd_dkv",
        grid=(b, h, nk, nq),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d), _kv_idx2),
            pl.BlockSpec((1, 1, block_k, d), _kv_idx2),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=_interpret(),
    )(*args)
    return dq, dk, dv


# ==========================================================================
# custom_vjp wrapper
# ==========================================================================
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_attention_core(q, k, v, bias, seed, scale, causal, block_q,
                          block_k, dropout_rate):
    out, _ = _flash_fwd(q, k, v, bias, scale, causal, block_q, block_k,
                        dropout_rate, seed)
    return out


def _flash_core_fwd(q, k, v, bias, seed, scale, causal, block_q, block_k,
                    dropout_rate):
    out, lse = _flash_fwd(q, k, v, bias, scale, causal, block_q, block_k,
                          dropout_rate, seed)
    return out, (q, k, v, bias, seed, out, lse)


def _flash_core_bwd(scale, causal, block_q, block_k, dropout_rate, res, do):
    q, k, v, bias, seed, out, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, bias, out, lse, do, scale, causal,
                            block_q, block_k, dropout_rate, seed)
    # The bias is a padding mask, treated as a CONSTANT: computing its true
    # gradient would require materializing dense (b,h,sq,sk) dS tensors,
    # defeating the flash kernel's memory savings on every masked step.
    # A trainable attention bias must use the unfused composition.
    dbias = None if bias is None else jnp.zeros_like(bias)
    dseed = None if seed is None else jnp.zeros_like(seed)
    return dq, dk, dv, dbias, dseed


_flash_attention_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention(q, k, v, bias=None, causal=False, scale=None,
                    dropout_rate=0.0, dropout_seed=None):
    """Fused scaled-dot-product attention.

    q/k/v: (batch, heads, seq, head_dim); bias: additive padding mask,
    shape (b, kv_seq) / (b,1,1,kv_seq), or None.  Uses the Pallas flash
    kernel on TPU when it wins (measured crossover ~1024 on v5e without
    dropout; WITH attention-probs dropout the naive composition pays
    extra full score-matrix passes, so the kernel engages from 512);
    falls back to the jnp composition elsewhere.  PT_FLASH_ATTENTION=1
    forces the kernel, =0 disables it.

    dropout_rate > 0 applies upscale-in-train dropout to the attention
    probabilities INSIDE the kernel: masks are regenerated in the
    backward from (dropout_seed, block coordinates), nothing is stored.
    dropout_seed: f32 scalar array (traced; one per step).

    On the kernel path the bias receives a zero gradient (it is a
    padding mask, not a parameter); the fallback path differentiates it
    normally.
    """
    scale, bias, seed, blocks = _flash_prologue(
        q, k, bias, scale, dropout_rate, dropout_seed)
    if blocks is None:
        return attention_reference(q, k, v, bias, causal, scale,
                                   dropout_rate=dropout_rate,
                                   dropout_seed=dropout_seed)
    return _flash_attention_core(q, k, v, bias, seed, scale, causal,
                                 blocks[0], blocks[1], float(dropout_rate))


def _flash_engage(sq, sk, d, dropout_rate):
    """Path selection shared by flash_attention and the residual API:
    (block_q, block_k) when the Pallas kernel engages, else None."""
    block_q = _pick_block(sq)
    block_k = _pick_block(sk)
    force = os.environ.get("PT_FLASH_ATTENTION")
    if force is not None:
        worth_it = force == "1"
    elif dropout_rate > 0.0:
        worth_it = sq >= 512
    else:
        worth_it = sq >= 1024
    if (not _use_pallas() or block_q is None or block_k is None
            or not worth_it or d % 8 != 0):
        return None
    return block_q, block_k


def _flash_prologue(q, k, bias, scale, dropout_rate, dropout_seed):
    """The shared entry normalization for every flash front-end
    (flash_attention / fwd_res / bwd_res): default scale, padding-bias
    normalization, dropout-seed validation+reshape, engage decision.
    Returns (scale, bias, seed, blocks-or-None)."""
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if bias is not None:
        bias = _normalize_bias(bias)
    if dropout_rate > 0.0 and dropout_seed is None:
        raise ValueError("flash_attention dropout requires dropout_seed")
    seed = None
    if dropout_rate > 0.0:
        seed = jnp.asarray(dropout_seed, jnp.float32).reshape((1,))
    blocks = _flash_engage(q.shape[2], k.shape[2], d, dropout_rate)
    return scale, bias, seed, blocks


def flash_attention_fwd_res(q, k, v, bias=None, causal=False, scale=None,
                            dropout_rate=0.0, dropout_seed=None):
    """Forward that RETURNS the (out, lse) residual pair so a framework
    tape can hand lse back to flash_attention_bwd_res and skip the
    forward replay jax.vjp would do (the custom_vjp path reruns the fwd
    kernel inside the backward to rebuild residuals — one whole extra
    fwd flash pass per step).  Returns (out, None) when the kernel does
    not engage; the caller must then differentiate the fallback
    composition instead."""
    scale, bias, seed, blocks = _flash_prologue(
        q, k, bias, scale, dropout_rate, dropout_seed)
    if blocks is None:
        return attention_reference(q, k, v, bias, causal, scale,
                                   dropout_rate=dropout_rate,
                                   dropout_seed=dropout_seed), None
    out, lse = _flash_fwd(q, k, v, bias, scale, causal, blocks[0], blocks[1],
                          dropout_rate, seed)
    return out, lse


def flash_attention_bwd_res(q, k, v, out, lse, do, bias=None, causal=False,
                            scale=None, dropout_rate=0.0, dropout_seed=None):
    """Backward from saved residuals (see flash_attention_fwd_res).
    Returns (dq, dk, dv); the padding bias is a constant, as in the
    custom_vjp path."""
    scale, bias, seed, blocks = _flash_prologue(
        q, k, bias, scale, dropout_rate, dropout_seed)
    if blocks is None:
        raise ValueError("flash_attention_bwd_res: kernel path does not "
                         "engage for these shapes — the forward cannot "
                         "have produced an lse residual")
    return _flash_bwd(q, k, v, bias, out, lse, do, scale, causal,
                      blocks[0], blocks[1], dropout_rate, seed)


# ==========================================================================
# Ragged paged attention (decode) — the serving-runtime kernel
# ==========================================================================
# KV pools are LOGICALLY ``(kv_heads, num_pages, page_size, head_dim)``:
# head-major so each (seq, head, page) grid step reads one contiguous
# page, page-granular so the serving allocator (inference/kv_cache.py)
# can hand pages to sequences in any order.  They are STORED with rows
# that fill the 128 lanes wherever head_dim leaves lanes empty
# (``KVCacheConfig.pool_shape``): ``(kv_heads, num_pages, page_size *
# head_dim / 128, 128)``, ``t = 128 / head_dim`` consecutive tokens of a
# page side by side in a row — a row-major bitcast of the logical pool,
# and the shape the chip's compiler holds row-major in exact (8, 128)
# tiles by its own choice (a ``head_dim``-64 pool in the logical shape
# it holds page-minor, and every kernel that wants pages costs a
# re-layout of the whole pool).  Every function here takes the stored
# pool and reads ``t`` from the shapes: the pool's last axis over
# head_dim, which ``q`` (the append: the rows) carries.  ``t = 1`` is the
# logical shape itself.
# Each decode query attends at its TRUE length: the grid walks only
# ``block_tables.shape[1]`` pages (the scheduler buckets that to the
# longest ACTIVE sequence, never the model max), whole pages past
# ``context_lens[b]`` are skipped before their tiles are touched, and
# the tail page masks per-token — mixed-length batches never pad to
# max-seq (Ragged Paged Attention, arXiv 2604.15464).


def _pool_tiles_ok(d: int, page_rows: int, width: int) -> bool:
    """Whether the pool kernels can address a stored pool's pages: a
    page's rows whole sublane groups of 8, and head_dim a multiple of 8
    unless the pool is lane-full (there it only has to divide the
    lanes, which being stored that way says)."""
    return page_rows % 8 == 0 and (d % 8 == 0 or width != d)


def _in_lane_group(shape, lo, d: int):
    """Mask over ``shape``: the lanes ``[lo, lo + d)`` of the last axis,
    one token's place in a lane-full row (``lo``: a scalar, or an array
    that broadcasts against ``shape``)."""
    lane = lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    return (lane >= lo) & (lane < lo + d)


def _gqa_group(n_heads: int, n_kv: int) -> int:
    """Query-per-KV-head group size, validated: a silent floor division
    here would read the wrong KV head for every query past the first
    group.  Under tensor parallelism both counts arrive already divided
    by the degree (the pool shards on its kv_heads dim), so the LOCAL
    counts must still divide — the engine guards ``num_heads % tp`` at
    construction, and this catches a mismatched pool handed in
    directly."""
    if n_kv <= 0 or n_heads % n_kv:
        raise ValueError(
            f"paged_attention: q_heads={n_heads} is not a positive "
            f"multiple of kv_heads={n_kv} (GQA grouping; with "
            f"tensor-parallel serving both are per-device LOCAL counts "
            f"— pick a tp that divides both)")
    return n_heads // n_kv


def paged_attention_reference(q, k_pages, v_pages, block_tables,
                              context_lens, scale=None,
                              k_scale=None, v_scale=None):
    """Dense gather oracle AND the CPU fallback — exactly the kernel's
    semantics, so tier-1 exercises the same op contract.

    q: (num_seqs, q_heads, head_dim) — one decode token per sequence.
    k_pages/v_pages: (kv_heads, num_pages, page_size, head_dim) pools,
    or their lane-full stored form (section comment): the gathered pages
    are read back as (tokens, head_dim) rows either way, a row-major
    reshape of the gather's result and never of a pool.
    block_tables: (num_seqs, pages_per_seq) int32 — pool page ids, in
    sequence order; entries past the sequence's last page must hold any
    valid page id (the scheduler pads with 0) — they are masked out.
    context_lens: (num_seqs,) int32 true lengths (including the current
    token, whose K/V must already be in the pool).
    GQA: q_heads must be a multiple of kv_heads; query head h reads kv
    head ``h // (q_heads // kv_heads)``.
    k_scale/v_scale: optional (kv_heads, num_pages) f32 per-page absmax
    scales for int8 pools — pages dequantize as ``q * scale / 127``
    right after the gather, and attention runs in f32 from there.  A
    bf16 pool (no scales) casts to f32 after the gather instead, so
    every quantized dtype accumulates attention in full precision; the
    f32 path is untouched (the cast is a trace-time no-op).
    """
    n_seqs, n_heads, d = q.shape
    n_kv = k_pages.shape[0]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    group = _gqa_group(n_heads, n_kv)
    flat = block_tables.reshape(-1)
    # (kv_heads, seqs*pages, rows, width) — sized by the BUCKETED table
    # width (longest active sequence), not the model max
    k = jnp.take(k_pages, flat, axis=1)
    v = jnp.take(v_pages, flat, axis=1)
    if k_scale is not None:
        ks = jnp.take(k_scale, flat, axis=1)[..., None, None]
        vs = jnp.take(v_scale, flat, axis=1)[..., None, None]
        k = k.astype(jnp.float32) * ks / 127.0
        v = v.astype(jnp.float32) * vs / 127.0
    elif k.dtype != jnp.float32:
        k = k.astype(jnp.float32)
        v = v.astype(jnp.float32)
    k = k.reshape(n_kv, n_seqs, -1, d)
    v = v.reshape(n_kv, n_seqs, -1, d)
    k = jnp.repeat(k, group, axis=0).transpose(1, 0, 2, 3)
    v = jnp.repeat(v, group, axis=0).transpose(1, 0, 2, 3)
    s = jnp.einsum("bhd,bhkd->bhk", q, k,
                   preferred_element_type=jnp.float32) * scale
    pos = lax.broadcasted_iota(jnp.int32, (n_seqs, 1, s.shape[-1]), 2)
    s = jnp.where(pos < context_lens[:, None, None], s, DEFAULT_MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhk,bhkd->bhd", p.astype(v.dtype), v).astype(q.dtype)


def _paged_decode_kernel(*refs, scale, page_size, n_pages, group, quant,
                         d):
    """One (seq, head, page) step of the ragged decode walk: online
    softmax over the page's K/V tile, accumulated in VMEM scratch
    exactly like the flash kernel's kv walk.

    The tile is the page as the pool stores it, ``(rows, width)`` with
    ``t = width // d`` tokens side by side in a row (``t = 1``: the
    logical ``(page_size, d)``).  ``q`` arrives as ``(t, width)``, the
    query in lane group ``g`` of row ``g`` and zero elsewhere, so ONE
    contraction over the lanes gives the ``(t, rows)`` scores of the
    tile: entry ``(g, r)`` is the token at offset ``r * t + g``.  Each
    lane group is its own softmax stream down the walk — row ``g`` of
    the ``m``/``l``/``acc`` scratch — so a step reduces along the lanes
    only, as the ``t = 1`` walk does.  ``p @ v`` is ``(t, width)``, of
    which row ``g`` is right in lane group ``g`` alone (elsewhere it
    pairs a token's weight with its neighbours' values: finite, carried
    along, never read).  The last step joins the streams by their
    maxima and keeps each row's own lane group; the wrapper folds the
    ``t`` groups of the ``(1, width)`` result into ``d`` lanes.

    ``quant`` (static): two extra scalar-prefetch refs carry the
    per-(kv_head, page) int8 absmax scales; the page's K/V tiles
    dequantize to f32 (``q * scale / 127``) INSIDE the loop — HBM
    traffic stays int8, both dots accumulate in f32.  A bf16 pool (no
    scales) casts its tiles to f32 the same way."""
    if quant:
        (bt_ref, cl_ref, ks_ref, vs_ref, q_ref, k_ref, v_ref, o_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        (bt_ref, cl_ref, q_ref, k_ref, v_ref, o_ref,
         m_scr, l_scr, acc_scr) = refs
    t = k_ref.shape[-1] // d
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    b_idx = pl.program_id(0)
    ctx = cl_ref[b_idx]
    start = i * page_size
    if quant:
        page = bt_ref[b_idx, i]
        h_kv = pl.program_id(1) // group
        k_deq = ks_ref[h_kv, page] / 127.0
        v_deq = vs_ref[h_kv, page] / 127.0

    @pl.when(start < ctx)
    def _page():
        q = q_ref[0, 0]                                # (t, width)
        k = k_ref[0, 0]                                # (rows, width)
        v = v_ref[0, 0]
        if quant:
            k = k.astype(jnp.float32) * k_deq
            v = v.astype(jnp.float32) * v_deq
        elif k_ref.dtype != jnp.float32:
            k = k.astype(jnp.float32)
            v = v.astype(jnp.float32)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        pos = start + lax.broadcasted_iota(jnp.int32, s.shape, 1) * t
        if t > 1:
            pos += lax.broadcasted_iota(jnp.int32, s.shape, 0)
        # a stream whose tokens so far are all past the context carries
        # the mask value as its maximum: finite, and its weight at the
        # join is exp(mask - a true score) = 0
        s = jnp.where(pos < ctx, s, DEFAULT_MASK_VALUE)
        m_prev = m_scr[...]                            # (t, 128) lane-bcast
        l_prev = l_scr[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next[:, :1])
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_next

    @pl.when(i == n_pages - 1)
    def _done():
        l_fin, acc = l_scr[...], acc_scr[...]
        if t > 1:
            m = m_scr[...]
            # -inf only where no page was walked (a padded row of the
            # batch, context 0): weight 0, so the row reads 0 as at t = 1
            w = jnp.where(m == -jnp.inf, 0.0,
                          jnp.exp(m - jnp.max(m, axis=0, keepdims=True)))
            l_fin = jnp.sum(w * l_fin, axis=0, keepdims=True)
            own = _in_lane_group(
                acc.shape, lax.broadcasted_iota(jnp.int32, acc.shape, 0) * d,
                d)
            acc = jnp.sum(jnp.where(own, acc * w, 0.0), axis=0,
                          keepdims=True)
        l_safe = jnp.where(l_fin == 0.0, 1.0, l_fin)
        o_ref[0, 0] = (acc / l_safe[:, :1]).astype(o_ref.dtype)


def _paged_decode_call(q, k_pages, v_pages, block_tables, context_lens,
                       scale, k_scale=None, v_scale=None):
    n_seqs, n_heads, d = q.shape
    n_kv, _, rows, width = k_pages.shape
    t = width // d                  # tokens a stored row (section comment)
    group = _gqa_group(n_heads, n_kv)
    n_pages = block_tables.shape[1]
    quant = k_scale is not None

    def _q_idx(b, h, i, bt, cl, *_):
        return (b, h, 0, 0)

    def _kv_idx(b, h, i, bt, cl, *_):
        # the page to stream is data-dependent: the block table is a
        # scalar-prefetch arg, so the index map reads it before the body
        return (h // group, bt[b, i], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        # the int8 scale pools ride as scalar-prefetch args too — tiny
        # (kv_heads, num_pages) f32 tables indexed per (head, page)
        num_scalar_prefetch=4 if quant else 2,
        grid=(n_seqs, n_heads, n_pages),
        # q/out ride as (seqs, heads, t | 1, width): Mosaic wants a
        # block's last two dims (8, 128)-aligned or equal to the
        # array's, and a (1, width) block of a (seqs, heads, width)
        # array is neither
        in_specs=[
            pl.BlockSpec((1, 1, t, width), _q_idx),
            pl.BlockSpec((1, 1, rows, width), _kv_idx),
            pl.BlockSpec((1, 1, rows, width), _kv_idx),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, width), _q_idx),
        scratch_shapes=[
            pltpu.VMEM((t, LANES), jnp.float32),
            pltpu.VMEM((t, LANES), jnp.float32),
            pltpu.VMEM((t, width), jnp.float32),
        ],
    )
    call = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=scale,
                          page_size=rows * t, n_pages=n_pages,
                          group=group, quant=quant, d=d),
        name="paged_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_seqs, n_heads, 1, width),
                                       q.dtype),
        interpret=_interpret(),
    )
    bt = block_tables.astype(jnp.int32)
    cl = context_lens.astype(jnp.int32)
    q4 = q[:, :, None, :]
    if t > 1:
        # the query in lane group g of row g, zero elsewhere
        q4 = (jnp.eye(t, dtype=q.dtype)[:, :, None]
              * q4[:, :, :, None, :]).reshape(n_seqs, n_heads, t, width)
    if quant:
        out = call(bt, cl, k_scale.astype(jnp.float32),
                   v_scale.astype(jnp.float32), q4, k_pages, v_pages)
    else:
        out = call(bt, cl, q4, k_pages, v_pages)
    out = out[:, :, 0, :]
    if t > 1:
        # each lane group holds the sum over its own tokens
        out = out.reshape(n_seqs, n_heads, t, d).sum(axis=2)
    return out


# ==========================================================================
# KV-pool append — the serving-runtime write
# ==========================================================================
# An XLA scatter into a pool is re-laid around on the chip (the compiler
# wants the update window's dims minor) and back: two pool-sized copies
# per pool per program call, whatever the number of rows written.  This
# kernel moves only the blocks it writes, WHERE THE POOL LIES, and its
# output aliases the pool operand, so with the pool donated to the
# program the append reads and writes no whole pool.
#
# Where the pool lies is the chip's choice, by shape.  A pool whose rows
# fill the 128 lanes — the stored form ``(kv_heads, num_pages, rows,
# 128)`` of every pool with head_dim under the lanes and pages of whole
# tiles (``KVCacheConfig.pool_shape``), or head_dim a multiple of 128 —
# lies row-major, in the tiles ``paged_decode`` reads: a token's write is
# head_dim lanes of one row of its page's ``(kv_heads, 1, rows, width)``
# block (``t = width // head_dim`` tokens a row; all of the row where
# ``t = 1``).  What is left for the third view is a pool that could not
# be stored lane-full: head_dim 32, 64 or 96 with ``page_size *
# head_dim`` not a multiple of 1024 (page_size 8 at head_dim 64) or
# head_dim 96.  There the chip keeps the PAGE axis minor instead of
# padding every row to 128 (``{1,3,2,0:T(8,128)}``) and the kernel works
# on the transposed view ``(kv_heads, page_size, d, num_pages)``, which
# is the same bytes (XLA makes the transpose a bitcast), a token's write
# being one lane of a ``(kv_heads, 1, d, 128)`` block; ``paged_decode``
# is handed a re-laid copy of such a pool, the append is not.
#
# One grid step per token, tokens ordered by the block they write so
# that a block's tokens are adjacent: the block is fetched when the walk
# enters it, each of its tokens selects its row (lane) into the resident
# block, and the block is written back when the walk leaves it (Pallas
# skips the copies while a block index repeats).  A block is therefore
# read once and written once per call however its tokens were spread
# over the feed, which also keeps the block pipeline's prefetch from
# ever reading a block whose write-back is still in flight.  Pad
# sentinel tokens sort last, stay on the last block a real token wrote
# and select nothing.


def _kv_append_kernel(keys_ref, sel_ref, order_ref, *refs, page_minor, d):
    """Grid step ``i`` writes sorted token ``i``: ``refs`` are, for each
    pool, the token's ``(1, kv_heads, 1, width)`` rows, then each pool's
    block as read, then each pool's block to write.  ``sel_ref[i]`` is
    the token's offset in its page (``page_minor``: the lane of its
    page), -1 for a pad token.  ``d`` is head_dim: a row of the block
    holds ``width // d`` tokens, and the token's row arrives with its
    values repeated in every lane group."""
    del order_ref                       # the rows' index map reads it
    n = len(refs) // 3
    rows, blocks_in, blocks_out = refs[:n], refs[n:2 * n], refs[2 * n:]
    i = pl.program_id(0)
    sel = sel_ref[i]

    @pl.when(jnp.logical_or(
        i == 0, keys_ref[i] != keys_ref[jnp.maximum(i - 1, 0)]))
    def _open():
        for src, dst in zip(blocks_in, blocks_out):
            dst[...] = src[...]

    @pl.when(sel >= 0)
    def _write():
        for row, dst in zip(rows, blocks_out):
            cur = dst[...]
            # the select runs 32 bits wide: the chip's vector unit has
            # no 16- or 8-bit compare/select, and both casts are exact
            wide = (jnp.float32 if jnp.issubdtype(cur.dtype, jnp.floating)
                    else jnp.int32)
            new = row[0].astype(wide)               # (kv_heads, 1, width)
            if page_minor:
                # the row's d values go down the sublanes of one lane:
                # turn (1, d) into (d, 1) through the diagonal of (d, d)
                n_kv = new.shape[0]
                diag = (lax.broadcasted_iota(jnp.int32, (n_kv, d, d), 1)
                        == lax.broadcasted_iota(jnp.int32, (n_kv, d, d), 2))
                new = jnp.sum(jnp.where(diag, new, 0), axis=2, keepdims=True)
                at = lax.broadcasted_iota(jnp.int32, cur.shape, 3) == sel
            else:
                t = cur.shape[3] // d
                at = lax.broadcasted_iota(
                    jnp.int32, cur.shape, 2) == lax.div(sel, t)
                if t > 1:
                    at &= _in_lane_group(cur.shape, lax.rem(sel, t) * d, d)
            dst[...] = jnp.where(at, new[:, None],
                                 cur.astype(wide)).astype(cur.dtype)


@functools.partial(jax.jit, static_argnames="page_minor")
def _kv_append_call(pools, rows, slots, page_minor):
    """``pools``: tuple of stored pools of one shape, ``(kv_heads,
    num_pages, page_rows, width)``; ``rows``: per pool ``(tokens,
    kv_heads, d)`` in the pool's dtype; ``slots``: ``(tokens,)`` int32.
    Jitted so that the layers of one program share one trace and one
    Mosaic lowering."""
    n_kv, n_pages, page_rows, width = pools[0].shape
    d = rows[0].shape[-1]
    t = width // d
    page_size = page_rows * t
    valid = (slots >= 0) & (slots < n_pages * page_size)
    page, off = lax.div(slots, page_size), lax.rem(slots, page_size)
    if page_minor:
        # a block is (offset, 128 pages): n_pages // LANES blocks a row
        per_off = n_pages // LANES
        key, sel = off * per_off + lax.div(page, LANES), lax.rem(page, LANES)
        block = (n_kv, 1, d, LANES)
        pools = [p.transpose(0, 2, 3, 1) for p in pools]

        def _block_idx(i, keys, sel, order):
            return (0, lax.div(keys[i], per_off), 0,
                    lax.rem(keys[i], per_off))
    else:
        key, sel = page, off
        block = (n_kv, 1, page_rows, width)

        def _block_idx(i, keys, sel, order):
            return (0, keys[i], 0, 0)

    def _row_idx(i, keys, sel, order):
        return (order[i], 0, 0, 0)

    # pads sort last and ride on the last block a real token wrote
    # (block 0 when the feed is all padding: read, written back as is)
    order = jnp.argsort(jnp.where(valid, key, jnp.iinfo(jnp.int32).max))
    order = order.astype(jnp.int32)
    key = jnp.where(valid, key, jnp.max(jnp.where(valid, key, 0)))[order]
    sel = jnp.where(valid, sel, -1)[order]
    # rows ride as (tokens, kv_heads, 1, width), a token's values in every
    # lane group of its row (the kernel selects the token's own): a
    # block's last two dims must be (8, 128)-aligned or the array's own
    # (as paged_decode's q)
    row_spec = pl.BlockSpec((1, n_kv, 1, width), _row_idx)
    block_spec = pl.BlockSpec(block, _block_idx)
    n = len(pools)
    out = pl.pallas_call(
        functools.partial(_kv_append_kernel, page_minor=page_minor, d=d),
        name="kv_append",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(slots.shape[0],),
            in_specs=[row_spec] * n + [block_spec] * n,
            out_specs=[block_spec] * n),
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        # operand indices count the three scalar-prefetch arguments
        input_output_aliases={3 + n + j: j for j in range(n)},
        interpret=_interpret(),
    )(key, sel, order,
      *[jnp.tile(r, t)[:, :, None, :] for r in rows], *pools)
    return [o.transpose(0, 3, 1, 2) for o in out] if page_minor else out


def kv_append(pools, rows, slots):
    """Write ``rows[j][t]`` (``(tokens, kv_heads, d)``, the pool's
    dtype) to flat slot ``slots[t]`` of ``pools[j]``, for every pool of
    the tuple; a slot outside the pool (the allocator's pad sentinel,
    ``num_pages * page_size``) drops its row.  Returns the new pools.

    A pool arrives as it is stored (the paged-attention section
    comment): ``(kv_heads, num_pages, page_rows, width)`` with ``width
    // d`` tokens a row — ``(…, page_size, d)`` itself, or its lane-full
    form ``(…, page_size * d / 128, 128)``.

    Engages like :func:`paged_attention`: the Pallas kernel on TPU (or
    under PT_PALLAS_INTERPRET=1) when a page's rows are whole sublane
    groups of 8 (``_pool_tiles_ok``); elsewhere a scatter by
    ``(page, offset)`` — the same result, and the tests' reference.  The
    kernel takes the pool in the view the chip holds it in (section
    comment): row-major for a lane-full pool; page-minor for what
    could not be stored lane-full though head_dim leaves lanes empty
    (there a block's sublane axis is whole tiles for every storage
    type, 32 rows of int8, and the pages must fill whole lane blocks)."""
    _, n_pages, page_rows, width = pools[0].shape
    d = rows[0].shape[-1]
    t = width // d
    slots = slots.astype(jnp.int32)
    if _use_pallas() and _pool_tiles_ok(d, page_rows, width):
        return tuple(_kv_append_call(
            tuple(pools), tuple(rows), slots,
            page_minor=width % LANES != 0 and d % 32 == 0
            and n_pages % LANES == 0))
    # 'drop' makes the sentinel (page == num_pages) a no-op
    page, off = slots // (page_rows * t), slots % (page_rows * t)
    if t == 1:
        return tuple(
            p.at[:, page, off, :].set(r.transpose(1, 0, 2), mode="drop")
            for p, r in zip(pools, rows))
    # a token's d values are lanes [g * d, (g + 1) * d) of its row
    lanes = (off % t * d)[:, None] + jnp.arange(d)
    return tuple(
        p.at[:, page[:, None], (off // t)[:, None], lanes].set(
            r.transpose(1, 0, 2), mode="drop")
        for p, r in zip(pools, rows))


# ==========================================================================
# Fused epilogues (r14) — conv+BN+act and matmul+bias+act
# ==========================================================================
# The profile-ranked fusion layer (utils/cost_model.rank_fusion_candidates
# -> framework/ir.py fuse_epilogue_pass) rewrites conv->BN(->add)->relu
# and matmul->bias->act chains onto the fused ops in ops/fused_ops.py;
# the kernels here are the TPU halves of those ops.  Two shapes of win
# (MLPerf TPU-v3 pods, arXiv 1909.09756 §4: fuse the bandwidth-bound
# epilogue into the surrounding compute):
#
# * ``bn_act_apply`` / ``bn_act_bwd_apply``: the BN scale/shift (+
#   residual add) + activation applied per-channel in ONE VMEM pass over
#   the conv output — the unfused chain pays a separate HBM read+write
#   per epilogue op.  The conv itself stays ``lax.conv_general_dilated``
#   (the MXU path XLA already schedules well); only the epilogue is
#   hand-fused.  Works on the channel-last (NHWC — the layout pass's
#   on-accelerator default) and channel-first tilings without
#   transposing: the same kernel body sees (rows, C) or (1, C-block,
#   cols) blocks and broadcasts the per-channel vectors either way.
# * ``matmul_bias_act``: a tiled MXU matmul whose bias+activation
#   epilogue is applied to the f32 VMEM accumulator before the single
#   HBM write of the output tile.
#
# Engage rules follow paged_attention: kernel on TPU (or under
# PT_PALLAS_INTERPRET=1); PT_FUSED_EPILOGUE=0 forces the jnp fallback,
# =1 forces the kernel past the backend check; hard shape constraints
# (block-divisible dims, sublane-multiple channels) always gate.  Every
# entry point returns None when the kernel does not engage — the ops in
# fused_ops.py then run the bit-identical jnp composition instead.

_EPILOGUE_ROW_BLOCKS = (512, 256, 128, 8)
_EPILOGUE_COL_BLOCKS = (512, 256, 128)
_EPILOGUE_CH_BLOCKS = (256, 128, 64, 32, 16, 8)


def _pick_div(n: int, candidates) -> int | None:
    """Largest candidate that divides n (padding-free BlockSpecs only)."""
    for c in candidates:
        if n % c == 0:
            return c
    return None


def _epilogue_engages() -> bool:
    force = os.environ.get("PT_FUSED_EPILOGUE")
    if force == "0":
        return False
    return _use_pallas() or force == "1"


# erf as the f32 rational x*P(x^2)/Q(x^2) on [-4, 4] (the classic
# single-precision form XLA's own erf expands to; max abs error 4.5e-7
# against math.erf): Mosaic lowers neither erf nor erfc.
_ERF_P = (-2.72614225801306e-10, 2.77068142495902e-08,
          -2.10102402082508e-06, -5.69250639462346e-05,
          -7.34990630326855e-04, -2.95459980854025e-03,
          -1.60960333262415e-02)
_ERF_Q = (-1.45660718464996e-05, -2.13374055278905e-04,
          -1.68282697438203e-03, -7.37332916720468e-03,
          -1.42647390514189e-02)


def _erf_f32(x):
    x = jnp.clip(x, -4.0, 4.0)
    x2 = x * x
    p = jnp.full_like(x, _ERF_P[0])
    for c in _ERF_P[1:]:
        p = p * x2 + c
    q = jnp.full_like(x, _ERF_Q[0])
    for c in _ERF_Q[1:]:
        q = q * x2 + c
    return x * p / q


def apply_act(y, act: str, in_kernel: bool = False):
    """The in-kernel (and fallback) activation menu.  ``relu`` uses the
    exact ``jnp.maximum(y, 0)`` form of the fused BN ops so kernel and
    fallback stay term-for-term identical.  ``gelu`` is the exact (erf)
    form: the fallback keeps ``jax.nn.gelu`` — bitwise the unfused op —
    and the kernel body (``in_kernel``, f32 accumulator) spells erf out
    in primitives Mosaic has."""
    if not act:
        return y
    if act == "relu":
        return jnp.maximum(y, jnp.zeros((), y.dtype))
    if act == "sigmoid":
        return jax.nn.sigmoid(y)
    if act == "tanh":
        return jnp.tanh(y)
    if act == "gelu":
        if in_kernel:
            return 0.5 * y * (1.0 + _erf_f32(y * (2.0 ** -0.5)))
        return jax.nn.gelu(y, approximate=False)
    raise NotImplementedError(f"fused epilogue act {act!r}")


def _act_mask_grad(y, dy, act: str):
    """g = act'(y) * dy from the SAVED OUTPUT y — exactly the grad form
    the unfused relu_grad/activation chains compute, so the fused
    backward epilogue stays bit-compatible with the fallback."""
    if not act:
        return dy
    if act == "relu":
        # compared in f32 (exact for bf16 y): v5e's vector unit has no
        # bf16 compare and Mosaic refuses one
        return jnp.where(y.astype(jnp.float32) > 0.0, dy,
                         jnp.zeros((), dy.dtype))
    raise NotImplementedError(f"fused epilogue act grad {act!r}")


def _scale_shift_act_kernel(x_ref, a_ref, b_ref, z_ref, o_ref, *, act):
    """One VMEM tile of y = act(x*a + b [+ z]): a/b broadcast over rows
    (channels-last blocks) or columns (channels-first blocks)."""
    y = x_ref[...] * a_ref[...] + b_ref[...]
    if z_ref is not None:
        y = y + z_ref[...]
    o_ref[...] = apply_act(y, act).astype(o_ref.dtype)


def _wrap_optional_mid(body, n_lead, has_opt):
    """Adapter: positional refs -> body(lead..., opt_ref or None, rest)."""

    def kernel(*refs):
        lead = list(refs[:n_lead])
        opt = refs[n_lead] if has_opt else None
        rest = refs[n_lead + 1 if has_opt else n_lead:]
        body(*lead, opt, *rest)

    return kernel


def _channel_tiling(x, c_axis):
    """(x_tiled, per-channel broadcast shape, specs, grid, restore) for a
    per-channel VMEM walk over ``x``, or None when no padding-free tiling
    exists.  channels-last: (M, C) rows blocks; channels-first:
    (B, C, L) with (1, bc, bl) blocks."""
    shape = jnp.shape(x)
    nd = len(shape)
    c = shape[c_axis]
    if c_axis == nd - 1:
        m = 1
        for d in shape[:-1]:
            m *= d
        if c % 8 != 0:
            return None
        bm = _pick_div(m, _EPILOGUE_ROW_BLOCKS)
        if bm is None:
            return None
        x2 = jnp.reshape(x, (m, c))
        vec_shape = (1, c)
        vec_spec = pl.BlockSpec((1, c), lambda i: (0, 0))
        dat_spec = pl.BlockSpec((bm, c), lambda i: (i, 0))
        return x2, vec_shape, dat_spec, vec_spec, (m // bm,), shape
    if c_axis == 1 and nd >= 2:
        b0 = shape[0]
        l = 1
        for d in shape[2:]:
            l *= d
        bl = _pick_div(l, _EPILOGUE_COL_BLOCKS)
        bc = _pick_div(c, _EPILOGUE_CH_BLOCKS)
        if bl is None or bc is None:
            return None
        x3 = jnp.reshape(x, (b0, c, l))
        vec_shape = (1, c, 1)
        vec_spec = pl.BlockSpec((1, bc, 1), lambda n, ci, li: (0, ci, 0))
        dat_spec = pl.BlockSpec((1, bc, bl), lambda n, ci, li: (n, ci, li))
        return x3, vec_shape, dat_spec, vec_spec, \
            (b0, c // bc, l // bl), shape
    return None


def bn_act_apply(x, a, b, z=None, act="relu", c_axis=1):
    """Pallas fused-epilogue forward: y = act(x*a + b [+ z]) with
    per-channel a/b (already cast to x.dtype — the fused BN fold).
    Returns None when the kernel does not engage; the caller must then
    run the identical jnp composition."""
    if not _epilogue_engages():
        return None
    tiling = _channel_tiling(x, c_axis)
    if tiling is None:
        return None
    xt, vec_shape, dat_spec, vec_spec, grid, shape = tiling
    a_t = jnp.reshape(a, vec_shape)
    b_t = jnp.reshape(b, vec_shape)
    in_specs = [dat_spec, vec_spec, vec_spec]
    args = [xt, a_t, b_t]
    if z is not None:
        in_specs.append(dat_spec)
        args.append(jnp.reshape(z, jnp.shape(xt)))
    out = pl.pallas_call(
        _wrap_optional_mid(
            functools.partial(_scale_shift_act_kernel, act=act),
            3, z is not None),
        name="bn_act_fwd",
        grid=grid,
        in_specs=in_specs,
        out_specs=dat_spec,
        out_shape=jax.ShapeDtypeStruct(jnp.shape(xt), x.dtype),
        interpret=_interpret(),
    )(*args)
    return jnp.reshape(out, shape)


def _bn_act_bwd_kernel(y_ref, dy_ref, x_ref, cg_ref, mean_ref, cx_ref,
                       c0_ref, dx_ref, g_ref, *, act, want_g):
    """One VMEM tile of the fused backward epilogue:
    g = act'(y)*dy;  dx = g*cg + (x - mean)*cx + c0 — the dX affine of
    the BN backward with the batch-stat corrections folded into the
    per-channel vectors (computed once outside)."""
    g = _act_mask_grad(y_ref[...], dy_ref[...], act)
    dx = (g * cg_ref[...]
          + (x_ref[...] - mean_ref[...]) * cx_ref[...]
          + c0_ref[...].astype(g.dtype))
    dx_ref[...] = dx.astype(dx_ref.dtype)
    if want_g:
        g_ref[...] = g.astype(g_ref.dtype)


def bn_act_bwd_apply(y, dy, x, cg, mean, cx, c0, act="relu", c_axis=1,
                     want_g=False):
    """Pallas fused-epilogue backward: one pass over (y, dy, x) emitting
    dx (and g — the residual-add gradient — when ``want_g``).  The
    per-channel vectors carry the already-reduced BN terms: cg = scale *
    inv_std (g.dtype), mean (x.dtype), cx = -scale*inv^2*sgx/n (x.dtype),
    c0 = -scale*inv*sg/n (f32) — the same terms the jnp fallback uses.
    Returns None when the kernel does not engage."""
    if not _epilogue_engages():
        return None
    tiling = _channel_tiling(x, c_axis)
    if tiling is None:
        return None
    xt, vec_shape, dat_spec, vec_spec, grid, shape = tiling
    args = [jnp.reshape(y, jnp.shape(xt)), jnp.reshape(dy, jnp.shape(xt)),
            xt, jnp.reshape(cg, vec_shape), jnp.reshape(mean, vec_shape),
            jnp.reshape(cx, vec_shape), jnp.reshape(c0, vec_shape)]
    in_specs = [dat_spec, dat_spec, dat_spec,
                vec_spec, vec_spec, vec_spec, vec_spec]
    out_specs = [dat_spec]
    out_shape = [jax.ShapeDtypeStruct(jnp.shape(xt), x.dtype)]
    if want_g:
        out_specs.append(dat_spec)
        out_shape.append(jax.ShapeDtypeStruct(jnp.shape(xt), dy.dtype))
    outs = pl.pallas_call(
        functools.partial(_bn_act_bwd_kernel, act=act, want_g=want_g)
        if want_g else
        (lambda *refs: _bn_act_bwd_kernel(*refs, None, act=act,
                                          want_g=False)),
        name="bn_act_bwd",
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=_interpret(),
    )(*args)
    if want_g:
        return (jnp.reshape(outs[0], shape), jnp.reshape(outs[1], shape))
    return (jnp.reshape(outs[0], shape), None)


def _matmul_bias_act_kernel(x_ref, w_ref, b_ref, o_ref, acc_scr, *,
                            act, n_k):
    """Tiled matmul with the bias+activation epilogue applied to the f32
    VMEM accumulator on the last k step — one HBM write per output tile,
    no separate bias/act passes."""
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    acc_scr[...] += lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _done():
        y = acc_scr[...] + b_ref[...].astype(jnp.float32)
        o_ref[...] = apply_act(y, act, in_kernel=True).astype(o_ref.dtype)


def matmul_bias_act(x, w, bias, act=""):
    """Pallas fused matmul+bias+activation over 2-D operands: x (M, K)
    @ w (K, N) + bias (N,) -> act.  Returns None when the kernel does
    not engage (off-TPU, or no padding-free block tiling exists)."""
    if not _epilogue_engages():
        return None
    m, k = jnp.shape(x)
    n = jnp.shape(w)[1]
    bm = _pick_div(m, _EPILOGUE_ROW_BLOCKS)
    bk = _pick_div(k, _EPILOGUE_COL_BLOCKS)
    bn = _pick_div(n, (256, 128))
    if bm is None or bk is None or bn is None:
        return None
    out_dtype = jnp.result_type(x, w)
    return pl.pallas_call(
        functools.partial(_matmul_bias_act_kernel, act=act, n_k=k // bk),
        name="matmul_bias_act",
        grid=(m // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, ki: (i, ki)),
            pl.BlockSpec((bk, bn), lambda i, j, ki: (ki, j)),
            pl.BlockSpec((1, bn), lambda i, j, ki: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, ki: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=_interpret(),
    )(x, w, jnp.reshape(bias, (1, n)))


def paged_attention(q, k_pages, v_pages, block_tables, context_lens,
                    scale=None, k_scale=None, v_scale=None):
    """Ragged paged attention for decode (one query token per sequence).

    Shapes as in :func:`paged_attention_reference`; ``k_scale`` /
    ``v_scale`` are the optional int8 per-(kv_head, page) scale pools
    (quantized pages dequantize inside the kernel's online-softmax
    loop, so HBM traffic shrinks with the storage dtype).  Takes the
    Pallas kernel on TPU (or under PT_PALLAS_INTERPRET=1);
    PT_PAGED_ATTENTION=0 forces the gather fallback, =1 forces the
    kernel past the backend check (combine with PT_PALLAS_INTERPRET=1
    off-TPU — a forced kernel on plain CPU fails loudly rather than
    silently measuring the fallback).  Hard shape constraints always
    gate: head_dim and a page's stored rows multiples of 8 (sublane),
    q_heads a multiple of kv_heads; anything else falls back.  The pools
    arrive as stored (the section comment): logical, or lane-full."""
    n_seqs, n_heads, d = q.shape
    n_kv, _, page_rows, width = k_pages.shape
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    force = os.environ.get("PT_PAGED_ATTENTION")
    shape_ok = _pool_tiles_ok(d, page_rows, width) and n_heads % n_kv == 0
    eligible = shape_ok and (_use_pallas() or force == "1")
    if force == "0" or not eligible:
        return paged_attention_reference(q, k_pages, v_pages, block_tables,
                                         context_lens, scale,
                                         k_scale=k_scale, v_scale=v_scale)
    return _paged_decode_call(q, k_pages, v_pages, block_tables,
                              context_lens, scale,
                              k_scale=k_scale, v_scale=v_scale)
