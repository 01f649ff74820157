"""Pallas TPU kernels of the gated delta-rule mixers (Kimi Delta Attention,
arXiv:2510.26692 section 3, and Gated DeltaNet, arXiv:2412.06464), each
beside the jnp composition that is its CPU fallback and its test oracle.

A head keeps a state ``S`` of ``(d_k, d_v)`` float32 a sequence.  A token
with key ``k``, value ``v``, query ``q`` (``k`` and ``q`` normalised by the
caller), log-decay ``g <= 0`` per channel of ``d_k`` and write strength
``beta`` does::

    S' = Diag(exp(g)) S;   u = beta (v - S'^T k);   S = S' + k u^T;   o = S^T q

* ``kda_recurrence`` — exactly that, one token at a time (``lax.scan``): the
  oracle of both kernels and the CPU fallback.
* ``kda_prefill`` — the same over one whole prompt in chunks of ``CHUNK``
  tokens.  The grid is ``(heads // group, chunks)`` (``prefill_grid``): a
  step takes chunk ``c`` of a group of heads, whose columns of ``[q | k |
  v]``, of the decay and of the output are adjacent and so one block, with
  the group's states carried across the chunks in VMEM.  Inside a chunk
  ``u`` solves ``(I + A) U = beta (V - Kbar S_0)`` with ``A_ij = beta_i
  sum_c k_i[c] k_j[c] exp(G_i[c] - G_j[c])`` for ``j < i`` (``G`` the
  running sum of ``g``), and ``o = Qbar S_0 + B U`` with ``B`` the same sum
  over ``q_i`` and ``j <= i``.  The textbook form writes ``exp(G_i - G_j)``
  as ``exp(G_i) exp(-G_j)``, whose second factor overflows float32 under
  strong decay.  Here every pair ``j < i`` is split at the boundary ``b`` of
  the smallest aligned power-of-two block that holds both, ``exp(G_i - G_b)
  exp(G_b - G_j)``: both factors at most 1, and one level of blocks is one
  matmul (``log2(CHUNK)`` levels).  The same levels invert ``I + A`` block
  by block (``[[X, 0], [Y, Z]]^-1 = [[X^-1, 0], [-Z^-1 Y X^-1, Z^-1]]``).
  ``G``, the inverse and the state are held in float32 and the solve ``U =
  T rhs`` is a float32 product; the operands of the other matmuls are
  bfloat16, accumulated in float32 (``_SOLVE``).  A head's products are a
  chain of about 17 small ones, each waiting for the one before, and the
  compiler schedules a step's body alone and much in the order written.  So
  the heads of a group, which need nothing of each other, are written side
  by side, stage by stage, and a level's pair products (which need ``G``
  alone) stand inside the merge of the level before: a product that waits
  has another beside it.  What depends on the level alone (the boundary's
  selector, the halves, the pairs' mask, the diagonal and the triangle) is
  built once a step for the group.  A head's output and state are those of
  one head a step, bit for bit.
* ``kda_decode`` — one token a sequence against the state pool ``(slots + 1,
  heads, d_k, d_v)``, rewritten in place (the pool aliased onto the output):
  a grid step reads one sequence's states through its slot number, applies
  the token and writes them back; nothing of pool size moves beside that.
  Memory-bound: 2 x heads x d_k x d_v x 4 bytes a sequence and layer.
* ``gdn_prefill``, ``gdn_decode`` — the same rule as Gated DeltaNet writes it
  (arXiv:2412.06464): ONE log-decay a head, a rectangular state (``d_k`` 96
  under ``d_v`` 192, neither a multiple of the 128 lanes) and ``beta`` up to
  2; the oracle is ``kda_recurrence`` with the decay broadcast over the
  channels.  With one decay a head ``exp(G_i - G_j)`` is one chunk x chunk
  matrix, at most 1 for ``j <= i``, so the chunked form needs none of the
  levels of split pair products above: ``A`` and ``B`` come of one product a
  chunk and the levels that are left are the triangular inverse's merges
  (two products each).  ``q``, ``k`` and ``v`` reach the kernel normalised
  and laid heads-first by XLA (a block's last dimension is then the whole
  ``d_k`` or ``d_v``: 30 heads give no group whose columns fill whole
  tiles), six heads a grid step where they divide.  The state pool stores
  ``gdn_pack`` heads SIDE BY SIDE along the lanes, ``(slots + 1, heads /
  pack, d_k, pack d_v)``: two heads of 192 are three whole tiles, so nothing
  is padded and a decode step moves the arithmetic's 2 x heads x d_k x d_v x
  4 bytes a sequence and layer; ``gdn_decode`` takes a pool row (a pair) at a
  time, a head's columns spread over its own lanes by a select.
* ``short_conv`` — the causal depthwise convolution of ``taps`` inputs a
  channel (jnp: XLA fuses it), its decode step against the ``taps - 1``
  inputs a sequence keeps; shared by both mixers, as ``normalised_heads``.

Engage rules follow ``mla_kernels``: kernel on TPU or under
``PT_PALLAS_INTERPRET=1``, the jnp composition elsewhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_kernels import LANES, _interpret, _use_pallas, own_jit

#: tokens one ``kda_prefill`` grid step takes (a power of two).  Measured on
#: the chip (PR 36, one layer's call over 8,192 tokens, 32 heads of 128, one
#: head a step): 64 tokens a step 14.4 ms, 128 11.5, 32 21.0 (a step's fixed
#: cost, and at 64 the chunk's square matrices fill half the lanes).  By
#: heads a step at 128 tokens (PR 40, the same call; one head a step as the
#: parent had it 7.07 ms): 1 head 6.00 ms, 2 4.80, 4 4.26, 8 4.23; written
#: a level of every head before the next level of any, but a level's pairs
#: and its merge one after the other, 7.07, 6.15, 5.84, 5.71; a head after
#: the other in one body 7.07, 6.88, 6.74, 6.68 (the compiler does not
#: interleave what is not written side by side); the masks as constant
#: operands instead of built in the step: no change (5.83 at 4 heads)
CHUNK = 128
_HI = lax.Precision.HIGHEST
#: the precision of the products that build the triangular inverse level by
#: level (float32 operands; DEFAULT is one bfloat16 pass on the MXU, HIGHEST
#: six).  Measured on the chip (PR 36, same call): HIGHEST 11.5 ms, DEFAULT
#: 7.2, and against the token-by-token recurrence the output moves from
#: 3.5e-4 to 4.3e-4 of 0.073 and the state from 2.7e-3 to 3.1e-3: the
#: inverse's own operand ``A`` is a product of bfloat16 operands already.
#: The solve itself (``U = T rhs``) and the running sum of the decay stay at
#: HIGHEST
_SOLVE = lax.Precision.DEFAULT
#: VMEM a ``kda_prefill`` grid step may plan for by ``_group_vmem``'s count:
#: half of what the compiler gives a kernel that asks for nothing (16 MiB on
#: a v5e), the other half for its spill slots.  At the cell's sizes that is
#: 4 heads a step and not 8, and the chip agrees for another reason: 8 are
#: no faster (4.23 against 4.26 ms a call) and their body, twice as long, is
#: traced and lowered once a prefill bucket (PR 40: the cell's warm set-up
#: +9.6 s at 8 heads a step)
_GROUP_VMEM = 8 * 1024 * 1024
_NT = (((1,), (1,)), ((), ()))       # x @ y^T
_TN = (((0,), (0,)), ((), ()))       # x^T @ y


# ==========================================================================
# the recurrence: oracle and CPU fallback
# ==========================================================================
def kda_recurrence(q, k, v, g, beta, state):
    """``q``, ``k``, ``g`` (t, heads, d_k), ``v`` (t, heads, d_v), ``beta``
    (t, heads), ``state`` (heads, d_k, d_v): one token at a time.  Returns
    ``(o (t, heads, d_v), state)``, float32."""
    f32 = jnp.float32

    def step(s, x):
        qt, kt, vt, gt, bt = x
        s = jnp.exp(gt)[..., None] * s
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", s, kt,
                                           precision=_HI))
        s = s + kt[..., None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, qt, precision=_HI)

    state, o = lax.scan(step, state.astype(f32), tuple(
        x.astype(f32) for x in (q, k, v, g, beta)))
    return o, state


# ==========================================================================
# kda_prefill
# ==========================================================================
def normalised_heads(qkv, heads: int, l2_eps: float, dk: int = 0):
    """``qkv`` (n, 3 heads d), the convolution's outputs after SiLU, to ``q``
    (l2-normalised a head, scaled by ``d^-1/2``), ``k`` (l2-normalised) and
    ``v``, each ``(n, heads, d)`` float32.  With ``dk`` the heads are
    rectangular: ``qkv`` (n, heads (2 d_k + d_v)), ``q`` and ``k`` ``(n,
    heads, d_k)`` and ``v`` the rest."""
    at = [heads * dk, 2 * heads * dk] if dk else 3
    q, k, v = (t.reshape(t.shape[0], heads, -1)
               for t in jnp.split(qkv.astype(jnp.float32), at, axis=-1))

    def unit(x):
        return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + l2_eps)

    return unit(q) * q.shape[-1] ** -0.5, unit(k), v


def _kda_prefill_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, s_ref,
                        st_ref, *, chunk, group, l2_eps):
    """Grid step ``(hg, c)``: chunk ``c`` of the ``group`` heads ``hg * group
    + j``.  ``q_ref``, ``k_ref`` and ``v_ref`` are the group's columns of the
    convolution's output (before the l2 norm), a head's ``d`` beside the
    next's, ``g_ref`` its log-decay a token, ``b_ref`` the chunk's write
    strengths, a lane a head.  ``st_ref`` (group, d_v, d_k) holds the heads'
    states transposed, so that a decay per channel of ``d_k`` scales lanes.

    A head's values are those of one head a step, product for product.  What
    the group changes is what stands beside what: each stage is written for
    every head before the next stage of any, and a level's pair products
    (which need nothing of the inverse) stand between the two products of
    the level before's merge, so that a product waiting for its operand has
    another head's, or the next level's, beside it."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    hg, c = pl.program_id(0), pl.program_id(1)
    d = q_ref.shape[1] // group

    @pl.when(c == 0)
    def _fresh():
        st_ref[...] = jnp.zeros(st_ref.shape, f32)

    def unit(x):
        return x * lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True) + l2_eps)

    # what no head owns: built once a step
    row = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tok = lax.broadcasted_iota(jnp.int32, (chunk, d), 0)
    diag = row == col
    eye, causal = diag.astype(f32), (col <= row).astype(f32)
    b = b_ref[...].astype(f32)
    lane = lax.broadcasted_iota(jnp.int32, b.shape, 1)

    def masks(level):
        half = 1 << (level - 1)
        # the boundary of each token's block at this level: the last token
        # of the block's left half.  Any value near G there will do (it
        # cancels in the product), so bfloat16 rows picked by a one-hot
        # matmul are enough
        bound = ((row >> level) << level) + (half - 1)
        pair = ((row >> level) == (col >> level)) & ((row & half) != 0) \
            & ((col & half) == 0)
        return (col == bound).astype(bf16), (tok & half) != 0, pair

    class Head:
        """One head's values across the stages."""

        def __init__(self, j):
            self.j, self.cols = j, slice(j * d, (j + 1) * d)
            self.q = unit(q_ref[:, self.cols].astype(f32)) * d ** -0.5
            self.k = unit(k_ref[:, self.cols].astype(f32))
            beta = jnp.sum(jnp.where(lane == hg * group + j, b, 0.0), axis=1,
                           keepdims=True)
            self.kb = self.k * beta
            self.vb = v_ref[:, self.cols].astype(f32) * beta
            # the running sum of the log-decay inside the chunk
            self.G = jnp.dot(causal, g_ref[:, self.cols].astype(f32),
                             precision=_HI, preferred_element_type=f32)
            self.G16 = self.G.astype(bf16)
            # the diagonal of B: q_i . k_i, no decay between a token and
            # itself
            self.B = jnp.where(diag, lax.dot_general(
                self.q.astype(bf16), self.k.astype(bf16), _NT,
                preferred_element_type=f32), 0.0)
            self.T = eye

        def pairs(self, pick, right, pair):
            """A level's pairs: ``B`` gains its, the inverse's are returned."""
            ref = jnp.dot(pick, self.G16, preferred_element_type=f32)
            E = jnp.exp(jnp.where(right, self.G - ref, ref - self.G))
            lhs = jnp.concatenate([self.kb * E, self.q * E],
                                  axis=0).astype(bf16)
            P = lax.dot_general(lhs, (self.k * E).astype(bf16), _NT,
                                preferred_element_type=f32)
            self.B = self.B + jnp.where(pair, P[chunk:], 0.0)
            return jnp.where(pair, P[:chunk], 0.0)

        def merge_left(self, level, A):
            if level == 1:
                self.T = self.T - A
            else:
                self.TA = jnp.dot(self.T, A, precision=_SOLVE,
                                  preferred_element_type=f32)

        def merge_right(self, level):
            if level > 1:
                self.T = self.T - jnp.dot(self.TA, self.T, precision=_SOLVE,
                                          preferred_element_type=f32)

        def solve(self):
            self.st = st_ref[self.j]                    # (d_v, d_k)
            self.st16 = self.st.astype(bf16)
            self.decay = jnp.exp(self.G)
            rhs = self.vb - lax.dot_general(
                (self.kb * self.decay).astype(bf16), self.st16, _NT,
                preferred_element_type=f32)
            U = jnp.dot(self.T, rhs, precision=_HI,
                        preferred_element_type=f32)
            self.U16 = U.astype(bf16)

        def write(self):
            o = lax.dot_general((self.q * self.decay).astype(bf16),
                                self.st16, _NT, preferred_element_type=f32) \
                + jnp.dot(self.B.astype(bf16), self.U16,
                          preferred_element_type=f32)
            o_ref[:, self.cols] = o.astype(o_ref.dtype)
            last = self.G[chunk - 1:chunk, :]           # (1, d_k)
            self.st = self.st * jnp.exp(last) + lax.dot_general(
                self.U16, (self.k * jnp.exp(last - self.G)).astype(bf16),
                _TN, preferred_element_type=f32)
            st_ref[self.j] = self.st

    levels = range(1, chunk.bit_length())      # blocks of 2 .. chunk
    heads = [Head(j) for j in range(group)]
    shared = masks(levels[0])
    ahead = [x.pairs(*shared) for x in heads]
    for level in levels:
        for x, A in zip(heads, ahead):
            x.merge_left(level, A)
        if level + 1 in levels:
            shared = masks(level + 1)
            ahead = [x.pairs(*shared) for x in heads]
        for x in heads:
            x.merge_right(level)
    for x in heads:
        x.solve()
    for x in heads:
        x.write()

    @pl.when(c == pl.num_programs(1) - 1)
    def _out():
        for x in heads:
            s_ref[x.j] = x.st.T


@functools.partial(jax.jit, static_argnames=("heads", "chunk", "group",
                                             "l2_eps"))
def _kda_prefill_call(qkv, g, beta, *, heads, chunk, group, l2_eps):
    t = qkv.shape[0]
    d = qkv.shape[1] // (3 * heads)
    f32 = jnp.float32
    n = -(-t // chunk)
    pad = n * chunk - t
    qkv, g, beta = qkv.astype(f32), g.astype(f32).reshape(t, heads * d), \
        beta.astype(f32)
    if pad:       # rows that decay nothing and write nothing
        qkv, g, beta = (jnp.pad(x, ((0, pad), (0, 0)))
                        for x in (qkv, g, beta))
    groups = heads // group

    def cols(offset):
        # the heads' columns are adjacent: a group is one block
        return pl.BlockSpec((chunk, group * d),
                            lambda hg, c: (c, offset + hg))

    o, state = pl.pallas_call(
        functools.partial(_kda_prefill_kernel, chunk=chunk, group=group,
                          l2_eps=l2_eps),
        name="kda_prefill",
        grid=(groups, n),
        # q, k and v: three views of the one array, a group's columns each
        in_specs=[cols(0), cols(groups), cols(2 * groups), cols(0),
                  pl.BlockSpec((chunk, heads), lambda hg, c: (c, 0))],
        out_specs=[cols(0),
                   pl.BlockSpec((group, d, d), lambda hg, c: (hg, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((n * chunk, heads * d), f32),
                   jax.ShapeDtypeStruct((heads, d, d), f32)],
        scratch_shapes=[pltpu.VMEM((group, d, d), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
    )(qkv, qkv, qkv, g, beta)
    return o[:t].reshape(t, heads, d), state


def _group_vmem(chunk: int, group: int, d: int) -> int:
    """Bytes of VMEM a grid step of ``group`` heads holds: the blocks of
    ``q``, ``k``, ``v``, ``g`` and ``o`` in two buffers, the states (scratch
    and the output's two buffers), and a head's float32 values that live
    across the levels (``q``, ``k``, ``kb``, ``vb``, ``G`` and a level's
    three; ``B``, ``T`` and a level's four squares).  At 128 tokens of 128
    channels the count is 13.5 MiB for 8 heads and 6.75 for 4; the compiler
    takes 8 heads under a limit of 16 MiB and refuses them under 12, takes
    4 under 8 and refuses them under 6 (compiler, sandbox, PR 40)."""
    return 4 * group * (10 * chunk * d + 3 * d * d
                        + 8 * chunk * d + 6 * chunk * chunk)


def _pick_chunk(t: int) -> int:
    c = CHUNK
    while c > 8 and c > t:
        c //= 2
    return c


def prefill_grid(t: int, heads: int, d: int):
    """``(chunk, group, grid)`` of a ``kda_prefill`` call over ``t`` tokens:
    the tokens and the heads a grid step takes and the steps of the call,
    ``(heads // group, chunks)``.  The group is the largest of 8, 4, 2 that
    divides ``heads`` and whose blocks and temporaries fit ``_GROUP_VMEM``,
    else one head."""
    chunk = _pick_chunk(t)
    group = next((n for n in (8, 4, 2) if heads % n == 0
                  and _group_vmem(chunk, n, d) <= _GROUP_VMEM), 1)
    return chunk, group, (heads // group, -(-t // chunk))


def prefill_engages(d: int) -> bool:
    return _use_pallas() and (_interpret() or d % LANES == 0)


def kda_prefill(qkv, g, beta, heads: int, l2_eps: float = 1e-6):
    """One whole prompt from an empty state: ``qkv`` (t, 3 heads d) the
    convolution's outputs after SiLU (``[q | k | v]``, before the l2 norm),
    ``g`` (t, heads, d) the log-decay, ``beta`` (t, heads).  Returns ``(o (t,
    heads, d), state (heads, d, d))`` float32.  Rows past the prompt are
    given ``g = 0`` and ``beta = 0`` by the caller and leave the state as the
    last real token left it."""
    t = qkv.shape[0]
    d = qkv.shape[1] // (3 * heads)
    if prefill_engages(d):
        chunk, group, _ = prefill_grid(t, heads, d)
        return own_jit(_kda_prefill_call)(
            qkv, g, beta, heads=heads, chunk=chunk, group=group,
            l2_eps=float(l2_eps))
    with jax.named_scope("kda_prefill"):
        q, k, v = normalised_heads(qkv, heads, l2_eps)
        return kda_recurrence(q, k, v, g, beta,
                              jnp.zeros((heads, d, d), jnp.float32))


# ==========================================================================
# kda_decode
# ==========================================================================
def kda_decode_reference(pool, slots, q, k, v, g, beta):
    """Gather, one step of the recurrence, scatter: ``pool`` (slots + 1,
    heads, d_k, d_v), ``slots`` (rows,), the rest (rows, heads, .).  Returns
    ``(o (rows, heads, d_v), pool)``."""
    f32 = jnp.float32
    s = jnp.exp(g.astype(f32))[..., None] * pool[slots]
    u = beta.astype(f32)[..., None] * (v.astype(f32) - jnp.einsum(
        "bhkv,bhk->bhv", s, k.astype(f32), precision=_HI))
    s = s + k.astype(f32)[..., None] * u[:, :, None, :]
    o = jnp.einsum("bhkv,bhk->bhv", s, q.astype(f32), precision=_HI)
    return o, pool.at[slots].set(s)


def _kda_decode_kernel(slot_ref, cols_ref, v_ref, s_in, o_ref, s_out, *,
                       heads):
    """Grid step ``b``: every head's state of sequence ``b`` (block index
    ``slot[b]``).  ``cols_ref`` (d_k, 4 heads) holds, a column a head, ``k``,
    ``exp(g)``, ``q`` and ``beta k``: what scales the ROWS of a state, laid
    so that a column broadcasts along lanes."""
    del slot_ref
    cols = cols_ref[0]
    for h in range(heads):
        kc = cols[:, h:h + 1]
        ac = cols[:, heads + h:heads + h + 1]
        qc = cols[:, 2 * heads + h:2 * heads + h + 1]
        bk = cols[:, 3 * heads + h:3 * heads + h + 1]
        s = ac * s_in[0, h]                              # (d_k, d_v)
        u = v_ref[0, h:h + 1, :] - jnp.sum(s * kc, axis=0, keepdims=True)
        s = s + bk * u
        o_ref[0, h:h + 1, :] = jnp.sum(s * qc, axis=0, keepdims=True)
        s_out[0, h] = s


@jax.jit
def _kda_decode_call(pool, slots, q, k, v, g, beta):
    rows, heads, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    k32, b = k.astype(f32), beta.astype(f32)[..., None]
    # (rows, d_k, 4 heads): a column a head of k | exp(g) | q | beta k
    cols = jnp.concatenate([k32, jnp.exp(g.astype(f32)), q.astype(f32),
                            k32 * b], axis=1).transpose(0, 2, 1)
    state = pl.BlockSpec((1, heads, dk, dv), lambda i, slot: (slot[i], 0, 0, 0))
    o, pool = pl.pallas_call(
        functools.partial(_kda_decode_kernel, heads=heads),
        name="kda_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows,),
            in_specs=[pl.BlockSpec((1, dk, 4 * heads),
                                   lambda i, slot: (i, 0, 0)),
                      pl.BlockSpec((1, heads, dv), lambda i, slot: (i, 0, 0)),
                      state],
            out_specs=[pl.BlockSpec((1, heads, dv),
                                    lambda i, slot: (i, 0, 0)), state]),
        out_shape=[jax.ShapeDtypeStruct((rows, heads, dv), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={3: 1},      # after the prefetch operand
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=_interpret(),
    )(slots.astype(jnp.int32), cols, v.astype(f32), pool)
    return o, pool


def decode_engages(dk: int, dv: int) -> bool:
    return dk == dv and prefill_engages(dk)


def kda_decode(pool, slots, q, k, v, g, beta):
    """One token a row against the states at ``slots`` of ``pool``, in
    place.  A padded row carries the pad slot (the pool's last), ``g = 0``
    and ``beta = 0``.  Returns ``(o, pool)``."""
    if decode_engages(q.shape[-1], v.shape[-1]):
        return own_jit(_kda_decode_call)(pool, slots, q, k, v, g, beta)
    with jax.named_scope("kda_decode"):
        return kda_decode_reference(pool, slots.astype(jnp.int32), q, k, v,
                                    g, beta)


# ==========================================================================
# gdn_prefill, gdn_decode: one decay a head, a rectangular state
# ==========================================================================
#: heads a ``gdn_prefill`` grid step takes: the largest that divides the
#: layer's (30 = 2 x 3 x 5 has no 4 and no 8)
_GDN_GROUPS = (6, 5, 4, 3, 2)


def gdn_pack(heads: int, dv: int) -> int:
    """Heads that lie SIDE BY SIDE along the lanes of the state pool: two
    where one head's ``d_v`` does not fill whole 128-lane tiles and the heads
    pair up (192 -> 384 = three tiles: nothing padded), else one."""
    return 2 if dv % LANES and heads % 2 == 0 else 1


def gdn_state_shape(heads: int, dk: int, dv: int) -> tuple:
    """One sequence's states as the pool stores them: ``(heads / pack, d_k,
    pack d_v)``, ``d_k`` on sublanes under the lanes of ``pack`` heads."""
    pack = gdn_pack(heads, dv)
    return (heads // pack, dk, pack * dv)


def gdn_pack_states(state):
    """``(..., heads, d_k, d_v)`` -> ``(..., heads / pack, d_k, pack d_v)``."""
    *lead, heads, dk, dv = state.shape
    pack = gdn_pack(heads, dv)
    if pack == 1:
        return state
    n = len(lead)
    return state.reshape(*lead, heads // pack, pack, dk, dv) \
        .transpose(*range(n), n, n + 2, n + 1, n + 3) \
        .reshape(*lead, heads // pack, dk, pack * dv)


def gdn_unpack_states(packed, heads: int):
    """The inverse of :func:`gdn_pack_states`."""
    *lead, groups, dk, width = packed.shape
    pack = heads // groups
    if pack == 1:
        return packed
    n = len(lead)
    return packed.reshape(*lead, groups, dk, pack, width // pack) \
        .transpose(*range(n), n, n + 2, n + 1, n + 3) \
        .reshape(*lead, heads, dk, width // pack)


def _gdn_prefill_kernel(q_ref, k_ref, v_ref, gb_ref, gt_ref, o_ref, s_ref,
                        st_ref, *, chunk, group):
    """Grid step ``(hg, c)``: chunk ``c`` of the ``group`` heads ``hg * group
    + j``.  ``q_ref`` and ``k_ref`` (group, chunk, d_k) are normalised,
    ``v_ref`` (group, chunk, d_v); ``gb_ref`` (1, chunk, 2 group) holds a
    column a head of the log-decay and then of the write strength, ``gt_ref``
    (1, group, chunk) the log-decay again, a row a head.  ``st_ref`` (group,
    d_k, d_v) carries the states across the chunks.

    With ONE decay a head ``exp(G_i - G_j)`` is one chunk x chunk matrix
    ``D``, at most 1 for ``j <= i``: ``A = (beta K K^T) . D`` below the
    diagonal and ``B = (Q K^T) . D`` on and below it come of one product, and
    the levels that are left are those of the triangular inverse alone
    (``kda_prefill``'s merges: two products a level).  The heads of a group
    are written side by side, stage by stage, as there."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _fresh():
        st_ref[...] = jnp.zeros(st_ref.shape, f32)

    row = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal, strict = col <= row, col < row
    eye, tri = (row == col).astype(f32), causal.astype(f32)
    gb = gb_ref[0].astype(f32)                            # (chunk, 2 group)
    # the running sum of the log-decay, a column and a row a head
    Gc = jnp.dot(tri, gb[:, :group], precision=_HI,
                 preferred_element_type=f32)              # (chunk, group)
    Gr = lax.dot_general(gt_ref[0].astype(f32), tri, _NT, precision=_HI,
                         preferred_element_type=f32)      # (group, chunk)

    def pair_mask(level):
        half = 1 << (level - 1)
        return ((row >> level) == (col >> level)) & ((row & half) != 0) \
            & ((col & half) == 0)

    class Head:
        def __init__(self, j):
            self.j = j
            self.q, self.k = q_ref[j].astype(f32), k_ref[j].astype(f32)
            beta = gb[:, group + j:group + j + 1]          # (chunk, 1)
            self.G = Gc[:, j:j + 1]
            self.kb = self.k * beta
            self.vb = v_ref[j].astype(f32) * beta
            D = jnp.exp(jnp.minimum(self.G - Gr[j:j + 1, :], 0.0))
            P = lax.dot_general(
                jnp.concatenate([self.kb, self.q], axis=0).astype(bf16),
                self.k.astype(bf16), _NT, preferred_element_type=f32)
            self.A = jnp.where(strict, P[:chunk] * D, 0.0)
            self.B = jnp.where(causal, P[chunk:] * D, 0.0)
            self.T = eye

        def merge_left(self, level, pair):
            A = jnp.where(pair, self.A, 0.0)
            if level == 1:
                self.T = self.T - A
            else:
                self.TA = jnp.dot(self.T, A, precision=_SOLVE,
                                  preferred_element_type=f32)

        def merge_right(self, level):
            if level > 1:
                self.T = self.T - jnp.dot(self.TA, self.T, precision=_SOLVE,
                                          preferred_element_type=f32)

        def solve(self):
            self.st = st_ref[self.j]                       # (d_k, d_v)
            self.st16 = self.st.astype(bf16)
            self.decay = jnp.exp(self.G)
            rhs = self.vb - jnp.dot((self.kb * self.decay).astype(bf16),
                                    self.st16, preferred_element_type=f32)
            self.U16 = jnp.dot(self.T, rhs, precision=_HI,
                               preferred_element_type=f32).astype(bf16)

        def write(self):
            o = jnp.dot((self.q * self.decay).astype(bf16), self.st16,
                        preferred_element_type=f32) \
                + jnp.dot(self.B.astype(bf16), self.U16,
                          preferred_element_type=f32)
            o_ref[self.j] = o.astype(o_ref.dtype)
            last = self.G[chunk - 1:chunk, :]              # (1, 1)
            # (1, 1) to a column first: the compiler broadcasts along one
            # of sublanes and lanes at a time
            keep = jnp.exp(jnp.broadcast_to(last, (self.st.shape[0], 1)))
            self.st = self.st * keep + lax.dot_general(
                (self.k * jnp.exp(last - self.G)).astype(bf16), self.U16,
                _TN, preferred_element_type=f32)
            st_ref[self.j] = self.st

    heads = [Head(j) for j in range(group)]
    for level in range(1, chunk.bit_length()):
        pair = pair_mask(level)
        for x in heads:
            x.merge_left(level, pair)
        for x in heads:
            x.merge_right(level)
    for x in heads:
        x.solve()
    for x in heads:
        x.write()

    @pl.when(c == pl.num_programs(1) - 1)
    def _out():
        for x in heads:
            s_ref[x.j] = x.st


@functools.partial(jax.jit, static_argnames=("chunk", "group"))
def _gdn_prefill_call(q, k, v, g, beta, *, chunk, group):
    t, heads, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    n = -(-t // chunk)
    pad = n * chunk - t
    groups = heads // group

    def head_major(x):                 # (t, heads, d) -> (heads, n chunk, d)
        return jnp.pad(x.astype(f32).transpose(1, 0, 2),
                       ((0, 0), (0, pad), (0, 0)))

    def by_group(x):       # (t, heads) -> (groups, n chunk, group); the rows
        # past the prompt decay nothing and write nothing
        return jnp.pad(x.astype(f32), ((0, pad), (0, 0))) \
            .reshape(n * chunk, groups, group).transpose(1, 0, 2)

    gg = by_group(g)
    gb = jnp.concatenate([gg, by_group(beta)], axis=-1)

    def rows(d):
        return pl.BlockSpec((group, chunk, d), lambda hg, c: (hg, c, 0))

    o, state = pl.pallas_call(
        functools.partial(_gdn_prefill_kernel, chunk=chunk, group=group),
        name="gdn_prefill",
        grid=(groups, n),
        in_specs=[rows(dk), rows(dk), rows(dv),
                  pl.BlockSpec((1, chunk, 2 * group),
                               lambda hg, c: (hg, c, 0)),
                  pl.BlockSpec((1, group, chunk), lambda hg, c: (hg, 0, c))],
        out_specs=[rows(dv),
                   pl.BlockSpec((group, dk, dv), lambda hg, c: (hg, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((heads, n * chunk, dv), f32),
                   jax.ShapeDtypeStruct((heads, dk, dv), f32)],
        scratch_shapes=[pltpu.VMEM((group, dk, dv), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
    )(head_major(q), head_major(k), head_major(v), gb,
      gg.transpose(0, 2, 1))
    return o[:, :t].transpose(1, 0, 2), state


def gdn_prefill_grid(t: int, heads: int):
    """``(chunk, group, grid)`` of a ``gdn_prefill`` call over ``t`` tokens:
    the tokens and the heads a grid step takes, and the steps of the call."""
    chunk = _pick_chunk(t)
    group = next((n for n in _GDN_GROUPS if heads % n == 0), 1)
    return chunk, group, (heads // group, -(-t // chunk))


def gdn_engages(heads: int, dk: int, dv: int) -> bool:
    """Both kernels: interpreted at any size; on the chip where ``d_k`` is
    whole sublane groups and the lanes of a pool row (``gdn_pack`` heads of
    ``d_v``) whole tiles: 96 x 192 with an even number of heads, 128 x
    128."""
    return _use_pallas() and (_interpret() or (
        dk % 8 == 0 and (gdn_pack(heads, dv) * dv) % LANES == 0))


def gdn_prefill(q, k, v, g, beta):
    """One whole prompt from an empty state: ``q`` and ``k`` (t, heads, d_k)
    normalised (``normalised_heads``), ``v`` (t, heads, d_v), ``g`` (t,
    heads) the log-decay, ONE a head, ``beta`` (t, heads), which may reach 2.
    Returns ``(o (t, heads, d_v), state (heads, d_k, d_v))`` float32.  Rows
    past the prompt are given ``g = 0`` and ``beta = 0`` by the caller."""
    t, heads, dk = q.shape
    dv = v.shape[-1]
    if gdn_engages(heads, dk, dv):
        chunk, group, _ = gdn_prefill_grid(t, heads)
        return own_jit(_gdn_prefill_call)(q, k, v, g, beta, chunk=chunk,
                                          group=group)
    with jax.named_scope("gdn_prefill"):
        return kda_recurrence(
            q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta,
            jnp.zeros((heads, dk, dv), jnp.float32))


def gdn_decode_reference(pool, slots, q, k, v, g, beta):
    """Gather, one step of the recurrence, scatter, over the pool's packed
    rows (:func:`gdn_state_shape`)."""
    heads = q.shape[1]
    o, states = kda_decode_reference(
        gdn_unpack_states(pool[slots], heads), jnp.arange(slots.shape[0]),
        q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta)
    return o, pool.at[slots].set(gdn_pack_states(states))


def _gdn_decode_kernel(slot_ref, cols_ref, rows_ref, s_in, o_ref, s_out, *,
                       heads, dv):
    """Grid step ``b``: every state of sequence ``b`` (block index
    ``slot[b]``), a pool row (``pack`` heads side by side) after the other.
    ``cols_ref`` (d_k, 2 heads) holds a column a head of ``k`` and then of
    ``q``: what scales the ROWS of a state; ``rows_ref`` (3, heads d_v) a lane
    a value of ``exp(g)``, ``beta`` (each head's repeated over its ``d_v``)
    and ``v``: what scales or meets its columns."""
    del slot_ref
    cols = cols_ref[0]
    groups, _, width = s_in.shape[1:]
    pack = heads // groups
    second = lax.broadcasted_iota(jnp.int32, (1, width), 1) >= dv

    def spread(at):
        """The columns ``at`` of the row's heads, each over its own lanes."""
        if pack == 1:
            return cols[:, at:at + 1]
        return jnp.where(second, cols[:, at + 1:at + 2], cols[:, at:at + 1])

    for r in range(groups):
        lanes = slice(r * width, (r + 1) * width)
        kc, qc = spread(r * pack), spread(heads + r * pack)
        s = s_in[0, r] * rows_ref[0, 0:1, lanes]           # (d_k, width)
        u = rows_ref[0, 1:2, lanes] * (
            rows_ref[0, 2:3, lanes] - jnp.sum(s * kc, axis=0, keepdims=True))
        s = s + kc * u
        o_ref[0, 0:1, lanes] = jnp.sum(s * qc, axis=0, keepdims=True)
        s_out[0, r] = s


@jax.jit
def _gdn_decode_call(pool, slots, q, k, v, g, beta):
    n, heads, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    # (n, d_k, 2 heads): a column a head of k | q
    cols = jnp.concatenate([k.astype(f32), q.astype(f32)],
                           axis=1).transpose(0, 2, 1)

    def lanes(x):                       # (n, heads) -> (n, heads d_v)
        return jnp.repeat(x.astype(f32), dv, axis=1)

    rows = jnp.stack([lanes(jnp.exp(g.astype(f32))), lanes(beta),
                      v.astype(f32).reshape(n, heads * dv)], axis=1)
    state = pl.BlockSpec((1,) + pool.shape[1:],
                         lambda i, slot: (slot[i], 0, 0, 0))
    o, pool = pl.pallas_call(
        functools.partial(_gdn_decode_kernel, heads=heads, dv=dv),
        name="gdn_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n,),
            in_specs=[pl.BlockSpec((1, dk, 2 * heads),
                                   lambda i, slot: (i, 0, 0)),
                      pl.BlockSpec((1, 3, heads * dv),
                                   lambda i, slot: (i, 0, 0)),
                      state],
            out_specs=[pl.BlockSpec((1, 1, heads * dv),
                                    lambda i, slot: (i, 0, 0)), state]),
        out_shape=[jax.ShapeDtypeStruct((n, 1, heads * dv), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={3: 1},      # after the prefetch operand
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=_interpret(),
    )(slots.astype(jnp.int32), cols, rows, pool)
    return o.reshape(n, heads, dv), pool


def gdn_decode(pool, slots, q, k, v, g, beta):
    """One token a row against the states at ``slots`` of ``pool`` (slots +
    1, heads / pack, d_k, pack d_v), in place; ``g`` (rows, heads) one decay
    a head.  A padded row carries the pad slot (the pool's last), ``g = 0``
    and ``beta = 0``.  Returns ``(o (rows, heads, d_v), pool)``."""
    heads, dk = q.shape[1:]
    if gdn_engages(heads, dk, v.shape[-1]):
        return own_jit(_gdn_decode_call)(pool, slots, q, k, v, g, beta)
    with jax.named_scope("gdn_decode"):
        return gdn_decode_reference(pool, slots.astype(jnp.int32), q, k, v,
                                    g, beta)


# ==========================================================================
# short_conv
# ==========================================================================
def short_conv(x, w):
    """Causal depthwise convolution of one sequence: ``x`` (t, channels),
    ``w`` (channels, taps); ``y_t = sum_j w[:, j] x_{t - taps + 1 + j}``,
    zeros before the sequence.  float32."""
    taps = w.shape[1]
    t = x.shape[0]
    with jax.named_scope("short_conv"):
        xp = jnp.pad(x.astype(jnp.float32), ((taps - 1, 0), (0, 0)))
        w = w.astype(jnp.float32)
        return sum(xp[j:j + t] * w[:, j] for j in range(taps))


def short_conv_tail(x, last_index, taps: int):
    """The ``taps - 1`` inputs that end at row ``last_index`` (zeros before
    the sequence): what the next token's convolution needs."""
    xp = jnp.pad(x.astype(jnp.float32), ((taps - 1, 0), (0, 0)))
    return lax.dynamic_slice_in_dim(xp, last_index + 1, taps - 1, axis=0)


def short_conv_step(tail, x, w):
    """One token a row: ``tail`` (rows, taps - 1, channels) the inputs kept,
    ``x`` (rows, channels) the new one.  Returns ``(y (rows, channels), the
    new tail)``."""
    with jax.named_scope("short_conv"):
        window = jnp.concatenate(
            [tail, x.astype(jnp.float32)[:, None, :]], axis=1)
        y = jnp.einsum("btc,ct->bc", window, w.astype(jnp.float32),
                       precision=_HI)
        return y, window[:, 1:]
