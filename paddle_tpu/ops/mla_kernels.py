"""Pallas TPU kernels of the latent-attention / sparse-expert decoder
(inference/mla_decoder.py), each beside the jnp composition that is its
CPU fallback and its test oracle.

* ``mla_decode`` — absorbed latent attention over the paged latent pool:
  every head of a sequence scores the same latent row (``c_kv`` | ``k_r``),
  so a grid step holds all heads of one row and scores one CHUNK of its
  context, several pages fetched by hand (``make_async_copy`` through the
  block table) into one of two VMEM buffers; both products are MXU
  matmuls (``(heads, 576) x (576, tokens)`` and ``(heads, tokens) x
  (tokens, 512)``).  The grid is a work list: one step for every (row,
  chunk) that holds context, rows in order, built on the device from the
  context lengths (:func:`decode_work_list`), and exactly as long as
  that list (its length is a value on the device: no bound to prove, no
  step that holds nothing), so a batch of ragged contexts under a table
  padded to its longest walks what it holds and no more, whatever pages
  the tables share.  Each visit starts the next visit's copies whatever
  row that is, so a row's first chunk is in flight under the last chunk
  of the row before, and a chunk is fetched by groups of pages, as many
  as hold context.
* ``mla_prefill`` — expanded causal attention of one whole prompt, a head
  and a block of query rows a grid step, the key blocks above the
  diagonal neither fetched nor scored; the rotary part of the score is a
  second product against the one ``k_r`` all heads share, so no key of
  192 lanes a head is ever put together.
* ``latent_append`` — a token's latent row into its page, the pool
  aliased onto the output as ``kv_append`` does: only the pages written
  move.
* ``moe_gmm`` — the grouped expert matmul: rows sorted by expert, one
  visit per (row tile, expert) pair that share rows, an expert's matrices
  fetched once however many row tiles it spans and never where it
  received no row.  ``gated`` computes ``silu(x @ wg) * (x @ wu)`` in one
  pass over ``x``.  Two schedules, chosen from the call's shapes
  (:func:`gmm_schedule`): a decode step's handful of rows an expert takes
  small tiles, column blocks and the pipeline's own fetches; groups of a
  tile or more (prefill) take one grid step a visit at the whole output
  width, the grid exactly as long as the list of visits, and an expert's
  matrices copied by the kernel into one of two VMEM slots while the
  expert before it is still being visited.
* ``moe_rows_in`` / ``moe_combine`` — the rows into and out of the grouped
  matmuls through the sort order, the rows the experts here own and no
  others: a copy a row by hand, as many as a list built on the device
  says.  The chip holds a ``(rows, h)`` array in tiles of eight rows and
  no copy takes one row out of a tile, so what is fetched a row at a time
  is stored with its ROWS APART, ``(rows, 1, h)``: the tokens' rows on the
  way in, the down call's result (``moe_gmm``'s ``rows_apart``) on the way
  out.

The latent pool is stored ``(1, num_pages, page_size, width)``, a row
``[c_kv | k_r | zeros]`` with ``width`` the 576 values of the published
widths rounded up to whole 128-lane tiles, 640.  Asked in the sandbox
(PR 32), the chip's compiler holds a ``[pages, 16, 576]`` bfloat16 array
in ``T(8,128)(2,1)`` tiles with the lanes padded to 640 all the same, a
copy out of it cannot take 576 lanes (Mosaic: a slice must be aligned to
the tiling), and XLA re-laid the 576-wide pool around the append (a
pool-sized temporary); two pools (512 and 64) pad the 64 to a whole
tile, the same 640 lanes.  So the padding is stated in the shape: one
pool, one copy a page, exact tiles, and no program re-lays it.  Nothing
but these kernels touches the pool on the chip.

Engage rules follow ``paged_attention``: kernel on TPU or under
``PT_PALLAS_INTERPRET=1``, the jnp composition elsewhere.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_kernels import (DEFAULT_MASK_VALUE, LANES, _interpret,
                             _use_pallas, own_jit)

#: pages one ``mla_decode`` grid step scores (1,024 tokens at 16 a page).
#: Measured on the chip (PR 33, 126 rows at 2.7 k of context): a step that
#: holds context costs about half a microsecond whatever it scores (the
#: wait, two matmuls and the softmax between them in one dependent chain)
#: and about 32 ns a page it fetches (the copies: 625 GB/s); a step that
#: holds none cost about 5 ns.  16 pages a step read 1.44 ms a call, 32
#: 1.09, 64 1.0 and 128 1.08 (half a chunk a row is fetched for nothing),
#: and with the fetch cut to the groups that hold context 64 read 0.88,
#: 128 0.84 (0.35 and 0.40 at 700 tokens of context)
DECODE_PAGES_PER_STEP = 64
#: pages one group of a chunk's copies moves (256 tokens): the groups past
#: the context are not fetched, a loop over groups and not over pages
DECODE_PAGES_PER_FETCH = 16
#: the widest block table, in pages, a serving engine feeds a decode form
#: of this kernel whatever its contexts hold (the widest any cell has run):
#: the grid is the list of chunks that hold context, so a column past a
#: row's context is int32 in SMEM (512 KiB at 128 rows) and an entry of the
#: list that repeats its last, never a step and never a fetch.  One width is
#: one program a batch bucket where a width a bucket of contexts was three
#: to six; beyond this many pages the engine buckets by the contexts again
DECODE_TABLE_PAGES = 1024


# ==========================================================================
# mla_decode
# ==========================================================================
def mla_decode_reference(q_lat, q_rope, pool, block_tables, context_lens,
                         scale):
    """Gather oracle and CPU fallback.  ``q_lat`` (n, heads, r), ``q_rope``
    (n, heads, dr), ``pool`` (1, pages, page_size, r + dr), ``block_tables``
    (n, w), ``context_lens`` (n,) true lengths, the current token's row
    already in the pool.  Returns ``o_lat`` (n, heads, r) float32: the
    softmax-weighted sum of the latent rows, before ``W_kvb^V``."""
    n, _, r = q_lat.shape
    rows = jnp.take(pool[0], block_tables.reshape(-1), axis=0)
    rows = rows.reshape(n, -1, pool.shape[-1]).astype(jnp.float32)
    c, kr = rows[..., :r], rows[..., r:r + q_rope.shape[-1]]
    s = jnp.einsum("nhr,ntr->nht", q_lat.astype(jnp.float32), c) \
        + jnp.einsum("nhd,ntd->nht", q_rope.astype(jnp.float32), kr)
    s = s * scale
    pos = lax.broadcasted_iota(jnp.int32, (n, 1, s.shape[-1]), 2)
    s = jnp.where(pos < context_lens[:, None, None], s, DEFAULT_MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("nht,ntr->nhr", p, c)


def decode_chunks(width, step=None):
    """``(pages, n_chunks)``: a grid step of ``mla_decode`` scores a chunk
    of ``pages`` pages, and a table ``width`` pages wide is ``n_chunks``
    of them (``step``: the module's pages a step unless given)."""
    pages = min(step or DECODE_PAGES_PER_STEP, width)
    return pages, -(-width // pages)


def live_chunks(context_lens, tokens, n_chunks):
    """Chunks of ``tokens`` positions each row's context reaches into: at
    least one (a row of no context still opens and writes its output), at
    most the table's.  Plain operators: numpy on the host, jnp traced."""
    return (-(-context_lens // tokens)).clip(1, n_chunks)


def decode_work_list(context_lens, tokens, n_chunks):
    """The walk of ``mla_decode`` as ``(row, chunk, n_live)``: every (row,
    chunk) pair that holds context once, rows in order, a row's chunks
    ascending.  The lists are as long as every row walking its whole
    table (static); their first ``n_live`` entries are the walk, and the
    rest repeat the last of them."""
    cnt = live_chunks(context_lens.astype(jnp.int32), tokens, n_chunks)
    ends = jnp.cumsum(cnt)
    n_live = ends[-1]
    v = jnp.minimum(jnp.arange(cnt.shape[0] * n_chunks, dtype=jnp.int32),
                    n_live - 1)
    row = jnp.sum(ends[None, :] <= v[:, None], axis=1, dtype=jnp.int32)
    chunk = v - (ends - cnt)[row]
    return row, chunk, n_live


def decode_walk_counts(context_lens, width, page_size):
    """What one ``mla_decode`` call over these contexts (a host array, one
    a row) and tables ``width`` pages wide walks, by the sizes the
    kernel's wrapper uses: ``(grid steps, chunks the tables span)``; the
    grid is the list of chunks that hold context and no longer."""
    pages, n_chunks = decode_chunks(width)
    live = int(live_chunks(context_lens, pages * page_size, n_chunks).sum())
    return live, len(context_lens) * n_chunks


def _mla_decode_kernel(bt_ref, cl_ref, row_ref, chunk_ref, nl_ref, ql_ref,
                       qr_ref, pool_ref, o_ref, buf, sem, m_scr, l_scr,
                       acc_scr, *, scale, pages, fetch, rank, rope, reps):
    """Grid step ``v`` of ``n_live``: chunk ``chunk[v]`` (``pages`` pages)
    of row ``row[v]``; the grid is as long as the list, so every step
    holds context.  Visit ``v`` lies in buffer ``v % 2``; it was started by
    visit ``v - 1`` WHATEVER ROW that was (by itself where ``v == 0``), and
    starts visit ``v + 1`` before it waits, so a row's first chunk is in
    flight under the last chunk of the row before.  A chunk is fetched by
    groups of ``fetch`` pages, as many as hold context: what the buffer
    holds past them is stale, and masked like the rest of a last page.
    The running max, sum and accumulator open on a row's first visit and
    are written out on its last."""
    v = pl.program_id(0)
    n_live = nl_ref[0]
    b, i = row_ref[v], chunk_ref[v]
    ctx = cl_ref[b]
    page_size = buf.shape[3]
    tokens, span = pages * page_size, fetch * page_size

    def groups_of(u):
        """Groups of visit ``u`` that hold context; at least one: a row of
        no context still has its one visit."""
        left = cl_ref[row_ref[u]] - chunk_ref[u] * tokens
        return jnp.clip((left + span - 1) // span, 1, pages // fetch)

    def start(u):
        """Start the copies of visit ``u``: a loop over its groups, a
        group's copies unrolled (a rolled loop a page is slow).  What is
        indexed by a traced value is taken once a group and the pages
        under it by plain integers: a kernel's trace costs by the traced
        index, and no compile cache keeps a trace."""
        table, first = row_ref[u] // reps, chunk_ref[u] * pages
        slot = u % 2

        def group(g, carry):
            base = first + g * fetch
            rows, done = buf.at[slot, g], sem.at[slot, g]
            for j in range(fetch):
                pltpu.make_async_copy(
                    pool_ref.at[0, bt_ref[table, base + j]], rows.at[j],
                    done).start()
            return carry

        lax.fori_loop(0, groups_of(u), group, 0)

    def wait(u):
        """Wait for visit ``u``: a group's copies signal one semaphore, so
        one wait the size of the group's rows takes them all."""
        n_groups, slot = groups_of(u), u % 2
        for g in range(pages // fetch):
            rows = buf.at[slot, g]

            @pl.when(g < n_groups)
            def _():
                pltpu.make_async_copy(rows, rows, sem.at[slot, g]).wait()

    @pl.when(v == 0)
    def _first():
        start(0)

    @pl.when(v + 1 < n_live)
    def _next():
        start(v + 1)

    @pl.when(i == 0)
    def _open():
        m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    wait(v)
    rows = buf[v % 2].reshape(tokens, buf.shape[-1])     # (tokens, r + dr)
    c_kv, k_r = rows[:, :rank], rows[:, rank:rank + rope]
    q_lat, q_rope = ql_ref[0], qr_ref[0]                 # (heads, r | dr)
    s = lax.dot_general(q_lat, c_kv, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    s += lax.dot_general(q_rope, k_r, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)
    s = s * scale
    pos = i * tokens + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < ctx, s, DEFAULT_MASK_VALUE)
    m_prev, l_prev = m_scr[...], l_scr[...]              # lane-broadcast
    m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.exp(s - m_next[:, :1])
    l_scr[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    # a row of the chunk past the context may hold anything (a page never
    # written, a group not fetched): its weight is exactly 0, but 0 * NaN
    # is NaN
    c_safe = jnp.where(
        i * tokens + lax.broadcasted_iota(jnp.int32, c_kv.shape, 0) < ctx,
        c_kv.astype(jnp.float32), 0.0).astype(c_kv.dtype)
    acc_scr[...] = acc_scr[...] * alpha[:, :1] + lax.dot_general(
        p.astype(c_kv.dtype), c_safe, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_next

    # the row's last visit: the next one is another row's, or none
    @pl.when(jnp.logical_or(
        v + 1 == n_live,
        row_ref[jnp.minimum(v + 1, row_ref.shape[0] - 1)] != b))
    def _done():
        l_fin = l_scr[...]
        l_safe = jnp.where(l_fin == 0.0, 1.0, l_fin)
        o_ref[0] = (acc_scr[...] / l_safe[:, :1]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "step", "fetch"))
def _mla_decode_call(q_lat, q_rope, pool, block_tables, context_lens, *,
                     scale, step, fetch):
    """The kernel's call, ``step`` pages a chunk fetched by groups of
    ``fetch``, over a grid of exactly the list's ``n_live`` steps (a grid
    may be as long as a value on the device says).  Under a ``jit`` of its
    own: the layers of a program share one trace and one lowering of it (a
    kernel's trace is Python time that no compile cache keeps)."""
    n, heads, rank = q_lat.shape
    rope = q_rope.shape[-1]
    _, _, page_size, width = pool.shape
    w = block_tables.shape[1]
    pages, n_chunks = decode_chunks(w, step)
    fetch = fetch if pages % fetch == 0 else pages
    if n_chunks * pages != w:
        block_tables = jnp.pad(block_tables,
                               ((0, 0), (0, n_chunks * pages - w)))
    row, chunk, n_live = decode_work_list(context_lens, pages * page_size,
                                          n_chunks)

    def _q_idx(v, bt_ref, cl_ref, row_ref, chunk_ref, nl_ref):
        return (row_ref[v], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n_live,),
        in_specs=[
            pl.BlockSpec((1, heads, rank), _q_idx),
            pl.BlockSpec((1, heads, rope), _q_idx),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, heads, rank), _q_idx),
        scratch_shapes=[
            pltpu.VMEM((2, pages // fetch, fetch, page_size, width),
                       pool.dtype),
            pltpu.SemaphoreType.DMA((2, pages // fetch)),
            pltpu.VMEM((heads, LANES), jnp.float32),
            pltpu.VMEM((heads, LANES), jnp.float32),
            pltpu.VMEM((heads, rank), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_mla_decode_kernel, scale=scale, pages=pages,
                          fetch=fetch, rank=rank, rope=rope,
                          reps=n // block_tables.shape[0]),
        name="mla_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, heads, rank), jnp.float32),
        interpret=_interpret(),
    )(block_tables.astype(jnp.int32), context_lens.astype(jnp.int32), row,
      chunk, n_live[None], q_lat.astype(pool.dtype),
      q_rope.astype(pool.dtype), pool)


def decode_engages(page_size, heads) -> bool:
    """Whether ``mla_decode`` runs its kernel for these sizes here."""
    return _use_pallas() and page_size % 8 == 0 and heads % 8 == 0


def mla_decode(q_lat, q_rope, pool, block_tables, context_lens, scale):
    """Absorbed latent attention of one query row a sequence over the paged
    latent pool (shapes as :func:`mla_decode_reference`; ``block_tables``
    may hold fewer rows than ``q_lat``: one table for ``n / rows``
    consecutive rows each, a verify call).  The kernel wants a page of
    whole sublane groups and heads a multiple of 8."""
    n, heads, _ = q_lat.shape
    page_size = pool.shape[2]
    if decode_engages(page_size, heads):
        return own_jit(_mla_decode_call)(
            q_lat, q_rope, pool, block_tables, context_lens,
            scale=float(scale), step=DECODE_PAGES_PER_STEP,
            fetch=DECODE_PAGES_PER_FETCH)
    if block_tables.shape[0] != n:
        block_tables = jnp.repeat(block_tables, n // block_tables.shape[0],
                                  axis=0)
    return mla_decode_reference(q_lat, q_rope, pool, block_tables,
                                context_lens, scale)


# ==========================================================================
# mla_prefill
# ==========================================================================
#: query rows (and key rows) a grid step of ``mla_prefill`` scores
PREFILL_BLOCK = 512


def mla_prefill_reference(q_nope, q_rope, k_nope, k_r, v, scale):
    """Oracle and fallback: causal attention of one sequence, heads first.
    ``q_nope``/``k_nope`` (heads, s, dn), ``q_rope`` (heads, s, dr), ``k_r``
    (s, dr) shared by the heads, ``v`` (heads, s, dv).  Query blocks of
    ``PREFILL_BLOCK`` rows each see the keys up to their own end (static
    slices), so the scores of a long prompt are never one array.  Returns
    (heads, s, dv) float32."""
    s = q_nope.shape[1]
    bq = min(PREFILL_BLOCK, s)
    out = []
    for lo in range(0, s, bq):
        hi = min(lo + bq, s)
        sc = jnp.einsum("hqd,hkd->hqk", q_nope[:, lo:hi], k_nope[:, :hi],
                        preferred_element_type=jnp.float32)
        sc += jnp.einsum("hqd,kd->hqk", q_rope[:, lo:hi], k_r[:hi],
                         preferred_element_type=jnp.float32)
        rows = lo + lax.broadcasted_iota(jnp.int32, (hi - lo, hi), 0)
        cols = lax.broadcasted_iota(jnp.int32, (hi - lo, hi), 1)
        sc = jnp.where(cols <= rows, sc * scale, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        out.append(jnp.einsum("hqk,hkd->hqd", p.astype(v.dtype), v[:, :hi],
                              preferred_element_type=jnp.float32))
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)


def _mla_prefill_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref,
                        m_scr, l_scr, acc_scr, *, scale, block, n_blocks):
    """Grid step ``(h, qi, ki)``: query block ``qi`` of head ``h`` against
    key block ``ki``, online softmax down ``ki``.  Blocks above the
    diagonal do nothing (their index maps stay on the diagonal block, so
    nothing is fetched for them either)."""
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(ki <= qi)
    def _score():
        v = v_ref[0]
        s = lax.dot_general(qn_ref[0], kn_ref[0], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        s += lax.dot_general(qr_ref[0], kr_ref[...], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        rows = qi * block + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = ki * block + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(rows >= cols, s * scale, DEFAULT_MASK_VALUE)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next[:, :1])
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_next

    @pl.when(ki == n_blocks - 1)
    def _done():
        o_ref[0] = (acc_scr[...] / l_scr[...][:, :1]).astype(o_ref.dtype)


def _mla_prefill_call(q_nope, q_rope, k_nope, k_r, v, scale):
    heads, s, dn = q_nope.shape
    dr, dv = q_rope.shape[-1], v.shape[-1]
    block = min(PREFILL_BLOCK, s)
    n = s // block

    def _q_idx(h, qi, ki):
        return (h, qi, 0)

    def _k_idx(h, qi, ki):
        return (h, jnp.minimum(ki, qi), 0)

    return pl.pallas_call(
        functools.partial(_mla_prefill_kernel, scale=scale, block=block,
                          n_blocks=n),
        name="mla_prefill",
        grid=(heads, n, n),
        in_specs=[
            pl.BlockSpec((1, block, dn), _q_idx),
            pl.BlockSpec((1, block, dr), _q_idx),
            pl.BlockSpec((1, block, dn), _k_idx),
            pl.BlockSpec((block, dr),
                         lambda h, qi, ki: (jnp.minimum(ki, qi), 0)),
            pl.BlockSpec((1, block, dv), _k_idx),
        ],
        out_specs=pl.BlockSpec((1, block, dv), _q_idx),
        out_shape=jax.ShapeDtypeStruct((heads, s, dv), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block, LANES), jnp.float32),
            pltpu.VMEM((block, LANES), jnp.float32),
            pltpu.VMEM((block, dv), jnp.float32),
        ],
        interpret=_interpret(),
    )(q_nope, q_rope, k_nope, k_r, v)


def mla_prefill(q_nope, q_rope, k_nope, k_r, v, scale):
    """Expanded causal attention of one prompt (shapes as
    :func:`mla_prefill_reference`).  The kernel wants the prompt's bucket in
    whole blocks: a multiple of ``PREFILL_BLOCK``, or one block of whole
    lane tiles."""
    s = q_nope.shape[1]
    if _use_pallas() and (s % PREFILL_BLOCK == 0
                          or (s < PREFILL_BLOCK and s % LANES == 0)):
        return _mla_prefill_call(q_nope, q_rope, k_nope, k_r, v,
                                 float(scale))
    return mla_prefill_reference(q_nope, q_rope, k_nope, k_r, v, scale)


# ==========================================================================
# latent_append
# ==========================================================================
def _pad_rows(pool, rows):
    """``rows`` (tokens, values) widened with zeros to the pool's row."""
    pad = pool.shape[-1] - rows.shape[-1]
    rows = rows.astype(pool.dtype)
    return jnp.pad(rows, ((0, 0), (0, pad))) if pad else rows


def latent_append_reference(pool, rows, slots):
    """Scatter oracle and CPU fallback: ``rows`` (tokens, width) to flat
    ``slots`` of ``pool`` (1, pages, page_size, width); a slot outside the
    pool (the allocator's pad sentinel) drops its row."""
    page_size = pool.shape[2]
    rows = _pad_rows(pool, rows)
    page, off = slots // page_size, slots % page_size
    return pool.at[0, page, off, :].set(rows.astype(pool.dtype), mode="drop")


def _latent_append_kernel(page_ref, off_ref, order_ref, row_ref, blk_in,
                          blk_out):
    """Grid step ``i`` writes sorted token ``i`` into its page's block:
    the block is copied in when the walk enters the page and written back
    when it leaves (Pallas skips both while the block index repeats)."""
    del order_ref
    i = pl.program_id(0)
    off = off_ref[i]

    @pl.when(jnp.logical_or(
        i == 0, page_ref[i] != page_ref[jnp.maximum(i - 1, 0)]))
    def _open():
        blk_out[...] = blk_in[...]

    @pl.when(off >= 0)
    def _write():
        cur = blk_out[0, 0]                              # (page_size, width)
        at = lax.broadcasted_iota(jnp.int32, cur.shape, 0) == off
        # 32 bits wide: the vector unit has no 16-bit compare/select
        blk_out[0, 0] = jnp.where(
            at, row_ref[0].astype(jnp.float32),
            cur.astype(jnp.float32)).astype(cur.dtype)


def _latent_append_call(pool, rows, slots):
    _, n_pages, page_size, width = pool.shape
    valid = (slots >= 0) & (slots < n_pages * page_size)
    page, off = lax.div(slots, page_size), lax.rem(slots, page_size)
    # pads sort last and ride on the last page a real token wrote
    order = jnp.argsort(jnp.where(valid, page, jnp.iinfo(jnp.int32).max))
    order = order.astype(jnp.int32)
    page = jnp.where(valid, page, jnp.max(jnp.where(valid, page, 0)))[order]
    off = jnp.where(valid, off, -1)[order]
    block = pl.BlockSpec((1, 1, page_size, width),
                         lambda i, page, off, order: (0, page[i], 0, 0))
    return pl.pallas_call(
        _latent_append_kernel,
        name="latent_append",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(slots.shape[0],),
            in_specs=[pl.BlockSpec((1, 1, width),
                                   lambda i, page, off, order:
                                   (order[i], 0, 0)),
                      block],
            out_specs=block),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={4: 0},     # after the three prefetch operands
        interpret=_interpret(),
    )(page, off, order, _pad_rows(pool, rows)[:, None, :], pool)


def latent_append(pool, rows, slots):
    """Write ``rows[t]`` to flat slot ``slots[t]`` of the latent pool and
    return the new pool (the operand, aliased, on the chip)."""
    slots = slots.astype(jnp.int32)
    if _use_pallas() and pool.shape[2] % 8 == 0:
        return _latent_append_call(pool, rows, slots)
    return latent_append_reference(pool, rows, slots)


# ==========================================================================
# moe_gmm
# ==========================================================================
def moe_gmm_reference(x, weights, group_sizes, gated: bool,
                      out_dtype=jnp.float32):
    """Oracle and CPU fallback: ``x`` (rows, k) sorted by expert,
    ``weights`` one ``(experts, k, n)`` array (two where ``gated``),
    ``group_sizes`` (experts,) rows each expert owns, in order.  Row ``i``
    of the result is ``x[i] @ w[expert of i]``; rows past the groups are
    zero.  One expert at a time would be a loop of 256; this is a masked
    sum over experts in blocks, which the tests' sizes afford."""
    rows = x.shape[0]
    ends = jnp.cumsum(group_sizes)
    owner = jnp.searchsorted(ends, jnp.arange(rows), side="right")
    live = (jnp.arange(rows) < ends[-1])[:, None]
    owner = jnp.minimum(owner, group_sizes.shape[0] - 1)

    def one(w):
        return jnp.einsum("mk,mkn->mn", x.astype(jnp.float32),
                          jnp.take(w, owner, axis=0).astype(jnp.float32))

    if gated:
        g, u = one(weights[0]), one(weights[1])
        y = jax.nn.silu(g) * u
    else:
        y = one(weights[0])
    return jnp.where(live, y, 0.0).astype(out_dtype)


#: elements the matrices of ONE expert may hold for a call to keep two
#: experts' worth in VMEM (the prefill schedule's two slots): 6 M is 48 MiB
#: of float32 slots, 24 MiB of bfloat16 (Kimi-Linear's gated call: 4.7 M)
GMM_AHEAD_MAX_ELEMENTS = 6 * 1024 * 1024


#: rows of a piece of a taller tile: a visit in which the expert does not
#: own the whole tile takes the product over the pieces it owns a row of
GMM_PIECE_ROWS = 128


class GmmSchedule(NamedTuple):
    """How one ``moe_gmm`` call walks: ``tm`` rows a tile, ``tn`` output
    columns a grid step, and whether the experts' matrices come by the
    kernel's own copies one expert ahead (``ahead``: the grid is then the
    list of visits and no longer) or by the pipeline's, a grid step ahead
    (the grid is ``n // tn`` times the most visits there can be)."""
    tm: int
    tn: int
    ahead: bool


def gmm_schedule(rows, experts, k, n, n_weights) -> GmmSchedule:
    """The schedule of a call, from what the call can observe: its shapes.

    *Decode* (a handful of rows an expert, ``rows < 16 * experts``): small
    tiles, column blocks, the pipeline's own fetches.  It is bound by the
    weights it streams and reads 89 % of that bound (PR 33): left as it was.

    *Prefill* (groups of a tile or more): one grid step a (row tile,
    expert) visit at the whole output width, an expert's matrices fetched
    by the kernel when the expert BEFORE it starts its visits.  Measured on
    the chip at the cells' shapes (PR 38, `PERF.md` section 6): the
    pipeline starts a step's fetches one step ahead, so an expert's 6 MB
    hid behind the last visit of the one before it alone (3.57 ms a JoyAI
    gated call of 256 experts astride two tiles each; 2.45 with the copy
    one expert ahead, which is what the copies alone take, 2.43, the
    products alone 2.26).  Pieces of 64 or 32 rows change that by 1 %, and
    tiles of 64 rows read 28 % more.  A tile of 256 rows where the groups
    are that large, a boundary visit taking the product over the 128-row
    pieces the expert owns (Kimi-Linear's down call, a quarter of 65,536
    rows owned by 64 experts: 0.66 ms against 0.83 at tiles of 128, the
    same products in two thirds of the grid steps, and 0.75 with the whole
    tile's product on every visit; JoyAI's 128 rows an expert read 3 %
    more at it than at tiles of 128).  Matrices too large for two experts'
    worth of VMEM keep column blocks and the pipeline's fetch."""
    tm = _gmm_tile_rows(rows, experts)
    if rows < 16 * experts or n_weights * k * n > GMM_AHEAD_MAX_ELEMENTS:
        return GmmSchedule(tm, _pick_tn(n), False)
    return GmmSchedule(tm, n, True)


def _gmm_tile_rows(rows, experts) -> int:
    if rows < 16 * experts:
        return min(32, -(-rows // 16) * 16)
    return 256 if rows >= 256 * experts else 128


def _tiles_of_groups(sizes, tm):
    """Row tiles of ``tm`` rows each group reaches into, 0 where it owns no
    row.  Plain operators: numpy on the host, jnp traced."""
    ends = sizes.cumsum()
    return ((ends - 1) // tm - (ends - sizes) // tm + 1) * (sizes > 0)


def gmm_walk_counts(group_sizes, rows):
    """What one ``moe_gmm`` call of ``rows`` rows over these groups (a host
    array, rows an expert) walks, by the tile its wrapper uses: ``(row
    tiles, visits)``, the tiles that hold a row some expert owns and the
    (row tile, expert) pairs that take a product.  An expert that straddles
    a tile boundary visits both tiles: ``visits / row tiles`` is near 2
    where the groups are about a tile each, near 1 where they are many."""
    sizes = np.asarray(group_sizes, np.int64)
    tm = _gmm_tile_rows(rows, len(sizes))
    return int(-(-sizes.sum() // tm)), int(_tiles_of_groups(sizes, tm).sum())


def _gmm_work_list(sizes, tm, tiles):
    """The walk of ``moe_gmm``: every (row tile, expert) pair in which the
    expert owns a row, experts in order, an expert's tiles ascending, as
    ``(group, tile, ends, n_visits, next, slot)``.  The lists are as long
    as the most visits there can be (static); their first ``n_visits``
    entries are the walk and the rest repeat the last of them.  ``next``:
    the next expert that owns a row (-1: none); ``slot``: which of the two
    weight slots a visit's expert sits in (its ordinal among those that own
    rows, odd or even)."""
    experts = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    first = (ends - sizes) // tm
    n_tiles = _tiles_of_groups(sizes, tm)
    visits = tiles + experts - 1                # the most there can be
    group = jnp.repeat(jnp.arange(experts, dtype=jnp.int32), n_tiles,
                       total_repeat_length=visits)
    v_start = jnp.cumsum(n_tiles) - n_tiles
    tile = first[group] + jnp.arange(visits, dtype=jnp.int32) - v_start[group]
    n_visits = jnp.sum(n_tiles)
    # visits past the last real one stay on its blocks and compute nothing
    last = jnp.maximum(n_visits - 1, 0)
    live = jnp.arange(visits) < n_visits
    group = jnp.clip(jnp.where(live, group, group[last]), 0, experts - 1)
    tile = jnp.clip(jnp.where(live, tile, tile[last]), 0, tiles - 1)
    owns = sizes > 0
    after = lax.cummin(jnp.where(owns, jnp.arange(experts, dtype=jnp.int32),
                                 experts), reverse=True)
    nxt = jnp.concatenate([after[1:], jnp.full((1,), experts, jnp.int32)])
    nxt = jnp.where(nxt < experts, nxt, -1)
    slot = (jnp.cumsum(owns.astype(jnp.int32)) - 1) % 2
    return (group, tile, ends, n_visits[None].astype(jnp.int32), nxt[group],
            slot[group])


def _moe_gmm_kernel(group_ref, tile_ref, ends_ref, n_ref, *refs, gated, tm,
                    ahead):
    """Visit ``v`` pairs row tile ``tile[v]`` with expert ``group[v]``; the
    rows of the tile that the expert owns take ``x @ w[expert]``, the rest
    keep what an earlier visit of the tile wrote (zero on the tile's first
    visit).  ``ahead``: the weights are whole arrays in HBM and the kernel
    keeps two experts' matrices in ``bufs``; an expert's first visit waits
    for its own and starts the NEXT expert's, which then has all of this
    expert's visits to hide behind."""
    nw = 2 if gated else 1
    if ahead:
        next_ref, slot_ref, *refs = refs
    x_ref, w_refs, o_ref = refs[0], refs[1:1 + nw], refs[1 + nw]
    if len(o_ref.shape) == 3:       # rows apart: ``(tm, 1, n)``
        o_ref = o_ref.at[:, 0]
    v = pl.program_id(0 if ahead else 1)
    g, t = group_ref[v], tile_ref[v]

    @pl.when(jnp.logical_or(
        v == 0, t != tile_ref[jnp.maximum(v - 1, 0)]))
    def _open():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    def lowest():
        """The first row expert ``g`` owns."""
        return jnp.where(g == 0, 0, ends_ref[jnp.maximum(g - 1, 0)])

    @pl.when(v < n_ref[0])
    def _visit():
        if ahead:
            bufs, sem = refs[2 + nw:2 + 2 * nw], refs[2 + 2 * nw]
            slot = slot_ref[v]

            def copy(e, s, i):
                return pltpu.make_async_copy(w_refs[i].at[e], bufs[i].at[s],
                                             sem.at[i, s])

            @pl.when(v == 0)
            def _first():
                for i in range(nw):
                    copy(g, slot, i).start()

            @pl.when(jnp.logical_or(
                v == 0, g != group_ref[jnp.maximum(v - 1, 0)]))
            def _turn():
                @pl.when(next_ref[v] >= 0)
                def _fetch_ahead():
                    for i in range(nw):
                        copy(next_ref[v], 1 - slot, i).start()

                for i in range(nw):
                    copy(g, slot, i).wait()

            def weight(i):
                return bufs[i][slot]
        else:
            def weight(i):
                return w_refs[i][0]

        def product(r0, size):
            """Rows ``r0 .. r0 + size`` of the tile (static)."""
            x = x_ref[r0:r0 + size, :]
            y = jnp.dot(x, weight(0), preferred_element_type=jnp.float32)
            if gated:
                u = jnp.dot(x, weight(1),
                            preferred_element_type=jnp.float32)
                y = y * jax.nn.sigmoid(y) * u
            # no ``+ 0`` at a tile's one piece: the decode-size kernel stays
            # the module it was, operation for operation
            row = (t * tm + r0 if r0 else t * tm) \
                + lax.broadcasted_iota(jnp.int32, y.shape, 0)
            mine = (row >= lowest()) & (row < ends_ref[g])
            o_ref[r0:r0 + size, :] = jnp.where(
                mine, y, o_ref[r0:r0 + size, :].astype(jnp.float32)) \
                .astype(o_ref.dtype)

        if tm <= GMM_PIECE_ROWS:
            product(0, tm)
        else:
            # a tile of several pieces: the whole tile in one product where
            # the expert owns all of it, else the pieces it owns a row of
            lo, hi = lowest(), ends_ref[g]
            whole = (lo <= t * tm) & (hi >= (t + 1) * tm)
            pl.when(whole)(functools.partial(product, 0, tm))

            @pl.when(jnp.logical_not(whole))
            def _pieces():
                for r0 in range(0, tm, GMM_PIECE_ROWS):
                    pl.when((hi > t * tm + r0)
                            & (lo < t * tm + r0 + GMM_PIECE_ROWS))(
                        functools.partial(product, r0, GMM_PIECE_ROWS))


def _pick_tn(n: int) -> int:
    for tn in (512, 384, 256, 128):
        if n % tn == 0:
            return tn
    return n


@functools.partial(jax.jit,
                   static_argnames=("gated", "out_dtype", "rows_apart"))
def _moe_gmm_call(x, weights, group_sizes, *, gated, out_dtype,
                  rows_apart=False):
    """The kernel's call by :func:`gmm_schedule`.  Under a ``jit`` of its
    own: the layers of a program share one trace and one lowering.  A tile
    no expert owns a row of is never visited: its rows are whatever the
    buffer held.  ``rows_apart``: the result is ``(rows, 1, n)``, which the
    chip stores a row after a row (a ``(rows, n)`` array lies in tiles of
    eight rows, and no copy can take one row out of a tile), for
    ``moe_combine`` to fetch rows of."""
    rows, k = x.shape
    experts, _, n = weights[0].shape
    tm, tn, ahead = gmm_schedule(rows, experts, k, n, len(weights))
    padded = -(-rows // tm) * tm
    if padded != rows:
        x = jnp.pad(x, ((0, padded - rows), (0, 0)))
    tiles = padded // tm
    lists = _gmm_work_list(group_sizes.astype(jnp.int32), tm, tiles)
    def out_block(r, c):
        return (r, 1, c) if rows_apart else (r, c)

    def out_at(t, j):
        return (t, 0, j) if rows_apart else (t, j)

    if ahead:
        # the grid is the list of visits and no longer (a grid may be as
        # long as a value on the device says); one step where it is empty
        grid = (jnp.maximum(lists[3][0], 1),)
        semantics = ("arbitrary",)
        x_spec = pl.BlockSpec((tm, k), lambda v, group, tile, *_:
                              (tile[v], 0))
        o_spec = pl.BlockSpec(out_block(tm, n), lambda v, group, tile, *_:
                              out_at(tile[v], 0))
        w_specs = [pl.BlockSpec(memory_space=pl.ANY)] * len(weights)
        scratch = [pltpu.VMEM((2, k, n), w.dtype) for w in weights] \
            + [pltpu.SemaphoreType.DMA((len(weights), 2))]
    else:
        lists = lists[:4]           # no expert is fetched ahead
        grid = (n // tn, tiles + experts - 1)
        semantics = ("parallel", "arbitrary")
        x_spec = pl.BlockSpec((tm, k), lambda j, v, group, tile, *_:
                              (tile[v], 0))
        o_spec = pl.BlockSpec(out_block(tm, tn),
                              lambda j, v, group, tile, *_:
                              out_at(tile[v], j))
        w_specs = [pl.BlockSpec((1, k, tn), lambda j, v, group, *_:
                                (group[v], 0, j))] * len(weights)
        scratch = []
    out = pl.pallas_call(
        functools.partial(_moe_gmm_kernel, gated=gated, tm=tm, ahead=ahead),
        name="moe_gmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(lists), grid=grid,
            in_specs=[x_spec] + w_specs, out_specs=o_spec,
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct(out_block(padded, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics,
            vmem_limit_bytes=(96 if ahead else 64) * 1024 * 1024),
        interpret=_interpret(),
    )(*lists, x, *weights)
    return out[:rows]


def gmm_engages(k, n) -> bool:
    """Whether ``moe_gmm`` runs its kernel for these widths here."""
    return _use_pallas() and k % LANES == 0 and n % LANES == 0


def moe_gmm(x, weights, group_sizes, gated: bool = False,
            out_dtype=jnp.float32, rows_apart: bool = False):
    """Grouped matmul over rows sorted by expert (shapes as
    :func:`moe_gmm_reference`): operands in the weights' type, accumulated
    in float32, the result in ``out_dtype``.  The kernel wants the
    contraction and the output width in whole lanes.  ROWS PAST THE GROUPS
    ARE UNSPECIFIED: the kernel writes the tiles it visits, so a tile no
    expert owns a row of holds whatever the buffer held (a NaN, for all the
    caller knows), and a pass to blank it would be an XLA operation of
    output size.  A caller reads no such row unmasked
    (``mla_ops.experts_forward``'s combine fetches the owned rows alone, or
    selects); fed back as
    ``x`` they reach no row an expert owns: a row's result is its own
    row's product, and the tiles past the groups are never visited.
    ``rows_apart``: the result is ``(rows, 1, n)``, for ``moe_combine``."""
    k, n = weights[0].shape[1:]
    x = x.astype(weights[0].dtype)
    if gmm_engages(k, n):
        return own_jit(_moe_gmm_call)(
            x, tuple(weights), group_sizes, gated=gated,
            out_dtype=jnp.dtype(out_dtype), rows_apart=rows_apart)
    out = moe_gmm_reference(x, weights, group_sizes, gated, out_dtype)
    return out[:, None, :] if rows_apart else out


# ==========================================================================
# moe_rows_in, moe_combine
# ==========================================================================
#: sorted rows a grid step of ``moe_rows_in`` fetches, and the bytes its two
#: buffers of them may take (a row of 6,144 float32 lanes: 128 rows a step)
ROWS_IN_TILE = 256
ROWS_IN_BUFFER_BYTES = 8 * 1024 * 1024
#: tokens a grid step of ``moe_combine`` sums, and the bytes its two buffers
#: of their ``k`` rows each may take (12 rows of 6,144 lanes a token: 32)
COMBINE_TOKENS = 128
COMBINE_BUFFER_BYTES = 40 * 1024 * 1024
#: a block of a list in SMEM is a whole number of these, or the whole list
SMEM_BLOCK = 1024


def _halved_to_fit(rows: int, row_bytes: int, room: int) -> int:
    """``rows``, halved until two buffers of them fit ``room``."""
    while 2 * rows * row_bytes > room and rows > 8:
        rows //= 2
    return rows


def moe_rows_in_reference(x, order, total, k: int, dtype):
    """Oracle and fallback: EVERY sorted row, ``x[order[p] // k]``."""
    del total
    return jnp.take(x, order // k, axis=0).astype(dtype)


def _rows_in_kernel(tok_ref, x_ref, o_ref, buf, sem, *, tile):
    """Grid step ``i`` of ``ceil(total / tile)``: sorted rows ``i * tile``
    and on, a copy a row out of ``x`` (in HBM, rows apart) through ``tok``
    into buffer ``i % 2``; started by step ``i - 1`` (by itself where ``i ==
    0``).  A tile's copies signal one semaphore, so one wait the size of
    the tile takes them all: the rows of the last tile past ``total`` are
    fetched too (``tok`` names a real token for every sorted row), a tile's
    worth a call at most."""
    i = pl.program_id(0)

    def start(u):
        slot, base = u % 2, u * tile

        def eight(g, carry):
            for r in range(8):      # a rolled loop a row is slow
                pltpu.make_async_copy(
                    x_ref.at[tok_ref[base + g * 8 + r]],
                    buf.at[slot, g * 8 + r], sem.at[slot]).start()
            return carry

        lax.fori_loop(0, tile // 8, eight, 0)

    @pl.when(i == 0)
    def _first():
        start(0)

    @pl.when(i + 1 < pl.num_programs(0))
    def _next():
        start(i + 1)

    rows = buf.at[i % 2]
    pltpu.make_async_copy(rows, rows, sem.at[i % 2]).wait()
    o_ref[...] = rows[:, 0, :].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("k", "dtype"))
def _moe_rows_in_call(x, order, total, *, k, dtype):
    """The kernel's call over a grid as long as the tiles that hold an
    owned row (a value on the device).  Under a ``jit`` of its own: the
    layers of a program share one trace."""
    rows, h = order.shape[0], x.shape[1]
    tile = min(_halved_to_fit(ROWS_IN_TILE, h * x.dtype.itemsize,
                              ROWS_IN_BUFFER_BYTES), -(-rows // 8) * 8)
    padded = -(-rows // tile) * tile
    tok = (order // k).astype(jnp.int32)
    if padded != rows:
        tok = jnp.pad(tok, (0, padded - rows))
    out = pl.pallas_call(
        functools.partial(_rows_in_kernel, tile=tile),
        name="moe_rows_in",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(jnp.maximum(-(-total // tile), 1),),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile, h), lambda i, tok: (i, 0)),
            scratch_shapes=[pltpu.VMEM((2, tile, 1, h), x.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((padded, h), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
    )(tok, x[:, None, :])
    return out[:rows]


def moe_combine_reference(ys, order, total, weight):
    """Oracle and fallback: ``back`` the sorted row of every choice, all of
    them gathered, those past ``total`` (padding, another chip's experts:
    ``moe_gmm`` wrote none of them) selected away, and a token's ``k`` rows
    summed under their weights.  ``ys`` (rows, h) float32, ``order`` (rows,)
    the choice of each sorted row, ``weight`` (n, k)."""
    n, k = weight.shape
    back = jnp.argsort(order)
    y = jnp.take(ys, back, axis=0).reshape(n, k, -1)
    # by ``where``, never by a zero weight: an unwritten row may hold a NaN
    y = jnp.where((back < total).reshape(n, k, 1), y, 0.0)
    return jnp.einsum("nkh,nk->nh", y, weight)


def _combine_kernel(src_ref, place_ref, start_ref, ys_ref, w_ref, o_ref, buf,
                    sem, *, k):
    """Grid step ``i``: tokens ``i * tokens`` and on.  The owned choices of
    a step are entries ``start[i] .. start[i + 1]`` of the lists, in the
    tokens' order: ``src`` the sorted row of ``ys`` (in HBM, rows apart),
    ``place`` the choice's place in the step, ``t * k + j``.  A copy each
    into the next row of buffer ``i % 2``, started by step ``i - 1``; then,
    entry by entry, the running sum of a token's rows under their weights,
    opened anew where the token changes and stored at every entry (the last
    store of a token is its sum: no load of the output, no chain through
    memory).  A choice that is not owned has no entry: its row of ``ys`` is
    never read.  Loops by eights, a rolled loop an entry is slow."""
    i = pl.program_id(0)
    h = o_ref.shape[-1]

    def by_eights(lo, hi, eight, one, carry):
        """``one(q, carry)`` for ``q`` in ``lo .. hi``; ``eight(q, carry)``
        takes eight entries from ``q`` on."""
        groups = (hi - lo) // 8
        carry = lax.fori_loop(
            0, groups, lambda g, c: eight(lo + g * 8, c), carry)
        return lax.fori_loop(lo + groups * 8, hi, one, carry)

    def start(u):
        slot, base = u % 2, start_ref[u]

        def one(q, carry):
            pltpu.make_async_copy(ys_ref.at[src_ref[q]],
                                  buf.at[slot, q - base],
                                  sem.at[slot]).start()
            return carry

        def eight(q, carry):
            for r in range(8):
                one(q + r, carry)
            return carry

        by_eights(base, start_ref[u + 1], eight, one, 0)

    @pl.when(i == 0)
    def _first():
        start(0)

    @pl.when(i + 1 < pl.num_programs(0))
    def _next():
        start(i + 1)

    slot, base, end = i % 2, start_ref[i], start_ref[i + 1]
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    def wait(rows):
        def go(q, carry):
            pltpu.make_async_copy(ys_ref.at[pl.ds(0, rows)],
                                  buf.at[slot, pl.ds(0, rows)],
                                  sem.at[slot]).wait()
            return carry
        return go

    # copies may land in any order: all of the step's before any is read
    by_eights(base, end, wait(8), wait(1), 0)

    def add(q, carry):
        was, acc = carry
        place = place_ref[q]
        t = place // k
        acc = jnp.where(t == was, acc, 0.0) + w_ref[place] * buf[slot,
                                                                 q - base]
        o_ref[t] = acc
        return t, acc

    def add_eight(q, carry):
        for r in range(8):
            carry = add(q + r, carry)
        return carry

    by_eights(base, end, add_eight, add,
              (jnp.int32(-1), jnp.zeros((1, h), jnp.float32)))


def combine_work_list(order, total, n: int, k: int, h: int = 0):
    """``moe_combine``'s lists, built on the device: the owned choices
    (sorted rows ``p < total``) in the tokens' order, ``src`` the sorted row
    of each, ``place`` its place ``t * k + j`` in its grid step's tokens,
    and ``start`` (steps + 1,) where each step's entries begin, the last the
    number owned.  One sort (the path before took an ``argsort`` for
    ``back``); a choice that is not owned sorts past every step's entries,
    also where the last step's tokens run past ``n``.  Beside them the
    tokens a step sums (fewer where ``k`` rows of ``h`` lanes a token would
    outgrow the step's buffers) and the steps."""
    tokens = min(_halved_to_fit(COMBINE_TOKENS, k * h * 4,
                                COMBINE_BUFFER_BYTES), -(-n // 8) * 8)
    steps = -(-n // tokens)
    width = k * tokens
    p = jnp.arange(n * k, dtype=jnp.int32)
    choice, src = lax.sort(
        (jnp.where(p < total, order.astype(jnp.int32), steps * width), p),
        num_keys=1)
    start = jnp.searchsorted(
        choice, jnp.arange(steps + 1, dtype=jnp.int32) * width,
        method="compare_all").astype(jnp.int32)
    return (src, choice % width, start), tokens, steps


@jax.jit
def _moe_combine_call(ys, order, total, weight):
    """The kernel's call over :func:`combine_work_list`.  Under a ``jit`` of
    its own."""
    n, k = weight.shape
    h = ys.shape[-1]
    lists, tokens, tiles = combine_work_list(order, total, n, k, h)
    span = k * tokens               # a step's choices, and their weights:
    # a block of the list in SMEM, so a whole number of SMEM_BLOCK where
    # there is more than one (8 choices x 128 tokens are; 12 x 32 are not)
    width = span if tiles == 1 else -(-span // SMEM_BLOCK) * SMEM_BLOCK
    weight = jnp.pad(weight, ((0, tiles * tokens - n), (0, 0))) \
        .reshape(tiles, span)
    weight = jnp.pad(weight, ((0, 0), (0, width - span))).reshape(-1)
    out = pl.pallas_call(
        functools.partial(_combine_kernel, k=k),
        name="moe_combine",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(tiles,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((width,), lambda i, *_: (i,),
                                   memory_space=pltpu.SMEM)],
            out_specs=pl.BlockSpec((tokens, 1, h), lambda i, *_: (i, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, span, 1, h), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((tiles * tokens, 1, h), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=_interpret(),
    )(*lists, ys, weight)
    return out[:n, 0, :]


def moe_rows_engage(rows, experts, h) -> bool:
    """Whether a call of ``rows`` sorted rows ``h`` wide moves them by
    ``moe_rows_in`` and ``moe_combine`` here."""
    return _use_pallas() and h % LANES == 0 and rows >= 16 * experts


def moe_rows_in(x, order, total, k: int, dtype):
    """The rows of ``x`` (n, h) in the sorted order of the ``n * k``
    choices: row ``p`` is ``x[order[p] // k]`` in ``dtype`` for ``p <
    total``, the rows the experts here own; THE ROWS PAST THEM ARE
    UNSPECIFIED (``moe_gmm`` visits no tile of them)."""
    return own_jit(_moe_rows_in_call)(x, order, total, k=k,
                                      dtype=jnp.dtype(dtype))


def moe_combine(ys, order, total, weight):
    """A token's sum of its owned choices' rows of ``ys`` (rows, 1, h)
    float32 (``moe_gmm``'s ``rows_apart`` result) under their weights:
    ``(n, h)`` float32.  A row past ``total`` is never read."""
    return own_jit(_moe_combine_call)(ys, order, total, weight)
