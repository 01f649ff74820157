"""Pallas TPU kernels of the grouped-query decoder with window layers
(inference/gqa_decoder.py), each beside the jnp composition that is its CPU
fallback and its test oracle.

* ``rope_half`` — rotary embedding in the half-rotated form (``rotate_half``:
  lane ``i`` pairs with lane ``i + r/2`` inside the first ``r`` lanes, the
  rest pass through), the frequencies a table (:func:`rope_frequencies`:
  plain, or YaRN's blend of interpolated and extrapolated ones), cos and sin
  times a factor (YaRN's ``attention_factor``).  Plain jnp: it fuses into the
  projection's consumer.
* ``gqa_prefill`` — causal flash attention of one whole prompt, ``heads``
  query heads over ``kv_heads`` key/value heads.  A grid step takes one K/V
  head, a block of query rows OF ALL THE HEADS OF ITS GROUP (``group *
  block`` rows of one product) and one key block, so a K/V block is read
  once for its 6 or 8 query heads.  With a window the key blocks wholly
  behind it are not visited, like those wholly in the future: the grid's
  last axis is as long as the longest walk of any query block
  (:func:`prefill_walk`), not the prompt.  The running maximum is tiled
  over the scores lane tile by lane tile and the running sum kept as
  lane-partial sums (one reduction across lanes and one broadcast a row
  group and visit, where there were two and two), and a layer without a
  window scores 512 keys an update.
* ``gqa_decode`` — one query row a sequence over the paged K/V pools
  ``(kv_heads, pages, page_size, head_dim)``.  A grid step is one (sequence,
  chunk of pages) and serves EVERY query head from one read of each page: a
  page's ``kv_heads`` slabs arrive in one strided copy a pool, the products
  are batched over the K/V heads with the group's 6 or 8 rows each.  The
  grid is a work list as ``mla_decode``'s: the chunks that hold attended
  positions and no more, the next visit's copies started before this one
  waits.  A window layer's walk starts at the page of position ``ctx -
  window`` in the row's own (short) table, whose first entry is the page of
  ``first`` (:func:`decode_span`): at most ``window / page_size + 1`` pages
  a sequence whatever its context.

Engage rules follow ``paged_attention``: kernel on TPU or under
``PT_PALLAS_INTERPRET=1`` (interpreted, at any size), the jnp composition
elsewhere.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .mla_kernels import decode_work_list
from .pallas_kernels import (DEFAULT_MASK_VALUE, LANES, _interpret,
                             _use_pallas, own_jit)

#: query rows a head a grid step of ``gqa_prefill`` scores, and the keys of
#: a window layer's step
PREFILL_BLOCK = 256
#: keys a grid step scores in a layer without a window
PREFILL_KEYS = 512
#: pages one ``gqa_decode`` grid step scores (512 tokens at 16 a page: a
#: window's walk is one chunk, or two where it straddles)
DECODE_PAGES_PER_STEP = 32
#: pages one group of a chunk's copies moves; groups past the context are
#: not fetched
DECODE_PAGES_PER_FETCH = 8
#: the widest block table, in pages, a serving engine feeds a decode form
#: of this kernel whatever its contexts hold: as ``mla_kernels.
#: DECODE_TABLE_PAGES`` (the grid is the work list, a column past a row's
#: walk is never visited)
DECODE_TABLE_PAGES = 1024


# ==========================================================================
# rope_half
# ==========================================================================
def rope_frequencies(rotated: int, base: float, yarn: dict | None = None):
    """The ``rotated / 2`` inverse frequencies of a rotary embedding over the
    first ``rotated`` lanes, float64.  Plain: ``base^(-2i/rotated)``.  With
    ``yarn`` (``factor``, ``original_max_position_embeddings``, ``beta_fast``,
    ``beta_slow``): below the dimension that turns ``beta_fast`` times over
    the original length the plain frequency, above the one that turns
    ``beta_slow`` times the frequency over ``factor``, a linear ramp
    between."""
    half = rotated // 2
    f = float(base) ** (np.arange(half, dtype=np.float64) * 2.0 / rotated)
    if not yarn:
        return 1.0 / f
    factor, orig = float(yarn["factor"]), \
        float(yarn["original_max_position_embeddings"])

    def dim_of(turns):
        return rotated * math.log(orig / (2.0 * math.pi * turns)) \
            / (2.0 * math.log(float(base)))

    low = max(math.floor(dim_of(float(yarn["beta_fast"]))), 0)
    high = min(math.ceil(dim_of(float(yarn["beta_slow"]))), rotated - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low),
                   0.0, 1.0)
    return (1.0 / (factor * f)) * ramp + (1.0 / f) * (1.0 - ramp)


def rope_half(x, positions, inv_freq, factor: float = 1.0):
    """``x`` (..., heads, d) at ``positions`` (...): the first ``2 *
    len(inv_freq)`` lanes turn in the half-rotated form, ``out[i] = x[i] cos
    - x[i + r/2] sin``, ``out[i + r/2] = x[i + r/2] cos + x[i] sin`` at the
    angle ``pos * inv_freq[i]``, cos and sin times ``factor``; the lanes past
    them pass through.  Computed in float32."""
    inv = jnp.asarray(inv_freq, jnp.float32)
    half = inv.shape[0]
    ang = positions.astype(jnp.float32)[..., None, None] * inv
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:2 * half]
    out = jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x32[..., 2 * half:]], axis=-1)
    return out.astype(x.dtype)


# ==========================================================================
# gqa_prefill
# ==========================================================================
def _mask(rows, cols, window: int):
    """Key ``cols`` a query at ``rows`` attends: itself and what lies before
    it, and with a window only the last ``window`` of those."""
    ok = cols <= rows
    return ok & (cols > rows - window) if window else ok


def gqa_prefill_reference(q, k, v, scale, window: int = 0):
    """Oracle and fallback: causal (windowed) attention of one sequence,
    heads first.  ``q`` (heads, s, d), ``k``/``v`` (kv_heads, s, d); head
    ``h`` reads K/V head ``h // group``.  Query blocks of ``PREFILL_BLOCK``
    rows see the keys from their window's start to their own end (static
    slices).  Returns (heads, s, d) float32."""
    heads, s, d = q.shape
    kvh = k.shape[0]
    q = q.reshape(kvh, heads // kvh, s, d)
    bq = min(PREFILL_BLOCK, s)
    out = []
    for lo in range(0, s, bq):
        hi = min(lo + bq, s)
        k0 = max(0, lo - window + 1) if window else 0
        sc = jnp.einsum("kgqd,ktd->kgqt", q[:, :, lo:hi], k[:, k0:hi],
                        preferred_element_type=jnp.float32)
        rows = lo + lax.broadcasted_iota(jnp.int32, (hi - lo, hi - k0), 0)
        cols = k0 + lax.broadcasted_iota(jnp.int32, (hi - lo, hi - k0), 1)
        sc = jnp.where(_mask(rows, cols, window), sc * scale, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        out.append(jnp.einsum("kgqt,ktd->kgqd", p.astype(v.dtype),
                              v[:, k0:hi],
                              preferred_element_type=jnp.float32))
    o = out[0] if len(out) == 1 else jnp.concatenate(out, axis=2)
    return o.reshape(heads, s, d)


def _first_key_block(qi, block: int, keys: int, window: int):
    """The first key block query block ``qi`` reaches (plain operators: a
    host integer, or traced in an index map)."""
    if not window:
        return qi * 0
    lo = qi * block - window + 1
    return (lo + abs(lo)) // 2 // keys           # max(lo, 0) // keys


def _last_key_block(qi, block: int, keys: int):
    """The key block that holds query block ``qi``'s last row: its diagonal."""
    return ((qi + 1) * block - 1) // keys


def prefill_walk(s: int, window: int = 0):
    """What one ``gqa_prefill`` call over a bucket of ``s`` rows walks, by
    the sizes the kernel's wrapper uses: ``(block, keys, steps, visited,
    causal)``: query rows and keys a block, the length of the grid's key
    axis (the longest walk of any query block), the (query block, key
    block) pairs that hold an unmasked pair, and what a causal walk without
    a window would visit.
    Without a window the keys are ``PREFILL_KEYS`` wide where the bucket
    holds whole ones: half as many updates of the running statistics a key.
    A window layer keeps the block: its walk is the window's edge and the
    diagonal, and a wider block scores more keys than it spares updates."""
    block = min(PREFILL_BLOCK, s)
    keys = PREFILL_KEYS if not window and s % PREFILL_KEYS == 0 else block
    spans, causal = [], 0
    for qi in range(-(-s // block)):
        last = _last_key_block(qi, block, keys)
        spans.append(last - int(_first_key_block(qi, block, keys, window))
                     + 1)
        causal += last + 1
    return block, keys, max(spans), sum(spans), causal


def _lanes(x, width: int):
    """Lane-replicated statistics ``(rows, LANES)`` at ``width`` lanes: whole
    lane tiles side by side, never a column broadcast back over the lanes
    (on the chip that is a cross-lane permute a row group, and those
    units, not the vector slots, set the pace of a visit).  The slice serves
    the tests' blocks of 8 and 16 alone: the chip's widths are whole tiles."""
    reps = -(-width // LANES)
    x = jnp.tile(x, (1, reps)) if reps > 1 else x
    return x if x.shape[1] == width else x[:, :width]


def _lane_sums(p):
    """``p`` (rows, width) summed into ``LANES`` partial sums a row: lane
    tile on lane tile, no reduction across lanes (a width that is no whole
    number of tiles, the tests' blocks, is padded with zeros)."""
    if p.shape[1] % LANES:
        p = jnp.pad(p, ((0, 0), (0, -p.shape[1] % LANES)))
    out = p[:, :LANES]
    for j in range(LANES, p.shape[1], LANES):
        out = out + p[:, j:j + LANES]
    return out


def _gqa_prefill_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                        scale, block, keys, steps, window):
    """Grid step ``(h, qi, r)``: query block ``qi`` of the ``group`` heads of
    K/V head ``h`` against key block ``first(qi) + r``, online softmax down
    ``r``.  Steps past the diagonal do nothing (their index maps stay on
    it, so nothing is fetched for them either).  The running maximum is
    lane-replicated and the running sum 128 lane-partial sums a row, summed
    across lanes once, at the end: a visit reduces across lanes once (the
    maximum) and broadcasts once (it, into ``m``)."""
    qi, r = pl.program_id(1), pl.program_id(2)
    ki = _first_key_block(qi, block, keys, window) + r
    group, _, d = q_ref.shape[1:]

    @pl.when(r == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(ki <= _last_key_block(qi, block, keys))
    def _score():
        q = q_ref[0].reshape(group * block, d)
        v = v_ref[0]
        s = lax.dot_general(q, k_ref[0], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        rows = qi * block + lax.broadcasted_iota(jnp.int32, (block, keys), 0)
        cols = ki * keys + lax.broadcasted_iota(jnp.int32, (block, keys), 1)
        # a row wholly masked in this block (behind its window) scores the
        # mask value everywhere and is wiped by its first real key
        # (``alpha`` 0): the diagonal block, visited last, holds the row's own
        s = jnp.where(_mask(rows, cols, window)[None],
                      s.reshape(group, block, keys) * scale,
                      DEFAULT_MASK_VALUE).reshape(group * block, keys)
        m_prev = m_scr[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - _lanes(m_next, keys))
        l_scr[...] = alpha * l_scr[...] + _lane_sums(p)
        acc_scr[...] = acc_scr[...] * _lanes(alpha, d) + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_next

    @pl.when(r == steps - 1)
    def _done():
        l = jnp.sum(l_scr[...], axis=1, keepdims=True)
        o_ref[0] = (acc_scr[...] / l).reshape(group, block, d) \
            .astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "window"))
def _gqa_prefill_call(q, k, v, *, scale, window):
    heads, s, d = q.shape
    kvh = k.shape[0]
    group = heads // kvh
    block, keys, steps = prefill_walk(s, window)[:3]

    def _q_idx(h, qi, r):
        return (h, 0, qi, 0)

    def _k_idx(h, qi, r):
        return (h, jnp.minimum(_first_key_block(qi, block, keys, window) + r,
                               _last_key_block(qi, block, keys)), 0)

    out = pl.pallas_call(
        functools.partial(_gqa_prefill_kernel, scale=scale, block=block,
                          keys=keys, steps=steps, window=window),
        name="gqa_prefill",
        grid=(kvh, s // block, steps),
        in_specs=[
            pl.BlockSpec((1, group, block, d), _q_idx),
            pl.BlockSpec((1, keys, d), _k_idx),
            pl.BlockSpec((1, keys, d), _k_idx),
        ],
        out_specs=pl.BlockSpec((1, group, block, d), _q_idx),
        out_shape=jax.ShapeDtypeStruct((kvh, group, s, d), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((group * block, LANES), jnp.float32),
            pltpu.VMEM((group * block, LANES), jnp.float32),
            pltpu.VMEM((group * block, d), jnp.float32),
        ],
        interpret=_interpret(),
    )(q.reshape(kvh, group, s, d), k, v)
    return out.reshape(heads, s, d)


def prefill_engages(s: int, d: int) -> bool:
    """Whether ``gqa_prefill`` runs its kernel for this bucket here: whole
    blocks, and on the chip whole lane tiles."""
    if not _use_pallas():
        return False
    whole = s % PREFILL_BLOCK == 0 or s < PREFILL_BLOCK
    return whole and (_interpret() or (d % LANES == 0 and s % LANES == 0))


def gqa_prefill(q, k, v, scale, window: int = 0):
    """Causal (windowed) grouped-query attention of one prompt (shapes as
    :func:`gqa_prefill_reference`)."""
    if prefill_engages(q.shape[1], q.shape[2]):
        return own_jit(_gqa_prefill_call)(q, k, v, scale=float(scale),
                                          window=int(window))
    return gqa_prefill_reference(q, k, v, scale, window)


# ==========================================================================
# gqa_decode
# ==========================================================================
def decode_span(context_lens, first, page_size: int, window: int):
    """``(lo, p0, n_pages)`` a row: the first position it attends, that
    position's page as an index into the row's table (whose entry 0 is the
    page of position ``first``), and the pages from there to the context's
    end (at least one).  Plain operators: numpy on the host, jnp traced."""
    lo = (context_lens - window).clip(0) if window else context_lens * 0
    base = first // page_size
    p0 = lo // page_size - base
    n_pages = (-(-context_lens // page_size) - base - p0).clip(1)
    return lo, p0, n_pages


def decode_chunks(width: int, step: int | None = None):
    """``(pages, n_chunks)``: a grid step of ``gqa_decode`` scores a chunk of
    ``pages`` pages, and a table ``width`` pages wide is at most ``n_chunks``
    of them."""
    pages = min(step or DECODE_PAGES_PER_STEP, width)
    return pages, -(-width // pages)


def decode_walk_counts(context_lens, first, width: int, page_size: int,
                       window: int):
    """What one ``gqa_decode`` call over these rows (host arrays) and tables
    ``width`` pages wide walks, by the sizes the kernel's wrapper uses:
    ``(grid steps, pages walked, pages in context)``, the last what a walk
    from position 0 would take."""
    ctx = np.asarray(context_lens, np.int64)
    _, _, n_pages = decode_span(ctx, np.asarray(first, np.int64), page_size,
                                window)
    pages, n_chunks = decode_chunks(width)
    steps = int((-(-n_pages // pages)).clip(1, n_chunks).sum())
    return steps, int(n_pages.sum()), int((-(-ctx // page_size)).clip(1).sum())


def gqa_decode_reference(q, k_pool, v_pool, block_tables, context_lens,
                         first, scale, window: int = 0):
    """Gather oracle and CPU fallback.  ``q`` (n, heads, d), pools
    ``(kv_heads, pages, page_size, d)``, ``block_tables`` (n, w) whose entry
    0 is the page of position ``first`` (n,), ``context_lens`` (n,) true
    lengths, the current token's row already in the pools.  Returns (n,
    heads, d) float32."""
    n, heads, d = q.shape
    kvh, _, page_size, _ = k_pool.shape

    def rows(pool):
        got = jnp.take(pool, block_tables.reshape(-1), axis=1)
        return got.reshape(kvh, n, -1, d).astype(jnp.float32)

    k, v = rows(k_pool), rows(v_pool)
    qg = q.astype(jnp.float32).reshape(n, kvh, heads // kvh, d)
    s = jnp.einsum("nkgd,kntd->nkgt", qg, k) * scale
    pos = first[:, None] + lax.broadcasted_iota(jnp.int32, (n, k.shape[2]), 1)
    ctx = context_lens[:, None]
    ok = pos < ctx
    if window:
        ok &= pos >= ctx - window
    s = jnp.where(ok[:, None, None, :], s, DEFAULT_MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    v = jnp.where(ok[None, :, :, None], v, 0.0)
    return jnp.einsum("nkgt,kntd->nkgd", p, v).reshape(n, heads, d)


def _gqa_decode_kernel(bt_ref, cl_ref, base_ref, row_ref, chunk_ref, nl_ref,
                       q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, m_scr,
                       l_scr, acc_scr, *, scale, pages, fetch, window):
    """Grid step ``u`` of ``n_live``: chunk ``chunk[u]`` (``pages`` pages) of
    row ``row[u]``'s walk, every K/V head of it.  Visit ``u`` lies in buffer
    ``u % 2``, was started by visit ``u - 1`` whatever row that was, and
    starts visit ``u + 1`` before it waits.  A chunk is fetched by groups of
    ``fetch`` pages, as many as hold attended positions; what the buffer
    holds past them is stale and masked."""
    u = pl.program_id(0)
    n_live = nl_ref[0]
    b, i = row_ref[u], chunk_ref[u]
    page_size = kbuf.shape[2] // pages
    span = fetch * page_size
    width = bt_ref.shape[1]

    def walk_of(w):
        """``(row, lo, first table index of the chunk, groups to fetch)`` of
        visit ``w``."""
        row = row_ref[w]
        lo, p0, n_pages = decode_span(cl_ref[row], base_ref[row] * page_size,
                                      page_size, window)
        at = p0 + chunk_ref[w] * pages
        left = n_pages - chunk_ref[w] * pages
        return row, lo, at, jnp.clip((left + fetch - 1) // fetch, 1,
                                     pages // fetch)

    def start(w):
        row, _, at, groups = walk_of(w)
        slot = w % 2

        def group(g, carry):
            for j in range(fetch):
                page = bt_ref[row, jnp.minimum(at + g * fetch + j, width - 1)]
                rows = pl.ds(g * span + j * page_size, page_size)
                pltpu.make_async_copy(k_hbm.at[:, page],
                                      kbuf.at[slot, :, rows],
                                      sem.at[slot, 0, g]).start()
                pltpu.make_async_copy(v_hbm.at[:, page],
                                      vbuf.at[slot, :, rows],
                                      sem.at[slot, 1, g]).start()
            return carry

        lax.fori_loop(0, groups, group, 0)

    def wait(w):
        _, _, _, groups = walk_of(w)
        slot = w % 2
        for g in range(pages // fetch):
            rows = pl.ds(g * span, span)

            @pl.when(g < groups)
            def _():
                for which, buf in enumerate((kbuf, vbuf)):
                    part = buf.at[slot, :, rows]
                    pltpu.make_async_copy(part, part,
                                          sem.at[slot, which, g]).wait()

    @pl.when(u == 0)
    def _first():
        start(0)

    @pl.when(u + 1 < n_live)
    def _next():
        start(u + 1)

    @pl.when(i == 0)
    def _open():
        m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    wait(u)
    _, lo, at, _ = walk_of(u)
    ctx = cl_ref[b]
    k, v = kbuf[u % 2], vbuf[u % 2]                   # (kv_heads, tokens, d)
    s = lax.dot_general(q_ref[0], k, (((2,), (2,)), ((0,), (0,))),
                        preferred_element_type=jnp.float32) * scale
    origin = (base_ref[b] + at) * page_size
    pos = origin + lax.broadcasted_iota(jnp.int32, s.shape, 2)
    s = jnp.where((pos >= lo) & (pos < ctx), s, DEFAULT_MASK_VALUE)
    m_prev, l_prev = m_scr[...], l_scr[...]           # lane-broadcast
    m_next = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.exp(s - m_next[:, :, :1])
    l_scr[...] = alpha * l_prev + jnp.sum(p, axis=2, keepdims=True)
    # a row of the chunk outside the walk may hold anything (a page never
    # written, a group not fetched): its weight is exactly 0, but 0 * NaN
    # is NaN
    at_v = origin + lax.broadcasted_iota(jnp.int32, v.shape, 1)
    v_safe = jnp.where((at_v >= lo) & (at_v < ctx), v.astype(jnp.float32),
                       0.0).astype(v.dtype)
    acc_scr[...] = acc_scr[...] * alpha[:, :, :1] + lax.dot_general(
        p.astype(v.dtype), v_safe, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_next

    @pl.when(jnp.logical_or(
        u + 1 == n_live,
        row_ref[jnp.minimum(u + 1, row_ref.shape[0] - 1)] != b))
    def _done():
        l_fin = l_scr[...]
        l_safe = jnp.where(l_fin == 0.0, 1.0, l_fin)
        o_ref[0] = (acc_scr[...] / l_safe[:, :, :1]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "window", "step",
                                             "fetch"))
def _gqa_decode_call(q, k_pool, v_pool, block_tables, context_lens, first, *,
                     scale, window, step, fetch):
    """The kernel's call over a grid of exactly the list's ``n_live`` steps.
    Under a ``jit`` of its own: the layers of a kind share one trace and one
    lowering of it."""
    n, heads, d = q.shape
    kvh, _, page_size, _ = k_pool.shape
    group = heads // kvh
    w = block_tables.shape[1]
    pages, n_chunks = decode_chunks(w, step)
    fetch = fetch if pages % fetch == 0 else pages
    ctx = context_lens.astype(jnp.int32)
    first = first.astype(jnp.int32)
    _, _, n_pages = decode_span(ctx, first, page_size, window)
    # the work list of ``mla_decode``, over each row's walk in place of its
    # whole context: the chunks that hold attended positions, rows in order
    row, chunk, n_live = decode_work_list(n_pages * page_size,
                                          pages * page_size, n_chunks)

    def _q_idx(u, bt, cl, base, row_ref, chunk_ref, nl):
        return (row_ref[u], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(n_live,),
        in_specs=[
            pl.BlockSpec((1, kvh, group, d), _q_idx),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, kvh, group, d), _q_idx),
        scratch_shapes=[
            pltpu.VMEM((2, kvh, pages * page_size, d), k_pool.dtype),
            pltpu.VMEM((2, kvh, pages * page_size, d), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2, pages // fetch)),
            pltpu.VMEM((kvh, group, LANES), jnp.float32),
            pltpu.VMEM((kvh, group, LANES), jnp.float32),
            pltpu.VMEM((kvh, group, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_gqa_decode_kernel, scale=scale, pages=pages,
                          fetch=fetch, window=window),
        name="gqa_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, kvh, group, d), jnp.float32),
        interpret=_interpret(),
    )(block_tables.astype(jnp.int32), ctx, first // page_size, row, chunk,
      n_live[None], q.astype(k_pool.dtype).reshape(n, kvh, group, d),
      k_pool, v_pool)
    return out.reshape(n, heads, d)


def decode_engages(page_size: int, d: int) -> bool:
    """Whether ``gqa_decode`` runs its kernel for these sizes here: on the
    chip a page of whole (packed) sublane groups and rows of whole lanes."""
    return _use_pallas() and (_interpret()
                              or (page_size % 16 == 0 and d % LANES == 0))


def gqa_decode(q, k_pool, v_pool, block_tables, context_lens, first, scale,
               window: int = 0):
    """Grouped-query attention of one query row a sequence over the paged
    pools (shapes as :func:`gqa_decode_reference`)."""
    if decode_engages(k_pool.shape[2], q.shape[2]):
        return own_jit(_gqa_decode_call)(
            q, k_pool, v_pool, block_tables, context_lens, first,
            scale=float(scale), window=int(window),
            step=DECODE_PAGES_PER_STEP, fetch=DECODE_PAGES_PER_FETCH)
    return gqa_decode_reference(q, k_pool, v_pool,
                                block_tables.astype(jnp.int32),
                                context_lens.astype(jnp.int32),
                                first.astype(jnp.int32), scale, window)
