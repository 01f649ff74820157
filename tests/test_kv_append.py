"""The KV-pool append (ops/pallas_kernels.py ``kv_append``, behind the
``kv_cache_append`` op) against the flat scatter it replaced.

The reference is the old write, kept here as plain ``jnp``: the pool
viewed as ``(kv_heads, num_pages * page_size, d)`` and scattered at the
flat slots with ``mode="drop"``.  The op is run twice per case, once
with its write swapped for that reference and once as shipped — on the
``jnp`` path (a scatter by page and offset) and with the real kernel
body under ``PT_PALLAS_INTERPRET=1`` — in every form a pool reaches it
in: stored as the logical ``(kv_heads, num_pages, page_size, d)``
(row-major to the kernel, or page-minor where head_dim leaves lanes
empty and the pool could not be stored lane-full), and stored lane-full,
``128 / d`` tokens side by side in a row.  Every output (pools, and for
int8 the scale pools) must be bit-identical.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.ops import paged_ops
from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.ops.registry import eager_call

PAGE = 8
# the pool as stored, (kv_heads, num_pages, rows, width), and its head_dim;
# the name is the view the kernel takes of it
POOLS = {"row-major": ((2, 6, PAGE, 16), 16),
         "page-minor": ((2, 256, PAGE, 32), 32),
         # pages of 8 tokens, 4 a row of 128 lanes: (PAGE, 32) stored (2, 128)
         # would be a quarter tile, so the page is 32 tokens: 8 rows
         "packed": ((2, 6, 8, 128), 32)}
PAD = -1                  # stands for the allocator's sentinel below

# (page, offset) per token, pages up to 5 (and two 128-page blocks apart
# in the larger pool: page p there is p * 51)
TOKENS = {
    # one token a sequence, each in its own page, bucket padding between
    "decode-with-pad-sentinels": [(1, 5), PAD, (5, 0), (0, 7), PAD, (2, 6),
                                  PAD, PAD],
    # a prompt from the middle of page 1 through page 2 into page 3,
    # padded to its bucket
    "prefill-across-pages": [(1, o) for o in range(5, 8)]
    + [(2, o) for o in range(8)] + [(3, o) for o in range(3)] + [PAD] * 2,
    # a recycled page opened at offset 0 (int8: its scale resets and
    # its stale rows are zeroed), beside a page appended to mid-way
    "page-reopened-at-offset-0": [(4, 0), (4, 1), (4, 2), (2, 5), PAD],
    "all-padding": [PAD] * 8,
    # rows of one page apart in the feed, in descending order
    "one-page-split-across-the-feed": [(2, 2), (5, 1), (2, 1), (0, 3),
                                       (2, 0)],
}


def flat_scatter(pools, rows, slots):
    """The write as it was: a scatter on the flat view of the pool (every
    stored form is a row-major bitcast of it)."""
    out = []
    for pool, new in zip(pools, rows):
        flat = pool.reshape(pool.shape[0], -1, new.shape[-1])
        flat = flat.at[:, slots, :].set(new.transpose(1, 0, 2), mode="drop")
        out.append(flat.reshape(pool.shape))
    return tuple(out)


def _feeds(dtype, shape, d, tokens, seed):
    n_kv, n_pages = shape[:2]
    page_size = shape[2] * shape[3] // d
    spread = n_pages // 5                 # page p of the table -> p * spread
    # the patterns' offsets run to PAGE - 1: in a larger page they are
    # spread over all of its rows and lane groups (0 stays 0)
    wide = page_size // PAGE
    slots = [n_pages * page_size if t == PAD
             else t[0] * spread * page_size + t[1] * wide + t[1] % wide
             for t in tokens]
    rng = np.random.RandomState(seed)
    ins = {"SlotMapping": [jnp.asarray(slots, jnp.int32)]}
    for name in "KV":
        ins[name] = [jnp.asarray(
            rng.randn(len(slots), n_kv, d).astype(np.float32) * 3.0)]
        if dtype == "int8":
            ins[name + "Cache"] = [jnp.asarray(
                rng.randint(-127, 128, shape).astype(np.int8))]
            # a live scale on every page, so a reset is seen as one
            ins[name + "Scale"] = [jnp.asarray(
                rng.uniform(0.5, 4.0, (n_kv, n_pages)).astype(np.float32))]
        else:
            ins[name + "Cache"] = [jnp.asarray(
                rng.randn(*shape).astype(np.float32)).astype(dtype)]
    outs = {"KCacheOut": 1, "VCacheOut": 1}
    if dtype == "int8":
        outs.update(KScaleOut=1, VScaleOut=1)
    return ins, outs


@pytest.mark.parametrize("path", ["jnp", "jnp-packed", "kernel-row-major",
                                  "kernel-page-minor", "kernel-packed"])
@pytest.mark.parametrize("case", list(TOKENS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
def test_append_matches_flat_scatter(dtype, case, path, monkeypatch):
    how, _, view = path.partition("-")
    view = view or "row-major"
    ins, outs = _feeds(dtype, *POOLS[view], TOKENS[case], seed=len(case))
    with monkeypatch.context() as m:
        m.setattr(paged_ops, "_kv_append_impl", flat_scatter)
        want = eager_call("kv_cache_append", ins, {}, outs)
    views = []
    real = pk._kv_append_call
    monkeypatch.setattr(
        pk, "_kv_append_call", lambda *a, page_minor:
        views.append(page_minor) or real(*a, page_minor=page_minor))
    if how == "jnp":
        monkeypatch.delenv("PT_PALLAS_INTERPRET", raising=False)
    else:
        monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    got = eager_call("kv_cache_append", ins, {}, outs)
    assert set(views) == ({view == "page-minor"} if how != "jnp" else set())
    for name in outs:
        assert got[name][0].dtype == want[name][0].dtype
        assert got[name][0].shape == want[name][0].shape
        np.testing.assert_array_equal(np.asarray(got[name][0]),
                                      np.asarray(want[name][0]), name)
    if case == "all-padding" and dtype != "int8":
        np.testing.assert_array_equal(np.asarray(got["KCacheOut"][0]),
                                      np.asarray(ins["KCache"][0]))
