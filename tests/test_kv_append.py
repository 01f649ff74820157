"""The KV-pool append (ops/pallas_kernels.py ``kv_append``, behind the
``kv_cache_append`` op) against the flat scatter it replaced.

The reference is the old write, kept here as plain ``jnp``: the pool
viewed as ``(kv_heads, num_pages * page_size, d)`` and scattered at the
flat slots with ``mode="drop"``.  The op is run twice per case, once
with its write swapped for that reference and once as shipped — on the
``jnp`` path (the 4-D scatter by page and offset) and with the real
kernel body under ``PT_PALLAS_INTERPRET=1``, in both views the kernel
takes of a pool (row-major, and page-minor where head_dim leaves lanes
empty) — and every output (pools, and for int8 the scale pools) must be
bit-identical.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.ops import paged_ops
from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.ops.registry import eager_call

PAGE = 8
# (kv_heads, num_pages, page_size, d) -> the view the kernel takes
POOLS = {"row-major": (2, 6, PAGE, 16), "page-minor": (2, 256, PAGE, 32)}
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
    """The write as it was: a scatter on the flat view of the pool."""
    out = []
    for pool, new in zip(pools, rows):
        n_kv, n_pages, page_size, d = pool.shape
        flat = pool.reshape(n_kv, n_pages * page_size, d)
        flat = flat.at[:, slots, :].set(new.transpose(1, 0, 2), mode="drop")
        out.append(flat.reshape(pool.shape))
    return tuple(out)


def _feeds(dtype, shape, tokens, seed):
    n_kv, n_pages, page_size, d = shape
    spread = n_pages // 5                 # page p of the table -> p * spread
    slots = [n_pages * page_size if t == PAD
             else t[0] * spread * page_size + t[1] for t in tokens]
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


@pytest.mark.parametrize("path", ["jnp", "kernel-row-major",
                                  "kernel-page-minor"])
@pytest.mark.parametrize("case", list(TOKENS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
def test_append_matches_flat_scatter(dtype, case, path, monkeypatch):
    view = path.removeprefix("kernel-") if path != "jnp" else "row-major"
    ins, outs = _feeds(dtype, POOLS[view], TOKENS[case], seed=len(case))
    with monkeypatch.context() as m:
        m.setattr(paged_ops, "_kv_append_impl", flat_scatter)
        want = eager_call("kv_cache_append", ins, {}, outs)
    views = []
    real = pk._kv_append_call
    monkeypatch.setattr(
        pk, "_kv_append_call", lambda *a, page_minor:
        views.append(page_minor) or real(*a, page_minor=page_minor))
    if path == "jnp":
        monkeypatch.delenv("PT_PALLAS_INTERPRET", raising=False)
    else:
        monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    got = eager_call("kv_cache_append", ins, {}, outs)
    assert set(views) == ({view == "page-minor"} if path != "jnp" else set())
    for name in outs:
        assert got[name][0].dtype == want[name][0].dtype
        np.testing.assert_array_equal(np.asarray(got[name][0]),
                                      np.asarray(want[name][0]), name)
    if case == "all-padding" and dtype != "int8":
        np.testing.assert_array_equal(np.asarray(got["KCacheOut"][0]),
                                      np.asarray(ins["KCache"][0]))
