"""Collective op lowerings: `c_*` ops retargeted from NCCL rings to XLA
collectives over mesh axes.

Capability parity with reference: paddle/fluid/operators/collective/
(c_allreduce_op.h:58-106, c_broadcast_op, c_allgather_op,
c_reducescatter_op, c_comm_init_op, c_gen_nccl_id_op,
c_sync_calc_stream_op, c_sync_comm_stream_op) — the north star's "Fleet
collective mode retargets from NCCL rings to ICI allreduce".

Semantics: inside a shard_map region (the executor's SPMD path), each op
lowers to the matching lax collective over the axis its ring_id maps to
(parallel/mesh.py registry).  Outside any mesh (single-device执行) they are
identity — a 1-rank world, matching the reference's behavior when
nranks==1.  Stream-sync ops are no-ops: XLA's dataflow order subsumes
cudaStreamSynchronize.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .registry import op


def _axis(ctx):
    from ..parallel.mesh import registry

    ring_id = ctx.attr("ring_id", 0)
    axis = registry().axis_for_ring(ring_id)
    return axis


def _in_shard_map(axis):
    """True if `axis` is a bound axis name in the current trace (i.e. we
    are inside shard_map/pmap and the collective is meaningful)."""
    if axis is None:
        return False
    try:
        lax.axis_index(axis)
        return True
    except Exception:
        return False


def _allreduce(reduce_fn):
    def lower(ctx):
        x = ctx.in_("X")
        axis = _axis(ctx)
        if _in_shard_map(axis):
            x = reduce_fn(x, axis)
        ctx.set_out("Out", x)

    return lower


@op("c_allreduce_sum", no_grad=True)
def _c_allreduce_sum(ctx):
    x = ctx.in_("X")
    axis = _axis(ctx)
    if _in_shard_map(axis):
        x = lax.psum(x, axis)
        if ctx.attr("use_mean", False):
            # mean without knowing nranks at graph-build time (the DGC
            # optimizer's dense path)
            x = x / lax.axis_size(axis)
    ctx.set_out("Out", x)
op("c_allreduce_max", no_grad=True)(_allreduce(lambda x, a: lax.pmax(x, a)))
op("c_allreduce_min", no_grad=True)(_allreduce(lambda x, a: lax.pmin(x, a)))
op("c_allreduce_prod", no_grad=True)(
    _allreduce(lambda x, a: jnp.exp(lax.psum(jnp.log(x), a)))
)
op("allreduce", no_grad=True)(_allreduce(lambda x, a: lax.psum(x, a)))


def _static_axis_size(axis):
    """Axis size as a python int (needed for reshape chunk counts): the
    registered mesh knows it at trace time."""
    from ..parallel.mesh import current_mesh

    mesh = current_mesh()
    if mesh is not None and axis in mesh.shape:
        return int(mesh.shape[axis])
    return int(lax.axis_size(axis))


def _bf16_wire_psum(flat, axis):
    """EQuARX-style compressed allreduce (arxiv 2506.17615): payload
    crosses the wire as bf16 (half the bytes of f32) in both phases of a
    reduce-scatter/all-gather decomposition, while the reduction itself
    accumulates in f32 — so quantization error is one rounding per
    addend, not a cascade through the ring."""
    n = int(flat.shape[0])
    nranks = _static_axis_size(axis)
    pad = (-n) % nranks
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    # phase 1 (reduce-scatter): each device ships chunk d to device d in
    # bf16; the receiver accumulates its chunk's nranks addends in f32
    chunks = jnp.reshape(flat, (nranks, -1)).astype(jnp.bfloat16)
    recv = lax.all_to_all(chunks, axis, split_axis=0, concat_axis=0,
                          tiled=False)
    red = jnp.sum(recv.astype(jnp.float32), axis=0)
    # phase 2 (all-gather): the reduced shard goes back out in bf16
    out = lax.all_gather(red.astype(jnp.bfloat16), axis, axis=0, tiled=True)
    out = out.astype(flat.dtype)
    return out[:n] if pad else out


@op("c_fused_allreduce", no_grad=True)
def _c_fused_allreduce(ctx):
    """One flattened collective over a bucket of gradient tensors
    (reference: ir/fuse_all_reduce_op_pass.cc lowering a grad group onto
    one coalesced buffer — framework/ir.py fuse_all_reduce_pass emits
    this op).  All bucket members share one dtype (the pass refuses
    mixed-dtype merges); `compress="bf16"` rides the EQuARX wire format
    for f32 payloads and is a graph-visible attr so the compiled program
    records which format it shipped."""
    xs = ctx.ins("X")
    axis = _axis(ctx)
    if not _in_shard_map(axis):
        ctx.set_out("Out", list(xs))
        return
    shapes = [jnp.shape(x) for x in xs]
    sizes = [int(np.prod(s)) if s else 1 for s in shapes]
    flat = jnp.concatenate([jnp.ravel(x) for x in xs])
    if ctx.attr("compress", "none") == "bf16" and flat.dtype == jnp.float32:
        flat = _bf16_wire_psum(flat, axis)
    else:
        flat = lax.psum(flat, axis)
    outs, off = [], 0
    for s, sz in zip(shapes, sizes):
        outs.append(jnp.reshape(lax.slice_in_dim(flat, off, off + sz, axis=0),
                                s))
        off += sz
    ctx.set_out("Out", outs)


@op("c_fused_reduce_scatter", no_grad=True)
def _c_fused_reduce_scatter(ctx):
    """ZeRO-2 lowering of a fused gradient bucket (reference: fleet
    sharding stage-2 — grads reduce into per-rank shards, never
    materializing at full width): every member tensor is laid out as
    (nranks, rows, ...) row-blocks, the blocks concatenate into ONE
    (nranks, total/nranks) payload, and a single psum_scatter hands each
    device exactly its row-shard of every reduced grad — which the DP
    runner's shard-aware optimizer update consumes directly.  Wire cost
    is (n-1)/n * payload, half an allreduce.  Outside a mesh the op is
    identity (1-rank world), so the same program runs single-device.
    `compress="bf16"` ships the scatter phase in bf16 with f32
    accumulation (the EQuARX wire format's reduce half)."""
    xs = ctx.ins("X")
    axis = _axis(ctx)
    if not _in_shard_map(axis):
        ctx.set_out("Out", list(xs))
        return
    nranks = _static_axis_size(axis)
    shapes = [tuple(jnp.shape(x)) for x in xs]
    rows = [s[0] // nranks for s in shapes]
    rests = [int(np.prod(s[1:])) if len(s) > 1 else 1 for s in shapes]
    blocks = [jnp.reshape(x, (nranks, r * q))
              for x, r, q in zip(xs, rows, rests)]
    payload = jnp.concatenate(blocks, axis=1)
    if ctx.attr("compress", "none") == "bf16" and payload.dtype == jnp.float32:
        recv = lax.all_to_all(payload.astype(jnp.bfloat16), axis,
                              split_axis=0, concat_axis=0, tiled=False)
        shard = jnp.sum(recv.astype(jnp.float32), axis=0).astype(payload.dtype)
    else:
        shard = lax.psum_scatter(jnp.ravel(payload), axis,
                                 scatter_dimension=0, tiled=True)
    outs, off = [], 0
    for s, r, q in zip(shapes, rows, rests):
        outs.append(jnp.reshape(shard[off:off + r * q], (r,) + s[1:]))
        off += r * q
    ctx.set_out("Out", outs)


@op("c_broadcast", no_grad=True)
def _c_broadcast(ctx):
    x = ctx.in_("X")
    axis = _axis(ctx)
    root = ctx.attr("root", 0)
    if _in_shard_map(axis):
        # take root's value on every shard
        gathered = lax.all_gather(x, axis)
        x = gathered[root]
    ctx.set_out("Out", x)


op("broadcast", no_grad=True)(lambda ctx: _c_broadcast(ctx))


@op("c_allgather", no_grad=True)
def _c_allgather(ctx):
    x = ctx.in_("X")
    axis = _axis(ctx)
    if _in_shard_map(axis):
        x = lax.all_gather(x, axis, axis=0, tiled=True)
    ctx.set_out("Out", x)


@op("c_reducescatter", no_grad=True)
def _c_reducescatter(ctx):
    x = ctx.in_("X")
    axis = _axis(ctx)
    if _in_shard_map(axis):
        x = lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True)
    ctx.set_out("Out", x)


@op("c_concat", no_grad=True)
def _c_concat(ctx):
    x = ctx.in_("X")
    axis = _axis(ctx)
    if _in_shard_map(axis):
        x = lax.all_gather(x, axis, axis=-1, tiled=True)
    ctx.set_out("Out", x)


@op("c_split", no_grad=True)
def _c_split(ctx):
    x = ctx.in_("X")
    axis = _axis(ctx)
    if _in_shard_map(axis):
        from ..parallel.mesh import current_mesh

        idx = lax.axis_index(axis)
        nranks = lax.axis_size(axis)
        d = jnp.shape(x)[-1] // nranks
        x = lax.dynamic_slice_in_dim(x, idx * d, d, axis=-1)
    ctx.set_out("Out", x)


@op("c_identity")
def _c_identity(ctx):
    ctx.set_out("Out", ctx.in_("X"))


@op("alltoall", no_grad=True)
def _alltoall(ctx):
    x = ctx.in_("X")
    axis = _axis(ctx)
    if _in_shard_map(axis):
        n = lax.axis_size(axis)
        xs = jnp.reshape(x, (n, jnp.shape(x)[0] // n) + jnp.shape(x)[1:])
        xs = lax.all_to_all(xs, axis, split_axis=0, concat_axis=0, tiled=False)
        x = jnp.reshape(xs, (-1,) + jnp.shape(x)[1:])
    ctx.set_out("Out", x)


# -- bootstrap / sync ops: no-ops under XLA ordering (kept for program
#    compatibility; reference inserts them around every collective) --------
@op("c_sync_calc_stream", no_grad=True,
    spec_hint={"attrs": {"ring_id": 0}})
def _c_sync_calc(ctx):
    ctx.set_out("Out", ctx.in_("X"))


@op("c_sync_comm_stream", no_grad=True,
    spec_hint={"attrs": {"ring_id": 0}})
def _c_sync_comm(ctx):
    xs = ctx.ins("X")
    ctx.set_out("Out", xs)


@op("c_comm_init", no_grad=True)
def _c_comm_init(ctx):
    """reference: c_comm_init_op.cc — creates a NCCL comm for a ring.
    Here: registers ring->axis in the mesh registry (host-side effect)."""
    from ..parallel.mesh import registry, current_mesh

    ring_id = ctx.attr("ring_id", 0)
    mesh = current_mesh()
    if mesh is not None:
        # hierarchical rings name their axis explicitly (inter/intra);
        # default rings bind to the first mesh axis
        axis = ctx.attr("axis_name", None) or mesh.axis_names[0]
        registry().register_ring(ring_id, axis)


@op("c_comm_init_all", no_grad=True)
def _c_comm_init_all(ctx):
    _c_comm_init(ctx)


@op("c_gen_nccl_id", no_grad=True)
def _c_gen_nccl_id(ctx):
    """reference: c_gen_nccl_id_op.cc — ncclUniqueId rendezvous over TCP.
    The JAX coordination service (jax.distributed.initialize) already
    performed rendezvous; nothing to do."""


@op("c_wait_calc_stream", no_grad=True)
def _c_wait_calc(ctx):
    ctx.set_out("Out", ctx.in_("X"))


@op("c_wait_comm_stream", no_grad=True)
def _c_wait_comm(ctx):
    ctx.set_out("Out", ctx.in_("X"))


@op("barrier", no_grad=True)
def _barrier(ctx):
    x = ctx.in_("X") if ctx.has_input("X") else None
    axis = _axis(ctx)
    if x is not None and _in_shard_map(axis):
        # data-dependent barrier: psum of zeros ties all shards
        x = x + jnp.zeros_like(x) * lax.psum(jnp.zeros((), jnp.float32), axis)
    if x is not None:
        ctx.set_out("Out", x)
