"""Pipeline parallelism, TPU-native.

Capability parity with the reference's pipeline stack:
  * ``PipelineOptimizer`` splits a program into sections at cut
    variables / ``device_guard`` annotations (reference:
    python/paddle/fluid/optimizer.py:3556-3640 — splits by cut-vars into
    sections across heterogeneous places).
  * ``PipelineTrainer`` + ``SectionWorker`` run the sections as threads
    connected by scope queues — an *async* pipeline with no 1F1B
    schedule (reference: framework/pipeline_trainer.cc:288,
    section_worker.cc:142, device_worker.h:345).

TPU-native redesign — two execution paths instead of threads+queues
(SURVEY.md §7 hard-part 7):

1. **Microbatched single-jit path** (general, any section shapes —
   `run_pipeline`): the forward sections are traced into one function,
   microbatches are driven through it with ``lax.scan`` accumulating
   parameter gradients (the reference's batch-merge/gradient-accumulation
   semantics, multi_batch_merge_pass.cc), and the program's own
   optimizer-role ops apply the update.  XLA schedules the section
   subgraphs; there is no host thread per stage.

2. **SPMD collective-permute pipeline** (homogeneous stages —
   `spmd_pipeline`): stage weights are stacked and sharded over a `pp`
   mesh axis; one ``shard_map`` program runs ``M + S - 1`` scan steps,
   rotating activations to the next stage with ``lax.ppermute`` each
   step.  Differentiating through the scan yields the mirrored reverse
   pipeline — a *synchronous* GPipe-style schedule, which improves on the
   reference's async-only pipeline (no stale weights).
"""
from __future__ import annotations

import dataclasses
from functools import partial

from typing import Any, Dict, List, Optional, Sequence

import numpy as np


# --------------------------------------------------------------------------
# Program splitting (PipelineOptimizer's section cutter)
# --------------------------------------------------------------------------
@dataclasses.dataclass
class Section:
    """One pipeline stage: a contiguous slice of forward ops.

    reference: optimizer.py:3556 `_split_program` produces one section
    program per cut; here sections keep op references into the original
    block plus their dataflow interface.
    """

    index: int
    ops: List[Any]
    device: Optional[str]
    in_names: List[str]        # activations consumed from earlier sections/feed
    out_names: List[str]       # activations produced for later sections
    param_names: List[str]     # persistable/state vars read by this section


def _op_role(op) -> int:
    try:
        r = op.attrs.get("op_role", 0)
    except AttributeError:
        r = 0
    return int(r) if r is not None else 0


def classify_ops(block):
    """Split a minimized program's ops into forward / optimize lists.

    The backward ops appended by append_backward are *not* replayed by
    the pipeline runner — gradients come from differentiating the traced
    forward (same per-op VJPs), so only forward + optimizer ops matter.
    """
    from ..backward import OpRole

    fwd, opt = [], []
    for op in block.ops:
        role = _op_role(op)
        if role & OpRole.Optimize or role & OpRole.LRSched:
            opt.append(op)
        elif role & OpRole.Backward or op.type.endswith("_grad"):
            continue
        else:
            fwd.append(op)
    return fwd, opt


def split_forward_sections(program, cut_var_names: Sequence[str] = (),
                           feed_names=()) -> List[Section]:
    """Cut the forward op list into sections.

    Boundaries: after the op producing each cut var (reference
    cut_list semantics); otherwise wherever the ``op_device``
    annotation changes (fluid.device_guard semantics).
    """
    block = program.global_block()
    fwd_ops, _ = classify_ops(block)
    cut_set = set(cut_var_names or ())

    groups: List[List[Any]] = [[]]
    devices: List[Optional[str]] = [None]
    if cut_set:
        for op in fwd_ops:
            groups[-1].append(op)
            if any(n in cut_set for n in op.output_arg_names):
                groups.append([])
                devices.append(None)
        if not groups[-1]:
            groups.pop()
            devices.pop()
    else:
        last_dev = object()
        groups, devices = [], []
        for op in fwd_ops:
            dev = op.attrs.get("op_device")
            if dev != last_dev:
                groups.append([])
                devices.append(dev)
                last_dev = dev
            groups[-1].append(op)
        if not groups:
            groups, devices = [[]], [None]

    feed_names = set(feed_names or ())
    produced_by: Dict[str, int] = {}
    for gi, ops in enumerate(groups):
        for op in ops:
            for n in op.output_arg_names:
                produced_by[n] = gi

    sections: List[Section] = []
    for gi, ops in enumerate(groups):
        ins, params = [], []
        local_out = set()
        for op in ops:
            for n in op.input_arg_names:
                if n in local_out or n == "@EMPTY@":
                    continue
                src = produced_by.get(n)
                if src is not None and src < gi:
                    if n not in ins:
                        ins.append(n)
                elif src is None and n not in feed_names:
                    var = block._find_var_recursive(n)
                    if var is not None and n not in params:
                        params.append(n)
            local_out.update(op.output_arg_names)
        sections.append(Section(gi, ops, devices[gi], ins, [], params))
    # second pass: out_names = vars consumed by any later section
    consumed_later: Dict[int, set] = {i: set() for i in range(len(sections))}
    for s in sections:
        for n in s.in_names:
            src = produced_by.get(n)
            if src is not None:
                consumed_later[src].add(n)
    for s in sections:
        s.out_names = sorted(consumed_later[s.index])
    return sections


# --------------------------------------------------------------------------
# Microbatched single-jit pipeline execution (general path)
# --------------------------------------------------------------------------
def run_pipeline(executor, program, feed, fetch_list, scope, return_numpy):
    import jax
    import jax.numpy as jnp

    from ..executor import _fetch_name, as_numpy
    from ..framework.dtype import to_numpy_dtype
    from ..framework.scope import LoDTensor, global_scope
    from ..ops import registry

    RNG_VAR = registry.LowerCtx.RNG_VAR
    meta = program._pipeline_opt
    scope = scope or global_scope()
    feed = dict(feed or {})
    fetch_names = [_fetch_name(f) for f in (fetch_list or [])]
    M = int(meta.get("num_microbatches", 1))

    block = program.global_block()
    feed_spec = tuple(sorted(
        (k, tuple(np.shape(v)),
         str(v.dtype) if hasattr(v, "dtype") else str(np.asarray(v).dtype))
        for k, v in feed.items()
    ))
    key = (program._version, feed_spec, tuple(fetch_names), M)
    cache = program.__dict__.setdefault("_pipeline_cache", {})
    entry = cache.get(key)

    if entry is None:
        fwd_ops, opt_ops = classify_ops(block)
        sections = split_forward_sections(
            program, meta.get("cut_vars") or (), set(feed)
        )
        param_names = [p for p, _ in meta["params_grads"]]
        grad_of = {p: g for p, g in meta["params_grads"]}
        loss_name = meta["loss_name"]

        # shared read/write analysis (grad vars bound from accumulation,
        # not scope, hence the @GRAD exclusion)
        from ..executor import analyze_state

        state_in, state_out, uses_rng, _ = analyze_state(
            fwd_ops + opt_ops, block, set(feed), scope,
            skip_suffixes=("@GRAD",)
        )

        trainable_names = [n for n in param_names if n in state_in]
        # persistable state written by *forward* ops (batch_norm running
        # stats): threaded sequentially through the microbatch scan so the
        # updates chain exactly like the plain-executor path
        fwd_written = set()
        for op_ in fwd_ops:
            fwd_written.update(op_.output_arg_names)
        fwd_mut_names = [n for n in state_out
                         if n in fwd_written and n not in set(trainable_names)
                         and n != RNG_VAR]

        def loss_fn(trainable, fwd_mut, static, mb_feed):
            env = dict(static)
            env.update(fwd_mut)
            env.update(trainable)
            env.update(mb_feed)
            for sec in sections:
                for op_ in sec.ops:
                    registry.run_op(op_, env, block)
            fetched = tuple(env[n] for n in fetch_names)
            new_fwd_mut = {n: env[n] for n in fwd_mut_names}
            return env[loss_name], (fetched, new_fwd_mut)

        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

        def step(state_vals, feed_vals):
            # non-batched (0-d) feeds broadcast to every microbatch
            mb_feeds = {
                k: v.reshape((M, v.shape[0] // M) + v.shape[1:])
                for k, v in feed_vals.items() if np.ndim(v) >= 1
            }
            static_feeds = {k: v for k, v in feed_vals.items()
                            if np.ndim(v) == 0}
            trainable = {n: state_vals[n] for n in trainable_names}
            fwd_mut0 = {n: state_vals[n] for n in fwd_mut_names}
            static = {n: v for n, v in state_vals.items()
                      if n not in set(trainable_names)
                      and n not in set(fwd_mut_names)}
            static.update(static_feeds)

            def scan_body(carry, xs):
                acc, fwd_mut = carry
                i, mb = xs
                st = dict(static)
                if uses_rng:
                    st[RNG_VAR] = jax.random.fold_in(state_vals[RNG_VAR], i)
                (loss, (fetched, fwd_mut)), grads = grad_fn(
                    trainable, fwd_mut, st, mb
                )
                acc = jax.tree.map(jnp.add, acc, grads)
                return (acc, fwd_mut), (loss, fetched)

            zeros = jax.tree.map(jnp.zeros_like, trainable)
            idx = jnp.arange(M)
            (acc, fwd_mut_fin), (_, fetched_stack) = jax.lax.scan(
                scan_body, (zeros, fwd_mut0), (idx, mb_feeds)
            )
            grads_avg = jax.tree.map(lambda g: g / M, acc)

            env = dict(state_vals)
            env.update(fwd_mut_fin)
            if uses_rng:
                env[RNG_VAR] = jax.random.fold_in(state_vals[RNG_VAR], M)
            for p in trainable_names:
                env[grad_of[p]] = grads_avg[p]
            for op_ in opt_ops:
                registry.run_op(op_, env, block)
            new_state = {n: env[n] for n in state_out if n in env}

            # per-microbatch scalars (loss/metrics) average across
            # microbatches; per-sample outputs concatenate back to the
            # full batch along axis 0
            def _merge(f):
                if f.ndim <= 1:  # stacked scalar: (M,)
                    return (f.mean(axis=0)
                            if jnp.issubdtype(f.dtype, jnp.floating)
                            else f[-1])
                return f.reshape((-1,) + f.shape[2:])

            fetched = tuple(_merge(f) for f in fetched_stack)
            return fetched, new_state

        jitted = jax.jit(step)
        entry = (jitted, state_in, state_out)
        cache[key] = entry

    jitted, state_in, state_out = entry
    device = executor.place.jax_device()

    feed_vals = {}
    for k, v in feed.items():
        arr = as_numpy(v) if isinstance(v, LoDTensor) else np.asarray(v)
        var = block._find_var_recursive(k)
        if var is not None and var.dtype is not None:
            want = to_numpy_dtype(var.dtype)
            if arr.dtype != want:
                arr = arr.astype(want)
        if arr.shape and arr.shape[0] % M != 0:
            raise ValueError(
                f"feed {k!r} batch {arr.shape[0]} not divisible by "
                f"{M} microbatches"
            )
        feed_vals[k] = jax.device_put(arr, device)

    state_vals = {}
    for name in state_in:
        if name == RNG_VAR:
            val = scope.get(RNG_VAR)
            if val is None:
                val = jax.random.key(program.random_seed or 0)
            state_vals[name] = val
            continue
        val = scope.get(name)
        if val is None:
            raise RuntimeError(
                f"Variable {name!r} has no value in scope — run the startup "
                f"program first"
            )
        if isinstance(val, LoDTensor):
            val = val.numpy()
        state_vals[name] = jax.device_put(np.asarray(val), device) \
            if isinstance(val, np.ndarray) else val

    fetched, new_state = jitted(state_vals, feed_vals)
    for name, val in new_state.items():
        scope.set(name, val)

    if fetch_names:
        if return_numpy:
            return [as_numpy(v) for v in fetched]
        return [LoDTensor(v) for v in fetched]
    return None


# --------------------------------------------------------------------------
# SPMD collective-permute pipeline (homogeneous stages, `pp` mesh axis)
# --------------------------------------------------------------------------
def spmd_pipeline(stage_fn, stage_params, microbatches, mesh, axis: str = "pp",
                  params_spec=None, mb_spec=None):
    """Run ``S`` homogeneous stages over a pipeline mesh axis.

    ``stage_params``: pytree whose leaves have leading dim ``S`` (stacked
    per-stage weights, sharded over ``axis``).  ``microbatches``: pytree
    whose leaves have leading dim ``M``; every microbatch flows through
    all stages.  ``stage_fn(params_k, x) -> y`` with ``y`` shaped like
    ``x``.  Returns outputs with leading dim ``M``.

    One shard_map program; each of ``M + S - 1`` scan steps computes the
    local stage then rotates activations with ``lax.ppermute`` —
    activation transfer rides ICI instead of the reference's host scope
    queues (section_worker.cc:142).  ``jax.grad`` through this function
    yields the reverse pipeline (synchronous schedule; the reference's
    pipeline is async-only).

    Composition with other mesh axes (r4): ``params_spec`` /``mb_spec``
    override the default shardings so PP composes with TP and DP on one
    mesh — e.g. ``params_spec=P("pp", None, "mp")`` (stage-stacked,
    column-TP weights) and ``mb_spec=P(None, "dp")`` (batch-sharded
    microbatches); ``stage_fn`` then issues its own ``mp``/``dp``
    collectives (all_gather/psum), exactly the Megatron recipe.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    S = mesh.shape[axis]
    leaves = jax.tree.leaves(microbatches)
    M = leaves[0].shape[0]
    T = M + S - 1
    perm = [(i, (i + 1) % S) for i in range(S)]
    if params_spec is None:
        params_spec = P(axis)
    if mb_spec is None:
        mb_spec = P()

    def _index(tree_, i):
        return jax.tree.map(
            lambda x: lax.dynamic_index_in_dim(x, i, 0, keepdims=False), tree_
        )

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(params_spec, mb_spec),
        out_specs=mb_spec,
        check_vma=False,
    )
    def run(params_local, mbs):
        params_k = jax.tree.map(lambda x: x[0], params_local)
        stage = lax.axis_index(axis)
        zero_mb = jax.tree.map(lambda x: jnp.zeros_like(x[0]), mbs)
        outputs = jax.tree.map(lambda x: jnp.zeros_like(x), mbs)

        def body(carry, t):
            state, outputs = carry
            inject = _index(mbs, jnp.clip(t, 0, M - 1))
            x = jax.tree.map(
                lambda i, s: jnp.where(stage == 0, i, s), inject, state
            )
            y = stage_fn(params_k, x)
            out_idx = jnp.clip(t - (S - 1), 0, M - 1)
            write = jnp.logical_and(stage == S - 1, t >= S - 1)

            def upd(buf, val):
                cur = lax.dynamic_index_in_dim(buf, out_idx, 0, keepdims=False)
                new = jnp.where(write, val, cur)
                return lax.dynamic_update_index_in_dim(buf, new, out_idx, 0)

            outputs = jax.tree.map(upd, outputs, y)
            state = jax.tree.map(
                lambda v: lax.ppermute(v, axis, perm), y
            )
            return (state, outputs), None

        (state, outputs), _ = lax.scan(
            body, (zero_mb, outputs), jnp.arange(T)
        )
        # outputs were only written on the last stage; broadcast them
        outputs = jax.tree.map(
            lambda o: lax.psum(
                jnp.where(stage == S - 1, o, jnp.zeros_like(o)), axis
            ),
            outputs,
        )
        return outputs

    return run(stage_params, microbatches)
