"""SPMD data-parallel execution of a CompiledProgram.

Replaces the reference's ParallelExecutor machinery
(reference: framework/parallel_executor.cc:443 ctor — per-device graph
clone + NCCL init + BCastParamsToDevices:570 + multi_devices_graph_pass
inserting AllReduceOpHandles; framework/details/
fast_threaded_ssa_graph_executor.cc hot loop) with two TPU-native paths:

* **pjit path** (no `c_*` ops in the program — CompiledProgram
  .with_data_parallel): the program's traced function is compiled once
  with batch-sharded feed and replicated parameter shardings over the
  mesh; GSPMD partitions the computation and inserts the gradient
  allreduce on ICI automatically.  Parameter "broadcast" is jax.device_put
  of replicated shardings (BCastParamsToDevices analog).

* **shard_map path** (program contains explicit `c_*` collective ops —
  Fleet-collective / transpiler-rewritten programs): the per-shard program
  runs under jax.shard_map, where each `c_allreduce_sum` lowers to
  lax.psum over the ring's mesh axis — a 1:1 mapping of the reference's
  multi-process NCCL model onto one SPMD program.

Fetch semantics match ParallelExecutor: fetched vars are stacked across
devices on a new leading axis (the reference concatenates per-device
fetches), so a fetched scalar loss has shape (ndev,).
"""
from __future__ import annotations

import dataclasses
import weakref
from typing import Any, Dict, List

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..executor import (_StateSession, device_put_owned, named_step,
                        program_label)
from ..framework.scope import LoDTensor
from ..ops import registry
from ..profiler import RecordEvent
from . import partition_rules
from .mesh import default_dp_mesh

RNG_VAR = registry.LowerCtx.RNG_VAR


@dataclasses.dataclass(slots=True)
class _CompiledDP:
    """One compiled data-parallel step (a ``_dp_cache`` entry): the
    executor's ``_Compiled`` for a mesh.  ``fn(mut, ro, feed)`` takes
    the state split at compile time into ``donatable`` (read and
    rewritten: parameters, moments, BN statistics, the RNG key) and
    ``readonly``; every state output is pinned to ``shardings``, the
    placement its input has, so a step's outputs are the next step's
    inputs as they stand and ``session`` (executor._StateSession)
    carries them across."""

    fn: Any                     # the jitted step
    donatable: tuple
    readonly: tuple
    use_shard_map: bool
    shardings: Dict[str, NamedSharding]     # by state name, in and out
    feed_sharding: NamedSharding            # the batch axis over the mesh
    feed_plan: dict
    numerics: Any               # probe layout (framework/numerics.py)
    session: Any = None
    # (fn, *abstract arguments): built at the entry's first step, again
    # only when a walk binds another shape
    last_exec: Any = None


def _program_has_collectives(program) -> bool:
    for blk in program.blocks:
        for op_ in blk.ops:
            if op_.type.startswith("c_") or op_.type in ("allreduce", "broadcast"):
                return True
    return False


def _mesh_fingerprint(mesh):
    """Value-based cache key for a mesh: id() can be reused by a new mesh
    after the old one is garbage-collected, silently resurrecting a
    stale compiled entry."""
    return (tuple(mesh.axis_names), tuple(np.asarray(mesh.devices).shape),
            tuple(d.id for d in mesh.devices.flat))


# What counts as per-parameter optimizer state, and which update ops
# tolerate running on a row shard, comes from the r16 partition-rule
# engine (parallel/partition_rules.py): state slots are DERIVED from
# each op's registered slot declarations (S read + SOut written), shard
# certification is a first-match-wins rule table, and beta-pow scalar
# accumulators stay replicated by rule.  Shared by the pjit sharding
# planner, the shard_map update wrapper below, fuse_all_reduce_pass's
# ZeRO-2 scatter eligibility, and the memory planner — one source of
# truth (the pre-r16 _OPT_STATE_SLOTS / _SHARDABLE_UPDATE_OPS tables
# are gone; tests/test_partition_rules.py pins the derivation equal to
# them).


def rank_shards(value):
    """[(rank, device_shard)] for a jax.Array contiguously row-sharded
    over >1 devices — i.e. exactly the ZeRO-1/2/3 state layouts this
    module produces (P('dp') over axis 0).  Rank r's entry is that
    device's resident row block, so the checkpoint layer
    (paddle_tpu/checkpoint.py) can snapshot 1/ndev of the bytes per
    rank WITHOUT gathering.  Returns None for replicated, host-side,
    scalar, or non-axis-0/non-contiguous layouts (tensor-parallel
    annotations) — those save full-width instead."""
    import jax

    if not isinstance(value, jax.Array) or not value.ndim \
            or not value.nbytes:
        return None
    try:
        shards = value.addressable_shards
    except Exception:
        return None
    if len(shards) <= 1 or shards[0].data.nbytes >= value.nbytes:
        return None  # single device or replicated
    blocks: Dict[int, Any] = {}
    for s in shards:
        idx = s.index
        if not idx or not isinstance(idx[0], slice):
            return None
        for sl in idx[1:]:
            # only whole trailing axes: row blocks, not 2D tiles
            if sl != slice(None, None, None):
                return None
        blocks.setdefault(int(idx[0].start or 0), s.data)
    out, expect = [], 0
    for rank, start in enumerate(sorted(blocks)):
        d = blocks[start]
        if start != expect:
            return None  # gap/overlap: not a contiguous row tiling
        expect += int(d.shape[0])
        out.append((rank, d))
    if expect != int(value.shape[0]):
        return None
    return out


def _update_shard_rows(op_, block, ndev):
    """Rows-per-device for a shard-eligible update op, else None.
    Eligible: elementwise update type, single dense param/grad, every
    tensor (param, grad, all state slots) sharing one leading dim
    divisible by ndev, and no tensor-parallel annotation to respect.
    Shared with fuse_all_reduce_pass so a grad only reduce-scatters
    when the runtime wrapper will really consume the shard."""
    from ..framework.dtype import VarType

    if ndev <= 1 or not partition_rules.shardable_update(op_.type):
        return None
    params = op_.inputs.get("Param", [])
    grads = op_.inputs.get("Grad", [])
    if len(params) != 1 or len(grads) != 1:
        return None
    names = [params[0], grads[0]]
    for slot in partition_rules.opt_state_slots(op_.type):
        names.extend(op_.inputs.get(slot, []))
    d0 = None
    for n in names:
        var = block._find_var_recursive(n)
        if (var is None or getattr(var, "_sharding", None)
                or getattr(var, "type", None) == VarType.SELECTED_ROWS
                or var.shape is None or not list(var.shape)):
            return None
        lead = var.shape[0]
        if not lead or lead < 0:
            return None
        if d0 is None:
            d0 = int(lead)
        elif int(lead) != d0:
            return None
    if d0 is None or d0 % ndev:
        return None
    return d0 // ndev


def _sharded_opt_state(ops, block, ndev):
    """Optimizer-state var names eligible for ZeRO-1 sharding on the
    pjit path: leading dim divisible by the mesh (jax has no
    uneven shards) and no explicit tensor-parallel annotation to
    respect.  GSPMD owns the update semantics there, so any op with
    derived state slots qualifies (including LAMB and the fused
    multi-tensor forms)."""
    names = set()
    for op_ in ops:
        slots = partition_rules.opt_state_slots(op_.type)
        if not slots:
            continue
        for slot in slots:
            for n in op_.inputs.get(slot, []):
                var = block._find_var_recursive(n)
                if (var is None or getattr(var, "_sharding", None)
                        or var.shape is None or not list(var.shape)):
                    continue
                d0 = var.shape[0]
                if d0 and d0 > 0 and d0 % ndev == 0:
                    names.add(n)
    return names


def _pjit_zero23_sets(ops, block, ndev, stage):
    """ZeRO-2/3 planning for the pjit path: (sharded_params,
    grad_constraints).  ``sharded_params`` (stage >= 3) pin their scope
    value and jit in/out shardings to P('dp') — each device holds
    1/ndev of every divisible parameter and GSPMD inserts the
    just-in-time all-gather at each forward/backward consumer (the
    gathered copy is a temporary XLA discards after use).
    ``grad_constraints`` (stage >= 2) maps update-op id -> grad names
    to pin with a with_sharding_constraint at the consumption point, so
    GSPMD lowers the batch-grad psum to a reduce-scatter feeding the
    shard update and the full gradient never materializes."""
    sharded_params: set = set()
    grad_constraints: Dict[int, List[str]] = {}
    if stage < 2 or ndev <= 1:
        return sharded_params, grad_constraints

    def divisible(name):
        var = block._find_var_recursive(name)
        if (var is None or getattr(var, "_sharding", None)
                or var.shape is None or not list(var.shape)):
            return False
        d0 = var.shape[0]
        return bool(d0) and d0 > 0 and d0 % ndev == 0

    for op_ in ops:
        if not partition_rules.is_update_op(op_.type):
            continue
        params = op_.inputs.get("Param", [])
        grads = op_.inputs.get("Grad", [])
        if not params or len(params) != len(grads):
            continue
        cons = []
        for p, g in zip(params, grads):
            if not divisible(p) or not divisible(g):
                continue
            cons.append(g)
            if stage >= 3:
                sharded_params.add(p)
        if cons:
            grad_constraints[id(op_)] = cons
    return sharded_params, grad_constraints


def _plan_wrapped_updates(ops, block, ndev, stage):
    """Shard-aware update plans for the shard_map/fleet-collective path
    (extends ZeRO-1..3 beyond pjit — ROADMAP open item).  Each plan
    tells the interpreter to slice (param, grad) to the device's row
    block, run the elementwise update against the locally-resident
    optimizer-state shard, and all-gather only the updated parameter
    (stage < 3) — the reduce-scatter -> shard-update -> all-gather
    decomposition of fleet's sharding strategy expressed over one SPMD
    program.  Returns (plans, sharded_state, sharded_params)."""
    plans: Dict[int, dict] = {}
    sharded_state: set = set()
    sharded_params: set = set()
    if stage < 1 or ndev <= 1:
        return plans, sharded_state, sharded_params
    for op_ in ops:
        rows = _update_shard_rows(op_, block, ndev)
        if rows is None:
            continue
        state_names = [n for slot in partition_rules.opt_state_slots(op_.type)
                       for n in op_.inputs.get(slot, [])]
        # stage 1 shards optimizer state only: wrapping a stateless
        # update (sgd) would pay slice+gather for no memory win
        if not state_names and stage < 2:
            continue
        p = op_.inputs["Param"][0]
        plans[id(op_)] = {"param": p, "grad": op_.inputs["Grad"][0],
                          "rows": rows, "d0": rows * ndev}
        sharded_state.update(state_names)
        if stage >= 3:
            sharded_params.add(p)
    return plans, sharded_state, sharded_params


def _plan_param_prefetch(ops, block, sharded_params, skip_op_ids, depth,
                         depths=None):
    """ZeRO-3 parameter-prefetch schedule (FLAGS_dp_prefetch_depth):
    for each sharded parameter, its all-gather hoists ``depth`` ops
    ahead of the first consumer in each direction (forward / backward,
    split by op_role) and the gathered copy is discarded right after
    the last consumer of that direction — one gather per param per
    direction instead of the r8 per-consumer just-in-time gather.
    Optimize/LRSched-role ops (and ``skip_op_ids`` — the wrapped shard
    updates) consume the SHARD and are never given the gathered copy.
    Windows never cross a write to the parameter, and overlapping
    fwd/bwd windows merge into one gather.  ``depths`` (r16 per-param
    autotune, framework/ir.py prefetch_autotune_pass) overrides the
    uniform depth per parameter name — each param's window is just deep
    enough to hide its modeled gather time.  Returns (records,
    gather_before, discard_after): op index -> param names to gather
    just before / drop just after that op."""
    records: List[dict] = []
    gather_before: Dict[int, List[str]] = {}
    discard_after: Dict[int, List[str]] = {}
    depths = depths or {}
    if (depth <= 0 and not any(d > 0 for d in depths.values())) \
            or not sharded_params:
        return records, gather_before, discard_after
    from ..backward import OpRole

    skip_roles = int(OpRole.Optimize) | int(OpRole.LRSched)
    for p in sorted(sharded_params):
        p_depth = int(depths.get(p, depth))
        if p_depth <= 0:
            continue
        consumers: Dict[str, List[int]] = {}
        writes: List[int] = []
        for i, op_ in enumerate(ops):
            if p in op_.output_arg_names:
                writes.append(i)
            if id(op_) in skip_op_ids:
                continue
            role = int(op_.attrs.get("op_role", 0))
            if role & skip_roles:
                continue
            if p in op_.input_arg_names:
                d = "bwd" if role & int(OpRole.Backward) else "fwd"
                consumers.setdefault(d, []).append(i)
        windows = []
        for d in ("fwd", "bwd"):
            idxs = consumers.get(d)
            if not idxs:
                continue
            first, last = min(idxs), max(idxs)
            # the gathered copy must come from the value the consumer
            # would have seen: never hoist past a write to p
            lo = max((w + 1 for w in writes if w < first), default=0)
            windows.append({"param": p, "direction": d,
                            "gather_at": max(lo, first - p_depth),
                            "first_consumer": first, "last_consumer": last})
        merged: List[dict] = []
        for w in sorted(windows, key=lambda w: w["gather_at"]):
            if merged and w["gather_at"] <= merged[-1]["last_consumer"]:
                merged[-1]["last_consumer"] = max(
                    merged[-1]["last_consumer"], w["last_consumer"])
                merged[-1]["direction"] += "+" + w["direction"]
            else:
                merged.append(w)
        for w in merged:
            records.append(w)
            gather_before.setdefault(w["gather_at"], []).append(p)
            discard_after.setdefault(w["last_consumer"], []).append(p)
    return records, gather_before, discard_after


def _run_sharded_update(op_, env, block, plan, axis, sharded_params):
    """Execute one update op on this device's row-shard.  The grad may
    arrive full-width (allreduced) or already scattered to the local
    rows by c_fused_reduce_scatter — distinguished by its leading dim.
    ParamOut all-gathers back to full width unless the parameter itself
    is ZeRO-3 sharded, in which case the local rows ARE the value.  A
    full-width grad is restored after the update: later consumers (a
    grad-norm log, EMA, ...) must keep seeing the whole tensor, not
    this device's slice."""
    from jax import lax

    rows, d0 = plan["rows"], plan["d0"]
    p, g = plan["param"], plan["grad"]
    idx = lax.axis_index(axis)
    if p not in sharded_params:
        env[p] = lax.dynamic_slice_in_dim(env[p], idx * rows, rows, axis=0)
    gv = env.get(g)
    sliced_grad = gv is not None and int(gv.shape[0]) == d0
    if sliced_grad:
        env[g] = lax.dynamic_slice_in_dim(gv, idx * rows, rows, axis=0)
    if partition_rules.norm_update(op_.type):
        # LAMB/LARS trust ratio: whole-parameter norms from row-shards
        # via psum of the local squared sums (ROADMAP r8 seed)
        from ..ops.optimizer_ops import cross_shard_norms

        with cross_shard_norms(axis):
            registry.run_op(op_, env, block)
    else:
        registry.run_op(op_, env, block)
    if sliced_grad and g not in op_.output_arg_names:
        env[g] = gv
    if p not in sharded_params:
        env[p] = lax.all_gather(env[p], axis, axis=0, tiled=True)


def _analyze(program, feed_names, scope):
    """Shared read/write analysis (executor.analyze_state)."""
    from ..executor import analyze_state

    block = program.global_block()
    state_in, state_out, uses_rng, _ = analyze_state(
        block.ops, block, feed_names, scope
    )
    return block, state_in, state_out, uses_rng


def _compile_dp(compiled_program, executor, program, feed, fetch_names,
                scope, mesh):
    feed_spec = tuple(sorted(
        (k, tuple(np.shape(v)),
         str(v.dtype) if hasattr(v, "dtype") else str(np.asarray(v).dtype))
        for k, v in feed.items()
    ))
    # sharding annotations participate in the key: apply_tensor_parallel
    # after a first run must not silently reuse the replicated-layout jit
    shard_sig = tuple(sorted(
        (v.name, getattr(v, "_sharding", None))
        for blk in program.blocks for v in blk.vars.values()
        if getattr(v, "_sharding", None)
    ))
    from ..utils.cost_model import calibration_version as \
        _calibration_version
    from ..utils.flags import dp_plan_auto, flag

    # -- auto-parallel plan search (FLAGS_dp_plan=auto, r16) --------------
    # Resolve the plan BEFORE the cache key and the IR pipeline: the
    # searcher prices every candidate (parallel/plan_search.py) and
    # plan_memory() rejects budget-infeasible ones before any compile;
    # the winner's flag values are then in effect for the whole compile
    # (applied_plan), so the result is bit-identical to setting those
    # flags by hand.  The RESOLVED plan tuple keys the cache — a
    # re-search after calibration changes can never serve a stale
    # fixed-flag compile.
    from . import plan_search as _ps

    dp_axis = "dp" if "dp" in mesh.axis_names else mesh.axis_names[0]
    plan = None
    plan_report = None
    if dp_plan_auto():
        plan, plan_report = _ps.resolve_plan(
            program, set(feed), fetch_names, _mesh_fingerprint(mesh),
            int(mesh.shape[dp_axis]), _program_has_collectives(program),
            scope=scope)

    from ..framework import numerics as _numerics
    from ..utils import chaos as _chaos

    key = (program._uid, program._version, feed_spec, tuple(fetch_names),
           _mesh_fingerprint(mesh), shard_sig, executor._nhwc_enabled(),
           executor._tpu_fuse_enabled(),
           compiled_program.__dict__.get("_ir_passes", True),
           bool(flag("apply_ir_passes")), int(flag("dp_sharding") or 0),
           bool(flag("dp_comm_overlap")),
           str(flag("fuse_grad_size_in_MB")),
           str(flag("dp_grad_compress", "none")),
           int(flag("dp_prefetch_depth") or 0),
           bool(flag("while_static_scan")),
           _calibration_version(),
           # memory relief rewrites the compiled program (see the
           # executor compile key): mode or budget flips recompile
           str(flag("memory_relief", "off") or "off"),
           str(flag("hbm_budget_mb") or 0),
           str(flag("dp_plan", "") or ""),
           # probe config + armed chaos NaN injection (see the
           # executor compile key for the step-K recompile contract)
           _numerics.probe_signature(), _chaos.nan_poison_target(),
           # donation is compiled into the step (see the executor key)
           bool(flag("tpu_donate_buffers")),
           # the resolved plan stays LAST: introspection (tests,
           # dp_comm_stats --plan) reads key[-1] as the plan tuple
           plan.as_tuple() if plan is not None else None)
    cache = compiled_program.__dict__.setdefault("_dp_cache", {})
    if key in cache:
        # keep the introspection plans in sync with the entry served (a
        # hit after a flag flip must not expose another config's plan)
        compiled_program.__dict__["_prefetch_plan"] = \
            compiled_program.__dict__.get("_prefetch_plans", {}).get(key, [])
        compiled_program.__dict__["_memory_plan"] = \
            compiled_program.__dict__.get("_memory_plans", {}).get(key)
        compiled_program.__dict__["_plan"] = \
            compiled_program.__dict__.get("_plans", {}).get(key)
        compiled_program.__dict__["_plan_report"] = \
            compiled_program.__dict__.get("_plan_reports", {}).get(key)
        return cache[key]

    with RecordEvent("executor/compile", timed=True) as build, \
            _ps.applied_plan(plan):
        entry = _compile_dp_miss(
            compiled_program, executor, program, feed, fetch_names, scope,
            mesh, key, plan, plan_report)
    # the same histogram as a single-device miss: IR passes and the
    # construction of the step function
    executor._tm.current().build_s.observe(build.end - build.begin)
    return entry


def _compile_dp_miss(compiled_program, executor, program, feed,
                       fetch_names, scope, mesh, key, plan, plan_report):
    from ..utils.flags import flag

    label = "dp_" + program_label(program)
    cache = compiled_program.__dict__.setdefault("_dp_cache", {})
    # the chosen plan (or None under flag-driven config) is attached for
    # introspection: tests/test_plan_search.py reads it back
    chosen = (plan_report or {}).get("chosen") if plan is not None else None
    compiled_program.__dict__["_plan"] = chosen
    compiled_program.__dict__.setdefault("_plans", {})[key] = chosen
    compiled_program.__dict__["_plan_report"] = plan_report
    compiled_program.__dict__.setdefault("_plan_reports", {})[key] = \
        plan_report

    # the DP runner goes through the same compile-time rewrite pipeline
    # as the single-device executor (bn-act fusion, fused optimizers,
    # FLAGS_tpu_nhwc layout pass) — the two paths must not drift apart.
    # Sharding annotations live on the ORIGINAL program's vars; carry
    # them over when the pipeline produced a rewritten clone.
    rewritten = program
    if compiled_program.__dict__.get("_ir_passes", True):
        # memory relief context: the pass prices fixes against THIS
        # config's modeled plan (ndev on the batch axis, the shard_map
        # vs pjit path, the stage/prefetch flags applied_plan already
        # set) and may escalate the parallel plan in auto mode
        relief_mode = str(flag("memory_relief", "off") or "off")
        axis0 = "dp" if "dp" in mesh.axis_names else mesh.axis_names[0]
        use_shard_map = _program_has_collectives(program)
        rewritten = executor._apply_ir_passes(
            program, fetch_names, feed_names=tuple(sorted(set(feed))),
            scope=scope,
            relief_ctx={"ndev": int(mesh.shape[axis0]),
                        "use_shard_map": use_shard_map,
                        "allow_escalate": relief_mode == "auto"},
            auto_partitioned=not use_shard_map)
    if rewritten is not program:
        # the clone preserves block structure, so specs map block-by-
        # block (a global-block-only lookup would drop sub-block specs)
        for blk in program.blocks:
            tgt_blk = rewritten.blocks[blk.idx]
            for v in blk.vars.values():
                spec = getattr(v, "_sharding", None)
                if spec:
                    tv = tgt_blk.vars.get(v.name)
                    if tv is not None:
                        tv._sharding = spec
        program = rewritten

    from ..framework import verifier

    if verifier.enabled():
        # same final-program lint as the single-device compile path
        verifier.lint_or_raise(program, feed, fetch_names,
                               "data_parallel_compile")

    # numerics probe (FLAGS_numerics_probe): the shared IR pipeline left
    # one packed stats vector — fetch it on this path too, so the probe
    # stream covers pjit AND shard_map runs (run_data_parallel strips
    # it and feeds numerics.on_step)
    from ..framework import numerics as _numerics

    n_layout = getattr(program, "_numerics_layout", None)
    if n_layout:
        fetch_names = list(fetch_names) + [_numerics.STATS_VAR]

    block, state_in, state_out, uses_rng = _analyze(program, set(feed), scope)
    use_shard_map = _program_has_collectives(program)
    ops = list(block.ops)
    # batch shards on the 'dp' axis when present (TP meshes are e.g.
    # ('dp','mp')); otherwise the first axis
    axis = "dp" if "dp" in mesh.axis_names else mesh.axis_names[0]
    ndev_axis = int(mesh.shape[axis])
    stage = int(flag("dp_sharding") or 0)
    relief_rep = getattr(program, "_memory_relief", None)
    if relief_rep and relief_rep.get("engaged"):
        # relief fix (c) may have escalated the plan: the pass's chosen
        # stage overrides the flag-derived config for the rest of this
        # compilation (the flags themselves stay untouched — the cache
        # key is a deterministic pre-relief-config -> artifact map)
        stage = int(relief_rep.get("stage", stage))

    # FLAGS_dp_sharding staging (ZeRO / fleet sharding_stage):
    # * pjit path: stage 1 shards optimizer state, stage 2 additionally
    #   pins gradient layouts (GSPMD reduce-scatters into the shard
    #   update), stage 3 shards the parameters themselves with GSPMD's
    #   just-in-time gather at each consumer;
    # * shard_map path: the same ladder via explicit slice/update/gather
    #   plans on the update ops (and c_fused_reduce_scatter buckets the
    #   fuse pass emits at stage >= 2).
    opt_sharded: set = set()
    sharded_params: set = set()
    grad_constraints: Dict[int, List[str]] = {}
    wrapped_updates: Dict[int, dict] = {}
    if stage >= 1 and ndev_axis > 1:
        if use_shard_map:
            wrapped_updates, opt_sharded, sharded_params = \
                _plan_wrapped_updates(ops, block, ndev_axis, stage)
        else:
            opt_sharded = _sharded_opt_state(ops, block, ndev_axis)
            sharded_params, grad_constraints = _pjit_zero23_sets(
                ops, block, ndev_axis, stage)

    # ZeRO-3 prefetch (FLAGS_dp_prefetch_depth): hoist + dedupe the
    # sharded params' all-gathers on both paths — explicit op-position
    # motion on the shard_map path, gather-hint placement (an early
    # replicated sharding constraint the window's consumers read) on
    # the pjit path.  Depth 0 restores the on-demand gather.  A searched
    # plan (FLAGS_dp_plan=auto) may carry PER-PARAM depths from the
    # prefetch_autotune_pass — each window just deep enough to hide its
    # modeled gather, still guarded by the verifier's window rule below.
    pf_depth = int(flag("dp_prefetch_depth") or 0)
    if relief_rep and relief_rep.get("engaged"):
        pf_depth = int(relief_rep.get("prefetch_depth", pf_depth))
    pf_depths = dict(plan.per_param_depths) if plan is not None else None
    pf_records: List[dict] = []
    pf_gather: Dict[int, List[str]] = {}
    pf_discard: Dict[int, List[str]] = {}
    if stage >= 3 and sharded_params and (pf_depth > 0 or pf_depths):
        pf_records, pf_gather, pf_discard = _plan_param_prefetch(
            ops, block, sharded_params, set(wrapped_updates), pf_depth,
            depths=pf_depths)
        if pf_records and verifier.enabled():
            # the verifier's window rule generalizes the planner's local
            # never-hoist-past-a-write check: any future planner change
            # that lets a gather window span a param write fails here
            verifier.check_prefetch_plan_or_raise(
                ops, block, pf_records, "dp_prefetch_plan")
    compiled_program.__dict__["_prefetch_plan"] = pf_records
    compiled_program.__dict__.setdefault("_prefetch_plans", {})[key] = \
        pf_records

    # static SPMD shard-safety gate (framework/shard_analysis.py): the
    # distribution-state checks over the FINAL per-device program, with
    # this compile's prefetch windows so the comm/compute hazard check
    # covers the r16 gather motion too.  Warn-only by default;
    # FLAGS_shard_safety_strict raises before anything is traced.
    from ..framework import shard_analysis

    shard_analysis.gate(program, feed_names=tuple(feed),
                        fetch_names=tuple(fetch_names),
                        prefetch_records=pf_records,
                        where="data_parallel_compile")

    # static HBM plan for THIS (stage, mesh, path) config
    # (framework/memory_plan.py): per-device modeled timeline/peak with
    # the ZeRO shard scaling and the exact prefetch windows compiled
    # above; gauged, budget-checked and trace-emitted by the shared
    # surfacing path, attached as compiled._memory_plan.
    from ..framework import memory_plan as _mp

    mem_plan = _mp.plan_and_surface(
        program, "data_parallel_compile", feed_names=set(feed),
        fetch_names=fetch_names, block=block, ndev=ndev_axis,
        stage=stage, use_shard_map=use_shard_map,
        prefetch_records=pf_records or None,
        prefetch_depth=pf_depth, scope=scope)
    compiled_program.__dict__["_memory_plan"] = mem_plan
    compiled_program.__dict__.setdefault("_memory_plans", {})[key] = mem_plan

    # per-var PartitionSpecs from the partition-rule engine: classes
    # from program structure, logical axes from DEFAULT_LOGICAL_RULES,
    # mesh mapping from the stage's zero_mesh_rules, eligibility from
    # the planners above (divisibility / TP annotations), explicit
    # tensor-parallel annotations winning over everything — the same
    # derivation the shard_map in_specs use below.
    param_names = {p.name for p in program.all_parameters()}
    opt_names = {n for op_ in ops
                 for slot in partition_rules.opt_state_slots(op_.type)
                 for n in op_.inputs.get(slot, [])}

    def _var_class(name):
        if name in param_names:
            return "param"
        if name in opt_names:
            return "opt_state"
        if name.endswith("@GRAD"):
            return "grad"
        return "other"

    def _annotation(name):
        var = block._find_var_recursive(name)
        return getattr(var, "_sharding", None) if var is not None else None

    # one batch rule-engine call over every name the compile will place
    # (state in/out covers params, optimizer state, and persistable
    # writes; the matcher's replicated fallback covers stragglers)
    _spec_names = sorted(set(state_in) | set(state_out))
    _specs = partition_rules.dp_partition_specs(
        _spec_names, {n: _var_class(n) for n in _spec_names}, stage, axis,
        eligible=sharded_params | opt_sharded,
        annotations={n: a for n in _spec_names
                     if (a := _annotation(n))})

    def param_sharding(name):
        """ZeRO-3 dp shard, tensor-parallel annotation
        (parallel.tensor_parallel.shard_parameter), or replicated —
        all from the rule engine's batch derivation."""
        return NamedSharding(mesh, P(*_specs.get(name, ())))

    state_sharding = param_sharding

    def body(state_vals, feed_vals, per_shard: bool):
        env: Dict[str, Any] = dict(state_vals)
        env.update(feed_vals)
        if uses_rng and per_shard:
            # decorrelate shard RNG (dropout etc.)
            env[RNG_VAR] = jax.random.fold_in(
                env[RNG_VAR], jax.lax.axis_index(axis)
            )
        prefetched: Dict[str, Any] = {}   # shard_map: param -> full copy
        hint_orig: Dict[str, Any] = {}    # pjit: param -> sharded value
        hint_val: Dict[str, Any] = {}     # pjit: param -> hinted value
        for oi, op_ in enumerate(ops):
            # ZeRO-3 prefetch: issue the window's all-gather (or the
            # replicated gather hint GSPMD materializes there) ahead of
            # the first consumer
            for p in pf_gather.get(oi, ()):
                if p not in env:
                    continue
                if per_shard:
                    prefetched[p] = jax.lax.all_gather(env[p], axis,
                                                       axis=0, tiled=True)
                else:
                    hint_orig[p] = env[p]
                    env[p] = jax.lax.with_sharding_constraint(
                        env[p], NamedSharding(mesh, P()))
                    hint_val[p] = env[p]
            plan = wrapped_updates.get(id(op_))
            if plan is not None:
                _run_sharded_update(op_, env, block, plan, axis,
                                    sharded_params)
            else:
                if not per_shard and grad_constraints and stage >= 2:
                    # ZeRO-2 (pjit): pin each eligible grad to the dp
                    # shard at its consumption point — GSPMD then
                    # produces it via reduce-scatter and the full
                    # gradient never exists
                    for gname in grad_constraints.get(id(op_), ()):
                        gval = env.get(gname)
                        if gval is not None:
                            env[gname] = jax.lax.with_sharding_constraint(
                                gval, NamedSharding(mesh, P(axis)))
                if per_shard and sharded_params:
                    # ZeRO-3 (shard_map): consumers inside a prefetch
                    # window read the hoisted copy; anything the plan
                    # missed falls back to the r8 just-in-time gather.
                    # The shard is restored right after the op.
                    gathered = {}
                    for n in set(op_.input_arg_names):
                        if n in sharded_params and n in env:
                            gathered[n] = env[n]
                            env[n] = prefetched[n] if n in prefetched \
                                else jax.lax.all_gather(env[n], axis,
                                                        axis=0, tiled=True)
                    registry.run_op(op_, env, block)
                    for n, local in gathered.items():
                        if n not in op_.output_arg_names:
                            env[n] = local
                else:
                    registry.run_op(op_, env, block)
            if prefetched:
                # a write to a cached param makes the copy stale
                for n in op_.output_arg_names:
                    prefetched.pop(n, None)
            for p in pf_discard.get(oi, ()):
                # discard after the window's last consumer: the full
                # copy dies here, the resident value stays the shard
                prefetched.pop(p, None)
                if p in hint_orig and env.get(p) is hint_val.get(p):
                    env[p] = hint_orig.pop(p)
                    hint_val.pop(p, None)
        fetched = tuple(env[n] for n in fetch_names)
        new_state = {n: env[n] for n in state_out if n in env}
        return fetched, new_state

    # what the step rewrites (parameters, moments, BN statistics, the RNG
    # key) is donated and carried across steps by the session; what it
    # only reads stays the scope's live buffer and is never donated
    written = set(state_out)
    donatable = tuple(n for n in state_in if n in written)
    readonly = tuple(n for n in state_in if n not in written)

    if use_shard_map:
        def shard_fn(mut_vals, ro_vals, feed_vals):
            fetched, new_state = body({**ro_vals, **mut_vals}, feed_vals,
                                      per_shard=True)
            # stack per-shard fetches on a new leading axis
            fetched = tuple(f[None] for f in fetched)
            return fetched, new_state

        sm_sharded = opt_sharded | sharded_params

        def sm_spec(name):
            return P(axis) if name in sm_sharded else P()

        fn = jax.shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=({n: sm_spec(n) for n in donatable},
                      {n: sm_spec(n) for n in readonly},
                      {k: P(axis) for k in feed}),
            out_specs=(tuple(P(axis) for _ in fetch_names),
                       {n: sm_spec(n) for n in state_out}),
            check_vma=False,
        )

        def state_sharding(name):  # noqa: F811 — shard_map placement
            """Scope values enter pre-placed to match the in_specs: the
            ZeRO-sharded names arrive split over dp (1/ndev resident
            bytes per device), everything else replicated."""
            return NamedSharding(mesh, sm_spec(name))
    else:
        def fn(mut_vals, ro_vals, feed_vals):
            return body({**ro_vals, **mut_vals}, feed_vals, per_shard=False)

    # every state output is pinned to the placement its input has (and
    # not left to jit's choice, which could also all-gather ZeRO-sharded
    # moments back after the update): a step's outputs are then the next
    # step's inputs as they stand.  Fetches stay unconstrained (the None
    # prefix).
    shardings = {n: state_sharding(n) for n in written.union(state_in)}
    feed_sharding = NamedSharding(mesh, P(axis))
    jitted = jax.jit(
        named_step(fn, label),
        in_shardings=({n: shardings[n] for n in donatable},
                      {n: shardings[n] for n in readonly},
                      {k: feed_sharding for k in feed}),
        out_shardings=(None, {n: shardings[n] for n in state_out}),
        donate_argnums=(0,) if flag("tpu_donate_buffers") else ())

    # feed-conversion plan (target numpy dtype per feed name), computed
    # once per compilation — same helper as the single-device executor
    from ..executor import build_feed_plan

    entry = _CompiledDP(jitted, donatable, readonly, use_shard_map,
                        shardings, feed_sharding,
                        build_feed_plan(block, feed), n_layout)
    cache[key] = entry
    return entry


def run_data_parallel(compiled, executor, feed, fetch_list, scope, return_numpy):
    """One data-parallel step.  Its spans are the single-device
    executor's (``executor/step`` and children; see Executor.run), with
    ``dp/lookup`` for the compile look-up and ``dp/handle`` for the
    AOT call handle kept after the call."""
    from ..framework.core import default_main_program

    program = compiled._program
    if program is None:
        program = default_main_program()
    executor._step_no += 1
    with RecordEvent("executor/step") as step:
        if step.recording:
            step.set(program="dp_" + program_label(program),
                     step=executor._step_no)
        return _run_dp_step(compiled, executor, program, feed, fetch_list,
                            scope, return_numpy)


def _bind_state(entry, executor, program, scope, use_session):
    """The step's state as ``(mut, ro, arrays placed)``.  While the scope
    still carries the stamp of our own write-back it comes from the step
    session: no scope read, nothing placed.  Otherwise the walk: every
    value read from the scope and put where the step wants it (host
    values that will be donated through ``device_put_owned``)."""
    from ..framework.scope import Scope

    sess = entry.session if use_session else None
    if sess is not None:
        if sess.scope_ref() is scope and sess.stamp == Scope.mutation_counter:
            bound = sess.deref()
            if bound is not None:
                return bound[0], bound[1], 0
        # stale: something outside our write-back wrote a scope
        entry.session = None
        executor._tm.current().invalidations.inc()

    def placed(name, donated):
        val = scope.get(name)
        if name == RNG_VAR:
            if val is None:
                val = jax.random.key(program.random_seed or 0)
        elif val is None:
            raise RuntimeError(
                f"Variable {name!r} has no value in scope — run the "
                f"startup program first"
            )
        if isinstance(val, LoDTensor):
            val = val.numpy()
        if donated and isinstance(val, np.ndarray):
            return device_put_owned(val, entry.shardings[name])
        return jax.device_put(val, entry.shardings[name])

    mut = {n: placed(n, True) for n in entry.donatable}
    ro = {n: placed(n, False) for n in entry.readonly}
    return mut, ro, len(mut) + len(ro)


def _abstract(tree):
    """Shape, dtype and sharding of every array, and no live buffer: a
    kept argument would pin a stale copy of the model on the devices."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=getattr(a, "sharding", None)), tree)


def _describes(specs, args) -> bool:
    """Whether the kept abstract arguments still describe ``args`` (the
    shardings are the entry's own and cannot differ)."""
    leaves = jax.tree_util.tree_leaves
    return all(s.shape == a.shape and s.dtype == a.dtype
               for s, a in zip(leaves(specs), leaves(args)))


def _run_dp_step(compiled, executor, program, feed, fetch_list, scope,
                 return_numpy):
    from ..framework.scope import Scope, global_scope
    from ..executor import as_numpy, _fetch_name
    from ..utils.flags import flag

    with RecordEvent("dp/lookup"):
        scope = scope or global_scope()
        feed = dict(feed or {})
        fetch_names = [_fetch_name(f) for f in (fetch_list or [])]

        ndev = None
        if compiled._places is not None:
            ndev = len(compiled._places)
        mesh = compiled.__dict__.get("_mesh")
        if mesh is None:
            mesh = default_dp_mesh(ndev)
            compiled.__dict__["_mesh"] = mesh

        entry = _compile_dp(compiled, executor, program, feed, fetch_names,
                            scope, mesh)
        use_session = bool(flag("tpu_step_session", True))

    feed_vals = {}
    with RecordEvent("executor/feed") as feed_span:
        n_cast = 0
        for k, v in feed.items():
            arr = as_numpy(v) if isinstance(v, LoDTensor) else np.asarray(v)
            want = entry.feed_plan.get(k)
            if want is not None and arr.dtype != want:
                arr = arr.astype(want)
                n_cast += 1
            if arr.shape and arr.shape[0] % mesh.size != 0:
                raise ValueError(
                    f"feed {k!r} batch {arr.shape[0]} not divisible by "
                    f"{mesh.size} devices"
                )
            feed_vals[k] = jax.device_put(arr, entry.feed_sharding)
        if feed_span.recording:
            feed_span.set(bytes=int(sum(v.nbytes for v in feed_vals.values())),
                          arrays_cast=n_cast)

    with RecordEvent("executor/bind") as bind_span:
        mut, ro, n_placed = _bind_state(entry, executor, program, scope,
                                        use_session)
        if bind_span.recording:
            bind_span.set(arrays=n_placed)

    try:
        with RecordEvent("executor/call"):
            fetched, new_state = entry.fn(mut, ro, feed_vals)
    except Exception as e:
        from ..framework import memory_plan as _mp
        from ..framework import numerics as _nm

        if _mp.is_resource_exhausted(e):
            # OOM flight recorder (FLAGS_oom_debris_dir): dump the plan
            # for THIS config + telemetry + trace, then re-raise
            _mp.record_oom_debris(
                "data_parallel_step", e,
                plan=compiled.__dict__.get("_memory_plan"),
                program=program)
        # NaN/Inf flight recorder (FLAGS_numerics_debris_dir): an armed
        # check failure dumps the failing op + stats ring, then re-raise
        _nm.maybe_record_check_failure("data_parallel_step", e,
                                       program=program)
        raise
    finally:
        # step-scoped chaos nan_inject: spent once this dispatch ran
        # (see Executor._execute)
        from ..utils import chaos as _chaos_mod

        if _chaos_mod.nan_poison_target() is not None:
            _chaos_mod.consume_nan_poison()
    if entry.numerics:
        # probe stream: the stats vector rides the fetch tail.  Its
        # partials are cross-shard-combined in-program, so on the
        # shard_map path every stacked row is identical — row 0 is THE
        # value; the pjit fetch is already global.
        from ..framework import numerics as _nm

        with RecordEvent("executor/probe"):
            sv = np.asarray(fetched[-1])
            _nm.on_step(entry.numerics, sv[0] if entry.use_shard_map else sv,
                        where="data_parallel")
        fetched = fetched[:-1]

    # the call handle + ABSTRACT arguments, for whoever re-lowers this
    # step AOT to read the compiled HLO (tools/verify_overlap.py, the
    # benchmark): built at the entry's first step, and again only when
    # a walk has bound another shape (a session step binds the outputs
    # of the same program; the feed's shapes are in the compile key)
    with RecordEvent("dp/handle"):
        if entry.last_exec is None or (
                n_placed and not _describes(entry.last_exec[1:3], (mut, ro))):
            entry.last_exec = (entry.fn, _abstract(mut), _abstract(ro),
                               _abstract(feed_vals))
        compiled.__dict__["_last_exec"] = entry.last_exec
    with RecordEvent("executor/writeback"):
        # drop this step's references first: what was not donated dies
        # here, at scope.set, and not unnamed when the function returns
        del mut, feed_vals
        for name, val in new_state.items():
            scope.set(name, val)
        entry.session = None
        if use_session:
            # the next step binds from this step's outputs (the scope
            # holds the same objects): weakly, so that an abandoned
            # session never pins a second copy of the model
            try:
                entry.session = _StateSession(
                    weakref.ref(scope), Scope.mutation_counter,
                    {n: weakref.ref(new_state[n]) for n in entry.donatable},
                    ro)
            except (KeyError, TypeError):
                # a donated var was not produced, or a state value cannot
                # be weakly referenced (a SelectedRows pytree): no session
                pass

    if fetch_names:
        with RecordEvent("executor/fetch"):
            if return_numpy:
                return [as_numpy(v) for v in fetched]
            return [LoDTensor(v) for v in fetched]
    return None
