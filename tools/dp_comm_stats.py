"""Comm introspection for data-parallel programs: collective-op counts,
per-bucket sizes, estimated wire bytes, the backward-overlap timeline,
the modeled per-op backward cost timeline, and the ZeRO-3 prefetch plan
— so a PR's comm OR schedule regression is reviewable from the program
graph without a chip.

``collect_comm_stats(program, nranks)`` walks the (optionally IR-rewritten)
program and models each collective's ring cost plus, per fused bucket,
(ready-at-op, issued-at-op, est. exposed-comm-bytes): a bucket issued
before the final backward compute op overlaps with the remaining
backward and exposes nothing; a bucket issued after it serializes its
full wire cost.  ``timeline_stats(program, nranks)`` adds the
measurement-driven view (utils/cost_model.py): per-bucket modeled
(ready_s, start_s, finish_s) on a serialized comm stream against the
modeled backward horizon, and the exposed tail in bytes.  The CLI
builds a 20-grad-tensor MLP, applies the GradAllReduce transpile plus
the executor's IR pipeline under the current FLAGS
(FLAGS_fuse_grad_size_in_MB, FLAGS_dp_grad_compress,
FLAGS_dp_comm_overlap, FLAGS_dp_sharding, FLAGS_dp_prefetch_depth),
and prints the before/after JSON:

    python tools/dp_comm_stats.py [--nranks 8] [--mb 32] [--compress bf16]
                                  [--overlap 0|1] [--stage 0..3]
                                  [--autotune] [--prefetch-depth K]
                                  [--calibrate-ms MS]
                                  [--calibrate-from-trace TRACE.json]
                                  [--plan] [--optimizer adam]

``--plan`` (r16) prints the FLAGS_dp_plan=auto searcher's full
candidate table for the probe program — per candidate: modeled step
time (the argmin objective), plan_memory() modeled peak, and the
rejection reason when FLAGS_hbm_budget_mb ruled it out before compile
— plus which candidate won.  This is how a searched plan is reviewed
without running anything.

``--autotune`` (== --mb auto, FLAGS_fuse_grad_size_in_MB="auto") turns
on the measurement-driven variable-bucket mode and prints BOTH the
fixed-32MB and the autotuned schedule side by side, so the exposed-
bytes win is auditable; ``--calibrate-ms`` rescales the cost model so
the modeled backward matches a profiled step time before the
comparison, and ``--calibrate-from-trace`` reads that step time out of
a profiler chrome trace (MIN ``executor_run`` duration, the steady-
state floor — the r13 profile -> calibrate -> autotune loop, no
hand-copied number).  With
neither flag, a profile already recorded in this process (utils/
cost_model.set_measured_profile, fed by profiler.disable_profiler) is
used automatically — the same rates the autotune pass itself sees.  ``--prefetch-depth`` (with --stage 3) prints the ZeRO-3
parameter-prefetch plan: per param per direction, where the all-gather
is issued vs its first consumer, and the dedup ratio (consumer sites
vs gathers issued).

Wire model (bidirectional ring, bytes per chip):
  allreduce        2*(n-1)/n * payload
  reduce-scatter     (n-1)/n * payload  (incl. ZeRO-2 fused buckets)
  all-gather         (n-1)/n * payload
  broadcast          (n-1)/n * payload
  fused bucket, compress=bf16: payload halves on the wire (f32 -> bf16
  transport, f32 accumulation — ops/collective_ops.py _bf16_wire_psum).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

#: collective type -> wire-traffic factor in units of payload bytes
#: (multiplied by (n-1)/n for the ring)
_RING_FACTOR = {
    "c_allreduce_sum": 2.0,
    "c_allreduce_max": 2.0,
    "c_allreduce_min": 2.0,
    "c_allreduce_prod": 2.0,
    "allreduce": 2.0,
    "c_fused_allreduce": 2.0,
    "c_fused_reduce_scatter": 1.0,
    "c_reducescatter": 1.0,
    "c_allgather": 1.0,
    "c_broadcast": 1.0,
    "broadcast": 1.0,
    "c_concat": 1.0,
    "c_split": 0.0,
    "alltoall": 1.0,
}


def _var_bytes(block, name):
    from paddle_tpu.framework.dtype import to_numpy_dtype

    var = block._find_var_recursive(name)
    if var is None or var.shape is None or var.dtype is None:
        return None
    shape = [abs(int(d)) for d in var.shape if d is not None]
    try:
        itemsize = np.dtype(to_numpy_dtype(var.dtype)).itemsize
    except Exception:
        return None
    return int(np.prod(shape)) * itemsize if shape else itemsize


#: fused bucket ops the overlap timeline tracks
_BUCKET_OPS = ("c_fused_allreduce", "c_fused_reduce_scatter")


def _overlap_timeline(blk, buckets):
    """Annotate each fused bucket with its schedule position: ready_at_op
    (index of the last op producing any member grad), issued_at_op (the
    collective's index) and est_exposed_comm_bytes (the bucket's wire
    bytes when it is issued after the final backward compute op — i.e.
    nothing is left to hide it behind; 0 when backward still runs)."""
    ops = list(blk.ops)
    writers = {}
    last_backward = -1
    sync_ops = {"c_sync_comm_stream", "c_sync_calc_stream",
                "c_wait_comm_stream", "c_wait_calc_stream", "barrier"}
    for i, op_ in enumerate(ops):
        role = op_.attrs.get("op_role", 0)
        if (op_.type not in _RING_FACTOR and op_.type not in sync_ops
                and int(role) & 1):
            last_backward = i
        if op_.type not in _BUCKET_OPS:
            for n in op_.output_arg_names:
                writers.setdefault(n, []).append(i)
    for b in buckets:
        i = b["_index"]
        ready = max((j for n in b["tensors"]
                     for j in writers.get(n, []) if j < i), default=-1)
        b["ready_at_op"] = ready
        b["issued_at_op"] = i
        b["overlapped"] = i < last_backward
        b["est_exposed_comm_bytes"] = (
            0 if b["overlapped"] else int(b["wire_bytes"]))
        del b["_index"]
    n_over = sum(1 for b in buckets if b["overlapped"])
    return {
        "last_backward_op": last_backward,
        "n_buckets": len(buckets),
        "n_buckets_overlapped": n_over,
        "frac_buckets_overlapped": (n_over / len(buckets)) if buckets else 0.0,
        "est_exposed_comm_bytes": sum(b["est_exposed_comm_bytes"]
                                      for b in buckets),
    }


def collect_comm_stats(program, nranks=8):
    """Walk every block; return collective counts, payload/wire bytes,
    the fused-bucket inventory, and the overlap timeline."""
    ops_by_type = {}
    payload_total = 0
    wire_total = 0.0
    buckets = []
    ring = (nranks - 1) / float(nranks) if nranks > 1 else 0.0
    for blk in program.blocks:
        for i, op_ in enumerate(blk.ops):
            factor = _RING_FACTOR.get(op_.type)
            if factor is None:
                continue
            names = op_.inputs.get("X", [])
            sizes = [_var_bytes(blk, n) for n in names]
            payload = sum(s for s in sizes if s is not None)
            wire = factor * ring * payload
            if (op_.type in _BUCKET_OPS
                    and op_.attrs.get("compress", "none") == "bf16"):
                wire /= 2.0
            ops_by_type[op_.type] = ops_by_type.get(op_.type, 0) + 1
            payload_total += payload
            wire_total += wire
            if op_.type in _BUCKET_OPS and blk.idx == 0:
                buckets.append({
                    "n_tensors": len(names),
                    "payload_bytes": payload,
                    "wire_bytes": int(wire),
                    "compress": op_.attrs.get("compress", "none"),
                    "scatter": op_.type == "c_fused_reduce_scatter",
                    "tensors": list(names),
                    "_index": i,
                })
    overlap = _overlap_timeline(program.global_block(), buckets)
    return {
        "nranks": nranks,
        "collective_ops": sum(ops_by_type.values()),
        "ops_by_type": ops_by_type,
        "payload_bytes": payload_total,
        "est_wire_bytes_per_chip": int(wire_total),
        "buckets": buckets,
        "overlap": overlap,
    }


def grad_buffer_bytes(program, nranks, sharding_stage=0):
    """Steady-state gradient-buffer bytes (total, per device), modeled
    from the program graph: a grad whose bucket reduce-scatters (ZeRO-2,
    `c_fused_reduce_scatter`) — or, on the collective-free pjit path, an
    eligible grad under stage >= 2's sharding constraint — holds only
    its 1/nranks row-shard per device; everything else stays full."""
    blk = program.global_block()
    scattered = set()
    has_collectives = False
    for op_ in blk.ops:
        if op_.type.startswith("c_") or op_.type in ("allreduce", "broadcast"):
            has_collectives = True
        if op_.type == "c_fused_reduce_scatter":
            scattered.update(op_.inputs.get("X", []))

    def divisible(name):
        var = blk._find_var_recursive(name)
        return (var is not None and var.shape and var.shape[0]
                and var.shape[0] > 0 and var.shape[0] % nranks == 0)

    grads = {}
    for op_ in blk.ops:
        if "Grad" in op_.inputs and "Param" in op_.inputs:
            for g in op_.inputs.get("Grad", []):
                b = _var_bytes(blk, g)
                if b:
                    grads[g] = b
    total = sum(grads.values())
    per_dev = 0
    for g, b in grads.items():
        sharded = (g in scattered
                   or (not has_collectives and sharding_stage >= 2
                       and divisible(g)))
        per_dev += b // nranks if sharded else b
    return total, per_dev


def build_mlp_dp_program(n_layers=10, width=64, nranks=8, optimizer="sgd",
                         lr=0.1, seed=3, transpile=True):
    """An MLP with 2*n_layers grad tensors, optionally GradAllReduce-
    transpiled — the >=20-grad-tensor shape the fuse-pass acceptance
    criterion names.  Shared by this CLI and tests/test_dp_sharding.py
    so the program the stats describe is the program the tests verify.
    Returns (main, startup, loss)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.transpiler import GradAllReduce

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [width])
        y = fluid.layers.data("y", [1])
        h = x
        for _ in range(n_layers - 1):
            h = fluid.layers.fc(h, width, act="relu")
        pred = fluid.layers.fc(h, 1)
        loss = fluid.layers.reduce_mean(
            fluid.layers.square_error_cost(pred, y))
        if optimizer == "adam":
            fluid.optimizer.AdamOptimizer(lr).minimize(loss)
        elif optimizer == "lamb":
            fluid.optimizer.LambOptimizer(lr).minimize(loss)
        elif optimizer == "lars":
            fluid.optimizer.LarsMomentumOptimizer(lr, 0.9).minimize(loss)
        elif optimizer == "momentum":
            fluid.optimizer.MomentumOptimizer(lr, 0.9).minimize(loss)
        else:
            fluid.optimizer.SGDOptimizer(lr).minimize(loss)
    if transpile:
        GradAllReduce().transpile(startup_program=startup, main_program=main,
                                  rank=0, endpoints=["127.0.0.1:6170"],
                                  nranks=nranks)
    return main, startup, loss


def timeline_stats(program, nranks, cost_model=None):
    """Measurement-driven schedule view: per-bucket modeled (ready_s,
    start_s, finish_s) on ONE serialized comm stream vs the modeled
    backward horizon (utils/cost_model.py), plus the exposed tail in
    bytes at ICI rate.  This is the objective the
    FLAGS_fuse_grad_size_in_MB="auto" partition minimizes."""
    from paddle_tpu.utils.cost_model import (
        CostModel, backward_timeline, collective_time_s, model_comm_stream)

    cm = cost_model or CostModel()
    blk = program.global_block()
    ops = list(blk.ops)
    times, t_bwd_end = backward_timeline(ops, blk, cm)
    stats = collect_comm_stats(program, nranks)
    modeled = []
    for b in stats["buckets"]:
        ready = times[b["ready_at_op"]] if b["ready_at_op"] >= 0 else 0.0
        factor = 1.0 if b["scatter"] else 2.0
        modeled.append({
            "n_tensors": b["n_tensors"],
            "payload_bytes": b["payload_bytes"],
            "ready_s": ready,
            "comm_s": collective_time_s(b["payload_bytes"], factor,
                                        nranks, cm),
        })
    stream = model_comm_stream(modeled, t_bwd_end, cm)
    return {
        "t_backward_end_s": stream["t_backward_end_s"],
        "comm_finish_s": stream["finish_s"],
        "exposed_s": stream["exposed_s"],
        "est_exposed_bytes_model": stream["est_exposed_bytes_model"],
        "buckets": [
            {k: (round(v, 9) if isinstance(v, float) else v)
             for k, v in b.items()}
            for b in stream["buckets"]
        ],
    }


def measured_step_ms_from_trace(path: str) -> float:
    """MIN ``executor_run`` duration (ms) out of a profiler chrome
    trace — the steady-state step floor (a compile-dominated first
    step must not poison the calibration: best of several).  Raises
    SystemExit(2) on an unloadable trace or one with no executor_run events (progcheck convention: non-zero on bad
    input)."""
    try:
        from trace_report import TraceInvalid, load_trace
    except ImportError:  # tools/ not on path (library use)
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from trace_report import TraceInvalid, load_trace
    try:
        trace = load_trace(path)
    except TraceInvalid as e:
        print(f"ERROR: {e}", file=sys.stderr)
        raise SystemExit(2)
    durs = [float(e["dur"]) for e in trace["traceEvents"]
            if e.get("ph") == "X" and e.get("name") == "executor_run"]
    if not durs:
        print(f"ERROR: {path}: no executor_run events — profile a step "
              f"first (paddle_tpu.profiler with profile_path=...)",
              file=sys.stderr)
        raise SystemExit(2)
    return min(durs) / 1e3  # trace dur is us


def prefetch_stats(program, nranks, depth):
    """ZeRO-3 prefetch-plan summary for the shard_map path: where each
    sharded param's all-gather is issued vs its first consumer, and the
    dedup ratio (gathers issued vs consumer sites)."""
    from paddle_tpu.parallel.data_parallel import (
        _plan_param_prefetch, _plan_wrapped_updates)

    blk = program.global_block()
    ops = list(blk.ops)
    plans, _, sharded_params = _plan_wrapped_updates(ops, blk, nranks, 3)
    records, _, _ = _plan_param_prefetch(ops, blk, sharded_params,
                                         set(plans), depth)
    sites = 0
    for p in sharded_params:
        for op_ in ops:
            if id(op_) in plans:
                continue
            if p in op_.input_arg_names:
                sites += 1
    hoisted = [r for r in records if r["first_consumer"] > 0]
    return {
        "depth": depth,
        "n_sharded_params": len(sharded_params),
        "n_gathers": len(records),
        "n_consumer_sites": sites,
        "min_hoist_ops": min((r["first_consumer"] - r["gather_at"]
                              for r in hoisted), default=0),
        "windows": records,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--nranks", type=int, default=8)
    ap.add_argument("--layers", type=int, default=10)
    ap.add_argument("--width", type=int, default=64)
    ap.add_argument("--mb", default=None,
                    help="override FLAGS_fuse_grad_size_in_MB "
                         "(a number, or 'auto' for the measurement-"
                         "driven variable-bucket mode)")
    ap.add_argument("--compress", default=None,
                    help="override FLAGS_dp_grad_compress (none|bf16)")
    ap.add_argument("--overlap", type=int, default=None,
                    help="override FLAGS_dp_comm_overlap (0|1)")
    ap.add_argument("--stage", type=int, default=None,
                    help="override FLAGS_dp_sharding (0..3, ZeRO stage)")
    ap.add_argument("--autotune", action="store_true",
                    help="shorthand for --mb auto; also prints the "
                         "fixed-32MB schedule next to the autotuned one")
    ap.add_argument("--prefetch-depth", type=int, default=None,
                    help="override FLAGS_dp_prefetch_depth and print "
                         "the ZeRO-3 prefetch plan (needs --stage 3)")
    ap.add_argument("--calibrate-ms", type=float, default=None,
                    help="measured backward time of one step: rescales "
                         "the cost model before the schedule decision")
    ap.add_argument("--calibrate-from-trace", default=None,
                    metavar="TRACE",
                    help="chrome-trace JSON from a profiled run "
                         "(profiler profile_path / tools/trace_report): "
                         "the MIN executor_run duration (steady-state "
                         "floor) becomes the measured step time for "
                         "--calibrate-ms")
    ap.add_argument("--verify", action="store_true",
                    help="run tools/progcheck.py's static verifier on "
                         "the rewritten program (plus the rank-0-vs-"
                         "rank-1 collective-order check) and exit "
                         "non-zero on errors")
    ap.add_argument("--plan", action="store_true",
                    help="run the FLAGS_dp_plan=auto searcher "
                         "(parallel/plan_search.py) on the probe program "
                         "and print EVERY candidate's modeled step time, "
                         "modeled HBM peak, and why it was rejected — "
                         "the explainability surface for the searched "
                         "plan (honors FLAGS_hbm_budget_mb; "
                         "--calibrate-ms/-from-trace calibrate it)")
    ap.add_argument("--optimizer", default="sgd",
                    help="probe optimizer (sgd|adam|lamb|lars|momentum) "
                         "— adam gives the plan search real opt state "
                         "to shard")
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        # a virtual nranks-device mesh so the ZeRO-2 scatter rewrite
        # (which asks the mesh for the ring size) is visible on one host
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.nranks}"
        ).strip()
    import paddle_tpu as pt
    from paddle_tpu.parallel import mesh as mesh_mod
    from paddle_tpu.utils import flags

    updates = {}
    if args.autotune and args.mb is None:
        args.mb = "auto"
    if args.mb is not None:
        updates["fuse_grad_size_in_MB"] = args.mb
    if args.compress is not None:
        updates["dp_grad_compress"] = args.compress
    if args.overlap is not None:
        updates["dp_comm_overlap"] = args.overlap
    if args.stage is not None:
        updates["dp_sharding"] = args.stage
    if args.prefetch_depth is not None:
        updates["dp_prefetch_depth"] = args.prefetch_depth
    if updates:
        flags.set_flags(updates)
    auto = flags.fuse_grad_mb_auto()
    if (int(flags.flag("dp_sharding") or 0) >= 2 or auto) and \
            mesh_mod.current_mesh() is None:
        # the scatter rewrite AND the autotune ring model need the ring
        # size at pass time
        import jax

        mesh_mod.init_mesh((min(args.nranks, len(jax.devices())),), ("dp",))

    calibrate_ms = args.calibrate_ms
    calibration_source = "flag" if calibrate_ms is not None else None
    if args.calibrate_from_trace is not None:
        calibrate_ms = measured_step_ms_from_trace(
            args.calibrate_from_trace)
        calibration_source = args.calibrate_from_trace
    cm = None
    if calibrate_ms is not None:
        from paddle_tpu.utils.cost_model import (CostModel,
                                                 backward_timeline)

        probe, _, _ = build_mlp_dp_program(args.layers, args.width,
                                           args.nranks)
        blk = probe.global_block()
        _, modeled = backward_timeline(list(blk.ops), blk, CostModel())
        cm = CostModel().calibrated(calibrate_ms / 1e3, modeled)
        # publish to the process store so the autotune PASS models with
        # the SAME rates this CLI reports (the closed loop)
        from paddle_tpu.utils import cost_model as cost_model_mod

        cost_model_mod.set_measured_profile(
            step_s=calibrate_ms / 1e3,
            source=calibration_source or "dp_comm_stats")
    else:
        from paddle_tpu.utils import cost_model as cost_model_mod

        prof = cost_model_mod.measured_profile()
        if prof is not None:
            # a profiler session already recorded a step in this
            # process: model with it (same as the autotune pass will)
            probe, _, _ = build_mlp_dp_program(args.layers, args.width,
                                               args.nranks)
            blk = probe.global_block()
            cm = cost_model_mod.default_cost_model(list(blk.ops), blk)
            calibration_source = prof.get("source") or "measured_profile"

    main_p, _, loss = build_mlp_dp_program(args.layers, args.width,
                                           args.nranks,
                                           optimizer=args.optimizer)
    before = collect_comm_stats(main_p, args.nranks)
    exe = pt.Executor(pt.CPUPlace())
    rewritten = exe._apply_ir_passes(main_p, [loss.name])
    after = collect_comm_stats(rewritten, args.nranks)
    stage = int(flags.flag("dp_sharding") or 0)
    grad_total, grad_per_dev = grad_buffer_bytes(rewritten, args.nranks,
                                                 stage)
    out = {
        "calibration": calibration_source,
        "fuse_grad_size_in_MB": flags.flag("fuse_grad_size_in_MB"),
        "dp_grad_compress": flags.flag("dp_grad_compress"),
        "dp_comm_overlap": bool(flags.flag("dp_comm_overlap")),
        "dp_sharding": stage,
        "dp_prefetch_depth": int(flags.flag("dp_prefetch_depth") or 0),
        "grad_buffer_bytes_total": grad_total,
        "grad_buffer_bytes_per_dev": grad_per_dev,
        "unfused": before,
        "fused": after,
        "timeline": timeline_stats(rewritten, args.nranks, cm),
    }
    if auto:
        # the comparison the autotune exists for: same program under
        # the fixed default threshold
        flags.set_flags({"fuse_grad_size_in_MB": 32.0})
        fixed_rw = exe._apply_ir_passes(main_p, [loss.name])
        out["fixed_32mb"] = collect_comm_stats(fixed_rw, args.nranks)
        out["fixed_32mb_timeline"] = timeline_stats(fixed_rw, args.nranks,
                                                    cm)
        flags.set_flags({"fuse_grad_size_in_MB": "auto"})
    if stage >= 3 and int(flags.flag("dp_prefetch_depth") or 0) > 0:
        out["prefetch"] = prefetch_stats(rewritten, args.nranks,
                                         int(flags.flag(
                                             "dp_prefetch_depth")))
    if args.plan:
        # every candidate the FLAGS_dp_plan=auto searcher would
        # consider, priced with the same (possibly calibrated) cost
        # model — modeled step time, modeled peak, rejection reason
        from paddle_tpu.parallel import plan_search

        if mesh_mod.current_mesh() is None:
            import jax

            mesh_mod.init_mesh((min(args.nranks, len(jax.devices())),),
                               ("dp",))
        plan_sel, report = plan_search.search_plan(
            main_p, ("x", "y"), (loss.name,), ndev=args.nranks,
            use_shard_map=True, cm=cm, strict=False)
        out["plan"] = report
        print(f"# plan search: {report['n_candidates']} candidates, "
              f"{report['n_rejected']} rejected by plan_memory(), "
              f"chosen: stage={plan_sel.stage} "
              f"bucket={plan_sel.bucket_mb} "
              f"prefetch={'auto' if plan_sel.prefetch_auto else plan_sel.prefetch_depth} "
              f"modeled={report['chosen']['modeled_step_s']:.3e}s "
              f"peak={report['chosen']['modeled_peak_mb']}MB",
              file=sys.stderr)
    rc = 0
    if args.verify:
        from progcheck import check_cross_device, check_program
        from paddle_tpu.transpiler import GradAllReduce

        diags = [d.as_dict() for d in
                 check_program(rewritten, feed_names=("x", "y"),
                               fetch_names=(loss.name,))]
        # ring-deadlock check: the same model transpiled for rank 1
        # must issue the identical collective sequence
        other, other_startup, other_loss = build_mlp_dp_program(
            args.layers, args.width, args.nranks, transpile=False)
        GradAllReduce().transpile(
            startup_program=other_startup, main_program=other, rank=1,
            endpoints=["127.0.0.1:6170", "127.0.0.1:6171"],
            nranks=args.nranks)
        other = exe._apply_ir_passes(other, [other_loss.name])
        diags += [d.as_dict() for d in
                  check_cross_device([rewritten, other])]
        n_err = sum(d["severity"] == "error" for d in diags)
        out["verify"] = {"errors": n_err,
                         "warnings": len(diags) - n_err,
                         "diagnostics": diags}
        rc = 1 if n_err else 0
    print(json.dumps(out, indent=2, default=str))
    return rc


if __name__ == "__main__":
    sys.exit(main())
