#!/usr/bin/env python
"""Modeled-vs-measured HBM report: the runtime-reconciliation half of
the memory observability layer (framework/memory_plan.py is the static
half).

For each requested (DP path, ZeRO stage) the tool trains a probe for a
few steps on the mesh, reads the static planner's per-device model off
``compiled._memory_plan``, measures the same device with
``utils/memory.py`` (PJRT allocator counters on chip; the shard-aware
live-arrays census on the CPU proxy — exact for framework-held state,
blind to XLA scratch, which is why modeled RESIDENT bytes are the
reconciliation target there and the modeled PEAK rides along as the
chip-facing number), and prints them side by side with the
ndev-scaling checks the ZeRO ladder claims:

  stage >= 1: modeled opt-state bytes/dev ~ full/ndev
  stage >= 3: modeled param bytes/dev     ~ full/ndev

Serving-side note (r19): the planner's ``kv_pool`` class models the
paged K/V pools as FIXED blocks sized by the allocator's pool shape —
CoW prefix sharing happens at page granularity INSIDE those blocks, so
a page mapped by N sequences is modeled (and census'd) exactly once
and the agreement tolerance here is unaffected by
``FLAGS_kv_prefix_cache`` (tests/test_prefix_cache.py pins the
shared-pages-counted-once reconciliation directly).

Usage:
  python tools/mem_report.py [--probe mlp|resnet50] [--ndev 8]
        [--stage 0..3] [--ab] [--steps 2] [--budget-mb MB] [--json]
  python tools/mem_report.py --quick     # bounded tier-1 smoke:
        mlp probe, stages {0,3} x both paths, asserts modeled-vs-
        measured agreement (15%) and ndev-scaling (2%); exit 1 on miss

``--ab`` sweeps the whole ZeRO ladder (stages 0-3) on BOTH DP paths
(pjit and shard_map/fleet-collective).  One stable ``MEM={json}`` line
(the BENCH/SERVING convention) carries every row plus the check
verdicts.  The tool re-execs itself into a subprocess with a forced
``--ndev`` virtual CPU mesh when the current process has fewer devices;
on a real chip run it inline.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

_MB = float(1 << 20)


def build_args():
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--probe", choices=("mlp", "resnet50"), default="mlp")
    ap.add_argument("--ndev", type=int, default=8)
    ap.add_argument("--stage", type=int, default=None, choices=(0, 1, 2, 3))
    ap.add_argument("--ab", action="store_true",
                    help="sweep ZeRO stages 0-3 on both DP paths")
    ap.add_argument("--steps", type=int, default=2)
    ap.add_argument("--budget-mb", type=float, default=0.0,
                    help="also run the FLAGS_hbm_budget_mb check against "
                         "each config's modeled peak (reported, not "
                         "enforced)")
    ap.add_argument("--json", action="store_true",
                    help="machine output only (the MEM= line)")
    ap.add_argument("--quick", action="store_true",
                    help="bounded CI smoke with hard assertions")
    ap.add_argument("--no-subprocess", action="store_true",
                    help="never re-exec for the virtual mesh (real-chip "
                         "runs)")
    return ap


def _respawn(args, argv):
    """Force an ndev-device CPU mesh in a child process when this one
    can't provide it."""
    import subprocess

    env = dict(os.environ)
    flags = " ".join(f for f in env.get("XLA_FLAGS", "").split()
                     if "xla_force_host_platform_device_count" not in f)
    env["XLA_FLAGS"] = (flags + f" --xla_force_host_platform_device_count="
                                f"{args.ndev}").strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["PT_MEM_REPORT_WORKER"] = "1"
    env["PYTHONPATH"] = REPO + (os.pathsep + env["PYTHONPATH"]
                                if env.get("PYTHONPATH") else "")
    child_args = list(argv) if argv is not None else sys.argv[1:]
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__)] + child_args,
        env=env, capture_output=True, text=True, timeout=1800)
    sys.stdout.write(proc.stdout)
    sys.stderr.write(proc.stderr)
    return proc.returncode


# --------------------------------------------------------------------------
# probes
# --------------------------------------------------------------------------
def build_probe(kind: str, collective: bool, ndev: int):
    """(main, startup, loss, feed) — a fresh probe program per config
    (fresh name generator => one init dict could seed all, but each
    config re-inits to keep measured bytes independent)."""
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu.framework import unique_name

    unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 3
    if kind == "resnet50":
        from paddle_tpu.models.resnet import build_resnet

        with fluid.program_guard(main, startup):
            img = fluid.layers.data("img", [3, 32, 32])
            label = fluid.layers.data("label", [1], dtype="int64")
            loss, _, _, _ = build_resnet(img, label, depth=50, class_num=10)
            fluid.optimizer.MomentumOptimizer(0.1, 0.9).minimize(loss)
        rng = np.random.RandomState(0)
        feed = {"img": rng.rand(ndev, 3, 32, 32).astype(np.float32),
                "label": rng.randint(0, 10, (ndev, 1)).astype(np.int64)}
    else:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from dp_comm_stats import build_mlp_dp_program

        main, startup, loss = build_mlp_dp_program(
            n_layers=3, width=64, optimizer="adam", transpile=False)
        rng = np.random.RandomState(0)
        xs = rng.randn(8 * ndev, 64).astype(np.float32)
        feed = {"x": xs, "y": (xs[:, :1] * 2 + 1).astype(np.float32)}
    if collective:
        from paddle_tpu.transpiler import GradAllReduce

        GradAllReduce().transpile(startup_program=startup,
                                  main_program=main, rank=0,
                                  endpoints=["127.0.0.1:6170"],
                                  nranks=ndev)
    return main, startup, loss, feed


def _ndev_scaling(plan, ndev: int):
    """Modeled per-dev vs full/ndev expectation for params and opt
    state: the 1/ndev claims, checked from the plan's own per-var rows
    (full bytes are the unsharded facts, dev bytes the model)."""
    out = {}
    for cls in ("param", "opt_state"):
        full = sum(v["bytes"] for v in plan.per_var.values()
                   if v["class"] == cls)
        dev = sum(v["dev_bytes"] for v in plan.per_var.values()
                  if v["class"] == cls)
        expect = full / ndev if ndev else full
        out[cls] = {
            "full_bytes": int(full), "dev_bytes": int(dev),
            "expect_scaled_bytes": int(expect),
            "err_pct": (abs(dev - expect) / expect * 100.0
                        if expect else 0.0),
        }
    return out


def run_config(kind: str, collective: bool, stage: int, ndev: int,
               steps: int):
    import numpy as np

    import paddle_tpu as pt
    import paddle_tpu.fluid as fluid
    from paddle_tpu.framework.scope import Scope
    from paddle_tpu.parallel import mesh as mesh_mod
    from paddle_tpu.utils import flags as _flags
    from paddle_tpu.utils.memory import PeakTracker

    mesh_mod.registry().clear()
    mesh_mod.init_mesh()
    _flags.set_flags({"dp_sharding": stage, "fuse_grad_size_in_MB": 32.0,
                      "dp_grad_compress": "none", "dp_comm_overlap": 1,
                      "dp_prefetch_depth": 2 if stage >= 3 else 1})
    main, startup, loss, feed = build_probe(kind, collective, ndev)
    exe = pt.Executor(pt.CPUPlace())
    scope = Scope()
    exe.run(startup, scope=scope)
    compiled = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name)
    tracker = PeakTracker(0)
    last = None
    for _ in range(max(steps, 1)):
        last = exe.run(compiled, feed=feed, fetch_list=[loss], scope=scope)
        tracker.sample()
    plan = compiled.__dict__.get("_memory_plan")
    row = {
        "probe": kind,
        "path": "shard_map" if collective else "pjit",
        "stage": stage,
        "loss": float(np.mean(last[0])) if last else None,
        "measured": tracker.as_dict(),
        "measured_peak_mb": round(tracker.peak_bytes / _MB, 3),
    }
    if plan is not None:
        feed_bytes = sum(v["dev_bytes"] for v in plan.per_var.values()
                         if v["class"] == "feed")
        # the live-arrays census sees scope state, not the step's feed
        # staging (collected when run() returns) — compare against the
        # state-resident part of the model
        modeled_state = plan.resident_bytes - feed_bytes
        agree = (abs(modeled_state - tracker.peak_bytes)
                 / max(tracker.peak_bytes, 1) * 100.0)
        row.update({
            "modeled_peak_mb": round(plan.peak_mb, 3),
            "modeled_resident_mb": round(plan.resident_mb, 3),
            "modeled_state_mb": round(modeled_state / _MB, 3),
            "modeled_vs_measured_pct": round(agree, 2),
            "peak_op": {"index": plan.peak_op_index,
                        "type": plan.peak_op_type},
            "prefetch_windows": plan.prefetch_windows,
            "scaling": _ndev_scaling(plan, ndev),
        })
    return row


def serving_kv_rows(tp: int = 2):
    """The r23 serving-side reconciliation: one row per KV storage
    dtype (``FLAGS_kv_cache_dtype``) on a tiny decode engine at a FIXED
    byte budget.  The planner's ``kv_pool`` class must EQUAL the
    engine's census for every dtype — both count the pools at their
    storage itemsize plus the int8 scale pools — and the row carries
    the capacity the dtype buys (pages, tokens, tokens/GB) at the same
    bytes.

    The r24 ``tensor_parallel`` sub-section repeats the reconciliation
    on a ``tp``-way engine at the SAME per-device budget: the planner's
    ``tp``/``tp_rules`` division must reproduce the engine census for
    BOTH the kv_pool class AND the decoder weights (per-device 1/tp of
    the global bytes), and the pages the budget buys must scale exactly
    tp x (the capacity headline)."""
    from paddle_tpu.framework import memory_plan as mp
    from paddle_tpu.inference.gpt2_decoder import (DecoderConfig,
                                                   init_decoder_weights)
    from paddle_tpu.inference.serving import _EngineCore

    cfg = DecoderConfig(vocab_size=32, hidden=16, num_heads=2,
                        num_layers=2, max_seq_len=32)
    page_size = 4
    page_bytes_f32 = (2 * cfg.num_layers * cfg.num_heads * page_size
                      * cfg.head_dim * 4)
    budget_mb = 16 * page_bytes_f32 / _MB

    def build_row(dtype, degree):
        core = _EngineCore(cfg, init_decoder_weights(cfg),
                           page_size=page_size, kv_dtype=dtype,
                           kv_budget_mb=budget_mb, tp=degree)
        plan = mp.plan_memory(core.decode_prog,
                              feed_names=core.decode_feeds,
                              fetch_names=core.decode_fetch,
                              scope=core.scope, tp=core.tp,
                              tp_rules=core._tp_rules or None)
        modeled = int(plan.resident_by_class["kv_pool"])
        census = int(core.kv_pool_resident_bytes())
        # decoder weights land in the planner's "state" class; the
        # engine census is memory_stats()["weight_bytes"] — both are
        # PER-DEVICE (1/tp of global for rule-matched vars)
        modeled_w = int(sum(v["dev_bytes"] for v in plan.per_var.values()
                            if v["class"] == "state"))
        census_w = int(core.memory_stats()["weight_bytes"])
        ms = core.memory_stats()
        tokens = core.kv_config.num_pages * page_size
        return {
            "dtype": dtype,
            "num_pages": int(core.kv_config.num_pages),
            "modeled_kv_pool_bytes": modeled,
            "census_kv_pool_bytes": census,
            "modeled_weight_bytes": modeled_w,
            "census_weight_bytes": census_w,
            "modeled_eq_census": bool(modeled == census
                                      and modeled_w == census_w),
            "scale_pool_bytes": int(ms["kv_pool_scale_bytes"]),
            "capacity_tokens": int(tokens),
            "tokens_per_gb": int((1 << 30) * tokens
                                 // max(int(budget_mb * _MB), 1)),
        }

    rows = [build_row(dtype, 1)
            for dtype in ("float32", "bfloat16", "int8")]

    import jax

    tp = max(int(tp), 1)
    can_tp = (tp > 1 and len(jax.devices()) >= tp
              and cfg.num_heads % tp == 0)
    tp_rows = []
    if can_tp:
        for r1 in rows:
            row = build_row(r1["dtype"], tp)
            row["pages_scale_x"] = round(
                row["num_pages"] / max(r1["num_pages"], 1), 3)
            row["capacity_ok"] = bool(
                row["num_pages"] == tp * r1["num_pages"])
            tp_rows.append(row)
    return {"budget_mb": round(budget_mb, 6), "rows": rows,
            "all_reconciled": bool(all(r["modeled_eq_census"]
                                       for r in rows)),
            "tensor_parallel": {
                "tp": tp, "available": bool(can_tp), "rows": tp_rows,
                "all_reconciled": bool(all(
                    r["modeled_eq_census"] and r["capacity_ok"]
                    for r in tp_rows)) if can_tp else None,
            }}


def relief_rows(steps: int = 3):
    """r25 memory relief gate: train an over-budget probe (unmodified
    modeled peak > 2x FLAGS_hbm_budget_mb) unconstrained and again
    under ``FLAGS_memory_relief=auto``, and require the pass to land
    the modeled peak under budget with bit-identical losses — on the
    CPU proxy the remat replays and identity-lowered memcpy staging
    must not change a single bit."""
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.framework import unique_name
    from paddle_tpu.framework.scope import Scope
    from paddle_tpu.utils import flags as _flags

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from dp_comm_stats import build_mlp_dp_program

    def train(flags):
        saved = dict(_flags._flags)
        try:
            _flags.set_flags(flags)
            unique_name.switch()
            main, startup, loss = build_mlp_dp_program(
                n_layers=6, width=16, optimizer="sgd", transpile=False)
            exe = pt.Executor(pt.CPUPlace())
            scope = Scope()
            exe.run(startup, scope=scope)
            rng = np.random.RandomState(0)
            xs = rng.randn(64, 16).astype(np.float32)
            ys = (xs[:, :1] * 2 + 1).astype(np.float32)
            losses = []
            for _ in range(max(steps, 1)):
                out = exe.run(main, feed={"x": xs, "y": ys},
                              fetch_list=[loss], scope=scope)
                losses.append(np.asarray(out[0]).copy())
            plan = list(exe._cache.values())[-1]._memory_plan
            return losses, plan
        finally:
            _flags._flags.clear()
            _flags._flags.update(saved)

    base, plan0 = train({})
    budget_mb = plan0.peak_bytes / 2.0 / _MB
    relieved, plan1 = train({"hbm_budget_mb": budget_mb,
                             "memory_relief": "auto"})
    rep = plan1.relief or {}
    bit_identical = all(np.array_equal(a, b)
                        for a, b in zip(base, relieved))
    under = (int(rep.get("peak_after_bytes", 1 << 62))
             <= int(rep.get("budget_bytes") or 0))
    return {
        "probe": "mlp-sgd", "budget_mb": round(budget_mb, 6),
        "unconstrained_peak_mb": round(plan0.peak_bytes / _MB, 6),
        "modeled_peak_before_mb": round(
            rep.get("peak_before_bytes", 0) / _MB, 6),
        "modeled_peak_after_mb": round(
            rep.get("peak_after_bytes", 0) / _MB, 6),
        "engaged": bool(rep.get("engaged")),
        "n_fixes": len(rep.get("fixes", [])),
        "fixes": rep.get("fixes", []),
        "modeled_overhead_s": float(rep.get("modeled_overhead_s", 0.0)),
        "under_budget": bool(under),
        "loss_bit_identical": bool(bit_identical),
        "ok": bool(rep.get("engaged") and under and bit_identical),
    }


def format_relief(section):
    lines = [
        f"relief (memory_relief=auto @ {section['budget_mb']:.4f}MB "
        f"budget, unconstrained peak "
        f"{section['unconstrained_peak_mb']:.4f}MB):",
        f"  modeled peak {section['modeled_peak_before_mb']:.4f}MB -> "
        f"{section['modeled_peak_after_mb']:.4f}MB in "
        f"{section['n_fixes']} fix(es), modeled overhead "
        f"{section['modeled_overhead_s']:.2e}s, under_budget="
        f"{section['under_budget']} bit_identical="
        f"{section['loss_bit_identical']}",
        f"  {'var':<34} {'fix':<8} {'saved_B':>9} {'cost_s':>10}"]
    for f in section["fixes"][:12]:
        lines.append(f"  {f['var']:<34} {f['fix']:<8} "
                     f"{f['saved_bytes']:>9} "
                     f"{f['modeled_cost_s']:>10.2e}")
    return "\n".join(lines)


def format_serving_kv(section):
    lines = [f"serving kv_pool @ {section['budget_mb']:.4f}MB budget:",
             f"  {'dtype':<10} {'pages':>6} {'modeled':>9} {'census':>9} "
             f"{'eq':>3} {'scale_B':>8} {'tokens':>7} {'tok/GB':>9}"]
    for r in section["rows"]:
        lines.append(
            f"  {r['dtype']:<10} {r['num_pages']:>6} "
            f"{r['modeled_kv_pool_bytes']:>9} "
            f"{r['census_kv_pool_bytes']:>9} "
            f"{'ok' if r['modeled_eq_census'] else 'NO':>3} "
            f"{r['scale_pool_bytes']:>8} {r['capacity_tokens']:>7} "
            f"{r['tokens_per_gb']:>9}")
    tp_sec = section.get("tensor_parallel") or {}
    if tp_sec.get("available"):
        lines.append(f"serving kv_pool tp={tp_sec['tp']} (same per-device "
                     f"budget; modeled/census are PER-DEVICE):")
        lines.append(f"  {'dtype':<10} {'pages':>6} {'x':>5} "
                     f"{'kv_mod':>9} {'kv_cen':>9} {'w_mod':>8} "
                     f"{'w_cen':>8} {'eq':>3}")
        for r in tp_sec["rows"]:
            ok = r["modeled_eq_census"] and r["capacity_ok"]
            lines.append(
                f"  {r['dtype']:<10} {r['num_pages']:>6} "
                f"{r['pages_scale_x']:>5} "
                f"{r['modeled_kv_pool_bytes']:>9} "
                f"{r['census_kv_pool_bytes']:>9} "
                f"{r['modeled_weight_bytes']:>8} "
                f"{r['census_weight_bytes']:>8} "
                f"{'ok' if ok else 'NO':>3}")
    return "\n".join(lines)


def format_rows(rows):
    hdr = (f"{'path':<10} {'stage':>5} {'modeled_peak':>13} "
           f"{'modeled_state':>14} {'measured':>10} {'agree%':>7} "
           f"{'param/dev':>10} {'opt/dev':>10}  peak op")
    lines = [hdr]
    for r in rows:
        sc = r.get("scaling", {})
        p = sc.get("param", {}).get("dev_bytes", 0) / _MB
        o = sc.get("opt_state", {}).get("dev_bytes", 0) / _MB
        lines.append(
            f"{r['path']:<10} {r['stage']:>5} "
            f"{r.get('modeled_peak_mb', float('nan')):>11.3f}MB "
            f"{r.get('modeled_state_mb', float('nan')):>12.3f}MB "
            f"{r['measured_peak_mb']:>8.3f}MB "
            f"{r.get('modeled_vs_measured_pct', float('nan')):>7.2f} "
            f"{p:>8.3f}MB {o:>8.3f}MB  "
            f"#{r.get('peak_op', {}).get('index', '?')} "
            f"{r.get('peak_op', {}).get('type', '?')}")
    return "\n".join(lines)


def check_rows(rows, ndev, agree_tol_pct=15.0, scale_tol_pct=2.0):
    """The acceptance checks: stage-0 modeled-vs-measured agreement and
    the ZeRO ndev-scaling errors.  Returns (checks_dict, ok)."""
    checks = {"agree_tol_pct": agree_tol_pct,
              "scale_tol_pct": scale_tol_pct, "failures": []}
    for r in rows:
        tag = f"{r['path']}/stage{r['stage']}"
        if "modeled_vs_measured_pct" not in r:
            checks["failures"].append(f"{tag}: no plan attached")
            continue
        if r["stage"] == 0 and r["modeled_vs_measured_pct"] > agree_tol_pct:
            checks["failures"].append(
                f"{tag}: modeled state vs measured differ "
                f"{r['modeled_vs_measured_pct']:.2f}% > {agree_tol_pct}%")
        sc = r.get("scaling", {})
        if r["stage"] >= 1 and sc.get("opt_state", {}).get(
                "err_pct", 0) > scale_tol_pct:
            checks["failures"].append(
                f"{tag}: opt-state bytes/dev off full/{ndev} by "
                f"{sc['opt_state']['err_pct']:.2f}% > {scale_tol_pct}%")
        if r["stage"] >= 3 and sc.get("param", {}).get(
                "err_pct", 0) > scale_tol_pct:
            checks["failures"].append(
                f"{tag}: param bytes/dev off full/{ndev} by "
                f"{sc['param']['err_pct']:.2f}% > {scale_tol_pct}%")
    return checks, not checks["failures"]


def main(argv=None) -> int:
    args = build_args().parse_args(argv)
    if args.quick:
        args.probe = "mlp"
        args.steps = min(args.steps, 2)

    if not os.environ.get("PT_MEM_REPORT_WORKER") \
            and not args.no_subprocess:
        import jax

        if len(jax.devices()) < args.ndev:
            return _respawn(args, argv)

    stages = ([args.stage] if args.stage is not None
              else [0, 1, 2, 3] if args.ab
              else [0, 3] if args.quick else [0])
    if args.budget_mb:
        from paddle_tpu.utils import flags as _flags

        _flags.set_flags({"hbm_budget_mb": args.budget_mb})

    rows = []
    for collective in (False, True):
        for stage in stages:
            rows.append(run_config(args.probe, collective, stage,
                                   args.ndev, args.steps))
    checks, ok = check_rows(rows, args.ndev)
    # the r23 serving-side pin: modeled == census for every KV storage
    # dtype (the quantized pools + int8 scale pools price correctly)
    serving_kv = serving_kv_rows()
    if not serving_kv["all_reconciled"]:
        checks["failures"].append(
            "serving kv_pool: modeled != census for "
            + ", ".join(r["dtype"] for r in serving_kv["rows"]
                        if not r["modeled_eq_census"]))
        ok = False
    # the r24 TP pin: per-device modeled (plan_memory tp/tp_rules) ==
    # census AND tp x pages at the same per-device budget
    tp_sec = serving_kv["tensor_parallel"]
    if tp_sec["available"] and not tp_sec["all_reconciled"]:
        checks["failures"].append(
            f"serving kv_pool tp={tp_sec['tp']}: modeled != census or "
            "capacity != tp x for "
            + ", ".join(r["dtype"] for r in tp_sec["rows"]
                        if not (r["modeled_eq_census"]
                                and r["capacity_ok"])))
        ok = False
    # the r25 relief gate: an over-budget probe must land under budget
    # with bit-identical losses once FLAGS_memory_relief=auto engages
    relief = relief_rows(args.steps)
    if not relief["ok"]:
        checks["failures"].append(
            "relief: over-budget probe did not land under budget with "
            f"bit-identical loss (engaged={relief['engaged']} "
            f"under_budget={relief['under_budget']} "
            f"bit_identical={relief['loss_bit_identical']})")
        ok = False
    budget = {}
    if args.budget_mb:
        budget = {
            "budget_mb": args.budget_mb,
            "over": [f"{r['path']}/stage{r['stage']}" for r in rows
                     if r.get("modeled_peak_mb", 0) > args.budget_mb],
        }
    payload = {
        "probe": args.probe, "ndev": args.ndev, "steps": args.steps,
        "quick": bool(args.quick), "rows": rows, "checks": checks,
        "serving_kv": serving_kv, "relief": relief, "ok": ok,
        **({"budget": budget} if budget else {}),
    }
    if not args.json:
        print(format_rows(rows))
        print(format_serving_kv(serving_kv))
        print(format_relief(relief))
        for f in checks["failures"]:
            print(f"CHECK FAIL: {f}")
    print("MEM=" + json.dumps(payload, sort_keys=True))
    if args.quick and not ok:
        print("FAIL: modeled-vs-measured reconciliation out of "
              "tolerance", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
