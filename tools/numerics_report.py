"""Numerics health report over a probed training run.

Trains the seeded MLP for N steps with the numerics probe armed
(framework/numerics.py + the ``numerics_probe_pass``) and reports the
signal layer the quantization/remat rungs will stand on:

* a **per-var stat trajectory table** — for every probed var (program
  order): kind, producing op, first->last absmax / rms, |mean| drift
  and cumulative nonfinite count over the run;
* **global health** — grad/param norm trajectory, update ratio, the
  HealthMonitor verdict (``numerics.health()``) with any trips;
* optional **chaos** — ``--chaos "seed=3;nan_inject=relu@2"`` runs the
  end-to-end oracle: the injection must show up as nonfinite stats, a
  monitor trip, and (with ``--debris-dir``) a flight-recorder dump.

The last line is the stable one-line ``NUMERICS={json}``.

Usage:
  python tools/numerics_report.py [--steps 8] [--layers 3] [--width 16]
      [--probe-ops REGEX] [--chaos SPEC] [--debris-dir DIR] [--json]
  python tools/numerics_report.py --quick   # bounded tier-1 smoke:
      exit 2 when the probe stream is empty, a clean run trips the
      monitor, or stats disagree with the scope-side numpy recompute
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
if os.path.join(REPO, "tools") not in sys.path:
    sys.path.insert(0, os.path.join(REPO, "tools"))


def build_args():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--layers", type=int, default=3)
    ap.add_argument("--width", type=int, default=16)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--optimizer", default="sgd")
    ap.add_argument("--probe-ops", default="",
                    help="FLAGS_numerics_probe_ops regex (default: "
                         "role-selected vars only)")
    ap.add_argument("--chaos", default="", help="FLAGS_chaos schedule")
    ap.add_argument("--debris-dir", default="",
                    help="FLAGS_numerics_debris_dir for this run")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--quick", action="store_true")
    return ap


def run(args):
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.framework import numerics, unique_name
    from paddle_tpu.framework.scope import Scope
    from paddle_tpu.utils import chaos
    from paddle_tpu.utils import flags as _flags

    from dp_comm_stats import build_mlp_dp_program

    _flags.set_flags({"numerics_probe": 1,
                      "numerics_probe_ops": args.probe_ops,
                      "chaos": args.chaos,
                      "numerics_debris_dir": args.debris_dir})
    chaos.reset()
    numerics.reset()
    with unique_name.guard():
        main, startup, loss = build_mlp_dp_program(
            n_layers=args.layers, width=args.width, seed=args.seed,
            optimizer=args.optimizer, transpile=False)
    scope = Scope()
    exe = pt.Executor(pt.CPUPlace())
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(args.seed)
    losses = []
    with numerics.capture() as cap:
        for step in range(1, args.steps + 1):
            xs = rng.randn(args.batch, args.width).astype(np.float32)
            ys = (xs[:, :1] * 2 + 1).astype(np.float32)
            chaos.on_step(step)
            out = exe.run(main, feed={"x": xs, "y": ys},
                          fetch_list=[loss], scope=scope)
            losses.append(float(np.asarray(out[0]).reshape(-1)[0]))
    return cap, losses, scope


def summarize(cap, losses):
    from paddle_tpu.framework import numerics

    rows = []
    if cap:
        first, last = cap[0]["stats"], cap[-1]["stats"]
        for var in cap[0]["order"]:
            a, b = first[var], last.get(var, first[var])
            rows.append({
                "var": var, "kind": a["kind"], "op": a["op_type"],
                "absmax_first": a["absmax"], "absmax_last": b["absmax"],
                "rms_first": a["rms"], "rms_last": b["rms"],
                "nonfinite": sum(e["stats"][var]["nonfinite"]
                                 for e in cap if var in e["stats"]),
                "numel": a["numel"],
            })
    h = numerics.health()
    return {
        "steps": len(cap), "losses": losses,
        "grad_norm": [e["grad_norm"] for e in cap],
        "update_ratio": h.get("update_ratio"),
        "nonfinite_total": h["nonfinite_total"],
        "healthy": h["healthy"],
        "trips": h["trips"],
        "vars": rows,
    }


def human(rep):
    print(f"numerics_report: {rep['steps']} steps, "
          f"{len(rep['vars'])} probed vars, "
          f"healthy={rep['healthy']} "
          f"nonfinite_total={rep['nonfinite_total']}")
    if rep["losses"]:
        print(f"  loss: {rep['losses'][0]:.6f} -> {rep['losses'][-1]:.6f}"
              f"   grad_norm: {rep['grad_norm'][0]:.4f} -> "
              f"{rep['grad_norm'][-1]:.4f}   "
              f"update_ratio: {rep['update_ratio']}")
    hdr = (f"  {'var':28s} {'kind':7s} {'op':18s} "
           f"{'absmax first->last':>22s} {'rms first->last':>22s} "
           f"{'nonfin':>6s}")
    print(hdr)
    for r in rep["vars"]:
        print(f"  {r['var'][:28]:28s} {r['kind']:7s} {r['op'][:18]:18s} "
              f"{r['absmax_first']:10.4f}->{r['absmax_last']:10.4f} "
              f"{r['rms_first']:10.4f}->{r['rms_last']:10.4f} "
              f"{r['nonfinite']:6d}")
    for t in rep["trips"]:
        print(f"  TRIP: {t['kind']} at step {t['step']}: {t['detail']}")


def quick_check(args) -> int:
    """Smoke: a clean probed run streams stats for every step, stays
    healthy, and the probe's param stats agree with a numpy recompute
    from the scope."""
    import numpy as np

    args.steps = 3
    args.layers = 2
    args.width = 8
    args.batch = 8
    cap, losses, scope = run(args)
    rep = summarize(cap, losses)
    ok = rep["steps"] == 3 and rep["healthy"] \
        and rep["nonfinite_total"] == 0 and rep["vars"]
    # cross-check: last-step param stats vs the scope values they probed
    agree = True
    if cap:
        for var, st in cap[-1]["stats"].items():
            if st["kind"] != "param":
                continue
            v = np.asarray(scope.get(var), dtype=np.float64)
            for stat, got in (("absmax", float(np.max(np.abs(v)))),
                              ("rms", float(np.sqrt(np.mean(v * v)))),
                              ("mean", float(np.mean(v)))):
                if abs(st[stat] - got) > 1e-5 + 1e-4 * abs(got):
                    agree = False
    # loss trained downward on this convex toy
    trained = losses[-1] < losses[0]
    rep.update({"quick": True, "stats_agree_with_numpy": agree,
                "trained": bool(trained)})
    print(f"quick: streamed={rep['steps']} healthy={rep['healthy']} "
          f"stats_agree={agree} trained={trained}")
    print("NUMERICS=" + json.dumps(rep, default=str))
    return 0 if (ok and agree) else 2


def main():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    args = build_args().parse_args()
    if args.quick:
        sys.exit(quick_check(args))
    cap, losses, _scope = run(args)
    rep = summarize(cap, losses)
    if not args.json:
        human(rep)
    print("NUMERICS=" + json.dumps(rep, default=str))
    sys.exit(0)


if __name__ == "__main__":
    main()
