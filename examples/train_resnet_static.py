"""ResNet-50 static-graph training (the PaddleClas-style recipe).

Run:  python examples/train_resnet_static.py [--depth 50] [--batch 128]
      [--steps 100] [--tiny]

The static Program compiles to ONE XLA executable per feed signature
(whole-program jit with buffer donation); AMP runs matmuls/convs in
bf16 with f32 master weights. The run names `TPUPlace(0)` and fails on a
host with no chip; `--tiny` shrinks everything for a smoke run on the
CPU.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--depth", type=int, default=50)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--classes", type=int, default=1000)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--no-amp", action="store_true")
    ap.add_argument("--tiny", action="store_true",
                    help="smoke config (CPU-friendly)")
    args = ap.parse_args()
    if args.tiny:
        args.depth, args.batch, args.image = 18, 4, 32
        args.classes, args.steps = 10, 3

    import paddle_tpu as pt
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models.resnet import build_resnet

    main_prog, startup = fluid.Program(), fluid.Program()
    main_prog.random_seed = 1
    with fluid.program_guard(main_prog, startup):
        img = fluid.layers.data("img", [3, args.image, args.image])
        label = fluid.layers.data("label", [1], dtype="int64")
        loss, acc1, acc5, _ = build_resnet(img, label, depth=args.depth,
                                           class_num=args.classes)
        opt = fluid.optimizer.MomentumOptimizer(args.lr, 0.9)
        if not args.no_amp:
            opt = fluid.contrib.mixed_precision.decorate(opt)
        opt.minimize(loss)

    place = pt.CPUPlace() if args.tiny else pt.TPUPlace(0)
    exe = fluid.Executor(place)
    exe.run(startup)
    rng = np.random.RandomState(0)
    t0 = time.perf_counter()
    for step in range(args.steps):
        feed = {
            "img": rng.rand(args.batch, 3, args.image,
                            args.image).astype(np.float32),
            "label": rng.randint(0, args.classes,
                                 (args.batch, 1)).astype(np.int64),
        }
        out = exe.run(main_prog, feed=feed,
                      fetch_list=[loss.name, acc1.name])
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step}: loss {float(np.asarray(out[0])):.4f} "
                  f"acc1 {float(np.asarray(out[1])):.3f}", flush=True)
    dt = time.perf_counter() - t0
    print(f"{args.steps} steps, {args.batch * args.steps / dt:.1f} img/s "
          "(incl. host feeds; the benchmark cell is resnet50.train-b128, "
          "python3 benchmark/run.py)")


if __name__ == "__main__":
    main()
