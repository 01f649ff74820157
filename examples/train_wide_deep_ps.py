"""wide&deep CTR training on the parameter-server sparse path (the
PaddleRec-style recipe).

Run:  python examples/train_wide_deep_ps.py [--steps 60] [--thread 4]
      [--tiny]

Starts an in-process PS shard (the C++ binary-framed table service),
transpiles the program for distributed lookup, and trains through
`train_from_dataset` with N Hogwild worker threads. For a real cluster,
launch with `python -m paddle_tpu.distributed.launch_ps` and a
PaddleCloudRoleMaker instead of the UserDefinedRoleMaker here.
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
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--slots", type=int, default=10)
    ap.add_argument("--vocab", type=int, default=100_000)
    ap.add_argument("--thread", type=int, default=1)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    if args.tiny:
        args.steps, args.batch, args.vocab, args.slots = 4, 16, 500, 3

    import paddle_tpu as pt
    import paddle_tpu.fluid as fluid
    from paddle_tpu.distributed_ps import runtime
    from paddle_tpu.distributed_ps.service import PSServer
    from paddle_tpu.framework.scope import Scope, scope_guard
    from paddle_tpu.incubate.fleet.base.role_maker import (Role,
                                                           UserDefinedRoleMaker)
    from paddle_tpu.incubate.fleet.parameter_server import FleetTranspiler
    from paddle_tpu.models.rec import build_wide_deep

    class SyntheticDataset:
        thread_num = args.thread

        def _iter_batches(self):
            r = np.random.RandomState(7)
            for _ in range(args.steps):
                ids = r.randint(0, args.vocab, (args.batch, args.slots))
                feed = {f"s{k}": ids[:, k:k + 1].astype(np.int64)
                        for k in range(args.slots)}
                feed["dense"] = r.rand(args.batch, 13).astype(np.float32)
                feed["label"] = (ids[:, :1] % 2).astype(np.int64)
                yield feed

    server = PSServer("127.0.0.1:0", n_trainers=1).start()
    fleet = FleetTranspiler()
    try:
        fleet.init(UserDefinedRoleMaker(
            current_id=0, role=Role.WORKER, worker_num=1,
            server_endpoints=[server.endpoint]))
        main_p, startup = fluid.Program(), fluid.Program()
        main_p.random_seed = 11
        with fluid.program_guard(main_p, startup):
            sparse = [fluid.layers.data(f"s{i}", [1], dtype="int64")
                      for i in range(args.slots)]
            dense = fluid.layers.data("dense", [13])
            label = fluid.layers.data("label", [1], dtype="int64")
            loss, prob = build_wide_deep(
                sparse, dense, label, vocab_size=args.vocab, embed_dim=8,
                is_distributed=True)
            fleet.distributed_optimizer(
                fluid.optimizer.SGDOptimizer(0.05)).minimize(loss)
        exe = fluid.Executor(
            pt.CPUPlace() if args.tiny else pt.TPUPlace(0))
        with scope_guard(Scope()):
            exe.run(startup)
            fleet.init_worker()
            try:
                t0 = time.perf_counter()
                exe.train_from_dataset(main_p, SyntheticDataset(),
                                       thread=args.thread,
                                       fetch_list=[loss], print_period=20)
                dt = time.perf_counter() - t0
                print(f"{args.steps} steps x {args.batch}, "
                      f"{args.steps * args.batch / dt:.0f} examples/s "
                      f"(thread={args.thread})")
            finally:
                fleet.stop_worker()
    finally:
        server.stop()
        runtime.clear()


if __name__ == "__main__":
    main()
