"""Serving-pass A/B (VERDICT r4 Weak #6): measure one inference speedup
delivered by the AnalysisPredictor pass list on an exported model.

Exports a 2-layer encoder written with the NAIVE attention composition
(matmul/softmax/matmul — what a user's exported model looks like), then
times AnalysisPredictor with the full TPU pass strategy vs with
fuse_multihead_attention_pass deleted.  At seq>=1024 the fused op takes
the Pallas flash kernel, so the pass is a real serving win, not a
cosmetic rewrite.

Usage: python tools/serving_ab.py [--seq 1024] [--batch 4] [--steps 20]
"""
from __future__ import annotations

import argparse
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def export_encoder(model_dir, seq, hidden=256, heads=4, layers=2):
    import paddle_tpu as pt
    import paddle_tpu.fluid as fluid

    d = hidden // heads
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 5
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [seq, hidden])
        h = x
        for _ in range(layers):
            q = fluid.layers.fc(h, hidden, num_flatten_dims=2)
            k = fluid.layers.fc(h, hidden, num_flatten_dims=2)
            v = fluid.layers.fc(h, hidden, num_flatten_dims=2)

            def split(t):
                t = fluid.layers.reshape(t, [-1, seq, heads, d])
                return fluid.layers.transpose(t, [0, 2, 1, 3])

            scores = fluid.layers.matmul(split(q), split(k),
                                         transpose_y=True,
                                         alpha=1.0 / np.sqrt(d))
            probs = fluid.layers.softmax(scores)
            ctxv = fluid.layers.matmul(probs, split(v))
            ctxv = fluid.layers.transpose(ctxv, [0, 2, 1, 3])
            ctxv = fluid.layers.reshape(ctxv, [-1, seq, hidden])
            h = fluid.layers.elementwise_add(
                h, fluid.layers.fc(ctxv, hidden, num_flatten_dims=2))
            ff = fluid.layers.fc(h, 4 * hidden, num_flatten_dims=2,
                                 act="gelu")
            h = fluid.layers.elementwise_add(
                h, fluid.layers.fc(ff, hidden, num_flatten_dims=2))
        out = fluid.layers.reduce_mean(h, dim=[2])
    exe = fluid.Executor(pt.TPUPlace(0))
    exe.run(startup)
    fluid.io.save_inference_model(model_dir, ["x"], [out], exe,
                                  main_program=main)


def run_one(model_dir, seq, batch, steps, with_mha_pass):
    from paddle_tpu.inference import AnalysisConfig, create_paddle_predictor

    config = AnalysisConfig(model_dir)
    config.switch_use_feed_fetch_ops(False)
    config.enable_tpu()
    if not with_mha_pass:
        config.pass_builder().delete_pass("fuse_multihead_attention_pass")
    pred = create_paddle_predictor(config)
    names = pred.get_input_names()
    handle = pred.get_input_handle(names[0])
    rng = np.random.RandomState(0)
    xv = rng.rand(batch, seq, int(os.environ.get("AB_HIDDEN", "256"))) \
        .astype(np.float32)
    handle.reshape(list(xv.shape))
    handle.copy_from_cpu(xv)
    out_h = pred.get_output_handle(pred.get_output_names()[0])
    for _ in range(3):
        pred.zero_copy_run()
    np.asarray(out_h.copy_to_cpu())
    # throughput loop UNCHANGED from prior rounds (pipelined dispatches,
    # one sync at the end) so the ex/s metric stays comparable across
    # BENCHMARKS.md rounds...
    t0 = time.perf_counter()
    for _ in range(steps):
        pred.zero_copy_run()
    np.asarray(out_h.copy_to_cpu())
    dt = time.perf_counter() - t0
    # ...latencies from a SEPARATE per-step-synced loop (a sync inside
    # the timed loop would redefine the throughput number)
    lats = []
    for _ in range(steps):
        s = time.perf_counter()
        pred.zero_copy_run()
        np.asarray(out_h.copy_to_cpu())
        lats.append(time.perf_counter() - s)
    prog_types = [op.type for op in pred.program().global_block().ops]
    return (batch * steps / dt, lats,
            prog_types.count("fused_multihead_attention"))


def main():
    from paddle_tpu.utils.loadgen import emit_json, pct

    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--json", action="store_true",
                    help="machine output only (the SERVING_AB= line)")
    args = ap.parse_args()
    with tempfile.TemporaryDirectory() as td:
        model_dir = os.path.join(td, "model")
        export_encoder(model_dir, args.seq)
        on, lat_on, n_fused = run_one(model_dir, args.seq, args.batch,
                                      args.steps, True)
        off, lat_off, n_off = run_one(model_dir, args.seq, args.batch,
                                      args.steps, False)
        assert n_fused > 0 and n_off == 0, (n_fused, n_off)
        if not args.json:
            print(f"seq={args.seq} b={args.batch}: mha-pass ON {on:.1f} "
                  f"ex/s ({n_fused} fused ops) vs OFF {off:.1f} ex/s "
                  f"-> {on / off:.2f}x")
        # one stable line so the A/B joins the bench trajectory
        # (same report helpers as tools/serving_bench.py)
        emit_json("SERVING_AB", {
            "seq": args.seq, "batch": args.batch, "steps": args.steps,
            "fused_ops": n_fused,
            "mha_on_ex_s": round(on, 2), "mha_off_ex_s": round(off, 2),
            "speedup": round(on / off, 3),
            "p50_latency_s_on": round(pct(lat_on, 50), 5),
            "p99_latency_s_on": round(pct(lat_on, 99), 5),
            "p50_latency_s_off": round(pct(lat_off, 50), 5),
            "p99_latency_s_off": round(pct(lat_off, 99), 5),
        })


if __name__ == "__main__":
    main()
