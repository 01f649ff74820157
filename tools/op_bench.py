"""Config-driven per-op micro-benchmark (the reference's
paddle/fluid/operators/benchmark/op_tester.cc analog) + the r14
one-lever-at-a-time A/B harness for the epilogue-fusion layer.

Usage:
    python tools/op_bench.py                      # built-in hot-op table
    python tools/op_bench.py --config cfg.json    # custom op list
    python tools/op_bench.py --op matmul --shape X=128,768 --shape Y=768,768

    # r14 A/B levers: fused-vs-unfused per chain kind, double-buffer
    # on/off — ONE lever per run line, everything else held fixed:
    python tools/op_bench.py --ab all [--quick] [--calibrate]

Each op runs through the SAME lowering registry the executor uses
(ops.registry.eager_call), jitted, so timings reflect the real kernel
XLA emits for that op in isolation.  Each --ab lever runs a whole train
program through the Executor pipeline with exactly one flag flipped
(FLAGS_tpu_fuse / FLAGS_tpu_double_buffer) and emits one stable
``OPBENCH={json}`` line (the ``BENCH=``/``SERVING=`` convention) with
wall times, fused-op counts, modeled memory-traffic savings from
``utils/cost_model.rank_fusion_candidates``, and a value-parity verdict.
``--calibrate`` feeds a measured step into the cost-model store first
(``cost_model.set_measured_profile``), so the reported rankings use
measured rates — the profile -> rank -> fuse -> A/B loop end to end.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


# 20 hot ops of the ResNet-50 / ERNIE / wide_deep steps, with
# representative shapes
DEFAULT_CONFIG = [
    {"op": "conv2d", "inputs": {"Input": {"shape": [32, 64, 56, 56]},
                                "Filter": {"shape": [64, 64, 3, 3]}},
     "attrs": {"paddings": [1, 1], "strides": [1, 1]}},
    {"op": "conv2d", "inputs": {"Input": {"shape": [32, 256, 56, 56]},
                                "Filter": {"shape": [64, 256, 1, 1]}}},
    {"op": "batch_norm",
     "inputs": {"X": {"shape": [32, 256, 56, 56]},
                "Scale": {"shape": [256]}, "Bias": {"shape": [256]},
                "Mean": {"shape": [256]}, "Variance": {"shape": [256]}},
     "outs": ["Y", "MeanOut", "VarianceOut", "SavedMean", "SavedVariance"]},
    {"op": "fused_batch_norm_act",
     "inputs": {"X": {"shape": [32, 256, 56, 56]},
                "Scale": {"shape": [256]}, "Bias": {"shape": [256]},
                "Mean": {"shape": [256]}, "Variance": {"shape": [256]}},
     "outs": ["Y", "MeanOut", "VarianceOut", "SavedMean", "SavedVariance"]},
    {"op": "matmul", "inputs": {"X": {"shape": [8192, 768]},
                                "Y": {"shape": [768, 768]}}},
    {"op": "matmul", "inputs": {"X": {"shape": [8192, 768]},
                                "Y": {"shape": [768, 3072]}}},
    {"op": "matmul", "inputs": {"X": {"shape": [8192, 768],
                                      "dtype": "bfloat16"},
                                "Y": {"shape": [768, 3072],
                                      "dtype": "bfloat16"}}},
    {"op": "softmax", "inputs": {"X": {"shape": [16, 12, 512, 512]}}},
    {"op": "layer_norm",
     "inputs": {"X": {"shape": [16, 512, 768]}, "Scale": {"shape": [768]},
                "Bias": {"shape": [768]}},
     "attrs": {"begin_norm_axis": 2},
     "outs": ["Y", "Mean", "Variance"]},
    {"op": "softmax_with_cross_entropy",
     "inputs": {"Logits": {"shape": [8192, 30522]},
                "Label": {"shape": [8192, 1], "dtype": "int32", "max": 30000}},
     "outs": ["Loss", "Softmax"]},
    {"op": "gelu", "inputs": {"X": {"shape": [16, 512, 3072]}}},
    {"op": "relu", "inputs": {"X": {"shape": [32, 256, 56, 56]}}},
    {"op": "elementwise_add", "inputs": {"X": {"shape": [32, 256, 56, 56]},
                                         "Y": {"shape": [32, 256, 56, 56]}}},
    {"op": "pool2d", "inputs": {"X": {"shape": [32, 64, 112, 112]}},
     "attrs": {"ksize": [3, 3], "strides": [2, 2], "paddings": [1, 1],
               "pooling_type": "max"}},
    {"op": "lookup_table",
     "inputs": {"W": {"shape": [30522, 768]},
                "Ids": {"shape": [8192, 1], "dtype": "int32", "max": 30000}}},
    {"op": "dropout", "inputs": {"X": {"shape": [16, 512, 768]}},
     "attrs": {"dropout_prob": 0.1,
               "dropout_implementation": "upscale_in_train"},
     "outs": ["Out", "Mask"]},
    {"op": "adam",
     "inputs": {"Param": {"shape": [768, 3072]},
                "Grad": {"shape": [768, 3072]},
                "Moment1": {"shape": [768, 3072]},
                "Moment2": {"shape": [768, 3072]},
                "Beta1Pow": {"shape": [1]}, "Beta2Pow": {"shape": [1]},
                "LearningRate": {"shape": [1]}},
     "outs": ["ParamOut", "Moment1Out", "Moment2Out", "Beta1PowOut",
              "Beta2PowOut"]},
    {"op": "momentum",
     "inputs": {"Param": {"shape": [256, 256, 3, 3]},
                "Grad": {"shape": [256, 256, 3, 3]},
                "Velocity": {"shape": [256, 256, 3, 3]},
                "LearningRate": {"shape": [1]}},
     "attrs": {"mu": 0.9}, "outs": ["ParamOut", "VelocityOut"]},
    {"op": "fused_multihead_attention",
     "inputs": {"Q": {"shape": [16, 12, 512, 64]},
                "K": {"shape": [16, 12, 512, 64]},
                "V": {"shape": [16, 12, 512, 64]}}},
    {"op": "transpose2", "inputs": {"X": {"shape": [16, 512, 12, 64]}},
     "attrs": {"axis": [0, 2, 1, 3]}, "outs": ["Out", "XShape"]},
    {"op": "reduce_sum", "inputs": {"X": {"shape": [16, 512, 768]}},
     "attrs": {"dim": [0, 1]}},
]


def _make_value(spec, rng):
    shape = list(spec.get("shape", []))
    dtype = spec.get("dtype", "float32")
    if dtype in ("int32", "int64"):
        hi = int(spec.get("max", 100))
        return rng.randint(0, hi, shape).astype(dtype)
    val = rng.rand(*shape).astype(np.float32)
    if dtype == "bfloat16":
        import jax.numpy as jnp

        return jnp.asarray(val, jnp.bfloat16)
    return val.astype(dtype)


def bench_entry(entry, repeat=None, warmup=3):
    import jax

    from paddle_tpu.ops import registry

    rng = np.random.RandomState(0)
    op_type = entry["op"]
    repeat = repeat or entry.get("repeat", 20)
    ins, arg_vals = {}, []
    for slot, spec in entry.get("inputs", {}).items():
        v = jax.device_put(_make_value(spec, rng))
        ins[slot] = v
    attrs = dict(entry.get("attrs", {}))
    outs = {o: 1 for o in entry.get("outs", ["Out"])}
    slots = sorted(ins)

    def run(*vals):
        r = registry.eager_call(op_type, {s: [v] for s, v in zip(slots, vals)},
                                attrs, outs,
                                rng_key=jax.random.key(0))
        return [x for vs in r.values() for x in vs if x is not None]

    jitted = jax.jit(run)
    vals = [ins[s] for s in slots]

    def sync(o):
        jax.block_until_ready(o)

    out = jitted(*vals)
    sync(out)
    for _ in range(warmup):
        out = jitted(*vals)
    sync(out)
    t0 = time.perf_counter()
    for _ in range(repeat):
        out = jitted(*vals)
    sync(out)
    # NOTE: the printed `floor` row (a [8]-element scale op) measures
    # the fixed per-execution dispatch cost — subtract it to compare ops.
    dt = (time.perf_counter() - t0) / repeat
    nbytes = sum(int(np.prod(s.get("shape", [1]))) *
                 (2 if s.get("dtype") == "bfloat16" else 4)
                 for s in entry.get("inputs", {}).values())
    return {"op": op_type, "ms": dt * 1e3,
            "approx_in_GB": nbytes / 1e9,
            "shapes": {k: v.get("shape") for k, v in
                       entry.get("inputs", {}).items()}}


# ==========================================================================
# r14 A/B levers — fused epilogues and input double-buffering
# ==========================================================================
def _build_conv_net(image, channels, classes=10):
    import paddle_tpu.fluid as fluid

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 11
    with fluid.program_guard(main, startup):
        img = fluid.layers.data("img", [3, image, image])
        label = fluid.layers.data("label", [1], dtype="int64")
        x = fluid.layers.conv2d(img, channels, 3, padding=1,
                                bias_attr=False)
        x = fluid.layers.batch_norm(x, act="relu")
        x = fluid.layers.conv2d(x, channels, 3, padding=1, bias_attr=False)
        x = fluid.layers.batch_norm(x, act="relu")
        x = fluid.layers.pool2d(x, pool_type="avg", global_pooling=True)
        logits = fluid.layers.fc(x, classes)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label))
        fluid.optimizer.MomentumOptimizer(0.1, 0.9).minimize(loss)
    return main, startup, loss


def _build_mlp(width, classes=10):
    import paddle_tpu.fluid as fluid

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 11
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [width])
        label = fluid.layers.data("label", [1], dtype="int64")
        h = fluid.layers.fc(x, width, act="relu")
        h = fluid.layers.fc(h, width, act="relu")
        logits = fluid.layers.fc(h, classes)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label))
        fluid.optimizer.MomentumOptimizer(0.1, 0.9).minimize(loss)
    return main, startup, loss


def _run_config(build, feed, steps, flag_updates):
    """Fresh scope + executor per config (compile caches key on flags,
    but a fresh Executor keeps the A/B airtight); returns (losses,
    ms/step, rewritten-program op-type counts)."""
    import paddle_tpu as pt
    import paddle_tpu.fluid as fluid
    from paddle_tpu.framework.scope import Scope, scope_guard
    from paddle_tpu.utils import flags as ptflags

    ptflags.set_flags(flag_updates)
    main, startup, loss = build()
    exe = fluid.Executor(pt.CPUPlace())
    with scope_guard(Scope()):
        exe.run(startup)
        losses = [float(exe.run(main, feed=feed,
                                fetch_list=[loss.name])[0])]
        t0 = time.perf_counter()
        for _ in range(steps):
            losses.append(float(exe.run(main, feed=feed,
                                        fetch_list=[loss.name])[0]))
        dt = (time.perf_counter() - t0) / steps
    rew = exe._apply_ir_passes(main, [loss.name])
    types = {}
    for o in rew.global_block().ops:
        types[o.type] = types.get(o.type, 0) + 1
    return losses, dt * 1e3, types, (main, exe, loss)


def _rank_summary(main, exe, loss):
    """Modeled per-chain savings on the UNFUSED rewritten program — the
    numbers the fuse pass ranked by."""
    from paddle_tpu.utils import cost_model, flags as ptflags

    ptflags.set_flags({"tpu_fuse": "0"})
    rew = exe._apply_ir_passes(main, [loss.name])
    cands = cost_model.rank_fusion_candidates(rew)
    return {
        "chains": len(cands),
        "modeled_saved_bytes_total": sum(c["saved_bytes"] for c in cands),
        "calibrated": bool(cands and cands[0]["calibrated"]),
        "top": [{k: c[k] for k in ("kind", "ops", "saved_bytes",
                                   "est_saved_s", "measured_epilogue_s")}
                for c in cands[:3]],
    }


def _maybe_calibrate(build, feed, enabled):
    """--calibrate: one measured unfused step -> the cost-model store,
    so rank_fusion_candidates runs on measured rates."""
    if not enabled:
        return None
    from paddle_tpu.utils import cost_model

    _, ms, _, _ = _run_config(build, feed, 1, {"tpu_fuse": "0"})
    cost_model.set_measured_profile(step_s=ms / 1e3, source="op_bench")
    return {"step_ms": round(ms, 3),
            "version": cost_model.calibration_version()}


def ab_fused(kind, quick=False, steps=None, calibrate=False):
    """One fused-vs-unfused A/B: same program, same feed, same scope
    discipline, FLAGS_tpu_fuse is the only lever."""
    import jax  # noqa: F401  (fail early off-jax)

    rng = np.random.RandomState(0)
    steps = steps or (3 if quick else 20)
    if kind == "conv_bn":
        image, ch, batch = (16, 16, 4) if quick else (32, 32, 16)
        build = lambda: _build_conv_net(image, ch)  # noqa: E731
        feed = {"img": rng.rand(batch, 3, image, image).astype(np.float32),
                "label": rng.randint(0, 10, (batch, 1)).astype(np.int64)}
    else:  # matmul_bias
        width, batch = (64, 16) if quick else (512, 128)
        build = lambda: _build_mlp(width)  # noqa: E731
        feed = {"x": rng.rand(batch, width).astype(np.float32),
                "label": rng.randint(0, 10, (batch, 1)).astype(np.int64)}
    cal = _maybe_calibrate(build, feed, calibrate)
    l0, ms0, t0, _ = _run_config(build, feed, steps, {"tpu_fuse": "0"})
    l1, ms1, t1, ctx1 = _run_config(build, feed, steps, {"tpu_fuse": "1"})
    fused_ops = {t: n for t, n in t1.items()
                 if t.startswith(("fused_conv_bn_act", "fused_matmul_bias"))}
    payload = {
        "lever": f"fuse:{kind}",
        "quick": quick,
        "steps": steps,
        "unfused_ms_per_step": round(ms0, 3),
        "fused_ms_per_step": round(ms1, 3),
        "fused_ops": fused_ops,
        "loss_bit_identical": l0 == l1,
        "rank": _rank_summary(*ctx1),
    }
    if cal:
        payload["calibration"] = cal
    return payload


def ab_double_buffer(quick=False, steps=None):
    """Double-buffer on/off over FRESH host batches each step (the lever
    is input staging, so the feed must actually change): same batch
    stream both ways, FLAGS_tpu_double_buffer is the only lever."""
    import paddle_tpu as pt
    import paddle_tpu.fluid as fluid
    from paddle_tpu.executor import FeedStager, double_buffered_feeds
    from paddle_tpu.framework.scope import Scope, scope_guard
    from paddle_tpu.utils import flags as ptflags

    steps = steps or (4 if quick else 30)
    image, ch, batch = (16, 16, 4) if quick else (32, 32, 32)
    build = lambda: _build_conv_net(image, ch)  # noqa: E731

    def batches():
        rng = np.random.RandomState(7)
        for _ in range(steps):
            yield {"img": rng.rand(batch, 3, image, image
                                   ).astype(np.float32),
                   "label": rng.randint(0, 10, (batch, 1)
                                        ).astype(np.int64)}

    results = {}
    losses = {}
    for mode in ("0", "1"):
        ptflags.set_flags({"tpu_double_buffer": mode, "tpu_fuse": "0"})
        main, startup, loss = build()
        exe = fluid.Executor(pt.CPUPlace())
        stager = FeedStager(main, ["img", "label"], pt.CPUPlace())
        with scope_guard(Scope()):
            exe.run(startup)
            ls = []
            t0 = time.perf_counter()
            for staged in double_buffered_feeds(batches(), stager):
                ls.append(float(exe.run(main, feed=staged,
                                        fetch_list=[loss.name])[0]))
            dt = (time.perf_counter() - t0) / steps
        results[mode] = dt * 1e3
        losses[mode] = ls
    return {
        "lever": "double_buffer",
        "quick": quick,
        "steps": steps,
        "off_ms_per_step": round(results["0"], 3),
        "on_ms_per_step": round(results["1"], 3),
        "loss_bit_identical": losses["0"] == losses["1"],
    }


def run_ab(levers, quick=False, steps=None, calibrate=False):
    from paddle_tpu.utils.loadgen import emit_json

    out = []
    for lever in levers:
        if lever == "double_buffer":
            payload = ab_double_buffer(quick=quick, steps=steps)
        else:
            payload = ab_fused(lever, quick=quick, steps=steps,
                               calibrate=calibrate)
        payload["backend"] = __import__("jax").default_backend()
        emit_json("OPBENCH", payload)
        out.append(payload)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", help="JSON list of op entries")
    ap.add_argument("--op")
    ap.add_argument("--shape", action="append", default=[],
                    help="SLOT=d0,d1,...")
    ap.add_argument("--attr", action="append", default=[],
                    help="name=json_value")
    ap.add_argument("--repeat", type=int, default=None)
    ap.add_argument("--ab", choices=["conv_bn", "matmul_bias",
                                     "double_buffer", "all"],
                    help="one-lever A/B harness (OPBENCH= lines)")
    ap.add_argument("--quick", action="store_true",
                    help="small shapes / few steps (the tier-1 smoke)")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--calibrate", action="store_true",
                    help="feed a measured step into the cost-model store "
                         "so --ab rankings use measured rates")
    args = ap.parse_args()

    if args.ab:
        levers = (["conv_bn", "matmul_bias", "double_buffer"]
                  if args.ab == "all" else [args.ab])
        run_ab(levers, quick=args.quick, steps=args.steps,
               calibrate=args.calibrate)
        return

    if args.op:
        entry = {"op": args.op, "inputs": {}, "attrs": {}}
        for s in args.shape:
            slot, dims = s.split("=")
            entry["inputs"][slot] = {
                "shape": [int(d) for d in dims.split(",")]}
        for a in args.attr:
            k, v = a.split("=", 1)
            entry["attrs"][k] = json.loads(v)
        cfg = [entry]
    elif args.config:
        with open(args.config) as f:
            cfg = json.load(f)
    else:
        cfg = DEFAULT_CONFIG
        # measured per-execution floor first: tiny op, pure overhead
        cfg = [{"op": "scale", "inputs": {"X": {"shape": [8]}},
                "attrs": {"scale": 1.0}}] + cfg

    print(f"{'op':34s} {'ms/call':>10s} {'~GB in':>8s}  shapes")
    for entry in cfg:
        try:
            r = bench_entry(entry, repeat=args.repeat)
            print(f"{r['op']:34s} {r['ms']:10.4f} {r['approx_in_GB']:8.3f}  "
                  f"{r['shapes']}")
        except Exception as e:  # keep the table going
            print(f"{entry['op']:34s} {'FAILED':>10s}          {e}")


if __name__ == "__main__":
    main()
