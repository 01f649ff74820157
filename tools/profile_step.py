"""Profile one model's train step on the attached chip and print a
per-fusion device-time table.

Usage: python tools/profile_step.py [resnet50|ernie] [--steps N]
           [--top-ops N] [--quick]
Writes the raw trace under /tmp/pt_trace/, prints the top device ops
aggregated by fusion kind, and ends with one stable ``PROFILE={json}``
line (the ``SERVING=``/``BENCH=`` convention) so the driver can diff
profiles across rounds without scraping the human tables.

``--top-ops N`` (r14) prints the top-N ops by measured self-time from
the trace — or, when the backend wrote no device trace (the CPU proxy),
by modeled time from the profile-calibrated cost model — followed by the
ranked epilogue-fusion candidates: the human-readable front door to
``utils/cost_model.rank_fusion_candidates``.  ``--quick`` is the
bounded tier-1 smoke (tiny resnet, 2 steps, implies --top-ops 10).
"""
from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_resnet(steps=8, batch=128, image=224, amp=True, depth=50,
               place=None):
    import jax
    import numpy as np

    import paddle_tpu as pt
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models.resnet import build_resnet

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 1
    with fluid.program_guard(main, startup):
        img = fluid.layers.data("img", [3, image, image])
        label = fluid.layers.data("label", [1], dtype="int64")
        loss, acc1, acc5, logits = build_resnet(img, label, depth=depth)
        opt = fluid.optimizer.MomentumOptimizer(0.1, 0.9)
        if amp:
            opt = fluid.contrib.mixed_precision.decorate(opt)
        opt.minimize(loss)
    place = place or pt.TPUPlace(0)
    exe = fluid.Executor(place)
    exe.run(startup)
    rng = np.random.RandomState(0)
    device = place.jax_device()
    feed = {
        "img": jax.device_put(
            rng.rand(batch, 3, image, image).astype(np.float32), device),
        "label": jax.device_put(
            rng.randint(0, 1000, (batch, 1)).astype(np.int32), device),
    }

    def step():
        return exe.run(main, feed=feed, fetch_list=[loss.name],
                       return_numpy=False)

    # --top-ops introspects the program the step actually compiled
    step.program, step.exe, step.loss = main, exe, loss
    return step


def run_ernie(steps=8, batch=None, seq=512, attn_dropout=True):
    # defaults: chip_smoke.py's BERT-base phase (b38, s512, AMP O2)
    batch = batch or int(os.environ.get("BENCH_BATCH", "38"))
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu.dygraph import jit_train_step
    from paddle_tpu.models.bert import BertConfig, BertForPretraining

    cfg = BertConfig(
        attention_probs_dropout_prob=0.1 if attn_dropout else 0.0)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int64)
    labels = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int64)
    from paddle_tpu.dygraph import enable_dygraph

    enable_dygraph()
    model = BertForPretraining(cfg)
    opt = fluid.optimizer.AdamOptimizer(1e-4,
                                        parameter_list=model.parameters())
    fn = jit_train_step(model, opt, lambda m, i, l: m(i, l),
                        amp=os.environ.get("BENCH_AMP", "1") != "0",
                        amp_level=os.environ.get("BENCH_AMP_LEVEL", "O2"))

    def step():
        return fn(ids, labels)

    step.fn = fn  # the raw (ids, labels) -> loss step
    return step


def top_ops_report(step, trace_device, n):
    """Top-N ops by measured self-time (the trace's per-event totals)
    or, on trace-less backends, by modeled per-op time from the
    profile-calibrated cost model — then the ranked fusion candidates
    (the front door to rank_fusion_candidates)."""
    rows = []
    source = "trace"
    if trace_device and trace_device.get("top_ops_ms_per_step"):
        rows = sorted(trace_device["top_ops_ms_per_step"].items(),
                      key=lambda kv: -kv[1])[:n]
    else:
        source = "model"
        program = getattr(step, "program", None)
        exe = getattr(step, "exe", None)
        if program is None:
            print("--top-ops: no trace and no program to model "
                  "(dygraph model) — skipping")
            return None
        from paddle_tpu.utils import cost_model

        rew = exe._apply_ir_passes(program,
                                   [getattr(step, "loss").name])
        block = rew.global_block()
        cm = cost_model.default_cost_model(block.ops, block)
        agg = {}
        for op_ in block.ops:
            if op_.type in cost_model.COMM_OPS:
                continue
            agg[op_.type] = agg.get(op_.type, 0.0) + \
                cost_model.op_time_s(op_, block, cm) * 1e3
        rows = sorted(agg.items(), key=lambda kv: -kv[1])[:n]
    print(f"\ntop {n} ops by {'measured' if source == 'trace' else 'modeled'}"
          f" self-time:")
    for name, ms in rows:
        print(f"  {ms:10.4f} ms  {name[:100]}")
    cands = []
    program = getattr(step, "program", None)
    if program is not None:
        from paddle_tpu.utils import cost_model, flags

        # rank on the UNFUSED rewrite: on-accelerator the pipeline has
        # already fused these chains (FLAGS_tpu_fuse auto), and ranking
        # the fused program would always report zero candidates
        old_fuse = flags._flags.get("FLAGS_tpu_fuse")
        flags._flags["FLAGS_tpu_fuse"] = "0"
        try:
            rew = step.exe._apply_ir_passes(program, [step.loss.name])
        finally:
            flags._flags["FLAGS_tpu_fuse"] = old_fuse
        cands = cost_model.rank_fusion_candidates(rew)
        if cands:
            print(f"\nranked fusion candidates ({len(cands)}, "
                  f"{'calibrated' if cands[0]['calibrated'] else 'uncalibrated'}):")
            for c in cands[:n]:
                meas = (f" measured={c['measured_epilogue_s'] * 1e3:.3f}ms"
                        if c["measured_epilogue_s"] else "")
                print(f"  {c['saved_bytes'] / 1e6:9.2f} MB saved  "
                      f"{'+'.join(c['ops'])}{meas}")
        else:
            print("\nno fusible epilogue chains "
                  "(already fused, or none present)")
    return {"source": source, "top": dict(rows),
            "fusion_candidates": len(cands),
            "fusion_saved_bytes": sum(c["saved_bytes"] for c in cands)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("model", nargs="?", default="resnet50",
                    choices=["resnet50", "ernie"])
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--top-ops", type=int, default=0, metavar="N",
                    help="print top-N ops by measured (trace) or modeled "
                         "self-time + ranked fusion candidates")
    ap.add_argument("--quick", action="store_true",
                    help="tiny bounded smoke (CPU-safe): resnet18 "
                         "image=32 batch=4, 2 steps, implies --top-ops 10")
    args = ap.parse_args()
    which = args.model
    steps = args.steps
    top_n = args.top_ops
    import jax
    import numpy as np

    if args.quick:
        steps = 2
        top_n = top_n or 10
        which = "resnet18_quick"
        import paddle_tpu as pt

        step = run_resnet(steps=steps, batch=4, image=32, amp=False,
                          depth=18, place=pt.CPUPlace())
    elif which == "ernie":
        step = run_ernie()
    else:
        step = run_resnet()

    def sync(out):
        v = out[0] if isinstance(out, (list, tuple)) else out
        arr = v.value() if hasattr(v, "value") else v
        np.asarray(arr)

    # warmup/compile
    for _ in range(3):
        out = step()
    sync(out)
    trace_dir = f"/tmp/pt_trace/{which}" + ("_amp" if os.environ.get("BENCH_AMP", "1") != "0" else "")
    os.makedirs(trace_dir, exist_ok=True)
    with jax.profiler.trace(trace_dir):
        for _ in range(steps):
            out = step()
        sync(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = step()
    sync(out)
    wall = (time.perf_counter() - t0) / steps
    print(f"wall per step (untraced): {wall * 1e3:.2f} ms")
    device = summarize(trace_dir, steps)
    # the stable machine line: wall + device breakdown + the measured
    # step time fed into the cost-model calibration store, so a
    # profile -> autotune round is auditable end to end
    from paddle_tpu.utils import cost_model
    from paddle_tpu.utils.loadgen import emit_json

    cost_model.set_measured_profile(step_s=wall, source="profile_step")
    # after calibration on purpose: the modeled top-ops fallback and the
    # fusion ranking then run on measured rates
    top = top_ops_report(step, device, top_n) if top_n else None
    emit_json("PROFILE", {
        "model": which,
        "steps": steps,
        "quick": args.quick,
        "backend": jax.default_backend(),
        "wall_ms_per_step": round(wall * 1e3, 3),
        "calibration": cost_model.measured_profile()["source"],
        "device": device,
        "top_ops": top,
    })


def summarize(trace_dir, steps):
    """Aggregate device-side event durations from the xplane protobuf via
    the tensorboard_plugin_profile-free path: parse trace.json.gz.
    Returns the machine-readable breakdown (None when the backend wrote
    no device trace — e.g. the CPU proxy)."""
    files = glob.glob(os.path.join(
        trace_dir, "plugins/profile/*/*.trace.json.gz"))
    if not files:
        print("no trace.json.gz found under", trace_dir)
        return None
    path = sorted(files)[-1]
    with gzip.open(path, "rt") as f:
        data = json.load(f)
    events = data.get("traceEvents", [])
    # device lanes: pid whose process name mentions TPU / device
    pid_names = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pid_names[e["pid"]] = e["args"].get("name", "")
    dev_pids = {p for p, n in pid_names.items()
                if "TPU" in n or "/device" in n.lower()}
    agg = {}
    total = 0.0
    for e in events:
        if e.get("ph") != "X" or e.get("pid") not in dev_pids:
            continue
        name = e.get("name", "")
        dur = e.get("dur", 0) / 1e3  # us -> ms
        # bucket by op kind
        key = name
        for tag in ("fusion", "convolution", "copy", "dynamic-update-slice",
                    "custom-call", "reduce", "transpose", "dot",
                    "all-reduce", "select-and-scatter", "scatter", "rng"):
            if tag in name:
                key = tag
                break
        agg[key] = agg.get(key, 0.0) + dur
        total += dur
    print(f"\ndevice total: {total / steps:.2f} ms/step  ({path})")
    for k, v in sorted(agg.items(), key=lambda kv: -kv[1])[:25]:
        print(f"  {v / steps:8.3f} ms  {k}")
    # also top individual events
    per_ev = {}
    for e in events:
        if e.get("ph") != "X" or e.get("pid") not in dev_pids:
            continue
        per_ev[e["name"]] = per_ev.get(e["name"], 0.0) + e.get("dur", 0) / 1e3
    print("\ntop 30 individual HLO ops:")
    for k, v in sorted(per_ev.items(), key=lambda kv: -kv[1])[:30]:
        print(f"  {v / steps:8.3f} ms  {k[:110]}")
    return {
        "total_ms_per_step": round(total / steps, 3),
        "by_kind_ms_per_step": {
            k: round(v / steps, 3)
            for k, v in sorted(agg.items(), key=lambda kv: -kv[1])[:25]},
        "top_ops_ms_per_step": {
            k[:110]: round(v / steps, 3)
            for k, v in sorted(per_ev.items(), key=lambda kv: -kv[1])[:10]},
        "trace": path,
    }


if __name__ == "__main__":
    main()
