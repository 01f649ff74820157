"""Runner ``train_static``: a static ``Program`` trained through
``fluid.Executor`` the way ``examples/train_resnet_static.py`` does it: host
numpy batches fed every step, the loss read back every step.  On more than one
chip the program goes through ``CompiledProgram.with_data_parallel``.

The configuration file names the model builder and the optimizer, the traffic
(job) file the batch, the pool of batches and what the compiled step must
hold.  Before the window the program's first two steps at the check batch are
held against the plain reference's forward pass and its own gradient step.
"""
from __future__ import annotations

import importlib
import math
import time

import numpy as np

from benchmark.lib import device as device_lib
from benchmark.lib.harness import longest, say
from benchmark.lib.watch import require_kernels


def _build(cell):
    import paddle_tpu.fluid as fluid

    model = cell.config["model"]
    builder = getattr(importlib.import_module(model["module"]),
                      model["builder"])
    image, classes = cell.config["image_size"], cell.config["num_classes"]
    opt_cfg = cell.config["optimizer"]
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = cell.seed % (2 ** 31 - 1) or 1
    with fluid.program_guard(main, startup):
        img = fluid.layers.data("img", [3, image, image])
        label = fluid.layers.data("label", [1], dtype="int64")
        loss = builder(img, label, depth=cell.config["depth"],
                       class_num=classes)[0]
        opt = getattr(fluid.optimizer, opt_cfg["class"])(
            opt_cfg["learning_rate"], opt_cfg["momentum"])
        if cell.config["amp"] == "bf16":
            opt = fluid.contrib.mixed_precision.decorate(opt)
        opt.minimize(loss)
    return main, startup, loss


def _batches(cell, batch: int, pool: int):
    """``pool`` host batches from the seed: one drawn, the others the same
    images in another order with their own labels (drawing 77 MB of floats
    per batch would be set-up that serves no step)."""
    image, classes = cell.config["image_size"], cell.config["num_classes"]
    rng = np.random.default_rng(cell.seed)
    base = rng.random((batch, 3, image, image), dtype=np.float32)
    labels = rng.integers(0, classes, (batch, 1), dtype=np.int64)
    return [{"img": np.ascontiguousarray(np.roll(base, i, axis=0)),
             "label": np.ascontiguousarray(np.roll(labels, i, axis=0))}
            for i in range(pool)]


def _check_against_reference(cell, env, exe, run_prog, startup, loss_name,
                             reference):
    """Steps 1 and 2 of the program at the check batch against the
    reference, both on this device, from the program's own initial weights."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.framework.scope import Scope, scope_guard

    check = cell.config["check"]
    feed = _batches(cell, cell.config["check_batch"], 1)[0]
    scope = Scope()
    with scope_guard(scope):
        with env.span("first_call"):
            exe.run(startup)
        init = {k: np.asarray(v) for k, v in scope.items()
                if not k.startswith("@")}
        with env.span("first_call"):
            got = [float(np.mean(exe.run(run_prog, feed=feed,
                                         fetch_list=[loss_name])[0]))
                   for _ in range(2)]
    opt = cell.config["optimizer"]
    depth = cell.config["depth"]
    with env.span("reference"):
        dev = env.devices[0]
        w = {k: jax.device_put(jnp.asarray(v, jnp.float32), dev)
             for k, v in reference.trainable(init).items()}
        want = jax.jit(lambda w, x, y: reference.two_step_losses(
            w, x, y, depth, opt["learning_rate"], opt["momentum"]))(
            w, jax.device_put(feed["img"], dev),
            jax.device_put(feed["label"][:, 0].astype(np.int32), dev))
        want = [float(v) for v in want]
    rel = [abs(g - r) / abs(r) for g, r in zip(got, want)]
    fall = (got[0] - got[1], want[0] - want[1])
    ok = (rel[0] <= check["loss1_rel_tol"] and rel[1] <= check["loss2_rel_tol"]
          and abs(fall[0] - fall[1]) <= check["fall_rel_tol"] * abs(fall[1]))
    say(check="reference", program_losses=got, reference_losses=want,
        rel_diff=rel, program_fall=fall[0], reference_fall=fall[1],
        tolerances=check, passed=ok)
    return ok


def run(cell, env, reference) -> dict:
    import paddle_tpu as pt
    import paddle_tpu.fluid as fluid
    from paddle_tpu.framework.scope import Scope, scope_guard

    jax = env.jax
    chips = cell.chips
    per_chip = cell.traffic["per_chip_batch"]
    batch = per_chip * chips
    with env.span("build"):
        main, startup, loss = _build(cell)
        place = pt.CPUPlace() if cell.rehearsal else pt.TPUPlace(0)
        exe = fluid.Executor(place)
        run_prog = main if chips == 1 else \
            fluid.CompiledProgram(main).with_data_parallel(
                loss_name=loss.name)
    correct = _check_against_reference(cell, env, exe, run_prog, startup,
                                       loss.name, reference)

    with env.span("batches"):
        pool = _batches(cell, batch, cell.traffic["batch_pool"])
    scope = Scope()
    expect = cell.traffic["expect"]
    with scope_guard(scope):
        with env.span("first_call"):
            exe.run(startup)
            for i in range(cell.traffic["warmup_steps"]):
                float(np.mean(exe.run(run_prog, feed=pool[i % len(pool)],
                                      fetch_list=[loss.name])[0]))
        found = require_kernels(env.watch, expect["kernels"], env.interpreted)
        warm = env.watch.mark()
        setup_counters = env.watch.since()

        # ---- the measured window ----------------------------------------
        t_open = time.perf_counter()
        setup_s = t_open - env.t_start
        ends, losses, i = [], [], 0
        while True:
            with env.span("feed"):
                feed = pool[i % len(pool)]
            with env.span("exe.run"):
                out = exe.run(run_prog, feed=feed, fetch_list=[loss.name])
                losses.append(float(np.mean(out[0])))
            t = time.perf_counter() - t_open
            ends.append(t)
            i += 1
            env.tracer.poll(t)
            if t >= cell.seconds:
                break
        env.tracer.stop(time.perf_counter() - t_open)
        in_window = env.watch.since(warm)

        memory = device_lib.memory_peak_bytes(env.devices)
        compiled_text = ""
        if expect["compiled"]:
            # the data-parallel runner keeps its step's call handle and
            # abstract arguments for exactly this: the compiled text
            jitted, *specs = run_prog.__dict__["_last_exec"]
            compiled_text = jitted.lower(*specs).compile().as_text()
    missing = [s for s in expect["compiled"] if s not in compiled_text]
    finite = all(math.isfinite(v) for v in losses)
    say(window="train", steps=len(ends),
        step_times=longest(zip([0.0] + ends, ends)),
        gc=env.gc_watch.since(t_open), first_loss=losses[0],
        last_loss=losses[-1], all_losses_finite=finite, kernel_calls=found,
        missing_in_compiled_step=missing, memory_peak_bytes=memory,
        memory_stats=device_lib.memory_stats(env.devices),
        **{f"window_{k}": v for k, v in in_window.items()})
    exe.close()
    return {
        "setup_s": setup_s, "window_s": ends[-1], "step_ends": ends,
        "samples_per_step": batch, "losses": losses,
        "attempted": len(ends), "failed": sum(not math.isfinite(v)
                                              for v in losses),
        "correct": bool(correct and finite and not missing),
        "compiles_in_window": in_window["compilations"],
        "memory_peak_bytes": memory,
        "setup_counters": setup_counters,
        "traced_steps": sum(1 for t in ends
                            if env.tracer.on_at is not None
                            and env.tracer.on_at < t
                            <= (env.tracer.off_at or -1)),
    }
