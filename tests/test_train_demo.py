"""No-Python C++ training demo (native/train_demo.cpp; reference:
paddle/fluid/train/demo/demo_trainer.cc) — export a train step as
StableHLO, compile the demo against the PJRT C-API runtime, and train
from pure C++.

The run needs a PJRT plugin with a live device (like the native
inference test); the export + build steps run everywhere.
"""
import os
import subprocess
import tempfile

import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.fluid as fluid
from paddle_tpu.framework.scope import Scope, scope_guard
from paddle_tpu.inference.export import export_train_step

HERE = os.path.dirname(os.path.abspath(__file__))
NATIVE = os.path.join(os.path.dirname(HERE), "paddle_tpu", "native")


def _export_linear_train(dirname):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 4
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [4])
        y = fluid.layers.data("y", [1])
        pred = fluid.layers.fc(x, 1)
        loss = fluid.layers.reduce_mean(
            fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
    exe = fluid.Executor(pt.CPUPlace())
    with scope_guard(Scope()) as _:
        from paddle_tpu.framework import scope as scope_mod

        exe.run(startup)
        export_train_step(
            dirname, main,
            {"x": ((8, 4), "float32"), "y": ((8, 1), "float32")},
            [loss], scope=scope_mod._global_scope)
    return main


def test_export_train_step_artifacts(tmp_path):
    d = str(tmp_path / "exp")
    _export_linear_train(d)
    for f in ("model.stablehlo.mlir", "state.ptw", "weights.ptw",
              "meta.json", "meta.txt"):
        assert os.path.exists(os.path.join(d, f)), f
    import json

    meta = json.load(open(os.path.join(d, "meta.json")))
    assert meta["state_in"] and meta["feeds"] == ["x", "y"]
    # every state output loops back to a state input of the same name
    assert set(meta["state_out"]) <= set(meta["state_in"])
    assert "stablehlo" in open(
        os.path.join(d, "model.stablehlo.mlir")).read()[:4000]


def _build_demo(out_dir):
    from paddle_tpu.native.build import _tf_include_dir

    exe_path = os.path.join(out_dir, "train_demo")
    inc = _tf_include_dir()
    cmd = ["g++", "-O2", "-std=c++17",
           os.path.join(NATIVE, "train_demo.cpp"),
           os.path.join(NATIVE, "predictor_capi.cpp"),
           f"-I{NATIVE}"] + ([f"-I{inc}"] if inc else []) + \
          ["-ldl", "-o", exe_path]
    subprocess.run(cmd, check=True, capture_output=True, text=True)
    return exe_path


def test_train_demo_builds(tmp_path):
    exe = _build_demo(str(tmp_path))
    assert os.path.exists(exe)
    r = subprocess.run([exe], capture_output=True, text=True)
    assert r.returncode == 2 and "usage" in r.stderr


def test_train_demo_trains_without_python(tmp_path):
    from conftest import native_plugin_or_skip, pjrt_timeout

    plugin = native_plugin_or_skip()
    d = str(tmp_path / "exp")
    _export_linear_train(d)
    exe = _build_demo(str(tmp_path))
    # 20 steps through a cold TPU compile can run minutes
    r = subprocess.run([exe, d, plugin, "20"], capture_output=True,
                       text=True, timeout=pjrt_timeout())
    assert r.returncode == 0, r.stderr[-2000:]
    losses = [float(l.rsplit(" ", 1)[1])
              for l in r.stdout.splitlines()
              if l.startswith("step ")]
    assert len(losses) == 20, r.stdout
    assert losses[-1] < losses[0] * 0.9, losses
