"""Repairs of the first chip run (PR 21): no fallback that hides the
device, a compile cache placed from outside, one process per chip, and
Mosaic kernels kept out of programs XLA partitions by itself."""
import os
import subprocess
import sys

import jax
import pytest

import paddle_tpu as pt
import paddle_tpu.fluid as fluid
from paddle_tpu.framework import place as place_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _python(argv, env_extra):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = ROOT + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env.update(env_extra)
    return subprocess.run([sys.executable, *argv], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=300)


def test_tpu_place_never_resolves_to_another_device(monkeypatch):
    # this host's backend is the CPU: a TPU place is an error, not a CPU
    with pytest.raises(RuntimeError, match="not a TPU"):
        pt.TPUPlace(0).jax_device()
    # pretend the 8 virtual devices are chips: in range resolves to THAT
    # device, out of range raises instead of wrapping round to chip 0
    monkeypatch.setattr(place_mod, "is_compiled_with_tpu", lambda: True)
    n = len(jax.devices())
    assert pt.TPUPlace(n - 1).jax_device() == jax.devices()[n - 1]
    with pytest.raises(ValueError, match="out of range"):
        pt.TPUPlace(n).jax_device()
    with pytest.raises(ValueError, match="out of range"):
        pt.CUDAPlace(n + 3).jax_device()


_CACHE_PROBE = (
    "import paddle_tpu, jax\n"
    "from jax._src import xla_bridge\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
    "print(paddle_tpu.COMPILE_CACHE_DIR)\n"
    "print(len(xla_bridge._backends))\n")


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_is_placed_from_outside(tmp_path, from_env):
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)} if from_env else {}
    r = _python(["-c", _CACHE_PROBE], env)
    assert r.returncode == 0, r.stderr[-2000:]
    jax_dir, pkg_dir, backends = r.stdout.split()[-3:]
    want = str(tmp_path) if from_env else os.path.join(ROOT, ".jax_cache")
    assert jax_dir == pkg_dir == want
    # importing the package takes no device: a launcher that imports it
    # leaves the chip to the child it starts
    assert backends == "0"


def test_chip_smoke_refuses_to_run_without_a_tpu():
    r = _python(["chip_smoke.py"], {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr and "nothing was run" in r.stderr
    assert r.stdout.strip() == ""       # no phase line, no result line


def test_launch_rejects_several_processes_on_a_tpu_host(monkeypatch):
    from paddle_tpu.distributed import launch

    monkeypatch.setattr(place_mod, "host_tpu_chips", lambda: 4)
    monkeypatch.setattr(sys, "argv", ["launch", "--nproc_per_node", "2",
                                      "train.py"])
    with pytest.raises(SystemExit, match="nproc_per_node=2 on a TPU host"):
        launch.launch()


def test_dryrun_multichip_fails_on_a_chip_with_too_few_devices(monkeypatch):
    sys.path.insert(0, ROOT)
    import __graft_entry__ as entry

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="not re-running on virtual CPU"):
        entry.dryrun_multichip(len(jax.devices()) + 1)


def test_epilogue_fuser_stays_out_of_auto_partitioned_programs():
    """Mosaic kernels cannot be partitioned by XLA's SPMD partitioner
    (the compiler refuses the pjit DP step on four chips), so the fuser
    runs for per-device programs only."""
    from paddle_tpu.utils import flags

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data("img", [3, 8, 8])
        x = fluid.layers.conv2d(img, 8, 3, padding=1, bias_attr=False)
        x = fluid.layers.batch_norm(x, act="relu")
        loss = fluid.layers.mean(x)
        fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
    exe = fluid.Executor(pt.CPUPlace())
    prev = flags.flag("tpu_fuse")
    flags.set_flags({"tpu_fuse": "1"})
    try:
        def fused(**kw):
            prog = exe._apply_ir_passes(main, [loss.name], **kw)
            return sum(o.type == "fused_conv_bn_act"
                       for o in prog.global_block().ops)

        assert fused() == 1
        assert fused(auto_partitioned=True) == 0
    finally:
        flags.set_flags({"tpu_fuse": prev})
