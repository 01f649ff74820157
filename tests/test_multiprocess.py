"""Real multi-process distributed tests.

The reference's main distributed oracle forks actual subprocesses and
compares per-step losses against a local single-process run
(test_dist_base.py:506 check_with_place:933).  These tests do the same:
every rank is a real OS process with its own jax runtime, rendezvousing
over the jax coordination service (gloo CPU collectives), so
TPURoleMaker / init_parallel_env's jax.distributed.initialize path runs
for real.
"""
import json
import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
RUNNER = os.path.join(HERE, "dist_runner.py")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


# -- gloo capability probe ---------------------------------------------------
# Some sandboxes ship a jaxlib whose gloo binding cannot initialize (the
# make_gloo_tcp_collectives signature rejects the runtime's arguments, or
# the coordination-service rendezvous is blocked).  That is an environment
# capability, not a framework bug — tests that need cross-process gloo
# collectives skip with a clear reason instead of failing.
_GLOO_ERR_SIGNATURES = (
    # gloo-specific markers only: a generic backend-init failure must
    # FAIL, not skip — we only excuse the sandbox's gloo binding
    "make_gloo_tcp_collectives",
    "jax_cpu_collectives_implementation",
)


def _maybe_skip_gloo(stderr: str, rank):
    if any(sig in (stderr or "") for sig in _GLOO_ERR_SIGNATURES):
        pytest.skip(
            f"gloo CPU collectives cannot initialize in this sandbox "
            f"(rank {rank}): {stderr.strip().splitlines()[-1][:200]}")


def _rank_env(rank, nproc, port):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # one device per process
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    env["PADDLE_COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
    env["PADDLE_NUM_PROCESSES"] = str(nproc)
    env["PADDLE_PROCESS_ID"] = str(rank)
    return env


def _spawn_ranks(mode, nproc=2, timeout=240):
    port = _free_port()
    procs = [
        subprocess.Popen(
            [sys.executable, RUNNER, mode],
            env=_rank_env(r, nproc, port),
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            cwd=HERE)
        for r in range(nproc)
    ]
    results = {}
    try:
        for r, p in enumerate(procs):
            out, err = p.communicate(timeout=timeout)
            if p.returncode != 0:
                _maybe_skip_gloo(err, r)
            assert p.returncode == 0, f"rank {r} failed:\n{err[-3000:]}"
            line = [l for l in out.splitlines() if l.startswith("RESULT=")]
            assert line, f"rank {r} printed no RESULT:\n{out}\n{err[-2000:]}"
            results[r] = json.loads(line[0][len("RESULT="):])
    finally:
        # a timeout/skip/assert on an early rank must not leak the later
        # ranks (they'd block minutes in the rendezvous holding the port)
        for q in procs:
            if q.poll() is None:
                q.kill()
    return results


def _single_process_oracle(steps=6, seed=3, lr=0.1):
    """Local full-batch run — the check_with_place oracle."""
    import paddle_tpu as pt
    import paddle_tpu.fluid as fluid
    from paddle_tpu.framework.scope import Scope, scope_guard
    from tests.dist_runner import _data

    xs, ys = _data()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [8])
        y = fluid.layers.data("y", [1])
        h = fluid.layers.fc(x, 16, act="relu")
        pred = fluid.layers.fc(h, 1)
        loss = fluid.layers.reduce_mean(
            fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGDOptimizer(lr).minimize(loss)
    exe = pt.Executor(pt.CPUPlace())
    with scope_guard(Scope()):
        exe.run(startup)
        return [float(exe.run(main, feed={"x": xs, "y": ys},
                              fetch_list=[loss])[0]) for _ in range(steps)]


def test_dygraph_dataparallel_two_processes():
    """2-process dygraph DataParallel: per-step global losses finite,
    equal across ranks (same allreduced grads ⇒ same params), and
    decreasing.  The 6-param model's grads must cross the wire in ONE
    coalesced collective per step (imperative/all_reduce.cc analog), not
    one per parameter."""
    results = _spawn_ranks("dygraph_dp", nproc=2)
    l0, l1 = results[0]["losses"], results[1]["losses"]
    np.testing.assert_allclose(l0, l1, rtol=1e-5, atol=1e-6)
    assert np.isfinite(l0).all()
    assert l0[-1] < l0[0], l0
    for r in results.values():
        assert max(r["collectives_per_step"]) <= 1, r["collectives_per_step"]


def test_fleet_collective_two_processes_matches_local():
    """2-process static fleet-collective DP must track the local
    full-batch run (mean-loss + averaged-grad DP is exactly full-batch
    SGD)."""
    results = _spawn_ranks("fleet_collective", nproc=2)
    l0, l1 = results[0]["losses"], results[1]["losses"]
    np.testing.assert_allclose(l0, l1, rtol=1e-5, atol=1e-6)
    oracle = _single_process_oracle()
    np.testing.assert_allclose(l0, oracle, rtol=1e-4, atol=1e-5)


def test_ps_server_in_separate_process():
    """PS server in its own OS process; trainer process trains against
    it and must match the local oracle exactly (sync PS, 1 trainer)."""
    port = _free_port()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    env["PADDLE_PSERVER_ENDPOINT"] = f"127.0.0.1:{port}"
    env["PADDLE_TRAINERS_NUM"] = "1"
    server = subprocess.Popen(
        [sys.executable, RUNNER, "ps_server"], env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, cwd=HERE)
    try:
        # wait for the listener
        deadline = time.time() + 60
        while time.time() < deadline:
            try:
                s = socket.create_connection(("127.0.0.1", port), timeout=1)
                s.close()
                break
            except OSError:
                time.sleep(0.2)
        else:
            raise TimeoutError("PS server never opened its port")
        trainer = subprocess.run(
            [sys.executable, RUNNER, "ps_trainer"], env=env,
            capture_output=True, text=True, timeout=240, cwd=HERE)
        if trainer.returncode != 0:
            _maybe_skip_gloo(trainer.stderr, "trainer")
        assert trainer.returncode == 0, trainer.stderr[-3000:]
        line = [l for l in trainer.stdout.splitlines()
                if l.startswith("RESULT=")][0]
        losses = json.loads(line[len("RESULT="):])["losses"]

        oracle = _single_process_oracle(seed=13)
        np.testing.assert_allclose(losses, oracle, rtol=1e-4, atol=1e-5)
    finally:
        server.kill()
        server.wait()


@pytest.mark.parametrize("trainer0_late_s", [0.0, 5.0])
def test_ps_two_trainers_sync_parity(trainer0_late_s):
    """The test_dist_base.py:933 check_with_place layout for real: a PS
    server process + TWO trainer processes over localhost, sync mode.
    Each round both trainers pull w_t, compute their half-shard mean
    grads g0/g1, and push; barriers separate rounds, so the trajectory
    is exactly w_{t+1} = w_t - lr*(g0 + g1).  The oracle replicates
    that locally with a two-branch loss (sum of per-half means) and the
    per-trainer loss curves must match.

    The trajectory may not depend on which trainer is up first: with
    trainer 0 started late, trainer 1 reaches its first push before
    trainer 0's initial values are on the server (what a loaded machine
    does to the undelayed case now and then)."""
    port = _free_port()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    env["PADDLE_PSERVER_ENDPOINT"] = f"127.0.0.1:{port}"
    env["PADDLE_TRAINERS_NUM"] = "2"
    server = subprocess.Popen(
        [sys.executable, RUNNER, "ps_server"], env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, cwd=HERE)
    try:
        deadline = time.time() + 60
        while time.time() < deadline:
            try:
                s = socket.create_connection(("127.0.0.1", port), timeout=1)
                s.close()
                break
            except OSError:
                time.sleep(0.2)
        else:
            raise TimeoutError("PS server never opened its port")
        trainers = [None, None]
        for tid in (1, 0):
            if tid == 0:
                time.sleep(trainer0_late_s)
            tenv = dict(env)
            tenv["PADDLE_TRAINER_ID"] = str(tid)
            trainers[tid] = subprocess.Popen(
                [sys.executable, RUNNER, "ps_trainer"], env=tenv,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True, cwd=HERE)
        outs = []
        try:
            for t in trainers:
                out, err = t.communicate(timeout=240)
                if t.returncode != 0:
                    _maybe_skip_gloo(err, "trainer")
                assert t.returncode == 0, err[-3000:]
                line = [l for l in out.splitlines()
                        if l.startswith("RESULT=")][0]
                outs.append(json.loads(line[len("RESULT="):])["losses"])
        finally:
            # a skip/assert on trainer 0 must not leak trainer 1
            for t in trainers:
                if t.poll() is None:
                    t.kill()

        # ---- local oracle: one process computing the same trajectory
        import paddle_tpu as pt
        import paddle_tpu.fluid as fluid
        from paddle_tpu.framework.scope import Scope, scope_guard
        from tests.dist_runner import _data

        xs, ys = _data()
        halves = [(xs[0::2], ys[0::2]), (xs[1::2], ys[1::2])]
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = 13
        with fluid.program_guard(main, startup):
            x0 = fluid.layers.data("x0", [8])
            y0 = fluid.layers.data("y0", [1])
            x1 = fluid.layers.data("x1", [8])
            y1 = fluid.layers.data("y1", [1])

            def branch(xv, yv):
                h = fluid.layers.fc(
                    xv, 16, act="relu",
                    param_attr=fluid.ParamAttr(name="o_fc0.w"),
                    bias_attr=fluid.ParamAttr(name="o_fc0.b"))
                pred = fluid.layers.fc(
                    h, 1, param_attr=fluid.ParamAttr(name="o_fc1.w"),
                    bias_attr=fluid.ParamAttr(name="o_fc1.b"))
                return fluid.layers.reduce_mean(
                    fluid.layers.square_error_cost(pred, yv))

            l0 = branch(x0, y0)
            l1 = branch(x1, y1)
            total = fluid.layers.elementwise_add(l0, l1)
            fluid.optimizer.SGDOptimizer(0.1).minimize(total)
        exe = pt.Executor(pt.CPUPlace())
        with scope_guard(Scope()):
            exe.run(startup)
            oracle0, oracle1 = [], []
            for _ in range(6):
                o = exe.run(main, feed={
                    "x0": halves[0][0], "y0": halves[0][1],
                    "x1": halves[1][0], "y1": halves[1][1]},
                    fetch_list=[l0, l1])
                oracle0.append(float(np.asarray(o[0]).ravel()[0]))
                oracle1.append(float(np.asarray(o[1]).ravel()[0]))
        np.testing.assert_allclose(outs[0], oracle0, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(outs[1], oracle1, rtol=1e-4, atol=1e-5)
    finally:
        server.kill()
        server.wait()
