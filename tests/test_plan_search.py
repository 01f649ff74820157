"""Cost-model-driven auto-parallel plan search (FLAGS_dp_plan=auto,
parallel/plan_search.py) — the r16 tentpole's search half.

Oracles:
* the searched plan's modeled step time is <= EVERY hand-flag
  configuration in the sweep (stage x bucket x prefetch), on the 8-dev
  virtual mesh, for the bench MLP probe AND a conv (ResNet-shaped)
  probe, on BOTH DP paths — by construction (one pricing function) and
  checked explicitly here;
* training under FLAGS_dp_plan=auto is BIT-identical to setting the
  chosen plan's flags by hand (both paths);
* memory-infeasible candidates are rejected by plan_memory() BEFORE any
  compile under a tight FLAGS_hbm_budget_mb (the report says so, the
  chosen plan fits, strict mode raises with no compile);
* FLAGS_dp_plan unset runs the flag-driven path: no search, no _plan;
* the DP compile cache keys on the RESOLVED plan tuple: a calibration
  change re-searches instead of serving a stale compile;
* the per-param prefetch autotune is a verifier-checked IR pass whose
  windows satisfy the r10 check_prefetch_plan rule;
* tools/progcheck.py --plan lints a saved program's plan in a bounded
  subprocess (JSON mode, non-zero exit when nothing fits the budget).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.fluid as fluid
from paddle_tpu.framework.scope import Scope
from paddle_tpu.parallel import mesh as mesh_mod
from paddle_tpu.parallel import plan_search as ps
from paddle_tpu.utils import flags as _flags

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))
from dp_comm_stats import build_mlp_dp_program  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    saved = dict(_flags._flags)
    mesh_mod.registry().clear()
    ps.clear_search_cache()
    yield
    _flags._flags.clear()
    _flags._flags.update(saved)
    mesh_mod.registry().clear()
    ps.clear_search_cache()


def _mlp(collective, optimizer="adam", layers=3, width=16):
    from paddle_tpu.framework import unique_name

    unique_name.switch()
    return build_mlp_dp_program(n_layers=layers, width=width,
                                optimizer=optimizer, transpile=collective)


def _conv_probe(collective):
    """The ResNet-shaped probe: conv -> bn -> relu -> pool -> fc with
    adam — conv/bn state plus matmul tails, small enough for tier-1."""
    from paddle_tpu.framework import unique_name
    from paddle_tpu.transpiler import GradAllReduce

    unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 3
    with fluid.program_guard(main, startup):
        img = fluid.layers.data("img", [3, 8, 8])
        y = fluid.layers.data("y", [1])
        h = fluid.layers.conv2d(img, 8, 3, padding=1, act=None)
        h = fluid.layers.batch_norm(h, act="relu")
        h = fluid.layers.pool2d(h, 2, "max", 2)
        h = fluid.layers.fc(h, 16, act="relu")
        pred = fluid.layers.fc(h, 1)
        loss = fluid.layers.reduce_mean(
            fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.AdamOptimizer(0.01).minimize(loss)
    if collective:
        GradAllReduce().transpile(startup_program=startup,
                                  main_program=main, rank=0,
                                  endpoints=["127.0.0.1:6170"], nranks=8)
    return main, startup, loss


def _hand_sweep(use_shard_map):
    """The hand-flag configurations the acceptance criterion names:
    the stage x bucket x prefetch grid."""
    sweep = []
    buckets = ("0", "4.0", "32.0", "auto") if use_shard_map else ("32.0",)
    for stage in (0, 1, 2, 3):
        for mb in buckets:
            for depth in ((0, 1, 2, 4) if stage == 3 else (1,)):
                sweep.append(ps.ParallelPlan(stage=stage, bucket_mb=mb,
                                             prefetch_depth=depth,
                                             overlap=True))
    return sweep


# --------------------------------------------------------------------------
# argmin vs the hand-flag sweep
# --------------------------------------------------------------------------
@pytest.mark.parametrize("collective", [False, True],
                         ids=["pjit", "shard_map"])
@pytest.mark.parametrize("probe", ["mlp", "conv"])
def test_auto_plan_beats_every_hand_config(collective, probe):
    main, _, loss = (_mlp(collective) if probe == "mlp"
                     else _conv_probe(collective))
    feeds = ("x", "y") if probe == "mlp" else ("img", "y")
    plan, report = ps.search_plan(main, feeds, (loss.name,), ndev=8,
                                  use_shard_map=collective)
    chosen_s = report["chosen"]["modeled_step_s"]
    assert report["chosen"]["feasible"]
    for hand in _hand_sweep(collective):
        hand_s = ps.modeled_step_time(main, 8, hand, collective)
        assert chosen_s <= hand_s["modeled_step_s"] + 1e-15, (
            plan.as_dict(), hand.as_dict(), chosen_s,
            hand_s["modeled_step_s"])


def test_candidate_table_is_explainable():
    main, _, loss = _mlp(True)
    _, report = ps.search_plan(main, ("x", "y"), (loss.name,), ndev=8,
                               use_shard_map=True)
    assert report["n_candidates"] == len(report["candidates"]) > 10
    assert sum(r["chosen"] for r in report["candidates"]) == 1
    for r in report["candidates"]:
        assert r["modeled_step_s"] > 0
        assert r["modeled_peak_bytes"] > 0
        assert r["feasible"] and r["rejected"] is None
    # the per-param autotune candidate is in the space
    assert any(r["prefetch_auto"] for r in report["candidates"])


# --------------------------------------------------------------------------
# bit-identity: auto == the chosen plan's flags set by hand
# --------------------------------------------------------------------------
def _run_mode(main, startup, loss, init, flags_dict, steps=5, width=16):
    _flags.set_flags(flags_dict)
    mesh_mod.registry().clear()
    mesh_mod.init_mesh()
    exe = pt.Executor(pt.CPUPlace())
    sc = Scope()
    for k, v in init.items():
        sc.set(k, v.copy())
    xs = np.random.RandomState(0).randn(16, width).astype(np.float32)
    ys = (xs[:, :1] * 2 + 1).astype(np.float32)
    compiled = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name)
    out = [np.asarray(exe.run(compiled, feed={"x": xs, "y": ys},
                              fetch_list=[loss], scope=sc)[0])
           for _ in range(steps)]
    return np.asarray(out), compiled


@pytest.mark.parametrize("collective", [False, True],
                         ids=["pjit", "shard_map"])
def test_auto_plan_loss_bit_identical_to_hand_flags(collective):
    main, startup, loss = _mlp(collective)
    exe = pt.Executor(pt.CPUPlace())
    sa = Scope()
    exe.run(startup, scope=sa)
    init = {k: np.asarray(v) for k, v in sa.items()
            if not k.startswith("@")}

    defaults = {"dp_sharding": 0, "fuse_grad_size_in_MB": 32.0,
                "dp_prefetch_depth": 1, "dp_comm_overlap": 1}
    auto_l, compiled = _run_mode(main, startup, loss, init,
                                 {**defaults, "dp_plan": "auto"})
    chosen = compiled.__dict__.get("_plan")
    assert chosen is not None and chosen["chosen"]
    hand_flags = {**defaults, "dp_plan": "",
                  "dp_sharding": chosen["stage"],
                  "fuse_grad_size_in_MB": chosen["bucket_mb"],
                  "dp_prefetch_depth": chosen["prefetch_depth"],
                  "dp_comm_overlap": int(chosen["overlap"])}
    hand_l, hand_c = _run_mode(main, startup, loss, init, hand_flags)
    np.testing.assert_array_equal(auto_l, hand_l)  # BIT identical
    assert hand_c.__dict__.get("_plan") is None    # no search ran


def test_dp_plan_unset_is_flag_driven():
    """FLAGS_dp_plan="" (default): no search runs, no plan attaches,
    the compile is keyed and driven purely by the hand flags."""
    main, startup, loss = _mlp(False)
    exe = pt.Executor(pt.CPUPlace())
    sa = Scope()
    exe.run(startup, scope=sa)
    init = {k: np.asarray(v) for k, v in sa.items()
            if not k.startswith("@")}
    _, compiled = _run_mode(main, startup, loss, init,
                            {"dp_plan": "", "dp_sharding": 2})
    assert compiled.__dict__.get("_plan") is None
    assert compiled.__dict__.get("_plan_report") is None
    key = next(iter(compiled.__dict__["_dp_cache"]))
    assert key[-1] is None  # no resolved-plan tuple in the key


# --------------------------------------------------------------------------
# budget gating
# --------------------------------------------------------------------------
def test_infeasible_candidates_rejected_before_compile():
    """With a budget between the stage-0 and stage-3 peaks, the
    searcher rejects the fat plans via plan_memory() (the report names
    the rejection) and compiles a feasible one — and training still
    runs."""
    main, startup, loss = _mlp(True, layers=4, width=64)
    # find a budget that splits the ladder
    _, probe = ps.search_plan(main, ("x", "y"), (loss.name,), ndev=8,
                              use_shard_map=True)
    peaks = {r["stage"]: r["modeled_peak_mb"]
             for r in probe["candidates"]}
    budget_mb = (max(peaks.values()) + min(peaks.values())) / 2.0
    assert min(peaks.values()) < budget_mb < max(peaks.values())

    exe = pt.Executor(pt.CPUPlace())
    sa = Scope()
    exe.run(startup, scope=sa)
    init = {k: np.asarray(v) for k, v in sa.items()
            if not k.startswith("@")}
    _flags.set_flags({"hbm_budget_mb": budget_mb})
    xs = np.random.RandomState(0).randn(16, 64).astype(np.float32)
    ys = (xs[:, :1] * 2 + 1).astype(np.float32)
    _flags.set_flags({"dp_plan": "auto"})
    mesh_mod.init_mesh()
    sc = Scope()
    for k, v in init.items():
        sc.set(k, v.copy())
    compiled = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name)
    out = exe.run(compiled, feed={"x": xs, "y": ys}, fetch_list=[loss],
                  scope=sc)[0]
    assert np.isfinite(np.asarray(out)).all()
    chosen = compiled.__dict__["_plan"]
    report = compiled.__dict__["_plan_report"]
    assert report["n_rejected"] > 0
    assert not report["infeasible"]
    assert chosen["feasible"]
    assert chosen["modeled_peak_mb"] <= budget_mb
    rejected = [r for r in report["candidates"] if r["rejected"]]
    assert rejected and all("rejected before compile" in r["rejected"]
                            for r in rejected)


def test_impossible_budget_strict_raises_without_compile():
    from paddle_tpu.framework.memory_plan import MemoryBudgetError

    main, _, loss = _mlp(True)
    _flags.set_flags({"hbm_budget_strict": True})
    with pytest.raises(MemoryBudgetError, match="no candidate fits"):
        ps.search_plan(main, ("x", "y"), (loss.name,), ndev=8,
                       use_shard_map=True, budget_bytes=1024)
    # non-strict: warns and hands back the minimum-peak plan
    _flags.set_flags({"hbm_budget_strict": False})
    with pytest.warns(ResourceWarning, match="no candidate fits"):
        plan, report = ps.search_plan(main, ("x", "y"), (loss.name,),
                                      ndev=8, use_shard_map=True,
                                      budget_bytes=1024)
    assert report["infeasible"]
    min_peak = min(r["modeled_peak_bytes"] for r in report["candidates"])
    assert report["chosen"]["modeled_peak_bytes"] == min_peak


# --------------------------------------------------------------------------
# cache keys on the resolved plan
# --------------------------------------------------------------------------
def test_calibration_change_rekeys_auto_compile():
    """A new measured profile may move the argmin: the DP cache must
    grow a NEW entry keyed on the re-resolved plan instead of serving
    the stale one (the satellite fix)."""
    from paddle_tpu.utils import cost_model

    main, startup, loss = _mlp(True)
    exe = pt.Executor(pt.CPUPlace())
    sa = Scope()
    exe.run(startup, scope=sa)
    init = {k: np.asarray(v) for k, v in sa.items()
            if not k.startswith("@")}
    _, compiled = _run_mode(main, startup, loss, init,
                            {"dp_plan": "auto"})
    n0 = len(compiled.__dict__["_dp_cache"])
    assert n0 == 1
    # same config again: served from cache, no second entry
    _flags.set_flags({"dp_plan": "auto"})
    exe2 = pt.Executor(pt.CPUPlace())
    sc = Scope()
    for k, v in init.items():
        sc.set(k, v.copy())
    xs = np.random.RandomState(0).randn(16, 16).astype(np.float32)
    ys = (xs[:, :1] * 2 + 1).astype(np.float32)
    exe2.run(compiled, feed={"x": xs, "y": ys}, fetch_list=[loss],
             scope=sc)
    assert len(compiled.__dict__["_dp_cache"]) == 1
    # calibration changes -> re-search -> new key (never a stale serve)
    cost_model.set_measured_profile(0.0123, source="test")
    try:
        exe2.run(compiled, feed={"x": xs, "y": ys}, fetch_list=[loss],
                 scope=sc)
        assert len(compiled.__dict__["_dp_cache"]) == 2
        keys = list(compiled.__dict__["_dp_cache"])
        assert keys[0] != keys[1]
        assert keys[0][-1] is not None and keys[1][-1] is not None
    finally:
        cost_model.clear_measured_profile()


# --------------------------------------------------------------------------
# per-param prefetch autotune (verifier-checked IR pass)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("collective", [False, True],
                         ids=["pjit", "shard_map"])
def test_prefetch_autotune_pass_windows_are_verified(collective):
    from paddle_tpu.framework import verifier
    from paddle_tpu.framework.ir import get_pass

    main, _, loss = _mlp(collective, layers=4, width=64)
    p = get_pass("prefetch_autotune_pass", ndev=8,
                 use_shard_map=collective)
    # Pass.apply: verifier-bracketed like every IR pass (tier-1 arms it)
    assert verifier.enabled()
    p.apply(main)
    depths = p.report["depths"]
    records = p.report["records"]
    assert depths and records
    assert all(d >= 1 for d in depths.values())
    assert len(set(depths.values())) > 1, depths  # genuinely per-param
    blk = main.global_block()
    diags = verifier.check_prefetch_plan(list(blk.ops), blk, records)
    assert diags == []


@pytest.mark.parametrize("collective", [False, True],
                         ids=["pjit", "shard_map"])
def test_per_param_depth_plan_trains_bit_identically(collective,
                                                     monkeypatch):
    """A searched plan carrying PER-PARAM depths (prefetch_auto)
    compiles through the normal path — windows verified, params still
    1/ndev resident — and trains bit-identically to the uniform-depth
    stage-3 run: prefetch only moves gathers, never values."""
    main, startup, loss = _mlp(collective, layers=3, width=64)
    exe = pt.Executor(pt.CPUPlace())
    sa = Scope()
    exe.run(startup, scope=sa)
    init = {k: np.asarray(v) for k, v in sa.items()
            if not k.startswith("@")}
    base = {"dp_sharding": 3, "fuse_grad_size_in_MB": 32.0,
            "dp_comm_overlap": 1, "dp_plan": ""}
    uni_l, _ = _run_mode(main, startup, loss, init,
                         {**base, "dp_prefetch_depth": 1}, width=64)

    from paddle_tpu.framework.ir import get_pass

    p = get_pass("prefetch_autotune_pass", ndev=8,
                 use_shard_map=collective)
    p.apply(main)
    assert p.report["depths"]
    forced = ps.ParallelPlan(
        stage=3, bucket_mb="32.0", prefetch_depth=1, overlap=True,
        prefetch_auto=True,
        per_param_depths=tuple(sorted(
            (k, int(v)) for k, v in p.report["depths"].items())))
    monkeypatch.setattr(ps, "resolve_plan",
                        lambda *a, **k: (forced, {"chosen": dict(
                            forced.as_dict(), modeled_step_s=0.0,
                            modeled_peak_mb=0.0, feasible=True,
                            chosen=True)}))
    auto_l, compiled = _run_mode(main, startup, loss, init,
                                 {**base, "dp_plan": "auto",
                                  "dp_sharding": 0}, width=64)
    np.testing.assert_array_equal(uni_l, auto_l)  # BIT identical
    # the per-param windows really drove the compile
    assert compiled.__dict__["_prefetch_plan"]
    assert compiled.__dict__["_dp_cache"]
    key = next(iter(compiled.__dict__["_dp_cache"]))
    assert key[-1] == forced.as_tuple()


# --------------------------------------------------------------------------
# tools: progcheck --plan subprocess smoke (bounded)
# --------------------------------------------------------------------------
def test_progcheck_plan_subprocess_smoke(tmp_path):
    main, _, loss = _mlp(True)
    prog = tmp_path / "prog.json"
    prog.write_bytes(main.serialize_to_string())
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)

    def run(*extra):
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "progcheck.py"),
             str(prog), "--plan", "--ndev", "8", "--feed", "x,y",
             "--json", *extra],
            capture_output=True, text=True, timeout=240, env=env,
            cwd=REPO)

    ok = run()
    assert ok.returncode == 0, ok.stderr[-2000:]
    out = json.loads(ok.stdout)
    row = out["plan"][0]
    assert row["n_candidates"] > 10
    assert row["chosen"]["feasible"]
    assert out["plan_infeasible"] == []

    bad = run("--budget-mb", "0.0001")
    assert bad.returncode == 1, bad.stderr[-2000:]
    out2 = json.loads(bad.stdout)
    assert out2["plan"][0]["infeasible"]
    assert out2["plan_infeasible"]


# --------------------------------------------------------------------------
# fleet plumbing + telemetry
# --------------------------------------------------------------------------
def test_fleet_strategy_dp_plan_knob():
    from paddle_tpu.framework import unique_name
    from paddle_tpu.incubate.fleet.collective import (
        CollectiveOptimizer, DistributedStrategy)

    mesh_mod.init_mesh()
    unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [8])
        y = fluid.layers.data("y", [1])
        pred = fluid.layers.fc(x, 1)
        loss = fluid.layers.reduce_mean(
            fluid.layers.square_error_cost(pred, y))
        strategy = DistributedStrategy()
        strategy.dp_plan = "auto"
        CollectiveOptimizer(fluid.optimizer.SGDOptimizer(0.1),
                            strategy).minimize(loss)
    assert _flags.dp_plan_auto()
    unique_name.switch()
    main2, startup2 = fluid.Program(), fluid.Program()
    with fluid.program_guard(main2, startup2):
        x = fluid.layers.data("x", [8])
        y = fluid.layers.data("y", [1])
        pred = fluid.layers.fc(x, 1)
        loss2 = fluid.layers.reduce_mean(
            fluid.layers.square_error_cost(pred, y))
        CollectiveOptimizer(fluid.optimizer.SGDOptimizer(0.1),
                            DistributedStrategy()).minimize(loss2)
    assert _flags.flag("dp_plan") == _flags._INITIAL["FLAGS_dp_plan"]


def test_plan_gauges_published():
    from paddle_tpu.utils import telemetry as tm

    main, _, loss = _mlp(True)
    ps.resolve_plan(main, {"x", "y"}, [loss.name], ("m",), 8, True)
    snap = tm.snapshot()
    assert "dp_plan_stage" in snap
    assert "dp_plan_modeled_step_s" in snap
    assert "dp_plan_searches_total" in snap
    stage_rows = snap["dp_plan_stage"]["series"]
    assert any(r["labels"].get("path") == "shard_map"
               for r in stage_rows)
