"""HBM memory observability (r15): the static liveness planner
(framework/memory_plan.py), its runtime reconciliation, the budget
gate, and the OOM flight recorder.

Oracles:
* ZeRO ladder ratios — modeled opt-state (stage >= 1) and parameter
  (stage 3) bytes/dev sit within 2% of full/ndev on BOTH DP paths,
  straight off ``compiled._memory_plan``;
* ResNet-50 probe — modeled framework-resident state agrees with the
  shard-aware live-arrays census within 15% at stage 0 (the acceptance
  reconciliation; the full-mesh run rides ``tools/mem_report.py``);
* donation aliasing — FLAGS_tpu_step_session=0 / donation off charges
  a second copy of every in-place-updated state var;
* ZeRO-3 prefetch windows — the transient full-size bump follows
  ``compiled._prefetch_plan`` exactly;
* FLAGS_hbm_budget_mb — off by default (bit-identical training), warn
  names the peak op + top vars, strict raises;
* OOM flight recorder — an injected RESOURCE_EXHAUSTED dumps plan +
  telemetry + trace debris and re-raises unchanged;
* op-sweep coverage gate — every registered op is classified in the
  planner's byte model (explicit transient entry or audited default).
"""
import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.fluid as fluid
from paddle_tpu.framework import memory_plan as mp
from paddle_tpu.framework.scope import Scope
from paddle_tpu.parallel import mesh as mesh_mod
from paddle_tpu.utils import flags as _flags

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))
from dp_comm_stats import build_mlp_dp_program  # noqa: E402

_MB = float(1 << 20)


@pytest.fixture(autouse=True)
def _fresh_flags_and_mesh():
    saved = dict(_flags._flags)
    mesh_mod.registry().clear()
    yield
    _flags._flags.clear()
    _flags._flags.update(saved)
    mesh_mod.registry().clear()


def _probe(collective=False, optimizer="adam", n_layers=3, width=64):
    from paddle_tpu.framework import unique_name

    unique_name.switch()
    return build_mlp_dp_program(n_layers=n_layers, width=width,
                                optimizer=optimizer, transpile=collective)


def _data(width=64, n=64):
    rng = np.random.RandomState(0)
    xs = rng.randn(n, width).astype(np.float32)
    return xs, (xs[:, :1] * 2 + 1).astype(np.float32)


def _dp_run(main, startup, loss, stage, steps=2, depth=1):
    mesh_mod.registry().clear()
    mesh_mod.init_mesh()
    _flags.set_flags({"dp_sharding": stage, "fuse_grad_size_in_MB": 32.0,
                      "dp_grad_compress": "none", "dp_comm_overlap": 1,
                      "dp_prefetch_depth": depth})
    exe = pt.Executor(pt.CPUPlace())
    scope = Scope()
    exe.run(startup, scope=scope)
    xs, ys = _data()
    compiled = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name)
    losses = []
    for _ in range(steps):
        out = exe.run(compiled, feed={"x": xs, "y": ys},
                      fetch_list=[loss], scope=scope)
        losses.append(float(np.mean(out[0])))
    return compiled, scope, losses


def _class_bytes(plan, cls, key="dev_bytes"):
    return sum(v[key] for v in plan.per_var.values() if v["class"] == cls)


# ==========================================================================
# ZeRO ladder modeled ratios (both DP paths)
# ==========================================================================
@pytest.mark.parametrize("collective", [False, True],
                         ids=["pjit", "shard_map"])
def test_stage_ladder_modeled_ratios(collective):
    """Stage >= 1 opt state and stage-3 params model 1/ndev per device
    within 2% of the full/ndev expectation; stage 0 models full bytes.
    Pure static analysis off compiled._memory_plan — no tolerance games,
    the only slack is non-divisible [1]-shaped vars."""
    main, startup, loss = _probe(collective)
    plans = {}
    for stage in (0, 1, 3):
        compiled, _, _ = _dp_run(main, startup, loss, stage, steps=1)
        plans[stage] = compiled.__dict__["_memory_plan"]
        assert plans[stage] is not None
        assert plans[stage].path == ("shard_map" if collective else "pjit")
        assert plans[stage].stage == stage
    opt_full = _class_bytes(plans[0], "opt_state", "bytes")
    par_full = _class_bytes(plans[0], "param", "bytes")
    assert opt_full > 0 and par_full > 0
    # stage 0: everything full
    assert _class_bytes(plans[0], "opt_state") == opt_full
    assert _class_bytes(plans[0], "param") == par_full
    # stage 1: opt state ~ 1/8, params still full
    got = _class_bytes(plans[1], "opt_state")
    assert abs(got - opt_full / 8) <= 0.02 * (opt_full / 8), (got, opt_full)
    assert _class_bytes(plans[1], "param") == par_full
    # stage 3: params ~ 1/8 too
    got = _class_bytes(plans[3], "param")
    assert abs(got - par_full / 8) <= 0.02 * (par_full / 8), (got, par_full)
    # and the resident total shrinks monotonically down the ladder
    assert plans[1].resident_bytes < plans[0].resident_bytes
    assert plans[3].resident_bytes < plans[1].resident_bytes


def test_stage2_grad_sharding_modeled():
    """ZeRO-2: eligible grads model 1/ndev — throughout on the pjit
    path (GSPMD reduce-scatter at production), from the
    c_fused_reduce_scatter op on the shard_map path (full before it,
    1/ndev after; the transient flat payload is charged at the op)."""
    # pjit
    main, startup, loss = _probe(False)
    compiled, _, _ = _dp_run(main, startup, loss, 2, steps=1)
    plan = compiled.__dict__["_memory_plan"]
    sharded = {n: v for n, v in plan.per_var.items()
               if v["class"] == "grad" and v["sharded"]}
    assert sharded, "no grads modeled as sharded at stage 2 (pjit)"
    for n, v in sharded.items():
        assert v["dev_bytes"] * 8 == v["bytes"], (n, v)
    # shard_map: the rewritten program carries the fused scatter
    main, startup, loss = _probe(True)
    compiled, _, _ = _dp_run(main, startup, loss, 2, steps=1)
    plan = compiled.__dict__["_memory_plan"]
    scatter = [t for t in plan.transients
               if t["type"] == "c_fused_reduce_scatter"]
    assert scatter, "fused reduce-scatter transient missing from plan"
    assert all(t["bytes"] > 0 for t in scatter)
    assert any(v["sharded"] for v in plan.per_var.values()
               if v["class"] == "grad")


# ==========================================================================
# ResNet-50 probe (the acceptance reconciliation)
# ==========================================================================
def test_resnet50_probe_modeled_vs_measured_and_scaling():
    """ResNet-50 probe (CPU proxy, 8-dev mesh model): (a) modeled
    framework-resident state within 15% of the live-arrays measured
    bytes after state lands on device at stage 0; (b) modeled stage-3
    param and stage-1 opt-state bytes within 2% of the ndev-scaled
    expectation on BOTH DP paths.  The state staging runs the startup
    program only (the full fwd+bwd mesh run is tools/mem_report.py
    --probe resnet50 and the slow-marked test below — an XLA compile
    of ResNet-50 does not belong in tier-1)."""
    from paddle_tpu.framework import unique_name
    from paddle_tpu.models.resnet import build_resnet
    from paddle_tpu.utils.memory import live_arrays_bytes

    unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 1
    with fluid.program_guard(main, startup):
        img = fluid.layers.data("img", [3, 32, 32])
        label = fluid.layers.data("label", [1], dtype="int64")
        loss, _, _, _ = build_resnet(img, label, depth=50, class_num=10)
        fluid.optimizer.MomentumOptimizer(0.1, 0.9).minimize(loss)

    # (a) measured: startup stages every param/opt/BN-stat on device;
    # at stage 0 the 8-dev mesh replicates, so the per-device census
    # equals this single-device one (delta: leftover arrays cancel)
    import gc

    gc.collect()
    base = live_arrays_bytes(0)["bytes_in_use"]
    exe = pt.Executor(pt.CPUPlace())
    scope = Scope()
    exe.run(startup, scope=scope)
    measured = live_arrays_bytes(0)["bytes_in_use"] - base
    assert measured > 10 * _MB  # ResNet-50 params alone are ~90 MB

    plan0 = mp.plan_memory(main, feed_names=("img", "label"),
                           fetch_names=(loss.name,), ndev=8, stage=0)
    feed_bytes = _class_bytes(plan0, "feed")
    modeled_state = plan0.resident_bytes - feed_bytes
    agree = abs(modeled_state - measured) / measured
    assert agree <= 0.15, (modeled_state, measured, agree)
    assert plan0.peak_bytes > plan0.resident_bytes  # activations exist

    # (b) ndev-scaling on both paths, static
    from paddle_tpu.transpiler import GradAllReduce

    main_c = fluid.Program.from_desc_dict(main.desc_dict())
    startup_c = fluid.Program.from_desc_dict(startup.desc_dict())
    GradAllReduce().transpile(startup_program=startup_c,
                              main_program=main_c, rank=0,
                              endpoints=["127.0.0.1:6170"], nranks=8)
    for prog in (main, main_c):
        p1 = mp.plan_memory(prog, feed_names=("img", "label"),
                            fetch_names=(loss.name,), ndev=8, stage=1)
        p3 = mp.plan_memory(prog, feed_names=("img", "label"),
                            fetch_names=(loss.name,), ndev=8, stage=3)
        opt_full = _class_bytes(p1, "opt_state", "bytes")
        par_full = _class_bytes(p3, "param", "bytes")
        opt_dev = _class_bytes(p1, "opt_state")
        par_dev = _class_bytes(p3, "param")
        assert abs(opt_dev - opt_full / 8) <= 0.02 * (opt_full / 8), \
            (prog is main_c, opt_dev, opt_full)
        assert abs(par_dev - par_full / 8) <= 0.02 * (par_full / 8), \
            (prog is main_c, par_dev, par_full)


@pytest.mark.slow
def test_resnet50_probe_full_mesh_run():
    """The full-fidelity version: one real DP step of ResNet-50 on the
    8-dev mesh, census taken live (tools/mem_report.py --probe resnet50
    prints the same numbers).  Slow-marked: the XLA compile alone is
    minutes on the CPU proxy."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import mem_report

    row = mem_report.run_config("resnet50", False, 0, 8, 1)
    assert row["modeled_vs_measured_pct"] <= 15.0, row


# ==========================================================================
# donation aliasing
# ==========================================================================
def test_donation_aliasing_models_second_copy():
    """Donation off (FLAGS_tpu_donate_buffers=0 or
    FLAGS_tpu_step_session=0): every in-place-updated state var charges
    a second buffer from its update to the end of the step — the
    timeline tail grows by exactly the updated-state bytes."""
    main, startup, loss = _probe(False)
    fc = ("x", "y")
    on = mp.plan_memory(main, feed_names=fc, fetch_names=(loss.name,),
                        donate=True)
    off = mp.plan_memory(main, feed_names=fc, fetch_names=(loss.name,),
                         donate=False)
    # in-place-updated state: params + opt state (adam writes them all)
    updated = sum(v["dev_bytes"] for n, v in on.per_var.items()
                  if v["resident"] and v["class"] in ("param", "opt_state"))
    assert updated > 0
    assert off.timeline[-1] - on.timeline[-1] == updated
    assert off.peak_bytes >= on.peak_bytes
    # the flag wiring: step session off -> donate modeled off
    _flags.set_flags({"tpu_step_session": 0})
    resolved = mp.plan_memory(main, feed_names=fc,
                              fetch_names=(loss.name,))
    assert resolved.donate is False
    assert resolved.timeline[-1] == off.timeline[-1]


# ==========================================================================
# ZeRO-3 prefetch windows
# ==========================================================================
def test_prefetch_window_bump_matches_plan():
    """The modeled transient full-size bump for a ZeRO-3 parameter
    follows compiled._prefetch_plan exactly: inside [gather_at,
    last_consumer] the full copy is charged, outside only the 1/ndev
    shard."""
    main, startup, loss = _probe(False)
    compiled, _, _ = _dp_run(main, startup, loss, 3, steps=1, depth=2)
    records = compiled.__dict__["_prefetch_plan"]
    assert records, "ZeRO-3 at depth 2 must produce prefetch windows"
    plan = compiled.__dict__["_memory_plan"]
    assert plan.prefetch_windows == len(records)

    # re-plan with the windows stripped: the delta at a window-interior
    # op that consumes no sharded param is exactly the bump of every
    # window covering it
    block = main.global_block()
    exe = pt.Executor(pt.CPUPlace())
    rewritten = exe._apply_ir_passes(main, [loss.name])
    rblock = rewritten.global_block()
    ops = list(rblock.ops)
    base = mp.plan_memory(rewritten, feed_names=("x", "y"),
                          fetch_names=(loss.name,), ndev=8, stage=3,
                          prefetch_records=[])
    with_pf = mp.plan_memory(rewritten, feed_names=("x", "y"),
                             fetch_names=(loss.name,), ndev=8, stage=3,
                             prefetch_records=records)
    sharded = {n for n, v in with_pf.per_var.items()
               if v["class"] == "param" and v["sharded"]}
    assert sharded

    def bump(p):
        b = mp.var_bytes(rblock, p, 64)
        return b - b // 8

    checked = 0
    for rec in records:
        g = int(rec["gather_at"])
        if g >= len(ops):
            continue
        reads = set(ops[g].input_arg_names)
        if reads & sharded:
            continue  # the JIT-gather baseline also bumps here
        expect = sum(bump(r["param"]) for r in records
                     if int(r["gather_at"]) <= g <= int(r["last_consumer"]))
        got = with_pf.timeline[g] - base.timeline[g]
        assert got == expect, (rec, got, expect)
        checked += 1
    assert checked > 0, "no window-interior op without a sharded read"


# ==========================================================================
# budget gate
# ==========================================================================
def _tiny_program(seed=0):
    from paddle_tpu.framework import unique_name

    unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [16])
        y = fluid.layers.data("y", [1])
        h = fluid.layers.fc(x, 32, act="relu")
        pred = fluid.layers.fc(h, 1)
        loss = fluid.layers.reduce_mean(
            fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.AdamOptimizer(0.01).minimize(loss)
    return main, startup, loss


def _train(main, startup, loss, steps=3):
    exe = pt.Executor(pt.CPUPlace())
    scope = Scope()
    exe.run(startup, scope=scope)
    xs, ys = _data(16, 16)
    out = []
    for _ in range(steps):
        v = exe.run(main, feed={"x": xs, "y": ys}, fetch_list=[loss],
                    scope=scope)
        out.append(np.asarray(v[0]).copy())
    return out, exe, scope


def test_budget_off_by_default_and_bit_identical():
    """FLAGS_hbm_budget_mb defaults to 0 (off); training with a
    (satisfied) budget configured is bit-identical to budget-off — the
    planner is pure analysis."""
    assert _flags.flag("hbm_budget_mb") == 0.0
    assert _flags.flag("hbm_budget_strict") is False
    main, startup, loss = _tiny_program()
    base, exe, _ = _train(main, startup, loss)
    plan = list(exe._cache.values())[-1]._memory_plan
    assert plan is not None and plan.peak_bytes > 0
    _flags.set_flags({"hbm_budget_mb": 4096.0})  # generous: no warning
    main2, startup2, loss2 = _tiny_program()
    with warnings.catch_warnings():
        warnings.simplefilter("error", ResourceWarning)
        got, _, _ = _train(main2, startup2, loss2)
    for a, b in zip(base, got):
        np.testing.assert_array_equal(a, b)


def test_budget_warn_names_peak_op_and_top_vars():
    main, startup, loss = _tiny_program(seed=1)
    _flags.set_flags({"hbm_budget_mb": 1e-5})
    with pytest.warns(ResourceWarning) as rec:
        _train(main, startup, loss, steps=1)
    msg = "\n".join(str(w.message) for w in rec)
    assert "modeled HBM peak" in msg
    assert "top live vars" in msg
    assert "fc_0" in msg  # a real top var is named
    assert "op #" in msg


def test_budget_strict_raises():
    main, startup, loss = _tiny_program(seed=2)
    _flags.set_flags({"hbm_budget_mb": 1e-5, "hbm_budget_strict": 1})
    with pytest.raises(mp.MemoryBudgetError) as ei:
        _train(main, startup, loss, steps=1)
    assert "exceeds FLAGS_hbm_budget_mb" in str(ei.value)


# ==========================================================================
# OOM flight recorder
# ==========================================================================
def test_oom_debris_dump(tmp_path):
    """An injected RESOURCE_EXHAUSTED on the step path dumps plan +
    telemetry + error debris into FLAGS_oom_debris_dir and re-raises
    the original exception unchanged."""
    main, startup, loss = _tiny_program(seed=3)
    base, exe, scope = _train(main, startup, loss, steps=1)
    compiled = list(exe._cache.values())[-1]
    assert compiled._memory_plan is not None

    def boom(*a, **k):
        raise RuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
            "123456 bytes.")

    compiled.fn = boom
    compiled.session = None
    _flags.set_flags({"oom_debris_dir": str(tmp_path / "debris")})
    xs, ys = _data(16, 16)
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        exe.run(main, feed={"x": xs, "y": ys}, fetch_list=[loss],
                scope=scope)
    dirs = sorted((tmp_path / "debris").iterdir())
    assert len(dirs) == 1
    files = {p.name for p in dirs[0].iterdir()}
    assert {"error.txt", "plan.json", "telemetry.json"} <= files
    plan = json.loads((dirs[0] / "plan.json").read_text())
    assert plan["peak_bytes"] > 0 and "timeline_bytes" in plan
    assert "RESOURCE_EXHAUSTED" in (dirs[0] / "error.txt").read_text()


def test_non_oom_errors_leave_no_debris(tmp_path):
    main, startup, loss = _tiny_program(seed=4)
    _, exe, scope = _train(main, startup, loss, steps=1)
    compiled = list(exe._cache.values())[-1]

    def boom(*a, **k):
        raise ValueError("some unrelated failure")

    compiled.fn = boom
    compiled.session = None
    _flags.set_flags({"oom_debris_dir": str(tmp_path / "debris")})
    xs, ys = _data(16, 16)
    with pytest.raises(ValueError):
        exe.run(main, feed={"x": xs, "y": ys}, fetch_list=[loss],
                scope=scope)
    assert not (tmp_path / "debris").exists()


def test_oom_debris_disabled_by_default():
    assert _flags.flag("oom_debris_dir") == ""
    err = RuntimeError("RESOURCE_EXHAUSTED: oom")
    assert mp.is_resource_exhausted(err)
    assert mp.record_oom_debris("unit", err) is None


# ==========================================================================
# transient byte model + coverage gate
# ==========================================================================
def test_memory_audit_covers_registry():
    """The op-sweep-style coverage gate: every registered op has an
    explicit transient-bytes entry or sits on the audited default list
    — a new op cannot ride a silent default (the r14 _EPILOGUE_TRAFFIC
    lesson).  Structural suspects must be explicit."""
    from paddle_tpu.ops.registry import OPS

    unclassified = sorted(t for t in OPS
                          if mp.memory_audit(t) == "unclassified")
    assert not unclassified, (
        f"{len(unclassified)} registered op(s) missing from the memory "
        f"planner's byte model — add a TRANSIENT_BYTES entry or audit "
        f"them onto AUDITED_DEFAULT: {unclassified}")
    for suspect in ("c_fused_allreduce", "c_fused_reduce_scatter",
                    "c_allgather", "while", "paged_attention",
                    "coalesce_tensor"):
        assert mp.memory_audit(suspect) == "explicit", suspect
    # higher-order grads derive coverage from their forward op (the
    # generic vjp replays its lowering)...
    assert mp.memory_audit("tanh_grad_grad") == "default"
    # ...and runtime-registered custom ops are the author's contract
    from paddle_tpu.utils.custom_op import CUSTOM_REGISTERED

    CUSTOM_REGISTERED.add("___probe_custom")
    try:
        assert mp.memory_audit("___probe_custom") == "custom"
        assert mp.memory_audit("___probe_custom_grad") == "custom"
    finally:
        CUSTOM_REGISTERED.discard("___probe_custom")
    assert mp.memory_audit("___definitely_unknown") == "unclassified"


def test_fused_bucket_transient_bytes():
    """A c_fused_allreduce bucket charges 2x its flat payload at the
    collective op (concat in + reduced out)."""
    main, startup, loss = _probe(True)
    _flags.set_flags({"fuse_grad_size_in_MB": 32.0, "dp_comm_overlap": 1,
                      "dp_sharding": 0})
    exe = pt.Executor(pt.CPUPlace())
    rewritten = exe._apply_ir_passes(main, [loss.name])
    rblock = rewritten.global_block()
    fused = [op for op in rblock.ops if op.type == "c_fused_allreduce"]
    assert fused, "fuse pass produced no bucket"
    plan = mp.plan_memory(rewritten, feed_names=("x", "y"),
                          fetch_names=(loss.name,), ndev=8, stage=0)
    recorded = {t["type"]: t for t in plan.transients}
    assert "c_fused_allreduce" in recorded
    op = fused[0]
    payload = sum(mp.var_bytes(rblock, n, 64)
                  for n in op.inputs["X"])
    idx = list(rblock.ops).index(op)
    t = [t for t in plan.transients if t["op_index"] == idx][0]
    assert t["bytes"] == 2 * payload


def test_while_subblock_charged_once():
    """A while loop's body contributes its OWN peak as a transient at
    the loop op (carries reuse buffers under the scan lowering) — not
    a per-iteration accumulation."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        i = fluid.layers.fill_constant([1], "float32", 0.0)
        acc = fluid.layers.fill_constant([256], "float32", 0.0)
        ten = fluid.layers.fill_constant([1], "float32", 10.0)

        def cond_fn(i, acc):
            return fluid.layers.less_than(i, ten)

        def body_fn(i, acc):
            return [i + 1.0, acc + 1.0]

        i_out, acc_out = fluid.layers.while_loop(cond_fn, body_fn,
                                                 [i, acc])
    plan = mp.plan_memory(main, fetch_names=(acc_out.name,))
    wt = [t for t in plan.transients
          if t["type"] in ("while", "while_loop")]
    assert wt, "while op missing a sub-block transient"
    # body peak is bounded: a handful of [256]/[1] temporaries, never
    # 10 iterations' worth
    assert 0 < wt[0]["bytes"] <= 16 * 256 * 4


def test_kv_pool_is_fixed_resident_block(tiny_engine=None):
    """The serving decode program's K/V pools model as a fixed
    kv_pool-class resident block equal to the engine's
    kv_pool_resident_bytes."""
    from paddle_tpu.inference.gpt2_decoder import init_decoder_weights
    from paddle_tpu.inference.serving import DecoderConfig, _EngineCore

    cfg = DecoderConfig(vocab_size=32, hidden=16, num_heads=2,
                        num_layers=2, max_seq_len=32)
    core = _EngineCore(cfg, init_decoder_weights(cfg), num_pages=16,
                       page_size=4)
    plan = mp.plan_memory(core.decode_prog,
                          feed_names=core.decode_feeds,
                          fetch_names=core.decode_fetch,
                          scope=core.scope)
    assert plan.resident_by_class["kv_pool"] == \
        core.kv_pool_resident_bytes()
    ms = core.memory_stats()
    assert ms["kv_pool_resident_bytes"] == core.kv_pool_resident_bytes()
    assert ms["weight_bytes"] > 0


# ==========================================================================
# runtime reconciliation
# ==========================================================================
def test_modeled_vs_live_arrays_small_probe():
    """Inline reconciliation: after 2 DP steps at stage 0, the modeled
    framework-resident state (minus feeds, which die with the step)
    agrees with the shard-aware live-arrays census within 15%."""
    import gc

    from paddle_tpu.utils.memory import live_arrays_bytes

    main, startup, loss = _probe(False)
    gc.collect()
    # delta census: earlier tests' leftover arrays cancel out
    base = live_arrays_bytes(0)["bytes_in_use"]
    compiled, scope, _ = _dp_run(main, startup, loss, 0, steps=2)
    gc.collect()
    measured = live_arrays_bytes(0)["bytes_in_use"] - base
    plan = compiled.__dict__["_memory_plan"]
    modeled = plan.resident_bytes - _class_bytes(plan, "feed")
    assert abs(modeled - measured) / max(measured, 1) <= 0.15, \
        (modeled, measured)


def test_shard_aware_census_counts_shards_not_globals():
    """The census charges a P('dp')-sharded array 1/ndev per device and
    a replicated one in full — the fix that lets measured bytes agree
    with the ZeRO model."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.utils.memory import live_arrays_bytes

    mesh_mod.registry().clear()
    mesh = mesh_mod.init_mesh()
    base = live_arrays_bytes(0)["bytes_in_use"]
    arr = np.zeros((64, 1024), np.float32)  # 256 KB
    sharded = jax.device_put(arr, NamedSharding(mesh, P("dp")))
    repl = jax.device_put(arr, NamedSharding(mesh, P()))
    after = live_arrays_bytes(0)["bytes_in_use"]
    got = after - base
    expect = arr.nbytes // 8 + arr.nbytes
    assert got == expect, (got, expect)
    del sharded, repl


def test_peak_tracker_and_gauge():
    from paddle_tpu.utils import telemetry
    from paddle_tpu.utils.memory import PeakTracker

    telemetry.registry().reset()
    t = PeakTracker(0)
    p1 = t.sample()
    assert p1 >= 0 and t.samples == 1
    d = t.as_dict()
    assert d["source"] in ("pjrt", "live_arrays")
    snap = telemetry.snapshot()
    if p1 > 0:
        assert snap["hbm_measured_peak_bytes"]["series"][0]["value"] == p1


# ==========================================================================
# trace lane + tool smokes
# ==========================================================================
def test_trace_memory_counters_and_report(tmp_path):
    """Compiling under a live profiler emits the modeled live-bytes
    timeline as "C" events on the memory lane; trace_report summarizes
    peak and (with a budget) time-over-80%."""
    from paddle_tpu import profiler
    from trace_report import load_trace, report

    _flags.set_flags({"hbm_budget_mb": 1.0})
    main, startup, loss = _tiny_program(seed=5)
    path = str(tmp_path / "t.json")
    profiler.enable_profiler("All")
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            _train(main, startup, loss, steps=1)
    finally:
        profiler.disable_profiler(profile_path=path, print_summary=False)
    rep = report(load_trace(path))
    assert "memory" in rep["lanes"], rep["lanes"].keys()
    ctr = rep["lanes"]["memory"]["counters"]["hbm_modeled_live_bytes"]
    assert ctr["samples"] > 0 and ctr["peak"] > 0
    assert ctr["budget"] == 1.0 * _MB
    assert ctr["time_over_80pct_budget_ms"] is not None


def test_progcheck_mem_budget_exit(tmp_path):
    from progcheck import main as pc_main

    main, startup, loss = _tiny_program(seed=6)
    p = tmp_path / "prog.json"
    p.write_bytes(main.serialize_to_string())
    assert pc_main([str(p), "--mem", "--feed", "x,y", "--quiet"]) == 0
    assert pc_main([str(p), "--mem", "--feed", "x,y", "--quiet",
                    "--budget-mb", "1e-5"]) == 1


def test_progcheck_mem_tp_division(tmp_path, capsys):
    """--mem --tp N --tp-rules: rule-matched vars are charged 1/tp per
    device in the planner rows (the serving-decoder modeling knob), and
    the engage-only ``tp`` field marks the row."""
    from progcheck import main as pc_main

    main, startup, loss = _tiny_program(seed=8)
    p = tmp_path / "prog.json"
    p.write_bytes(main.serialize_to_string())

    def mem_row(extra):
        assert pc_main([str(p), "--mem", "--feed", "x,y", "--quiet",
                        "--json"] + extra) == 0
        out = json.loads(capsys.readouterr().out)
        return out["memory"][0]

    base = mem_row([])
    # the rule covers every fc param (weights AND biases), so the param
    # class halves exactly; opt-state moments don't match and hold
    tp = mem_row(["--tp", "2", "--tp-rules", r"fc_\d+\.(w|b)_0"])
    assert base["resident_by_class"]["param"] > 0
    assert tp["resident_by_class"]["param"] * 2 == \
        base["resident_by_class"]["param"]
    assert tp["resident_by_class"]["opt_state"] == \
        base["resident_by_class"]["opt_state"]
    assert tp["tp"] == 2 and "tp" not in base


def test_mem_report_quick_subprocess():
    """tools/mem_report.py --quick: the bounded tier-1 reconciliation
    smoke — MLP probe, stages {0,3} x both DP paths, hard 15%/2%
    assertions, one stable MEM= line."""
    bound = int(os.environ.get("PD_MEM_REPORT_TIMEOUT", 480))
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "mem_report.py"),
         "--quick", "--json"],
        cwd=ROOT, capture_output=True, text=True, timeout=bound,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("MEM=")][-1]
    rep = json.loads(line[len("MEM="):])
    assert rep["ok"] is True
    assert rep["quick"] is True
    rows = rep["rows"]
    assert {(r_["path"], r_["stage"]) for r_ in rows} == {
        ("pjit", 0), ("pjit", 3), ("shard_map", 0), ("shard_map", 3)}
    for r_ in rows:
        if r_["stage"] == 0:
            assert r_["modeled_vs_measured_pct"] <= 15.0
        if r_["stage"] >= 3:
            assert r_["scaling"]["param"]["err_pct"] <= 2.0
            assert r_["scaling"]["opt_state"]["err_pct"] <= 2.0
    # r24: the serving TP reconciliation rows — per-device modeled
    # (plan_memory tp/tp_rules) == engine census for kv_pool AND the
    # decoder weights, and pages scale exactly tp x, every KV dtype
    tp_sec = rep["serving_kv"]["tensor_parallel"]
    assert tp_sec["available"] is True and tp_sec["all_reconciled"] is True
    assert {r_["dtype"] for r_ in tp_sec["rows"]} == {
        "float32", "bfloat16", "int8"}
    for r_ in tp_sec["rows"]:
        assert r_["modeled_eq_census"] is True
        assert r_["pages_scale_x"] == float(tp_sec["tp"])


def test_executor_plan_attached_and_gauged():
    from paddle_tpu.utils import telemetry

    telemetry.registry().reset()
    main, startup, loss = _tiny_program(seed=7)
    _, exe, scope = _train(main, startup, loss, steps=1)
    plan = list(exe._cache.values())[-1]._memory_plan
    assert plan is not None
    assert plan.peak_op_index < plan.n_ops
    assert plan.timeline[plan.peak_op_index] == plan.peak_bytes
    snap = telemetry.snapshot()
    series = snap["hbm_modeled_peak_bytes"]["series"]
    by_where = {s["labels"]["where"]: s["value"] for s in series}
    assert by_where.get("executor_compile") == plan.peak_bytes
