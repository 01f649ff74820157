"""Sharded data parallelism: ZeRO-1/2/3 + coalesced, overlap-scheduled
gradient comms (r7 + r8).

Oracles:
* fuse_all_reduce_pass bucket counts on a >=20-grad-tensor program and
  bit-identity of the fused path with compression off (reference:
  fuse_all_reduce_op_pass.cc semantics);
* bucket-boundary behavior: empty / one-tensor / mixed-dtype groups
  refuse to merge;
* bf16 wire compression stays inside its quantization error bound;
* FLAGS_dp_sharding stages: stage 1 shards optimizer state 1/ndev per
  device on BOTH the pjit and the shard_map/fleet-collective path,
  stage 2 reduce-scatters fused grad buckets straight into the shard
  update (c_fused_reduce_scatter), stage 3 shards the parameters with
  just-in-time gather — all at loss parity with stage 0 and with
  single-device execution, including mid-run stage flips carrying
  state;
* overlap scheduling: each fused bucket's collective is issued at its
  last-gradient-ready position, before the last backward op of any
  later bucket (FLAGS_dp_comm_overlap=0 restores the append schedule);
* every mode rolls back to today's behavior via its flag.
"""
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.fluid as fluid
from paddle_tpu.framework.scope import Scope
from paddle_tpu.parallel import mesh as mesh_mod
from paddle_tpu.utils import flags as _flags

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))
from dp_comm_stats import (  # noqa: E402
    build_mlp_dp_program, collect_comm_stats)


@pytest.fixture(autouse=True)
def _fresh_flags_and_mesh():
    saved = dict(_flags._flags)
    mesh_mod.registry().clear()
    yield
    _flags._flags.clear()
    _flags._flags.update(saved)
    mesh_mod.registry().clear()


def _init_scope(startup, scope):
    exe = pt.Executor(pt.CPUPlace())
    exe.run(startup, scope=scope)
    return {k: np.asarray(v) for k, v in scope.items()
            if not k.startswith("@")}


def _data(width=64, n=64, seed=0):
    rng = np.random.RandomState(seed)
    xs = rng.randn(n, width).astype(np.float32)
    ys = (xs[:, :1] * 2 + 1).astype(np.float32)
    return xs, ys


# --------------------------------------------------------------------------
# fuse_all_reduce_pass
# --------------------------------------------------------------------------
def test_fuse_pass_bucket_count_bound():
    """>=20 grad tensors collapse to <= ceil(total_MB / threshold_MB)
    collectives — the acceptance bound."""
    import math

    main, startup, loss = build_mlp_dp_program(n_layers=10, width=64)
    pre = collect_comm_stats(main, 8)
    assert pre["collective_ops"] >= 20

    mb = 0.05
    _flags.set_flags({"fuse_grad_size_in_MB": mb})
    exe = pt.Executor(pt.CPUPlace())
    rewritten = exe._apply_ir_passes(main, [loss.name])
    post = collect_comm_stats(rewritten, 8)
    total_mb = pre["payload_bytes"] / float(1 << 20)
    assert post["collective_ops"] <= math.ceil(total_mb / mb), post
    # payload is conserved across the rewrite
    assert post["payload_bytes"] == pre["payload_bytes"]
    # every bucket carries >1 tensor (single-tensor groups keep their op)
    assert all(b["n_tensors"] >= 2 for b in post["buckets"])


def test_fuse_pass_bit_identical_and_rollback():
    """Fused (compress off) loses not one bit vs the unfused graph, and
    FLAGS_fuse_grad_size_in_MB=0 restores the unfused graph exactly."""
    mesh_mod.init_mesh()
    width = 16
    main, startup, loss = build_mlp_dp_program(n_layers=3, width=width,
                                               seed=3)
    xs, ys = _data(width)
    exe = pt.Executor(pt.CPUPlace())

    def run(mb):
        _flags.set_flags({"fuse_grad_size_in_MB": mb,
                          "dp_grad_compress": "none"})
        scope = Scope()
        for k, v in init.items():
            scope.set(k, v.copy())
        compiled = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name)
        losses = [
            np.asarray(exe.run(compiled, feed={"x": xs, "y": ys},
                               fetch_list=[loss], scope=scope)[0])
            for _ in range(5)
        ]
        params = {k: np.asarray(scope.get(k)) for k in init}
        return losses, params

    sa = Scope()
    init = _init_scope(startup, sa)
    fused_l, fused_p = run(mb=32)
    unfused_l, unfused_p = run(mb=0)
    for a, b in zip(fused_l, unfused_l):
        np.testing.assert_array_equal(a, b)
    for k in init:
        np.testing.assert_array_equal(fused_p[k], unfused_p[k])

    # rollback: threshold 0 leaves the program untouched by the pass
    _flags.set_flags({"fuse_grad_size_in_MB": 0})
    rewritten = exe._apply_ir_passes(main, [loss.name])
    stats = collect_comm_stats(rewritten, 8)
    assert "c_fused_allreduce" not in stats["ops_by_type"]
    assert stats["ops_by_type"]["c_allreduce_sum"] == \
        collect_comm_stats(main, 8)["ops_by_type"]["c_allreduce_sum"]


def test_fuse_pass_bucket_boundaries():
    """Empty program: no-op.  One-tensor group: original op kept.
    Mixed dtypes: refuse to merge across the boundary."""
    from paddle_tpu.framework.ir import get_pass

    # empty — no collectives at all
    empty = fluid.Program()
    with fluid.program_guard(empty, fluid.Program()):
        fluid.layers.data("e", [4])
    p = get_pass("fuse_all_reduce_pass", max_bytes=1 << 20)
    p.apply(empty)
    assert p.fused_count == 0

    def ar_program(specs):
        main = fluid.Program()
        block = main.global_block()
        for name, dtype in specs:
            v = block.create_var(name=name, shape=[8], dtype=dtype)
            want = v.dtype
            block.append_op("c_allreduce_sum", inputs={"X": [name]},
                            outputs={"Out": [name]}, attrs={"ring_id": 0})
            # append_op's shape inference defaults the out var to f32;
            # restore the declared dtype (grad programs carry real ones)
            v.dtype = want
        return main, block

    # single tensor — nothing to fuse, op list unchanged
    main, block = ar_program([("a", "float32")])
    p = get_pass("fuse_all_reduce_pass", max_bytes=1 << 20)
    p.apply(main)
    assert [o.type for o in block.ops] == ["c_allreduce_sum"]

    # f32 / f64 / f32: the f64 both stays per-tensor and splits the f32s
    main, block = ar_program(
        [("a", "float32"), ("b", "float64"), ("c", "float32")])
    p = get_pass("fuse_all_reduce_pass", max_bytes=1 << 20)
    p.apply(main)
    assert [o.type for o in block.ops] == ["c_allreduce_sum"] * 3

    # two adjacent f32s merge; the trailing f64 keeps its own op
    main, block = ar_program(
        [("a", "float32"), ("c", "float32"), ("b", "float64")])
    p = get_pass("fuse_all_reduce_pass", max_bytes=1 << 20)
    p.apply(main)
    types = [o.type for o in block.ops]
    assert types.count("c_fused_allreduce") == 1
    assert types.count("c_allreduce_sum") == 1
    fused = [o for o in block.ops if o.type == "c_fused_allreduce"][0]
    assert fused.inputs["X"] == ["a", "c"]


def test_compressed_allreduce_error_bound():
    """bf16 wire format: fused allreduce of random f32 payloads stays
    within the quantization bound of the exact sum (one rounding per
    addend — f32 accumulation, EQuARX-style)."""
    mesh_mod.init_mesh()
    _flags.set_flags({"fuse_grad_size_in_MB": 32,
                      "dp_grad_compress": "bf16"})
    main = fluid.Program()
    block = main.global_block()
    names = []
    for i in range(3):
        # static [8, 4] shape (grad tensors are static; the pass skips
        # dynamic -1 batch dims)
        block.create_var(name=f"x{i}", shape=[8, 4], dtype="float32")
        block.append_op(
            "c_allreduce_sum", inputs={"X": [f"x{i}"]},
            outputs={"Out": [f"x{i}"]}, attrs={"ring_id": 0})
        names.append(f"x{i}")
    rng = np.random.RandomState(0)
    feeds = {n: rng.randn(8, 4).astype(np.float32) for n in names}
    exe = pt.Executor(pt.CPUPlace())
    compiled = fluid.CompiledProgram(main).with_data_parallel()
    got = exe.run(compiled, feed=dict(feeds), fetch_list=list(names),
                  scope=Scope())
    # the rewritten program really shipped ONE compressed bucket
    rewritten = exe._apply_ir_passes(main, list(names))
    stats = collect_comm_stats(rewritten, 8)
    assert stats["ops_by_type"] == {"c_fused_allreduce": 1}
    assert stats["buckets"][0]["compress"] == "bf16"
    for n, g in zip(names, got):
        expect = feeds[n].sum(axis=0, keepdims=True)
        assert np.asarray(g).shape == (8, 1, 4)
        for i in range(8):
            np.testing.assert_allclose(np.asarray(g)[i], expect,
                                       rtol=5e-2, atol=5e-2)
        # and the bound is real: bf16 wire cannot be bit-exact in general
        scale = np.max(np.abs(expect))
        assert np.max(np.abs(np.asarray(g)[0] - expect)) < 0.02 * scale + 1e-3


# --------------------------------------------------------------------------
# ZeRO-1: pjit path
# --------------------------------------------------------------------------
def _moment_shards(scope):
    import jax

    out = {}
    for k, v in scope.items():
        if "moment" in k and isinstance(v, jax.Array):
            out[k] = (tuple(v.shape),
                      v.addressable_shards[0].data.nbytes / v.nbytes)
    return out


def test_pjit_sharded_optimizer_parity_and_memory():
    """FLAGS_dp_sharding=1: >=10-step loss parity with single-device
    Adam, and every divisible moment holds 1/8 of its bytes per device
    (the [1]-shaped pow accumulators stay replicated — the padding
    allowance)."""
    width = 16
    main, startup, loss = build_mlp_dp_program(
        n_layers=2, width=width, optimizer="adam", lr=0.01, transpile=False)
    xs, ys = _data(width)
    exe = pt.Executor(pt.CPUPlace())
    sa = Scope()
    init = _init_scope(startup, sa)
    single = [float(exe.run(main, feed={"x": xs, "y": ys},
                            fetch_list=[loss], scope=sa)[0])
              for _ in range(10)]

    _flags.set_flags({"dp_sharding": 1})
    sb = Scope()
    for k, v in init.items():
        sb.set(k, v.copy())
    compiled = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name)
    dp = [float(exe.run(compiled, feed={"x": xs, "y": ys},
                        fetch_list=[loss], scope=sb)[0])
          for _ in range(10)]
    np.testing.assert_allclose(single, dp, rtol=1e-4, atol=1e-5)

    shards = _moment_shards(sb)
    assert shards, "no optimizer state found in scope"
    for name, (shape, frac) in shards.items():
        if shape[0] % 8 == 0:
            assert frac == pytest.approx(1 / 8), (name, shape, frac)
        else:
            assert frac == 1.0, (name, shape, frac)
    assert any(shape[0] % 8 == 0 for shape, _ in shards.values())


def test_pjit_sharding_rollback_replicated():
    """Default FLAGS_dp_sharding=0 keeps every moment fully replicated —
    today's behavior."""
    width = 16
    main, startup, loss = build_mlp_dp_program(
        n_layers=2, width=width, optimizer="adam", lr=0.01, transpile=False)
    xs, ys = _data(width)
    exe = pt.Executor(pt.CPUPlace())
    scope = Scope()
    _init_scope(startup, scope)
    compiled = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name)
    for _ in range(2):
        exe.run(compiled, feed={"x": xs, "y": ys}, fetch_list=[loss],
                scope=scope)
    for name, (shape, frac) in _moment_shards(scope).items():
        assert frac == 1.0, (name, shape, frac)


# --------------------------------------------------------------------------
# the data-parallel step session: state bound once, donated, rebound from
# the step's own outputs (the one-chip _StateSession on a mesh)
# --------------------------------------------------------------------------
SESSION_CASES = [pytest.param(False, 0, id="pjit"),
                 pytest.param(False, 1, id="zero1"),
                 pytest.param(False, 2, id="zero2"),
                 pytest.param(False, 3, id="zero3"),
                 pytest.param(True, 0, id="shard_map"),
                 pytest.param(True, 3, id="shard_map_zero3")]


def _invalidations():
    from paddle_tpu.utils import telemetry

    fam = telemetry.registry().snapshot().get(
        "executor_step_session_invalidations_total")
    return fam["series"][0]["value"] if fam and fam["series"] else 0


def _session_run(program, stage, session, steps=6, poke_at=None):
    """`steps` DP steps of `program` (as `_staged_program` returns it)
    from its initial weights, under the repo's profiler.  Gives the raw
    fetched losses, the final state, the arrays `executor/bind` placed
    on each step, the session invalidations the run counted, the scope,
    the compiled entry and `step()` for one more step.  `poke_at`:
    before that step one parameter is overwritten through `scope.set`."""
    from paddle_tpu import profiler

    main, _, loss, init = program
    mesh_mod.registry().clear()
    mesh_mod.init_mesh()
    _flags.set_flags({"dp_sharding": stage, "tpu_step_session": session})
    xs, ys = _data(16)
    exe = pt.Executor(pt.CPUPlace())
    scope = Scope()
    for k, v in init.items():
        scope.set(k, v.copy())
    compiled = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name)
    poked = sorted(k for k in init if k.endswith(".w_0"))[0]

    def step():
        return np.asarray(exe.run(compiled, feed={"x": xs, "y": ys},
                                  fetch_list=[loss], scope=scope)[0])

    counted = _invalidations()
    profiler.enable_profiler()
    try:
        losses = []
        for i in range(steps):
            if i == poke_at:
                scope.set(poked, np.full_like(init[poked], 0.25))
            losses.append(step())
        placed = [e["args"]["arrays"] for e in profiler.get_events()
                  if e["name"] == "executor/bind"]
    finally:
        profiler.disable_profiler(print_summary=False)
        profiler.reset_profiler()
    entry, = compiled.__dict__["_dp_cache"].values()
    return SimpleNamespace(
        losses=losses, placed=placed, scope=scope, entry=entry, step=step,
        invalidations=_invalidations() - counted,
        # copies: on XLA:CPU a numpy view of a buffer keeps it from
        # being donated
        state={k: np.array(v) for k, v in scope.items()
               if not k.startswith("@")})


def _assert_same_run(a, b):
    for x, y in zip(a.losses, b.losses):
        np.testing.assert_array_equal(x, y)
    assert a.state.keys() == b.state.keys()
    for k in a.state:
        np.testing.assert_array_equal(a.state[k], b.state[k], err_msg=k)


@pytest.mark.parametrize("collective,stage", SESSION_CASES)
def test_dp_step_session_bit_identical_to_the_walk(collective, stage):
    """With the session a step binds its state from the step before and
    places nothing; losses and final state are, bit for bit, those of
    the scope walk (FLAGS_tpu_step_session=0), and the step is compiled
    once: its outputs come back under the shardings its inputs have."""
    program = _staged_program(collective)
    on = _session_run(program, stage, session=1)
    off = _session_run(program, stage, session=0)
    _assert_same_run(on, off)
    n_state = len(on.entry.donatable) + len(on.entry.readonly)
    assert n_state > 20
    assert on.placed == [n_state, 0, 0, 0, 0, 0]
    assert off.placed == [n_state] * 6
    assert (on.invalidations, off.invalidations) == (0, 0)
    assert on.entry.fn._cache_size() == off.entry.fn._cache_size() == 1
    assert on.entry.session is not None and off.entry.session is None
    assert on.losses[0].shape == ((8,) if collective else ())


@pytest.mark.parametrize("collective,stage", SESSION_CASES)
def test_dp_step_session_honours_a_scope_write(collective, stage):
    """A `scope.set` of a parameter between two steps drops the session
    once: the next step walks the scope and trains on the written value,
    and the session is back the step after."""
    program = _staged_program(collective)
    plain = _session_run(program, stage, session=1)
    on = _session_run(program, stage, session=1, poke_at=3)
    off = _session_run(program, stage, session=0, poke_at=3)
    _assert_same_run(on, off)
    n_state = on.placed[0]
    assert on.placed == [n_state, 0, 0, n_state, 0, 0]
    assert (on.invalidations, off.invalidations) == (1, 0)
    np.testing.assert_array_equal(np.stack(on.losses[:3]),
                                  np.stack(plain.losses[:3]))
    assert not np.array_equal(on.losses[3], plain.losses[3])


@pytest.mark.parametrize("collective,stage", SESSION_CASES)
def test_dp_step_donates_its_state_and_keeps_one_copy(collective, stage):
    """After a step the arrays the step before left in the scope are
    deleted (donated), what the step only reads is not, the scope holds
    the session's own objects, and no second copy of the state is alive;
    an overwritten scope frees the state although a session remains."""
    import gc

    import jax

    program = _staged_program(collective)
    gc.collect()
    before = jax.live_arrays()          # held, so no id below is reused
    run = _session_run(program, stage, session=1, steps=3)
    entry, scope = run.entry, run.scope
    held = {n: scope.get(n) for n in entry.donatable}
    kept = entry.session.ro             # the device copies of host values
    assert len(held) > 20 and kept.keys() == set(entry.readonly)
    run.step()
    assert all(v.is_deleted() for v in held.values())
    assert not any(v.is_deleted() for v in kept.values())
    mut, _ = entry.session.deref()
    assert all(mut[n] is scope.get(n) for n in entry.donatable)
    state_bytes = sum(v.nbytes for v in mut.values())
    del held, mut
    gc.collect()
    known = {id(a) for a in before}
    alive = sum(a.nbytes for a in jax.live_arrays() if id(a) not in known)
    assert state_bytes <= alive < 1.5 * state_bytes
    # an abandoned session pins nothing: the scope's entries were the
    # only strong references to what the step rewrites
    for n in entry.donatable:
        scope.set(n, None)
    gc.collect()
    assert entry.session.deref() is None
    alive = sum(a.nbytes for a in jax.live_arrays() if id(a) not in known)
    assert alive < 0.5 * state_bytes


def test_dp_step_with_selected_rows_state_walks_every_step():
    """A state value that cannot be weakly referenced (a SelectedRows
    pytree) means no session, as on one chip: every step walks the
    scope, and the value is still carried from step to step."""
    import jax.numpy as jnp

    from paddle_tpu import profiler
    from paddle_tpu.framework import unique_name
    from paddle_tpu.framework.selected_rows import SelectedRows

    unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [4])
        total = fluid.layers.reduce_sum(x)
        sparse = main.global_block().create_var(
            name="sparse_state", shape=[6, 2], dtype="float32",
            persistable=True)
        main.global_block().append_op(
            type="scale", inputs={"X": [sparse]}, outputs={"Out": [sparse]},
            attrs={"scale": 0.5})
    mesh_mod.init_mesh()
    exe = pt.Executor(pt.CPUPlace())
    scope = Scope()
    scope.set("sparse_state", SelectedRows(
        jnp.asarray([1, 4], jnp.int32), jnp.ones((2, 2), jnp.float32), 6))
    compiled = fluid.CompiledProgram(main).with_data_parallel()
    counted = _invalidations()
    profiler.enable_profiler()
    try:
        for _ in range(3):
            exe.run(compiled, feed={"x": np.ones((8, 4), np.float32)},
                    fetch_list=[total], scope=scope)
        placed = [e["args"]["arrays"] for e in profiler.get_events()
                  if e["name"] == "executor/bind"]
    finally:
        profiler.disable_profiler(print_summary=False)
        profiler.reset_profiler()
    entry, = compiled.__dict__["_dp_cache"].values()
    assert entry.donatable == ("sparse_state",)
    assert placed == [1, 1, 1] and entry.session is None
    assert _invalidations() == counted
    got = scope.get("sparse_state")
    assert isinstance(got, SelectedRows)
    np.testing.assert_array_equal(np.asarray(got.values),
                                  np.full((2, 2), 0.125, np.float32))


# --------------------------------------------------------------------------
# ZeRO-1: dygraph fused-Adam flat buffers
# --------------------------------------------------------------------------
def _dygraph_train(flip_on_at=None, flip_off_at=None, steps=14):
    import jax
    from paddle_tpu.dygraph import Linear, Sequential, guard, to_variable

    mesh_mod.registry().clear()
    mesh_mod.init_mesh()
    _flags.set_flags({"dp_sharding": 0})
    xs = np.random.RandomState(0).randn(32, 8).astype(np.float32)
    ys = (xs[:, :1] * 1.5 - 0.5).astype(np.float32)
    with guard():
        net = Sequential(Linear(8, 16, act="relu"), Linear(16, 1))
        rs = np.random.RandomState(11)
        for p in net.parameters():
            p._value = jax.numpy.asarray(
                (rs.rand(*p.shape).astype(np.float32) - 0.5) * 0.2)
        opt = fluid.optimizer.AdamOptimizer(
            0.01, parameter_list=net.parameters())
        losses = []
        for i in range(steps):
            if flip_on_at is not None and i == flip_on_at:
                _flags.set_flags({"dp_sharding": 1})
            if flip_off_at is not None and i == flip_off_at:
                _flags.set_flags({"dp_sharding": 0})
            pred = net(to_variable(xs))
            loss = fluid.layers.reduce_mean(
                fluid.layers.square_error_cost(pred, to_variable(ys)))
            loss.backward()
            opt.minimize(loss)
            net.clear_gradients()
            losses.append(float(np.asarray(loss.value()).ravel()[0]))
        state = dict(opt._param_state.get("@fused", {}))
    _flags.set_flags({"dp_sharding": 0})
    return losses, state


def test_dygraph_fused_adam_sharding_mode_flip():
    """Flat fused-Adam state survives sharding on AND off mid-run with
    the identical trajectory, and the sharded buffer really holds
    1/ndev (+pad) per device."""
    base, _ = _dygraph_train(steps=14)
    flip, state = _dygraph_train(flip_on_at=4, flip_off_at=10, steps=14)
    np.testing.assert_allclose(base, flip, rtol=1e-6, atol=1e-7)
    # flag is off at the end: buffers sliced back to logical length
    n_params = 8 * 16 + 16 + 16 * 1 + 1  # 161
    assert int(state["m1"].shape[0]) == n_params

    _, sharded_state = _dygraph_train(flip_on_at=4, steps=14)
    m1 = sharded_state["m1"]
    padded = -(-n_params // 8) * 8
    assert int(m1.shape[0]) == padded
    assert len(m1.sharding.device_set) == 8
    assert m1.addressable_shards[0].data.nbytes == m1.nbytes // 8


def test_dygraph_fused_mp_master_sharding():
    """amp-O2 path (_apply_fused_mp): bf16-resident params with f32
    grads keep their f32 master sharded under FLAGS_dp_sharding, at an
    unchanged trajectory."""
    import jax
    import jax.numpy as jnp

    def run(shard_from=None, steps=8):
        mesh_mod.registry().clear()
        mesh_mod.init_mesh()
        _flags.set_flags({"dp_sharding": 0})
        rs = np.random.RandomState(5)
        params = [
            SimpleNamespace(name=f"p{i}",
                            _value=jnp.asarray(
                                rs.rand(*s).astype(np.float32)
                            ).astype(jnp.bfloat16))
            for i, s in enumerate([(4, 8), (8,), (8, 2)])
        ]
        opt = fluid.optimizer.AdamOptimizer(0.01)
        grs = np.random.RandomState(7)
        grads_per_step = [
            [jnp.asarray(grs.randn(*np.shape(p._value)).astype(np.float32))
             for p in params]
            for _ in range(steps)
        ]
        for i in range(steps):
            if shard_from is not None and i == shard_from:
                _flags.set_flags({"dp_sharding": 1})
            opt._dygraph_apply(list(zip(params, grads_per_step[i])))
        vals = [np.asarray(p._value.astype(jnp.float32)) for p in params]
        state = dict(opt._param_state.get("@fused_mp", {}))
        _flags.set_flags({"dp_sharding": 0})
        return vals, state

    base_vals, _ = run()
    flip_vals, state = run(shard_from=3)
    for a, b in zip(base_vals, flip_vals):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
    master = state["master"]
    n = 4 * 8 + 8 + 8 * 2  # 56 -> multiple of 8 already
    assert int(master.shape[0]) == n
    assert len(master.sharding.device_set) == 8
    assert master.addressable_shards[0].data.nbytes == master.nbytes // 8


def test_dygraph_sharding_mesh_resize_repads():
    """A flat buffer padded for one dp size re-pads when the mesh is
    rebuilt with another — dp=4's 164-pad must not be device_put with an
    8-way sharding (not divisible)."""
    import jax
    import jax.numpy as jnp

    rs = np.random.RandomState(5)
    # 3 + 158 = 161 elements: pad 164 on dp=4, 168 on dp=8
    params = [
        SimpleNamespace(name=f"q{i}",
                        _value=jnp.asarray(rs.rand(*s).astype(np.float32)))
        for i, s in enumerate([(3,), (158,)])
    ]
    opt = fluid.optimizer.AdamOptimizer(0.01)
    grs = np.random.RandomState(7)

    def step():
        grads = [jnp.asarray(grs.randn(*np.shape(p._value))
                             .astype(np.float32)) for p in params]
        opt._dygraph_apply(list(zip(params, grads)))

    _flags.set_flags({"dp_sharding": 1})
    mesh_mod.registry().clear()
    mesh_mod.init_mesh((4,), ("dp",))
    for _ in range(2):
        step()
    m1 = opt._param_state["@fused"]["m1"]
    assert int(m1.shape[0]) == 164

    mesh_mod.registry().clear()
    mesh_mod.init_mesh((8,), ("dp",))
    for _ in range(2):
        step()
    m1 = opt._param_state["@fused"]["m1"]
    assert int(m1.shape[0]) == 168
    assert len(m1.sharding.device_set) == 8
    for p in params:
        assert np.isfinite(np.asarray(p._value)).all()


# --------------------------------------------------------------------------
# ZeRO-2/3 stages (r8): pjit + shard_map paths, stage flips, overlap
# --------------------------------------------------------------------------
def _shard_fracs(scope):
    import jax

    out = {}
    for k, v in scope.items():
        if isinstance(v, jax.Array) and v.ndim and v.nbytes:
            out[k] = v.addressable_shards[0].data.nbytes / v.nbytes
    return out


def _run_staged(stage, init, main, startup, loss, steps=8,
                width=16, schedule=None):
    """Train `steps` with FLAGS_dp_sharding=stage (optionally flipping
    per-step via `schedule`: list of stages, one per step).  Which DP
    path runs is decided by `main` itself: transpiled programs (c_* ops)
    take shard_map, untranspiled take pjit."""
    mesh_mod.registry().clear()
    mesh_mod.init_mesh()
    _flags.set_flags({"dp_sharding": stage, "fuse_grad_size_in_MB": 32.0,
                      "dp_grad_compress": "none", "dp_comm_overlap": 1})
    xs, ys = _data(width)
    exe = pt.Executor(pt.CPUPlace())
    scope = Scope()
    for k, v in init.items():
        scope.set(k, v.copy())
    compiled = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name)
    losses = []
    for i in range(steps):
        if schedule is not None:
            _flags.set_flags({"dp_sharding": schedule[i]})
        out = exe.run(compiled, feed={"x": xs, "y": ys},
                      fetch_list=[loss], scope=scope)[0]
        losses.append(float(np.mean(out)))
    return losses, scope, exe


def _staged_program(collective, optimizer="adam"):
    from paddle_tpu.framework import unique_name

    unique_name.switch()
    main, startup, loss = build_mlp_dp_program(
        n_layers=3, width=16, optimizer=optimizer, lr=0.01,
        transpile=collective)
    sa = Scope()
    init = _init_scope(startup, sa)
    return main, startup, loss, init


@pytest.mark.parametrize("collective", [False, True],
                         ids=["pjit", "shard_map"])
def test_zero23_loss_parity_and_sharded_bytes(collective):
    """Stages 2 and 3 match the stage-0 and stage-1 trajectories, shard
    every divisible moment 1/8, and at stage 3 every divisible param
    1/8 — on BOTH DP paths."""
    main, startup, loss, init = _staged_program(collective)
    base, scope0, _ = _run_staged(0, init, main, startup, loss)
    ref1, _, _ = _run_staged(1, init, main, startup, loss)
    np.testing.assert_allclose(base, ref1, rtol=1e-5, atol=1e-6)
    for stage in (2, 3):
        got, scope, exe = _run_staged(stage, init, main, startup, loss)
        np.testing.assert_allclose(base, got, rtol=1e-5, atol=1e-6)
        fr = _shard_fracs(scope)
        moments = {k: v for k, v in fr.items() if "moment" in k}
        assert moments
        for k, v in moments.items():
            want = 1 / 8 if int(scope.get(k).shape[0]) % 8 == 0 else 1.0
            assert v == pytest.approx(want), (k, v)
        params = {k: v for k, v in fr.items()
                  if k.endswith(".w_0") or k.endswith(".b_0")}
        assert params
        for k, v in params.items():
            want = (1 / 8 if stage >= 3
                    and int(scope.get(k).shape[0]) % 8 == 0 else 1.0)
            assert v == pytest.approx(want), (stage, k, v)
        if collective and stage >= 2:
            # the fused buckets really lowered to reduce-scatter
            rewritten = exe._apply_ir_passes(main, [loss.name])
            stats = collect_comm_stats(rewritten, 8)
            assert stats["ops_by_type"].get("c_fused_reduce_scatter"), stats
            from dp_comm_stats import grad_buffer_bytes

            total, per_dev = grad_buffer_bytes(rewritten, 8, stage)
            # every divisible grad holds 1/8; only the [1]-bias stays full
            assert per_dev < total / 8 + 16, (total, per_dev)


@pytest.mark.parametrize("collective", [False, True],
                         ids=["pjit", "shard_map"])
def test_stage_flip_mid_run_carries_state(collective):
    """Walking the whole ladder mid-run (0 -> 1 -> 2 -> 3 -> 0) carries
    optimizer state through every re-layout: the trajectory equals a
    constant stage-0 run."""
    main, startup, loss, init = _staged_program(collective)
    base, _, _ = _run_staged(0, init, main, startup, loss, steps=10)
    schedule = [0, 0, 1, 1, 2, 2, 3, 3, 0, 0]
    flip, scope, _ = _run_staged(0, init, main, startup, loss,
                                 steps=10, schedule=schedule)
    np.testing.assert_allclose(base, flip, rtol=1e-5, atol=1e-6)
    # back at stage 0: everything replicated again
    for k, v in _shard_fracs(scope).items():
        assert v == 1.0, (k, v)


def test_shard_map_zero1_shares_slot_table():
    """Satellite: ZeRO-1 on the fleet-collective path — SGD has no
    state to shard (stays unwrapped at stage 1), momentum's Velocity
    (derived by the shared partition-rule engine from the registered
    slot declarations) shards 1/8 at unchanged trajectory."""
    from paddle_tpu.parallel import partition_rules
    from paddle_tpu.parallel.data_parallel import _update_shard_rows

    assert partition_rules.opt_state_slots("momentum") == ("Velocity",)
    from paddle_tpu.framework import unique_name

    unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 3
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [16])
        y = fluid.layers.data("y", [1])
        h = fluid.layers.fc(x, 32, act="relu")
        pred = fluid.layers.fc(h, 1)
        loss = fluid.layers.reduce_mean(
            fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.MomentumOptimizer(0.05, 0.9).minimize(loss)
    from paddle_tpu.transpiler import GradAllReduce

    GradAllReduce().transpile(startup_program=startup, main_program=main,
                              rank=0, endpoints=["127.0.0.1:6170"], nranks=8)
    # the shared eligibility helper sees the momentum ops
    blk = main.global_block()
    rows = [_update_shard_rows(o, blk, 8) for o in blk.ops
            if o.type == "momentum"]
    assert rows and any(r for r in rows)

    sa = Scope()
    init = _init_scope(startup, sa)
    base, _, _ = _run_staged(0, init, main, startup, loss)
    got, scope, _ = _run_staged(1, init, main, startup, loss)
    np.testing.assert_allclose(base, got, rtol=1e-5, atol=1e-6)
    vel = {k: v for k, v in _shard_fracs(scope).items() if "velocity" in k}
    assert vel
    assert any(v == pytest.approx(1 / 8) for v in vel.values()), vel


# --------------------------------------------------------------------------
# backward-overlap collective scheduling
# --------------------------------------------------------------------------
def _bucket_schedule(mb=0.05, overlap=True, stage=0):
    mesh_mod.registry().clear()
    mesh_mod.init_mesh()
    _flags.set_flags({"fuse_grad_size_in_MB": mb, "dp_comm_overlap":
                      int(overlap), "dp_sharding": stage,
                      "dp_grad_compress": "none"})
    from paddle_tpu.framework import unique_name

    unique_name.switch()
    main, startup, loss = build_mlp_dp_program(n_layers=10, width=64)
    exe = pt.Executor(pt.CPUPlace())
    rewritten = exe._apply_ir_passes(main, [loss.name])
    return collect_comm_stats(rewritten, 8), main, loss, exe


def test_overlap_schedule_orders_buckets_by_readiness():
    """Each bucket's collective is issued at last-gradient-ready + its
    prologue, precedes the last backward op of any LATER bucket (it is
    in flight while their grads are still being produced), and >= half
    the buckets land before the final backward op."""
    stats, _, _, _ = _bucket_schedule(overlap=True)
    buckets = stats["buckets"]
    assert len(buckets) >= 3
    for b in buckets:
        assert b["ready_at_op"] < b["issued_at_op"], b
    issued = [b["issued_at_op"] for b in buckets]
    assert issued == sorted(issued)
    for i, b in enumerate(buckets[:-1]):
        for later in buckets[i + 1:]:
            assert b["issued_at_op"] < later["ready_at_op"], (b, later)
    ov = stats["overlap"]
    assert ov["n_buckets_overlapped"] * 2 >= ov["n_buckets"], ov
    assert ov["est_exposed_comm_bytes"] < sum(b["wire_bytes"]
                                              for b in buckets), ov


def test_overlap_rollback_restores_append_schedule():
    """FLAGS_dp_comm_overlap=0 restores the r7 schedule: every fused
    collective sits in the program tail, after the last backward
    compute op — and the collective count is unchanged vs overlap=1 at
    the default bucket size (the overlap pass reorders, never splits)."""
    on, _, _, _ = _bucket_schedule(mb=32.0, overlap=True)
    off, _, _, _ = _bucket_schedule(mb=32.0, overlap=False)
    assert on["collective_ops"] == off["collective_ops"]
    assert sum(b["payload_bytes"] for b in on["buckets"]) == \
        sum(b["payload_bytes"] for b in off["buckets"])
    assert all(not b["overlapped"] for b in off["buckets"]), off["buckets"]
    assert all(b["overlapped"] for b in on["buckets"][:-1])


def test_overlap_bit_identical_to_append():
    """Reordering the collectives changes no value: overlap on/off
    trains bit-identically (the same reductions run, just earlier)."""
    mesh_mod.init_mesh()
    width = 16
    from paddle_tpu.framework import unique_name

    unique_name.switch()
    main, startup, loss = build_mlp_dp_program(n_layers=3, width=width,
                                               seed=3)
    xs, ys = _data(width)
    exe = pt.Executor(pt.CPUPlace())
    sa = Scope()
    init = _init_scope(startup, sa)

    def run(overlap):
        _flags.set_flags({"fuse_grad_size_in_MB": 0.01,
                          "dp_comm_overlap": overlap,
                          "dp_grad_compress": "none", "dp_sharding": 0})
        scope = Scope()
        for k, v in init.items():
            scope.set(k, v.copy())
        compiled = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name)
        losses = [np.asarray(exe.run(compiled, feed={"x": xs, "y": ys},
                                     fetch_list=[loss], scope=scope)[0])
                  for _ in range(5)]
        return losses, {k: np.asarray(scope.get(k)) for k in init}

    on_l, on_p = run(1)
    off_l, off_p = run(0)
    for a, b in zip(on_l, off_l):
        np.testing.assert_array_equal(a, b)
    for k in on_p:
        np.testing.assert_array_equal(on_p[k], off_p[k])


def test_sharded_update_restores_full_grad_for_later_consumers():
    """A post-update consumer of a gradient (grad-norm log / EMA
    pattern) must see the full tensor on the wrapped shard_map path,
    not the device's slice the update consumed."""
    from paddle_tpu.framework import unique_name

    mesh_mod.init_mesh()
    unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 3
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [16])
        y = fluid.layers.data("y", [1])
        pred = fluid.layers.fc(x, 1)
        loss = fluid.layers.reduce_mean(
            fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.MomentumOptimizer(0.05, 0.9).minimize(loss)
    from paddle_tpu.transpiler import GradAllReduce

    GradAllReduce().transpile(startup_program=startup, main_program=main,
                              rank=0, endpoints=["127.0.0.1:6170"], nranks=8)
    block = main.global_block()
    gname = "fc_0.w_0@GRAD"
    block.create_var(name="g_snapshot", shape=[16, 1], dtype="float32")
    block.append_op("scale", inputs={"X": [gname]},
                    outputs={"Out": ["g_snapshot"]}, attrs={"scale": 1.0})
    xs, ys = _data(16)
    exe = pt.Executor(pt.CPUPlace())
    scope = Scope()
    init = _init_scope(startup, scope)

    def run(stage):
        _flags.set_flags({"dp_sharding": stage})
        sc = Scope()
        for k, v in init.items():
            sc.set(k, v.copy())
        compiled = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name)
        snap = exe.run(compiled, feed={"x": xs, "y": ys},
                       fetch_list=["g_snapshot"], scope=sc)[0]
        return np.asarray(snap)

    full = run(0)
    sharded = run(1)
    assert sharded.shape == full.shape, (sharded.shape, full.shape)
    np.testing.assert_allclose(full, sharded, rtol=1e-6, atol=1e-7)


def test_zero2_scatter_refuses_unsafe_consumers():
    """A grad with a post-reduce consumer besides the shard-eligible
    update (here: an extra elementwise read) must NOT reduce-scatter —
    the consumer would see a 1/ndev shard."""
    from paddle_tpu.framework.ir import get_pass

    mesh_mod.registry().clear()
    mesh_mod.init_mesh()
    main = fluid.Program()
    block = main.global_block()
    for name in ("p", "g", "v", "p2", "g2", "v2"):
        block.create_var(name=name, shape=[8, 4], dtype="float32",
                         persistable=name in ("p", "v", "p2", "v2"))
    block.create_var(name="lr", shape=[1], dtype="float32",
                     persistable=True)
    block.create_var(name="peek", shape=[8, 4], dtype="float32")
    for g in ("g", "g2"):
        block.append_op("c_allreduce_sum", inputs={"X": [g]},
                        outputs={"Out": [g]},
                        attrs={"ring_id": 0, "op_role": 1})
    # post-reduce extra consumer of g only
    block.append_op("scale", inputs={"X": ["g"]}, outputs={"Out": ["peek"]},
                    attrs={"scale": 2.0})
    for p, g, v in (("p", "g", "v"), ("p2", "g2", "v2")):
        block.append_op("momentum",
                        inputs={"Param": [p], "Grad": [g], "Velocity": [v],
                                "LearningRate": ["lr"]},
                        outputs={"ParamOut": [p], "VelocityOut": [v]},
                        attrs={"mu": 0.9, "op_role": 2})
    p_ = get_pass("fuse_all_reduce_pass", max_bytes=1 << 20, overlap=True,
                  sharding_stage=2, ndev=8)
    p_.apply(main)
    types = [o.type for o in block.ops]
    # g (unsafe) keeps allreduce; g2 (safe) is a 1-tensor scatter group
    # -> no fusion but also no scatter op with g in it
    for o in block.ops:
        if o.type == "c_fused_reduce_scatter":
            assert "g" not in o.inputs["X"]
    assert "c_allreduce_sum" in types


# --------------------------------------------------------------------------
# multiclass_nms2 kept-index satellite
# --------------------------------------------------------------------------
def test_multiclass_nms2_duplicate_boxes_index():
    """Duplicate coordinates must map to the box the NMS actually kept,
    not to the first coordinate match (the old O(N*K*M) re-match)."""
    from paddle_tpu.contrib.layers import multiclass_nms2

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        bb = fluid.data(name="nb", shape=[1, 3, 4], dtype="float32")
        sc = fluid.data(name="ns", shape=[1, 2, 3], dtype="float32")
        out, idx = multiclass_nms2(bb, sc, score_threshold=0.3,
                                   nms_top_k=3, keep_top_k=3,
                                   background_label=0, return_index=True)
    boxes = np.zeros((1, 3, 4), np.float32)
    boxes[0, 0] = [0, 0, 5, 5]
    boxes[0, 1] = [0, 0, 5, 5]      # duplicate of box 0
    boxes[0, 2] = [20, 20, 25, 25]  # well separated
    scores = np.zeros((1, 2, 3), np.float32)
    # box 0 is BELOW threshold; the kept duplicate is box 1
    scores[0, 1] = [0.1, 0.9, 0.8]
    exe = pt.Executor(pt.CPUPlace())
    exe.run(startup)
    o, ind = exe.run(main, feed={"nb": boxes, "ns": scores},
                     fetch_list=[out, idx])
    assert float(o[0, 0, 1]) == pytest.approx(0.9)
    assert int(ind[0, 0]) == 1, ind  # the coordinate re-match said 0
    assert int(ind[0, 1]) == 2
    assert int(ind[0, 2]) == -1
