"""Static SPMD shard-safety analyzer (r26): the distribution-state
abstract interpreter (framework/shard_analysis.py) and its check
catalog.

Oracles:
* the engine's ``variant_names`` is pinned bit-for-bit against a
  REFERENCE copy of the r20 numerics taint walk (the private
  ``NumericsProbePass._shard_variant_names`` this PR deleted) on real
  ZeRO 0-3 x both-DP-path training programs — replacement, not drift;
* each seeded fault class is caught AT the named op with the right
  code: collective under a shard-variant cond predicate, divergent
  while trip count, replication-soundness (variant LearningRate /
  beta-pow slot, shard-variant numerics stats vector), donation vs
  outstanding-collective hazard, and ring / reduce-op / dtype member
  mismatches via the extended collective signature;
* zero false positives over the existing program zoo: DP training
  programs (4 optimizers x ZeRO 0-3 x both paths) and serving decoder
  forms (5 modes x tp in {2,4}, serving_tp_pass applied);
* the extended ``collective_signature`` records (type, ring, nargs,
  shape, reduce-op, dtype) and descends into sub-blocks at the parent
  op's position;
* gate semantics: default = RuntimeWarning + program untouched,
  FLAGS_shard_safety_strict = VerifyError, FLAGS_shard_safety=0 = no
  analysis at all (bit-identity by construction);
* tools/progcheck.py --shard lints saved program sets (JSON + nonzero
  exit on a seeded mismatch) and --shard --quick self-tests in a
  bounded subprocess.
"""
import json
import os
import subprocess
import sys
import warnings

import pytest

from paddle_tpu.framework import numerics, shard_analysis, unique_name
from paddle_tpu.framework import verifier
from paddle_tpu.framework.core import Program
from paddle_tpu.framework.dtype import VarType
from paddle_tpu.framework.ir import get_pass
from paddle_tpu.inference.gpt2_decoder import (DecoderConfig,
                                               build_decoder_program)
from paddle_tpu.inference.serving import SERVING_TP_RING_ID
from paddle_tpu.parallel import mesh as mesh_mod
from paddle_tpu.utils import flags as _flags

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))
from dp_comm_stats import build_mlp_dp_program  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_flags():
    saved = dict(_flags._flags)
    yield
    _flags._flags.clear()
    _flags._flags.update(saved)
    mesh_mod.registry().clear()


# ==========================================================================
# reference r20 taint walk — the EXACT semantics of the deleted
# NumericsProbePass._shard_variant_names, pinned here as the parity
# oracle for the shared engine
# ==========================================================================
def _r20_reference_walk(block):
    from paddle_tpu.ops import registry as _registry
    from paddle_tpu.utils.flags import flag

    ops = list(block.ops)
    stage = int(flag("dp_sharding") or 0)
    try:
        from paddle_tpu.parallel.mesh import ring_axis_size

        ndev = int(ring_axis_size(0))
    except Exception:
        ndev = 1
    plans = {}
    sharded_state = set()
    if stage >= 1 and ndev > 1:
        from paddle_tpu.parallel.data_parallel import _plan_wrapped_updates

        plans, sharded_state, _ = _plan_wrapped_updates(
            ops, block, ndev, stage)

    written, feeds = set(), set()
    for op_ in ops:
        for n in op_.input_arg_names:
            if n in written or n == "@EMPTY@":
                continue
            var = block._find_var_recursive(n)
            if var is None or not getattr(var, "persistable", False):
                feeds.add(n)
        written.update(op_.output_arg_names)

    clears = shard_analysis.REPLICATING_COLLECTIVES
    shards = shard_analysis.SHARDING_COLLECTIVES
    tainted = set(feeds) | set(sharded_state)
    for op_ in ops:
        outs = [n for n in op_.output_arg_names if n != "@EMPTY@"]
        plan = plans.get(id(op_))
        if plan is not None:
            for n in outs:
                (tainted.discard if n == plan["param"]
                 else tainted.add)(n)
            continue
        if op_.type in clears:
            tainted.difference_update(outs)
            continue
        if op_.type in shards:
            tainted.update(outs)
            continue
        d = _registry.OPS.get(op_.type)
        if (d is not None and d.stateful) or any(
                n in tainted for n in op_.input_arg_names):
            tainted.update(outs)
        else:
            tainted.difference_update(outs)
    return tainted


@pytest.mark.parametrize("transpile", [False, True])
@pytest.mark.parametrize("stage", [0, 1, 2, 3])
def test_variant_names_parity_with_r20_walk(transpile, stage):
    """Engine output == reference walk on real DP programs, every ZeRO
    stage x both DP paths — the replaced walk cannot have drifted."""
    mesh_mod.registry().clear()
    mesh_mod.init_mesh()
    _flags.set_flags({"FLAGS_dp_sharding": stage})
    with unique_name.guard():
        main, _, _ = build_mlp_dp_program(
            n_layers=3, width=16, nranks=8, optimizer="adam",
            transpile=transpile)
    blk = main.global_block()
    assert shard_analysis.variant_names(main, blk) == \
        _r20_reference_walk(blk)


def test_state_chain_provenance():
    """Every non-replicated state carries a human-readable inferred
    chain (seed + op steps) — the actionability contract."""
    prog = Program()
    b = prog.global_block()
    b.create_var(name="x", shape=[4], dtype=VarType.FP32)
    b.create_var(name="y", shape=[4], dtype=VarType.FP32)
    b.append_op("scale", inputs={"X": ["x"]}, outputs={"Out": ["y"]},
                attrs={"scale": 2.0, "bias": 0.0,
                       "bias_after_scale": True})
    res = shard_analysis.analyze(prog)
    st = res.state_of("y")
    assert st.kind == shard_analysis.VARIANT
    assert "feed-like" in st.describe() and "op #0" in st.describe()
    assert res.state_of("never_written").replicated


# ==========================================================================
# seeded fault injections — each caught at the named op
# ==========================================================================
def _cond_with_collective():
    prog = Program()
    b = prog.global_block()
    b.create_var(name="p", shape=[1], dtype=VarType.BOOL)
    b.create_var(name="g", shape=[4], dtype=VarType.FP32)
    b.create_var(name="s", shape=[4], dtype=VarType.FP32)
    sub = prog._create_block()
    sub.append_op("c_allreduce_sum", inputs={"X": ["g"]},
                  outputs={"Out": ["s"]}, attrs={"ring_id": 0})
    prog._rollback()
    b.append_op("cond", inputs={"Cond": ["p"]}, outputs={"Out": ["s"]},
                attrs={"true_block": sub, "false_block": sub,
                       "true_out_names": ["s"], "false_out_names": ["s"],
                       "input_names": []})
    return prog


def test_collective_under_variant_predicate_caught():
    ds = shard_analysis.check_program(_cond_with_collective())
    hit = [d for d in ds
           if d.code == "collective-under-variant-predicate"]
    assert len(hit) == 1
    d = hit[0]
    assert d.op_index == 0 and d.op_type == "cond" and d.var == "p"
    assert "c_allreduce_sum" in d.message
    assert "feed-like" in d.message  # the inferred state chain


def test_divergent_trip_count_caught():
    prog = Program()
    b = prog.global_block()
    b.create_var(name="n", shape=[1], dtype=VarType.FP32)
    b.create_var(name="c", shape=[1], dtype=VarType.BOOL)
    b.create_var(name="acc", shape=[4], dtype=VarType.FP32)
    b.append_op("less_than", inputs={"X": ["n"], "Y": ["n"]},
                outputs={"Out": ["c"]}, attrs={})
    sub = prog._create_block()
    sub.append_op("c_allreduce_sum", inputs={"X": ["acc"]},
                  outputs={"Out": ["acc"]}, attrs={"ring_id": 0})
    prog._rollback()
    b.append_op("while", inputs={"Cond": ["c"], "X": ["acc"]},
                outputs={"Out": ["acc"], "StepScopes": []},
                attrs={"sub_block": sub, "cond_name": "c",
                       "carry_names": ["acc"]})
    ds = shard_analysis.check_program(prog)
    hit = [d for d in ds if d.code == "divergent-trip-count"]
    assert len(hit) == 1
    assert hit[0].op_index == 1 and hit[0].op_type == "while"


def test_replicated_predicate_with_collective_is_clean():
    """The dual: a REPLICATED predicate over the same collective body
    is legal SPMD — no finding (false-positive guard)."""
    prog = _cond_with_collective()
    b = prog.global_block()
    b.var("p").persistable = True  # counter-style predicate: replicated
    assert shard_analysis.check_program(prog) == []


def _sgd_with_variant_lr():
    prog = Program()
    b = prog.global_block()
    b.create_var(name="lr", shape=[1], dtype=VarType.FP32)
    b.create_var(name="p", shape=[4], dtype=VarType.FP32,
                 persistable=True)
    b.create_var(name="gr", shape=[4], dtype=VarType.FP32)
    b.create_var(name="gred", shape=[4], dtype=VarType.FP32)
    b.append_op("c_allreduce_sum", inputs={"X": ["gr"]},
                outputs={"Out": ["gred"]}, attrs={"ring_id": 0})
    b.append_op("sgd", inputs={"Param": ["p"], "Grad": ["gred"],
                               "LearningRate": ["lr"]},
                outputs={"ParamOut": ["p"]}, attrs={})
    return prog


def test_replication_soundness_variant_lr_caught():
    ds = shard_analysis.check_program(_sgd_with_variant_lr())
    hit = [d for d in ds if d.code == "replication-required"]
    assert len(hit) == 1
    d = hit[0]
    assert d.op_index == 1 and d.op_type == "sgd" and d.var == "lr"
    assert "LearningRate" in d.message and "feed-like" in d.message


def test_replication_soundness_beta_pow_slot_caught():
    """A shard-variant value in adam's Beta1Pow slot (REPLICATED_SLOT_
    RULES) is flagged; the allreduced grad is not."""
    prog = Program()
    b = prog.global_block()
    for n, shape, pers in (("b1", [1], False), ("lr", [1], True),
                           ("p", [4], True), ("m", [4], True),
                           ("v", [4], True), ("b2", [1], True),
                           ("gr", [4], False), ("gred", [4], False)):
        b.create_var(name=n, shape=shape, dtype=VarType.FP32,
                     persistable=pers)
    b.append_op("c_allreduce_sum", inputs={"X": ["gr"]},
                outputs={"Out": ["gred"]}, attrs={"ring_id": 0})
    b.append_op("adam", inputs={"Param": ["p"], "Grad": ["gred"],
                                "LearningRate": ["lr"],
                                "Moment1": ["m"], "Moment2": ["v"],
                                "Beta1Pow": ["b1"], "Beta2Pow": ["b2"]},
                outputs={"ParamOut": ["p"], "Moment1Out": ["m"],
                         "Moment2Out": ["v"], "Beta1PowOut": ["b1"],
                         "Beta2PowOut": ["b2"]},
                attrs={"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8})
    ds = shard_analysis.check_program(prog)
    hit = [d for d in ds if d.code == "replication-required"]
    assert [d.var for d in hit] == ["b1"]
    assert "Beta1Pow" in hit[0].message


def test_numerics_stats_var_replication_contract():
    """A shard-variant @numerics_stats@ vector (probe partials never
    cross-shard combined) violates the probe's row-0 contract."""
    prog = Program()
    b = prog.global_block()
    b.create_var(name="x", shape=[4], dtype=VarType.FP32)
    b.create_var(name="r", shape=[4], dtype=VarType.FP32)
    b.create_var(name=numerics.STATS_VAR, shape=[4], dtype=VarType.FP32)
    b.append_op("c_allreduce_sum", inputs={"X": ["x"]},
                outputs={"Out": ["r"]}, attrs={"ring_id": 0})
    b.append_op("scale", inputs={"X": ["x"]},
                outputs={"Out": [numerics.STATS_VAR]},
                attrs={"scale": 1.0, "bias": 0.0,
                       "bias_after_scale": True})
    ds = shard_analysis.check_program(prog)
    hit = [d for d in ds if d.code == "replication-required"
           and d.var == numerics.STATS_VAR]
    assert len(hit) == 1


def test_comm_compute_hazard_caught():
    """A write into the payload of a still-outstanding collective (no
    read between issue and clobber) is the donation race."""
    prog = Program()
    b = prog.global_block()
    b.create_var(name="g", shape=[4], dtype=VarType.FP32)
    b.create_var(name="t", shape=[4], dtype=VarType.FP32)
    b.append_op("c_allreduce_sum", inputs={"X": ["g"]},
                outputs={"Out": ["g"]}, attrs={"ring_id": 0})
    b.append_op("scale", inputs={"X": ["t"]}, outputs={"Out": ["g"]},
                attrs={"scale": 2.0, "bias": 0.0,
                       "bias_after_scale": True})
    ds = shard_analysis.check_program(prog)
    hit = [d for d in ds if d.code == "comm-compute-hazard"]
    assert len(hit) == 1
    assert hit[0].op_index == 1 and hit[0].var == "g"


def test_comm_hazard_read_closes_window():
    """The dual: a READ of the payload awaits the collective, so a
    write after it is safe (false-positive guard — this is the normal
    in-place grad allreduce + update pattern)."""
    prog = Program()
    b = prog.global_block()
    b.create_var(name="g", shape=[4], dtype=VarType.FP32)
    b.create_var(name="p", shape=[4], dtype=VarType.FP32,
                 persistable=True)
    b.create_var(name="lr", shape=[1], dtype=VarType.FP32,
                 persistable=True)
    b.append_op("c_allreduce_sum", inputs={"X": ["g"]},
                outputs={"Out": ["g"]}, attrs={"ring_id": 0})
    b.append_op("sgd", inputs={"Param": ["p"], "Grad": ["g"],
                               "LearningRate": ["lr"]},
                outputs={"ParamOut": ["p"]}, attrs={})
    b.append_op("scale", inputs={"X": ["p"]}, outputs={"Out": ["g"]},
                attrs={"scale": 1.0, "bias": 0.0,
                       "bias_after_scale": True})
    assert shard_analysis.check_program(prog) == []


# ==========================================================================
# extended collective signature + member agreement
# ==========================================================================
def _member(ring=0, op="c_allreduce_sum", dtype=VarType.FP32):
    prog = Program()
    b = prog.global_block()
    b.create_var(name="x", shape=[4], dtype=dtype)
    b.create_var(name="g", shape=[4], dtype=dtype)
    b.create_var(name="s", shape=[4], dtype=dtype)
    b.append_op("scale", inputs={"X": ["x"]}, outputs={"Out": ["g"]},
                attrs={"scale": 1.0, "bias": 0.0,
                       "bias_after_scale": True})
    b.append_op(op, inputs={"X": ["g"]}, outputs={"Out": ["s"]},
                attrs={"ring_id": ring})
    return prog


def test_signature_records_reduce_op_and_dtype():
    sig = verifier.collective_signature(_member())
    assert sig == [("c_allreduce_sum", 0, 1, (4,), "sum", "float32")]
    sig16 = verifier.collective_signature(
        _member(op="c_allreduce_max", dtype=VarType.FP16))
    assert sig16[0][4:] == ("max", "float16")


def test_signature_descends_into_sub_blocks_in_issue_order():
    """A collective inside a cond branch appears at the PARENT op's
    position, between the outer collectives around it."""
    prog = Program()
    b = prog.global_block()
    b.create_var(name="p", shape=[1], dtype=VarType.BOOL,
                 persistable=True)
    b.create_var(name="a", shape=[4], dtype=VarType.FP32)
    b.create_var(name="z", shape=[4], dtype=VarType.FP32)
    b.append_op("c_allreduce_sum", inputs={"X": ["a"]},
                outputs={"Out": ["a"]}, attrs={"ring_id": 0})
    sub = prog._create_block()
    sub.append_op("c_allreduce_max", inputs={"X": ["a"]},
                  outputs={"Out": ["z"]}, attrs={"ring_id": 1})
    prog._rollback()
    b.append_op("cond", inputs={"Cond": ["p"]}, outputs={"Out": ["z"]},
                attrs={"true_block": sub, "false_block": sub,
                       "true_out_names": ["z"], "false_out_names": ["z"],
                       "input_names": []})
    b.append_op("c_allreduce_sum", inputs={"X": ["z"]},
                outputs={"Out": ["z"]}, attrs={"ring_id": 0})
    types = [s[0] for s in verifier.collective_signature(prog)]
    assert types == ["c_allreduce_sum", "c_allreduce_max",
                     "c_allreduce_sum"]


@pytest.mark.parametrize("mutate,field", [
    (dict(ring=1), "ring"),
    (dict(op="c_allreduce_max"), "reduce-op"),
    (dict(dtype=VarType.FP16), "dtype"),
])
def test_member_mismatch_caught(mutate, field):
    ds = shard_analysis.check_member_programs(
        [_member(), _member(**mutate)])
    assert len(ds) == 1
    assert ds[0].code == "collective-order-mismatch"
    assert ds[0].op_index == 0  # at the diverging collective


def test_member_agreement_clean_pair():
    assert shard_analysis.check_member_programs(
        [_member(), _member()]) == []


# ==========================================================================
# zero false positives over the existing program zoo
# ==========================================================================
@pytest.mark.parametrize("optimizer", ["sgd", "adam", "lamb", "momentum"])
@pytest.mark.parametrize("stage", [0, 1, 2, 3])
def test_zoo_dp_training_no_findings(optimizer, stage):
    mesh_mod.registry().clear()
    mesh_mod.init_mesh()
    _flags.set_flags({"FLAGS_dp_sharding": stage})
    for transpile in (False, True):
        with unique_name.guard():
            main, _, loss = build_mlp_dp_program(
                n_layers=3, width=16, nranks=8, optimizer=optimizer,
                transpile=transpile)
        assert shard_analysis.check_program(main, (), (loss,)) == []


_CFG = DecoderConfig(vocab_size=64, hidden=32, num_heads=4, num_layers=2,
                     max_seq_len=128)


@pytest.mark.parametrize("tp", [2, 4])
def test_zoo_serving_tp_no_findings(tp):
    for mode in ("reference", "prefill", "decode", "chunk", "verify"):
        with unique_name.guard():
            prog, feeds, fetch = build_decoder_program(_CFG, mode, tp=tp)
            get_pass("serving_tp_pass",
                     ring_id=SERVING_TP_RING_ID).apply(prog)
        assert shard_analysis.check_program(prog, feeds, fetch) == [], mode
        # tp member bodies are SPMD-identical: the member-agreement leg
        # over two builds of the same form is clean too
        with unique_name.guard():
            prog2 = build_decoder_program(_CFG, mode, tp=tp)[0]
            get_pass("serving_tp_pass",
                     ring_id=SERVING_TP_RING_ID).apply(prog2)
        assert shard_analysis.check_member_programs([prog, prog2]) == []


# ==========================================================================
# gate semantics: warn / strict / off
# ==========================================================================
def test_gate_default_warns_and_never_mutates():
    prog = _sgd_with_variant_lr()
    before = json.dumps(prog.desc_dict(), default=str)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        ds = shard_analysis.gate(prog, where="test_gate")
    assert any(d.code == "replication-required" for d in ds)
    assert any("test_gate" in str(x.message) for x in w)
    assert json.dumps(prog.desc_dict(), default=str) == before


def test_gate_strict_raises_verify_error():
    _flags.set_flags({"FLAGS_shard_safety_strict": 1})
    with pytest.raises(verifier.VerifyError) as ei:
        shard_analysis.gate(_sgd_with_variant_lr(), where="strict_gate")
    assert "replication-required" in str(ei.value)


def test_gate_off_flag_is_inert():
    _flags.set_flags({"FLAGS_shard_safety": 0})
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert shard_analysis.gate(_sgd_with_variant_lr()) == []
    assert not w


def test_shard_safety_pass_is_analysis_only():
    """The compile-pipeline pass form: same program object out, desc
    unchanged, findings in the report."""
    prog = _sgd_with_variant_lr()
    before = json.dumps(prog.desc_dict(), default=str)
    p = get_pass("shard_safety_pass", where="pass_test")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        out = p.apply(prog)
    assert out is prog
    assert json.dumps(prog.desc_dict(), default=str) == before
    codes = [d["code"] for d in p.report["diagnostics"]]
    assert "replication-required" in codes


def test_no_collectives_short_circuit():
    """Single-device programs carry no SPMD obligations: zero findings
    and no distribution-state work at all."""
    prog = Program()
    b = prog.global_block()
    b.create_var(name="x", shape=[4], dtype=VarType.FP32)
    b.create_var(name="y", shape=[4], dtype=VarType.FP32)
    b.append_op("scale", inputs={"X": ["x"]}, outputs={"Out": ["y"]},
                attrs={"scale": 2.0, "bias": 0.0,
                       "bias_after_scale": True})
    assert shard_analysis.check_program(prog) == []


# ==========================================================================
# numerics_probe_pass consumes the shared engine
# ==========================================================================
def test_numerics_probe_uses_shared_engine(monkeypatch):
    """The old private walk is gone; the probe's combine decision calls
    shard_analysis.variant_names."""
    from paddle_tpu.framework.ir import NumericsProbePass

    assert not hasattr(NumericsProbePass, "_shard_variant_names")
    assert not hasattr(NumericsProbePass, "_CLEARS")
    calls = []
    real = shard_analysis.variant_names
    monkeypatch.setattr(shard_analysis, "variant_names",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    _flags.set_flags({"FLAGS_numerics_probe": 1})
    with unique_name.guard():
        main, _, _ = build_mlp_dp_program(n_layers=2, width=8, nranks=8,
                                          optimizer="sgd", transpile=True)
    get_pass("numerics_probe_pass").apply(main)
    assert calls  # engine consulted on the collective path


# ==========================================================================
# progcheck --shard / --quick
# ==========================================================================
def test_progcheck_shard_flags_member_mismatch(tmp_path, capsys):
    import progcheck

    good = _member()
    bad = _member(ring=3)
    pa = tmp_path / "dev0.json"
    pb = tmp_path / "dev1.json"
    pa.write_bytes(good.serialize_to_string())
    pb.write_bytes(bad.serialize_to_string())
    rc = progcheck.main([str(pa), str(pb), "--shard", "--feed", "x",
                         "--fetch", "s", "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert "shard" in out
    assert any(d["code"] == "collective-order-mismatch"
               for d in out["diagnostics"])


def test_progcheck_shard_clean_pair_exits_zero(tmp_path, capsys):
    import progcheck

    pa = tmp_path / "dev0.json"
    pb = tmp_path / "dev1.json"
    pa.write_bytes(_member().serialize_to_string())
    pb.write_bytes(_member().serialize_to_string())
    rc = progcheck.main([str(pa), str(pb), "--shard", "--feed", "x",
                         "--fetch", "s", "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert out["shard"]["errors"] == 0


def test_progcheck_quick_subprocess_smoke():
    """The bounded tier-1 CI smoke: --shard --quick self-tests the
    analyzer in a fresh interpreter (clean pair clean, seeded ring and
    reduce-op mismatches caught) and exits 0."""
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "progcheck.py"),
         "--shard", "--quick", "--json"],
        capture_output=True, text=True, timeout=240,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stdout + r.stderr
    out = json.loads(r.stdout)
    assert out["quick"]["ok"] is True


# ==========================================================================
# plan_search attaches shard-safety to its report
# ==========================================================================
def test_plan_search_report_carries_shard_safety():
    from paddle_tpu.parallel import plan_search

    mesh_mod.registry().clear()
    mesh_mod.init_mesh()
    with unique_name.guard():
        main, _, loss = build_mlp_dp_program(
            n_layers=2, width=8, nranks=8, optimizer="sgd",
            transpile=True)
    plan, report = plan_search.search_plan(main, (), (loss,), ndev=8,
                                           budget_bytes=0, strict=False)
    assert report["shard_safety"] == []  # the zoo stays clean


def test_tensor_parallel_annotation_seeding():
    """Partition-rule specs seed SHARDED states (the tensor_parallel
    helper feeds the analyzer)."""
    from paddle_tpu.parallel.tensor_parallel import (annotated_shard_axes,
                                                     shard_parameter)

    prog = _member()
    b = prog.global_block()
    b.var("x").persistable = True
    shard_parameter(b.var("x"), (None, "mp"))
    assert annotated_shard_axes(prog) == {"x": (None, "mp")}
    res = shard_analysis.analyze(prog)
    assert res.state_of("x").kind == shard_analysis.SHARDED
    assert res.state_of("x").axis == "mp"
