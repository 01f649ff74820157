"""Device symbols (PR 39): the executor notes which compiled steps ran while a
span recorded, and ``profiler.device_symbols()`` reads their compiled text
into a table from instruction to the part of the model it serves.  On the
CPU: a tiny latent-attention / expert form and a tiny hybrid (KDA beside MLA)
form, each run under ``enable_profiler()``.

The persistent compile cache is off here: it keys an executable without its
metadata, so a program another tree compiled with other scopes would come back
under that tree's (``foreign`` in the table says when that happened).
"""
import dataclasses
import json
import os
import sys

import jax
import numpy as np
import pytest

from paddle_tpu import profiler
from paddle_tpu.inference.mla_decoder import (MLADecoderConfig,
                                              init_mla_weights)
from paddle_tpu.inference.serving import Request, ServingEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# vocabulary 96 (no other test's): the dataclass's tiny MLA / expert model
MLA = MLADecoderConfig(vocab_size=96)
HYBRID = MLADecoderConfig(
    vocab_size=96, hidden=64, num_heads=4, num_layers=4, first_k_dense=1,
    intermediate=128, moe_intermediate=32, n_routed_experts=8,
    experts_held=4, num_experts_per_tok=2, q_lora_rank=0, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, rope=False,
    rms_norm_eps=1e-5, routed_scaling_factor=2.446,
    mixers=("kda", "kda", "kda", "mla"), kda_heads=4, kda_head_dim=16,
    kda_gate_rank=16, max_seq_len=128)
PARTS = {"mla": {"embed", "mla_part", "moe_part", "dense_ffn", "head"},
         "hybrid": {"embed", "mla_part", "kda_part", "moe_part", "dense_ffn",
                    "head"}}


@pytest.fixture(autouse=True)
def _own_compiles():
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    profiler.reset_profiler()
    yield
    profiler.reset_profiler()
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def serve(cfg, prompts=(5, 9), new_tokens=4):
    eng = ServingEngine(cfg=cfg, weights=init_mla_weights(cfg, 0),
                        kv_dtype="float32", page_size=8, max_batch=4,
                        token_budget=64, num_pages=32)
    rng = np.random.RandomState(1)
    for i, n in enumerate(prompts):
        eng.submit(Request(i, rng.randint(0, 96, size=n).tolist(),
                           new_tokens))
    eng.run_to_completion()
    return eng


def _arrays_under(obj, depth=6):
    """Every jax.Array reachable from ``obj`` through containers."""
    if isinstance(obj, jax.Array):
        return [obj]
    if depth == 0:
        return []
    if isinstance(obj, dict):
        obj = list(obj.values())
    if isinstance(obj, (list, tuple, set)):
        return [a for o in obj for a in _arrays_under(o, depth - 1)]
    return []


@pytest.mark.parametrize("which,cfg", [("mla", MLA), ("hybrid", HYBRID)])
def test_every_instruction_of_a_noted_step_has_a_part(which, cfg):
    profiler.enable_profiler()
    eng = serve(cfg)
    profiler.disable_profiler(print_summary=False)
    notes = list(profiler._PROGRAMS.values())
    # one note an entry however many calls: both prompts share a bucket, the
    # decode steps one batch and table width
    assert sorted(n["program"] for n in notes) == ["decode", "prefill"]
    calls = {n["program"]: n["calls"] for n in notes}
    assert calls["prefill"] == 2 and calls["decode"] >= 3
    # shapes, types and placements: no array
    for n in notes:
        assert _arrays_under(n["abstract"]) == []
        leaves = jax.tree.leaves(n["abstract"])
        assert leaves and all(isinstance(x, jax.ShapeDtypeStruct)
                              for x in leaves)
    # the note outlives the engine: the readers run after the runner returned
    del eng
    tables = profiler.device_symbols()
    assert [t["program"] for t in tables] == [n["program"] for n in notes]
    for t in tables:
        assert t.get("error") is None, t
        assert t["module"] == "jit_pt_" + t["program"]
        assert t["foreign"] == 0
        assert t["calls"] == calls[t["program"]]
        assert t["feed"]["tokens"].startswith("int32[")
        named = [i for i in t["instructions"]
                 if i["op_name"] and i["opcode"] != "parameter"]
        assert len(named) > 100
        assert {i["part"] for i in named} == PARTS[which]
        assert all(i["via"] is None for i in named)
        assert not any("unscoped" in i["scopes"] for i in t["instructions"])
        # the op's type follows its part, as run_op opens them
        for i in named:
            assert i["scopes"][0] == i["part"] and i["scopes"][1] == i["op"]
    # a second call reads and parses nothing again
    before = [n["table"] for n in profiler._PROGRAMS.values()]
    again = profiler.device_symbols()
    assert all(a is b for a, b in zip(
        before, (n["table"] for n in profiler._PROGRAMS.values())))
    assert [t["read_s"] for t in again] == [t["read_s"] for t in tables]
    # and the store empties with the profiler
    profiler.reset_profiler()
    assert profiler._PROGRAMS == {} and profiler.device_symbols() == []


def test_nothing_recording_notes_nothing():
    assert not profiler.is_profiler_enabled()
    serve(MLA)
    assert profiler._PROGRAMS == {}
    assert profiler.get_events() == []


def test_reading_waits_for_the_session_to_end(tmp_path):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        serve(MLA, prompts=(5,), new_tokens=2)
        # a JAX session alone records, as in the benchmark's traced window
        assert len(profiler._PROGRAMS) == 2
        with pytest.raises(RuntimeError, match="after the profiler session"):
            profiler.device_symbols()
    finally:
        jax.profiler.stop_trace()
    assert len(profiler.device_symbols()) == 2


def test_scopes_of_an_op_name():
    path = ("jit(pt_prefill)/jit(main)/moe_part/moe_experts/moe_dispatch/"
            "jit(_take)/transpose(jvp(a/b))/while/body/closed_call/gather")
    assert profiler.scopes_of(path) == ["moe_part", "moe_experts",
                                        "moe_dispatch"]
    assert profiler.scopes_of("gather") == []


HLO = """HloModule jit_pt_decode, is_scheduled=true

%fused_computation.1 (param_0: f32[8,128]) -> f32[8,128] {
  %param_0 = f32[8,128]{1,0} parameter(0)
  ROOT %mul.1 = f32[8,128]{1,0} multiply(%param_0, %param_0), metadata={op_name="jit(pt_decode)/mla_part/rms_norm/mul"}
}

%fused_computation.2 (param_0.1: f32[8,128]) -> f32[8,128] {
  %param_0.1 = f32[8,128]{1,0} parameter(0)
  ROOT %add.9 = f32[8,128]{1,0} add(%param_0.1, %param_0.1), metadata={op_name="jit(pt_decode)/moe_part/elementwise_add/add"}
}

%body.3 (p: (s32[], f32[8,128])) -> (s32[], f32[8,128]) {
  %p = (s32[], f32[8,128]{1,0}) parameter(0)
  %fusion.7 = f32[8,128]{1,0:T(8,128)} fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(pt_decode)/mla_part/rms_norm/while/body/mul"}
  ROOT %tuple.1 = (s32[], f32[8,128]{1,0}) tuple(%p, %fusion.7)
}

%cond.4 (p.1: (s32[], f32[8,128])) -> pred[] {
  %p.1 = (s32[], f32[8,128]{1,0}) parameter(0)
  ROOT %lt.1 = pred[] constant(true)
}

ENTRY %main.9 (x: f32[8,128], w: bf16[128,128]) -> f32[8,128] {
  %x = f32[8,128]{1,0} parameter(0), metadata={op_name="feed['x']"}
  %w = bf16[128,128]{1,0} parameter(1), metadata={op_name="ro['w']"}
  %copy-start.1 = (bf16[128,128]{1,0:S(1)}, bf16[128,128]{1,0}, u32[]) copy-start(%w)
  %copy-done.1 = bf16[128,128]{1,0:S(1)} copy-done(%copy-start.1)
  %moe_gmm.5 = bf16[8,128]{1,0:T(8,128)(2,1)} custom-call(%x, %copy-done.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(pt_decode)/moe_part/moe_experts/jit(_moe_gmm_call)/pallas_call"}
  %fusion.8 = f32[8,128]{1,0} fusion(%moe_gmm.5), kind=kLoop, calls=%fused_computation.2
  %moe_rows_in.6 = bf16[8,128]{1,0:T(8,128)(2,1)} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(pt_decode)/moe_part/moe_experts/moe_dispatch/jit(_moe_rows_in_call)/moe_rows_in/pallas_call"}
  %moe_combine.7 = f32[8,1,128]{2,1,0:T(1,128)} custom-call(%moe_rows_in.6), custom_call_target="tpu_custom_call", metadata={op_name="jit(pt_decode)/moe_part/moe_experts/moe_combine/jit(_moe_combine_call)/moe_combine/pallas_call"}
  %while.2 = (s32[], f32[8,128]{1,0}) while(%fusion.8), condition=%cond.4, body=%body.3, metadata={op_name="jit(pt_decode)/mla_part/rms_norm/while"}
  %custom-call.3 = f32[4]{0} custom-call(), custom_call_target="AllocateBuffer"
  ROOT %gte.1 = f32[8,128]{1,0} get-tuple-element(%while.2), index=1
}
"""


def test_a_compiled_modules_text_as_a_table(monkeypatch):
    from paddle_tpu.ops import registry

    monkeypatch.setattr(registry, "PARTS", {"mla_part", "moe_part"})
    table = profiler.hlo_symbols(HLO)
    assert table["module"] == "jit_pt_decode"
    rows = {r["name"]: r for r in table["instructions"]}
    # the entry, the loop's body and condition; not a fusion's inside
    assert set(rows) == {"x", "w", "copy-start.1", "copy-done.1",
                         "moe_gmm.5", "fusion.8", "moe_rows_in.6",
                         "moe_combine.7", "while.2", "custom-call.3",
                         "gte.1", "p", "fusion.7", "tuple.1", "p.1", "lt.1"}
    kernel = rows["moe_gmm.5"]
    assert (kernel["opcode"], kernel["shape"]) == ("custom-call",
                                                   "bf16[8,128]")
    assert kernel["scopes"] == ["moe_part", "moe_experts", "moe_gmm"]
    assert (kernel["part"], kernel["op"], kernel["via"]) == \
        ("moe_part", "moe_experts", None)
    # the kernels around the grouped matmuls, under the scopes of the XLA
    # they took the place of: the same part and op, so "the part less its
    # ``moe_gmm`` kernels" goes on holding them
    assert rows["moe_rows_in.6"]["scopes"] == [
        "moe_part", "moe_experts", "moe_dispatch", "moe_rows_in"]
    assert rows["moe_combine.7"]["scopes"] == [
        "moe_part", "moe_experts", "moe_combine", "moe_combine"]
    for name in ("moe_rows_in.6", "moe_combine.7"):
        assert (rows[name]["part"], rows[name]["op"]) == \
            ("moe_part", "moe_experts")
    inner = rows["fusion.7"]
    assert (inner["shape"], inner["part"], inner["op"]) == \
        ("f32[8,128]", "mla_part", "rms_norm")
    # a fusion the compiler left bare: the part its inside names
    assert (rows["fusion.8"]["part"], rows["fusion.8"]["via"],
            rows["fusion.8"]["op"]) == ("moe_part", "fused",
                                        "elementwise_add")
    # data movement it added: the part of what reads it, a chain deep
    assert (rows["copy-done.1"]["part"], rows["copy-done.1"]["via"]) == \
        ("moe_part", "users")
    assert (rows["copy-start.1"]["part"], rows["copy-start.1"]["via"]) == \
        ("moe_part", "users")
    assert rows["copy-start.1"]["shape"] == "bf16[128,128]"
    # what reads one part's result
    assert (rows["gte.1"]["part"], rows["gte.1"]["via"]) == \
        ("mla_part", "operands")
    # nothing near it: no part; a parameter never takes one
    assert rows["custom-call.3"]["part"] is None
    assert rows["custom-call.3"]["op_name"] is None
    assert rows["w"]["part"] is None and rows["x"]["part"] is None


def test_device_table_joins_rows_to_the_noted_steps(monkeypatch):
    tables = [
        {"program": "prefill", "instructions": [
            {"name": "fusion.1", "shape": "f32[64,8]", "part": "moe_part",
             "op": "moe_experts"},
            {"name": "fusion.2", "shape": "s32[1]", "part": "embed",
             "op": "reshape2"}]},
        {"program": "decode", "instructions": [
            {"name": "fusion.1", "shape": "f32[4,8]", "part": "mla_part",
             "op": "rms_norm"},
            {"name": "fusion.2", "shape": "s32[1]", "part": "head",
             "op": "arg_max"}]}]
    monkeypatch.setattr(profiler, "device_symbols", lambda: tables)
    dev = "/device:TPU:0"
    rows = [
        # as the benchmark's trace.read_rows keeps a row
        (dev, "XLA Ops", "fusion f32[64,8]|fusion.1", 0, 3_000_000),
        (dev, "XLA Ops", "fusion f32[64,8]|fusion.1", 5_000_000, 2_000_000),
        # as the profile names an event
        (dev, "XLA Ops", "%fusion.1 = f32[4,8]{1,0} fusion(f32[4,8]{1,0} "
         "%p.1), kind=kLoop", 9_000_000, 1_000_000),
        (dev, "XLA Ops", "fusion s32[1]|fusion.2", 11_000_000, 500_000),
        (dev, "XLA Ops", "copy f32[7]|copy.9", 12_000_000, 250_000),
        ("/host:CPU", "python", "bench/window", 0, 20_000_000)]
    assert profiler.device_table(rows) == [
        {"program": "prefill", "part": "moe_part", "op": "moe_experts",
         "seconds": pytest.approx(0.005), "events": 2},
        {"program": "decode", "part": "mla_part", "op": "rms_norm",
         "seconds": pytest.approx(0.001), "events": 1},
        # the two steps disagree on fusion.2 s32[1]; no step holds copy.9
        {"program": None, "part": None, "op": None,
         "seconds": pytest.approx(0.00075), "events": 2}]


def test_trace_report_prints_the_device_table(tmp_path, capsys):
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import trace_report

    path = tmp_path / "trace.json"
    profiler._write_chrome_trace(
        [{"name": "executor_run", "cat": "host", "ts": 0.0, "dur": 0.01,
          "tid": 1, "depth": 0, "parent": None}], str(path),
        [{"program": "prefill", "part": "moe_part", "op": "moe_experts",
          "seconds": 0.005, "events": 2}])
    assert trace_report.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "Device: program" in out
    assert [l.split() for l in out.splitlines()
            if l.startswith("prefill")] == [
        ["prefill", "moe_part", "moe_experts", "2", "5.000"]]
    rep = json.loads(next(l for l in out.splitlines()
                          if l.startswith("TRACE="))[len("TRACE="):])
    assert rep["device"][0]["part"] == "moe_part"
    # a trace without one prints as before
    profiler._write_chrome_trace([], str(path))
    assert trace_report.main([str(path)]) == 0
    assert "Device: program" not in capsys.readouterr().out
