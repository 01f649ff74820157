"""The program's spans (PR 24): one primitive, ``profiler.RecordEvent``, two
sinks.  Under a JAX profiler session one ``Executor.run``, one data-parallel
step and one ``ServingEngine.step`` each yield their span tree, both in
``profiler.get_events()`` and as ``pt/...`` events of the written
``.xplane.pb``; with neither the session nor the profiler on nothing is
recorded; the completed-event ring is bounded; the compile counters move on a
cache miss and not for a plain ``jax.jit`` outside the executor."""
import collections
import glob
import os

import jax
import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.fluid as fluid
from paddle_tpu import profiler
from paddle_tpu.framework.scope import Scope, scope_guard
from paddle_tpu.inference.serving import (DecoderConfig, Request,
                                          ServingEngine)
from paddle_tpu.utils import telemetry as tm

CFG = DecoderConfig(vocab_size=64, hidden=32, num_heads=4, num_layers=2,
                    max_seq_len=128)


@pytest.fixture(autouse=True)
def _clean_profiler():
    profiler.reset_profiler()
    yield
    profiler.reset_profiler()


def _mlp():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [8])
        y = fluid.layers.data("y", [1])
        h = fluid.layers.fc(x, 16, act="relu")
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(fluid.layers.fc(h, 1), y))
        fluid.optimizer.SGD(0.1).minimize(loss)
    feed = {"x": np.ones((8, 8), np.float32), "y": np.ones((8, 1), np.float32)}
    return main, startup, loss, feed


def _engine(**kw):
    return ServingEngine(CFG, num_pages=32, page_size=8, max_batch=4,
                         token_budget=64, prefill_bucket_min=8, **kw)


class _Session:
    """A JAX profiler session without the Python tracer."""

    def __init__(self, path):
        self.path = str(path)

    def __enter__(self):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.path, profiler_options=options)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()

    def annotations(self):
        """name -> stats of every ``pt/`` event of the written file."""
        from jax.profiler import ProfileData

        found = {}
        pb = sorted(glob.glob(os.path.join(
            self.path, "plugins", "profile", "*", "*.xplane.pb")))[-1]
        for plane in ProfileData.from_file(pb).planes:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("pt/"):
                        found.setdefault(ev.name, []).append(dict(ev.stats))
        return found


def _tree(events):
    """[(name, parent)] in the order the spans began."""
    return [(e["name"], e["parent"])
            for e in sorted(events, key=lambda e: e["ts"])
            if "ph" not in e]              # spans, not instants/counters


def _children_cover(events, name):
    """Every span called ``name``: its children lie inside it."""
    for outer in (e for e in events if e["name"] == name):
        kids = [e for e in events if e["parent"] == name
                and e["depth"] == outer["depth"] + 1
                and outer["ts"] <= e["ts"]
                and e["ts"] + e["dur"] <= outer["ts"] + outer["dur"] + 1e-9]
        assert kids, name


EXECUTOR_TREE = [("executor/step", None),
                 ("executor/feed", "executor/step"),
                 ("executor_run", "executor/step"),
                 ("executor/bind", "executor_run"),
                 ("executor/call", "executor_run"),
                 ("executor/writeback", "executor/step"),
                 ("executor/fetch", "executor/step")]


def test_executor_run_span_tree_under_a_jax_session(tmp_path):
    main, startup, loss, feed = _mlp()
    exe = fluid.Executor(pt.CPUPlace())
    with scope_guard(Scope()):
        exe.run(startup)
        assert profiler.get_events() == []          # no session: nothing
        with _Session(tmp_path) as session:
            exe.run(main, feed=feed, fetch_list=[loss])   # a cache miss
            exe.run(main, feed=feed, fetch_list=[loss])   # a hit
        exe.run(main, feed=feed, fetch_list=[loss])
    events = profiler.get_events()
    first = EXECUTOR_TREE[:1] + [("executor/compile", "executor/step")] \
        + EXECUTOR_TREE[1:]
    assert _tree(events) == first + EXECUTOR_TREE    # and none after stop
    steps = [e for e in events if e["name"] == "executor/step"]
    assert [s["args"]["program"] for s in steps] == ["main", "main"]
    assert steps[1]["args"]["step"] == steps[0]["args"]["step"] + 1
    feeds = [e for e in events if e["name"] == "executor/feed"]
    assert feeds[0]["args"] == {"bytes": 8 * 8 * 4 + 8 * 4, "arrays_cast": 0}
    _children_cover(events, "executor/step")
    notes = session.annotations()
    assert {"pt/" + n for n, _ in first} <= set(notes)
    assert notes["pt/executor/step"][1]["program"] == "main"
    assert notes["pt/executor/feed"][0]["bytes"] == 8 * 8 * 4 + 8 * 4


def test_dp_step_span_tree_on_two_virtual_devices(tmp_path):
    main, startup, loss, feed = _mlp()
    exe = fluid.Executor(pt.CPUPlace())
    prog = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name, places=[pt.CPUPlace(), pt.CPUPlace()])
    with scope_guard(Scope()):
        exe.run(startup)
        exe.run(prog, feed=feed, fetch_list=[loss])
        with _Session(tmp_path) as session:
            exe.run(prog, feed=feed, fetch_list=[loss])
    events = profiler.get_events()
    assert _tree(events) == [
        ("executor/step", None), ("dp/lookup", "executor/step"),
        ("executor/feed", "executor/step"), ("executor/bind", "executor/step"),
        ("executor/call", "executor/step"), ("dp/handle", "executor/step"),
        ("executor/writeback", "executor/step"),
        ("executor/fetch", "executor/step")]
    step = next(e for e in events if e["name"] == "executor/step")
    assert step["args"]["program"] == "dp_main"
    # a session step: the state is the step before's, nothing is placed
    assert next(e for e in events if e["name"] == "executor/bind")[
        "args"] == {"arrays": 0}
    assert {"pt/dp/lookup", "pt/dp/handle", "pt/executor/step"} \
        <= set(session.annotations())
    # the compiled step says which program it is
    jitted, *specs = prog.__dict__["_last_exec"]
    assert "jit_pt_dp_main" in jitted.lower(*specs).as_text()


def test_dp_last_exec_is_abstract_and_kept_across_session_steps():
    """`_last_exec` is the step's call handle with abstract arguments:
    built when the entry first runs, the same objects after any number
    of steps that bind the same shapes (nothing is rebuilt a step),
    holding no live buffer, and enough to compile the step's text,
    gradient all-reduce included."""
    main, startup, loss, feed = _mlp()
    exe = fluid.Executor(pt.CPUPlace())
    prog = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name, places=[pt.CPUPlace(), pt.CPUPlace()])
    scope = Scope()
    with scope_guard(scope):
        exe.run(startup)
        exe.run(prog, feed=feed, fetch_list=[loss])
        first = prog.__dict__["_last_exec"]
        for _ in range(4):
            exe.run(prog, feed=feed, fetch_list=[loss])
        last = prog.__dict__["_last_exec"]
        assert len(last) == len(first) and all(
            a is b for a, b in zip(first, last))
        jitted, *specs = last
        leaves = jax.tree_util.tree_leaves(specs)
        assert len(leaves) > 4 and all(
            type(leaf) is jax.ShapeDtypeStruct for leaf in leaves)
        assert "all-reduce" in jitted.lower(*specs).compile().as_text()
        # a walk keeps them too while it binds the same shapes, with the
        # session off as well as after a scope write
        scope.set("@poke", np.zeros(1, np.float32))
        exe.run(prog, feed=feed, fetch_list=[loss])
        assert prog.__dict__["_last_exec"] is first


def test_engine_step_span_tree_admission_and_decode(tmp_path):
    eng = _engine()
    eng.submit(Request("a", [3, 4, 5, 6, 7], 4))
    eng.step(0.0)                       # compiles prefill and decode
    eng.submit(Request("b", [9, 8, 7], 4))
    with _Session(tmp_path) as session:
        eng.step(1.0)                   # admits b, decodes a and b
    events = profiler.get_events()
    names = [n for n, _ in _tree(events)]
    top = [(n, p) for n, p in _tree(events)
           if p in (None, "engine/step")]
    assert top == [("engine/step", None), ("engine/schedule", "engine/step"),
                   ("engine/schedule", "engine/step"),
                   ("engine/prefill", "engine/step"),
                   ("engine/emit", "engine/step"),
                   ("engine/schedule", "engine/step"),
                   ("engine/decode", "engine/step"),
                   ("engine/emit", "engine/step")]
    by = {}
    for e in events:
        by.setdefault(e["name"], []).append(e)
    assert by["engine/step"][0]["args"] == {"step": 2, "running": 1,
                                            "waiting": 1}
    assert by["engine/prefill"][0]["args"] == {
        "req": "b", "prompt_tokens": 3, "bucket": 8}
    assert by["engine/decode"][0]["args"] == {
        "batch": 2, "padded_batch": 2, "table_width": 1}
    assert by["prefill"][0]["parent"] == "engine/prefill"
    assert by["decode_batch"][0]["parent"] == "engine/decode"
    assert {e["parent"] for e in by["engine/feed_build"]} == {
        "engine/prefill", "engine/decode"}
    # exe.run under the pinned spans holds the executor's own tree
    assert sorted(e["parent"] for e in by["executor/step"]) == [
        "decode_batch", "prefill"]
    assert {s["args"]["program"] for s in by["executor/step"]} == {
        "prefill", "decode"}
    assert names.count("executor/fetch") == 2
    for name in ("engine/step", "engine/prefill", "engine/decode"):
        _children_cover(events, name)
    notes = session.annotations()
    assert {"pt/engine/step", "pt/engine/schedule", "pt/engine/prefill",
            "pt/engine/feed_build", "pt/prefill", "pt/engine/decode",
            "pt/decode_batch", "pt/engine/emit", "pt/executor/step"} \
        <= set(notes)
    assert notes["pt/engine/decode"][0]["batch"] == 2


def test_nothing_is_recorded_with_session_and_profiler_off():
    main, startup, loss, feed = _mlp()
    exe = fluid.Executor(pt.CPUPlace())
    eng = _engine()
    with scope_guard(Scope()):
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
    out = eng.generate([[3, 4, 5], [6, 7]], 3)
    assert all(len(o) == 3 for o in out)
    assert profiler.get_events() == [] and profiler.dropped_events() == 0
    span = profiler.RecordEvent("idle")
    with span:
        pass
    assert (span.begin, span.end, span.recording) == (None, None, False)
    with profiler.RecordEvent("stamped", timed=True) as span:
        pass
    assert span.end >= span.begin and profiler.get_events() == []


def test_a_traced_request_takes_its_walls_from_the_engine_spans():
    """utils/tracing.py is on, no profiler: the request's prefill and
    decode spans carry the stamps of engine/prefill and engine/decode."""
    from paddle_tpu.utils import flags, tracing

    saved = dict(flags._flags)
    flags.set_flags({"trace_requests": 1})
    try:
        tracing.reset()
        eng = _engine()
        eng.generate([[3, 4, 5, 6]], 3)
        trace = tracing.store().traces()[-1]
    finally:
        flags._flags.clear()
        flags._flags.update(saved)
        tracing.reset()
    prefill = trace.spans_named("prefill")[0]
    decodes = trace.spans_named("decode_step")
    assert prefill.wall1 > prefill.wall0
    assert len(decodes) == 2
    assert all(d.wall1 > d.wall0 >= prefill.wall1 for d in decodes)
    assert profiler.get_events() == []


def test_the_ring_drops_the_oldest_and_counts_it(monkeypatch):
    monkeypatch.setattr(profiler, "_EVENTS", collections.deque(maxlen=4))
    profiler.enable_profiler("All")
    assert profiler.get_events() == []           # enable keeps the ring
    for i in range(7):
        with profiler.RecordEvent(f"e{i}", args={"i": i}):
            pass
    profiler.disable_profiler(print_summary=False)
    events = profiler.get_events()
    assert [e["name"] for e in events] == ["e3", "e4", "e5", "e6"]
    assert [e["args"]["i"] for e in events] == [3, 4, 5, 6]
    assert profiler.dropped_events() == 3
    profiler.reset_profiler()
    assert profiler.dropped_events() == 0


def test_parent_and_depth_follow_the_thread_stack():
    profiler.enable_profiler("All")
    with profiler.RecordEvent("a"):
        with profiler.RecordEvent("b", cat="serving") as b:
            b.set(n=1)
            with profiler.RecordEvent("c"):
                pass
        with profiler.RecordEvent("d"):
            pass
    profiler.disable_profiler(print_summary=False)
    got = {e["name"]: (e["parent"], e["depth"]) for e in
           profiler.get_events()}
    assert got == {"a": (None, 0), "b": ("a", 1), "c": ("b", 2),
                   "d": ("a", 1)}
    assert next(e for e in profiler.get_events()
                if e["name"] == "b")["args"] == {"n": 1}


def _counter(name):
    family = tm.snapshot().get(name)
    return family["series"][0]["value"] if family else 0.0


def test_compile_counters_move_on_a_miss_and_not_for_a_plain_jit():
    names = ("executor_jax_trace_seconds_total",
             "executor_jax_lower_seconds_total")
    main, startup, loss, feed = _mlp()
    exe = fluid.Executor(pt.CPUPlace())
    with scope_guard(Scope()):
        exe.run(startup)
        before = [_counter(n) for n in names]
        exe.run(main, feed=feed, fetch_list=[loss])        # miss: traces
        after_miss = [_counter(n) for n in names]
        assert all(b > a for a, b in zip(before, after_miss))
        # a plain jit of the user's, outside any step: left out
        jax.jit(lambda a: a * 3 + 1)(np.arange(5.0)).block_until_ready()
        assert [_counter(n) for n in names] == after_miss
        builds = tm.snapshot()["executor_compile_build_s"]["series"][0]
        assert builds["count"] >= 1 and builds["sum"] > 0


@pytest.mark.parametrize("mode,label", [
    ("prefill", "pt_prefill"), ("decode", "pt_decode"),
    ("chunk", "pt_chunk"), ("verify", "pt_verify"),
    ("reference", "pt_reference")])
def test_serving_programs_carry_their_form_as_label(mode, label):
    from paddle_tpu.executor import program_label
    from paddle_tpu.inference.gpt2_decoder import build_decoder_program

    prog, _feeds, _fetch = build_decoder_program(CFG, mode)
    assert "pt_" + program_label(prog) == label


def test_the_compiled_step_is_named_after_its_program():
    main, startup, loss, feed = _mlp()
    exe = fluid.Executor(pt.CPUPlace())
    with scope_guard(Scope()) as _:
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
    compiled = [c for c in exe._cache.values() if c.donatable]
    assert compiled and all(c.fn.__name__ == "pt_main" for c in compiled)
    assert all(c.raw_fn.__name__ == "pt_main" for c in compiled)


def test_handles_follow_the_flag_and_a_cleared_registry():
    from paddle_tpu.utils import flags

    handles = tm.Handles(n=("counter", "handles_probe_total", "probe"),
                         idle=("counter", "handles_idle_total", "untouched"))
    handles.current().n.inc()
    assert _counter("handles_probe_total") == 1.0
    # made at its first use, as the by-name factories made it
    assert "handles_idle_total" not in tm.snapshot()
    flags.set_flags({"telemetry": False})
    try:
        assert handles.current().n is tm.NOOP
    finally:
        flags.set_flags({"telemetry": True})
    tm.registry().clear()
    handles.current().n.inc(2)
    assert _counter("handles_probe_total") == 2.0
