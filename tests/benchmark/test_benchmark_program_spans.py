"""The join of the program's spans with the device trace, on the fixture laid
out by hand (``benchmark/lib/recorded_program_spans.json``): the alignment of
the two clocks, each new reader, and each reader's answer where there is
nothing to read.  Every expected number is worked out below from the fixture's
layout, in milliseconds on the trace's clock.

train: window 0-400.  bench/exe.run 10-130, 140-260, 270-390; the program's
executor/step begins 0.02 / 0.03 / 0.04 ms after the harness span and ends as
much before its end, its perf_counter 1000 s ahead of the trace's clock.  In a
step: feed 10, executor_run 10 (bind 1, call 9), writeback 1, fetch the rest.
Device 0 busy 40-120, 165-250, 300-385; device 1 all through.

serve: window 0-450.  bench/step 10-140, 150-290, 300-440; engine/step begins
0.01 / 0.02 / 0.03 ms after, clock 500 s ahead.  Steps 1 and 3: schedule 1,
prefill 50 (feed_build 2, prefill 48: executor/step with feed 1, run 2,
writeback 0.5, fetch 44.5), emit 1, decode (feed_build 3, decode_batch), emit
1; step 2: schedule 1, decode, emit 1.  Device 0 busy 15-61.5, 67-138,
156-288, 305-350, 357-438.
"""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import manifest as manifest_lib
from benchmark.lib import program_spans
from benchmark.lib import trace as trace_lib

HERE = manifest_lib.HERE
NEW_READERS = ("exec_host_ms_p50", "idle_before_call_pct",
               "idle_after_call_pct", "engine_host_ms_p50",
               "prefill_device_share_pct", "ir_pass_s", "jax_trace_lower_s")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "lib", "recorded_program_spans.json")) as f:
        body = json.load(f)
    return {kind: {"rows": [tuple(r) for r in body[kind]["rows"]],
                   "events": sorted(body[kind]["events"],
                                    key=lambda e: e["ts"])}
            for kind in ("train", "serve")}


def _reader(name):
    import importlib

    return importlib.import_module(f"benchmark.layer_metrics.{name}")


@pytest.fixture
def as_the_program(monkeypatch, recorded):
    """The readers read the fixture's events as the program's record."""
    def use(kind):
        monkeypatch.setattr(program_spans, "program_events",
                            lambda: recorded[kind]["events"])
        rows = recorded[kind]["rows"]
        return {}, dict(trace_lib.reduce(rows), rows=rows)
    return use


# ---------------------------------------------------------------------------
# the alignment
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind,step,offset", [
    ("train", "executor/step", -1000.00003),   # median of -.00002 -.00003 -.00004
    ("serve", "engine/step", -500.00002)])
def test_alignment_is_the_median_difference_of_the_starts(recorded, kind,
                                                          step, offset):
    found = program_spans.align(recorded[kind]["rows"],
                                recorded[kind]["events"])
    assert found["step"] == step and found["steps"] == 3
    assert found["offset_s"] == pytest.approx(offset, abs=1e-9)
    # quartiles of three differences 0.01 ms apart: the outer two
    assert found["error_ms"] == pytest.approx(0.02, abs=1e-6)
    assert found["worst_ms"] == pytest.approx(0.02, abs=1e-6)


def test_no_alignment_where_steps_do_not_pair(recorded):
    rows, events = recorded["train"]["rows"], recorded["train"]["events"]
    short = [e for e in events if not (e["name"] == "executor/step"
                                       and e["args"]["step"] == 42)]
    assert program_spans.align(rows, short) is None
    assert program_spans.analyse(rows, short) is None
    assert program_spans.align([r for r in rows if "exe.run" not in r[2]],
                               events) is None


# ---------------------------------------------------------------------------
# idle before and after the call
# ---------------------------------------------------------------------------
def test_idle_split_by_whether_the_step_is_issued(recorded):
    found = program_spans.analyse(**recorded["train"])
    # idle: 0-40, 120-165, 250-300, 385-400 = 150 of 400
    assert found["idle_share"] == pytest.approx(0.375)
    # issued (call's start to step's end, 0.03 early by the alignment):
    # 20.99-129.95, 151.00-259.94, 281.01-389.93; of the idle inside them:
    # 19.01 + 9.95 + 14.00 + 9.94 + 18.99 + 4.93 = 76.82
    assert found["idle_after_call_share"] == pytest.approx(76.82 / 400)
    assert found["idle_before_call_share"] == pytest.approx(73.18 / 400)
    assert found["idle_after_call_share"] + found["idle_before_call_share"] \
        == pytest.approx(found["idle_share"])


def test_idle_and_busy_seconds_by_innermost_program_span(recorded):
    found = program_spans.analyse(**recorded["train"])
    idle = found["idle_s_by_span"]
    want = {"outside-spans": 40.18, "executor/feed": 30.0,
            "executor/bind": 3.0, "executor/call": 27.0,
            "executor/writeback": 3.0, "executor/fetch": 46.82}
    assert set(idle) == set(want)
    for name, ms in want.items():
        assert idle[name] == pytest.approx(ms / 1e3), name
    assert sum(idle.values()) == pytest.approx(0.150)
    assert found["busy_s_by_span"] == {"executor/fetch": pytest.approx(0.250)}
    assert found["step_self_share_max"] == pytest.approx(0.0, abs=1e-9)


def test_attribute_counts_every_piece_once():
    """Three spans in one gap: trace.attribute_gaps lets a free piece grow
    past its end there; this one cuts the span to the piece first."""
    gap = [(0, 100)]
    spans = [("a", 0, 10), ("b", 50, 60), ("c", 70, 80)]
    assert program_spans.attribute(gap, spans) == {
        "a": 10 / 1e9, "b": 10 / 1e9, "c": 10 / 1e9,
        "outside-spans": 70 / 1e9}


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------
def test_train_readers_on_the_fixture(as_the_program):
    record, reduction = as_the_program("train")
    # executor/step less its fetch: 10 + 10 + 1 in each of the three steps
    assert _reader("exec_host_ms_p50").read(record, reduction, None) \
        == pytest.approx(21.0)
    before = _reader("idle_before_call_pct").read(record, reduction, None)
    after = _reader("idle_after_call_pct").read(record, reduction, None)
    assert before == pytest.approx(18.295) and after == pytest.approx(19.205)
    idle = _reader("device_idle_pct").read(record, reduction, None)
    assert before + after == pytest.approx(idle)
    assert _reader("engine_host_ms_p50").read(record, reduction, None) is None
    assert _reader("prefill_device_share_pct").read(record, reduction,
                                                    None) is None


def test_serve_readers_on_the_fixture(as_the_program):
    record, reduction = as_the_program("serve")
    # engine/step less the fetches inside it: 129.98 - 114.98, 139.96 -
    # 131.46, 139.94 - 124.94 = 15.0, 8.5, 15.0
    assert _reader("engine_host_ms_p50").read(record, reduction, None) \
        == pytest.approx(15.0)
    # busy 46.5 + 71 + 132 + 45 + 81 = 375.5; inside the prefill spans
    # (10.99-60.99 and 301.01-351.01 once aligned): 45.99 + 45
    assert _reader("prefill_device_share_pct").read(record, reduction, None) \
        == pytest.approx(100 * 90.99 / 375.5)
    # no top-level executor/step: the engine's are its own
    assert _reader("exec_host_ms_p50").read(record, reduction, None) is None


@pytest.mark.parametrize("name", NEW_READERS[:5])
def test_span_readers_read_nothing_from_a_program_without_spans(
        monkeypatch, recorded, name):
    """The parent commit's program records no span: no value, no error."""
    monkeypatch.setattr(program_spans, "program_events", lambda: [])
    rows = recorded["train"]["rows"]
    reduction = dict(trace_lib.reduce(rows), rows=rows)
    assert _reader(name).read({}, reduction, None) is None


@pytest.mark.parametrize("name,want", [
    ("exec_host_ms_p50", 21.0), ("idle_before_call_pct", None),
    ("idle_after_call_pct", None), ("prefill_device_share_pct", None)])
def test_without_a_device_plane_only_host_readers_read(as_the_program, name,
                                                       want):
    """The CPU rehearsal: the trace reduces to nothing."""
    record, _ = as_the_program("train")
    got = _reader(name).read(record, {}, None)
    assert got is None if want is None else got == pytest.approx(want)


def test_counter_readers(monkeypatch):
    series = {
        "executor_compile_build_s": [{"labels": {}, "count": 3, "sum": 4.5}],
        "executor_jax_trace_seconds_total": [{"labels": {}, "value": 2.0}],
        "executor_jax_lower_seconds_total": [{"labels": {}, "value": 0.25}]}
    monkeypatch.setattr(program_spans, "counter_series", series.get)
    assert _reader("ir_pass_s").read({}, {}, None) == 4.5
    assert _reader("jax_trace_lower_s").read({}, {}, None) == 2.25
    # the parent's program has the histogram and not the two counters
    del series["executor_jax_lower_seconds_total"]
    assert _reader("jax_trace_lower_s").read({}, {}, None) is None
    del series["executor_compile_build_s"]
    assert _reader("ir_pass_s").read({}, {}, None) is None


def test_counter_series_reads_the_programs_registry():
    from paddle_tpu.utils import telemetry

    telemetry.counter("program_spans_probe_total", "probe").inc(3)
    assert program_spans.counter_series("program_spans_probe_total")[0][
        "value"] == 3
    assert program_spans.counter_series("no_such_family") is None


def test_the_note_line_is_printed_once(as_the_program, capsys):
    record, reduction = as_the_program("train")
    first = program_spans.of_run(record, reduction)
    again = program_spans.of_run(record, reduction)
    assert first is again
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert len(lines) == 1
    note = lines[0]["program_spans"]
    assert note["alignment"]["error_ms"] == pytest.approx(0.02, abs=1e-6)
    assert note["idle_s_by_span"][0][0] == "executor/fetch"   # the largest


@pytest.mark.parametrize("lost,says", [
    ("a step", "NO ALIGNMENT"), ("the device plane", "no device plane")])
def test_the_note_says_why_the_joined_readers_read_nothing(
        monkeypatch, recorded, capsys, lost, says):
    rows, events = recorded["train"]["rows"], recorded["train"]["events"]
    if lost == "a step":
        events = [e for e in events if not (e["name"] == "executor/step"
                                            and e["args"]["step"] == 42)]
    else:
        rows = [r for r in rows if not trace_lib.device_ids([r])]
    monkeypatch.setattr(program_spans, "program_events", lambda: events)
    # as the harness hands it over: empty without a device plane
    assert program_spans.of_run({}, trace_lib.reduce(rows)) is None
    note = json.loads(capsys.readouterr().out)["program_spans"]
    assert says in note
    if lost == "a step":
        assert "'exe.run': (3, 2)" in note


# ---------------------------------------------------------------------------
# the manifest
# ---------------------------------------------------------------------------
def test_the_grown_manifest_passes_and_names_the_new_metrics():
    manifest = manifest_lib.load_manifest()
    assert manifest_lib.check(manifest) == []
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    readers = {manifest_lib.reader_of(n) for n in by_name}
    assert set(NEW_READERS) <= readers
    resnet = ["resnet50.train-b128", "resnet50.dp4-b512"]
    for name in ("exec_host_ms_p50", "idle_before_call_pct",
                 "idle_after_call_pct"):
        assert by_name[name]["workloads"] == resnet
        assert by_name[name]["moves"] == "train_samples_per_s"
        assert by_name[name]["source"] == "program_span"
    assert by_name["engine_host_ms_p50.chat"]["moves"] == "itl_p50_ms"
    assert by_name["prefill_device_share_pct.chat"]["moves"] == "itl_p50_ms"
    for name in ("engine_host_ms_p50.backlog",
                 "prefill_device_share_pct.backlog"):
        assert by_name[name]["moves"] == "serve_tokens_per_s"
        assert by_name[name]["workloads"] == ["gpt2-small.prompt-backlog"]
    for name in ("ir_pass_s", "jax_trace_lower_s"):
        assert by_name[name]["source"] == "program_counter"
        assert by_name[name]["moves"] == "setup_s"
        assert len(by_name[name]["workloads"]) == 4
    assert all(by_name[n]["better"] == "lower" for n in by_name
               if manifest_lib.reader_of(n) in NEW_READERS)
