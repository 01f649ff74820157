"""The benchmark's own arithmetic, on the CPU: percentiles and the
failed-counts-as-beyond rule, open-loop timing from the due instant, the trace
reduction on the small recorded trace, the roofline functions against
hand-worked shapes, the knee rule, and the manifest check.

In-process, no sockets, no child, no sleep that decides an assertion.
"""
import json
import math
import os
import random
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import knee as knee_lib
from benchmark.generators import open_loop
from benchmark.lib import device as device_lib
from benchmark.lib import loadgen, manifest as manifest_lib, stats
from benchmark.lib import trace as trace_lib
from benchmark.lib.watch import kernel_calls
from benchmark.rooflines import bn_act, paged_decode

HERE = manifest_lib.HERE


# ---------------------------------------------------------------------------
# percentiles
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("q,want", [(50, 5), (90, 9), (95, 10), (100, 10),
                                    (10, 1), (1, 1)])
def test_percentile_is_nearest_rank(q, want):
    assert stats.percentile(range(1, 11), q) == want


def test_percentile_failed_sort_beyond_every_value():
    values = [1.0] * 18
    # 20 samples, 2 failed: rank 18 is the last real one, rank 19 is failed
    assert stats.percentile(values, 90, failed=2) == 1.0
    assert stats.percentile(values, 95, failed=2) == math.inf
    assert stats.percentile(values, 95, failed=2, censored=7.5) == 7.5
    # a censored bound below a real value never undercuts it
    assert stats.percentile([9.0], 100, failed=1, censored=2.0) == 9.0


def test_percentile_refuses_an_empty_sample_and_a_bad_rank():
    with pytest.raises(ValueError):
        stats.percentile([], 95)
    with pytest.raises(ValueError):
        stats.percentile([1.0], 0)


def test_samples_beyond_and_spread():
    assert stats.samples_beyond(200, 95) == 10
    assert stats.samples_beyond(12, 95) == 0
    # quartiles of 1..7 (exclusive method) are 2 and 6, the median 4
    assert stats.spread([1, 2, 3, 4, 5, 6, 7]) == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# traffic plans
# ---------------------------------------------------------------------------
def _traffic(**over):
    t = {"population_seed": 0, "lead_in_s": 2.0, "drain_s": 1.0,
         "arrivals": {"process": "poisson", "rate_per_s": 20.0},
         "prompt_len": {"dist": "lognormal", "median": 30, "sigma": 0.9,
                        "min": 4, "max": 90},
         "output_len": {"dist": "uniform", "min": 2, "max": 9}}
    t.update(over)
    return t


def test_plan_same_seed_same_requests_and_a_large_seed_works():
    a = open_loop.plan(_traffic(), 2 ** 31 + 12345, 10.0, 100, 128)
    b = open_loop.plan(_traffic(), 2 ** 31 + 12345, 10.0, 100, 128)
    assert [(p.due, p.prompt, p.want) for p in a] == \
        [(p.due, p.prompt, p.want) for p in b]
    assert all(-2.0 <= p.due < 10.0 for p in a)
    assert all(4 <= len(p.prompt) <= 90 and 2 <= p.want <= 9 for p in a)


def test_plan_every_seed_offers_the_same_schedule_with_other_tokens():
    a = open_loop.plan(_traffic(), 1, 10.0, 100, 128)
    b = open_loop.plan(_traffic(), 2, 10.0, 100, 128)
    assert [(p.due, len(p.prompt), p.want) for p in a] == \
        [(p.due, len(p.prompt), p.want) for p in b]
    assert [p.prompt for p in a] != [p.prompt for p in b]
    rate = len([p for p in a if p.due >= 0]) / 10.0
    assert 14.0 < rate < 26.0          # Poisson at 20/s over 10 s


def test_plan_at_once_puts_everything_at_the_lead_in():
    t = _traffic(arrivals={"process": "at_once", "count": 17})
    plan = open_loop.plan(t, 3, 5.0, 100, 128)
    assert len(plan) == 17 and {p.due for p in plan} == {-2.0}


def test_plan_cuts_a_prompt_that_would_outgrow_the_context():
    plan = open_loop.plan(_traffic(), 5, 10.0, 100, 40)
    assert max(len(p.prompt) + p.want for p in plan) == 40
    assert all(len(p.prompt) >= 4 for p in plan)


@pytest.mark.parametrize("spec,lo,hi,median", [
    ({"dist": "lognormal", "median": 128, "sigma": 0.9, "min": 16,
      "max": 768}, 16, 768, 128),
    ({"dist": "uniform", "min": 512, "max": 960}, 512, 960, 736)])
def test_lengths_keep_to_their_limits_and_their_median(spec, lo, hi, median):
    x = open_loop.draw_lengths(spec, np.random.RandomState(0), 4000)
    assert x.min() == lo and x.max() == hi
    assert abs(np.median(x) - median) < 0.06 * median
    with pytest.raises(ValueError):
        open_loop.draw_lengths(dict(spec, dist="zipf"),
                               np.random.RandomState(0), 1)


def test_every_serving_mix_names_a_generator_that_is_a_file():
    manifest = manifest_lib.load_manifest()
    named = set()
    for w in manifest["workloads"]:
        with open(manifest_lib.traffic_file(w["traffic"])) as f:
            named.add(json.load(f).get("generator"))
    assert "open_loop" in named
    for name in named - {None}:
        assert os.path.exists(os.path.join(HERE, "generators", name + ".py"))


def _serving_mixes():
    manifest = manifest_lib.load_manifest()
    out = {}
    for w in manifest["workloads"]:
        with open(manifest_lib.traffic_file(w["traffic"])) as f:
            body = json.load(f)
        if "generator" in body:
            out[w["name"]] = body
    return out


def test_every_backlog_is_deep_enough_for_its_window():
    """The rate must never read the queue's tail: by the numbers each traffic
    file records of its last runs (``depth``), the queue still holds half of
    the count when the window closes, and what completed and what waits are
    no more than what was offered."""
    backlogs = {n: t for n, t in _serving_mixes().items()
                if t["arrivals"]["process"] == "at_once"}
    assert len(backlogs) >= 5
    for name, t in backlogs.items():
        count, depth = t["arrivals"]["count"], t["depth"]
        assert count % 100 == 0, name
        assert 2 * depth["queue_end"] >= count, name
        assert 0 < depth["completed"] <= count - depth["queue_end"], name


def test_an_open_loop_rate_lies_under_four_fifths_of_its_swept_knee():
    rated = {n: t["arrivals"] for n, t in _serving_mixes().items()
             if t["arrivals"]["process"] == "poisson"}
    assert rated
    for name, a in rated.items():
        assert 0.0 < a["rate_per_s"] <= 0.8 * a["knee_per_s"] * 1.001, name
        assert a["knee_found_on"].strip(), name


# ---------------------------------------------------------------------------
# open-loop replay against a stalled fake engine, on a fake clock
# ---------------------------------------------------------------------------
class _Event:
    def __init__(self, req_id, finished):
        self.req_id, self.token, self.finished = req_id, 0, finished


class _Handle:
    def __init__(self, p, due):
        self.req_id, self.want, self.out_tokens = p.req_id, p.want, []
        self.admitted_at = None


class _FakeEngine:
    """One request at a time, one token a step; each step costs
    ``step_s`` of the fake clock; the engine stalls for ``stall_s`` in the
    step that admits request ``stall_on``."""

    def __init__(self, clock, step_s, stall_on=None, stall_s=0.0):
        self.clock, self.step_s = clock, step_s
        self.stall_on, self.stall_s = stall_on, stall_s
        self.waiting, self.running = [], None

    def submit(self, h):
        if h.want > 50:
            raise ValueError("too long")
        self.waiting.append(h)

    def has_work(self):
        return bool(self.waiting or self.running)

    def step(self, now):
        if self.running is None:
            self.running = self.waiting.pop(0)
            self.running.admitted_at = now
            if self.running.req_id == self.stall_on:
                self.clock.t += self.stall_s
        self.clock.t += self.step_s
        h = self.running
        h.out_tokens.append(0)
        done = len(h.out_tokens) == h.want
        if done:
            self.running = None
        return [_Event(h.req_id, done)]


class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


def _replay(planned, **engine_kw):
    clock = _Clock()
    eng = _FakeEngine(clock, **engine_kw)
    raw = loadgen.replay(eng, planned, window_s=10.0, drain_s=5.0,
                         make_request=_Handle, clock=clock,
                         sleep=clock.sleep)
    return raw, loadgen.request_table(raw, lambda p: p.handle.admitted_at)


def test_replay_times_from_the_due_instant_through_a_stall():
    planned = [loadgen.Planned(0, 1.0, [1] * 4, 2),
               loadgen.Planned(1, 1.5, [1] * 4, 2),
               loadgen.Planned(2, 2.0, [1] * 4, 2)]
    raw, rows = _replay(planned, step_s=0.1, stall_on=0, stall_s=3.0)
    # request 0: admitted at 1.0, stalls 3 s, first token at 4.1
    assert rows[0]["ttft_s"] == pytest.approx(3.1)
    assert rows[0]["lag_s"] == pytest.approx(0.0)
    # request 1 was due at 1.5 but could only be submitted after the stall:
    # its time still runs from 1.5, and the generator's lateness is on record
    assert rows[1]["lag_s"] == pytest.approx(4.1 - 1.5)
    assert rows[1]["queue_wait_s"] == pytest.approx(4.2 - 1.5)
    assert rows[1]["ttft_s"] == pytest.approx(4.3 - 1.5)
    assert rows[2]["ttft_s"] == pytest.approx(4.5 - 2.0)
    assert rows[1]["mean_gap_s"] == pytest.approx(0.1)
    assert not any(r["failed"] for r in rows)
    assert raw["closed_at"] >= 10.0 and raw["ended_at"] < 15.0


def test_replay_counts_refused_and_unserved_as_failed_and_skips_lead_in():
    planned = [loadgen.Planned(0, -1.0, [1] * 4, 3),     # lead-in
               loadgen.Planned(1, 0.5, [1] * 4, 99),     # refused
               loadgen.Planned(2, 9.9, [1] * 4, 50),     # cut by the drain
               loadgen.Planned(3, 9.95, [1] * 4, 2)]     # never started
    raw, rows = _replay(planned, step_s=0.2)
    assert [r["req_id"] for r in rows] == [1, 2, 3]
    assert rows[0]["failed"] and rows[0]["refused"]
    assert not rows[1]["failed"] and rows[1]["finished"] is None
    assert rows[2]["failed"] and rows[2]["ttft_s"] is None
    assert rows[2]["waited_s"] == pytest.approx(raw["ended_at"] - 9.95)
    assert raw["ended_at"] == pytest.approx(15.0, abs=0.2)


def test_replay_sleeps_to_the_next_due_instant_not_in_slices():
    planned = [loadgen.Planned(0, 7.0, [1] * 4, 1)]
    clock = _Clock()
    sleeps = []

    def sleep(s):
        sleeps.append(s)
        clock.t += s

    eng = _FakeEngine(clock, step_s=0.1)
    loadgen.replay(eng, planned, 10.0, 0.0, _Handle, clock=clock, sleep=sleep)
    assert sleeps[0] == pytest.approx(0.0, abs=1e-9) or \
        sleeps[0] == pytest.approx(7.0)
    assert max(sleeps) >= 2.9          # one sleep to the window's close


# ---------------------------------------------------------------------------
# the window's tokens, counted as whole steps deliver them
# ---------------------------------------------------------------------------
class _ScriptEngine:
    """Steps of hand-made lengths.  Each entry of ``script`` is ``(seconds,
    events)``; an event is ``(req_id, finished)`` for a token, or
    ``("preempt", req_id)``, which resets that request's ``out_tokens`` as
    the engine does.  A step waits for the requests it names."""

    def __init__(self, clock, script):
        self.clock, self.script, self.k = clock, list(script), 0
        self.handles, self.waiting = {}, []

    def submit(self, h):
        if h.want > 50:
            raise ValueError("too long")
        self.handles[h.req_id] = h
        self.waiting.append(h)

    def has_work(self):
        """The next step runs once the requests it names have come."""
        return self.k < len(self.script) and all(
            e[1 if e[0] == "preempt" else 0] in self.handles
            for e in self.script[self.k][1])

    def step(self, now):
        seconds, events = self.script[self.k]
        self.k += 1
        self.clock.t += seconds
        out = []
        for first, second in events:
            if first == "preempt":
                self.handles[second].out_tokens.clear()
                continue
            h = self.handles[first]
            if h in self.waiting:
                self.waiting.remove(h)
            h.out_tokens.append(0)
            out.append(_Event(first, second))
        return out


def _scripted(planned, script, window_s, hook=None, drain_s=0.0):
    clock = _Clock()
    eng = _ScriptEngine(clock, script)
    return loadgen.replay(eng, planned, window_s, drain_s, _Handle,
                          clock=clock, sleep=clock.sleep, between_steps=hook)


def _read_rate(raw):
    from benchmark.end_to_end import serve_tokens_per_s
    return serve_tokens_per_s.read({"raw": raw}, None)


def test_rate_counts_whole_steps_between_the_two_straddling_steps():
    planned = [loadgen.Planned(0, -1.0, [1] * 5, 3),
               loadgen.Planned(1, -1.0, [1] * 7, 2),
               loadgen.Planned(2, -1.0, [1] * 4, 1)]
    raw = _scripted(planned, [
        (0.7, [(0, False)]),             # -1.0 .. -0.3: before the window
        (0.5, [(0, False)]),             # -0.3 ..  0.2: astride 0, left out
        (4.8, [(1, False)]),             #  0.2 ..  5.0: prompt 7 + 1
        (4.9, [(0, True), (1, True)]),   #  5.0 ..  9.9: 2
        (0.4, [(2, True)]),              #  9.9 .. 10.3: astride 10, kept
    ], window_s=10.0)
    assert raw["opened_at"] == pytest.approx(0.2)
    assert raw["closed_at"] == pytest.approx(10.3)
    got = loadgen.delivered(raw)
    assert got["delivered_tokens"] == (7 + 1) + 2 + (4 + 1)
    assert _read_rate(raw) == pytest.approx(15 / 10.1)
    # the reading before: whole requests that finished in [0, closed_at]
    assert loadgen.completed_tokens_per_s(raw) == pytest.approx(
        ((5 + 3) + (7 + 2) + (4 + 1)) / 10.3)
    note = loadgen.window_note(raw)
    assert note["delivered_tokens_per_s"] == pytest.approx(15 / 10.1)
    assert note["completed_requests"] == 3
    assert {"opened_at", "closed_at", "completed_tokens_per_s"} <= set(note)


def test_rate_opens_at_zero_and_closes_at_the_look_where_the_engine_idles():
    planned = [loadgen.Planned(0, 1.0, [1] * 6, 2)]
    raw = _scripted(planned, [(0.5, [(0, False)]), (0.5, [(0, True)])],
                    window_s=10.0)
    assert raw["opened_at"] == 0.0
    assert raw["closed_at"] == pytest.approx(10.0)
    assert _read_rate(raw) == pytest.approx((6 + 2) / 10.0)
    # no step at all: nothing delivered, and the interval is the window
    idle = _scripted([], [], window_s=4.0)
    assert idle["opened_at"] == 0.0 and _read_rate(idle) == 0.0


def _steady_schedule(stall_s):
    """130-odd requests, one at a time: a prefill step of 0.3 s that delivers
    the 299-token prompt and the first token, then ten decode steps of 1 ms:
    1000 tokens a second whatever the step.  The first starts 0.2995 s
    before the window, so the 130th completes in the step that ends at
    40.0005 s; ``stall_s`` lengthens one prefill in mid-window."""
    planned = [loadgen.Planned(i, -0.2995, [1] * 299, 11)
               for i in range(140)]
    script = []
    for i in range(140):
        script.append((0.3 + (stall_s if i == 60 else 0.0), [(i, False)]))
        script += [(0.001, [(i, k == 9)]) for k in range(10)]
    return _scripted(planned, script, window_s=40.0)


def test_rate_does_not_jump_by_a_request_where_the_closing_step_falls():
    a, b = _steady_schedule(0.0), _steady_schedule(0.02)
    # the closing steps end 10 ms apart, either side of the 130th completion
    assert a["closed_at"] == pytest.approx(40.0005)
    assert b["closed_at"] == pytest.approx(40.0105)
    assert [len(loadgen.completed(raw)) for raw in (a, b)] == [130, 129]
    new_a, new_b = _read_rate(a), _read_rate(b)
    assert new_a == pytest.approx(1000.0)
    # the stall's 20 ms of a 40 s window, and nothing else
    assert abs(new_a - new_b) / new_a < 0.001
    assert new_a - new_b == pytest.approx(1000.0 * 0.02 / 40.01, rel=0.01)
    old_a = loadgen.completed_tokens_per_s(a)
    old_b = loadgen.completed_tokens_per_s(b)
    assert old_a - old_b == pytest.approx(310 / 40.0, rel=0.05)
    assert (old_a - old_b) / old_a > 0.007


@pytest.mark.parametrize("case", ["preempted", "refused"])
def test_rate_counts_a_preempted_request_once_and_a_refused_one_never(case):
    planned = [loadgen.Planned(0, 0.5, [1] * 5, 2),
               loadgen.Planned(1, 0.5, [1] * 3, 1),
               loadgen.Planned(2, 0.5, [1] * 9, 99)]       # refused
    raw = _scripted(planned, [
        (0.5, [(0, False)]),                    # 0.5 .. 1.0: 0's first run
        (0.5, [("preempt", 0), (1, True)]),     # 1.0 .. 1.5
        (0.5, [(0, False)]),                    # 1.5 .. 2.0: 0 again
        (0.5, [(0, True)]),
    ], window_s=5.0)
    by_id = {p.req_id: p for p in raw["requests"]}
    if case == "preempted":
        assert len(by_id[0].token_times) == 3
        assert loadgen.final_stamps(by_id[0]) == pytest.approx([2.0, 2.5])
        assert loadgen.delivered(raw)["delivered_tokens"] == \
            (5 + 2) + (3 + 1)
        rows = loadgen.request_table(raw, lambda p: None)
        assert rows[0]["n_out"] == 2 and rows[0]["ttft_s"] == \
            pytest.approx(1.5)
    else:
        assert by_id[2].refused and not by_id[2].token_times
        assert loadgen.final_stamps(by_id[2]) == []
        only = dict(raw, requests=[by_id[2]])
        assert loadgen.delivered(only)["delivered_tokens"] == 0
        assert loadgen.completed_tokens_per_s(only) == 0.0


def test_chat_holds_a_latency_end_to_end_and_its_tails_per_layer():
    """What chat's users feel is bounded: the middle request's token gap.
    The tails read 4-9 % apart over runs of one tree at every rate tried
    (PERF.md section 7.1) and stay as per-layer readings; the cell is not
    among the rate's, whose reading under the knee is the offered rate."""
    manifest = manifest_lib.load_manifest()
    chat = "gpt2-small.chat-poisson"
    e2e = {m["name"]: m for m in
           manifest_lib.metrics_of(manifest, "end_to_end", chat)}
    assert set(e2e) == {"setup_s", "itl_p50_ms"}
    assert e2e["itl_p50_ms"]["better"] == "lower"
    assert 0.01 <= e2e["itl_p50_ms"]["bound"] <= 0.1
    layer = {m["name"]: m for m in
             manifest_lib.metrics_of(manifest, "per_layer", chat)}
    for name in ("ttft_p90_ms", "itl_p90_ms"):
        assert layer[name]["workloads"] == [chat]
        assert layer[name]["moves"] == "itl_p50_ms"
        assert layer[name]["source"] == "host_clock"
    ends = {m["name"] for m in manifest["end_to_end"]}
    assert all(m["moves"] in ends for m in manifest["per_layer"])
    assert all(m["moves"] != "serve_tokens_per_s" for m in layer.values())


def test_the_middle_gap_counts_failures_beyond_and_leaves_out_the_gapless():
    from benchmark.end_to_end import itl_p50_ms

    rows = [{"failed": False, "mean_gap_s": k / 1e3, "waited_s": 1.0}
            for k in range(1, 10)]
    rows.append({"failed": False, "mean_gap_s": None, "waited_s": 1.0})
    assert itl_p50_ms.read({"rows": rows}, None) == pytest.approx(5.0)
    # three failures of twelve: rank 6 of 12 is the sixth real gap
    rows += [{"failed": True, "mean_gap_s": None, "waited_s": 9.0}] * 3
    assert itl_p50_ms.read({"rows": rows}, None) == pytest.approx(6.0)
    # where the middle falls among the failed, the longest known wait
    rows += [{"failed": True, "mean_gap_s": None, "waited_s": 7.0}] * 9
    assert itl_p50_ms.read({"rows": rows}, None) == pytest.approx(9000.0)


def test_latency_note_reads_requests_and_pooled_gaps_of_the_window():
    planned = [loadgen.Planned(2, -1.0, [1] * 3, 2),      # due in the lead-in
               loadgen.Planned(0, 0.5, [1] * 5, 3),
               loadgen.Planned(1, 0.5, [1] * 3, 2)]
    raw = _scripted(planned, [
        (0.5, [(2, False)]), (0.5, [(2, True)]),          # -1.0 .. 0.0
        (0.5, [(0, False)]),                              #  0.5 .. 1.0
        (0.2, [(0, False), (1, False)]),                  #  1.0 .. 1.2
        (0.6, [(0, True), (1, True)]),                    #  1.2 .. 1.8
    ], window_s=5.0)
    rows = loadgen.request_table(raw, lambda p: None)
    assert sorted(loadgen.token_gaps(raw)) == pytest.approx([0.2, 0.6, 0.6])
    note = loadgen.latency_note(raw, rows)
    assert note["gap_p50_ms"] == pytest.approx(600.0)
    assert note["gap_mean_ms"] == pytest.approx(1400.0 / 3)
    assert note["itl_p50_ms"] == pytest.approx(400.0)      # 0.4 and 0.6
    assert note["itl_mean_ms"] == pytest.approx(500.0)
    assert note["ttft_p50_ms"] == pytest.approx(500.0)     # 0.5 and 0.7
    assert note["ttft_mean_ms"] == pytest.approx(600.0)
    assert loadgen.latency_note(dict(raw, requests=[]), []) == {}


@pytest.mark.parametrize("reader,key,want", [
    ("ttft_p90_ms", "ttft_s", 9.0), ("itl_p90_ms", "mean_gap_s", 9.0)])
def test_tail_readers_rank_failures_beyond_and_read_nothing_from_nothing(
        reader, key, want):
    import importlib

    read = importlib.import_module(f"benchmark.layer_metrics.{reader}").read
    rows = [{"failed": False, "ttft_s": k / 1e3, "mean_gap_s": k / 1e3,
             "waited_s": 1.0} for k in range(1, 11)]
    assert read({"rows": rows}, {}, None) == pytest.approx(want)
    rows[0] = {"failed": True, "ttft_s": None, "mean_gap_s": None,
               "waited_s": 7.0}
    rows[1] = dict(rows[0], waited_s=3.0)
    # 8 real and 2 failed: rank 9 of 10 is a failed one, censored at 7 s
    assert read({"rows": rows}, {}, None) == pytest.approx(7000.0)
    assert read({"rows": []}, {}, None) is None
    assert read({}, {}, None) is None


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------
def test_union_gaps_and_clip_by_hand():
    cover = trace_lib.union([(0, 4), (2, 6), (10, 12), (11, 11)])
    assert cover == [(0, 6), (10, 12)]
    assert trace_lib.gaps(cover, (0, 20)) == [(6, 10), (12, 20)]
    assert trace_lib.clip(cover, (5, 11)) == [(5, 6), (10, 11)]
    assert trace_lib.total(cover) == 8


def test_attribute_gaps_innermost_span_wins():
    idle = [(0, 100)]
    spans = [("step", 0, 80), ("submit", 10, 30)]
    got = trace_lib.attribute_gaps(idle, spans)
    assert got == {"submit": pytest.approx(20e-9),
                   "step": pytest.approx(60e-9),
                   "outside-spans": pytest.approx(20e-9)}


def _every_span_against_every_piece(pieces, spans):
    """``program_spans.attribute`` as it stood until PR 49: each piece walks
    every span.  Two minutes of a traced chat run; kept here as what the
    one-pass walk (``trace.meeting``) has to agree with."""
    out = {}
    ordered = sorted(spans, key=lambda s: s[2] - s[1])
    for piece in pieces:
        free = [piece]
        for name, a, b in ordered:
            if b <= piece[0] or a >= piece[1] or not free:
                continue
            taken = trace_lib.clip(free, (a, b))
            if taken:
                out[name] = out.get(name, 0.0) + trace_lib.total(taken) / 1e9
                free = [g for f in free for g in
                        trace_lib.gaps(trace_lib.clip([(a, b)], f), f)]
        rest = trace_lib.total(free)
        if rest:
            out["outside-spans"] = out.get("outside-spans", 0.0) + rest / 1e9
    return out


def _feed_by_hand(idle, spans):
    """Idle nanoseconds under ``feed``, the innermost span: by hand."""
    return sum(trace_lib.total(trace_lib.clip(idle, (a, b)))
               for n, a, b in spans if n == "feed") / 1e9


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_one_pass_over_pieces_and_spans_reads_what_the_product_read(seed):
    """Steps of nested spans, some of equal length, one span over the whole
    trace and one that touches no piece; pieces as a device's busy cover and
    its gaps: every second by span is the same, to the last bit."""
    from benchmark.lib import program_spans

    rnd = random.Random(seed)
    spans, ops, t = [("whole", 0, 10 ** 7), ("never", -50, -10)], [], 100
    for k in range(40):
        end = t + rnd.randint(2000, 9000)
        spans.append(("step", t, end))
        inner = sorted(rnd.sample(range(t, end), 6))
        spans += [("feed", inner[0], inner[1]), ("call", inner[2], inner[3]),
                  ("fetch", inner[2], inner[5]), ("twin", inner[4], inner[5]),
                  ("twin2", inner[4], inner[5])]
        u = t + rnd.randint(0, 500)
        while u < end + 300:
            d = rnd.randint(5, 400)
            ops.append((u, u + d))
            u += d + rnd.randint(0, 200)
        t = end + rnd.randint(0, 1000)
    window = (0, t + 500)
    busy = trace_lib.union(trace_lib.clip(ops, window))
    idle = trace_lib.gaps(busy, window)
    assert len(busy) > 500 and len(idle) > 500
    for pieces in (busy, idle, list(reversed(idle))):
        want = _every_span_against_every_piece(sorted(pieces), spans)
        assert program_spans.attribute(pieces, spans) == want
        assert set(want) >= {"step", "fetch", "whole"} and "never" not in want
    # the few harness spans, with the reader that lets a free piece grow
    few = [s for s in spans if s[0] in ("step", "feed")]
    got = trace_lib.attribute_gaps(idle, few)
    assert sum(got.values()) >= trace_lib.total(idle) / 1e9 - 1e-12
    assert got["feed"] == pytest.approx(_feed_by_hand(idle, few),
                                       rel=1e-12)


def test_meeting_hands_each_piece_the_spans_that_overlap_it_in_order():
    ordered = [("c", 40, 45), ("a", 0, 10), ("b", 5, 50), ("d", 60, 70)]
    got = dict(trace_lib.meeting([(46, 60), (0, 5), (8, 41)], ordered))
    assert got == {(0, 5): [("a", 0, 10)],
                   (8, 41): [("c", 40, 45), ("a", 0, 10), ("b", 5, 50)],
                   (46, 60): [("b", 5, 50)]}
    assert list(trace_lib.meeting([], ordered)) == []
    assert list(trace_lib.meeting([(0, 9)], [])) == [((0, 9), [])]


def test_reduce_the_recorded_trace():
    path = os.path.join(HERE, "lib", "recorded_trace.json")
    with open(path) as f:
        want = json.load(f)["expected"]
    red = trace_lib.reduce(trace_lib.load_rows(path))
    assert red["devices"] == want["devices"]
    assert red["window_s"] == pytest.approx(want["window_s"])
    assert red["busy_s"] == pytest.approx(want["busy_s"])
    assert red["idle_share"] == pytest.approx(want["idle_share"])
    # nested and parallel events are counted once in busy time ...
    summed = sum(red["ops"].values())
    assert summed >= red["busy_s_by_device"][red["devices"][0]]
    # ... and in full per name
    for name, seconds in want["ops"].items():
        assert red["ops"][name] == pytest.approx(seconds)
    assert dict(red["idle_gaps"]) == pytest.approx(want["idle_gaps"])
    assert len(red["device_ops"]) <= 10


def test_reduce_without_a_device_plane_reads_nothing():
    assert trace_lib.reduce([("/host:CPU", "t", "bench/window", 0, 10)]) == {}


# ---------------------------------------------------------------------------
# rooflines
# ---------------------------------------------------------------------------
def test_bn_act_sites_and_bytes_by_hand():
    sites = bn_act.sites(50, 224)
    assert len(sites) == 49 and sum(r for _, r in sites) == 16
    assert sites[0] == (64 * 112 * 112, False)
    # first block: 64@56x56, 64@56x56, 256@56x56 with the residual
    assert sites[1:4] == [(64 * 56 * 56, False), (64 * 56 * 56, False),
                          (256 * 56 * 56, True)]
    # first block of stage 3: the 1x1 still at 56x56, then 28x28
    assert sites[10:13] == [(128 * 56 * 56, False), (128 * 28 * 28, False),
                            (512 * 28 * 28, True)]
    assert sites[-1] == (2048 * 7 * 7, True)
    need = bn_act.needed_bytes_per_step(50, 224, 1, 2)
    plain = sum(e for e, r in sites if not r)
    resid = sum(e for e, r in sites if r)
    assert need["bn_act_fwd"] == {"bytes": 2 * (2 * plain + 3 * resid),
                                  "calls": 49}
    assert need["bn_act_bwd"]["bytes"] == 2 * (4 * plain + 5 * resid)
    assert bn_act.needed_bytes_per_step(50, 224, 128, 2)["bn_act_fwd"][
        "bytes"] == 128 * need["bn_act_fwd"]["bytes"]


def test_paged_decode_bytes_by_hand():
    # 12 heads x 64 x float32, K and V: 6144 bytes a token and layer
    per_token = 2 * 12 * 64 * 4
    assert paged_decode.needed_bytes([100, 28], per_token) == 128 * 6144


# ---------------------------------------------------------------------------
# the knee rule
# ---------------------------------------------------------------------------
def _row(rate, due_by_end, half, end):
    return {"rate_per_s": rate, "due_by_end": due_by_end,
            "queue_half": half, "queue_end": end}


def test_knee_is_the_highest_rate_below_which_all_were_sustained():
    rows = [_row(2.0, 100, 0, 0), _row(2.5, 125, 0, 1),   # one waiter: noise
            _row(3.125, 156, 4, 3),
            _row(3.9, 195, 3, 9),             # queue grows past 2 %
            _row(4.9, 245, 0, 0)]             # a lucky rate past the knee
    assert knee_lib.knee(rows) == 3.125
    assert knee_lib.knee(rows[3:]) is None
    # shrinking, but still a backlog of more than twice the allowance
    assert not knee_lib.sustained(_row(1.0, 100, 9, 5))
    assert knee_lib.sustained(_row(1.0, 100, 2, 2))
    # the sweep of PR 23, rows as measured: the knee is 3.81, not 1.95
    measured = [(1.25, 52, 0, 0), (1.5625, 70, 0, 0), (1.953125, 93, 0, 0),
                (2.44140625, 115, 0, 1), (3.0517578125, 143, 1, 0),
                (3.814697265625, 167, 4, 1), (4.76837158203125, 219, 19, 41)]
    assert knee_lib.knee([_row(*m) for m in measured]) == 3.814697265625


# ---------------------------------------------------------------------------
# kernel presence
# ---------------------------------------------------------------------------
def test_kernel_calls_reads_the_lowered_text():
    text = ('stablehlo.custom_call @tpu_custom_call(...) '
            '{kernel_name = "bn_act_fwd"} ... kernel_name = "bn_act_fwd"')
    assert kernel_calls([text, "no kernels"], ["bn_act_fwd", "bn_act_bwd"],
                        False) == {"bn_act_fwd": 2, "bn_act_bwd": 0}
    assert kernel_calls(["jit(f)/paged_decode/pallas_call"],
                        ["paged_decode"], True) == {"paged_decode": 1}


# ---------------------------------------------------------------------------
# the harness's own pieces
# ---------------------------------------------------------------------------
def test_rehearsal_block_is_laid_over_the_file():
    from benchmark.lib.harness import with_rehearsal

    body = {"depth": 50, "deployment": {"num_pages": 4096, "page_size": 16},
            "arrivals": {"process": "poisson", "rate_per_s": 3.0},
            "rehearsal": {"depth": 18, "deployment": {"num_pages": 64},
                          "arrivals": {"process": "at_once", "count": 4}}}
    got = with_rehearsal(body)
    assert got["depth"] == 18
    assert got["deployment"] == {"num_pages": 64, "page_size": 16}
    assert got["arrivals"] == {"process": "at_once", "count": 4}
    assert body["depth"] == 50 and body["deployment"]["num_pages"] == 4096


class _FakeProfiler:
    def __init__(self):
        self.calls = []
        self.profiler = self

    def ProfileOptions(self):                      # noqa: N802
        return type("O", (), {})()

    def start_trace(self, d, profiler_options=None):
        self.calls.append(("start", profiler_options.python_tracer_level))

    def stop_trace(self):
        self.calls.append(("stop",))

    def TraceAnnotation(self, name):               # noqa: N802
        calls = self.calls

        class _A:
            def __enter__(self):
                calls.append(("enter", name))

            def __exit__(self, *exc):
                calls.append(("exit", name))
        return _A()


def test_tracer_takes_the_last_seconds_of_the_window(tmp_path):
    from benchmark.lib.harness import Tracer

    fake = _FakeProfiler()
    tr = Tracer(fake, True, start_after_s=37.0, trace_s=3.0)
    for t in (1.0, 36.9):
        tr.poll(t)
    assert not tr.active and fake.calls == []
    tr.poll(37.2)
    assert tr.active and fake.calls == [("start", 0),
                                        ("enter", "bench/window")]
    tr.poll(39.0)
    assert tr.active
    tr.poll(40.3)
    assert not tr.active and fake.calls[-2:] == [("exit", "bench/window"),
                                                 ("stop",)]
    tr.stop(41.0)                       # a second stop does nothing
    assert fake.calls.count(("stop",)) == 1
    off = Tracer(fake, False, 0.0, 1.0)
    off.poll(5.0)
    assert not off.active and off.reduction() == {}


def _runner_hook(tracer, seconds):
    """What every serving runner's ``between_steps`` does with the tracer."""
    def hook(t, engine):
        if t < seconds:
            tracer.poll(t)
        else:
            tracer.stop(t)
    return hook


@pytest.mark.parametrize("last_work_ends", [8.5, 6.5])
def test_tracer_starts_and_stops_where_the_engine_idles(last_work_ends):
    """The engine runs out of work before the window closes: the profiler
    still stops at the close and, where a request comes due inside the traced
    seconds after an idle stretch, starts at that instant and not a step
    later."""
    from benchmark.lib.harness import Tracer

    fake = _FakeProfiler()
    tr = Tracer(fake, True, start_after_s=7.0, trace_s=3.0)
    n = int(round(last_work_ends / 0.5))
    planned = [loadgen.Planned(0, 0.0, [1] * 4, n),
               loadgen.Planned(1, 8.0, [1] * 4, 1)]
    script = [(0.5, [(0, k == n - 1)]) for k in range(n)]
    if last_work_ends < 8.0:
        script.append((0.4, [(1, True)]))        # 8.0 .. 8.4, then idle
    raw = _scripted(planned[:len(script) - n + 1], script, window_s=10.0,
                    hook=_runner_hook(tr, 10.0), drain_s=5.0)
    assert raw["closed_at"] == pytest.approx(10.0)
    assert tr.on_at == pytest.approx(7.0 if last_work_ends > 8.0 else 8.0)
    assert tr.off_at == pytest.approx(10.0) and not tr.active
    assert fake.calls[-2:] == [("exit", "bench/window"), ("stop",)]


def test_a_trace_without_a_device_operation_is_a_fault_line_then_an_exit(
        capsys):
    from benchmark import run as run_lib
    from benchmark.lib.harness import say

    run_lib.refuse_empty_trace(False, {}, say)
    run_lib.refuse_empty_trace(True, {"busy_s": 1.0}, say)
    assert capsys.readouterr().out == ""
    with pytest.raises(SystemExit) as stop:
        run_lib.refuse_empty_trace(True, {}, say)
    assert stop.value.code not in (0, None)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["fault"] == "the trace holds no device operation"
    assert "correct" not in line and "metrics" not in line


def test_longest_steps_and_gc_watch():
    import gc

    from benchmark.lib.harness import GcWatch, longest

    got = longest([(0.0, 0.1), (0.1, 0.2), (0.2, 3.2), (3.2, 3.3)], top=2)
    assert got["longest"][0] == [0.2, pytest.approx(3.0)]
    assert got["median_s"] == pytest.approx(0.1)
    watch = GcWatch()
    try:
        gc.collect()
        seen = watch.since(0.0)
        assert seen["collections"] >= 1 and seen["full_collections"] >= 1
        assert seen["longest_s"] <= seen["total_s"]
    finally:
        watch.close()
    assert watch._on not in gc.callbacks



class _Device:
    def __init__(self, in_use, reserved):
        self._stats = {"peak_bytes_in_use": in_use,
                       "peak_bytes_reserved": reserved}

    def memory_stats(self):
        return self._stats


def test_memory_peak_is_the_larger_field_of_the_fullest_chip():
    devices = [_Device(5, 2), _Device(1, 9), _Device(3, 3)]
    assert device_lib.memory_peak_bytes(devices) == 9
    assert device_lib.memory_stats(devices)[0] == {
        "peak_bytes_in_use": 5, "peak_bytes_reserved": 2}


def test_kv_pool_peak_reader_and_its_absence():
    from benchmark.layer_metrics import kv_pool_peak_pct

    record = {"kv": {"peak_pages": 775, "pages_total": 4096}}
    assert kv_pool_peak_pct.read(record, {}, None) == \
        pytest.approx(18.92, abs=0.01)
    assert kv_pool_peak_pct.read({}, {}, None) is None


def test_median_step_reader_ignores_a_stall_and_its_absence():
    from benchmark.layer_metrics import step_ms_p50

    # 0.1 s steps, the third stalled by 0.2 s: the rate sees it, the median
    # does not
    record = {"step_ends": [0.1, 0.2, 0.5, 0.6, 0.7]}
    assert step_ms_p50.read(record, {}, None) == pytest.approx(100.0)
    assert step_ms_p50.read({}, {}, None) is None
    assert step_ms_p50.read({"step_ends": [0.1]}, {}, None) is None
