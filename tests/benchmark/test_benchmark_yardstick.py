"""The benchmark's own arithmetic, on the CPU: percentiles and the
failed-counts-as-beyond rule, open-loop timing from the due instant, the trace
reduction on the small recorded trace, the roofline functions against
hand-worked shapes, the knee rule, and the manifest check.

In-process, no sockets, no child, no sleep that decides an assertion.
"""
import json
import math
import os
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
