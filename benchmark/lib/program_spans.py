"""The program's own spans, joined to the device trace.

Inside one call of the harness (``bench/exe.run`` around ``Executor.run``,
``bench/step`` around ``ServingEngine.step``) the program names what it does:
``executor/step`` with ``executor/feed``, ``executor/bind``, ``executor/call``,
``executor/fetch`` …, ``engine/step`` with ``engine/schedule``,
``engine/prefill``, ``engine/decode``, ``engine/emit`` (the program's
``profiler.RecordEvent``, recorded while the tracer's session is active).  The
readers take the completed spans from ``paddle_tpu.profiler.get_events()`` in
this process; they are on ``time.perf_counter``, the trace's rows on the
profiler's clock.

**The alignment.**  Each traced harness span encloses exactly one program step
span, so the k-th ``bench/exe.run`` of the rows and the k-th top-level
``executor/step`` of the record are the same call seen on both clocks.  The
offset is the median over the traced steps of (harness start - program start);
the interquartile distance of those differences is printed as the error of the
alignment (the harness enters its span a few microseconds before the program
enters its own; gaps on the device are milliseconds).  ``trace.read_rows``
admits only ``bench/`` events; once it admits the program's ``pt/`` events,
which lie in the same file on the trace's clock, this alignment goes (PERF.md
section 7).

A program without these spans (the parent commit of the PR that brought them)
records nothing: every reader here then returns ``None``.  Without a device
plane (the CPU rehearsal) the trace-joined readers return ``None`` and the
host-only ones still read.
"""
from __future__ import annotations

import json
import statistics
from typing import Dict, List, Optional, Sequence, Tuple

from . import trace as trace_lib

#: harness span -> the program's step span it encloses
STEP_OF = {"exe.run": "executor/step", "step": "engine/step"}
#: from the start of this child to the end of its ``executor/step`` the step
#: is issued: the host has handed the device its work and waits or tidies up
ISSUED_FROM = "executor/call"

Span = Tuple[str, int, int]


def program_events() -> List[dict]:
    """The program's completed duration spans (no instants, no counters),
    oldest first; empty where the program has no such record."""
    try:
        from paddle_tpu import profiler
    except ImportError:
        return []
    events = getattr(profiler, "get_events", lambda: [])()
    return sorted((e for e in events if e.get("ph") is None
                   and "parent" in e), key=lambda e: e["ts"])


def counter_series(name: str) -> Optional[List[dict]]:
    """The series of one family of the program's telemetry registry, or
    ``None`` where the program has no such family."""
    try:
        from paddle_tpu.utils import telemetry
    except ImportError:
        return None
    family = telemetry.snapshot().get(name)
    return None if family is None else family["series"]


def steps_named(events: Sequence[dict], name: str) -> List[dict]:
    """The outermost spans of that name (an ``executor/step`` under an
    ``engine/step`` is the engine's, not a step of its own)."""
    return [e for e in events if e["name"] == name and e["parent"] is None]


def inside(events: Sequence[dict], outer: dict,
           depth: Optional[int] = None) -> List[dict]:
    """The spans of ``outer``'s thread that lie within it; with ``depth``,
    only those that many levels below it."""
    a, b = outer["ts"], outer["ts"] + outer["dur"]
    return [e for e in events if e is not outer and e["tid"] == outer["tid"]
            and e["depth"] > outer["depth"] and e["ts"] >= a
            and e["ts"] + e["dur"] <= b
            and (depth is None or e["depth"] == outer["depth"] + depth)]


def self_share(events: Sequence[dict], step: dict) -> float:
    """The share of ``step`` that none of its children covers."""
    covered = sum(e["dur"] for e in inside(events, step, depth=1))
    return 1.0 - covered / step["dur"] if step["dur"] else 0.0


def host_ms_p50(events: Sequence[dict], step_name: str) -> Optional[float]:
    """Median over the recorded steps of the step span less the
    ``executor/fetch`` time inside it: what the host spends apart from
    waiting on the device."""
    took = []
    for step in steps_named(events, step_name):
        waits = sum(e["dur"] for e in inside(events, step)
                    if e["name"] == "executor/fetch")
        took.append(step["dur"] - waits)
    return 1e3 * statistics.median(took) if took else None


def align(rows: Sequence[trace_lib.Row], events: Sequence[dict]
          ) -> Optional[dict]:
    """The offset (seconds) that puts a ``perf_counter`` stamp of the program
    on the trace's clock, from the harness spans that enclose the program's
    steps; ``None`` where the two do not pair one to one."""
    for harness_name, step_name in STEP_OF.items():
        outer = sorted((a, b) for n, a, b in trace_lib.spans(rows)
                       if n == harness_name)
        steps = steps_named(events, step_name)
        if not outer:
            continue
        if len(outer) != len(steps):
            return None
        diffs = [a / 1e9 - s["ts"] for (a, _b), s in zip(outer, steps)]
        error = 0.0
        if len(diffs) >= 2:
            q1, _, q3 = statistics.quantiles(diffs, n=4)
            error = q3 - q1
        return {"offset_s": statistics.median(diffs), "error_ms": 1e3 * error,
                "worst_ms": 1e3 * (max(diffs) - min(diffs)),
                "steps": len(steps), "step": step_name}
    return None


def on_trace_clock(events: Sequence[dict], offset_s: float) -> List[Span]:
    return [(e["name"], round((e["ts"] + offset_s) * 1e9),
             round((e["ts"] + e["dur"] + offset_s) * 1e9)) for e in events]


def issued_intervals(events: Sequence[dict], offset_s: float
                     ) -> List[trace_lib.Interval]:
    """For every ``executor/step``: from the start of its ``executor/call``
    to the step's end."""
    out = []
    for step in (e for e in events if e["name"] == "executor/step"):
        calls = [e for e in inside(events, step)
                 if e["name"] == ISSUED_FROM]
        if calls:
            out.append((round((calls[0]["ts"] + offset_s) * 1e9),
                        round((step["ts"] + step["dur"] + offset_s) * 1e9)))
    return out


def _within(cover: Sequence[trace_lib.Interval],
            regions: Sequence[trace_lib.Interval]) -> int:
    """Nanoseconds of the disjoint ``cover`` that fall inside ``regions``."""
    return sum(trace_lib.total(trace_lib.clip(cover, r))
               for r in trace_lib.union(regions))


def attribute(intervals: Sequence[trace_lib.Interval],
              spans: Sequence[Span]) -> Dict[str, float]:
    """Seconds of the disjoint ``intervals`` by the span that covers them:
    the innermost, i.e. shortest, where several do; ``outside-spans`` where
    none does.  (``trace.attribute_gaps`` is for a few harness spans: where
    three or more spans meet one gap its free pieces can grow past their end
    and count twice; here each span is cut to the piece before it is taken
    out of it.)"""
    out: Dict[str, float] = {}
    ordered = sorted(spans, key=lambda s: s[2] - s[1])
    for piece, over in trace_lib.meeting(intervals, ordered):
        free = [piece]
        for name, a, b in over:
            if not free:
                break
            taken = trace_lib.clip(free, (a, b))
            if taken:
                out[name] = out.get(name, 0.0) + trace_lib.total(taken) / 1e9
                free = [g for f in free for g in
                        trace_lib.gaps(trace_lib.clip([(a, b)], f), f)]
        rest = trace_lib.total(free)
        if rest:
            out["outside-spans"] = out.get("outside-spans", 0.0) + rest / 1e9
    return out


def analyse(rows: Sequence[trace_lib.Row], events: Sequence[dict],
            window: Optional[trace_lib.Interval] = None) -> Optional[dict]:
    """Everything the trace-joined readers take, or ``None`` where there is
    no device plane, no program span or no alignment."""
    devices = trace_lib.device_ids(rows)
    if not devices or not events:
        return None
    found = align(rows, events)
    if found is None:
        return None
    window = window or trace_lib.window_of(rows)
    window_ns = window[1] - window[0]
    busy = trace_lib.union(trace_lib.clip(
        trace_lib.device_intervals(rows, devices[0]), window))
    idle = trace_lib.gaps(busy, window)
    spans = on_trace_clock(events, found["offset_s"])
    issued = issued_intervals(events, found["offset_s"])
    idle_after = _within(idle, issued)
    prefills = [(a, b) for n, a, b in spans if n == "engine/prefill"]
    busy_ns = trace_lib.total(busy)
    steps = steps_named(events, found["step"])
    selfs = [self_share(events, s) for s in steps]
    return {
        "alignment": found,
        "idle_share": trace_lib.total(idle) / window_ns,
        "idle_after_call_share": idle_after / window_ns,
        "idle_before_call_share":
            (trace_lib.total(idle) - idle_after) / window_ns,
        "prefill_device_share": _within(busy, prefills) / busy_ns
        if prefills and busy_ns else None,
        "idle_s_by_span": attribute(idle, spans),
        "busy_s_by_span": attribute(busy, spans),
        "step_self_share_median": statistics.median(selfs),
        "step_self_share_max": max(selfs),
    }


def of_run(record: dict, reduction: dict) -> Optional[dict]:
    """``analyse`` for this run, computed once and kept on the record; the
    first call prints the run's note line: the alignment, idle seconds and
    device-busy seconds by program span, and the step span's self time."""
    if "program_spans" not in record:
        events = program_events()
        found = analyse(reduction["rows"], events, reduction["window"]) \
            if reduction else None
        record["program_spans"] = found
        if found is not None:
            print(json.dumps({"program_spans": {
                **found,
                "idle_s_by_span": _sorted(found["idle_s_by_span"]),
                "busy_s_by_span": _sorted(found["busy_s_by_span"])}}),
                flush=True)
        elif events:
            print(json.dumps({"program_spans": _why_not(reduction, events),
                              "events": len(events)}), flush=True)
    return record["program_spans"]


def _why_not(reduction: dict, events: Sequence[dict]) -> str:
    """Why the trace-joined readers return nothing though the program
    recorded spans: said aloud, so that a lost alignment is not read as a
    program without spans."""
    rows = reduction["rows"] if reduction else []
    if not trace_lib.device_ids(rows):
        return "no device plane; host-only readers still read"
    names = [n for n, _a, _b in trace_lib.spans(rows)]
    pairs = {h: (names.count(h), len(steps_named(events, s)))
             for h, s in STEP_OF.items()}
    return ("NO ALIGNMENT though the device plane is there: traced harness "
            "spans against the program's steps (each pair has to be equal) "
            f"{pairs}; every trace-joined metric is left out")


def _sorted(table: Dict[str, float]) -> List[list]:
    return [[n, s] for n, s in sorted(table.items(), key=lambda kv: -kv[1])]
