"""Phase-breakdown report over a unified chrome trace.

The profiler's merged timeline (host executor events, serving-scheduler
decisions, RPC spans, chaos injections — one pid lane each, see
paddle_tpu/profiler.py LANES) is great in Perfetto and useless in a
terminal.  This tool turns a trace file into the terminal view: one
summary row per lane, the top events by total time inside each, and a
stable one-line ``TRACE={json}`` (the ``SERVING=``/``BENCH=``
convention) so the driver can diff phase breakdowns across rounds.
Where the session also traced a device, the file carries the profiler's
``deviceTable`` (seconds by program, part of the model and op type:
``profiler.device_table``) and the report prints it below the lanes.

Usage:
  python tools/trace_report.py TRACE.json [--top N] [--json]
  python tools/trace_report.py --quick     # bounded self-contained smoke

Exit codes (progcheck convention): 0 = report produced; 1 = --quick
smoke found the merged trace structurally wrong (a lane missing); 2 =
the trace file is truncated / invalid JSON / not a chrome trace.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


class TraceInvalid(Exception):
    """The file is not a loadable chrome trace (truncated mid-write,
    wrong format, events missing required fields)."""


def load_trace(path: str) -> dict:
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError) as e:
        raise TraceInvalid(f"{path}: not loadable JSON ({e})") from e
    if not isinstance(data, dict) or not isinstance(
            data.get("traceEvents"), list):
        raise TraceInvalid(f"{path}: no traceEvents list (not a chrome "
                           f"trace)")
    for i, e in enumerate(data["traceEvents"]):
        if not isinstance(e, dict) or "ph" not in e:
            raise TraceInvalid(f"{path}: event #{i} is not a phased "
                               f"trace event")
        if e["ph"] == "X" and not ("name" in e and "ts" in e
                                   and "dur" in e):
            raise TraceInvalid(f"{path}: complete event #{i} missing "
                               f"name/ts/dur")
        if e["ph"] == "C" and not ("name" in e and "ts" in e
                                   and isinstance(e.get("args"), dict)):
            raise TraceInvalid(f"{path}: counter event #{i} missing "
                               f"name/ts/args")
    return data


def _counter_value(args: dict):
    """The scalar a counter sample carries: the ``bytes`` series (the
    memory lane's convention) or the first numeric arg."""
    if "bytes" in args:
        return float(args["bytes"])
    for v in args.values():
        if isinstance(v, (int, float)):
            return float(v)
    return 0.0


def report(trace: dict, top: int = 10) -> dict:
    """Aggregate per lane: event counts, total ms, top names by total
    duration, instant-marker counts.  Lane names come from the
    ``process_name`` metadata the profiler writes (``lane:host`` etc.);
    unnamed pids fall back to ``pid<N>``."""
    events = trace["traceEvents"]
    lane_of = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            name = (e.get("args") or {}).get("name", "")
            lane_of[e["pid"]] = name[5:] if name.startswith("lane:") \
                else (name or f"pid{e['pid']}")
    lanes: dict = {}
    counter_samples: dict = {}  # (lane, name) -> [(ts, value, budget)]
    t_min, t_max = float("inf"), float("-inf")
    n_events = 0
    for e in events:
        ph = e.get("ph")
        if ph not in ("X", "i", "C"):
            continue
        lane = lane_of.get(e.get("pid", 0), f"pid{e.get('pid', 0)}")
        row = lanes.setdefault(lane, {
            "events": 0, "total_ms": 0.0, "by_name": {}, "instants": {}})
        n_events += 1
        ts = float(e.get("ts", 0.0))
        t_min = min(t_min, ts)
        if ph == "C":
            args = e.get("args") or {}
            counter_samples.setdefault((lane, e["name"]), []).append(
                (ts, _counter_value(args),
                 float(args.get("budget_bytes", 0.0))))
            t_max = max(t_max, ts)
            continue
        if ph == "i":
            row["instants"][e["name"]] = \
                row["instants"].get(e["name"], 0) + 1
            t_max = max(t_max, ts)
            continue
        dur_ms = float(e["dur"]) / 1e3
        t_max = max(t_max, ts + float(e["dur"]))
        row["events"] += 1
        row["total_ms"] += dur_ms
        r = row["by_name"].setdefault(e["name"], {"calls": 0,
                                                  "total_ms": 0.0})
        r["calls"] += 1
        r["total_ms"] += dur_ms
    # counter (ph "C") series: the memory lane's modeled live-bytes
    # timeline and friends — peak, mean, and time spent over 80% of the
    # recorded budget (sample k holds its value until sample k+1)
    for (lane, name), samples in counter_samples.items():
        samples.sort(key=lambda s: s[0])
        values = [v for _, v, _ in samples]
        budget = max((b for _, _, b in samples), default=0.0)
        over_ms = None
        if budget > 0 and len(samples) > 1:
            over_us = 0.0
            for (ts0, v, _), (ts1, _, _) in zip(samples, samples[1:]):
                if v >= 0.8 * budget:
                    over_us += ts1 - ts0
            over_ms = round(over_us / 1e3, 6)
        row = lanes[lane].setdefault("counters", {})
        row[name] = {
            "samples": len(values),
            "peak": max(values) if values else 0.0,
            "mean": (sum(values) / len(values)) if values else 0.0,
            **({"budget": budget,
                "time_over_80pct_budget_ms": over_ms}
               if budget > 0 else {}),
        }
    for row in lanes.values():
        row["total_ms"] = round(row["total_ms"], 6)
        row["by_name"] = dict(sorted(
            row["by_name"].items(),
            key=lambda kv: -kv[1]["total_ms"])[:top])
        for r in row["by_name"].values():
            r["total_ms"] = round(r["total_ms"], 6)
    rep = {
        "n_events": n_events,
        "span_ms": (round((t_max - t_min) / 1e3, 6)
                    if n_events else 0.0),
        "lanes": dict(sorted(lanes.items())),
    }
    if trace.get("deviceTable"):
        # the device's seconds by program, part and op type, as the
        # profiler joined the device profile to its compiled steps
        # (profiler.device_table); written where a session traced a device
        rep["device"] = trace["deviceTable"][:top]
    return rep


def validate_request_lane(trace: dict, top: int = 5) -> dict:
    """Structural validation of the per-request tracing lane (r17):
    spans must NEST inside their parents, every non-root parent id
    must exist in the same trace (no orphans), and every span event
    must carry its trace/span args.  Also summarizes the top-N slowest
    requests by TTFT (root-span ``ttft_s`` attr).  Used by ``--quick``
    and by the default report whenever the lane is present (exit 2 on
    malformed)."""
    events = trace["traceEvents"]
    lane_pid = None
    for e in events:
        if (e.get("ph") == "M" and e.get("name") == "process_name"
                and (e.get("args") or {}).get("name") == "lane:request"):
            lane_pid = e["pid"]
    spans = ([e for e in events
              if e.get("ph") == "X" and e.get("pid") == lane_pid]
             if lane_pid is not None else [])
    by_trace: dict = {}
    malformed = []
    for e in spans:
        a = e.get("args") or {}
        tid_, sid = a.get("trace"), a.get("span")
        if not tid_ or not sid:
            malformed.append(
                f"span event {e.get('name')!r} missing trace/span args")
            continue
        by_trace.setdefault(tid_, {})[sid] = e
    orphans, nest_bad, open_parents, tops = [], [], [], []
    EPS = 5.0  # µs: clock-read ordering slack
    for tid_, ss in by_trace.items():
        # spans are emitted at span END: a still-open parent (an
        # in-flight request when the profiler stopped) is legitimately
        # absent.  Once the trace's ROOT is present the request
        # finished and every referenced parent must have been emitted
        # — a missing one is then a real orphan.
        has_root = any(not (e.get("args") or {}).get("parent")
                       for e in ss.values())
        for sid, e in ss.items():
            parent = (e.get("args") or {}).get("parent") or ""
            if parent:
                pe = ss.get(parent)
                if pe is None:
                    (orphans if has_root else open_parents).append(
                        f"{tid_}:{sid} parent {parent} "
                        + ("missing" if has_root else "still open"))
                elif (e["ts"] < pe["ts"] - EPS
                      or e["ts"] + e.get("dur", 0.0)
                      > pe["ts"] + pe.get("dur", 0.0) + EPS):
                    nest_bad.append(
                        f"{tid_}:{sid} [{e['name']}] outside parent "
                        f"{parent} [{pe['name']}]")
            if e["name"] == "request":
                a = e.get("args") or {}
                tops.append({
                    "trace": tid_, "req": a.get("req", ""),
                    "ttft_s": (float(a["ttft_s"])
                               if "ttft_s" in a else None),
                    "tokens": a.get("tokens"),
                    "wall_ms": round(e.get("dur", 0.0) / 1e3, 3),
                })
    with_ttft = [t for t in tops if t["ttft_s"] is not None]
    tops = sorted(with_ttft, key=lambda r: -r["ttft_s"])[:top] \
        or tops[:top]
    return {
        "present": lane_pid is not None,
        "traces": len(by_trace),
        "spans": len(spans),
        "orphan_spans": orphans,
        "open_parent_spans": open_parents,  # in-flight capture: not an error
        "nesting_violations": nest_bad,
        "malformed": malformed,
        "top_ttft": tops,
    }


def request_lane_ok(val: dict) -> bool:
    return not (val["orphan_spans"] or val["nesting_violations"]
                or val["malformed"])


def format_table(rep: dict) -> str:
    lines = [f"{'Lane':<10} {'Events':>8} {'Total(ms)':>12}  Top events"]
    for lane, row in rep["lanes"].items():
        tops = ", ".join(
            f"{n} ({r['total_ms']:.2f}ms x{r['calls']})"
            for n, r in list(row["by_name"].items())[:3])
        inst = ("  [" + ", ".join(f"{n} x{c}"
                                  for n, c in row["instants"].items())
                + "]") if row["instants"] else ""
        ctr = ""
        if row.get("counters"):
            parts = []
            for n, c in row["counters"].items():
                s = f"{n}: peak {c['peak'] / (1 << 20):.2f}MB"
                if c.get("time_over_80pct_budget_ms") is not None:
                    s += (f", {c['time_over_80pct_budget_ms']:.3f}ms "
                          f"over 80% budget")
                parts.append(s)
            ctr = "  {" + "; ".join(parts) + "}"
        lines.append(f"{lane:<10} {row['events']:>8} "
                     f"{row['total_ms']:>12.3f}  {tops}{inst}{ctr}")
    lines.append(f"span: {rep['span_ms']:.3f} ms over "
                 f"{rep['n_events']} events")
    if rep.get("device"):
        lines.append(f"{'Device: program':<18} {'part':<12} {'op':<26} "
                     f"{'Events':>8} {'Total(ms)':>12}")
        for r in rep["device"]:
            lines.append(
                f"{r['program'] or '-':<18} {r['part'] or '-':<12} "
                f"{r['op'] or '-':<26} {r['events']:>8} "
                f"{r['seconds'] * 1e3:>12.3f}")
    req = rep.get("requests")
    if req and req.get("present"):
        lines.append(
            f"request lane: {req['traces']} traces / {req['spans']} "
            f"spans, {len(req['orphan_spans'])} orphans, "
            f"{len(req['nesting_violations'])} nesting violations")
        for t in req["top_ttft"]:
            ttft = ("-" if t["ttft_s"] is None
                    else f"{t['ttft_s']:.5f}s")
            lines.append(f"  slowest by TTFT: req {t['req']} "
                         f"ttft={ttft} tokens={t['tokens']} "
                         f"wall={t['wall_ms']:.3f}ms [{t['trace']}]")
    return "\n".join(lines)


def run_quick(tmpdir: str) -> int:
    """Self-contained smoke for CI: produce a real merged trace (host
    lane from the executor, serving lane from a tiny engine, plus rpc /
    chaos markers), then require this tool to load it and find every
    lane.  Bounded: the decoder is minimal and the trace is tiny."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu import profiler
    from paddle_tpu.inference.serving import (DecoderConfig, Request,
                                              ServingEngine)
    from paddle_tpu.utils import flags as _flags
    from paddle_tpu.utils import tracing

    # request lane (r17): trace the engine run so the per-request span
    # tree lands in the merged file and the validator has work to do
    _flags.set_flags({"trace_requests": 1})
    tracing.reset()
    path = os.path.join(tmpdir, "quick_trace.json")
    profiler.enable_profiler("All")
    # host lane: one tiny program through the executor
    import paddle_tpu.fluid as fluid

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [4])
        out = fluid.layers.mean(fluid.layers.fc(x, 4))
    exe = pt.Executor(pt.CPUPlace())
    exe.run(startup)
    exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
            fetch_list=[out.name])
    # serving lane: a two-request continuous-batching run
    cfg = DecoderConfig(vocab_size=32, hidden=16, num_heads=2,
                        num_layers=1, max_seq_len=32)
    eng = ServingEngine(cfg, num_pages=16, page_size=4,
                        prefill_bucket_min=4)
    for i in range(2):
        eng.submit(Request(i, [1 + i, 2, 3], max_new_tokens=2))
    eng.run_to_completion()
    # rpc + chaos lanes: representative markers (the full PS round trip
    # is covered by tests/test_telemetry.py's merged-trace test)
    with profiler.record_event("rpc:ping", cat="rpc"):
        pass
    profiler.instant_event("chaos:none", cat="chaos")
    profiler.disable_profiler(profile_path=path, print_summary=False)

    data = load_trace(path)
    rep = report(data)
    val = validate_request_lane(data)
    rep["requests"] = val
    print(format_table(rep))
    print("TRACE=" + json.dumps(rep, sort_keys=True))
    missing = [lane for lane in ("host", "serving", "rpc", "chaos",
                                 "memory", "request")
               if lane not in rep["lanes"]]
    if missing:
        print(f"FAIL: lanes missing from merged trace: {missing}",
              file=sys.stderr)
        return 1
    if not rep["lanes"]["serving"]["instants"]:
        print("FAIL: serving lane carries no scheduler decisions",
              file=sys.stderr)
        return 1
    ctr = rep["lanes"]["memory"].get("counters", {})
    if not any(c.get("peak", 0) > 0 for c in ctr.values()):
        print("FAIL: memory lane carries no modeled live-bytes "
              "counters", file=sys.stderr)
        return 1
    if not val["traces"] or not val["top_ttft"]:
        print("FAIL: request lane carries no complete request traces",
              file=sys.stderr)
        return 1
    if not request_lane_ok(val):
        print(f"FAIL: request lane malformed: "
              f"orphans={val['orphan_spans']} "
              f"nesting={val['nesting_violations']} "
              f"malformed={val['malformed']}", file=sys.stderr)
        return 1
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", nargs="?", help="chrome-trace JSON file")
    ap.add_argument("--top", type=int, default=10,
                    help="events per lane in the breakdown")
    ap.add_argument("--json", action="store_true",
                    help="machine output only (the TRACE= line)")
    ap.add_argument("--quick", action="store_true",
                    help="bounded self-contained smoke (CI)")
    args = ap.parse_args(argv)
    if args.quick:
        import tempfile

        with tempfile.TemporaryDirectory() as td:
            return run_quick(td)
    if not args.trace:
        ap.error("need a trace file (or --quick)")
    try:
        data = load_trace(args.trace)
        rep = report(data, args.top)
    except TraceInvalid as e:
        print(f"ERROR: {e}", file=sys.stderr)
        return 2
    # per-request lane validation (r17): a present-but-malformed lane
    # (orphaned span ids, spans escaping their parents) is a broken
    # trace — same exit code as a truncated file
    val = validate_request_lane(data, args.top)
    if val["present"]:
        rep["requests"] = val
    if not args.json:
        print(format_table(rep))
    print("TRACE=" + json.dumps(rep, sort_keys=True))
    if val["present"] and not request_lane_ok(val):
        print(f"ERROR: request lane malformed: "
              f"orphans={val['orphan_spans']} "
              f"nesting={val['nesting_violations']} "
              f"malformed={val['malformed']}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
