"""The knee sweep of an open-loop serving cell: one process, one engine, an
ascending geometric ladder of offered rates, the cell's own length
distributions at each.  Run once, when the cell is defined; its table goes
into PERF.md and the chosen rate (about four fifths of the knee) into the
traffic file as data.

    python3 benchmark/sweep.py --workload <cell> --start 2 --factor 1.25 \
        --rates 8 --seconds 20 --seed 1 [--out chiprun_out/sweep.json]

Between rates the engine is drained empty, so each rate starts as the cell
does: from an idle engine, through the traffic file's lead-in.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import run as run_lib  # noqa: E402
from benchmark.lib import knee as knee_lib  # noqa: E402
from benchmark.lib import manifest as manifest_lib  # noqa: E402
from benchmark.lib.stats import percentile  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--start", type=float, default=2.0)
    ap.add_argument("--factor", type=float, default=1.25)
    ap.add_argument("--rates", type=int, default=8)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--lead-in", type=float, default=None,
                    help="seconds of arrivals before each rate's window "
                         "(default: the traffic file's)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse-on-cpu", action="store_true")
    args = ap.parse_args(argv)

    manifest = manifest_lib.load_manifest()
    entry = {w["name"]: w for w in manifest["workloads"]}[args.workload]
    run_lib.prepare_environment(args.rehearse_on_cpu, entry["chips"])
    import jax

    from benchmark.lib import device as device_lib
    from benchmark.lib import loadgen
    from benchmark.lib.harness import (Cell, close_env, make_env, say,
                                       with_rehearsal)
    from paddle_tpu.inference.serving import Request

    devices = device_lib.require(jax, entry["chips"], args.rehearse_on_cpu)
    about = device_lib.describe(jax)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    config = {c["name"]: c for c in manifest["configs"]}[entry["config"]]
    with open(os.path.join(ROOT, config["file"])) as f:
        config_body = json.load(f)
    with open(manifest_lib.traffic_file(entry["traffic"])) as f:
        traffic_body = json.load(f)
    if args.rehearse_on_cpu:
        config_body, traffic_body = with_rehearsal(config_body), \
            with_rehearsal(traffic_body)
    cell = Cell(entry["name"], config_body, traffic_body, entry["chips"],
                args.seed, args.seconds, False, args.rehearse_on_cpu)
    env = make_env(jax, devices, T_START, False,
                   os.environ.get("PT_PALLAS_INTERPRET") == "1",
                   seconds=args.seconds)
    rates = [args.start * args.factor ** i for i in range(args.rates)]
    rows = []
    # the configuration's own runner: it has ``build``, ``plan``, ``warm_up``
    runner = importlib.import_module(
        f"benchmark.runners.{config_body['runner']}")
    try:
        eng, cfg, _weights = runner.build(cell, env)
        traffic = cell.traffic

        def plan_at(rate):
            t = dict(traffic)
            t["arrivals"] = dict(traffic["arrivals"], rate_per_s=rate)
            if args.lead_in is not None:
                t["lead_in_s"] = args.lead_in
            return runner.plan(cell, cfg, t)

        runner.warm_up(eng, plan_at(rates[-1]), env)
        say(sweep=args.workload, rates=rates, seconds=args.seconds, **about,
            setup_s=env.since_start())
        for rate in rates:
            planned = plan_at(rate)
            marks = {}

            def between(t, engine):
                if "open" not in marks and t >= 0.0:
                    marks["open"] = dict(engine.stats)
                if "close" not in marks and t >= args.seconds:
                    marks["close"] = dict(engine.stats)

            c0 = env.watch.compiles
            raw = loadgen.replay(
                eng, planned, args.seconds, 0.0,
                lambda p, due: Request(p.req_id, list(p.prompt), p.want, due),
                between_steps=between)
            table = loadgen.request_table(raw,
                                          lambda p: p.handle.admitted_at)
            completed = len(loadgen.completed(raw))
            ttft = [r["ttft_s"] for r in table if r["ttft_s"] is not None]
            gaps = [r["mean_gap_s"] for r in table
                    if r["mean_gap_s"] is not None]
            a, b = marks.get("open", {}), marks.get("close", eng.stats)
            dsteps = b["decode_steps"] - a.get("decode_steps", 0)
            row = {
                "rate_per_s": rate, "due": len(table),
                "due_by_end": len(planned),
                "completed": completed, "queue_half": raw["queue_half"],
                "queue_end": raw["queue_end"],
                "running_end": len(eng.running),
                # middle, 90th percentile and mean of first-token times, of
                # requests' mean gaps (itl) and of all gaps pooled (gap)
                **loadgen.latency_note(raw, table),
                "ttft_p95_ms": 1e3 * percentile(ttft, 95) if ttft else None,
                "itl_p95_ms": 1e3 * percentile(gaps, 95) if gaps else None,
                "decode_batch_mean": (b["decode_tokens"]
                                      - a.get("decode_tokens", 0)) / dsteps
                if dsteps else None,
                "engine_steps": len(raw["steps"]),
                "compilations": env.watch.compiles - c0,
            }
            row["sustained"] = knee_lib.sustained(row)
            rows.append(row)
            say(**row)
            drain_t0 = time.perf_counter()
            while eng.has_work():          # empty the engine between rates
                eng.step(0.0)
            say(drained_s=time.perf_counter() - drain_t0)
            if len(rows) >= 2 and not rows[-1]["sustained"] \
                    and not rows[-2]["sustained"]:
                break                       # two rates past the knee: enough
    finally:
        close_env(env)
    found = knee_lib.knee(rows)
    result = {"workload": args.workload, "seed": args.seed,
              "seconds": args.seconds, "device": about, "rows": rows,
              "knee_per_s": found,
              "four_fifths": None if found is None else 0.8 * found}
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    print(json.dumps({k: v for k, v in result.items() if k != "rows"}))


if __name__ == "__main__":
    main()
