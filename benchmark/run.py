"""The benchmark's one command: one cell, one run, one process.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data: ``BENCHMARK.json`` names the cell's
configuration and traffic mix and the metrics it reports, and this file finds
by those names the configuration (``configs/``), its runner (``runners/``) and
plain reference (``reference/``), the traffic mix (``traffic/``) and one
reader per metric (``end_to_end/``, ``layer_metrics/``).  The last line of the
output is the result; earlier lines are notes.

Without a TPU, or with fewer chips than the cell asks for, nothing is run and
the exit code is not 0.  ``--rehearse-on-cpu`` (tiny sizes from the files'
``rehearsal`` blocks, kernels interpreted, virtual devices) exercises the
whole path in the sandbox and never prints the result line.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib import manifest as manifest_lib  # noqa: E402


def load_by_path(folder: str, name: str):
    path = os.path.join(manifest_lib.HERE, folder, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{folder}_{name.replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="a rehearsal, never a result")
    return ap.parse_args(argv)


def prepare_environment(rehearsal: bool, chips: int):
    """Before JAX is imported: the compile cache's fixed home inside the
    checkout (where the machine names none) and, for a rehearsal, the CPU
    with interpreted kernels and as many virtual devices as the cell asks."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault("PT_PALLAS_INTERPRET", "1")
        os.environ.setdefault("FLAGS_tpu_nhwc", "1")
        os.environ.setdefault("FLAGS_tpu_fuse", "1")
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={chips}"
            ).strip()


def refuse_empty_trace(traced: bool, reduction: dict, say):
    """A traced run has to have seen the device work: where its trace holds
    no device operation (the engine had drained before the traced seconds),
    say so as a fault line, then leave with no result."""
    if traced and not reduction:
        say(fault="the trace holds no device operation",
            why="no step ran on the device inside the traced seconds, the "
                "window's last: the queue had drained, or nothing was due")
        sys.exit("benchmark: the trace holds no device operation")


def main(argv=None):
    args = parse(argv)
    manifest = manifest_lib.load_manifest()
    faults = manifest_lib.check(manifest)
    if faults:
        sys.exit("benchmark: the manifest is faulty:\n  " + "\n  ".join(faults))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        sys.exit(f"benchmark: no cell {args.workload!r}; there are "
                 f"{sorted(cells)}")
    entry = cells[args.workload]
    rehearsal = args.rehearse_on_cpu
    prepare_environment(rehearsal, entry["chips"])

    import jax

    try:
        import paddle_tpu  # noqa: F401 — the system under test
    except ImportError as e:
        sys.exit(f"benchmark: the program is not in this directory ({e}); "
                 f"nothing was run")
    from benchmark.lib import device as device_lib
    from benchmark.lib.harness import (Cell, close_env, make_env, say,
                                       with_rehearsal)

    devices = device_lib.require(jax, entry["chips"], rehearsal)
    about = device_lib.describe(jax)
    peaks = None if rehearsal else manifest_lib.check_peaks(about["kind"])
    # every program goes to the persistent cache, however quick its compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    config = {c["name"]: c for c in manifest["configs"]}[entry["config"]]
    with open(os.path.join(ROOT, config["file"])) as f:
        config_body = json.load(f)
    with open(manifest_lib.traffic_file(entry["traffic"])) as f:
        traffic = json.load(f)
    if rehearsal:
        config_body, traffic = with_rehearsal(config_body), \
            with_rehearsal(traffic)
    seconds = args.seconds if args.seconds is not None \
        else manifest["run_seconds"]
    cell = Cell(name=entry["name"], config=config_body, traffic=traffic,
                chips=entry["chips"], seed=args.seed, seconds=float(seconds),
                trace=bool(args.trace), rehearsal=rehearsal)
    env = make_env(jax, devices, T_START, cell.trace,
                   interpreted=os.environ.get("PT_PALLAS_INTERPRET") == "1",
                   seconds=cell.seconds)
    say(cell=cell.name, seed=cell.seed, seconds=cell.seconds,
        trace=cell.trace, rehearsal=rehearsal, **about,
        compile_cache_dir=os.environ["JAX_COMPILATION_CACHE_DIR"])
    try:
        runner = importlib.import_module(
            f"benchmark.runners.{config_body['runner']}")
        reference = load_by_path("reference", config["name"])
        record = runner.run(cell, env, reference)
        reduction = env.tracer.reduction() if cell.trace else {}
    finally:
        close_env(env)
    record["harness"] = {
        "build_span_s": env.span_seconds("build", "first_call"),
        "build_compile_s": env.compile_s_in.get("build", 0.0)
        + env.compile_s_in.get("first_call", 0.0),
        "peaks": peaks,
    }

    say(setup_counters=record["setup_counters"],
        span_seconds={n: env.span_seconds(n)
                      for n in sorted({s[0] for s in env.spans})},
        backend_compile_s_in_span=env.compile_s_in)

    group, folder = ("per_layer", "layer_metrics") if cell.trace \
        else ("end_to_end", "end_to_end")
    metrics = {}
    for m in manifest_lib.metrics_of(manifest, group, cell.name):
        reader = importlib.import_module(
            f"benchmark.{folder}.{manifest_lib.reader_of(m['name'])}")
        value = reader.read(record, reduction, cell) if cell.trace \
            else reader.read(record, cell)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    if record["compiles_in_window"]:
        say(fault="compilation inside the measured window",
            compilations=record["compiles_in_window"])
    correct = bool(record["correct"]) and not record["compiles_in_window"]
    device = dict(about, memory_peak_bytes=record["memory_peak_bytes"])
    result = {"correct": correct, "attempted": record["attempted"],
              "failed": record["failed"], "metrics": metrics,
              "device": device}
    if cell.trace and reduction:
        device["busy_s"] = reduction["busy_s"]
        device["window_s"] = reduction["window_s"]
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in reduction["device_ops"]],
            "idle_gaps": [[n, s] for n, s in reduction["idle_gaps"]]}
    if rehearsal:
        # never the contract's line: a rehearsal is not a result
        say(rehearsal="passed" if correct else "FAILED", **about,
            rehearsal_metrics=metrics, attempted=record["attempted"],
            failed=record["failed"])
        sys.exit(0 if correct else 1)
    refuse_empty_trace(cell.trace, reduction, say)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
