"""The manifest (``BENCHMARK.json``) and the files its names resolve to.

``check`` is run by ``run.py`` at start and by the tests: a manifest that the
driver would refuse, or a name with no file behind it, stops the run before
anything is measured.
"""
from __future__ import annotations

import json
import os
import re
from typing import List

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.join(ROOT, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
DATA_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def reader_of(metric: str) -> str:
    """A metric's reader is the file named by the part of its name before the
    first dot: ``device_idle_pct.train`` is read by ``device_idle_pct.py``."""
    return metric.split(".", 1)[0]


def traffic_file(name: str) -> str:
    for suffix in DATA_SUFFIXES:
        path = os.path.join(HERE, "traffic", name + suffix)
        if os.path.exists(path):
            return path
    return os.path.join(HERE, "traffic", name + ".json")


def cells_of(metric: dict, manifest: dict) -> List[str]:
    return metric.get("workloads") or [w["name"] for w in
                                       manifest["workloads"]]


def metrics_of(manifest: dict, group: str, cell: str) -> List[dict]:
    return [m for m in manifest[group] if cell in cells_of(m, manifest)]


def check(manifest: dict, here: str = HERE) -> List[str]:
    """Every fault found, as a sentence; an empty list passes."""
    faults: List[str] = []

    def name_ok(what, value):
        if not isinstance(value, str) or not NAME.match(value):
            faults.append(f"{what} {value!r} is not a name of at most 64 "
                          f"letters, digits, '_', '.', '-'")

    configs = {c["name"]: c for c in manifest["configs"]}
    cells = [w["name"] for w in manifest["workloads"]]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in manifest[group]]
        for n in names:
            name_ok(f"{group} name", n)
        if len(set(names)) != len(names):
            faults.append(f"{group}: a name appears twice")
    if set(e2e) & {m["name"] for m in manifest["per_layer"]}:
        faults.append("a metric name is both end-to-end and per-layer")
    if "setup_s" not in e2e:
        faults.append("no end-to-end metric setup_s")

    for c in manifest["configs"]:
        path = os.path.join(ROOT, c["file"])
        if not os.path.exists(path):
            faults.append(f"config {c['name']}: no file {c['file']}")
            continue
        with open(path) as f:
            body = json.load(f)
        runner = body.get("runner")
        if not runner or not os.path.exists(
                os.path.join(here, "runners", f"{runner}.py")):
            faults.append(f"config {c['name']}: runner {runner!r} has no "
                          f"file under runners/")
        if not os.path.exists(os.path.join(here, "reference",
                                           f"{c['name']}.py")):
            faults.append(f"config {c['name']}: no plain reference under "
                          f"reference/")
        if sorted(body.get("reduced", [])) != sorted(c["reduced"]):
            faults.append(f"config {c['name']}: 'reduced' differs between "
                          f"the manifest and {c['file']}")
        if not any(w["config"] == c["name"] for w in manifest["workloads"]):
            faults.append(f"config {c['name']} is used by no cell")

    pairs = set()
    for w in manifest["workloads"]:
        name_ok("traffic", w["traffic"])
        if w["config"] not in configs:
            faults.append(f"cell {w['name']}: unknown config {w['config']}")
        if not os.path.exists(traffic_file(w["traffic"])):
            faults.append(f"cell {w['name']}: no traffic file for "
                          f"{w['traffic']!r}")
        elif traffic_file(w["traffic"]).endswith(".json"):
            with open(traffic_file(w["traffic"])) as f:
                generator = json.load(f).get("generator")
            if generator and not os.path.exists(
                    os.path.join(here, "generators", f"{generator}.py")):
                faults.append(f"traffic {w['traffic']}: generator "
                              f"{generator!r} has no file under generators/")
        if w["chips"] not in (1, 4):
            faults.append(f"cell {w['name']}: chips must be 1 or 4")
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"]:
            faults.append(f"cell {w['name']}: why must be one line of at "
                          f"most 200 characters")
        if (w["config"], w["traffic"]) in pairs:
            faults.append(f"cell {w['name']}: its config and traffic pair "
                          f"appears twice")
        pairs.add((w["config"], w["traffic"]))
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    if four > max(1, len(cells) // 4):
        faults.append(f"{four} cells ask for four chips; at most a quarter "
                      f"(rounded down, one always) may")

    for group, folder in (("end_to_end", "end_to_end"),
                          ("per_layer", "layer_metrics")):
        for m in manifest[group]:
            if not UNIT.match(m["unit"]):
                faults.append(f"metric {m['name']}: unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                faults.append(f"metric {m['name']}: better {m['better']!r}")
            if m["source"] not in SOURCES:
                faults.append(f"metric {m['name']}: source {m['source']!r}")
            for cell in m.get("workloads", []):
                if cell not in cells:
                    faults.append(f"metric {m['name']}: unknown cell {cell}")
            reader = os.path.join(here, folder, reader_of(m["name"]) + ".py")
            if not os.path.exists(reader):
                faults.append(f"metric {m['name']}: no reader "
                              f"{folder}/{reader_of(m['name'])}.py")
    for m in manifest["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            faults.append(f"end-to-end metric {m['name']} must be taken by "
                          f"the benchmark itself")
        if not 0 < m["bound"] <= 0.1:
            faults.append(f"metric {m['name']}: bound {m['bound']}")
    for m in manifest["per_layer"]:
        moved = e2e.get(m["moves"])
        if moved is None:
            faults.append(f"metric {m['name']} moves {m['moves']!r}, which "
                          f"is no end-to-end metric")
            continue
        lacking = set(cells_of(m, manifest)) - set(cells_of(moved, manifest))
        if lacking:
            faults.append(f"metric {m['name']} moves {m['moves']}, which "
                          f"cells {sorted(lacking)} do not report")
    for cell in cells:
        if len(metrics_of(manifest, "end_to_end", cell)) < 2:
            faults.append(f"cell {cell} reports no end-to-end metric "
                          f"besides setup_s")
        if not metrics_of(manifest, "per_layer", cell):
            faults.append(f"cell {cell} reports no per-layer metric")
    return faults


def check_peaks(device_kind: str, here: str = HERE) -> dict:
    """The peaks of ``device_kind``; a kind not in the table is an error."""
    with open(os.path.join(here, "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    if device_kind not in peaks:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json: "
                       f"add it with its source, never a default")
    return peaks[device_kind]
