"""Device time by program form and by part of the model, from the device's own
rows and the program's table of its compiled steps.

A device event is an HLO instruction's name, kind and shape (``trace.
read_rows``); a kernel's name says what it is and ``fusion.123`` says nothing.
The program knows: it notes which compiled steps ran while the tracer's
session was active and reads their text into tables
(``paddle_tpu.profiler.device_symbols()``: per step its ``program`` label,
``prefill`` or ``decode``, and per instruction its name, opcode, first output
shape, named scopes and the ``part`` of the model it serves: ``embed``,
``mla_part``, ``kda_part``, ``moe_part``, ``dense_ffn``, ``head``).  The
profile is gone by the time a reader runs, so this joins what is left:
``reduction["rows"]`` and the tables, in this process.

**The join.**  A row of device 0 that lies inside ``bench/window`` is looked up
by ``(instruction name, output shape)``, both as ``trace.short_name`` and
``trace.kind_and_shape`` print them (``("fusion.123", "f32[32768,2048]")``).
Instruction names are unique within a compiled step and reused across steps,
so the shape tells the steps apart: a prefill bucket's large shapes carry its
row count, a decode step's its batch.  Two questions are asked of a key, and
each has its own rule of ambiguity:

- *which form*: the ``program`` labels of the steps that hold the key.  One
  label: the row is that form's.  Several (a scalar both forms compute under
  one name): the row has no form.
- *which part*: the ``part`` of the key in each step that holds it.  One part:
  the row is that part's.  Several, or none: the row is ``unnamed``.  The
  decode steps of different table widths hold the same names, shapes and
  parts: that is agreement, not ambiguity.

``unnamed`` therefore holds: rows no table knows (a step that ran before the
session noted it, a program outside the executor), keys whose steps disagree,
and instructions the compiler left without a scope and the table could not
place (``part`` null).  It is the measurement's own gauge.

Seconds are unions of intervals, so a nested or parallel event counts once; an
event has one part, so the parts and ``unnamed`` add up to the busy time (the
union of all rows inside the window: the base of every share here, the one
``lib/scopes.py`` uses).  A Mosaic kernel is an instruction whose table entry
is a ``custom-call`` named after its innermost scope; ``moe_xla`` is
``moe_part`` less its ``moe_gmm`` kernels.

Where the program offers no ``device_symbols`` (the parent of the PR that
brought it), noted nothing, or the run has no device plane, every reader
returns ``None``.
"""
from __future__ import annotations

import json
import re
import time
from typing import Dict, List, Optional, Sequence, Tuple

from . import trace as trace_lib

Key = Tuple[str, Optional[str]]
#: rows of the note line's table
TOP = 20


def program_tables() -> Optional[List[dict]]:
    """The program's tables of the compiled steps it noted, or ``None`` where
    it offers none."""
    try:
        from paddle_tpu import profiler
    except ImportError:
        return None
    offered = getattr(profiler, "device_symbols", None)
    return None if offered is None else offered()


def key_of(row_name: str) -> Key:
    """A device row's ``<kind> <shape>|<short name>`` as ``(short name,
    shape)``; the shape is None where the event printed none."""
    label, _, short = row_name.partition("|")
    kind, _, shape = label.partition(" ")
    return (short or kind, shape or None)


def index(tables: Sequence[dict]) -> Dict[Key, dict]:
    """key -> ``labels`` and ``parts`` (the sets the steps that hold the key
    give), and of the first such step ``scope`` (the innermost), ``via`` and
    ``kernel`` (its name where the instruction is a Mosaic kernel)."""
    out: Dict[Key, dict] = {}
    for table in tables:
        for ins in table["instructions"]:
            scopes = ins["scopes"]
            kind = re.sub(r"\.\d+$", "", ins["name"])
            entry = out.setdefault((ins["name"], ins["shape"]), {
                "labels": set(), "parts": set(),
                "scope": scopes[-1] if scopes else None, "via": ins["via"],
                "kernel": kind if ins["opcode"] == "custom-call" and scopes
                and scopes[-1] == kind else None})
            entry["labels"].add(table["program"])
            entry["parts"].add(ins["part"])
    return out


def _one(values: set):
    """The single value of a set that holds one, else None."""
    return next(iter(values)) if len(values) == 1 else None


def _seconds(intervals) -> float:
    return trace_lib.total(trace_lib.union(intervals)) / 1e9


def analyse(rows: Sequence[trace_lib.Row], window: trace_lib.Interval,
            tables: Sequence[dict]) -> Optional[dict]:
    """Device 0's seconds inside ``window`` by form, by part, by how the part
    was found, its kernels' seconds by part, and the largest groups of rows;
    ``None`` without a device plane or without a table."""
    devices = trace_lib.device_ids(rows)
    if not devices or not tables:
        return None
    plane, known = f"/device:TPU:{devices[0]}", index(tables)
    every, by_label, by_part, by_via, kernels, why = [], {}, {}, {}, {}, {}
    groups: Dict[tuple, List[float]] = {}
    for p, _l, name, start, dur in rows:
        if p != plane or start < window[0] or start + dur > window[1]:
            continue
        at = (start, start + dur)
        entry = known.get(key_of(name))
        label = _one(entry["labels"]) if entry else None
        part = _one(entry["parts"]) if entry else None
        every.append(at)
        if label is not None:
            by_label.setdefault(label, []).append(at)
        by_part.setdefault(part or "unnamed", []).append(at)
        if part is None:
            reason = "no_table" if entry is None else \
                "ambiguous" if len(entry["parts"]) > 1 else "no_scope"
            why.setdefault(reason, []).append(at)
        else:
            by_via.setdefault(entry["via"] or "own", []).append(at)
            if entry["kernel"]:
                kernels.setdefault(part, {}).setdefault(
                    entry["kernel"], []).append(at)
        group = groups.setdefault(
            (label, part, entry["scope"] if entry else None,
             name.partition("|")[0]), [0.0, 0])
        group[0] += dur / 1e9
        group[1] += 1
    busy = _seconds(every)
    if not busy:
        return None
    return {
        "busy_s": busy,
        "by_program": {k: _seconds(v) for k, v in by_label.items()},
        "by_part": {k: _seconds(v) for k, v in by_part.items()},
        "unnamed_why": {k: _seconds(v) for k, v in why.items()},
        "named_via": {k: _seconds(v) for k, v in by_via.items()},
        "kernels_s": {part: {k: _seconds(v) for k, v in of.items()}
                      for part, of in kernels.items()},
        "largest": [[*key, s, n] for key, (s, n) in sorted(
            groups.items(), key=lambda kv: -kv[1][0])[:TOP]],
    }


def share(found: Optional[dict], table: str, name: str) -> Optional[float]:
    """``found[table][name]`` as a percentage of the busy seconds (0 where the
    table holds no such name), or None."""
    if found is None:
        return None
    return 100.0 * found[table].get(name, 0.0) / found["busy_s"]


def kernel_seconds(found: dict, part: str, kernel: str) -> float:
    return found["kernels_s"].get(part, {}).get(kernel, 0.0)


def of_run(record: dict, reduction: dict) -> Optional[dict]:
    """``analyse`` for this run, computed once and kept on the record; the
    first call prints the run's note line: the steps the program noted with
    their calls while the session was active, the seconds the program took to
    read their text, and the tables of ``analyse``."""
    if "device_symbols" not in record:
        t0 = time.perf_counter()
        tables = program_tables() if reduction else None
        took = time.perf_counter() - t0
        found = analyse(reduction["rows"], reduction["window"], tables) \
            if tables else None
        record["device_symbols"] = found
        if tables is not None:
            print(json.dumps({"device_symbols": {
                "symbols_read_s": took,
                "programs": [
                    {k: t.get(k) for k in ("program", "module", "feed",
                                           "calls", "read_s", "foreign",
                                           "error")}
                    | {"instructions": len(t["instructions"])}
                    for t in tables],
                **({k: _sorted(v) if k in ("by_program", "by_part") else v
                    for k, v in found.items()} if found else {})}}),
                flush=True)
    return record["device_symbols"]


def _sorted(table: Dict[str, float]) -> List[list]:
    return [[n, s] for n, s in sorted(table.items(), key=lambda kv: -kv[1])]
