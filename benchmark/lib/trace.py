"""Reduction of a profiler trace to device busy time, idle gaps and per-name
operation time.

The profiler's ``.xplane.pb`` is read with ``jax.profiler.ProfileData`` into
plain rows ``(plane, line, name, start_ns, duration_ns)``; everything after
that is arithmetic on the rows, tested on the small recorded trace beside this
file (``recorded_trace.json``).

What is a device plane, which of its lines holds the operations, and how the
harness's own spans are recognised is stated once, here:

- a device plane is named ``/device:TPU:<n>``;
- its operations are the events of the line ``XLA Ops``; where a device plane
  has no such line (the CPU rehearsal has no device plane at all) there is
  nothing to reduce;
- an operation's event carries the whole HLO instruction as its name
  (``%fusion.7 = bf16[...] fusion(...)``); ``short_name`` keeps the
  instruction's own name (``fusion.7``), which is what readers match, so an
  operand that mentions another instruction never counts as that one;
- the harness's spans are ``TraceAnnotation``s whose names start with
  ``bench/``; they lie on host planes and share the device events' clock.

Busy time is the *union* of the operation intervals, so nested and parallel
events are counted once; summed durations are used only per name.
"""
from __future__ import annotations

import glob
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench/"

Row = Tuple[str, str, str, int, int]
Interval = Tuple[int, int]


def short_name(event_name: str) -> str:
    """``%bn_act_fwd.49 = bf16[..] custom-call(..)`` -> ``bn_act_fwd.49``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def kind_and_shape(event_name: str) -> str:
    """A label that groups like operations: the instruction's name without
    its number, and its (first) output shape: ``fusion bf16[128,56,56,256]``."""
    base = re.sub(r"\.\d+$", "", short_name(event_name))
    shape = re.search(r" = \(?([a-z0-9]+\[[0-9,]*\])", event_name)
    return f"{base} {shape.group(1)}" if shape else base


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_rows(xplane_path: str, seen: Optional[dict] = None) -> List[Row]:
    """Device operations and harness spans of a recorded profile.  ``seen``
    is filled with the event count of every other line of every device plane:
    what to look at by hand before trusting the reduction on a new device."""
    from jax.profiler import ProfileData

    rows: List[Row] = []
    data = ProfileData.from_file(xplane_path)
    for plane in data.planes:
        device = DEVICE_PLANE.match(plane.name) is not None
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                if seen is not None:
                    seen.setdefault(plane.name, {})[line.name] = \
                        sum(1 for _ in line.events)
                continue
            for ev in line.events:
                if device:
                    rows.append((plane.name, line.name,
                                 kind_and_shape(ev.name) + "|"
                                 + short_name(ev.name),
                                 int(ev.start_ns), int(ev.duration_ns)))
                elif ev.name.startswith(SPAN_PREFIX):
                    rows.append((plane.name, line.name, ev.name,
                                 int(ev.start_ns), int(ev.duration_ns)))
    return rows


def load_rows(path: str) -> List[Row]:
    with open(path) as f:
        return [tuple(r) for r in json.load(f)["rows"]]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Disjoint, ascending cover of ``intervals``."""
    out: List[List[int]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def gaps(cover: Sequence[Interval], window: Interval) -> List[Interval]:
    """The parts of ``window`` that the disjoint ``cover`` leaves free."""
    out, at = [], window[0]
    for a, b in cover:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if window[1] > at:
        out.append((at, window[1]))
    return out


def total(intervals: Iterable[Interval]) -> int:
    return sum(b - a for a, b in intervals)


def device_ids(rows: Sequence[Row]) -> List[int]:
    return sorted({int(DEVICE_PLANE.match(r[0]).group(1)) for r in rows
                   if DEVICE_PLANE.match(r[0])})


def device_intervals(rows: Sequence[Row], device: int) -> List[Interval]:
    plane = f"/device:TPU:{device}"
    return [(s, s + d) for p, _l, _n, s, d in rows if p == plane]


def spans(rows: Sequence[Row]) -> List[Tuple[str, int, int]]:
    return [(n[len(SPAN_PREFIX):], s, s + d) for p, _l, n, s, d in rows
            if not DEVICE_PLANE.match(p) and n.startswith(SPAN_PREFIX)]


def window_of(rows: Sequence[Row], name: str = "window") -> Interval:
    """The traced window: the harness's span of that name."""
    found = [(a, b) for n, a, b in spans(rows) if n == name]
    if not found:
        raise ValueError(f"the trace holds no span {SPAN_PREFIX}{name}")
    return min(a for a, _ in found), max(b for _, b in found)


def _label(row_name: str, which: int) -> str:
    """A device row's name is ``<kind and shape>|<short name>``."""
    parts = row_name.split("|", 1)
    return parts[min(which, len(parts) - 1)]


def per_name_seconds(rows: Sequence[Row], device: int, window: Interval,
                     grouped: bool = False) -> Dict[str, float]:
    """Summed device time inside the window of each instruction (by short
    name) or, ``grouped``, of each kind and shape of instruction."""
    plane, out = f"/device:TPU:{device}", {}
    for p, _l, n, s, d in rows:
        if p == plane and s >= window[0] and s + d <= window[1]:
            key = _label(n, 0 if grouped else 1)
            out[key] = out.get(key, 0.0) + d / 1e9
    return out


def name_events(rows: Sequence[Row], device: int, window: Interval,
                pattern: str) -> List[int]:
    """Durations (ns) of the device events whose short name matches
    ``pattern``."""
    plane, rx = f"/device:TPU:{device}", re.compile(pattern)
    return [d for p, _l, n, s, d in rows
            if p == plane and s >= window[0] and s + d <= window[1]
            and rx.search(_label(n, 1))]


def meeting(pieces: Sequence[Interval], ordered: Sequence[tuple]):
    """Each of ``pieces`` (taken in ascending order) with the spans of
    ``ordered`` (``(name, start, end)``) that overlap it, in ``ordered``'s
    own order.  One pass over both: a span is looked at from the first piece
    that ends after its start to the last that starts before its end, so a
    chat trace's 250,000 pieces against 3,000 spans cost their sum and not
    their product (which was two minutes of a traced run)."""
    by_start = sorted(range(len(ordered)), key=lambda i: ordered[i][1])
    live: List[int] = []
    k = 0
    for piece in sorted(pieces):
        while k < len(by_start) and ordered[by_start[k]][1] < piece[1]:
            live.append(by_start[k])
            k += 1
        live = [i for i in live if ordered[i][2] > piece[0]]
        yield piece, [ordered[i] for i in sorted(live)
                      if ordered[i][1] < piece[1]]


def attribute_gaps(idle: Sequence[Interval],
                   host_spans: Sequence[Tuple[str, int, int]]
                   ) -> Dict[str, float]:
    """Idle seconds by what the host was doing: each gap is shared among the
    harness spans that overlap it (the innermost, i.e. shortest, span wins
    where several do) and what no span covers is ``outside-spans``."""
    out: Dict[str, float] = {}
    ordered = sorted(host_spans, key=lambda s: s[2] - s[1])
    for gap, over in meeting(idle, ordered):
        free = [gap]
        for name, a, b in over:
            taken = clip(free, (a, b))
            if taken:
                out[name] = out.get(name, 0.0) + total(taken) / 1e9
                free = [g for f in free for g in gaps([(a, b)], f)]
        rest = total(free)
        if rest:
            out["outside-spans"] = out.get("outside-spans", 0.0) + rest / 1e9
    return out


def reduce(rows: Sequence[Row], top: int = 10) -> dict:
    """Everything the per-layer readers and the ``breakdown`` take from a
    trace.  ``busy_s`` is averaged over the devices that ran anything;
    ``idle_share`` and the tables are device 0's."""
    devices = device_ids(rows)
    if not devices:
        return {}
    window = window_of(rows)
    window_s = (window[1] - window[0]) / 1e9
    busy = {}
    for d in devices:
        cover = union(clip(device_intervals(rows, d), window))
        busy[d] = (cover, total(cover) / 1e9)
    first = devices[0]
    idle = gaps(busy[first][0], window)
    by_span = attribute_gaps(
        idle, [s for s in spans(rows) if s[0] != "window"])
    ops = per_name_seconds(rows, first, window)
    kinds = per_name_seconds(rows, first, window, grouped=True)
    return {
        "window": window, "window_s": window_s, "devices": devices,
        "busy_s": sum(b for _, b in busy.values()) / len(devices),
        "busy_s_by_device": {d: b for d, (_, b) in busy.items()},
        "idle_share": 1.0 - busy[first][1] / window_s,
        "ops": ops,
        "device_ops": sorted(kinds.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(by_span.items(), key=lambda kv: -kv[1])[:top],
        "rows": rows,
    }

