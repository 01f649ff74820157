"""Device time by the part of a decoder block it belongs to, read from the
profile itself.

``trace.read_rows`` keeps an operation's kind, shape and instruction name,
which is enough for a kernel (its name is its own) and says nothing of an XLA
fusion (``fusion.123``).  The program names its parts where it builds them
(``jax.named_scope``: the op's type, and the part of the block it serves).
This reader takes every string an event carries (its text and its string
stats) and classes the event by the first pattern that matches; what matches
none is ``other``.  On JAX 0.9.0 / libtpu 0.0.34 a device event carries its
HLO instruction and three numeric stats (``device_offset_ps``,
``device_duration_ps``, ``Time Scale Multiplier``; my chip run, PR 32) and no
scope path, so a kernel is classed by its name and an XLA fusion is ``other``:
the shares read here are the kernels' until the profile says more.  Times are unions of
intervals inside the harness's ``bench/window`` span, so a nested or parallel
event counts once.

A program that names no such part (any but the one that brought them) reads
all of its time as ``other``; a run without a device plane reads ``None``.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

from . import trace as trace_lib

#: part -> pattern over everything an event says of itself; first match wins
PARTS: Sequence[Tuple[str, str]] = (
    ("moe", r"moe_"),
    ("mla", r"mla_|latent_"),
    ("dense_ffn", r"dense_ffn"),
    ("head", r"\bhead\b|/head/|token_score|arg_max|sample_token"),
    ("embed", r"lookup_table"),
)


def part_of(text: str) -> str:
    for part, pattern in PARTS:
        if re.search(pattern, text):
            return part
    return "other"


def event_text(ev) -> str:
    """Everything a profile event says of itself: its name and its string
    stats."""
    said = [str(ev.name)]
    try:
        said += [f"{k}={v}" for k, v in ev.stats if isinstance(v, str)]
    except (TypeError, ValueError):
        pass
    return " ".join(said)


def classify(events: Sequence[Tuple[str, int, int]],
             window: trace_lib.Interval) -> dict:
    """``events``: ``(text, start_ns, duration_ns)`` of one device's
    operations.  Seconds by part inside ``window``, the busy seconds (the
    union of all), and for each part the texts of its three longest
    events: what to read by hand before trusting a share."""
    by_part: Dict[str, List[trace_lib.Interval]] = {}
    longest: Dict[str, List[Tuple[int, str]]] = {}
    for text, start, dur in events:
        if start < window[0] or start + dur > window[1]:
            continue
        part = part_of(text)
        by_part.setdefault(part, []).append((start, start + dur))
        longest.setdefault(part, []).append((dur, text[:240]))
    seconds = {p: trace_lib.total(trace_lib.union(iv)) / 1e9
               for p, iv in by_part.items()}
    busy = trace_lib.total(trace_lib.union(
        [i for iv in by_part.values() for i in iv])) / 1e9
    return {"seconds": seconds, "busy_s": busy,
            "longest": {p: [t for _d, t in sorted(v, reverse=True)[:3]]
                        for p, v in longest.items()}}


def of_trace(trace_dir: Optional[str]) -> Optional[dict]:
    """The classes of device 0's operations in the profile under
    ``trace_dir``; ``None`` where there is no profile, no device plane or no
    window span."""
    if not trace_dir:
        return None
    from jax.profiler import ProfileData

    try:
        data = ProfileData.from_file(trace_lib.find_xplane(trace_dir))
    except FileNotFoundError:
        return None
    events, window = [], None
    for plane in data.planes:
        found = trace_lib.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if found and found.group(1) == "0" \
                    and line.name == trace_lib.OPS_LINE:
                events += [(event_text(ev), int(ev.start_ns),
                            int(ev.duration_ns)) for ev in line.events]
            elif not found:
                for ev in line.events:
                    if ev.name == trace_lib.SPAN_PREFIX + "window":
                        a, b = int(ev.start_ns), \
                            int(ev.start_ns + ev.duration_ns)
                        window = (a, b) if window is None else \
                            (min(a, window[0]), max(b, window[1]))
    if not events or window is None:
        return None
    return classify(events, window)
