"""What a runner is handed: the cell, the clock of the process, the harness's
own spans, the compile watch and the tracer of the ``--trace 1`` run."""
from __future__ import annotations

import contextlib
import gc
import json
import os
import shutil
import tempfile
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from . import trace as trace_lib
from .watch import Watch


class GcWatch:
    """The interpreter's collector pauses, by its own callbacks: a pause of
    the host inside the window is a gap on the device."""

    def __init__(self):
        self.pauses: List[Tuple[float, float, int]] = []   # (start, s, gen)
        self._t0 = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append((self._t0, time.perf_counter() - self._t0,
                                info["generation"]))

    def since(self, t: float) -> dict:
        took = [(s, g) for t0, s, g in self.pauses if t0 >= t]
        return {"collections": len(took),
                "total_s": sum(s for s, _ in took),
                "longest_s": max((s for s, _ in took), default=0.0),
                "full_collections": sum(g == 2 for _, g in took)}

    def close(self):
        gc.callbacks.remove(self._on)


def longest(durations, top: int = 3):
    """The ``top`` longest of ``(start, end)`` pairs as ``[start, seconds]``,
    and the median: a stall shows here and nowhere else."""
    took = sorted(((b - a, a) for a, b in durations), reverse=True)
    mid = sorted(d for d, _ in took)[len(took) // 2] if took else None
    return {"median_s": mid, "longest": [[a, d] for d, a in took[:top]]}


def say(**fields):
    """An earlier line of the output: never the contract's last line."""
    print(json.dumps(fields), flush=True)


def with_rehearsal(body: dict) -> dict:
    """``body`` with its ``rehearsal`` block laid over it: the tiny sizes a
    rehearsal runs at, key by key (one level deep for nested groups)."""
    out = dict(body)
    for key, value in body.get("rehearsal", {}).items():
        out[key] = {**body[key], **value} if isinstance(value, dict) \
            and isinstance(body.get(key), dict) and key != "arrivals" \
            else value
    return out


@dataclass
class Cell:
    """One run's cell: ``config`` and ``traffic`` are the files' contents (in
    a rehearsal with their ``rehearsal`` blocks laid over them)."""
    name: str
    config: dict
    traffic: dict
    chips: int
    seed: int
    seconds: float
    trace: bool
    rehearsal: bool



class Tracer:
    """Traces the last few seconds of the measured window: on at the first
    poll ``start_after_s`` into the window, off at the first poll ``trace_s``
    later, which is about where the window closes, so that the seconds
    ``stop_trace`` blocks for fall after it.  Polls come between steps, so
    the trace holds whole steps."""

    def __init__(self, jax, enabled: bool, start_after_s: float,
                 trace_s: float):
        self.jax, self.enabled = jax, enabled
        self.start_after_s, self.trace_s = start_after_s, trace_s
        self.dir: Optional[str] = None
        self.on_at: Optional[float] = None
        self.off_at: Optional[float] = None
        self._window = None

    @property
    def active(self) -> bool:
        return self.on_at is not None and self.off_at is None

    def poll(self, t: float):
        if not self.enabled or self.off_at is not None:
            return
        if self.on_at is None:
            if t >= self.start_after_s:
                self.dir = tempfile.mkdtemp(prefix="bench_trace_")
                # the Python tracer would slow the host it is measuring
                options = self.jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                self.jax.profiler.start_trace(self.dir,
                                              profiler_options=options)
                self._window = self.jax.profiler.TraceAnnotation(
                    trace_lib.SPAN_PREFIX + "window")
                self._window.__enter__()
                self.on_at = t
        elif t - self.on_at >= self.trace_s:
            self.stop(t)

    def stop(self, t: float):
        if self.active:
            self._window.__exit__(None, None, None)
            self.jax.profiler.stop_trace()
            self.off_at = t

    def reduction(self) -> dict:
        if self.dir is None:
            return {}
        try:
            seen: dict = {}
            rows = trace_lib.read_rows(trace_lib.find_xplane(self.dir), seen)
            say(trace_rows=len(rows), other_device_lines=seen)
            return trace_lib.reduce(rows)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


@dataclass
class Env:
    jax: object
    devices: list
    t_start: float                     # perf_counter at process start
    tracer: Tracer
    watch: Watch
    interpreted: bool
    gc_watch: GcWatch = field(default_factory=GcWatch)
    spans: List[Tuple[str, float, float]] = field(default_factory=list)
    compile_s_in: Dict[str, float] = field(default_factory=dict)

    @contextlib.contextmanager
    def span(self, name: str):
        """A harness span: kept in memory by name and host clock and, while
        the tracer is on, written into the profiler's trace as well."""
        note = self.jax.profiler.TraceAnnotation(
            trace_lib.SPAN_PREFIX + name) if self.tracer.active else None
        c0, t0 = self.watch.compile_s, time.perf_counter()
        if note is not None:
            note.__enter__()
        try:
            yield
        finally:
            if note is not None:
                note.__exit__(None, None, None)
            self.spans.append((name, t0, time.perf_counter()))
            self.compile_s_in[name] = self.compile_s_in.get(name, 0.0) \
                + self.watch.compile_s - c0

    def span_seconds(self, *names: str) -> float:
        return sum(b - a for n, a, b in self.spans if n in names)

    def since_start(self) -> float:
        return time.perf_counter() - self.t_start


def make_env(jax, devices, t_start, trace: bool, interpreted: bool,
             seconds: float, trace_s: float = 3.0) -> Env:
    dump = tempfile.mkdtemp(prefix="bench_ir_")
    return Env(jax=jax, devices=devices, t_start=t_start,
               tracer=Tracer(jax, trace, max(0.5, seconds - trace_s),
                             trace_s),
               watch=Watch(jax, dump), interpreted=interpreted)


def close_env(env: Env):
    env.gc_watch.close()
    shutil.rmtree(env.watch.dump_dir, ignore_errors=True)
    if env.tracer.dir and os.path.isdir(env.tracer.dir):
        shutil.rmtree(env.tracer.dir, ignore_errors=True)
