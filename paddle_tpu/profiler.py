"""Profiler — host event tracing + device (XLA) profiler bridge.

Reference: paddle/fluid/platform/profiler.h:208 (EnableProfiler/
DisableProfiler/ResetProfiler), platform/profiler.cc (RecordEvent RAII,
event tree, summary table, chrome-trace protobuf), python surface
python/paddle/fluid/profiler.py (profiler/start_profiler/stop_profiler
context managers), and the CUPTI DeviceTracer (device_tracer.h:41).

TPU-native shape:
* host events — same RecordEvent nesting/summary/chrome-trace design,
  pure Python (host-side op dispatch is Python here; there is no C++
  executor loop to instrument).
* device events — XLA owns the device timeline.  The CUPTI analog is
  the JAX/XLA profiler: ``start_profiler`` with a trace dir starts
  ``jax.profiler`` (TensorBoard trace with per-HLO timing).  The profile
  names a device event by its HLO instruction and carries no scope (JAX
  0.9.0 / libtpu 0.0.34), so op→kernel correlation does not come from the
  profile: the ``jax.named_scope`` annotations the executor emits while
  tracing (``registry.run_op``: the part of the model, then the op's type)
  reach the compiled program's text, the executor notes which compiled
  steps ran while a span recorded (``note_program``), and
  ``device_symbols()`` reads their text into a table from instruction to
  scope path afterwards; ``device_table(rows)`` joins a profile's events to
  it: seconds by program, part and op type (the correlation
  device_tracer.cc gets from CUPTI's ids).

Unified timeline (r13): events carry a *lane* (``cat``) — "host" for
executor RecordEvents, "serving" for scheduler decisions
(inference/serving.py), "rpc" for PS client spans
(distributed_ps/service.py), "chaos" for injected faults
(utils/chaos.py).  ``_write_chrome_trace`` maps each lane to its own
pid with a ``process_name`` metadata row, so one chrome-trace /
Perfetto file shows training, serving and RPC activity side by side
(``tools/trace_report.py`` turns it into a phase-breakdown table).
Zero-duration decisions (admit/preempt/evict, chaos drops) are
*instant* events (``ph: "i"``).

One span primitive, two sinks (PR 24): a ``RecordEvent`` is recorded while
this profiler is enabled OR while a JAX profiler session is active
(``jax.profiler.start_trace``, which ``enable_profiler(trace_dir=...)`` also
starts).  During a session the span is, besides its record here, a
``jax.profiler.TraceAnnotation`` named ``pt/<name>`` carrying its ``args``:
it lies in the ``.xplane.pb`` beside the device's operations, on their clock.
With neither on, entering a span is that one check.  Completed events live in
a bounded ring; ``dropped_events()`` counts what an hour-long session pushed
out.

Closing the calibration loop: ``disable_profiler`` feeds the measured
``executor_run`` step time (and the per-op means of the summary) into
``utils.cost_model.set_measured_profile``, so the next
``FLAGS_fuse_grad_size_in_MB="auto"`` bucket decision runs on measured
rates instead of the hand-set defaults.
"""
from __future__ import annotations

import collections
import contextlib
import json
import os
import re
import threading
import time
from typing import Deque, Dict, List, Optional

from jax.profiler import TraceAnnotation as _Annotation

__all__ = [
    "RecordEvent", "record_event", "instant_event", "counter_event",
    "complete_event",
    "enable_profiler", "disable_profiler", "reset_profiler",
    "start_profiler", "stop_profiler", "profiler", "is_profiler_enabled",
    "get_events", "dropped_events", "npu_profiler", "cuda_profiler", "LANES",
    "note_program", "device_symbols", "device_table",
]

#: lane -> chrome-trace pid.  Lanes not listed get pids allocated past
#: the reserved block, deterministically by first appearance.
#: "request" (r17) is the per-request tracing lane: utils/tracing.py
#: emits each request's span tree there with tid = one row per trace.
LANES = {"host": 0, "serving": 1, "rpc": 2, "chaos": 3, "memory": 4,
         "request": 5}

_state = threading.local()
_GLOBAL_LOCK = threading.Lock()
_ENABLED = False
_TRACE_DIR: Optional[str] = None
# completed events: name, cat, ts, dur, tid, depth, parent (+ args).  A
# ring of the newest 65,536: a serving step records some 25 spans, so it
# holds the last half hour of a busy engine
_EVENTS: Deque[dict] = collections.deque(maxlen=1 << 16)
_DROPPED = 0  # events the ring pushed out since the last reset
# the compiled steps that ran while a span recorded, by id of the jitted
# callable (which the note keeps alive): label, callable, the abstract
# signature of the call, calls so far; ``device_symbols`` adds the table
_PROGRAMS: Dict[int, dict] = {}
#: a JAX profiler session is active (about 40 ns a call)
_session_active = _Annotation.is_enabled
#: every thread's live event stack, keyed by thread ident — the
#: thread-local fast path aliases these lists.  Kept globally so
#: reset_profiler can clear a stack left behind by a thread that died
#: (or errored) mid-event: before r13 such a leftover skewed ``depth``
#: for the next session on a reused (pool) thread, and the dead
#: thread's stack leaked.
_STACKS: Dict[int, List[str]] = {}


def _append(ev: dict):
    global _DROPPED
    with _GLOBAL_LOCK:
        if len(_EVENTS) == _EVENTS.maxlen:
            _DROPPED += 1
        _EVENTS.append(ev)


def _stack() -> List[str]:
    stack = getattr(_state, "stack", None)
    if stack is None:
        stack = _state.stack = []
        with _GLOBAL_LOCK:
            _STACKS[threading.get_ident()] = stack
    return stack


def is_profiler_enabled() -> bool:
    return _ENABLED


class RecordEvent:
    """RAII host-event marker (reference: platform/profiler.h RecordEvent;
    used as ``with profiler.RecordEvent("fwd"): ...``).  Nested events
    form a tree via ``depth`` and ``parent`` (the enclosing span of this
    thread); ``cat`` picks the timeline lane ("host" unless a runtime
    says otherwise); ``args`` is a small dict of ids and counts, never
    arrays.

    Recorded while the profiler is enabled or a JAX profiler session is
    active (then also as the ``TraceAnnotation`` ``pt/<name>``); a no-op
    otherwise.  ``begin``/``end`` are the span's own ``perf_counter``
    stamps: a caller that needs the times of the call it wraps reads
    them here instead of timing the call again, and passes
    ``timed=True`` where it needs them even while nothing records."""

    __slots__ = ("name", "cat", "args", "begin", "end", "recording",
                 "_timed", "_note")

    def __init__(self, name: str, cat: str = "host",
                 args: Optional[dict] = None, timed: bool = False):
        self.name = name
        self.cat = cat
        self.args = args
        self.begin = self.end = None
        self.recording = False
        self._timed = timed
        self._note = None

    def set(self, **args):
        """Add attributes known only inside the span (a byte count, a
        bucket).  Callers guard with ``if span.recording:`` so the off
        path builds nothing."""
        self.args = {**self.args, **args} if self.args else args
        if self._note is not None:
            self._note.set_metadata(**args)

    def __enter__(self):
        session = _session_active()
        if _ENABLED or session:
            self.recording = True
            if session:
                self._note = _Annotation("pt/" + self.name,
                                         **(self.args or {}))
                self._note.__enter__()
            _stack().append(self.name)
            self.begin = time.perf_counter()
        elif self._timed:
            self.begin = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if not self.recording:
            if self._timed:
                self.end = time.perf_counter()
            return False
        self.end = end = time.perf_counter()
        self.recording = False
        note, self._note = self._note, None
        if note is not None:
            note.__exit__(*exc)
        stack = _stack()
        if stack:
            # empty = reset_profiler cleared this thread's stack while
            # the event was in flight (cross-thread reset): record the
            # completion at depth 0 instead of crashing the worker
            stack.pop()
        ev = {
            "name": self.name,
            "cat": self.cat,
            "ts": self.begin,
            "dur": end - self.begin,
            "tid": threading.get_ident(),
            "depth": len(stack),
            "parent": stack[-1] if stack else None,
        }
        if self.args:
            ev["args"] = dict(self.args)
        _append(ev)
        return False


@contextlib.contextmanager
def record_event(name: str, cat: str = "host"):
    """Functional spelling of RecordEvent."""
    with RecordEvent(name, cat):
        yield


def instant_event(name: str, cat: str = "host",
                  args: Optional[dict] = None):
    """Zero-duration marker on a lane (chrome-trace ``ph: "i"``): a
    scheduler decision, an injected fault — things that happen AT a
    moment rather than over one.  No-op when the profiler is off."""
    if not _ENABLED:
        return
    ev = {
        "name": name, "cat": cat, "ts": time.perf_counter(), "dur": 0.0,
        "tid": threading.get_ident(), "depth": len(_stack()), "ph": "i",
    }
    if args:
        ev["args"] = dict(args)
    _append(ev)


def counter_event(name: str, values: dict, cat: str = "memory",
                  ts: Optional[float] = None):
    """Chrome-trace counter sample (``ph: "C"``): a named scalar series
    rendered as a filled lane graph (the memory lane:
    framework/memory_plan.py emits the modeled live-bytes timeline
    here).  ``values`` maps series name -> number; ``ts`` overrides the
    sample time (modeled timelines space samples by modeled op time).
    No-op when the profiler is off."""
    if not _ENABLED:
        return
    ev = {
        "name": name, "cat": cat,
        "ts": time.perf_counter() if ts is None else float(ts),
        "dur": 0.0, "tid": threading.get_ident(), "depth": 0, "ph": "C",
        "args": {k: float(v) for k, v in values.items()},
    }
    _append(ev)


def complete_event(name: str, cat: str = "host", ts: float = 0.0,
                   dur: float = 0.0, tid: Optional[int] = None,
                   args: Optional[dict] = None):
    """Append an already-timed complete event (chrome ``ph: "X"``):
    the request-tracing lane (utils/tracing.py) times spans with its
    own clocks and records them here at span end.  ``tid`` overrides
    the thread id so one request's spans share a row regardless of
    which thread (client, server handler) produced them.  No-op when
    the profiler is off."""
    if not _ENABLED:
        return
    ev = {
        "name": name, "cat": cat, "ts": float(ts), "dur": float(dur),
        "tid": threading.get_ident() if tid is None else int(tid),
        "depth": 0, "ph": "X",
    }
    if args:
        ev["args"] = dict(args)
    _append(ev)


def enable_profiler(state: str = "All", trace_dir: Optional[str] = None):
    """reference: profiler.h:208 EnableProfiler.  ``state`` is kept for
    API parity ('CPU'/'GPU'/'All'); device tracing starts whenever a
    ``trace_dir`` is given (jax.profiler TensorBoard trace)."""
    global _ENABLED, _TRACE_DIR
    if state not in ("CPU", "GPU", "TPU", "All"):
        raise ValueError("state must be 'CPU', 'GPU', 'TPU' or 'All'")
    reset_profiler()
    _ENABLED = True
    if trace_dir is not None:
        import jax

        os.makedirs(trace_dir, exist_ok=True)
        jax.profiler.start_trace(trace_dir)
        _TRACE_DIR = trace_dir


start_profiler = enable_profiler


def reset_profiler():
    """reference: profiler.py reset_profiler.  Clears completed events
    AND every thread's live event stack — a stack abandoned mid-event
    (crashed thread, unexited manual ``__enter__``) must not skew depth
    for the next session (regression-tested)."""
    global _DROPPED
    with _GLOBAL_LOCK:
        _EVENTS.clear()
        _PROGRAMS.clear()
        _DROPPED = 0
        live = {t.ident for t in threading.enumerate()}
        for ident in list(_STACKS):
            _STACKS[ident].clear()     # aliased by that thread's local
            if ident not in live:
                del _STACKS[ident]     # dead thread: drop the entry too


def disable_profiler(sorted_key: Optional[str] = None,
                     profile_path: Optional[str] = None,
                     print_summary: bool = True):
    """reference: profiler.h:209 DisableProfiler — stops collection,
    prints the summary table (``print_summary=False`` collects silently
    for library callers), optionally writes a chrome-trace JSON (the
    profiler.proto analog; load via chrome://tracing / perfetto), and
    feeds the measured step time into the cost-model calibration store
    (utils/cost_model.py) so bucket autotune runs on measured rates."""
    global _ENABLED, _TRACE_DIR
    _ENABLED = False
    device = None
    if _TRACE_DIR is not None:
        import glob

        import jax

        jax.profiler.stop_trace()
        written = sorted(glob.glob(os.path.join(
            _TRACE_DIR, "plugins", "profile", "*", "*.xplane.pb")))
        if profile_path and written:
            device = device_table(written[-1])
        _TRACE_DIR = None
    with _GLOBAL_LOCK:
        events = list(_EVENTS)
    if profile_path:
        _write_chrome_trace(events, profile_path, device)
    summary = summarize(events, sorted_key or "default")
    _feed_calibration(summary)
    if summary and print_summary:
        print(_format_summary(summary))
    if print_summary:
        # allocator stats line (SURVEY §2.9 #9 — allocator_facade shim)
        try:
            from .utils.memory import memory_summary

            print("[memory] " + memory_summary(0))
        except Exception:
            pass
    return summary


def stop_profiler(sorted_key: Optional[str] = None,
                  profile_path: Optional[str] = None,
                  print_summary: bool = True):
    return disable_profiler(sorted_key, profile_path, print_summary)


def get_events() -> List[dict]:
    """Copy of the completed-event ring, oldest first (tools, tests and
    the benchmark's per-layer readers)."""
    with _GLOBAL_LOCK:
        return [dict(e) for e in _EVENTS]


def dropped_events() -> int:
    """Completed events the ring pushed out since the last reset."""
    return _DROPPED


# ---- device symbols ---------------------------------------------------------
# A device profile names an operation by its HLO instruction (``fusion.123``)
# and, on the stack this repo supports, says nothing of where it came from.
# The compiled program does: every instruction carries the path of named
# scopes it was traced under (``registry.run_op``: the part of the model an
# op serves, then its type; the lowerings' own scopes below).  So the program
# notes which compiled steps ran while a span recorded, and afterwards reads
# their text into a table from instruction to scope path.
#: what JAX itself puts into a scope path: transforms and call wrappers
_JAX_WRAPPER = re.compile(
    r"^(?:\w+\(.*\)|pjit|while|body|cond|body_fun|cond_fun|branch_\d+_fun|"
    r"closed_call|core_call|custom_jvp_call|custom_vjp_call|checkpoint|"
    r"remat|scan)$")
_HLO_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+)\s+=\s+(.+)$")
_HLO_SHAPE = re.compile(r"^\(?([a-z0-9]+\[[0-9,]*\])")
_HLO_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
#: the computations an instruction runs as device operations of their own
#: (a fusion's ``calls=`` and a reduction's ``to_apply=`` are not)
_HLO_CALLEES = {
    "while": re.compile(r"(?:condition|body)=%?([\w.\-]+)"),
    "call": re.compile(r"to_apply=%?([\w.\-]+)"),
    "conditional": re.compile(
        r"(?:true_computation|false_computation)=%?([\w.\-]+)"
        r"|branch_computations=\{([^}]*)\}"),
}
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_FUSED = re.compile(r"calls=%?([\w.\-]+)")
_HLO_OPERAND = re.compile(r"%([\w.\-]+)")
_HLO_NAME = re.compile(r"[\w.\-]+")
MOSAIC_TARGET = "tpu_custom_call"


def _abstract(x):
    import jax

    return jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=getattr(x, "sharding", None),
        weak_type=getattr(x, "weak_type", False))


def note_program(label: str, jitted, args: tuple):
    """One call of a compiled step while a span records (``Executor.
    _execute``, under its ``executor/call``).  The first call of an entry
    keeps its label, the jitted callable and the abstract signature of the
    call (shapes, types and placements: no array); every call is counted.
    An entry without one executable to read (a hybrid program's segments,
    the checkify wrapper) is left out."""
    note = _PROGRAMS.get(id(jitted))
    if note is None:
        if not hasattr(jitted, "lower"):
            return
        import jax

        note = {"program": label, "jitted": jitted, "calls": 0,
                "abstract": jax.tree.map(_abstract, args)}
        with _GLOBAL_LOCK:
            note = _PROGRAMS.setdefault(id(jitted), note)
    note["calls"] += 1


def _split_path(op_name: str) -> List[str]:
    """``a/f(b/c)/d`` -> ``[a, f(b/c), d]``: a slash inside a transform's
    parentheses is part of its name."""
    out, depth, at = [], 0, 0
    for i, ch in enumerate(op_name):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            out.append(op_name[at:i])
            at = i + 1
    out.append(op_name[at:])
    return out


def scopes_of(op_name: str) -> List[str]:
    """The named scopes of an instruction's ``op_name``, outer to inner:
    the path less its last element (the primitive) and less JAX's own
    wrappers (``jit(pt_prefill)``, ``pjit``, transforms, loop bodies)."""
    return [p for p in _split_path(op_name)[:-1]
            if p and not _JAX_WRAPPER.match(p)]


def _parse_computations(text: str):
    """``(module name, entry computation, computation -> [(instruction
    name, the rest of its line)])`` of a compiled module's text."""
    computations: Dict[str, List[tuple]] = {}
    module = entry = current = None
    for line in text.splitlines():
        if current is None:
            if line.startswith("HloModule "):
                module = line.split()[1].rstrip(",")
            elif line.endswith("{") and "->" in line \
                    and not line[0].isspace():
                words = line.split()
                is_entry = words[0] == "ENTRY"
                current = words[1 if is_entry else 0].lstrip("%")
                computations[current] = []
                if is_entry:
                    entry = current
        elif line.startswith("}"):
            current = None
        else:
            found = _HLO_INSTRUCTION.match(line)
            if found:
                computations[current].append(found.groups())
    return module, entry, computations


def _op_name(rest: str) -> Optional[str]:
    said = _HLO_OP_NAME.search(rest)
    return said.group(1) if said else None


def hlo_symbols(text: str) -> dict:
    """A compiled module's text as a table: its name, and for every
    instruction of the entry computation and of the computations it runs
    (loop bodies and conditions, branches, calls) its ``name`` as a device
    event has it (``fusion.123``), ``opcode``, first output ``shape``
    (``f32[8,128]``), ``op_name`` as it stands (or None), ``scopes``
    (``scopes_of`` it; a Mosaic kernel's name is its innermost scope),
    ``part`` (the outermost scope that is a part of the model:
    ``registry.PARTS``) and ``op`` (the outermost scope that is a
    registered op type).

    XLA:TPU leaves some instructions without a scope: a fusion it built
    late, and the data movement it adds itself (layout copies, the slices
    and copies that bring an operand in ahead of its use).  Those take the
    part they serve, and ``via`` says how it was found: ``fused`` (the
    instructions inside the fusion name one part), ``operands`` (what it
    reads comes from one part), ``users`` (what reads it lies in one
    part).  ``via`` is None where the instruction's own ``op_name`` gave
    the part; ``part`` is None where nothing did."""
    from .ops.registry import OPS, PARTS

    def part_of(scopes):
        return next((s for s in scopes if s in PARTS), None)

    module, entry, computations = _parse_computations(text)
    rows, seen, todo = [], set(), [entry] if entry else []
    reads: Dict[str, List[str]] = {}
    while todo:
        comp = todo.pop()
        if comp in seen or comp not in computations:
            continue
        seen.add(comp)
        for name, rest in computations[comp]:
            shape = _HLO_SHAPE.match(rest)
            opcode = _HLO_OPCODE.search(rest)
            operands = []
            if opcode:
                operands = _HLO_OPERAND.findall(
                    rest[opcode.end():].split(")", 1)[0])
                opcode = opcode.group(1)
            op_name = _op_name(rest)
            scopes = scopes_of(op_name) if op_name else []
            via = None
            if opcode == "custom-call" and \
                    f'custom_call_target="{MOSAIC_TARGET}"' in rest:
                kernel = re.sub(r"\.\d+$", "", name)
                if not scopes or scopes[-1] != kernel:
                    scopes.append(kernel)
            elif opcode == "fusion" and part_of(scopes) is None:
                fused = _HLO_FUSED.search(rest)
                inside = [scopes_of(said) for said in (
                    _op_name(line) for _, line in computations.get(
                        fused.group(1) if fused else "", ())) if said]
                inside = [sc for sc in inside if part_of(sc)]
                if len({part_of(sc) for sc in inside}) == 1:
                    scopes, via = inside[0], "fused"
            if opcode in _HLO_CALLEES:
                for found in _HLO_CALLEES[opcode].findall(rest):
                    todo += _HLO_NAME.findall(
                        found if isinstance(found, str) else ",".join(found))
            reads[name] = operands
            rows.append({
                "name": name, "opcode": opcode,
                "shape": shape.group(1) if shape else None,
                "op_name": op_name, "scopes": scopes,
                "part": part_of(scopes), "via": via,
                "op": next((s for s in scopes if s in OPS), None)})
    # what has no part yet takes the one part of what it reads, else the one
    # part of what reads it, again while that settles anything (a chain of
    # slice-start, slice-done and a layout copy is three deep)
    by_name = {r["name"]: r for r in rows}
    read_by: Dict[str, List[str]] = {}
    for name, operands in reads.items():
        for o in operands:
            read_by.setdefault(o, []).append(name)
    for _ in range(8):
        settled = False
        for r in rows:
            if r["part"] is not None or r["opcode"] == "parameter":
                continue
            for via, near in (("operands", reads[r["name"]]),
                              ("users", read_by.get(r["name"], ()))):
                parts = {by_name[n]["part"] for n in near
                         if n in by_name} - {None}
                if len(parts) == 1:
                    r["part"], r["via"] = parts.pop(), via
                    settled = True
                    break
        if not settled:
            break
    return {"module": module, "instructions": rows}


def _read_symbols(note: dict) -> dict:
    """The table of one noted entry: the text of the executable its calls
    ran (JAX's in-process caches hand the lowering and the executable back:
    nothing is traced or compiled again), parsed."""
    t0 = time.perf_counter()
    feed = note["abstract"][-1]
    table = {"program": note["program"], "module": None,
             "feed": {k: f"{v.dtype.name}[{','.join(map(str, v.shape))}]"
                      for k, v in sorted(feed.items())}
             if isinstance(feed, dict) else {},
             "instructions": []}
    try:
        lowered = note["jitted"].lower(*note["abstract"])
        table.update(hlo_symbols(lowered.compile().as_text()))
        # JAX's persistent cache keys an executable without its metadata:
        # one that an older tree compiled comes back under that tree's
        # scopes.  The lowering is always this process's own trace, so an
        # outermost pair of scopes (``run_op``'s: the part, the op's type)
        # that it does not hold gives such an executable away.
        # (a call's own location ends in a scope, not in a primitive)
        own = {tuple(scopes_of(path + tail)[:2]) for path in re.findall(
            r'loc\("([^"]+)"', lowered.as_text(debug_info=True))
            for tail in ("", "/call")}
        table["foreign"] = sum(
            1 for i in table["instructions"]
            if i["op_name"] and i["opcode"] != "parameter"
            and tuple(scopes_of(i["op_name"])[:2]) not in own)
    except Exception as e:   # a table is an observation: never the run's fault
        table["error"] = f"{type(e).__name__}: {e}"[:240]
    table["read_s"] = time.perf_counter() - t0
    return table


def device_symbols() -> List[dict]:
    """For every compiled step that ran while a span recorded: ``program``
    (its label), ``module`` (``jit_pt_<label>``), ``feed`` (its feeds'
    shapes), ``calls`` (while recording), ``read_s``, ``instructions``
    (``hlo_symbols``) and ``foreign`` (how many of them lie under a part
    and op type that this process's own trace of the step does not hold:
    above 0 the executable came from the persistent compile cache, where
    another tree with other scopes had put it, and its parts are that
    tree's).  An entry's text is read once, at the first call here after it
    was noted, and never while a JAX profiler session is active: reading
    belongs after the window it would disturb."""
    if _session_active():
        raise RuntimeError("device_symbols() reads every noted executable's "
                           "text: call it after the profiler session")
    with _GLOBAL_LOCK:
        notes = list(_PROGRAMS.values())
    out = []
    for note in notes:
        if "table" not in note:
            note["table"] = _read_symbols(note)
        out.append(dict(note["table"], calls=note["calls"]))
    return out


def _event_key(name: str) -> tuple:
    """``(instruction name, first output shape)`` of a device event: from
    the whole instruction, as the profile names an event (``%fusion.7 =
    bf16[8,128]{1,0} fusion(...)``), or from ``<kind> <shape>|<name>``, as
    the benchmark's ``trace.read_rows`` keeps it."""
    if " = " in name:
        short, rest = name.split(" = ", 1)
        shape = _HLO_SHAPE.match(rest)
        return short.lstrip("%"), shape.group(1) if shape else None
    label, _, short = name.partition("|")
    return short or label, label.partition(" ")[2] or None


def _xplane_rows(path: str) -> List[tuple]:
    """``(plane, line, event name, start_ns, duration_ns)`` of the device
    planes' ``XLA Ops`` lines in a written ``.xplane.pb``."""
    from jax.profiler import ProfileData

    return [(plane.name, line.name, ev.name, int(ev.start_ns),
             int(ev.duration_ns))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/device:")
            for line in plane.lines if line.name == "XLA Ops"
            for ev in line.events]


def device_table(rows) -> List[dict]:
    """The device's half of the op summary (reference: profiler.h:208's
    table of ops, which there came from CUPTI's correlation ids): seconds
    and events by ``program``, ``part`` and ``op`` type, largest first.
    ``rows``: device events as ``(plane, line, name, start_ns,
    duration_ns)``, or the path of a ``.xplane.pb``.  Each event is looked
    up in ``device_symbols()`` by its instruction's name and output shape;
    where the noted steps that hold the pair disagree, or none holds it,
    that column is None."""
    if isinstance(rows, (str, os.PathLike)):
        rows = _xplane_rows(os.fspath(rows))
    known: Dict[tuple, tuple] = {}
    for table in device_symbols():
        for ins in table["instructions"]:
            sets = known.setdefault((ins["name"], ins["shape"]),
                                    (set(), set(), set()))
            for held, value in zip(sets, (table["program"], ins["part"],
                                          ins["op"])):
                held.add(value)
    out: Dict[tuple, dict] = {}
    for plane, _line, name, _start, dur in rows:
        if not plane.startswith("/device:"):
            continue
        group = tuple(next(iter(held)) if len(held) == 1 else None
                      for held in known.get(_event_key(name), ((),) * 3))
        row = out.setdefault(group, dict(
            zip(("program", "part", "op"), group), seconds=0.0, events=0))
        row["seconds"] += dur / 1e9
        row["events"] += 1
    return sorted(out.values(), key=lambda r: -r["seconds"])


def _feed_calibration(summary: List[dict]):
    """Profiled step -> cost model: the MIN ``executor_run`` wall time
    becomes the measured step time — the steady-state floor, so a
    compile-dominated first step can't poison the calibration (best
    of several, as tools/dp_comm_stats.py reads a trace).  Per-name
    means ride along for finer consumers.  Best-effort: calibration
    must never break a profiling session."""
    try:
        row = next((r for r in summary if r["name"] == "executor_run"), None)
        if row is None:
            return
        from .utils import cost_model

        cost_model.set_measured_profile(
            step_s=row["min"],
            per_op_s={r["name"]: r["ave"] for r in summary},
            source="profiler")
    except Exception:
        pass


def summarize(events: List[dict], sorted_key: str = "default") -> List[dict]:
    rows: Dict[str, dict] = {}
    for e in events:
        if e.get("ph") in ("i", "C", "X"):
            # instants/counters mark moments; explicit-"X" events are
            # pre-timed lane data (request spans) whose names overlap
            # the host/serving RecordEvents — neither belongs in the
            # host summary (or the calibration feed) as extra calls
            continue
        r = rows.setdefault(e["name"], {
            "name": e["name"], "calls": 0, "total": 0.0,
            "max": 0.0, "min": float("inf"),
        })
        r["calls"] += 1
        r["total"] += e["dur"]
        r["max"] = max(r["max"], e["dur"])
        r["min"] = min(r["min"], e["dur"])
    out = list(rows.values())
    for r in out:
        r["ave"] = r["total"] / r["calls"]
        if r["min"] == float("inf"):
            r["min"] = 0.0
    keymap = {
        "default": lambda r: 0,          # insertion order
        "calls": lambda r: -r["calls"],
        "total": lambda r: -r["total"],
        "max": lambda r: -r["max"],
        "min": lambda r: -r["min"],
        "ave": lambda r: -r["ave"],
    }
    if sorted_key not in keymap:
        raise ValueError(f"sorted_key must be one of {sorted(keymap)}")
    if sorted_key != "default":
        out.sort(key=keymap[sorted_key])
    return out


def _format_summary(rows: List[dict]) -> str:
    hdr = (f"{'Event':<40} {'Calls':>8} {'Total(ms)':>12} {'Ave(ms)':>10} "
           f"{'Max(ms)':>10} {'Min(ms)':>10}")
    lines = ["------------------------->  Profiling Report  "
             "<-------------------------", hdr]
    for r in rows:
        lines.append(
            f"{r['name'][:40]:<40} {r['calls']:>8} {r['total']*1e3:>12.3f} "
            f"{r['ave']*1e3:>10.3f} {r['max']*1e3:>10.3f} "
            f"{r['min']*1e3:>10.3f}")
    return "\n".join(lines)


def _lane_pids(events: List[dict]) -> Dict[str, int]:
    """lane -> pid: the reserved LANES block first, then unknown lanes
    in first-appearance order."""
    pids = dict(LANES)
    nxt = max(pids.values()) + 1
    for e in events:
        cat = e.get("cat", "host")
        if cat not in pids:
            pids[cat] = nxt
            nxt += 1
    return pids


def _write_chrome_trace(events: List[dict], path: str,
                        device: Optional[List[dict]] = None):
    pids = _lane_pids(events)
    used = {e.get("cat", "host") for e in events}
    trace_events = [
        {
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": f"lane:{lane}"},
        }
        for lane, pid in sorted(pids.items(), key=lambda kv: kv[1])
        if lane in used
    ] + [
        {
            "name": "process_sort_index", "ph": "M", "pid": pid, "tid": 0,
            "args": {"sort_index": pid},
        }
        for lane, pid in sorted(pids.items(), key=lambda kv: kv[1])
        if lane in used
    ]
    for e in events:
        ev = {
            "name": e["name"], "cat": e.get("cat", "host"),
            "ts": e["ts"] * 1e6,
            "pid": pids[e.get("cat", "host")], "tid": e["tid"],
        }
        if e.get("ph") == "i":
            ev["ph"] = "i"
            ev["s"] = "t"  # thread-scoped instant
        elif e.get("ph") == "C":
            ev["ph"] = "C"
            ev["tid"] = 0  # counters are per-process series
        else:
            ev["ph"] = "X"
            ev["dur"] = e["dur"] * 1e6
        if e.get("args"):
            ev["args"] = e["args"]
        trace_events.append(ev)
    trace = {"traceEvents": trace_events}
    if device:
        # seconds on the device by program, part and op type
        # (``device_table``; tools/trace_report.py prints it)
        trace["deviceTable"] = device
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(trace, f)


@contextlib.contextmanager
def profiler(state: str = "All", sorted_key: Optional[str] = None,
             profile_path: Optional[str] = None,
             trace_dir: Optional[str] = None,
             print_summary: bool = True):
    """reference: fluid/profiler.py profiler context manager."""
    enable_profiler(state, trace_dir=trace_dir)
    try:
        yield
    finally:
        disable_profiler(sorted_key, profile_path, print_summary)


@contextlib.contextmanager
def cuda_profiler(*args, **kwargs):
    """Legacy API shape (reference: profiler.py cuda_profiler) — on TPU
    the device profiler is the jax trace; kept as an alias context."""
    with profiler():
        yield


npu_profiler = cuda_profiler
