"""Executor: lowers whole Programs to XLA via a single jax.jit trace.

Capability parity with the reference Executor
(reference: paddle/fluid/framework/executor.cc:184 Executor::Run,
executor.cc:380 Prepare, python/paddle/fluid/executor.py:461) — redesigned
TPU-first.  Where the reference interprets the program op-by-op
(RunPartialPreparedContext's hot loop, executor.cc:469-476, dispatching a
CUDA kernel per op), this executor *traces* the block once — each op's
registered lowering emits jax primitives into one function — and compiles
the whole thing with ``jax.jit``.  XLA then fuses across op boundaries,
which is the analog of ``Executor::Prepare``'s create-ops-once caching plus
the reference's fusion passes, for free.

Mutable Scope semantics (optimizer ops updating params in place,
SURVEY.md §7 hard-part 2) become functional state threading: the compiled
function takes ``(feed, state)`` and returns ``(fetches, new_state)``;
state is every var that is read before written (parameters, optimizer
moments, RNG key) plus every persistable var written (so startup programs
initialize the scope through the same path).  Param buffers are donated to
XLA so updates are in-place in HBM.
"""
from __future__ import annotations

import logging
import threading
import weakref
from typing import Any, Dict, List, Optional, Sequence

import jax
import numpy as np

from .framework.core import Program, Variable, default_main_program
from .framework.dtype import to_numpy_dtype
from .framework.place import CPUPlace, Place, _get_paddle_place
from .framework.scope import LoDTensor, Scope, global_scope
from .ops import registry
from .profiler import RecordEvent, note_program
from .utils import telemetry as tm

logger = logging.getLogger(__name__)

RNG_VAR = registry.LowerCtx.RNG_VAR

# ---- compile counters the program owns -----------------------------------
# JAX reports how long it traced a function to a jaxpr and lowered the
# jaxpr to MLIR, with the function's name.  One listener adds up the
# seconds of the compiled steps themselves (``pt_<label>``, named by
# ``named_step`` below): a user's own jax.jit or a plain reference model
# is left out, and so are the nested traces of jnp functions, whose time
# the step's own event already holds.  Compiles are rare: always on.
_JAX_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_JAX_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE_TM = tm.Handles(
    trace=("counter", "executor_jax_trace_seconds_total",
           "seconds JAX spent tracing the op lowerings of a compiled "
           "step (Executor.run, the DP step) to a jaxpr"),
    lower=("counter", "executor_jax_lower_seconds_total",
           "seconds JAX spent lowering a compiled step's jaxpr to an "
           "MLIR module"))


def _on_jax_duration(event, duration, fun_name="", **_):
    if event == _JAX_TRACE_EVENT:
        if fun_name.startswith("pt_"):
            _COMPILE_TM.current().trace.inc(duration)
    elif event == _JAX_LOWER_EVENT:
        if fun_name.startswith("jit(pt_"):
            _COMPILE_TM.current().lower.inc(duration)


jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


def program_label(program) -> str:
    """The program's form (``main`` unless its builder said otherwise:
    the serving forms are ``prefill``, ``decode``, ``chunk``, ``verify``,
    ``reference``).  Names the compiled step and the spans; never
    derived from a shape."""
    return getattr(program, "_label", "main")


def named_step(fn, label: str):
    """``fn`` renamed ``pt_<label>``: ``jax.jit`` calls its module
    ``jit_<function name>``, so the device trace's module line and the
    HLO dumps say which program ran instead of ``jit_fn`` for all."""
    fn.__name__ = fn.__qualname__ = f"pt_{label}"
    return fn


class _Compiled:
    """Compiled program handle.

    ``hybrid`` programs (host ops present) expose ``fn(feed, state)``;
    pure-XLA programs expose ``fn(mut, ro, feed)`` where the mut/ro
    partition is precomputed in ``donatable``/``readonly`` so the hot
    run path never re-partitions per step."""

    __slots__ = ("fn", "raw_fn", "state_in", "state_out", "fetch_names",
                 "donatable", "readonly", "hybrid", "feed_plan", "session",
                 "_memory_plan", "numerics", "tp_shard")

    def __init__(self, fn, state_in, state_out, fetch_names):
        self.fn = fn
        self.raw_fn = None
        self.state_in = state_in
        self.state_out = state_out
        self.fetch_names = fetch_names
        self.donatable = ()
        self.readonly = ()
        self.hybrid = False
        # tensor-parallel serving: {"axis", "degree", "mesh"} when the
        # program is compiled under shard_map (None on every other path)
        self.tp_shard = None
        # per-compilation step-loop plans (built once in _compile /
        # first _execute, reused every step):
        self.feed_plan = None   # {feed name: numpy dtype to cast to|None}
        self.session = None     # _StateSession — device-resident state
        self._memory_plan = None  # framework.memory_plan.MemoryPlan
        self.numerics = None    # probe layout (framework/numerics.py)


class _StateSession:
    """Device-resident state carried across steps of one (compiled,
    scope) pair: after a step, the donated inputs are dead and
    ``new_state`` holds their replacements — rebinding next step from
    here skips the scope.get + isinstance + device_put walk over every
    parameter/optimizer slot.  Invalidation is scope-mutation-counted:
    any scope write outside the executor's own post-step writeback
    (checkpoint load, manual set) bumps ``Scope.mutation_counter`` past
    the recorded stamp and forces a full re-read.

    ``mut`` (params + optimizer moments — the model-sized piece) holds
    WEAK references: while the session is valid the scope's own entries
    keep the arrays alive (they are the same objects), and once
    something overwrites the scope the old state is free to be
    collected — an abandoned session can never pin a second copy of the
    model in device memory.  ``ro`` holds STRONG references: read-only
    state is typically small (LR schedules, eval-side constants) and —
    unlike mut — its device copy may exist nowhere else when the scope
    holds a host-side value (numpy / LoDTensor) that state_val converted;
    a weak ref there would die instantly and silently disable the
    session for the rest of the run."""

    __slots__ = ("scope_ref", "stamp", "mut", "ro")

    def __init__(self, scope_ref, stamp, mut, ro):
        self.scope_ref = scope_ref
        self.stamp = stamp
        self.mut = mut    # {name: weakref to device array}
        self.ro = ro      # {name: device array} (strong)

    def deref(self):
        """(mut, ro) as strong dicts, or None if any mut value was
        collected (only possible after an unstamped mutation path)."""
        mut = {}
        for n, r in self.mut.items():
            v = r()
            if v is None:
                return None
            mut[n] = v
        return mut, self.ro


def device_put_owned(value, device):
    """Stage host state that may later be DONATED.

    ``jax.device_put`` of a 64-byte-aligned numpy array zero-copies on
    XLA:CPU — the returned device buffer ALIASES the host allocation
    (alignment is malloc luck, so whether a given array aliases is
    nondeterministic).  Aliasing is fine for read-only state, but a
    donated alias hands XLA memory it does not own: after donation the
    runtime recycles those bytes into its own pool while the numpy
    side still owns them, and a later allocation silently corrupts
    whichever live buffer lands on the overlap (surfaced as the r13
    serving flake — paged-decode K/V corrupted only when other engines
    had churned the heap).  This helper re-copies through XLA whenever
    the fast path aliased the host buffer, so the result is always
    safe to donate; backends whose arrays expose no host pointer (TPU:
    device_put is a real H2D copy) pass through untouched.  ``device``
    is a device or, for the data-parallel step, a ``Sharding``."""
    import jax.numpy as jnp

    arr = np.asarray(value)
    out = jax.device_put(arr, device)
    if isinstance(device, jax.sharding.Sharding):
        # the data-parallel step's placement: each shard may alias its
        # slice of the host buffer, so ask the platform and not a pointer
        aliased = next(iter(device.device_set)).platform == "cpu"
    else:
        try:
            aliased = out.unsafe_buffer_pointer() == arr.ctypes.data
        except Exception:
            # cannot PROVE ownership: on host-memory backends assume the
            # worst and copy (cheap, staging-time only); accelerator
            # device_put is a real H2D transfer by construction
            aliased = getattr(device, "platform", "cpu") == "cpu"
    if aliased:
        out = jnp.copy(out)
    return out


class FeedStager:
    """Compile-time feed staging for the step loop: applies the
    feed-conversion plan (target dtype per feed name — the same
    ``build_feed_plan`` rules the executor compiles in) and puts every
    array on device via :func:`device_put_owned`, so the staged values
    are (a) already in the program's dtype — the hot path's cast counter
    stays at zero, (b) XLA-owned — safe against the data loader reusing
    its host buffers for the next batch while the transfer or the step
    is still in flight (the r13 donation-aliasing gotcha, which a
    background-thread pipeline would otherwise hit nondeterministically).
    ``Executor.run`` recognizes staged values (jax arrays on the right
    device) and skips per-step conversion entirely."""

    def __init__(self, program, feed_names, place):
        self.plan = build_feed_plan(program.global_block(),
                                    list(feed_names))
        self.place = _get_paddle_place(place)
        self.device = self.place.jax_device()

    def stage(self, feed: Dict[str, Any]) -> Dict[str, Any]:
        out = {}
        for k, v in feed.items():
            if isinstance(v, jax.Array):
                out[k] = v if v.devices() == {self.device} \
                    else jax.device_put(v, self.device)
                continue
            if isinstance(v, LoDTensor):
                v = v.value()
            arr = np.asarray(v)
            want = self.plan.get(k)
            if want is not None and arr.dtype != want:
                arr = arr.astype(want)
            out[k] = device_put_owned(arr, self.device)
        return out


def double_buffered_feeds(feeds, stager: FeedStager):
    """Input-pipeline double buffering for the executor step session:
    yield staged feed dicts where batch k+1's staging (dtype cast +
    ``device_put_owned`` H2D copies) runs on a background thread while
    the caller executes step k — the MLPerf-style overlap of input
    conversion with device compute (arXiv 1909.09756 §3).

    ``FLAGS_tpu_double_buffer=0`` degrades to synchronous staging on the
    caller's thread: identical values (the rollback contract the tests
    pin), no overlap.  ``feeds`` is any iterable of feed dicts; staging
    errors surface on the consumer thread at the offending batch."""
    from .utils.flags import flag as _flag

    it = iter(feeds)
    if not _flag("tpu_double_buffer", True):
        for f in it:
            yield stager.stage(f)
        return
    import concurrent.futures

    staged = tm.counter(
        "executor_double_buffered_batches_total",
        "feed batches staged ahead on the double-buffer thread")
    pool = concurrent.futures.ThreadPoolExecutor(
        max_workers=1, thread_name_prefix="pt-feed-stage")
    try:
        fut = None
        for f in it:
            nxt = pool.submit(stager.stage, f)
            if fut is not None:
                yield fut.result()  # batch k out while k+1 stages
            fut = nxt
            staged.inc()
        if fut is not None:
            yield fut.result()
    finally:
        pool.shutdown(wait=False)


def _fetch_name(f) -> str:
    if isinstance(f, Variable):
        return f.name
    if isinstance(f, str):
        return f
    raise TypeError(f"bad fetch entry: {f!r}")


def as_numpy(value):
    if isinstance(value, LoDTensor):
        return value.numpy()
    from .framework.selected_rows import SelectedRows

    if isinstance(value, SelectedRows):
        return value.numpy()  # densified view for fetch consumers
    return np.asarray(value)


def analyze_state(ops, block, feed_names, scope, skip_suffixes=()):
    """Shared read/write analysis: which vars the op list reads before
    writing (``state_in``), which persistable/scope-resident vars it
    writes (``state_out``), whether any op consumes the RNG key, and
    whether any host (non-jittable) op is present.  Used by the
    single-device executor, the data-parallel runner, and the pipeline
    runner so the rules can't drift apart."""
    feed_names = set(feed_names)
    written: set = set()
    state_in: List[str] = []
    uses_rng = False
    has_host_ops = False
    for op_ in ops:
        d = registry.OPS.get(op_.type)
        if d is not None and d.stateful:
            uses_rng = True
        if registry.op_contains_host(op_):
            has_host_ops = True
        for name in op_.input_arg_names:
            if (name not in written and name not in feed_names
                    and name != "@EMPTY@" and name not in state_in
                    and not any(name.endswith(s) for s in skip_suffixes)):
                state_in.append(name)
        written.update(op_.output_arg_names)
    written.discard("@EMPTY@")
    state_out = sorted(
        n for n in written
        if ((v := block._find_var_recursive(n)) is not None and v.persistable)
        or scope.has(n)
    )
    if uses_rng:
        if RNG_VAR not in state_in:
            state_in.append(RNG_VAR)
        if RNG_VAR not in state_out:
            state_out.append(RNG_VAR)
    return state_in, state_out, uses_rng, has_host_ops


def build_feed_plan(block, feed):
    """Compile-time feed-conversion plan: target numpy dtype per feed
    name (None = leave as-is).  Shared by the single-device executor and
    the DP runner so the per-step conversion rules can't drift apart."""
    plan = {}
    for k in feed:
        var = block._find_var_recursive(k)
        plan[k] = (to_numpy_dtype(var.dtype)
                   if var is not None and var.dtype is not None else None)
    return plan


def _float_outputs(op_, env):
    import jax.numpy as jnp

    for name in op_.output_arg_names:
        v = env.get(name)
        if v is None or name == "@EMPTY@":
            continue
        try:
            if jnp.issubdtype(jnp.result_type(v), jnp.inexact):
                yield name, v
        except Exception:
            continue


def _eager_nan_check(op_, env):
    """FLAGS_check_nan_inf on the op-by-op (host-op) path — reference:
    framework/details/nan_inf_utils_detail.cc."""
    for name, v in _float_outputs(op_, env):
        arr = np.asarray(v)
        if not np.isfinite(arr).all():
            raise RuntimeError(
                f"Operator {op_.type!r} output {name!r} contains Inf/Nan")


def _traced_nan_check(op_, env):
    """Same check inside a jit trace, via checkify user checks."""
    import jax.numpy as jnp
    from jax.experimental import checkify

    for name, v in _float_outputs(op_, env):
        checkify.check(
            jnp.isfinite(v).all(),
            f"Operator {op_.type!r} output {name!r} contains Inf/Nan")


def _report_unused_vars(ops, fetch_names, state_out):
    """FLAGS_enable_unused_var_check — reference:
    framework/unused_var_check.cc: flags op results nothing ever reads."""
    import warnings

    read = set(fetch_names) | set(state_out)
    for op_ in ops:
        read.update(op_.input_arg_names)
    for op_ in ops:
        dead = [n for n in op_.output_arg_names
                if n not in read and n != "@EMPTY@"]
        if dead:
            warnings.warn(
                f"operator {op_.type!r} produces unused outputs {dead} "
                f"(FLAGS_enable_unused_var_check)", stacklevel=3)


class Executor:
    """reference: python/paddle/fluid/executor.py:461 Executor."""

    def __init__(self, place: Optional[Place] = None):
        self.place = _get_paddle_place(place)
        self._cache: Dict[tuple, _Compiled] = {}
        # serializes compilation: predictor clones share one Executor
        # (inference/predictor.py clone), so two workers' first runs on
        # the same shapes must not both pay the XLA compile or race the
        # cache insert; steady-state runs only pay an uncontended
        # acquire
        self._compile_lock = threading.Lock()
        self._closed = False
        self._step_no = 0
        # the step path's instruments, resolved here and not by name on
        # every step (FLAGS_telemetry honoured: utils/telemetry.Handles)
        self._tm = tm.Handles(
            hits=("counter", "executor_compile_cache_hits_total",
                  "Executor._compile cache hits"),
            misses=("counter", "executor_compile_cache_misses_total",
                    "Executor._compile cache misses (fresh trace+jit "
                    "construction)"),
            build_s=("histogram", "executor_compile_build_s",
                     "IR-pipeline + trace/jit construction seconds per "
                     "cache miss (XLA compilation itself is lazy: it "
                     "lands in the first step's executor_step_s)"),
            invalidations=(
                "counter", "executor_step_session_invalidations_total",
                "step sessions dropped because the scope was mutated "
                "outside the executor's own writeback"),
            feed_conversions=(
                "counter", "executor_feed_conversions_total",
                "feed arrays cast to the program dtype on the step path "
                "(stage the right dtype to avoid the copy)"),
            step_s=("histogram", "executor_step_s",
                    "Executor.run wall seconds (host dispatch; device "
                    "work may still be in flight — fetches are lazy)"))

    def _nhwc_enabled(self) -> bool:
        """FLAGS_tpu_nhwc resolved against this executor's place
        ("auto" -> on-accelerator only)."""
        from .utils.flags import nhwc_enabled

        return nhwc_enabled(self.place)

    def _tpu_fuse_enabled(self) -> bool:
        """FLAGS_tpu_fuse resolved against this executor's place
        ("auto" -> on-accelerator only)."""
        from .utils.flags import tpu_fuse_enabled

        return tpu_fuse_enabled(self.place)

    def _plan_compile_memory(self, program, block, feed, fetch_names,
                             where, scope=None):
        """Static HBM plan for one compilation — built, gauged,
        budget-checked and traced by the shared
        ``memory_plan.plan_and_surface`` (one surfacing path for the
        executor and the DP runner)."""
        from .framework import memory_plan as mp

        return mp.plan_and_surface(program, where, feed_names=feed,
                                   fetch_names=fetch_names, block=block,
                                   ndev=1, scope=scope)

    @staticmethod
    def _tp_signature(program):
        """Hashable cache-key element for a TP serving program: the
        mesh axis, degree, and exact device list (None everywhere
        else, so non-TP keys are unchanged)."""
        tp = getattr(program, "_tp_shard", None)
        if tp is None:
            return None
        return (tp["axis"], int(tp["degree"]),
                tuple(str(d) for d in tp["mesh"].devices.flat))

    # ------------------------------------------------------------------
    def run(
        self,
        program=None,
        feed: Optional[Dict[str, Any]] = None,
        fetch_list: Optional[Sequence] = None,
        feed_var_name: str = "feed",
        fetch_var_name: str = "fetch",
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
        use_program_cache: bool = True,
        use_prune: bool = False,
    ):
        if self._closed:
            raise RuntimeError("Executor is closed")
        from .parallel.compiled_program import CompiledProgram

        if isinstance(program, CompiledProgram):
            return program._run(self, feed, fetch_list, scope, return_numpy)
        if program is None:
            program = default_main_program()
        if getattr(program, "_pipeline_opt", None):
            from .parallel.pipeline import run_pipeline

            return run_pipeline(self, program, feed, fetch_list, scope,
                                return_numpy)
        self._step_no += 1
        with RecordEvent("executor/step") as step:
            if step.recording:
                step.set(program=program_label(program), step=self._step_no)
            scope = scope or global_scope()
            feed = dict(feed or {})
            fetch_names = [_fetch_name(f) for f in (fetch_list or [])]
            compiled = self._compile(program, feed, fetch_names, scope)
            return self._execute(compiled, feed, fetch_names, scope,
                                 return_numpy, program)

    # ------------------------------------------------------------------
    def _compile(self, program: Program, feed, fetch_names, scope) -> _Compiled:
        with self._compile_lock:
            return self._compile_locked(program, feed, fetch_names, scope)

    def _compile_locked(self, program: Program, feed, fetch_names,
                        scope) -> _Compiled:
        from .utils.flags import flag

        check_nan_inf = bool(flag("check_nan_inf"))
        unused_check = bool(flag("enable_unused_var_check"))
        ir_passes = bool(flag("apply_ir_passes"))
        donate = bool(flag("tpu_donate_buffers"))
        nhwc = self._nhwc_enabled()
        feed_spec = tuple(
            sorted(
                (k, tuple(np.shape(v)),
                 str(v.dtype) if hasattr(v, "dtype") else str(np.asarray(v).dtype))
                for k, v in feed.items()
            )
        )
        from .framework import numerics as _numerics
        from .utils import chaos as _chaos
        from .utils.cost_model import calibration_version

        key = (program._uid, program._version, feed_spec, tuple(fetch_names),
               check_nan_inf, unused_check, ir_passes, donate, nhwc,
               self._tpu_fuse_enabled(),
               str(flag("fuse_grad_size_in_MB")),
               str(flag("dp_grad_compress", "none")),
               int(flag("dp_sharding") or 0), bool(flag("dp_comm_overlap")),
               bool(flag("while_static_scan")),
               # FLAGS_dp_plan participates even though the search runs
               # on the DP path: flipping it must never serve a compile
               # built under the other regime
               str(flag("dp_plan", "") or ""),
               # a new measured profile can move autotuned bucket
               # boundaries — stale compilations must not be reused
               calibration_version(),
               # memory relief rewrites the traced program: flipping the
               # mode or the HBM budget must never serve a compilation
               # built under the other regime
               str(flag("memory_relief", "off") or "off"),
               str(flag("hbm_budget_mb") or 0),
               # probe config + any armed chaos NaN injection: step K of
               # a nan_inject schedule must trace the poisoned variant
               # and step K+1 must fall back to the clean cached one
               _numerics.probe_signature(), _chaos.nan_poison_target(),
               # tensor-parallel serving: the same program compiled over
               # a different mesh/degree is a different executable
               self._tp_signature(program))
        handles = self._tm.current()
        hit = self._cache.get(key)
        if hit is not None:
            handles.hits.inc()
            return hit
        handles.misses.inc()
        with RecordEvent("executor/compile", timed=True) as build:
            compiled = self._compile_miss(
                program, feed, fetch_names, scope, check_nan_inf,
                unused_check, donate)
            self._cache[key] = compiled
        handles.build_s.observe(build.end - build.begin)
        return compiled

    def _compile_miss(self, program: Program, feed, fetch_names, scope,
                      check_nan_inf, unused_check, donate) -> _Compiled:
        """A cache miss: the IR passes and the construction of the step
        function (XLA compilation itself is lazy: it lands in the first
        call)."""
        from .framework import numerics as _numerics

        label = program_label(program)

        tp_shard = getattr(program, "_tp_shard", None)
        src_block = program.global_block()
        program = self._apply_ir_passes(
            program, fetch_names, feed_names=tuple(sorted(feed)),
            scope=scope,
            # single-device compile: remat/offload only — there is no
            # parallel plan to escalate.  TP serving programs are never
            # relieved (the shard_map trace must match the engine's
            # weight placement op-for-op)
            relief_ctx=(None if tp_shard is not None
                        else {"ndev": 1, "allow_escalate": False}))
        if tp_shard is not None and program is not src_block.program:
            # the IR pipeline cloned through a desc round-trip, which
            # drops python-side sharding annotations — re-attach them so
            # the shard_map in/out specs below see the placements
            nb = program.global_block()
            for name, v in src_block.vars.items():
                s = getattr(v, "_sharding", None)
                if s is not None and name in nb.vars:
                    nb.vars[name]._sharding = s
        from .framework import verifier

        if verifier.enabled():
            # FLAGS_verify_passes: beyond the per-pass snapshot gate
            # (ir.Pass.apply), lint the FINAL program once per
            # compilation
            verifier.lint_or_raise(program, feed, fetch_names,
                                   "executor_compile")
        block = program.global_block()
        state_in, state_out, uses_rng, has_host_ops = analyze_state(
            block.ops, block, feed, scope
        )

        # feed-conversion plan: the target numpy dtype per feed name is a
        # compile-time fact (the cache key pins feed names/shapes/dtypes),
        # so the per-step loop never consults block vars again
        feed_plan = build_feed_plan(block, feed)

        # static HBM plan (framework/memory_plan.py): modeled per-device
        # liveness timeline + peak, attached for introspection, gauged,
        # and checked against FLAGS_hbm_budget_mb.  Pure analysis — the
        # program and the traced computation are untouched.
        mem_plan = self._plan_compile_memory(program, block, feed,
                                             fetch_names,
                                             "executor_compile", scope)

        ops = list(block.ops)
        if unused_check:
            _report_unused_vars(ops, fetch_names, state_out)
        fetch = list(fetch_names)
        # numerics probe (FLAGS_numerics_probe): the pass left one
        # packed stats vector — fetch it alongside the user's fetches;
        # _execute strips it and routes it to numerics.on_step
        n_layout = getattr(program, "_numerics_layout", None)
        if n_layout:
            fetch.append(_numerics.STATS_VAR)
        souts = list(state_out)

        if has_host_ops and tp_shard is not None:
            raise RuntimeError(
                "tensor-parallel serving programs cannot contain host "
                "ops: the whole step must trace into one shard_map")
        if has_host_ops:
            # Hybrid path (PS programs): host (RPC) ops run eagerly on
            # the Python side; the XLA ops BETWEEN them are grouped into
            # maximal segments, each traced+jitted once — so a PS step
            # costs a handful of device dispatches instead of one per op.
            # (The reference's op-by-op Executor loop, executor.cc:469-476,
            # pays per-op kernel launches; segment-jit is the TPU-native
            # improvement on it.)  check_nan_inf falls back to fully
            # eager execution so per-op outputs stay inspectable.
            segments: List[tuple] = []
            cur: List = []
            for op_ in ops:
                if registry.op_contains_host(op_):
                    if cur:
                        segments.append(("jit", cur))
                        cur = []
                    segments.append(("host", op_))
                else:
                    cur.append(op_)
            if cur:
                segments.append(("jit", cur))

            # per-segment IO: inputs read before produced inside; outputs
            # that later ops / fetches / state_out actually consume
            later_reads: List[set] = [set()] * len(segments)
            acc: set = set(fetch) | set(souts)
            for i in range(len(segments) - 1, -1, -1):
                later_reads[i] = set(acc)
                kind, payload = segments[i]
                seg_ops = [payload] if kind == "host" else payload
                for op_ in seg_ops:
                    acc.update(op_.input_arg_names)

            # vars any host op reads: after a jit segment produces one,
            # start its D2H copy immediately so the transfers pipeline
            host_reads: set = set()
            for kind, payload in segments:
                if kind == "host":
                    host_reads.update(payload.input_arg_names)

            jitted_segs: Dict[int, tuple] = {}
            if not check_nan_inf:
                for i, (kind, payload) in enumerate(segments):
                    if kind != "jit":
                        continue
                    produced: List[str] = []
                    needed: List[str] = []
                    prodset: set = set()
                    stateful = False
                    for op_ in payload:
                        d = registry.OPS.get(op_.type)
                        if d is not None and d.stateful:
                            stateful = True
                        for n in op_.input_arg_names:
                            if (n not in prodset and n != "@EMPTY@"
                                    and n not in needed):
                                needed.append(n)
                        for n in op_.output_arg_names:
                            if n != "@EMPTY@" and n not in prodset:
                                prodset.add(n)
                                produced.append(n)
                    if stateful:
                        if RNG_VAR not in needed:
                            needed.append(RNG_VAR)
                        prodset.add(RNG_VAR)
                        if RNG_VAR not in produced:
                            produced.append(RNG_VAR)
                    outs = [n for n in produced
                            if n in later_reads[i] or n == RNG_VAR]

                    def make_seg(seg_ops=payload, outs=tuple(outs)):
                        def seg_fn(in_vals):
                            env: Dict[str, Any] = dict(in_vals)
                            for op_ in seg_ops:
                                registry.run_op(op_, env, block)
                            return {n: env[n] for n in outs if n in env}
                        return jax.jit(named_step(seg_fn, label + "_seg"))

                    jitted_segs[i] = (tuple(needed), make_seg())

            def hybrid_call(feed_vals, state_vals):
                from .profiler import RecordEvent

                env: Dict[str, Any] = dict(state_vals)
                env.update(feed_vals)
                for i, (kind, payload) in enumerate(segments):
                    if kind == "host":
                        with RecordEvent(payload.type):
                            registry.run_op(payload, env, block)
                        if check_nan_inf:
                            _eager_nan_check(payload, env)
                    elif i in jitted_segs:
                        needed, jfn = jitted_segs[i]
                        with RecordEvent("jit_segment"):
                            in_vals = {n: env[n] for n in needed
                                       if n in env}
                            out_vals = jfn(in_vals)
                            env.update(out_vals)
                            for n, v in out_vals.items():
                                if n in host_reads and hasattr(
                                        v, "copy_to_host_async"):
                                    v.copy_to_host_async()
                    else:  # check_nan_inf: eager op-by-op
                        for op_ in payload:
                            with RecordEvent(op_.type):
                                registry.run_op(op_, env, block)
                            _eager_nan_check(op_, env)
                fetched = tuple(env[n] for n in fetch)
                new_state = {n: env[n] for n in souts if n in env}
                return fetched, new_state

            compiled = _Compiled(hybrid_call, state_in, state_out, fetch)
            compiled.raw_fn = hybrid_call
            compiled.hybrid = True
            compiled.feed_plan = feed_plan
            compiled._memory_plan = mem_plan
            compiled.numerics = n_layout
            return compiled

        # Donate only buffers that are both read and re-written (params,
        # optimizer moments): XLA updates them in place in HBM.  Read-only
        # state (eval-program params) must NOT be donated or the scope's
        # live buffers would be invalidated.
        donatable = [n for n in state_in if n in set(state_out)]
        readonly = [n for n in state_in if n not in set(state_out)]

        def fn(mut_vals: Dict[str, Any], ro_vals: Dict[str, Any],
               feed_vals: Dict[str, Any]):
            env: Dict[str, Any] = dict(ro_vals)
            env.update(mut_vals)
            env.update(feed_vals)
            for op_ in ops:
                registry.run_op(op_, env, block)
                if check_nan_inf:
                    _traced_nan_check(op_, env)
            fetched = tuple(env[n] for n in fetch)
            new_state = {n: env[n] for n in souts if n in env}
            return fetched, new_state

        if tp_shard is not None:
            # tensor-parallel serving (FLAGS_serving_tp > 1): the whole
            # traced step runs under shard_map over the serving mesh —
            # each rank executes the SHARD program on its 1/tp of the
            # weights and KV pool, the inserted c_* collectives resolve
            # their mesh axis through the ring registry, and fetches
            # (tokens) come back replicated.  State in/out specs follow
            # the per-var logical-axis annotations; feeds are replicated.
            from jax.sharding import PartitionSpec as _P

            def _pspec(name):
                v = block._find_var_recursive(name)
                s = getattr(v, "_sharding", None) if v is not None else None
                return _P(*s) if s else _P()

            in_specs = ({n: _pspec(n) for n in donatable},
                        {n: _pspec(n) for n in readonly},
                        {n: _P() for n in feed})
            out_specs = (tuple(_P() for _ in fetch),
                         {n: _pspec(n) for n in souts})
            fn = jax.shard_map(fn, mesh=tp_shard["mesh"],
                               in_specs=in_specs, out_specs=out_specs,
                               check_vma=False)
        fn = named_step(fn, label)

        if check_nan_inf:
            # FLAGS_check_nan_inf (reference: operator.cc:1020
            # CheckOpHasNanOrInf) — functionalize the per-op checks with
            # checkify so they survive jit, then re-raise on host.
            from jax.experimental import checkify

            checked = named_step(
                checkify.checkify(fn, errors=checkify.user_checks), label)
            # no donation here: when the check raises, the scope still
            # points at the input buffers — donating them would brick the
            # session on backends that honor donation, defeating the
            # debug flag's purpose (inspecting state after the NaN).
            jitted_inner = jax.jit(checked)

            def jitted(mut_vals, ro_vals, feed_vals):
                err, out = jitted_inner(mut_vals, ro_vals, feed_vals)
                checkify.check_error(err)
                return out
        else:
            # donation is disabled under the multi-thread trainer: with N
            # Hogwild workers sharing the parent scope's param buffers, a
            # donated buffer consumed by worker A would be a deleted
            # buffer in worker B's already-captured argument list
            jitted = jax.jit(fn, donate_argnums=(0,) if donate else ())
        compiled = _Compiled(jitted, state_in, state_out, fetch)
        compiled.raw_fn = fn
        compiled.tp_shard = tp_shard
        compiled.donatable = tuple(donatable)
        compiled.readonly = tuple(readonly)
        compiled.feed_plan = feed_plan
        compiled._memory_plan = mem_plan
        compiled.numerics = n_layout
        return compiled

    # ------------------------------------------------------------------
    def _apply_ir_passes(self, program: Program, fetch_names,
                         feed_names=(), scope=None, relief_ctx=None,
                         auto_partitioned=False):
        """Training-time fusion pipeline (reference: BuildStrategy
        fuse_bn_act_ops / fuse_bn_add_act_ops applied in
        parallel_executor.cc:581).  Runs on a clone so the user's program
        stays introspectable; the compile cache is keyed on the original
        program, so the clone+rewrite happens once per compilation.

        When ``relief_ctx`` is given (a dict of memory_relief_pass
        attrs: ndev / stage / use_shard_map / allow_escalate / ...) and
        ``FLAGS_memory_relief`` != off with an HBM budget set, the
        relief pass joins the pipeline after every fusion pass (it must
        price the final op stream) and before the numerics probe (the
        probes must see the relieved program); its decision report is
        attached to the clone as ``_memory_relief`` for
        ``plan_and_surface`` to pick up.

        ``auto_partitioned``: XLA's SPMD partitioner will split this
        program over a mesh (the pjit data-parallel path).  Mosaic
        kernels cannot be partitioned automatically ("wrap the call in
        a shard_map"), so the Pallas epilogue fuser stays out of such a
        program; per-device programs (one chip, shard_map) keep it."""
        from .utils.flags import flag

        from .framework.ir import _FUSABLE_OPT, PassManager, get_pass

        types = {o.type for b in program.blocks for o in b.ops}
        protected = tuple(fetch_names)
        passes = []
        sharding_stage = int(flag("dp_sharding") or 0)
        has_collectives = any(t.startswith("c_") for t in types)
        if not flag("apply_ir_passes"):
            types = set()  # skip the rewrite pipeline, not the probe
        if "batch_norm" in types:
            passes += [get_pass("fuse_bn_add_act_pass", protected=protected),
                       get_pass("fuse_bn_act_pass", protected=protected)]
        if types & set(_FUSABLE_OPT):
            if not (sharding_stage >= 1 and has_collectives):
                # FLAGS_dp_sharding on the collective path keeps
                # per-parameter update ops: the DP runner's shard-aware
                # wrapper slices each (param, grad, state) individually,
                # which the multi-tensor fused forms would defeat
                passes.append(get_pass("fuse_optimizer_ops_pass"))
        if self._nhwc_enabled() and types & {"conv2d", "depthwise_conv2d"}:
            # after the bn fusions so the NHWC walk sees the fused ops
            passes.append(get_pass("layout_transform_pass",
                                   protected=protected))
        if self._tpu_fuse_enabled() and not auto_partitioned and types & {
                "conv2d", "depthwise_conv2d", "mul", "matmul", "matmul_v2"}:
            # profile-ranked Pallas epilogue fusion (r14), AFTER the
            # bn-act and layout passes: the chain walk then sees the
            # fused BN forms in their final layout (fuse-after-layout;
            # the reverse order is verifier-clean too, but this one
            # avoids teaching the layout pass about freshly fused ops
            # mid-pipeline)
            passes.append(get_pass("fuse_epilogue_pass",
                                   protected=protected))
        if "c_allreduce_sum" in types:
            from .utils.flags import fuse_grad_mb_auto, fuse_grad_mb_value

            auto = fuse_grad_mb_auto()
            mb = fuse_grad_mb_value()
            if mb > 0 or auto:
                # coalesce per-tensor grad allreduces (the shard_map DP
                # path) into bucketed fused collectives, scheduled for
                # backward overlap (and reduce-scattered under ZeRO-2);
                # "auto" derives variable boundaries from the modeled
                # backward timeline instead of the fixed threshold
                from .parallel.mesh import ring_axis_size

                passes.append(get_pass(
                    "fuse_all_reduce_pass",
                    max_bytes=int(mb * (1 << 20)),
                    compress=str(flag("dp_grad_compress", "none")),
                    overlap=bool(flag("dp_comm_overlap")),
                    sharding_stage=sharding_stage,
                    ndev=ring_axis_size(0),
                    autotune=auto and bool(flag("dp_comm_overlap"))))
        relief = None
        if relief_ctx is not None:
            from .framework import memory_plan as _mp

            mode = str(flag("memory_relief", "off") or "off")
            if mode != "off" and _mp.budget_bytes() > 0:
                relief = get_pass("memory_relief_pass", mode=mode,
                                  feed_names=tuple(feed_names),
                                  fetch_names=tuple(fetch_names),
                                  scope=scope, **relief_ctx)
                passes.append(relief)
        from .framework import numerics as _numerics

        if _numerics.probe_armed():
            # LAST in the pipeline: probes read final values, so every
            # rewrite (fusion, layout, bucketing, relief) must already
            # have happened — the probed var set is the compiled
            # program's
            passes.append(get_pass("numerics_probe_pass",
                                   ops_regex=_numerics.probe_ops_regex()))
        shard_gate = None
        if has_collectives and flag("shard_safety"):
            # after even the numerics probe: the analyzer checks the
            # probe's cross-shard stat contract too.  Analysis only —
            # warns (or raises under FLAGS_shard_safety_strict), never
            # rewrites, and non-collective programs skip it entirely,
            # so defaults stay bit-identical.
            shard_gate = get_pass("shard_safety_pass",
                                  feed_names=tuple(feed_names),
                                  fetch_names=tuple(fetch_names),
                                  where="executor_compile")
        if not passes:
            if shard_gate is not None:
                # no rewrite pipeline to run: gate the original program
                # directly instead of paying a full desc-dict clone for
                # an analysis that cannot mutate it
                shard_gate.apply(program)
            return program
        if shard_gate is not None:
            passes.append(shard_gate)
        clone = Program.from_desc_dict(program.desc_dict())
        clone.random_seed = program.random_seed
        PassManager(passes).apply(clone)
        if relief is not None and relief.report is not None:
            clone._memory_relief = relief.report
        return clone

    # ------------------------------------------------------------------
    def _execute(self, compiled, feed, fetch_names, scope, return_numpy, program):
        handles = self._tm.current()
        device = self.place.jax_device()
        tp_shard = getattr(compiled, "tp_shard", None)
        if tp_shard is not None:
            # TP serving: feeds and any host-side state stage REPLICATED
            # over the serving mesh (the shard_map in_specs say P());
            # sharded weights/pools arrive as already-placed jax arrays
            # from the engine and pass through state_val untouched
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as _P

            device = NamedSharding(tp_shard["mesh"], _P())

        # ---- feed conversion: plan precomputed at compile time (dtype
        # per name), so the step loop does no block-var lookups.  The
        # H2D transfers are issued FIRST and asynchronously (device_put
        # returns before the copy lands), so the host-side state binding
        # below overlaps the transfer — the same pipelining idea as the
        # hybrid path's copy_to_host_async D2H (double-buffering: while
        # step N's dispatch consumes the staged feed, step N+1's run()
        # call starts its transfer before touching state).
        plan = compiled.feed_plan or {}
        hybrid = compiled.hybrid
        feed_vals = {}
        n_feed_conv = 0
        with RecordEvent("executor/feed", timed=True) as feed_span:
            for k, v in feed.items():
                if isinstance(v, LoDTensor):
                    v = v.value()
                if isinstance(v, jax.Array):
                    # already on device: skip even the device_put no-op
                    # when placement matches (the bench/reader staged path)
                    feed_vals[k] = v if v.devices() == {device} \
                        else jax.device_put(v, device)
                    continue
                arr = np.asarray(v)
                want = plan.get(k)
                if want is not None and arr.dtype != want:
                    arr = arr.astype(want)
                    n_feed_conv += 1
                # hybrid (PS) programs: keep feeds host-side — host ops
                # (e.g. distributed_lookup_table reading feed ids) then
                # cost no D2H round-trip; jit segments device_put what
                # they consume
                feed_vals[k] = arr if hybrid else jax.device_put(arr, device)
            if feed_span.recording:
                feed_span.set(
                    bytes=int(sum(getattr(v, "nbytes", 0)
                                  for v in feed_vals.values())),
                    arrays_cast=n_feed_conv)

        def state_val(name, donated=False):
            if name == RNG_VAR:
                val = scope.get(RNG_VAR)
                if val is None:
                    from .utils.prng import prng_key

                    seed = program.random_seed or 0
                    val = prng_key(seed)
                return val
            val = scope.get(name)
            if val is None:
                raise RuntimeError(
                    f"Variable {name!r} is read by the program but has no "
                    f"value in scope — run the startup program first or feed it"
                )
            if isinstance(val, jax.Array):
                return val
            if isinstance(val, LoDTensor):
                val = val.numpy()
            if isinstance(val, np.ndarray):
                # donated bindings must be XLA-owned: a zero-copy
                # device_put alias must never be donated (see
                # device_put_owned)
                val = device_put_owned(val, device) if donated \
                    else jax.device_put(val, device)
            return val

        from .utils.flags import flag as _flag

        use_session = not hybrid and bool(_flag("tpu_step_session", True))

        def bind():
            """The step's state: hybrid programs read the scope; the hot
            path has its mut/ro partition precomputed at compile time
            and binds from the step session when the scope hasn't been
            touched since our own writeback — zero scope reads a step."""
            if hybrid:
                return {n: state_val(n) for n in compiled.state_in}, None
            sess = compiled.session if use_session else None
            bound = None
            if (sess is not None and sess.scope_ref() is scope
                    and sess.stamp == Scope.mutation_counter):
                bound = sess.deref()
            if bound is not None:
                return bound
            if sess is not None:
                # stale — drop promptly (an external scope write
                # invalidated the device-resident binding)
                compiled.session = None
                handles.invalidations.inc()
            return ({n: state_val(n, donated=True)
                     for n in compiled.donatable},
                    {n: state_val(n) for n in compiled.readonly})

        def dispatch():
            with RecordEvent("executor_run"):
                with RecordEvent("executor/bind"):
                    mut, ro = bind()
                with RecordEvent("executor/call") as call:
                    if hybrid:
                        f, ns = compiled.fn(feed_vals, mut)
                    else:
                        f, ns = compiled.fn(mut, ro, feed_vals)
                    if call.recording:
                        # which compiled step ran, for the device's rows
                        # (profiler.device_symbols): once an entry, shapes
                        # only (the donated arrays keep theirs)
                        note_program(program_label(program), compiled.fn,
                                     (mut, ro, feed_vals))
                return f, ns, ro

        try:
            fetched, new_state, ro_bound = dispatch()
        except Exception as e:
            # OOM flight recorder: a device RESOURCE_EXHAUSTED dumps
            # plan + telemetry + trace to FLAGS_oom_debris_dir, then
            # propagates unchanged
            from .framework import memory_plan as mp
            from .framework import numerics as nm

            if mp.is_resource_exhausted(e):
                mp.record_oom_debris("executor_step", e,
                                     plan=compiled._memory_plan,
                                     program=program)
            # NaN/Inf flight recorder: an armed FLAGS_check_nan_inf
            # failure (eager or checkify path) dumps the failing op +
            # stats ring to FLAGS_numerics_debris_dir, then propagates
            # unchanged
            nm.maybe_record_check_failure("executor_step", e,
                                          program=program)
            raise
        finally:
            # a chaos nan_inject armed for THIS step is spent once the
            # dispatch ran (or raised) — it must never leak into a
            # later unrelated compile when no further on_step disarms
            from .utils import chaos as _chaos_mod

            if _chaos_mod.nan_poison_target() is not None:
                _chaos_mod.consume_nan_poison()
        if compiled.numerics:
            # probe stream: strip the packed stats vector off the fetch
            # tail and feed the three consumers (telemetry, the
            # HealthMonitor, capture sinks).  np.asarray is the step's
            # one forced device sync — armed-probe cost only.
            from .framework import numerics as nm

            with RecordEvent("executor/probe"):
                nm.on_step(compiled.numerics, np.asarray(fetched[-1]),
                           where="executor")
            fetched = fetched[:-1]
        with RecordEvent("executor/writeback", timed=True) as writeback:
            scope_set = scope.set
            for name, val in new_state.items():
                scope_set(name, val)
            if use_session:
                # rebind next step's state from this step's outputs: the
                # donated input buffers are dead, their replacements are
                # in new_state (now also held by the scope); read-only
                # state is still alive as-is
                try:
                    mut_refs = {n: weakref.ref(new_state[n])
                                for n in compiled.donatable}
                except (KeyError, TypeError):
                    # a donated var wasn't produced, or a state value
                    # isn't weakref-able (SelectedRows pytree) — no
                    # session
                    compiled.session = None
                else:
                    compiled.session = _StateSession(
                        weakref.ref(scope), Scope.mutation_counter,
                        mut_refs, ro_bound)
            elif not hybrid:
                compiled.session = None

            if n_feed_conv:
                handles.feed_conversions.inc(n_feed_conv)
        # feed to write-back, from the spans' own stamps: the host's
        # dispatch, not the fetch's wait for the device
        handles.step_s.observe(writeback.end - feed_span.begin)

        if fetch_names:
            with RecordEvent("executor/fetch"):
                if return_numpy:
                    return [as_numpy(v) for v in fetched]
                # keep device arrays lazy — no host sync until .numpy().
                # SelectedRows fetches densify (still lazy on device) so
                # the LoDTensor surface stays array-like.
                from .framework.selected_rows import SelectedRows

                return [LoDTensor(v.to_dense() if isinstance(v, SelectedRows)
                                  else v) for v in fetched]
        return None

    # ------------------------------------------------------------------
    def close(self):
        self._closed = True
        self._cache.clear()

    # dataset-driven training (reference: executor.py:1448) — phase 8
    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        from .reader import _train_from_dataset

        return _train_from_dataset(self, program, dataset, scope, fetch_list,
                                   fetch_info, print_period, thread=thread)

    def infer_from_dataset(self, *args, **kwargs):
        return self.train_from_dataset(*args, **kwargs)


def scope_var_to_numpy(scope: Scope, name: str) -> np.ndarray:
    return as_numpy(scope.get(name))


def snapshot_scope_state(scope: Scope, names) -> Dict[str, Any]:
    """Non-blocking checkpoint snapshot of scope state.

    After a step, the scope's entries for donated state ARE the step
    session's device-resident arrays (`_StateSession` writeback keeps
    them identical objects), so reading them here costs no device sync;
    ``copy_to_host_async`` starts every device->host transfer
    immediately so they pipeline while the caller keeps training.  The
    returned values stay device arrays — jax arrays are immutable, so
    the captured references pin the step-N values even while later
    steps produce replacements (the checkpoint writer materializes them
    on its own thread).  Names absent from the scope are skipped."""
    state: Dict[str, Any] = {}
    for n in names:
        v = scope.get(n)
        if v is None:
            continue
        if isinstance(v, LoDTensor):
            v = v.numpy()
        if hasattr(v, "copy_to_host_async"):
            try:
                v.copy_to_host_async()
            except Exception:
                pass
        state[n] = v
    return state
