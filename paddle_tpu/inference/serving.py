"""Continuous-batching decode serving runtime.

Grows AnalysisPredictor's one-shot run() into a serving engine
(ROADMAP direction 1, "millions of users" made measurable):

* **Paged KV cache** — inference/kv_cache.py allocator over device pool
  vars the ``kv_cache_append`` op updates in place: the executor
  donates each pool to the step program (a var both read and written)
  and the append kernel aliases its pool operand onto its output,
  moving only the blocks it writes (ops/paged_ops.py).
* **Continuous (inflight) batching** — new requests are admitted at
  EVERY decode step up to a token budget, finished sequences are
  evicted (pages freed) immediately, and pool exhaustion mid-decode
  preempts a sequence back to the waiting queue (recompute-on-resume,
  deterministically).
* **Pluggable admission/preemption policy** (inference/admission.py,
  ``FLAGS_admission_policy``) — ``fifo`` (default) keeps FIFO admission
  + youngest-first preemption byte-identical to the pre-policy engine;
  ``slo_aware`` orders admission by remaining SLO slack, sheds queued
  requests whose predicted TTFT can no longer meet the declared target
  (explicit ``shed`` outcome, traced + countered), and preempts the
  least-lost-work victim.  ``utils/chaos.py`` serving faults
  (decode_delay / req_burst / pool_spike) hook into the step loop for
  the overload oracle (tools/overload_bench.py).
* **Ragged paged attention** — the decode program's ``paged_attention``
  op gathers each query's K/V through its block table at its true
  length (Pallas kernel on TPU, identical-semantics gather on CPU), so
  a mixed-length batch never pads to max-seq: feed shapes are bucketed
  to the longest ACTIVE sequence (pages) and the next batch-size
  bucket, never to the model maximum.

The hot loop stays device-resident: prefill and decode are ordinary
Programs run through the Executor's step session — weights and KV
pools live on device across steps, and the jit cache is bounded by
shape bucketing (batch sizes and block-table widths are powers of two,
prompt lengths power-of-two bucketed), so batch composition never
recompiles.

The engine serves a MODEL DESCRIPTION and never asks which: what it asks
is ``decoder_program.ServedModel``, what a program form offers a call
beyond its tokens is ``decoder_program.FormExtras``.  The descriptions
live in modules of their own (``gpt2_decoder.py``, ``mla_decoder.py``,
``gqa_decoder.py``), none of which imports this one.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..framework.place import CPUPlace
from ..framework.scope import Scope, scope_guard
from ..executor import Executor
from ..profiler import RecordEvent, instant_event, is_profiler_enabled
from ..utils import chaos
from ..utils import telemetry as tm
from ..utils import tracing
from .admission import RequestRejected, get_policy
from .decoder_program import (SERVING_TP_AXIS, SERVING_TP_RING_ID,
                              ServedModel, _pow2_bucket, _sampled)
# DecoderConfig and decoder_param_specs stay importable from here for
# benchmark/runners/serve_decoder.py (and the tests that take the GPT-2
# description from the engine's module); load_decoder_config is
# ``from_model_dir``'s
from .gpt2_decoder import (DecoderConfig, decoder_param_specs,  # noqa: F401
                           load_decoder_config)
from .kv_cache import KVCacheConfig, PagedKVCache
from .spec_decode import NGramProposer, Proposer, SamplingParams, \
    get_proposer, rng_lane

__all__ = [
    "DecoderConfig", "Request", "StepEvent", "ServingEngine",
    "RequestRejected", "SamplingParams",
    "SERVING_TP_AXIS", "SERVING_TP_RING_ID",
]

NEG_INF = -1e9  # additive causal-mask value (finite: padded rows stay NaN-free)


# ==========================================================================
# Requests / events
# ==========================================================================
@dataclass
class Request:
    req_id: object
    prompt: List[int]
    max_new_tokens: int
    arrival_time: float = 0.0
    # filled by the engine
    out_tokens: List[int] = field(default_factory=list)
    admitted_at: Optional[float] = None
    finished_at: Optional[float] = None
    # set when the admission policy shed this queued request (its SLO
    # was no longer reachable) — a third terminal outcome, distinct
    # from finish and from the unservable submit rejection
    shed_at: Optional[float] = None
    preemptions: int = 0
    # engine-assigned submit sequence number: the deterministic
    # tie-breaker slo_aware ordering sorts on (req_ids may be any type)
    _seq: int = field(default=0, repr=False)
    # telemetry: previous emit time of the CURRENT run (reset with
    # out_tokens on preemption, matching loadgen's final-run accounting)
    _tm_last: Optional[float] = field(default=None, repr=False)
    # per-token gaps of the CURRENT run (gaps[0] = TTFT; reset with
    # out_tokens on preemption) — the SLO tracker's per-request input
    _tm_gaps: List[float] = field(default_factory=list, repr=False)
    # the request's span tree (utils/tracing.py Trace) when this
    # request was head-sampled under FLAGS_trace_requests, else None
    trace: Optional[object] = field(default=None, repr=False)
    # prompt tokens served from cached prefix pages at the LAST
    # prefill (0 with FLAGS_kv_prefix_cache off) — feeds the
    # shared-page-aware preemption cost (admission.lost_work_cost)
    _prefix_hit: int = field(default=0, repr=False)


@dataclass(frozen=True)
class StepEvent:
    req_id: object
    token: int
    finished: bool
    time: float


@dataclass
class _SeqState:
    req: Request
    last_token: int = 0
    # pipelined steps only (``ServingEngine(pipeline=True)``): the row of the
    # core's token board that holds this sequence's pending token, and how
    # many tokens have been dispatched for it (``req.out_tokens`` lags a step)
    lane: int = -1
    sent: int = 0


# The schedulers' per-step instruments, each resolved at its first use
# (FLAGS_telemetry honoured) and not looked up by name under the
# registry's lock for every token: utils/telemetry.Handles.  A family
# nothing touched (no preemption, spec decode off) is not published.
_TM = tm.Handles(
    token_latency=("histogram", "serving_token_latency_s",
                   "per-token latency (inter-token gap; first token from "
                   "arrival)"),
    ttft=("histogram", "serving_ttft_s",
          "time to first token from arrival"),
    admitted=("counter", "serving_admitted_total",
              "requests admitted (prefilled)"),
    prefill_tokens=("counter", "serving_prefill_tokens_total",
                    "prompt tokens prefilled"),
    preempted=("counter", "serving_preempted_total",
               "sequences preempted to the waiting queue on pool "
               "exhaustion"),
    decode_steps=("counter", "serving_decode_steps_total",
                  "batched decode steps run"),
    decode_tokens=("counter", "serving_decode_tokens_total",
                   "tokens produced by decode steps"),
    finished=("counter", "serving_finished_total",
              "requests finished (pages evicted on finish)"),
    spec_proposed=("counter", "spec_proposed_total",
                   "draft tokens proposed to spec-decode verify"),
    spec_accepted=("counter", "spec_accepted_total",
                   "draft tokens accepted by spec-decode verify"),
    spec_accept_rate=("gauge", "spec_accept_rate",
                      "cumulative spec-decode draft acceptance rate"))


def _observe_token(req: Request, now: float):
    """Per-token latency into the registry, with loadgen's exact
    convention (utils/loadgen.py latency_report): every token's gap
    from the previous one, the FIRST token's gap measured from arrival
    — that first gap is also the TTFT observation.  After a preemption
    ``out_tokens`` (and ``_tm_last``) reset, so only the final run's
    tokens are observed from a fresh arrival baseline; histograms match
    loadgen's percentiles exactly on preemption-free traces (pinned by
    test) and approximately otherwise (loadgen retroactively drops the
    evicted run's tokens, an online observer cannot)."""
    first = len(req.out_tokens) == 1
    prev = req.arrival_time if first or req._tm_last is None \
        else req._tm_last
    gap = max(now - prev, 0.0)
    req._tm_gaps.append(gap)
    # the histogram -> trace exemplar link: a traced request's latency
    # observation carries its trace id, so a p99 bucket names a trace
    ex = req.trace.trace_id if req.trace is not None else None
    handles = _TM.current()
    handles.token_latency.observe(gap, exemplar=ex)
    if first:
        handles.ttft.observe(gap, exemplar=ex)
    req._tm_last = now


# ==========================================================================
# request-scoped tracing hooks (utils/tracing.py) — shared by both
# schedulers.  Every hook short-circuits on req.trace is None, so with
# FLAGS_trace_requests=0 (or an unsampled request) the scheduler runs
# the exact pre-tracing instruction stream (bit-identity pinned).
# ==========================================================================
def _trace_submit(req: Request):
    """Root + queue_wait spans at submit (head-sampled: the keep/drop
    decision is deterministic in (FLAGS_trace_seed, req_id))."""
    if not tracing.enabled() or not tracing.sampled(req.req_id):
        return
    tr = tracing.new_trace(req.req_id)
    req.trace = tr
    tr._root = tr.start("request", t=req.arrival_time, attrs={
        "req": str(req.req_id), "prompt_tokens": len(req.prompt),
        "max_new_tokens": req.max_new_tokens})
    tr._wait = tr.start("queue_wait", t=req.arrival_time, parent=tr._root)


def _trace_reject(req: Request, reason: str, reason_code: str = "unservable"):
    """A request rejected at submit still gets a (one-span) trace: the
    finish/reject leg of the span tree.  ``reason_code`` is the
    machine-readable reject reason (pool / budget / max_seq_len) —
    the span-side mirror of ``serving_rejects_total{reason=}``."""
    if not tracing.enabled() or not tracing.sampled(req.req_id):
        return
    tr = tracing.new_trace(req.req_id)
    root = tr.start("request", t=req.arrival_time,
                    attrs={"req": str(req.req_id),
                           "prompt_tokens": len(req.prompt)})
    tr.end(root, t=req.arrival_time,
           attrs={"status": "rejected", "reason": reason,
                  "reject_reason": reason_code})
    tr.finish()


def _trace_shed(req: Request, now: float):
    """A shed request closes its open wait span (queue_wait, or the
    preempted span of an evicted run) and its root with
    ``status="shed"`` — the third terminal leg of the span tree,
    distinct from finish and reject.  The SLO tracker is deliberately
    NOT fed: a shed request is excluded from the goodput denominators
    (the policy refused the work; nothing was served late)."""
    tr = req.trace
    if tr is None:
        return
    tr.end(tr._wait, t=now)
    tr._wait = None
    tr.end(tr._root, t=now, attrs={
        "status": "shed", "reject_reason": "shed",
        "waited_s": round(now - req.arrival_time, 9),
        "preemptions": req.preemptions})
    tr.finish()


def _trace_backpressure(req: Request, kind: str):
    """Pool backpressure repeats every step while the head request
    waits — a counter ATTR on the open wait span keeps the signal
    bounded (an event per blocked step would grow without limit)."""
    tr = req.trace
    if tr is not None and tr._wait is not None:
        tr._wait.attrs[kind] = tr._wait.attrs.get(kind, 0) + 1


def _trace_admit(req: Request, now: float, job: "_PrefillJob",
                 cached: int = 0, chunks: int = 0):
    """Successful prefill: close the open wait span (queue_wait, or the
    preempted span of a resume cycle) and record the prefill span with
    its real wall bounds: the end of the job's last ``engine/prefill``
    span and, before it, the summed time of all its slices (a 5-chunk
    prefill reports 5 chunks' worth of wall).  ``cached``/``chunks``
    annotate prefix-cache hits and chunked prefills — attrs appear ONLY
    when the features engaged, so flag-off span streams stay
    byte-identical to r18."""
    tr = req.trace
    if tr is None:
        return
    wall0, wall1 = job.wall_end - job.wall_s, job.wall_end
    tr.end(tr._wait, t=now)
    tr._wait = None
    attrs = {"prompt_tokens": len(req.prompt),
             "resume": req.preemptions}
    if cached:
        attrs["cached_tokens"] = cached
    if chunks > 1:
        attrs["chunks"] = chunks
    tr.add("prefill", t0=now, wall0=wall0, wall1=wall1, parent=tr._root,
           attrs=attrs)


def _trace_decode(states: Sequence["_SeqState"], toks: Sequence[int],
                  now: float, wall0: float, wall1: float, step_no: int,
                  spec: Optional[Sequence[tuple]] = None, tp: int = 1):
    """One decode-step span per TRACED request in the batch (shared
    wall bounds: the batch runs as one program).  ``spec`` (the
    speculative path only) carries per-request ``(proposed, accepted)``
    draft counts — the attrs appear ONLY when spec decode engaged, so
    flag-off span streams stay byte-identical (the r19 pattern).
    ``tp`` > 1 (tensor-parallel decode) annotates the TP degree the
    same engage-only way."""
    for i, (st, tok) in enumerate(zip(states, toks)):
        tr = st.req.trace
        if tr is not None:
            attrs = {"step": step_no, "batch": len(states),
                     "token": int(tok)}
            if spec is not None:
                attrs["proposed"] = int(spec[i][0])
                attrs["accepted"] = int(spec[i][1])
            if tp > 1:
                attrs["tp"] = int(tp)
            tr.add("decode_step", t0=now, wall0=wall0, wall1=wall1,
                   parent=tr._root, attrs=attrs)


def _trace_preempt(req: Request, now: float):
    """Preemption opens a `preempted` span — the wait leg of this
    preempt/resume cycle; the resume's prefill closes it."""
    tr = req.trace
    if tr is None:
        return
    tr._wait = tr.start("preempted", t=now, parent=tr._root,
                        attrs={"cycle": req.preemptions})


def _trace_finish(req: Request, now: float):
    """Close the root span with the request's outcome and feed the SLO
    tracker (the tracker sees EVERY finished request — sampling only
    gates span recording, never the goodput denominators)."""
    tr = req.trace
    if tr is not None:
        attrs = {"status": "finished", "tokens": len(req.out_tokens),
                 "preemptions": req.preemptions}
        if req._tm_gaps:
            attrs["ttft_s"] = round(req._tm_gaps[0], 9)
        tr.end(tr._root, t=now, attrs=attrs)
        tr.finish()
    if tm.enabled():
        tm.slo_tracker().observe_request(
            req.req_id,
            ttft_s=req._tm_gaps[0] if req._tm_gaps else float("nan"),
            decode_gaps=req._tm_gaps[1:],
            trace_id=tr.trace_id if tr is not None else None,
            prefix_hit_tokens=req._prefix_hit,
            prompt_tokens=len(req.prompt))


_MASK_CACHE: Dict[int, np.ndarray] = {}


def _causal_mask(s: int) -> np.ndarray:
    # memoized per bucket: prefill and the oracle loop re-feed the same
    # handful of pow2 sizes thousands of times on the hot path
    m = _MASK_CACHE.get(s)
    if m is None:
        m = np.triu(np.full((s, s), NEG_INF, np.float32), k=1)[None, None]
        _MASK_CACHE[s] = m
    return m


def _worst_case_pages(req: Request, kv_config: KVCacheConfig) -> int:
    total = len(req.prompt) + req.max_new_tokens
    return -(-total // kv_config.page_size)


@dataclass
class _PrefillJob:
    """In-flight prefill of one request: ``pos`` tokens are already in
    the pool (prefix-cache hit + completed chunks), ``first_token`` is
    set when the final slice ran.  For a traced request ``wall_s``
    accumulates every slice's ``engine/prefill`` span so the request's
    prefill span covers ALL chunks, not just the completing one, and
    ``wall_end`` is the end of the last."""
    req: Request
    pos: int = 0
    hit: int = 0
    chunks: int = 0
    first_token: Optional[int] = None
    wall_s: float = 0.0
    wall_end: Optional[float] = None


_FORK_COPY = None


def _fork_copy_fn():
    """Jitted whole-page pool copy for CoW forks: ``pool[:, dst] =
    pool[:, src]`` with the pool donated (in-place in HBM, the pool is
    never duplicated).  Slots past the fork's valid count are garbage
    the appends that triggered the fork (and the masks) never read."""
    global _FORK_COPY
    if _FORK_COPY is None:
        import jax

        def copy(pool, src, dst):
            return pool.at[:, dst].set(pool[:, src])

        _FORK_COPY = jax.jit(copy, donate_argnums=(0,))
    return _FORK_COPY


_BOARD_FNS = None


def _board_fns():
    """Jitted ``(take, put)`` over the token board, a device vector with one
    row a running sequence: ``take(board, idx)`` is the decode call's
    ``tokens`` feed (a row index past the board reads 0: padding), and
    ``put(board, idx, toks)`` writes a call's tokens back (such an index
    writes nothing).  With them a token goes from the call that made it to
    the call that consumes it without the host in between."""
    global _BOARD_FNS
    if _BOARD_FNS is None:
        import jax

        def take(board, idx):
            return board.at[idx].get(mode="fill", fill_value=0)

        def put(board, idx, toks):
            return board.at[idx].set(
                toks.reshape(-1).astype(board.dtype), mode="drop")

        _BOARD_FNS = (jax.jit(take), jax.jit(put, donate_argnums=0))
    return _BOARD_FNS


def _reject_unservable(req: Request, cfg: ServedModel,
                       kv_config: KVCacheConfig):
    """Shared submit-time gate: a request that cannot complete even
    with the whole pool to itself would hang any scheduler (prefill
    backpressure forever, or a preempt loop).  Raises
    :class:`RequestRejected` (a ValueError) carrying the reason code
    for the labeled reject counter."""
    total = len(req.prompt) + req.max_new_tokens
    if total > cfg.max_seq_len:
        raise RequestRejected(
            f"request {req.req_id!r}: prompt+max_new_tokens "
            f"{len(req.prompt)}+{req.max_new_tokens} exceeds "
            f"max_seq_len {cfg.max_seq_len}", "max_seq_len")
    if _worst_case_pages(req, kv_config) > kv_config.num_pages:
        raise RequestRejected(
            f"request {req.req_id!r} needs more KV pages than the "
            f"whole pool holds ({total} tokens, "
            f"{kv_config.num_pages} pages of {kv_config.page_size})",
            "pool")


def _count_reject(e: ValueError):
    """One rejection -> the legacy aggregate counter (back-compat) plus
    the labeled by-reason family (r18 satellite: today all rejections
    look alike in telemetry)."""
    tm.counter("serving_rejected_total",
               "requests rejected at submit (unservable)").inc()
    tm.counter("serving_rejects_total",
               "requests refused, by reason (pool / budget / "
               "max_seq_len at submit; shed by the admission policy)",
               labels=("reason",)).labels(
                   reason=getattr(e, "reason", "unservable")).inc()


class _EngineCore:
    """Programs + scope + executor + KV pools, shared by the continuous
    and static drivers (one model, two scheduling policies)."""

    def __init__(self, cfg: ServedModel, weights: Dict[str, np.ndarray],
                 num_pages: int = 64, page_size: int = 16,
                 place=None, use_mha_fusion: bool = True,
                 prefill_bucket_min: int = 16,
                 prefix_cache: Optional[bool] = None,
                 prefix_seed: int = 0,
                 sampling: Optional[SamplingParams] = None,
                 sample_seed: int = 0,
                 kv_dtype: Optional[str] = None,
                 kv_budget_mb: float = 0.0,
                 tp: Optional[int] = None,
                 max_batch: int = 0):
        from ..utils.flags import flag

        self.cfg = cfg
        if tp is None:
            tp = int(flag("serving_tp", 1) or 1)
        self.tp = int(tp)
        cfg.validate(tp=self.tp)  # bugfix rider: fail loud here
        self.tp_mesh = None
        if self.tp > 1:
            import jax as _jax

            devs = _jax.devices()
            if self.tp > len(devs):
                raise ValueError(
                    f"serving_tp={self.tp} needs {self.tp} devices, have "
                    f"{len(devs)}")
            from jax.sharding import Mesh as _Mesh

            from ..parallel.mesh import registry as _mesh_registry

            # construct the serving mesh DIRECTLY (MeshRegistry.
            # create_mesh would also make it the process-wide current
            # mesh and capture ring 0 — both belong to data parallel);
            # only the dedicated TP ring maps onto the "mp" axis
            self.tp_mesh = _Mesh(np.array(devs[:self.tp]),
                                 (SERVING_TP_AXIS,))
            _mesh_registry().register_ring(
                SERVING_TP_RING_ID, SERVING_TP_AXIS,
                mesh_name="serving_tp")
        # greedy sampling normalizes to None: the serving programs are
        # then built EXACTLY as before (argmax head, no seeds feed) —
        # the flag-off bit-identity baseline
        self.sampling = sampling if _sampled(sampling) else None
        self.sample_seed = int(sample_seed)
        if kv_dtype is None:
            kv_dtype = str(flag("kv_cache_dtype", "float32") or "float32")
        if kv_dtype not in ("float32", "bfloat16", "int8"):
            raise ValueError(f"bad kv_cache_dtype {kv_dtype!r}")
        self.kv_dtype = kv_dtype
        self.exe = Executor(place)
        self.place = place = self.exe.place
        self.scope = Scope()
        self.prefill_bucket_min = prefill_bucket_min
        if kv_budget_mb and kv_budget_mb > 0:
            # pool sizing from a FIXED byte budget: page count is what
            # the budget buys at the storage dtype, so a cheaper dtype
            # is more CAPACITY at the same HBM (2x bf16 / 4x int8 —
            # the scale pool is charged as overhead on top, ~1.6% at
            # the default page geometry, not folded into the divisor:
            # folding it in would turn the exact 4x into 3.94x)
            # PER-DEVICE page bytes: under TP the pool shards on
            # kv_heads, so each device stores num_heads/tp of every
            # page — the same per-device budget buys tp x more pages
            # (the capacity headline; == the legacy expression at tp=1)
            page_bytes = page_size * cfg.kv_token_bytes(kv_dtype, self.tp)
            num_pages = max(1, int(kv_budget_mb * (1 << 20)) // page_bytes)
        self.kv_budget_mb = float(kv_budget_mb or 0.0)
        self.kv_config = cfg.kv_cache_config(num_pages, page_size, kv_dtype)
        # a model with per-sequence state beside its pages (a recurrent
        # layer) says what it keeps a slot (``state_pool_specs``); the one
        # cache manager then hands a sequence a slot with its first pages.
        # A slot a sequence of the engine's full batch (``max_batch``):
        # fewer would only cap the batch, more would never be owned
        self._state_specs = cfg.state_pool_specs(int(max_batch))
        if self._state_specs:
            if int(max_batch) < 1:
                raise ValueError("this model keeps a state a sequence: the "
                                 "core must be told the engine's max_batch")
            self.kv_config = dataclasses.replace(
                self.kv_config, state_slots=int(max_batch))
        # a model whose window layers keep their own group of pages says
        # which pools those are (``window_pool_names``); the group holds
        # what the engine's full batch can: its most pages a sequence
        # (``window_pages_per_seq``) times ``max_batch``, so a running
        # sequence never waits for a window page
        self._window_pools = frozenset(cfg.window_pool_names())
        if self.kv_config.window:
            if int(max_batch) < 1:
                raise ValueError("this model keeps a window group of pages: "
                                 "the core must be told the engine's "
                                 "max_batch")
            self.kv_config = dataclasses.replace(
                self.kv_config, window_pages=int(max_batch)
                * self.kv_config.window_pages_per_seq)
        self.kv = PagedKVCache(self.kv_config, prefix_cache=prefix_cache,
                               seed=prefix_seed)
        # what this model is not served with, refused here and loudly
        cfg.validate(tp=self.tp, kv_dtype=kv_dtype,
                     prefix_cache=self.kv.prefix_cache)
        # what a program offers beyond its tokens, kept on request: the
        # rows' last hidden state (a drafter that consumes it asks), each
        # emitted token's logit and log-sum-exp by request (a check of the
        # served logits asks: ``served_scores``); ``last`` holds the last
        # call's extras, on the device
        self.keep_hidden = False
        self.keep_scores = False
        self.last: Dict[str, object] = {}
        self.token_scores: Dict[object, list] = {}   # req -> [(call, row)]
        self._score_calls: list = []                 # (score, routes) a call
        self._moe_stats: Dict[str, Dict[str, float]] = {}
        self._moe_pending: list = []                 # (phase, counts) a call
        # (a call's place in ``_moe_pending``, what its form will say of its
        # kernels once the call's counts are read: ``_note_kernel_stats``)
        self._stats_owed: list = []
        # (phase, keys, values by expert layer) a call: the rows with no
        # expert here where the layers hold a share of their experts, the
        # choices on held experts, on identity experts and all of them
        # (expert layers, 3) where the model has identity experts
        self._sums_pending: list = []
        self.moe_calls: Optional[list] = None   # a list: every call's counts
        # what a program's kernels report of their own work, summed by
        # phase (``FormExtras.kernel_stats``): host integers, no device read
        self.kernel_stats: Dict[str, Dict[str, int]] = {}
        self._chunk = None   # (prog, feeds, fetch) — built on first use
        # (begin, end) of the last engine/decode span when a request in
        # its batch was traced, else (None, None)
        self.decode_wall = (None, None)
        self._verify = None  # spec-decode verify form — built on first use
        # the token board (``open_board``): with it a call's tokens stay on
        # the device and the calls return device arrays that nobody has read
        self.board = None

        self._tp_rules = cfg.tp_rules(kv_dtype) if self.tp > 1 else {}
        self.ref_prog, self.ref_feeds, self.ref_fetch = \
            self._build_form("reference")
        self.prefill_prog, self.prefill_feeds, self.prefill_fetch = \
            self._build_form("prefill", sampling=self.sampling,
                             kv_dtype=kv_dtype)
        self.decode_prog, self.decode_feeds, self.decode_fetch = \
            self._build_form("decode", sampling=self.sampling,
                             kv_dtype=kv_dtype)
        # a decode form whose kernels walk the chunks that hold context
        # (``FormExtras.live_walk_pages``) is fed ONE block-table width, the
        # bucket of the longest context the model serves up to the width the
        # form offers: one program a batch bucket.  Wider contexts, and every
        # form that offers none (0), take the contexts' own bucket
        offer = self.decode_prog._form_extras.live_walk_pages
        widest = offer(self.kv_config) if offer is not None else None
        self.decode_table_floor = 0 if widest is None else min(
            int(widest),
            _pow2_bucket(-(-cfg.max_seq_len // self.kv_config.page_size)))
        # by phase, the distinct (padded batch, table width) the decode form
        # has been fed: the programs it costs to build
        self.decode_feed_shapes: Dict[str, int] = {}
        self._decode_shapes_seen: Dict[str, set] = {}
        self.mha_fused = 0
        if use_mha_fusion:
            # the serving pass pipeline: the naive composition the
            # export carries is rewritten onto the fused attention op
            # (flash kernel when it engages), verifier-gated like every
            # pass application
            from ..framework.ir import get_pass

            for prog in (self.ref_prog, self.prefill_prog):
                p = get_pass("fuse_multihead_attention_pass")
                p.apply(prog)
                self.mha_fused += p.fused_count

        import jax

        from ..executor import device_put_owned

        if self.tp > 1:
            # stage every weight/pool SHARDED over the serving mesh per
            # its partition-rule placement (replicated when no rule):
            # each device holds 1/tp of the bytes, and the executor's
            # shard_map in_specs see exactly these placements
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as _P

            def _target(name):
                s = self._tp_spec(name)
                return NamedSharding(self.tp_mesh,
                                     _P(*s) if s else _P())
            dev_of = _target
        else:
            dev = place.jax_device()

            def dev_of(name):
                return dev
        for name, arr in weights.items():
            self.scope.set(name, jax.device_put(arr, dev_of(name)))
        for name in self._pool_names():
            # the pools are DONATED every prefill/decode step: they must
            # be XLA-owned buffers, never zero-copy host aliases
            pool = self.kv_config.make_scale_pool() if "_scale_" in name \
                else self.kv_config.make_pool(name in self._window_pools)
            self.scope.set(name, device_put_owned(pool, dev_of(name)))
        for name, (shape, dtype) in self._state_specs.items():
            # made on the device: a slot pool is gigabytes of zeros
            with jax.default_device(dev_of(name)):
                self.scope.set(name, jax.numpy.zeros(shape, dtype))
        if self.tp > 1:
            # engage-only telemetry (the flag-off registry is untouched):
            # the TP degree gauge plus each device's share of the pool
            tm.gauge("serving_tp_degree",
                     "tensor-parallel degree of the serving engine "
                     "mesh").set(self.tp)
            per_dev = self.kv_pool_resident_bytes()
            g = tm.gauge("kv_pool_resident_bytes",
                         "per-device KV pool residency under TP "
                         "(kv_heads-sharded)", labels=("device",))
            for d in self.tp_mesh.devices.flat:
                g.labels(device=str(d)).set(per_dev)

    @classmethod
    def from_model_dir(cls, model_dir: str, **kw) -> "_EngineCore":
        cfg = load_decoder_config(model_dir)
        scope = Scope()
        exe = Executor(CPUPlace())
        from .. import io as pt_io

        with scope_guard(scope):
            pt_io.load_inference_model(model_dir, exe)
        weights = {n: np.asarray(scope.get(n)) for n in cfg.param_specs()}
        return cls(cfg, weights, **kw)

    def _pool_names(self) -> List[str]:
        """Every pool var of the model's cache, the int8 scale pools
        (``kv_k_3`` -> ``kv_k_scale_3``) after the pools they scale."""
        names = list(self.cfg.cache_pool_names())
        if self.kv_config.quantized:
            names += ["_scale_".join(n.rsplit("_", 1)) for n in names]
        return names

    def _tp_spec(self, name: str):
        """Partition spec for one weight/pool var (None = replicated),
        resolved from the same rule set the programs are annotated
        with (exact name first, then regex fullmatch)."""
        import re as _re

        for pat, spec in self._tp_rules.items():
            if pat == name or _re.fullmatch(pat, name):
                return spec
        return None

    # -- model steps -------------------------------------------------------
    def _build_form(self, mode: str, sampling=None,
                    kv_dtype: str = "float32") -> tuple:
        """Build one program form at the engine's TP degree.  tp=1 is
        the exact legacy builder call.  tp>1 builds the shard body,
        runs the verifier-bracketed ``serving_tp_pass`` (combine
        collectives on the serving ring), annotates every weight/pool
        var with its partition-rule placement, and tags the program
        with the mesh so the executor compiles it under shard_map."""
        prog, feeds, fetch = self.cfg.build_program(
            mode, sampling=sampling, kv_dtype=kv_dtype, tp=self.tp)
        if self.tp > 1:
            from ..framework.ir import get_pass
            from ..parallel.tensor_parallel import apply_tensor_parallel

            get_pass("serving_tp_pass",
                     ring_id=SERVING_TP_RING_ID).apply(prog)
            rules = self._tp_rules
            if mode == "reference":
                # the reference form never touches the KV pool — its
                # rule set must not demand pool vars that don't exist
                rules = {k: v for k, v in rules.items()
                         if not k.startswith("kv_")}
            apply_tensor_parallel(prog, rules)
            prog._tp_shard = {"axis": SERVING_TP_AXIS, "degree": self.tp,
                              "mesh": self.tp_mesh}
            # static shard-safety gate over the finished shard body:
            # the combines just inserted plus the decoder_tp_rules
            # annotations are exactly what the analyzer audits (a
            # collective under a per-rank predicate, or a replicated-
            # slot read of a shard-resident value, deadlocks/corrupts
            # every rank of the serving mesh at once)
            from ..framework import shard_analysis

            shard_analysis.gate(prog, feed_names=tuple(feeds),
                                fetch_names=tuple(fetch),
                                where=f"serving_tp_compile[{mode}]")
        return prog, feeds, fetch

    @property
    def chunk_prog_parts(self):
        """The "chunk" program form (built lazily: the flag-off engine
        never constructs it, keeping its host path identical)."""
        if self._chunk is None:
            self._chunk = self._build_form("chunk",
                                           sampling=self.sampling,
                                           kv_dtype=self.kv_dtype)
        return self._chunk

    @property
    def verify_prog_parts(self):
        """The spec-decode "verify" program form (lazy like chunk: a
        spec-off engine never constructs it)."""
        if self._verify is None:
            self._verify = self._build_form("verify",
                                            sampling=self.sampling,
                                            kv_dtype=self.kv_dtype)
        return self._verify

    def open_board(self, lanes: int):
        """Keep the tokens on the device from here on (the engine's
        pipelined steps): ``prefill_job`` and ``decode_batch`` dispatch and
        return device arrays without reading them, and a running sequence's
        pending token lives in its lane of ``board`` (``_SeqState.lane``).
        Every shape of the board's two functions is compiled here, so none
        compiles while serving."""
        import jax

        take, put = _board_fns()
        dev = self.place.jax_device()
        self._board_pad = int(lanes)     # a row index that names no lane
        self.board = jax.device_put(np.zeros(lanes, np.int32), dev)
        b = 1
        while True:
            idx = np.full(b, self._board_pad, np.int32)
            self.board = put(self.board, idx, take(self.board, idx))
            if b >= lanes:
                break
            b *= 2

    def board_put(self, lanes: Sequence[int], toks):
        """``toks`` (a device array of a call's tokens) into ``lanes``."""
        self.board = _board_fns()[1](
            self.board, np.asarray(lanes, np.int32), toks)

    def _lane(self, req: Request, offset: int = 0) -> int:
        """RNG lane for the token ``offset`` positions past the
        request's next emission — ``len(prompt) + len(out_tokens)`` is
        the absolute index of the next token to draw, a pure function
        of request state, so lanes are preemption/resume-invariant and
        identical between monolithic and speculative decode."""
        return rng_lane(self.sample_seed, req.req_id,
                        len(req.prompt) + len(req.out_tokens) + offset)

    def _apply_forks(self):
        """Replay pending CoW forks (kv_cache.take_forks) as device
        page copies across every layer's K and V pool — MUST run before
        the program whose appends triggered the forks."""
        forks = self.kv.take_forks()
        if not forks:
            return
        fn = _fork_copy_fn()
        # pages AND their scales copy verbatim — a fork never
        # requantizes, so shared pages stay bit-stable (pinned)
        names = self._pool_names()
        for src, dst, _used in forks:
            s = np.int32(src)
            d = np.int32(dst)
            for nm in names:
                self.scope.set(nm, fn(self.scope.get(nm), s, d))

    def start_prefill(self, req: Request) -> _PrefillJob:
        """Open a prefill job: with prefix caching on, map every
        already-cached page of the prompt into the request's block
        table (capped at prompt-1 tokens — the last position is always
        computed, it produces the first output token)."""
        job = _PrefillJob(req)
        req._prefix_hit = 0
        if self.kv.prefix_cache and len(req.prompt) > 1:
            hit, pages = self.kv.match_prefix(req.prompt[:-1])
            if hit:
                self.kv.acquire_prefix(req.req_id, req.prompt[:hit], pages)
                job.pos = job.hit = hit
                req._prefix_hit = hit
        return job

    def advance_prefill(self, job: _PrefillJob,
                        max_tokens: Optional[int] = None) -> Optional[bool]:
        """Prefill up to ``max_tokens`` of the remaining prompt (all of
        it when None).  Returns True when the prompt is fully prefilled
        (``job.first_token`` set), False when chunks remain, None on
        pool backpressure (no slice was appended this call)."""
        req = job.req
        L = len(req.prompt)
        remaining = L - job.pos
        n = remaining if max_tokens is None else \
            min(int(max_tokens), remaining)
        chunk = req.prompt[job.pos:job.pos + n]
        # the request's trace (utils/tracing.py) takes its prefill wall
        # bounds from this span: timed for a traced request even while
        # no profiler records
        traced = req.trace is not None
        with RecordEvent("engine/prefill", "serving", timed=traced) as span:
            with RecordEvent("engine/feed_build", "serving"):
                slots = self.kv.append_tokens(req.req_id, n, tokens=chunk)
                if slots is None:
                    return None
                if job.chunks == 0:
                    # the FIRST slice that actually lands confirms the
                    # hit: counting here (not at acquire) keeps blocked-
                    # admission acquire/release retries out of the hit
                    # accounting
                    self.kv.commit_prefix_hit(req.req_id)
                self._apply_forks()
            final = job.pos + n == L
            if job.pos == 0 and final:
                tok = self._run_whole(req, slots, span)
            else:
                tok = self._run_chunk(req, job.pos, chunk, slots, span)
        if traced:
            job.wall_s += span.end - span.begin
            job.wall_end = span.end
        job.pos += n
        job.chunks += 1
        if final:
            job.first_token = tok
            return True
        return False

    def _run_whole(self, req: Request, slots, span) -> int:
        """Cold whole-prompt prefill: the classic (MHA-fused) path,
        bit-identical to the pre-chunking engine."""
        with RecordEvent("engine/feed_build", "serving"):
            L = len(req.prompt)
            S = _pow2_bucket(L, self.prefill_bucket_min, None)
            toks = np.zeros((1, S), np.int32)
            toks[0, :L] = req.prompt
            pos = np.minimum(np.arange(S, dtype=np.int32),
                             self.cfg.max_seq_len - 1)[None]
            slot_map = np.full(S, self.kv_config.pad_slot, np.int32)
            slot_map[:L] = slots
            feed = {"tokens": toks, "positions": pos,
                    "slot_mapping": slot_map,
                    "last_index": np.array([L - 1], np.int32)}
            if "attn_mask" in self.prefill_feeds:
                # a form that takes no mask builds it from the positions
                feed["attn_mask"] = _causal_mask(S)
            if self._state_specs:
                feed["state_slots"] = np.array(
                    [self.kv.state_slot(req.req_id)], np.int32)
            if self.kv_config.window:
                # the window group's slots: the head of a long prompt, behind
                # the window, carries its pad sentinel and is not written
                window = np.full(S, self.kv_config.window_pad_slot, np.int32)
                window[:L] = self.kv.window_slots(req.req_id)
                feed["window_slot_mapping"] = window
            if self.sampling is not None:
                feed["sample_seeds"] = np.array([self._lane(req)], np.int32)
        if span.recording:
            span.set(req=str(req.req_id), prompt_tokens=L, bucket=S)
        with RecordEvent("prefill", cat="serving"):
            out = self._run(self.prefill_prog, feed, self.prefill_fetch,
                            "prefill")
        self._note_scores([req.req_id], fresh=True)
        return out[0] if self.board is not None else int(out[0][0])

    def _run_chunk(self, req: Request, pos: int, chunk, slots, span) -> int:
        """One prompt slice at offset ``pos``: the slice's K/V enter
        the pool, its attention runs over the pool-resident prefix plus
        itself through the request's block table.  Bucketed in slice
        length AND block-table width, so the jit cache stays bounded."""
        with RecordEvent("engine/feed_build", "serving"):
            prog, _feeds, fetch = self.chunk_prog_parts
            n = len(chunk)
            S = _pow2_bucket(n, self.prefill_bucket_min, None)
            toks = np.zeros((1, S), np.int32)
            toks[0, :n] = chunk
            posf = np.minimum(pos + np.arange(S, dtype=np.int32),
                              self.cfg.max_seq_len - 1)[None]
            W = _pow2_bucket(self.kv.num_pages_of(req.req_id))
            C = W * self.kv_config.page_size
            tables = self.kv.block_table(req.req_id, W)
            slot_map = np.full(S, self.kv_config.pad_slot, np.int32)
            slot_map[:n] = slots
            # causal + context-bound mask over the gathered pool window:
            # slice position pos+i attends pool slots 0..pos+i (block-
            # table order IS token order); everything else — tail
            # garbage, padded table entries, padded slice rows — is
            # masked
            cols = np.arange(C, dtype=np.int64)[None, :]
            rows = np.arange(S, dtype=np.int64)[:, None]
            mask = np.where(cols <= pos + rows, 0.0, NEG_INF) \
                .astype(np.float32)[None, None]
            feed = {"tokens": toks, "positions": posf,
                    "attn_mask": mask, "slot_mapping": slot_map,
                    "chunk_tables": tables,
                    "last_index": np.array([n - 1], np.int32)}
            if self.sampling is not None:
                # the slice's token lands at absolute position pos+n;
                # only the FINAL slice's draw is consumed (pos+n ==
                # len(prompt)), so its lane matches the monolithic
                # prefill's exactly
                feed["sample_seeds"] = np.array(
                    [rng_lane(self.sample_seed, req.req_id, pos + n)],
                    np.int32)
        if span.recording:
            span.set(req=str(req.req_id), prompt_tokens=n, bucket=S,
                     table_width=W)
        with RecordEvent("prefill_chunk", cat="serving"):
            out = self.exe.run(prog, feed=feed,
                               fetch_list=fetch, scope=self.scope)
        return int(out[0][0])

    def abort_prefill(self, job: _PrefillJob):
        """Release a job's pages (backpressure mid-prefill).  With
        prefix caching on the completed slices stay warm in the index,
        so the retry re-acquires them instead of recomputing."""
        self.kv.free_sequence(job.req.req_id)

    def prefill_job(self, req: Request) -> Optional[_PrefillJob]:
        """Write the prompt's K/V into the pool and return the finished
        job (its ``first_token`` is the first generated token; a traced
        request's wall bounds ride on it); None when the pool can't
        hold the prompt (admission backpressure — with prefix caching
        off, nothing is mutated; with it on, acquired prefix pages are
        released back to the cache)."""
        job = self.start_prefill(req)
        if self.advance_prefill(job) is None:
            if job.hit:
                self.kv.free_sequence(req.req_id)
            return None
        return job

    def decode_batch(self, states: Sequence[_SeqState]) -> List[int]:
        """One continuous decode step for ``states`` (each sequence's
        pending token enters the pool, then attends at its true length).
        The caller guarantees page capacity.  Feed shapes bucket to the
        next power of two in batch and, for a form that pays for every
        table column (``paged_decode``, the gather-and-mask fallbacks), in
        block-table width: (log max_batch x log max_pages) shapes.  A form
        whose kernels walk live chunks only (``decode_table_floor``) is fed
        one width up to the one it offers: log max_batch shapes.
        ``decode_wall`` keeps the ``engine/decode`` span's stamps for
        the traced requests' decode-step spans.  With the token board open
        the pending tokens are read from their lanes and the new ones
        written back there, and what returns is the call's device array of
        tokens, unread."""
        B = len(states)
        lazy = self.board is not None
        traced = any(st.req.trace is not None for st in states)
        with RecordEvent("engine/decode", "serving", timed=traced) as span:
            with RecordEvent("engine/feed_build", "serving"):
                Bp = _pow2_bucket(max(B, 1))
                toks = np.zeros(Bp, np.int32)
                pos = np.zeros(Bp, np.int32)
                slot_map = np.full(Bp, self.kv_config.pad_slot, np.int32)
                ctx = np.ones(Bp, np.int32)
                for i, st in enumerate(states):
                    toks[i] = st.lane if lazy else st.last_token
                    pos[i] = min(self.kv.context_len(st.req.req_id),
                                 self.cfg.max_seq_len - 1)
                    slots = self.kv.append_tokens(
                        st.req.req_id, 1,
                        tokens=None if lazy else [st.last_token])
                    assert slots is not None, "caller must reserve pages"
                    slot_map[i] = slots[0]
                    ctx[i] = self.kv.context_len(st.req.req_id)
                self._apply_forks()
                W = max(self.decode_table_floor, _pow2_bucket(max(
                    (self.kv.num_pages_of(st.req.req_id) for st in states),
                    default=1)))
                tables = np.zeros((Bp, W), np.int32)
                for i, st in enumerate(states):
                    tables[i] = self.kv.block_table(st.req.req_id, W)
                if lazy:
                    toks[B:] = self._board_pad
                    rows, toks = toks, _board_fns()[0](self.board, toks)
                feed = {"tokens": toks, "positions": pos,
                        "block_tables": tables,
                        "context_lens": ctx, "slot_mapping": slot_map}
                if self._state_specs:
                    # padded rows carry the padding's slot, owned by none
                    state = np.full(Bp, self.kv_config.pad_state_slot,
                                    np.int32)
                    state[:B] = [self.kv.state_slot(st.req.req_id)
                                 for st in states]
                    feed["state_slots"] = state
                if self.kv_config.window:
                    # the window group: a table of fixed width (the most a
                    # sequence holds), its entry 0 the page of the row's
                    # first held position
                    kvc, ids = self.kv_config, [st.req.req_id
                                                for st in states]
                    Ww = kvc.window_pages_per_seq
                    slots = np.full(Bp, kvc.window_pad_slot, np.int32)
                    slots[:B] = [self.kv.window_slots(r)[0] for r in ids]
                    wtab = np.zeros((Bp, Ww), np.int32)
                    first = np.zeros(Bp, np.int32)
                    for i, r in enumerate(ids):
                        wtab[i] = self.kv.window_table(r, Ww)
                        first[i] = self.kv.window_first(r)
                    feed.update(window_slot_mapping=slots,
                                window_tables=wtab, window_first=first)
                if self.sampling is not None:
                    lanes = np.zeros(Bp, np.int32)
                    for i, st in enumerate(states):
                        lanes[i] = self._lane(st.req)
                    feed["sample_seeds"] = lanes
            if span.recording:
                span.set(batch=B, padded_batch=Bp, table_width=W)
            with RecordEvent("decode_batch", cat="serving"):
                out = self._run(self.decode_prog, feed, self.decode_fetch,
                                "decode")
            if lazy:
                self.board_put(rows, out[0])
                toks_out = out[0]
            else:
                toks_out = [int(out[0][i]) for i in range(B)]
            self._note_scores([st.req.req_id for st in states])
        self.decode_wall = (span.begin, span.end)
        return toks_out

    def verify_batch(self, items) -> List[List[int]]:
        """One spec-decode verify step: ``items`` is a list of
        ``(_SeqState, draft_tokens)`` pairs.  Each sequence's chunk
        ``[last_token] + draft`` enters the pool at allocator slots
        (the caller guaranteed page capacity), then ONE verify-program
        call scores every chunk position of every sequence against the
        pool-resident context.  Returns, per item, the target model's
        next token after each chunk position (``len(draft) + 1``
        tokens) — row j is what the baseline would emit after accepting
        the first j draft tokens, so accept-prefix comparison against
        it is exact.  Feed shapes bucket in batch, chunk length AND
        block-table width (all powers of two), keeping the jit cache
        bounded like every other serving form.  ``decode_wall`` as in
        ``decode_batch``."""
        B = len(items)
        traced = any(st.req.trace is not None for st, _ in items)
        with RecordEvent("engine/decode", "serving", timed=traced) as span:
            with RecordEvent("engine/feed_build", "serving"):
                prog, _feeds, fetch = self.verify_prog_parts
                Bp = _pow2_bucket(max(B, 1))
                S = _pow2_bucket(max(1 + len(d) for _, d in items))
                toks = np.zeros((Bp, S), np.int32)
                posf = np.zeros((Bp, S), np.int32)
                slot_map = np.full(Bp * S, self.kv_config.pad_slot,
                                   np.int32)
                pos0 = []
                for i, (st, draft) in enumerate(items):
                    rid = st.req.req_id
                    chunk = [int(st.last_token)] + [int(t) for t in draft]
                    n = len(chunk)
                    p0 = self.kv.context_len(rid)
                    pos0.append(p0)
                    slots = self.kv.append_tokens(rid, n, tokens=chunk)
                    assert slots is not None, "caller must reserve pages"
                    toks[i, :n] = chunk
                    posf[i] = np.minimum(
                        p0 + np.arange(S, dtype=np.int32),
                        self.cfg.max_seq_len - 1)
                    slot_map[i * S:i * S + n] = slots
                self._apply_forks()
                W = _pow2_bucket(max(
                    (self.kv.num_pages_of(st.req.req_id)
                     for st, _ in items), default=1))
                C = W * self.kv_config.page_size
                tables = np.zeros((Bp, W), np.int32)
                for i, (st, _d) in enumerate(items):
                    tables[i] = self.kv.block_table(st.req.req_id, W)
                # per-row causal + context-bound mask (the chunk form's
                # rule, one slice per batch row); padded batch rows are
                # fully masked — softmax over finite NEG_INF stays
                # NaN-free by construction
                cols = np.arange(C, dtype=np.int64)[None, None, :]
                rows = np.arange(S, dtype=np.int64)[None, :, None]
                base = np.asarray(pos0 + [-1] * (Bp - B),
                                  dtype=np.int64)[:, None, None]
                feed = {"tokens": toks, "positions": posf,
                        "slot_mapping": slot_map, "verify_tables": tables}
                if "attn_mask" in _feeds:
                    feed["attn_mask"] = np.where(
                        cols <= base + rows, 0.0, NEG_INF) \
                        .astype(np.float32)[:, None]
                if self.sampling is not None:
                    lanes = np.zeros(Bp * S, np.int32)
                    for i, (st, draft) in enumerate(items):
                        for j in range(len(draft) + 1):
                            # row j draws the token the sequence would
                            # emit at absolute position
                            # len(prompt)+len(out)+j — the SAME lane
                            # monolithic decode would use there
                            lanes[i * S + j] = self._lane(st.req, j)
                    feed["sample_seeds"] = lanes
            if span.recording:
                span.set(batch=B, padded_batch=Bp, table_width=W,
                         chunk=S)
            with RecordEvent("verify_batch", cat="serving"):
                out = self._run(prog, feed, fetch, "decode")
            self.last["chunk"] = S
            flat = out[0]
            targets = [[int(flat[i * S + j]) for j in range(len(d) + 1)]
                       for i, (_st, d) in enumerate(items)]
        self.decode_wall = (span.begin, span.end)
        return targets

    def _run(self, prog, feed, fetch, phase: str):
        """One call of a serving form.  A program may offer more than its
        tokens (its ``FormExtras``: the expert decoders' forms do, GPT-2's
        offer their logits alone and are run exactly as before).  What is
        offered and wanted rides on the same call and STAYS ON THE DEVICE
        (``self.last``, and the logs below): a call's one host read is its
        tokens, as ever.  A form that knows what its kernels will walk says
        so from the feed (``kernel_stats``): summed by phase into
        ``self.kernel_stats``."""
        offers = prog._form_extras
        if prog is self.decode_prog:
            feed = self._decode_feed_as_run(feed, phase)
        if offers.kernel_stats is not None:
            self._note_kernel_stats(
                phase, offers.kernel_stats(feed, self.kv_config))
        extras = {}
        if self.keep_hidden and offers.hidden:
            extras["hidden"] = offers.hidden
        if self.keep_scores and offers.score:
            extras["score"] = offers.score
            if offers.routes:
                extras["routes"] = offers.routes
            if offers.routes_all:
                extras["routes_all"] = offers.routes_all
        if offers.counts:
            extras["counts"] = offers.counts
            if offers.absent:
                extras["absent"] = offers.absent
            if offers.choices:
                extras["choices"] = offers.choices
        if not extras and self.board is None:
            self.last = {}
            return self.exe.run(prog, feed=feed, fetch_list=fetch,
                                scope=self.scope)
        out = self.exe.run(prog, feed=feed,
                           fetch_list=list(fetch) + list(extras.values()),
                           scope=self.scope, return_numpy=False)
        self.last = {k: t.value() for k, t in zip(extras, out[len(fetch):])}
        if "counts" in self.last:
            self._moe_pending.append((phase, self.last["counts"]))
            if "absent" in self.last:
                self._sums_pending.append(
                    (phase, ("rows_all_absent",), self.last["absent"]))
            if "choices" in self.last:
                self._sums_pending.append(
                    (phase, ("choices_held", "choices_identity",
                             "choices_all"), self.last["choices"]))
        if "score" in self.last:
            self._score_calls.append((self.last["score"],
                                      self.last.get("routes"),
                                      self.last.get("routes_all")))
        if self.board is not None:
            return [t.value() for t in out[:len(fetch)]]
        # the host waits for the device here: the executor's own fetch span
        # closed on arrays it did not read
        with RecordEvent("executor/fetch"):
            return [np.asarray(t) for t in out[:len(fetch)]]

    def _decode_feed_as_run(self, feed, phase: str):
        """The decode form's feed as it is run: a ``block_tables`` narrower
        than ``decode_table_floor`` widened to it with page 0, the value
        ``PagedKVCache.block_table`` pads with (``decode_batch`` builds its
        own at that width; a caller that enumerates widths, a warm-up, runs
        one program for all of them).  Counts the distinct (padded batch,
        table width) run, by phase."""
        tables = feed["block_tables"]
        short = self.decode_table_floor - tables.shape[1]
        if short > 0:
            tables = np.pad(np.asarray(tables), ((0, 0), (0, short)))
            feed = {**feed, "block_tables": tables}
        seen = self._decode_shapes_seen.setdefault(phase, set())
        if tables.shape not in seen:
            seen.add(tables.shape)
            self.decode_feed_shapes[phase] = len(seen)
            tm.counter("decode_feed_shapes", "distinct (padded batch, "
                       "block-table width) the decode form has been fed",
                       labels=("phase",)).labels(phase=phase).inc()
        return feed

    def _note_kernel_stats(self, phase: str, stats):
        """``stats``: counts to sum by phase.  Under ``from_counts`` a form
        may give a function of the call's expert counts instead (what a
        grouped matmul walks depends on them): owed until ``moe_stats``
        reads the counts, and found again by the place `_run`, which calls
        this before the call, gives the call in ``_moe_pending`` after it."""
        if not stats:
            return
        later = stats.pop("from_counts", None)
        if later is not None:
            self._stats_owed.append((len(self._moe_pending), later))
            if not stats:
                return
        st = self.kernel_stats.setdefault(phase, {})
        for key, value in stats.items():
            st[key] = st.get(key, 0) + value
            tm.counter(key, "a serving form's own count of its kernels' "
                       "work (calls, grid steps, the chunks its tables "
                       "span), summed over layers and calls",
                       labels=("phase",)).labels(phase=phase).inc(value)

    def _note_scores(self, req_ids, fresh: bool = False):
        """Row ``i`` of the call just made emitted a token of
        ``req_ids[i]``: remember where its score lies.  ``fresh`` opens the
        request's record anew (a prefill: a resumed request's earlier
        scores go with its earlier tokens)."""
        if not (self.keep_scores and "score" in self.last):
            return
        call = len(self._score_calls) - 1
        for i, rid in enumerate(req_ids):
            if fresh:
                self.token_scores[rid] = []
            self.token_scores.setdefault(rid, []).append((call, i))

    def served_scores(self, req_id):
        """For every token served to ``req_id`` (``keep_scores`` on): its
        ``(logit, row log-sum-exp)`` as the program that emitted it computed
        them, ``(tokens, 2)``, and the experts its row was routed to,
        ``(tokens, expert layers, k)`` (None where the model routes
        nothing).  Read from the device here, not when served."""
        at = self.token_scores[req_id]
        host = {c: (np.asarray(self._score_calls[c][0]),
                    None if self._score_calls[c][1] is None
                    else np.asarray(self._score_calls[c][1]))
                for c in {c for c, _ in at}}
        scores = np.stack([host[c][0][i] for c, i in at])
        if host[at[0][0]][1] is None:
            return scores, None
        return scores, np.stack([host[c][1][:, i] for c, i in at])

    def prompt_routes(self, req_id):
        """The experts every row of ``req_id``'s prompt was routed to by
        the prefill that served it, ``(expert layers, prompt rows, k)``;
        None where the model's prefill form does not offer them."""
        call = self.token_scores[req_id][0][0]      # its (last) prefill
        rows = self._score_calls[call][2]
        return None if rows is None else np.asarray(rows)

    @property
    def moe_stats(self) -> Dict[str, Dict[str, float]]:
        """By phase (``prefill``, ``decode``): expert layers run, experts
        that received a token and the fullest expert's load over the mean,
        each summed over expert layers and calls (a reader divides by
        ``layer_steps``); where the layers hold a share, the rows with no
        expert here; where the model has identity experts, the real tokens'
        choices on held experts, on identity experts and all of them.
        Reading it reads the calls' counts off the device: they are logged
        there, and nothing reads them while serving."""
        pending, self._moe_pending = self._moe_pending, []
        owed, self._stats_owed = self._stats_owed, []
        calls = [(phase, np.asarray(c, np.float64)) for phase, c in pending]
        for at, later in owed:
            if at < len(calls):
                self._note_kernel_stats(calls[at][0], later(calls[at][1]))
        if self.moe_calls is not None:
            self.moe_calls += calls
        for phase, sums in self.expert_sums(calls).items():
            st = self._moe_stats.setdefault(phase, dict.fromkeys(sums, 0.0))
            for key, value in sums.items():
                st[key] = st.get(key, 0.0) + value
                tm.counter("moe_" + key, "expert layers run / experts that "
                           "received a token / fullest expert's tokens over "
                           "the mean: summed over expert layers and calls",
                           labels=("phase",)).labels(phase=phase).inc(value)
        sums, self._sums_pending = self._sums_pending, []
        for phase, keys, by_layer in sums:
            st = self._moe_stats.setdefault(phase, {})
            over_layers = np.asarray(by_layer, np.float64) \
                .reshape(-1, len(keys)).sum(axis=0)
            for key, value in zip(keys, over_layers):
                st[key] = st.get(key, 0.0) + float(value)
        return self._moe_stats

    @staticmethod
    def expert_sums(calls) -> Dict[str, Dict[str, float]]:
        """``calls``: ``(phase, counts)`` with ``counts`` (expert layers,
        experts) the tokens each expert received in one program call.  By
        phase: ``layer_steps``, ``experts_touched`` and
        ``expert_load_max_over_mean`` summed over the expert layers that
        received any token (a call of padding alone counts nothing)."""
        out: Dict[str, Dict[str, float]] = {}
        for phase, counts in calls:
            counts = counts[counts.sum(axis=1) > 0]
            if not len(counts):
                continue
            st = out.setdefault(phase, {
                "layer_steps": 0.0, "experts_touched": 0.0,
                "expert_load_max_over_mean": 0.0})
            st["layer_steps"] += len(counts)
            st["experts_touched"] += float((counts > 0).sum())
            st["expert_load_max_over_mean"] += float(
                (counts.max(axis=1) / counts.mean(axis=1)).sum())
        return out

    def _reference_run(self, seq: Sequence[int], fetch_list):
        """One full-recompute step of the reference program over
        ``seq`` (the one-at-a-time oracle)."""
        L = len(seq)
        S = _pow2_bucket(L, self.prefill_bucket_min, None)
        toks = np.zeros((1, S), np.int32)
        toks[0, :L] = seq
        pos = np.minimum(np.arange(S, dtype=np.int32),
                         self.cfg.max_seq_len - 1)[None]
        feed = {"tokens": toks, "positions": pos,
                "last_index": np.array([L - 1], np.int32)}
        if "attn_mask" in self.ref_feeds:
            feed["attn_mask"] = _causal_mask(S)
        return self.exe.run(self.ref_prog, feed=feed,
                            fetch_list=fetch_list, scope=self.scope)

    def reference_next_token(self, seq: Sequence[int]) -> int:
        return int(self._reference_run(seq, self.ref_fetch)[0][0])

    def reference_logits(self, seq: Sequence[int]) -> np.ndarray:
        """The reference program's next-token logits after ``seq`` —
        what parity checks compare where an argmax could flip on a
        near-tie."""
        out = self._reference_run(seq,
                                  [self.ref_prog._form_extras.logits])
        return np.asarray(out[0]).reshape(-1)

    def greedy_reference(self, prompt: Sequence[int],
                         max_new_tokens: int) -> List[int]:
        seq = list(prompt)
        outs: List[int] = []
        for _ in range(max_new_tokens):
            t = self.reference_next_token(seq)
            outs.append(t)
            seq.append(t)
            if t == self.cfg.eos_id:
                break
        return outs

    def _finished(self, req: Request, token: int) -> bool:
        return (len(req.out_tokens) >= req.max_new_tokens
                or token == self.cfg.eos_id)

    # -- memory observability (r15) ---------------------------------------
    def kv_pool_resident_bytes(self) -> int:
        """PER-DEVICE bytes pinned by the paged K/V pools for the
        engine's lifetime: 2 pools (K and V) per layer at the
        allocator's fixed shape (stored lane-full or not, the same
        bytes: no padding), PLUS the int8 scale pools when the
        storage is quantized — the ``kv_pool`` resident block the
        static planner (framework/memory_plan.py) charges against the
        HBM budget.  Under TP the pools (and scale pools) shard on
        kv_heads, so each device holds exactly 1/tp of the global
        bytes (every sharded dim divides evenly — validate_tp_degree)."""
        per_pool = int(np.prod(self.kv_config.pool_shape())) * \
            np.dtype(self.kv_config.dtype).itemsize
        per_pool += self.kv_config.scale_bytes()
        names = self.cfg.cache_pool_names()
        held = (len(names) - len(self._window_pools)) * per_pool
        if self._window_pools:      # the window group's pools are smaller
            held += len(self._window_pools) * np.dtype(
                self.kv_config.dtype).itemsize * int(np.prod(
                    self.kv_config.pool_shape(window=True)))
        return held // self.tp

    def memory_stats(self) -> dict:
        """The serving-side memory section (tools/mem_report.py):
        fixed pool residency, the allocator's peak page usage converted
        to bytes, weight bytes, and the device's measured view."""
        from ..utils.memory import measured_peak

        ps = self.kv.stats()
        token_bytes = self.cfg.kv_token_bytes(self.kv_config.dtype)
        weights = 0
        for n in self.cfg.param_specs():
            v = self.scope.get(n)
            if v is not None and hasattr(v, "nbytes"):
                nb = int(v.nbytes)  # global bytes (sharded or not)
                if self.tp > 1 and self._tp_spec(n) is not None:
                    nb //= self.tp  # this device's shard of the var
                weights += nb
        try:
            measured = measured_peak(0)
        except Exception:
            measured = {"peak_bytes": 0, "source": "unavailable"}
        return {
            "kv_pool_resident_bytes": self.kv_pool_resident_bytes(),
            "kv_pool_dtype": self.kv_config.dtype,
            "kv_pool_scale_bytes": int(
                2 * self.cfg.num_layers * self.kv_config.scale_bytes()),
            "kv_pool_capacity_tokens": int(ps["effective_capacity_tokens"]),
            "kv_pool_peak_token_bytes": int(
                ps["peak_pages"] * self.kv_config.page_size * token_bytes),
            "kv_pool_peak_pages": int(ps["peak_pages"]),
            # peak/in-use pages count DISTINCT pages: a CoW-shared page
            # is one page of the (fixed) pool block the planner models
            "prefix_cache": ps["prefix_cache"],
            "weight_bytes": int(weights),
            "tp": self.tp,
            "measured": measured,
        }


class ServingEngine:
    """Continuous (inflight) batching over one _EngineCore.

    Scheduling is deterministic for a fixed request sequence: the
    admission policy (inference/admission.py, ``FLAGS_admission_policy``
    or the ``admission_policy`` kwarg) decides admission order, load
    shedding and the preemption victim as pure functions of the queue +
    SLO-tracker state; the default ``fifo`` policy keeps FIFO admission
    in submit order (head-of-line blocking, no reordering, no
    shedding), immediate eviction on finish, and youngest-first
    preemption on pool exhaustion — so a seeded trace replays
    bit-identically (pinned by test)."""

    def __init__(self, cfg: Optional[ServedModel] = None,
                 weights: Optional[Dict[str, np.ndarray]] = None,
                 model_dir: Optional[str] = None,
                 max_batch: int = 8, token_budget: int = 256,
                 seed: int = 0, admission_policy=None,
                 prefill_chunk: Optional[int] = None,
                 spec_k: Optional[int] = None,
                 proposer=None,
                 sampling: Optional[SamplingParams] = None,
                 pipeline: int = 0, **core_kw):
        from ..utils.flags import flag

        if sampling is None:
            # FLAGS_sample_temperature > 0 arms sampled decode with the
            # default nucleus-off/top-k-off params; richer configs come
            # through the kwarg (a SamplingParams)
            temp = float(flag("sample_temperature", 0.0) or 0.0)
            if temp > 0.0:
                sampling = SamplingParams(temperature=temp)
        self.sampling = sampling if _sampled(sampling) else None
        core_kw.setdefault("sampling", self.sampling)
        core_kw.setdefault("sample_seed", seed)
        core_kw["max_batch"] = max_batch
        if model_dir is not None:
            self.core = _EngineCore.from_model_dir(model_dir, **core_kw)
        else:
            if cfg is None:
                raise ValueError("need cfg or model_dir")
            self.core = _EngineCore(
                cfg, weights or cfg.init_weights(seed), **core_kw)
        self.cfg = self.core.cfg
        self.kv = self.core.kv
        self.kv_dtype = self.core.kv_dtype
        self.max_batch = max_batch
        self.token_budget = token_budget
        self.policy = get_policy(admission_policy)
        if prefill_chunk is None:
            prefill_chunk = int(flag("prefill_chunk_tokens", 0) or 0)
        self.prefill_chunk = max(int(prefill_chunk), 0)
        if spec_k is None:
            spec_k = int(flag("spec_decode_k", 0) or 0)
        self.spec_k = max(int(spec_k), 0)
        self.cfg.validate(prefill_chunk=self.prefill_chunk,
                          spec_k=self.spec_k)
        if isinstance(proposer, str):
            proposer = get_proposer(proposer)
        self.proposer: Optional[Proposer] = \
            proposer if proposer is not None else \
            (NGramProposer() if self.spec_k else None)
        if hasattr(self.proposer, "bind"):
            # a drafter that runs a program of its own on the engine's
            # scope and pools (the MTP module): it is told of each prefill
            # and each verify call, whose hidden states it consumes
            self.proposer.bind(self.core)
        # verify-call budget debt: tokens a verify emitted BEYOND the
        # one-per-sequence this step's budget already charged; settled
        # against the NEXT step's budget, so a verify call charges
        # accepted+1 tokens exactly like the monolithic paths (always 0
        # with spec off, and 0 at zero acceptance)
        self._spec_debt = 0
        self._prefill_job: Optional[_PrefillJob] = None
        self.waiting: List[Request] = []
        self.running: List[_SeqState] = []   # admission order
        self.stats = {"admitted": 0, "finished": 0, "preempted": 0,
                      "shed": 0, "decode_steps": 0, "prefill_tokens": 0,
                      "decode_tokens": 0, "prefill_hit_tokens": 0,
                      "prefill_chunks": 0, "max_prefill_step_tokens": 0,
                      "spec_proposed": 0, "spec_accepted": 0,
                      # by phase, what the forms' kernels say of their own
                      # work (the core's dict itself: it fills as calls go)
                      "kernels": self.core.kernel_stats,
                      # by phase, the distinct (padded batch, table width)
                      # the decode form was fed (the core's dict, likewise)
                      "decode_feed_shapes": self.core.decode_feed_shapes}
        self._step_no = 0
        self._submit_seq = 0
        # pipelined steps: step N is dispatched before the tokens of step
        # N - pipeline are read (``True``: 1), so the device never waits for
        # the host's bookkeeping, nor, at 2, for a host that stalls a step long
        self.pipeline = max(int(pipeline), 0)
        # per step in flight: its calls, (device tokens, [(state, last?)])
        self._in_flight: List[list] = []
        if self.pipeline:
            self._open_pipeline()

    def _open_pipeline(self):
        """``pipeline=True`` holds only where a step's schedule does not
        depend on the tokens it makes: refuse the rest here, loudly."""
        from ..utils.flags import flag

        why = [what for what, on in (
            ("sampled decoding (a lane counts emitted tokens)",
             self.sampling is not None),
            ("speculative decoding", bool(self.spec_k)),
            ("chunked prefill", bool(self.prefill_chunk)),
            ("the prefix cache (it indexes token values)",
             self.kv.prefix_cache),
            ("an EOS token (a finish the host must see)",
             self.cfg.eos_id >= 0),
            ("tensor parallelism", self.core.tp > 1),
            (f"admission policy {self.policy.name!r} (it reads token "
             f"times)", self.policy.name != "fifo"),
            ("request tracing", bool(flag("trace_requests", 0))),
        ) if on]
        if why:
            raise ValueError("ServingEngine(pipeline=True) cannot be served "
                             "with: " + "; ".join(why))
        self._free_lanes = list(range(self.max_batch - 1, -1, -1))
        self.core.open_board(self.max_batch)

    # -- API ---------------------------------------------------------------
    def submit(self, req: Request):
        try:
            _reject_unservable(req, self.cfg, self.core.kv_config)
            if len(req.prompt) + 1 > self.token_budget \
                    and not self.prefill_chunk:
                # admission requires prompt+1 tokens inside the budget;
                # a larger prompt would head-of-line block the FIFO
                # forever — UNLESS chunked prefill is on, which serves
                # it one budget-sized slice per step
                raise RequestRejected(
                    f"request {req.req_id!r}: prompt of "
                    f"{len(req.prompt)} tokens can never fit "
                    f"token_budget {self.token_budget}", "budget")
        except ValueError as e:
            _count_reject(e)
            _trace_reject(req, str(e), getattr(e, "reason", "unservable"))
            raise
        req._seq = self._submit_seq
        self._submit_seq += 1
        _trace_submit(req)
        self.waiting.append(req)

    def has_work(self) -> bool:
        return bool(self.waiting or self.running or self._in_flight
                    or self._prefill_job is not None)

    def step(self, now: float = 0.0) -> List[StepEvent]:
        """One serving iteration: shed what the policy gives up on,
        admit (in policy order, up to the token budget and pool
        capacity), prefill the admissions, decode every running
        sequence once, evict finishes.  Returns this step's emitted
        tokens.

        Spans (profiler.RecordEvent, lane "serving"): ``engine/step``
        holds ``engine/schedule`` (shed, order, admission checks,
        preemption), one ``engine/prefill`` a prefill or chunk and one
        ``engine/decode`` (both opened by the core, around feed
        building and the program call), and ``engine/emit`` (token
        append, latency observation, finish bookkeeping)."""
        self._step_no += 1
        with RecordEvent("engine/step", "serving") as span:
            if span.recording:
                span.set(step=self._step_no, running=len(self.running),
                         waiting=len(self.waiting))
            if self.pipeline:
                return self._step_pipelined(now)
            return self._step(now)

    def _step(self, now: float) -> List[StepEvent]:
        events: List[StepEvent] = []
        handles = _TM.current()
        with RecordEvent("engine/schedule", "serving"):
            # chaos serving faults (pool_spike / req_burst bookkeeping)
            # — a single cached None check when FLAGS_chaos is unset
            chaos.on_serving_step(self, self._step_no)
            # --- shedding: the policy gives up queued requests whose
            # SLO is no longer reachable BEFORE paying admission for
            # them ---------------------------------------------------
            for req in self.policy.shed(self, now):
                self._shed(req, now)
            # --- admission: every decode step takes new work, in
            # policy order (fifo: submit order — order() is a no-op) --
            self.policy.order(self, now)
            # settle last step's verify debt: tokens a verify call
            # emitted beyond one-per-sequence charge THIS step's
            # budget, so spec decode pays accepted+1 exactly like the
            # monolithic paths (_spec_debt is always 0 with spec off —
            # the term vanishes)
            budget = self.token_budget - len(self.running) - self._spec_debt
            self._spec_debt = 0
        prefilled_this_step = 0
        # --- in-flight chunked prefill: one budget-sized slice per
        # step, ahead of new admissions (it reached the head first);
        # decode still runs below, so a long prompt never stalls it ----
        if self._prefill_job is not None:
            job = self._prefill_job
            # the slice shrinks to this step's budget so progress is
            # guaranteed whenever any budget exists (a slice larger
            # than the budget would otherwise wait forever when
            # prefill_chunk > token_budget)
            n = min(self.prefill_chunk, len(job.req.prompt) - job.pos,
                    budget)
            if n > 0:
                r = self.core.advance_prefill(job, n)
                with RecordEvent("engine/emit", "serving"):
                    if r is None:
                        # pool can no longer cover the slice: release
                        # the pages (the prefix cache keeps finished
                        # slices warm) and requeue at the head
                        self.core.abort_prefill(job)
                        self.waiting.insert(0, job.req)
                        self._prefill_job = None
                        _trace_backpressure(job.req, "prefill_backpressure")
                    else:
                        # the completing slice also emits the first
                        # output token — charge its +1 like the
                        # monolithic paths
                        budget -= n + (1 if r else 0)
                        prefilled_this_step += n
                        self._count_prefill(n, job)
                        if r:
                            self._prefill_job = None
                            self._admit_job(job, now, events)
        while (self.waiting and len(self.running) < self.max_batch
               and self._prefill_job is None):
            req = self.waiting[0]
            cost = len(req.prompt) + 1
            if not self.prefill_chunk and not self.kv.prefix_cache:
                # the exact pre-feature (r18) admission path — pinned
                # byte-identical when both flags are off
                with RecordEvent("engine/schedule", "serving"):
                    if cost > budget:
                        break
                    if not self._admission_fits(req):
                        _trace_backpressure(req, "admission_backpressure")
                        break  # pool backpressure: retry next step
                job = self.core.prefill_job(req)
                if job is None:
                    _trace_backpressure(req, "prefill_backpressure")
                    break  # pool backpressure: retry next step
                with RecordEvent("engine/emit", "serving"):
                    tok = job.first_token
                    _trace_admit(req, now, job)
                    self.waiting.pop(0)
                    budget -= cost
                    prefilled_this_step += len(req.prompt)
                    req.admitted_at = now if req.admitted_at is None else \
                        req.admitted_at
                    self.stats["admitted"] += 1
                    self.stats["prefill_tokens"] += len(req.prompt)
                    handles.admitted.inc()
                    handles.prefill_tokens.inc(len(req.prompt))
                    if is_profiler_enabled():
                        instant_event("admit", cat="serving",
                                      args={"req": str(req.req_id),
                                            "prompt": len(req.prompt)})
                    st = _SeqState(req, tok)
                    req.out_tokens.append(tok)
                    _observe_token(req, now)
                    self._tell_drafter_of_prefill(req, tok)
                    if self.core._finished(req, tok):
                        events.append(self._finish(st, tok, now))
                    else:
                        events.append(
                            StepEvent(req.req_id, tok, False, now))
                        self.running.append(st)
                continue
            # feature path: prefix-cache hits shrink the admission cost
            # to the COMPUTED suffix, and long suffixes go through the
            # chunked path (one slice per step)
            with RecordEvent("engine/schedule", "serving"):
                # gate with a READ-ONLY hit estimate first: acquiring
                # and releasing prefix pages on every blocked step
                # would churn the allocator (and re-hash the prompt)
                # for nothing
                est_hit = self.kv.match_prefix(req.prompt[:-1])[0] \
                    if self.kv.prefix_cache and len(req.prompt) > 1 else 0
                if not self._admission_fits(req, len(req.prompt) - est_hit):
                    _trace_backpressure(req, "admission_backpressure")
                    break
                job = self.core.start_prefill(req)
                remaining = len(req.prompt) - job.pos
                # chunk whenever the remainder exceeds the chunk budget
                # OR can't fit this step's token budget whole — the
                # second arm is what keeps a prompt with remaining in
                # [budget, prefill_chunk] schedulable instead of
                # head-of-line blocking forever (submit waived the
                # budget reject)
                chunked = bool(self.prefill_chunk) and (
                    remaining > self.prefill_chunk
                    or remaining + 1 > budget)
                n = min(self.prefill_chunk, remaining, budget) \
                    if chunked else remaining
                if (n <= 0) if chunked else (remaining + 1 > budget):
                    self.core.abort_prefill(job)
                    break  # wait for budget headroom
            r = self.core.advance_prefill(job, n if chunked else None)
            with RecordEvent("engine/emit", "serving"):
                if r is None:
                    self.core.abort_prefill(job)
                    _trace_backpressure(req, "prefill_backpressure")
                    break
                self.waiting.pop(0)
                budget -= n + (1 if r else 0)   # +1: first output token
                prefilled_this_step += n
                self._count_prefill(n, job)
                if r:
                    self._admit_job(job, now, events)
                else:
                    self._prefill_job = job
                    # one chunked prefill in flight at a time: admission
                    # resumes when it completes (loop condition above)
        # --- preemption: decoding adds one token per running seq --------
        with RecordEvent("engine/schedule", "serving"):
            while self.running and not self._can_grow_all():
                # fifo: index -1 (youngest); slo_aware: least lost work
                victim = self.running.pop(
                    self.policy.victim_index(self.running))
                self.kv.free_sequence(victim.req.req_id, preempted=True)
                victim.req.out_tokens = []
                victim.req._tm_last = None
                victim.req._tm_gaps = []
                victim.req.preemptions += 1
                if hasattr(self.proposer, "forget"):
                    self.proposer.forget(victim.req.req_id)
                _trace_preempt(victim.req, now)
                self.waiting.insert(0, victim.req)
                self.stats["preempted"] += 1
                handles.preempted.inc()
                if is_profiler_enabled():
                    instant_event("preempt", cat="serving",
                                  args={"req": str(victim.req.req_id)})
        # --- decode ------------------------------------------------------
        if self.running and self.spec_k:
            events.extend(self._spec_decode_step(now))
        elif self.running:
            chaos.on_decode_step()
            toks = self.core.decode_batch(self.running)
            with RecordEvent("engine/emit", "serving"):
                self.stats["decode_steps"] += 1
                self.stats["decode_tokens"] += len(self.running)
                _trace_decode(self.running, toks, now,
                              *self.core.decode_wall,
                              self.stats["decode_steps"], tp=self.core.tp)
                handles.decode_steps.inc()
                handles.decode_tokens.inc(len(self.running))
                still = []
                for st, tok in zip(self.running, toks):
                    st.req.out_tokens.append(tok)
                    st.last_token = tok
                    _observe_token(st.req, now)
                    if self.core._finished(st.req, tok):
                        events.append(self._finish(st, tok, now))
                    else:
                        events.append(
                            StepEvent(st.req.req_id, tok, False, now))
                        still.append(st)
                self.running = still
        self.stats["max_prefill_step_tokens"] = max(
            self.stats["max_prefill_step_tokens"], prefilled_this_step)
        return events

    def _step_pipelined(self, now: float) -> List[StepEvent]:
        """``_step`` with the host off the device's path: the same
        admissions, prefills, preemptions and decode in the same order and
        through the same calls, but no call's tokens are read before the
        calls of the next ``pipeline`` steps are dispatched.  A token goes from the call that
        made it to the call that consumes it on the device (the core's
        token board); everything the schedule needs is known without it
        (``_open_pipeline`` refused what is not): a sequence ends when its
        count is full, and its pages are freed when its last call is
        dispatched, which the device runs before any call that takes them.
        So the schedule and every token are ``_step``'s, and a client has a
        step's tokens ``pipeline`` steps later: the events returned are
        those of that earlier step (and of all in flight, once nothing is
        left to dispatch)."""
        events: List[StepEvent] = []
        handles = _TM.current()
        calls = []
        with RecordEvent("engine/schedule", "serving"):
            chaos.on_serving_step(self, self._step_no)
            budget = self.token_budget - len(self.running)
        prefilled_this_step = 0
        while self.waiting and len(self.running) < self.max_batch:
            req = self.waiting[0]
            cost = len(req.prompt) + 1
            with RecordEvent("engine/schedule", "serving"):
                if cost > budget or not self._admission_fits(req):
                    break
            job = self.core.prefill_job(req)
            if job is None:
                break  # pool backpressure: retry next step
            with RecordEvent("engine/emit", "serving"):
                self.waiting.pop(0)
                budget -= cost
                prefilled_this_step += len(req.prompt)
                req.admitted_at = now if req.admitted_at is None else \
                    req.admitted_at
                self.stats["admitted"] += 1
                self.stats["prefill_tokens"] += len(req.prompt)
                handles.admitted.inc()
                handles.prefill_tokens.inc(len(req.prompt))
                st = _SeqState(req, sent=1)
                last = st.sent >= req.max_new_tokens
                if last:
                    self.kv.free_sequence(req.req_id)
                else:
                    st.lane = self._free_lanes.pop()
                    self.core.board_put([st.lane], job.first_token)
                    self.running.append(st)
                calls.append((job.first_token, [(st, last)]))
        with RecordEvent("engine/schedule", "serving"):
            if self.running and not self._can_grow_all():
                # a victim's tokens are with the client before it goes back
                # to the queue, as in ``_step``: read all that is in flight
                events.extend(self._deliver(self._in_flight + [calls], now))
                self._in_flight, calls = [], []
            while self.running and not self._can_grow_all():
                victim = self.running.pop(
                    self.policy.victim_index(self.running))
                self.kv.free_sequence(victim.req.req_id, preempted=True)
                self._free_lanes.append(victim.lane)
                victim.req.out_tokens = []
                victim.req._tm_last = None
                victim.req._tm_gaps = []
                victim.req.preemptions += 1
                self.waiting.insert(0, victim.req)
                self.stats["preempted"] += 1
                handles.preempted.inc()
        if self.running:
            chaos.on_decode_step()
            toks = self.core.decode_batch(self.running)
            with RecordEvent("engine/emit", "serving"):
                self.stats["decode_steps"] += 1
                self.stats["decode_tokens"] += len(self.running)
                handles.decode_steps.inc()
                handles.decode_tokens.inc(len(self.running))
                rows, still = [], []
                for st in self.running:
                    st.sent += 1
                    last = st.sent >= st.req.max_new_tokens
                    rows.append((st, last))
                    if last:
                        self.kv.free_sequence(st.req.req_id)
                        self._free_lanes.append(st.lane)
                    else:
                        still.append(st)
                self.running = still
                calls.append((toks, rows))
        self.stats["max_prefill_step_tokens"] = max(
            self.stats["max_prefill_step_tokens"], prefilled_this_step)
        # the device has this step's calls: now wait for an earlier step's
        self._in_flight.append(calls)
        keep = self.pipeline if self.waiting or self.running else 0
        due = max(len(self._in_flight) - keep, 0)
        events.extend(self._deliver(self._in_flight[:due], now))
        del self._in_flight[:due]
        return events

    def _deliver(self, steps, now: float) -> List[StepEvent]:
        """Read the tokens of the calls of dispatched ``steps`` and do for
        each what ``_step`` does when it has a token: the request's record,
        the latency observation, the event, the finish."""
        events = []
        for toks, rows in (call for calls in steps for call in calls):
            with RecordEvent("executor/fetch"):
                toks = np.asarray(toks).reshape(-1)
            with RecordEvent("engine/emit", "serving"):
                for (st, last), tok in zip(rows, toks.tolist()):
                    st.req.out_tokens.append(tok)
                    st.last_token = tok
                    _observe_token(st.req, now)
                    events.append(
                        self._finish(st, tok, now, free=False) if last
                        else StepEvent(st.req.req_id, tok, False, now))
        return events

    def _spec_decode_step(self, now: float) -> List[StepEvent]:
        """One speculative decode iteration (``spec_k > 0``): draft up
        to ``spec_k`` tokens per running sequence, verify every chunk
        in ONE program call, emit each sequence's longest agreeing
        draft prefix PLUS the verify's own next token, truncate
        rejected drafts back out of the KV cache.

        Greedy acceptance is exact-argmax match, so the emitted stream
        is token-identical to monolithic decode (pinned by test).
        Sampled acceptance draws row j from position j's RNG lane —
        the same lane monolithic decode uses there — so every emitted
        token is a valid lane-keyed draw from the target distribution;
        the stream can still differ from monolithic sampled decode at
        nucleus/top-k filter boundaries, because the verify and decode
        program forms are different FP compositions and
        ``jax.random.categorical`` is not ULP-robust the way argmax is
        (top_k=1 sampling IS exactly baseline — pinned by test; the
        sampled contracts are seeded-replay determinism and
        resume-invariant lanes, see tests/test_spec_decode.py).  A
        zero-accept step emits exactly one token per sequence —
        baseline step count and budget accounting."""
        events: List[StepEvent] = []
        handles = _TM.current()
        chaos.on_decode_step()
        batch = self.running
        with RecordEvent("engine/draft", "serving"):
            # page capacity: the preemption loop guaranteed one token of
            # growth per sequence; drafts spend only what remains AFTER
            # those base reservations, each shrinking until it fits (a
            # draft can never steal another sequence's guaranteed token)
            bases = [self.kv.pages_needed(st.req.req_id, 1)
                     + self.kv.cow_fork_need(st.req.req_id, 1)
                     for st in batch]
            avail = self.kv.num_free_pages - sum(bases)
            drafts: List[List[int]] = []
            for st, base in zip(batch, bases):
                req = st.req
                # never draft past max_new_tokens - 1: the verify's bonus
                # token always lands, so a full accept finishes exactly AT
                # the cap, never beyond it
                cap = min(self.spec_k,
                          req.max_new_tokens - len(req.out_tokens) - 1)
                d = [int(t) for t in self.proposer.propose(req, cap)][:cap] \
                    if cap > 0 else []
                while d:
                    extra = (self.kv.pages_needed(req.req_id, 1 + len(d))
                             + self.kv.cow_fork_need(req.req_id, 1 + len(d))
                             - base)
                    if extra <= avail:
                        avail -= extra
                        break
                    d.pop()
                drafts.append(d)
        items = list(zip(batch, drafts))
        targets = self.core.verify_batch(items)
        hidden, chunk = self.core.last.get("hidden"), \
            self.core.last.get("chunk")
        with RecordEvent("engine/emit", "serving"):
            accepts, emits = self._emit_verified(
                batch, drafts, targets, now, events, handles)
        told = getattr(self.proposer, "after_verify", None)
        if told is not None:
            with RecordEvent("engine/draft", "serving"):
                told(items, hidden, chunk, accepts, emits)
        return events

    def _emit_verified(self, batch, drafts, targets, now, events, handles):
        """Accept, emit and account one verify call's tokens."""
        self.stats["decode_steps"] += 1
        handles.decode_steps.inc()
        # per sequence: accept while the target agrees with the draft,
        # then pre-truncate the emission at max_new_tokens / EOS so the
        # token stream ends exactly where monolithic decode would stop
        accepts, emits = [], []
        for st, d, tgt in zip(batch, drafts, targets):
            a = 0
            while a < len(d) and tgt[a] == d[a]:
                a += 1
            accepts.append(a)
            room = st.req.max_new_tokens - len(st.req.out_tokens)
            emit = tgt[:min(a + 1, room)]
            if self.cfg.eos_id in emit:
                emit = emit[:emit.index(self.cfg.eos_id) + 1]
            emits.append(emit)
        _trace_decode(batch, [e[-1] for e in emits], now,
                      *self.core.decode_wall, self.stats["decode_steps"],
                      spec=[(len(d), a) for d, a in zip(drafts, accepts)],
                      tp=self.core.tp)
        still = []
        for st, d, a, emit in zip(batch, drafts, accepts, emits):
            req = st.req
            fin = False
            for tok in emit:
                req.out_tokens.append(tok)
                _observe_token(req, now)
                if self.core._finished(req, tok):
                    events.append(self._finish(st, tok, now))
                    fin = True
                    break
                events.append(StepEvent(req.req_id, tok, False, now))
            if not fin:
                # roll the rejected draft suffix back out of the pool
                # (a finished sequence was freed whole — no rollback)
                if len(d) > a:
                    self.kv.truncate_tokens(req.req_id, len(d) - a)
                st.last_token = emit[-1]
                still.append(st)
        self.running = still
        n_prop = sum(len(d) for d in drafts)
        n_acc = sum(accepts)
        used = sum(len(e) for e in emits)
        self.stats["decode_tokens"] += used
        self.stats["spec_proposed"] += n_prop
        self.stats["spec_accepted"] += n_acc
        self._spec_debt = used - len(batch)
        handles.decode_tokens.inc(used)
        handles.spec_proposed.inc(n_prop)
        handles.spec_accepted.inc(n_acc)
        if self.stats["spec_proposed"]:
            handles.spec_accept_rate.set(
                self.stats["spec_accepted"] / self.stats["spec_proposed"])
        # the same counts by who drafted: an n-gram lookup and the model's
        # own MTP module accept at rates that say different things
        drafter = getattr(self.proposer, "name",
                          type(self.proposer).__name__)
        for name, value in (("spec_drafted_by_drafter_total", n_prop),
                            ("spec_accepted_by_drafter_total", n_acc)):
            tm.counter(name, "speculative tokens, by drafter",
                       labels=("drafter",)).labels(
                           drafter=drafter).inc(value)
        return accepts, emits

    def _count_prefill(self, n: int, job: _PrefillJob):
        """Feature-path prefill accounting: ``prefill_tokens`` counts
        tokens COMPUTED (cache hits excluded — the 2x-drop metric),
        hits are counted once per job at its first slice."""
        self.stats["prefill_tokens"] += n
        self.stats["prefill_chunks"] += 1
        if job.chunks == 1 and job.hit:
            self.stats["prefill_hit_tokens"] += job.hit
        _TM.current().prefill_tokens.inc(n)

    def _admit_job(self, job: _PrefillJob, now: float, events: list):
        """Completed prefill job -> running sequence (the feature-path
        twin of the inline r18 admission bookkeeping)."""
        req, tok = job.req, job.first_token
        _trace_admit(req, now, job, cached=job.hit, chunks=job.chunks)
        req.admitted_at = now if req.admitted_at is None else \
            req.admitted_at
        self.stats["admitted"] += 1
        _TM.current().admitted.inc()
        if is_profiler_enabled():
            instant_event("admit", cat="serving",
                          args={"req": str(req.req_id),
                                "prompt": len(req.prompt)})
        st = _SeqState(req, tok)
        req.out_tokens.append(tok)
        _observe_token(req, now)
        self._tell_drafter_of_prefill(req, tok)
        if self.core._finished(req, tok):
            events.append(self._finish(st, tok, now))
        else:
            events.append(StepEvent(req.req_id, tok, False, now))
            self.running.append(st)

    def _tell_drafter_of_prefill(self, req: Request, tok: int):
        """A drafter that consumes hidden states is handed the prompt's
        (the prefill just run left them in ``core.last``)."""
        told = getattr(self.proposer, "after_prefill", None)
        if told is not None and self.spec_k:
            told(req, self.core.last["hidden"], tok)

    def _can_grow_all(self) -> bool:
        need = sum(self.kv.pages_needed(st.req.req_id, 1)
                   + self.kv.cow_fork_need(st.req.req_id, 1)
                   for st in self.running)
        return need <= self.kv.num_free_pages and self.kv.window_fits(
            (st.req.req_id, 1) for st in self.running)

    def _admission_fits(self, req: Request,
                        n_tokens: Optional[int] = None) -> bool:
        """Admit only when, AFTER the prompt's pages are taken, every
        running sequence plus the admission can still grow one token —
        otherwise this step's preemption loop would immediately evict
        the sequence we just paid a full prefill for (admit/preempt
        churn repeating the prefill every step).  ``n_tokens`` narrows
        the check to the COMPUTED suffix after a prefix-cache hit (the
        request's sequence already maps the hit pages)."""
        P = len(req.prompt)
        L = P if n_tokens is None else n_tokens
        ps = self.core.kv_config.page_size
        prompt_pages = self.kv.pages_needed(req.req_id, L) \
            + self.kv.cow_fork_need(req.req_id, L)
        growth = sum(self.kv.pages_needed(st.req.req_id, 1)
                     + self.kv.cow_fork_need(st.req.req_id, 1)
                     for st in self.running)
        if req.max_new_tokens > 1:
            # the admission's own one-token headroom — but a request
            # that finishes AT prefill (max_new <= 1: prefill itself
            # emits its only token) never decodes, so demanding growth
            # room for it would livelock a prompt that exactly fills
            # its page budget
            growth += -(-(P + 1) // ps) - -(-P // ps)
        # the window group is counted too: the prompt and its first token
        # there, and one token's growth of every running sequence
        return prompt_pages + growth <= self.kv.num_free_pages and \
            self.kv.window_fits(
                [(req.req_id, L + 1)]
                + [(st.req.req_id, 1) for st in self.running])

    def _shed(self, req: Request, now: float):
        """Terminal `shed` outcome for a queued request: the policy
        decided its SLO is no longer reachable, so refusing it NOW
        keeps the admitted requests' SLO intact.  Traced (root status
        "shed") + countered (serving_shed_total and
        serving_rejects_total{reason="shed"}) — never fed to the SLO
        tracker, so goodput denominators exclude it consistently with
        tools/slo_report.py's independent recomputation."""
        try:
            self.waiting.remove(req)
        except ValueError:
            return
        req.shed_at = now
        self.stats["shed"] += 1
        tm.counter("serving_shed_total",
                   "queued requests shed by the admission policy "
                   "(predicted TTFT can no longer meet the SLO)").inc()
        tm.counter("serving_rejects_total",
                   "requests refused, by reason (pool / budget / "
                   "max_seq_len at submit; shed by the admission policy)",
                   labels=("reason",)).labels(reason="shed").inc()
        _trace_shed(req, now)
        if is_profiler_enabled():
            instant_event("shed", cat="serving",
                          args={"req": str(req.req_id),
                                "waited": round(now - req.arrival_time, 6)})

    def _finish(self, st: _SeqState, tok: int, now: float,
                free: bool = True) -> StepEvent:
        if free:    # a pipelined step freed the pages at dispatch
            self.kv.free_sequence(st.req.req_id)
        if hasattr(self.proposer, "forget"):
            self.proposer.forget(st.req.req_id)
        st.req.finished_at = now
        self.stats["finished"] += 1
        _TM.current().finished.inc()
        _trace_finish(st.req, now)
        if is_profiler_enabled():
            instant_event("evict", cat="serving",
                          args={"req": str(st.req.req_id)})
        return StepEvent(st.req.req_id, tok, True, now)

    def slo_hint(self) -> dict:
        """Live burn rate, goodput and declared targets from the
        process SLO tracker — the signal the ``slo_aware`` admission
        policy (inference/admission.py) drives its slack ordering and
        shed threshold from.  The ``fifo`` policy never reads it."""
        return tm.slo_tracker().admission_hint()

    def run_to_completion(self, now: float = 0.0) -> List[StepEvent]:
        events = []
        while self.has_work():
            events.extend(self.step(now))
        return events

    def generate(self, prompts: Sequence[Sequence[int]],
                 max_new_tokens: int) -> List[List[int]]:
        """Convenience batch API: submit everything, drain, return each
        prompt's generated tokens in submit order."""
        reqs = [Request(i, list(p), max_new_tokens)
                for i, p in enumerate(prompts)]
        for r in reqs:
            self.submit(r)
        self.run_to_completion()
        return [r.out_tokens for r in reqs]
