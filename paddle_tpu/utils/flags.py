"""Global flag system.

Reference: paddle/fluid/platform/flags.cc (26+ gflags, read from FLAGS_*
env vars, exposed to Python via fluid.set_flags/get_flags,
pybind/global_value_getter_setter.cc).  Same three-tier shape: env-seeded
defaults, runtime set_flags, strategy dataclasses elsewhere.
"""
from __future__ import annotations

import os
import sys
from typing import Any, Dict

_DEFAULTS: Dict[str, Any] = {
    "FLAGS_check_nan_inf": False,          # flags.cc:44
    "FLAGS_fraction_of_cpu_memory_to_use": 1.0,   # cpu_info.cc:70
    "FLAGS_initial_cpu_memory_in_mb": 500,        # cpu_info.cc:81
    "FLAGS_communicator_max_merge_var_num": 20,
    "FLAGS_communicator_send_queue_size": 20,
    "FLAGS_communicator_independent_recv_thread": True,
    "FLAGS_communicator_send_wait_times": 5,
    "FLAGS_communicator_recv_wait_ms": 50,
    # RPC robustness (reference: flags.cc FLAGS_rpc_deadline /
    # FLAGS_rpc_retry_times, grpc_client.cc deadline handling): a PS
    # client call must complete within deadline ms; transport failures
    # retry up to retry_times with bounded exponential backoff
    # (backoff_ms * 2^attempt, capped at 2000 ms, +/-50% jitter).
    # Mutating calls carry an idempotence key so a retry after a lost
    # reply never double-applies (distributed_ps/update_recorder.py
    # RequestDeduper).
    "FLAGS_rpc_deadline": 180000,
    "FLAGS_rpc_retry_times": 3,
    "FLAGS_rpc_retry_backoff_ms": 50,
    "FLAGS_seed": 0,
    "FLAGS_enable_unused_var_check": False,
    "FLAGS_tpu_donate_buffers": True,
    # training-time IR fusion pipeline (reference: build_strategy
    # fuse_bn_act_ops / fuse_bn_add_act_ops); applied by the Executor at
    # compile time on a program clone
    "FLAGS_apply_ir_passes": True,
    # dygraph multi-tensor Adam: flatten all dense f32 param updates
    # into one fused kernel (reference: ir/fuse_optimizer_ops_pass/
    # fuse_adam_op_pass.cc does the same rewrite on the static graph)
    "FLAGS_fuse_optimizer_dygraph": True,
    # PRNG implementation for dropout/random ops on the single-device
    # paths: "rbg" uses the TPU hardware RNG (~10% of an ERNIE step
    # cheaper than threefry mask generation); "threefry2x32" restores
    # jax's default counter-based stream
    "FLAGS_tpu_prng_impl": "rbg",
    # NHWC layout propagation for conv/bn/pool chains (framework/ir.py
    # layout_transform_pass): "auto" enables it when the executor place
    # is an accelerator, "1"/"0" force it on/off everywhere.  "0"
    # restores the NCHW pipeline bit-for-bit.
    "FLAGS_tpu_nhwc": "auto",
    # executor step session: keep donated state device-resident across
    # Executor.run calls (zero scope reads per steady-state step).  Off
    # restores the per-step scope.get rebind path.
    "FLAGS_tpu_step_session": True,
    # profile-ranked Pallas epilogue fusion (framework/ir.py
    # fuse_epilogue_pass): rewrite conv2d->batch_norm(->add)->relu and
    # matmul/mul->elementwise_add->activation chains (fwd AND the
    # matching grad chains) into the fused_conv_bn_act /
    # fused_matmul_bias_act ops, ranked by utils/cost_model.py
    # rank_fusion_candidates.  "auto" enables it when the executor place
    # is an accelerator (like FLAGS_tpu_nhwc); "1"/"0" force on/off.
    # "0" restores the unfused pipeline bit-for-bit.
    "FLAGS_tpu_fuse": "auto",
    # input-pipeline double buffering (executor.py double_buffered_feeds):
    # batch k+1's feed staging (dtype cast + device_put_owned — the
    # donation-safe copy, see executor.device_put_owned) runs on a
    # background thread while step k's dispatch is in flight.  0 stages
    # synchronously on the caller's thread — same values, no overlap.
    "FLAGS_tpu_double_buffer": True,
    # Sharded data parallelism over the 'dp' mesh axis (the Fleet
    # `sharding` strategy analog), staged like fleet sharding_stage /
    # ZeRO:
    #   0  off (default): everything replicated — today's behavior;
    #   1  ZeRO-1: optimizer state (Adam moments / momentum velocities /
    #      the dygraph fused-Adam flat master) shards 1/ndev per device;
    #   2  ZeRO-2: stage 1 + gradients shard — fused grad buckets lower
    #      to reduce-scatter straight into the per-device shard update,
    #      with no full-gradient materialization;
    #   3  ZeRO-3: stage 2 + parameters shard over dp with just-in-time
    #      all-gather at each forward/backward consumer and immediate
    #      discard.
    # Truthy values coerce to stage 1 (the r7 flag was a bool).
    "FLAGS_dp_sharding": 0,
    # coalesced gradient communication (reference:
    # ir/fuse_all_reduce_op_pass.cc + coalesce_grad_tensor_pass.cc):
    # consecutive same-dtype c_allreduce_sum ops bucket up to this many
    # MB of payload and lower to ONE flattened collective.  0 disables
    # the rewrite (one collective per gradient tensor, today's graph).
    # "auto" (r9) derives VARIABLE bucket boundaries from the modeled
    # backward timeline (utils/cost_model.py): buckets are chosen so the
    # serialized collective stream finishes as early as possible —
    # minimizing est. exposed comm rather than bucket count.  Requires
    # FLAGS_dp_comm_overlap (with overlap off, "auto" behaves as the
    # 32 MB default).
    "FLAGS_fuse_grad_size_in_MB": 32.0,
    # compressed allreduce for fused gradient buckets (EQuARX-style,
    # arxiv 2506.17615): "bf16" halves wire bytes by casting the bucket
    # payload to bf16 for transport while accumulating the reduction in
    # f32; "none" (default) keeps full-width f32 allreduce.
    "FLAGS_dp_grad_compress": "none",
    # backward-overlap scheduling for fused gradient buckets (reference:
    # multi_devices_graph_pass backward-op-aware allreduce ordering):
    # order buckets by last-gradient-ready position and issue each
    # bucket's collective right after its last input producer, so bucket
    # 0's collective runs while later layers are still in backward.  Off
    # restores the r7 append-at-last-member schedule.
    "FLAGS_dp_comm_overlap": True,
    # ZeRO-3 parameter-prefetch window (ops): a sharded parameter's
    # all-gather is hoisted this many ops ahead of its first consumer in
    # each direction (forward / backward), deduping the per-consumer
    # gathers into one gather per param per direction with discard after
    # the last consumer — gather layer k+1 while layer k computes.  0
    # restores the r8 just-in-time gather at every consumer.
    "FLAGS_dp_prefetch_depth": 1,
    # cost-model-driven auto-parallel plan search (parallel/
    # plan_search.py, r16): "auto" makes the DP compile path ENUMERATE
    # candidate plans (ZeRO stage x bucket threshold incl. "auto" x
    # prefetch depth incl. per-param autotune x comm overlap), price
    # each with the calibrated cost model's modeled step time, reject
    # candidates whose plan_memory() modeled peak exceeds
    # FLAGS_hbm_budget_mb BEFORE any compile, and run the argmin through
    # the normal verifier-bracketed pass pipeline.  The chosen plan is
    # attached as compiled._plan, gauged in telemetry, and explainable
    # via tools/dp_comm_stats.py --plan.  "" (default) keeps today's
    # flag-driven behavior bit-for-bit: FLAGS_dp_sharding /
    # FLAGS_fuse_grad_size_in_MB / FLAGS_dp_prefetch_depth /
    # FLAGS_dp_comm_overlap apply exactly as set.
    "FLAGS_dp_plan": "",
    # while_loop with a statically-derivable trip count (counter-vs-
    # constant less_than cond, constant-step counter update) lowers to
    # lax.scan: the forward stays on-device and the backward becomes one
    # scan-vjp computation instead of the per-iteration host replay
    # loop.  0 restores the lax.while_loop / host-replay path.
    "FLAGS_while_static_scan": True,
    # deterministic fault injection (utils/chaos.py): a seeded schedule
    # string — e.g. "seed=7;kill@12;rpc_drop=recv@3;trunc_ckpt@1" —
    # that kills the rank at a step, drops/delays RPCs and truncates
    # checkpoint files, reproducibly.  Empty = all hooks are no-ops.
    "FLAGS_chaos": "",
    # unified runtime telemetry (utils/telemetry.py): the process-wide
    # metrics registry the executor / serving engine / PS client publish
    # to.  0 makes every instrument the shared no-op object — no
    # registry writes, no per-call allocation — restoring prior behavior
    # bit-for-bit (host-side bookkeeping only; it never touches program
    # numerics either way, which the telemetry tests pin).
    "FLAGS_telemetry": True,
    # request-scoped distributed tracing (utils/tracing.py): the
    # serving engine records a span tree per request (submit ->
    # queue_wait -> prefill -> decode steps -> preempt/resume cycles ->
    # finish/reject), the PS client injects trace context next to the
    # r11 idempotence key so the server's span joins the same trace,
    # and spans emit as a per-request lane in the unified chrome trace.
    # Off (default): nothing records, nothing allocates — serving token
    # streams and training losses are bit-identical (pinned by test).
    "FLAGS_trace_requests": False,
    # head-based sampling for request traces: the keep/drop decision is
    # a pure crc32 function of (FLAGS_trace_seed, req_id) made once at
    # submit, so a seeded loadgen trace samples the SAME requests on
    # every replay (the r12 determinism contract).  1.0 = every request.
    "FLAGS_trace_sample_rate": 1.0,
    "FLAGS_trace_seed": 0,
    # declared serving SLO targets (utils/telemetry.py SLOTracker):
    # TTFT and per-token latency bounds in ms (0 = target unset — every
    # request counts as within), the SLO objective (fraction of
    # requests that must meet the targets; 1-objective is the error
    # budget the burn rate is measured against) and the rolling
    # request window the burn rate is computed over.  Tools (slo_report
    # / overload_bench) override these per run via
    # telemetry.slo_tracker().configure().
    "FLAGS_slo_ttft_ms": 0.0,
    "FLAGS_slo_token_ms": 0.0,
    "FLAGS_slo_objective": 0.99,
    "FLAGS_slo_window": 256,
    # serving admission/preemption policy (inference/admission.py):
    # "fifo" (default) keeps FIFO admission order, youngest-first
    # preemption and no shedding — byte-identical to the pre-policy
    # engine (token streams, event streams and telemetry counters
    # pinned by test).  "slo_aware" orders admission by remaining SLO
    # slack (declared TTFT target scaled down by the live burn rate
    # from slo_hint(), minus time queued), SHEDS queued requests whose
    # predicted TTFT can no longer meet the target (explicit `shed`
    # outcome: traced root status="shed" +
    # serving_rejects_total{reason="shed"} — distinct from the
    # unservable submit rejection), and preempts the victim with the
    # LEAST lost work (prompt + decoded tokens recomputed on resume)
    # instead of the youngest.  Deterministic for a seeded trace on a
    # deterministic clock (tools/overload_bench.py is the A/B oracle).
    "FLAGS_admission_policy": "fifo",
    # copy-on-write KV prefix caching (inference/kv_cache.py): pages
    # become refcounted and immutable-once-full, full (and partial-tail)
    # prompt pages are indexed by a chained content hash, and a new
    # request's prefill SKIPS every already-cached page of its prompt —
    # the pages map into its block table at refcount+1; the first write
    # into a shared partial page forks it (CoW), frees decrement
    # refcounts and reclaim only at zero, and refcount-0 pages stay in
    # the index as evictable cached pages (deterministic seeded
    # eviction order) until fresh pages run out.  Off (default): the
    # allocator runs the exact r12 FIFO handout — byte-identical
    # (pinned by test).
    "FLAGS_kv_prefix_cache": False,
    # chunked prefill (inference/serving.py): when > 0, a prompt whose
    # uncached suffix exceeds this many tokens prefills in chunks of at
    # most this size, one chunk per engine step, through the normal
    # per-step admission loop — decode admission never stalls behind a
    # long prompt (the max prefill work in any step is bounded by this
    # budget), and prompts larger than the token budget become
    # servable.  Each chunk attends over the pool-resident prefix K/V
    # (the "chunk" program form).  0 (default): monolithic prefill,
    # byte-identical to r18 (pinned by test).
    "FLAGS_prefill_chunk_tokens": 0,
    # speculative decoding (inference/serving.py + spec_decode.py): when
    # > 0, each decode step drafts up to this many candidate tokens per
    # sequence (n-gram prompt-lookup proposer by default, no draft
    # model), scores all K+1 positions in ONE chunk-form verify program
    # call against the pool-resident K/V, accepts the longest agreeing
    # prefix (greedy: exact-argmax match, so greedy spec-decode is
    # token-identical to the monolithic baseline) and truncates the
    # rejected K/V appends in place.  The verify charges accepted+1
    # tokens against the token budget exactly like the monolithic path
    # (zero-accept degrades to baseline step count and accounting).
    # 0 (default): the r20 decode loop runs byte-identically (pinned
    # by test).
    "FLAGS_spec_decode_k": 0,
    # quantized KV page pool (inference/kv_cache.py + ops/paged_ops.py):
    # the serving engine stores K/V pages in this dtype — "bfloat16"
    # halves pool bytes, "int8" quarters them and carries a
    # per-(kv_head, page) absmax scale in a parallel f32 scale pool
    # (~1.6% overhead at page_size=16/head_dim=32).  Every attention
    # read (paged decode kernel + jnp fallback, chunk and spec-verify
    # gathers) dequantizes inline and accumulates in f32; writes
    # quantize in-program (int8: monotone per-page scale with touched-
    # page requant, so append order never rescales untouched pages
    # destructively).  CoW forks copy pages+scales verbatim, truncate
    # leaves surviving scales alone, and the prefix digest is a
    # function of token ids only, so prefix hits stay dtype-
    # independent.  The engine derives num_pages from a fixed byte
    # budget, so the dtype buys 2x/4x pool CAPACITY at the same HBM,
    # not just cheaper bytes.  "float32" (default): byte-identical to
    # the unquantized engine — no scale pool, no extra program vars
    # (pinned by test).
    "FLAGS_kv_cache_dtype": "float32",
    # tensor-parallel decode (inference/serving.py + parallel/
    # tensor_parallel.py): shard the serving decoder over an "mp" mesh
    # axis of this many devices — each device holds 1/tp of the
    # attention heads, MLP width and embedding columns (Megatron
    # placements derived from partition rules), with the two per-block
    # c_allreduce_sum combines inserted by the serving_tp_pass.  The
    # paged KV pool shards on its kv_heads dim, so a fixed PER-DEVICE
    # kv_budget_mb buys tp x more pages (the capacity headline).
    # Greedy decode is token-identical to tp=1 on seeded traces
    # (pinned).  1 (default): single-device engine, byte-identical to
    # the pre-TP serving paths — no mesh, no collectives (pinned by
    # test).
    "FLAGS_serving_tp": 1,
    # in-program sampling (ops/sampling_ops.py): when > 0, decode/
    # prefill/chunk/verify programs end in the sample_token op
    # (temperature + engine-level top-k/top-p) under per-slot RNG lane
    # feeds rng_lane(seed, req_id, position) — seeded traces replay
    # bit-identically and lanes are resume-invariant under preemption
    # (recomputed from position, never carried).  0.0 (default): the
    # programs end in arg_max exactly as before — byte-identical
    # (pinned by test).
    "FLAGS_sample_temperature": 0.0,
    # modeled-HBM budget gate (framework/memory_plan.py): when > 0, the
    # executor / DP compile paths check the static liveness planner's
    # modeled peak against this many MB and WARN naming the peak op and
    # the top live vars; FLAGS_hbm_budget_strict upgrades the warning to
    # MemoryBudgetError.  0 (default) skips the check entirely — the
    # planner still runs (it is pure analysis) but nothing gates on it,
    # and training is bit-identical either way (pinned by test).
    "FLAGS_hbm_budget_mb": 0.0,
    "FLAGS_hbm_budget_strict": False,
    # plan-driven memory relief (framework/ir.py memory_relief_pass):
    # when the modeled peak exceeds FLAGS_hbm_budget_mb, the compile
    # paths rewrite the program to fit — per over-budget activation the
    # pass prices (a) "remat" (replay the producing op before its
    # backward consumer: bit-identical, costs modeled recompute time),
    # (b) "offload" (paired memcpy_d2h/memcpy_h2d staged under the
    # double-buffering window: costs modeled host-link time), and on
    # the DP path (c) a plan escalation (raised ZeRO stage / shrunk
    # prefetch window), picking the cheapest by modeled
    # time-per-byte-saved and re-running plan_memory() after each fix.
    # "remat"/"offload" restrict the menu to that fix; "auto" allows
    # all three.  "off" (default): the pass never runs and the whole
    # pipeline is byte-identical to a relief-less build (pinned by
    # test).
    "FLAGS_memory_relief": "off",
    # numerics observability (framework/numerics.py + framework/ir.py
    # numerics_probe_pass): when on, every compile appends cheap
    # in-program stat reductions (absmax/mean/rms/nonfinite-count) over
    # grad/param/update-role vars — one extra fetched vector per step —
    # feeding the numerics_* telemetry gauges, the HealthMonitor
    # (numerics.health()) and the stats ring the NaN/Inf flight
    # recorder dumps.  0 (default) is bit-identical to the unprobed
    # pipeline: no pass, no extra fetch, no instrument (pinned by
    # test).
    "FLAGS_numerics_probe": False,
    # regex over op TYPES widening the probe beyond role-selected vars:
    # every output of a matching op is probed too (the bisector's
    # per-op stream; e.g. ".*" probes everything on a tiny program)
    "FLAGS_numerics_probe_ops": "",
    # last-K-steps per-var stats ring buffer depth (the flight
    # recorder's post-mortem window)
    "FLAGS_numerics_ring_steps": 8,
    # HealthMonitor loss-spike detector: a finite loss more than
    # spike_factor x the rolling window mean (after 8 warmup steps)
    # trips the monitor
    "FLAGS_numerics_spike_window": 32,
    "FLAGS_numerics_spike_factor": 4.0,
    # NaN/Inf flight recorder (framework/numerics.py record_nan_debris,
    # symmetric to FLAGS_oom_debris_dir): when set, an armed
    # FLAGS_check_nan_inf failure or a HealthMonitor trip dumps the
    # failing op, the stats ring, loss history, telemetry snapshot and
    # chrome trace into a fresh subdirectory here; exceptions propagate
    # unchanged either way.  Empty (default) disables the dump.
    "FLAGS_numerics_debris_dir": "",
    # OOM flight recorder (framework/memory_plan.py record_oom_debris):
    # when set, a RESOURCE_EXHAUSTED caught in the executor step/compile
    # paths dumps the memory plan + telemetry snapshot + profiler trace
    # + measured memory stats into a fresh subdirectory here before
    # re-raising, so a chip OOM is diagnosable post-mortem.  Empty
    # (default) disables the dump; the exception propagates unchanged
    # either way.
    "FLAGS_oom_debris_dir": "",
    # static program verifier gate (framework/verifier.py): snapshot
    # before every IR pass, verify dataflow/registry/layout invariants
    # after, raise a diagnostic naming the pass + op + hazard on
    # violation.  On by default under pytest (a structural gate every
    # pass test inherits); off in production — verification never
    # mutates the program, so 0 restores prior behavior bit-for-bit.
    "FLAGS_verify_passes": "pytest" in sys.modules,
    # static SPMD shard-safety analysis (framework/shard_analysis.py +
    # the shard_safety_pass compile gate): abstract-interpret each
    # compiled program's per-var distribution state (replicated /
    # sharded / shard-variant) and check replication soundness,
    # collectives under divergent control flow, and comm/compute
    # hazards.  Analysis only — ON by default as warnings, and programs
    # without collectives short-circuit, so defaults are bit-identical.
    "FLAGS_shard_safety": True,
    # escalate shard-safety ERROR findings from warnings to a raised
    # VerifyError at compile time (CI / pre-deploy linting posture)
    "FLAGS_shard_safety_strict": False,
}


def _auto_on_tpu(name: str, place) -> bool:
    """A tri-state lever flag against the executor place: "auto" means
    on for a TPU place only; truthy forces on, falsy off."""
    v = flag(name)
    if isinstance(v, str):
        s = v.strip().lower()
        if s == "auto":
            return (place is not None
                    and place.jax_device().platform == "tpu")
        return s in ("1", "true", "yes", "on")
    return bool(v)


def nhwc_enabled(place=None) -> bool:
    """FLAGS_tpu_nhwc resolved against the executor place."""
    return _auto_on_tpu("tpu_nhwc", place)


def tpu_fuse_enabled(place=None) -> bool:
    """FLAGS_tpu_fuse resolved against the executor place — the same
    contract as :func:`nhwc_enabled` so the two fusion levers A/B the
    same way."""
    return _auto_on_tpu("tpu_fuse", place)


def _coerce(cur, val):
    if isinstance(cur, bool):
        return str(val).lower() in ("1", "true", "yes", "on")
    if isinstance(cur, int):
        return int(val)
    if isinstance(cur, float):
        # sentinel string modes ride float-typed flags (e.g.
        # FLAGS_fuse_grad_size_in_MB="auto" selects bucket autotune)
        if isinstance(val, str) and val.strip().lower() == "auto":
            return "auto"
        return float(val)
    return val


def dp_plan_auto() -> bool:
    """True when FLAGS_dp_plan selects the searched auto-parallel plan
    (parallel/plan_search.py) instead of the hand-set flags."""
    v = flag("dp_plan", "")
    return isinstance(v, str) and v.strip().lower() == "auto"


def fuse_grad_mb_auto() -> bool:
    """True when FLAGS_fuse_grad_size_in_MB selects the measurement-
    driven variable-bucket mode."""
    v = flag("fuse_grad_size_in_MB")
    return isinstance(v, str) and v.strip().lower() == "auto"


def fuse_grad_mb_value(default: float = 32.0) -> float:
    """Numeric bucket cap: the flag's value, or `default` in auto mode
    (auto caps nothing — the cost model picks the boundaries)."""
    v = flag("fuse_grad_size_in_MB")
    if isinstance(v, str):
        try:
            return float(v)  # numeric string set through a raw layer
        except ValueError:
            return default  # "auto" (or garbage): cost model decides
    return float(v or 0)


_flags: Dict[str, Any] = {}
for k, v in _DEFAULTS.items():
    env = os.environ.get(k)
    _flags[k] = _coerce(v, env) if env is not None else v

#: frozen process-start values (defaults + FLAGS_* env overrides): the
#: restore point for config layers that reset a flag to "unconfigured"
#: (e.g. fleet DistributedStrategy knobs left at None) — restoring raw
#: _DEFAULTS would silently discard the operator's environment settings
_INITIAL: Dict[str, Any] = dict(_flags)


def set_flags(d: Dict[str, Any]):
    for k, v in d.items():
        if not k.startswith("FLAGS_"):
            k = "FLAGS_" + k
        # coerce against the flag's declared (default) type, not the
        # current runtime value: a sentinel string riding a float flag
        # ("auto" on FLAGS_fuse_grad_size_in_MB) must not stop a later
        # numeric set from coercing back to float
        cur = _DEFAULTS.get(k, _flags.get(k))
        _flags[k] = _coerce(cur, v) if cur is not None else v


def get_flags(keys):
    if isinstance(keys, str):
        keys = [keys]
    out = {}
    for k in keys:
        kk = k if k.startswith("FLAGS_") else "FLAGS_" + k
        out[k] = _flags.get(kk)
    return out


def flag(name, default=None):
    kk = name if name.startswith("FLAGS_") else "FLAGS_" + name
    return _flags.get(kk, default)
