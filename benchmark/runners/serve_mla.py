"""Runner ``serve_mla``: a latent-attention / sparse-expert decoder
(``paddle_tpu.inference.mla_decoder``) served by ``ServingEngine`` on one
chip, driven open-loop by the plan of the traffic file's generator.

As ``serve_decoder`` (whose helpers it uses): weights made on the device from
``--seed`` in the type the configuration states and handed to the engine's
constructor; warm-up of every program shape the plan can reach, with
all-padding feeds that write nothing into the pool and route no token; the
replay; then the comparison that decides ``correct``.

**The comparison.**  The timed programs return, beside each emitted token,
that token's logit and its row's log-sum-exp (two floats a row) and the
experts the row was routed to; the engine keeps them by request.  After the
replay a seeded sample of completed requests is teacher-forced through the
plain reference at the published widths, in one padded shape, and the
engine's two floats a served token are compared with the reference's.  Top-k
routing is discontinuous, so the reference is routed as the engine was FOR
THE SERVED ROWS, after holding that choice to its own scores (``slack``: each
chosen expert's score within a tolerance of the reference's k-th; a wrong
router fails here); see the reference's docstring.  Types are held as types.

For the per-layer readers the record carries, per traced step: the contexts
of the running sequences (``decode_ctx``), the tokens each expert of each
expert layer received in each program call (``moe_calls``), and the device
events of the trace classed by the part of the block they belong to
(``device_parts``, read from the profile before the harness reduces it).
"""
from __future__ import annotations

import sys
import time

import numpy as np

from benchmark.lib import device as device_lib
from benchmark.lib import loadgen
from benchmark.lib import scopes
from benchmark.lib.harness import longest, say
from benchmark.lib.stats import samples_beyond
from benchmark.lib.watch import require_kernels
from benchmark.runners.serve_decoder import _buckets, plan


def model_config(size: dict):
    """The program's description of the configuration file's model."""
    try:
        from paddle_tpu.inference.mla_decoder import MLADecoderConfig
    except ImportError as e:
        sys.exit(f"benchmark: this program has no MLA decoder ({e}); the "
                 f"cell cannot run on it")
    return MLADecoderConfig.from_source(
        size, max_seq_len=size["deployment"]["max_context"],
        weights_dtype=size["weights_dtype"])


def make_weights(jax, specs: dict, seed: int, device, dtype: str):
    """Each weight in a jitted call of its own on ``device`` (one call for
    all would hold the float32 draws of 11 GB of parameters at once): norm
    scales one, the router's correction bias normal x 0.01, the embedding
    normal, every matrix normal over sqrt(fan-in): logits of unit scale."""
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype)
    root = jax.random.PRNGKey(seed % (2 ** 31))

    def draw(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32) * scale) \
            .astype(dtype)

    draw = jax.jit(draw, static_argnums=(1, 2))
    out = {}
    with jax.default_device(device):
        for i, name in enumerate(sorted(specs)):
            shape = tuple(specs[name])
            if name.endswith("_scale"):
                out[name] = jnp.ones(shape, dtype)
                continue
            scale = 0.01 if name.endswith("router_bias") else \
                1.0 if name == "dec_embed" else float(shape[-2]) ** -0.5
            out[name] = draw(jax.random.fold_in(root, i), shape, scale)
    return out


def warm_up(eng, planned, env):
    """Run each program shape the plan can reach once, through the engine's
    own call (so that what rides on a call is compiled with it): prefill
    buckets from the shortest prompt to the longest, decode batch buckets up
    to ``max_batch`` by block-table widths up to the longest context."""
    core, cfg, kvc = eng.core, eng.cfg, eng.core.kv_config
    pad, page = kvc.pad_slot, kvc.page_size
    lens = [len(p.prompt) for p in planned]
    ends = [len(p.prompt) + p.want for p in planned]
    widths = _buckets(-(-(min(lens) + 1) // page), -(-max(ends) // page))
    batches = _buckets(1, eng.max_batch)
    prefills = _buckets(max(core.prefill_bucket_min, min(lens)), max(lens))

    def prefill_feed(s):
        return {"tokens": np.zeros((1, s), np.int32),
                "positions": np.minimum(np.arange(s, dtype=np.int32),
                                        cfg.max_seq_len - 1)[None],
                "slot_mapping": np.full(s, pad, np.int32),
                "last_index": np.zeros(1, np.int32)}

    def decode_feed(b, w):
        return {"tokens": np.zeros(b, np.int32),
                "positions": np.zeros(b, np.int32),
                "block_tables": np.zeros((b, w), np.int32),
                "context_lens": np.ones(b, np.int32),
                "slot_mapping": np.full(b, pad, np.int32)}

    # the first shape of each form runs twice: a program's first call of all
    # leaves its RNG state in the scope in another type, which compiles that
    # shape again.  Each call's tokens are read: a pipelined engine's calls
    # return before the device has run them
    for s in prefills + prefills[:1]:
        with env.span("first_call"):
            np.asarray(core._run(core.prefill_prog, prefill_feed(s),
                                 core.prefill_fetch, "warm")[0])
    combos = [(b, w) for b in batches for w in widths]
    for b, w in combos[:1] + combos:
        with env.span("first_call"):
            np.asarray(core._run(core.decode_prog, decode_feed(b, w),
                                 core.decode_fetch, "warm")[0])
    return {"prefill": prefills, "decode_batch": batches,
            "decode_width": widths}


def build(cell, env):
    import paddle_tpu as pt
    from paddle_tpu.inference.serving import ServingEngine

    size, deploy = cell.config, cell.config["deployment"]
    cfg = model_config(size)
    with env.span("weights"):
        weights = make_weights(env.jax, cfg.param_specs(), cell.seed,
                               env.devices[0], size["weights_dtype"])
    with env.span("build"):
        eng = ServingEngine(
            cfg=cfg, weights=weights, kv_dtype=size["kv_dtype"],
            place=pt.CPUPlace() if cell.rehearsal else pt.TPUPlace(0),
            num_pages=deploy["num_pages"], page_size=deploy["page_size"],
            max_batch=deploy["max_batch"], token_budget=deploy["token_budget"],
            pipeline=deploy["pipeline"])
    eng.core.keep_scores = True
    return eng, cfg, weights


def reference_config(size: dict) -> dict:
    """The configuration file's own numbers, as the reference reads them."""
    return {k: v for k, v in size.items()
            if isinstance(v, (bool, int, float))}


def compare(cell, env, eng, weights, reference, done) -> dict:
    """The engine's two floats a served token against the reference's, over
    a seeded sample of completed requests."""
    check = cell.config["check"]
    rng = np.random.RandomState(cell.seed % (2 ** 32))
    sample = [done[i] for i in rng.permutation(len(done))[:check["sample"]]]
    pad_to = max((len(p.prompt) + p.want for p in done), default=0)
    cfg = reference_config(cell.config)
    errs, slack, margin, finite = [], 0.0, [], True
    with env.span("reference"):
        for p in sample:
            got, routes = eng.core.served_scores(p.req_id)
            ref = reference.served_token_scores(
                weights, cfg, p.prompt, p.handle.out_tokens, routes, pad_to)
            errs.append(np.stack([got[:, 0] - ref["logit"],
                                  got[:, 1] - ref["lse"]]))
            slack = max(slack, float(ref["slack"].max()))
            margin.append(ref["margin"].min(axis=1))
            finite = finite and ref["finite"] and bool(np.isfinite(got).all())
    errs = np.concatenate(errs, axis=1) if errs else np.zeros((2, 0))
    margin = np.concatenate(margin) if margin else np.zeros(0)
    out = {
        "checked": len(sample), "served_tokens_checked": int(errs.shape[1]),
        "logit_abs_err": float(np.abs(errs).max()) if errs.size else None,
        "logit_rms_err": float(np.sqrt(np.mean(errs ** 2)))
        if errs.size else None,
        "route_slack": slack, "finite": finite,
        # how often the reference alone would have been within a tolerance
        # of picking other experts than it did: why the served rows are
        # routed as the engine was
        "rows_with_margin_under_slack_tol": float(np.mean(
            margin < check["route_slack_tol"])) if margin.size else None,
        "limits": {k: check[k] for k in ("logit_abs_tol", "logit_rms_tol",
                                         "route_slack_tol")},
    }
    out["within"] = bool(
        sample and finite and out["logit_abs_err"] <= check["logit_abs_tol"]
        and out["logit_rms_err"] <= check["logit_rms_tol"]
        and slack <= check["route_slack_tol"])
    return out


def run(cell, env, reference) -> dict:
    from paddle_tpu.inference.serving import Request

    eng, cfg, weights = build(cell, env)
    traffic = cell.traffic
    planned = plan(cell, cfg)
    shapes = warm_up(eng, planned, env)
    found = require_kernels(env.watch, cell.config["kernels"],
                            env.interpreted)
    warm = env.watch.mark()
    setup_counters = env.watch.since()
    say(warmed=shapes, planned=len(planned),
        due_in_window=sum(p.measured(cell.seconds) for p in planned),
        memory_after_warm_up=device_lib.memory_stats(env.devices),
        memory_limit_bytes=(env.devices[0].memory_stats() or {})
        .get("bytes_limit"))

    core = eng.core
    core.moe_stats                # folds the warm-up's calls away
    core.moe_calls = []           # (phase, counts) of every program call

    def calls_seen():
        return len(core.moe_calls) + len(core._moe_pending)
    marks, decode_ctx, traced = {}, [], {}
    t_replay = time.perf_counter()
    lead = -min(planned[0].due, 0.0)

    def snapshot(engine):
        return {"stats": dict(engine.stats), "calls": calls_seen()}

    def between_steps(t, engine):
        if "open" not in marks and t >= 0.0:
            marks["open"] = snapshot(engine)
        if "close" not in marks and t >= cell.seconds:
            marks["close"] = snapshot(engine)
        if env.tracer.active:
            traced.setdefault("calls_from", calls_seen())
            traced["calls_to"] = calls_seen()
            if engine.running:
                decode_ctx.append([engine.kv.context_len(st.req.req_id)
                                   for st in engine.running])
        if t < cell.seconds:
            env.tracer.poll(t)
        else:
            env.tracer.stop(t)

    raw = loadgen.replay(
        eng, planned, cell.seconds, float(traffic.get("drain_s", 0.0)),
        lambda p, due: Request(p.req_id, list(p.prompt), p.want, due),
        span=env.span, between_steps=between_steps)
    env.tracer.stop(raw["ended_at"])
    setup_s = (t_replay - env.t_start) + lead
    in_window = env.watch.since(warm)
    zero = {"stats": dict.fromkeys(eng.stats, 0), "calls": 0}
    marks.setdefault("open", zero)
    marks.setdefault("close", snapshot(eng))
    rows = loadgen.request_table(raw, lambda p: p.handle.admitted_at)
    memory = device_lib.memory_peak_bytes(env.devices)
    memory_stats = device_lib.memory_stats(env.devices)
    kv = eng.kv.stats()
    device_parts = scopes.of_trace(env.tracer.dir) if cell.trace else None

    # ---- correctness --------------------------------------------------------
    done = [p for p in raw["requests"] if p.finished is not None]
    short = [p.req_id for p in done if len(p.handle.out_tokens) != p.want]
    types = {"kv": kv["dtype"],
             "weights": sorted({str(w.dtype) for w in weights.values()})}
    as_stated = types == {"kv": cell.config["kv_dtype"],
                          "weights": [cell.config["weights_dtype"]]}
    # the pools have served: their room is the reference's
    for name in cfg.cache_pool_names():
        core.scope.erase([name])
    verdict = compare(cell, env, eng, weights, reference, done)
    correct = verdict["within"] and not short and as_stated

    carried = [p for p in raw["requests"] if p.due < 0.0
               and (p.finished is None or p.finished >= 0.0)]
    attempted = len(rows) + len(carried)
    failed = sum(r["failed"] for r in rows) \
        + sum(p.refused is not None for p in carried)
    moe_stats = core.moe_stats    # folds what is pending into moe_calls
    calls = core.moe_calls[traced.get("calls_from", 0):
                           traced.get("calls_to", 0)]
    say(window="serve", due=len(rows),
        samples_beyond={q: samples_beyond(len(rows), q) for q in (90, 95)},
        carried_into_window=len(carried), failed=failed,
        ended_at=raw["ended_at"], **loadgen.window_note(raw),
        queue_half=raw["queue_half"], queue_end=raw["queue_end"],
        engine_steps=len(raw["steps"]), steps=longest(raw["steps"]),
        gc=env.gc_watch.since(t_replay), scheduler=eng.stats,
        moe=moe_stats, kv=kv, kernel_calls=found, check=verdict,
        types=types, types_as_stated=as_stated, wrong_token_count=short,
        memory_peak_bytes=memory, memory_stats=memory_stats,
        traced_decode_steps=len(decode_ctx), traced_moe_calls=len(calls),
        device_parts=device_parts,
        **{f"window_{k}": v for k, v in in_window.items()})
    size = cell.config
    return {
        "setup_s": setup_s, "window_s": raw["closed_at"], "rows": rows,
        "raw": raw, "stats_open": marks["open"]["stats"],
        "stats_close": marks["close"]["stats"],
        "moe_open": core.expert_sums(core.moe_calls[:marks["open"]["calls"]]),
        "moe_close": core.expert_sums(
            core.moe_calls[:marks["close"]["calls"]]),
        "decode_ctx": decode_ctx, "moe_calls": calls,
        "device_parts": device_parts, "kv": kv,
        "model": {
            "layers": cfg.num_layers,
            "expert_layers": cfg.num_layers - cfg.first_k_dense,
            "heads": cfg.num_heads, "latent_values": cfg.latent_width,
            "kv_lora_rank": cfg.kv_lora_rank,
            "hidden": cfg.hidden, "expert_width": cfg.moe_intermediate,
            "item_bytes": np.dtype(eng.core.scope.get("dec_head").dtype)
            .itemsize,
            "cache_item_bytes": 2 if size["kv_dtype"] == "bfloat16" else 4,
        },
        "attempted": attempted, "failed": failed, "correct": correct,
        "compiles_in_window": in_window["compilations"],
        "memory_peak_bytes": memory, "setup_counters": setup_counters,
    }
