"""Runner ``serve_decoder``: a decoder LM served by ``ServingEngine`` on one
chip, driven open-loop by the plan of the traffic file's generator.

Weights are made on the device from ``--seed`` in one jitted call, in the type
the configuration states, and handed to the engine's constructor (no export to
disk).  Warm-up runs every program shape the cell's traffic can reach (decode
batch and block-table buckets, prefill buckets) with all-padding feeds, which
write nothing into the pools.  After the drain a seeded sample of completed
requests is teacher-forced through the plain reference.
"""
from __future__ import annotations

import importlib
import time

import numpy as np

from benchmark.lib import device as device_lib
from benchmark.lib import loadgen
from benchmark.lib.harness import longest, say
from benchmark.lib.stats import samples_beyond
from benchmark.lib.watch import require_kernels


def make_weights(jax, specs: dict, seed: int, device, dtype: str):
    """Every weight in one jitted call on ``device``: layer-norm scales one,
    biases zero, the rest normal over sqrt(fan-in) — the program's own
    initial distribution, so logits have unit scale."""
    import jax.numpy as jnp

    names, dtype = sorted(specs), jnp.dtype(dtype)

    def init(key):
        out = {}
        for i, name in enumerate(names):
            shape = specs[name]
            if name.endswith("_scale"):
                out[name] = jnp.ones(shape, dtype)
            elif name.endswith("_bias"):
                out[name] = jnp.zeros(shape, dtype)
            else:
                out[name] = jax.random.normal(
                    jax.random.fold_in(key, i), shape, dtype) \
                    / np.sqrt(shape[-1])
        return out

    with jax.default_device(device):
        return jax.jit(init)(jax.random.PRNGKey(seed % (2 ** 31)))


def _buckets(lo: int, hi: int):
    out, b = [], 1
    while b < lo:
        b *= 2
    while True:
        out.append(b)
        if b >= hi:
            return out
        b *= 2


def warm_up(eng, planned, env):
    """Run each program shape the plan can reach once.  Shapes follow the
    engine's own bucketing (powers of two): prefill length from 16, decode
    batch up to ``max_batch``, block-table width up to the pages of the
    longest context.  Returns the shapes warmed."""
    core, cfg, kvc = eng.core, eng.cfg, eng.core.kv_config
    pad = kvc.pad_slot
    lens = [len(p.prompt) for p in planned]
    ends = [len(p.prompt) + p.want for p in planned]
    page = kvc.page_size
    widths = _buckets(-(-(min(lens) + 1) // page), -(-max(ends) // page))
    batches = _buckets(1, eng.max_batch)
    prefills = _buckets(max(core.prefill_bucket_min, min(lens)), max(lens))
    from paddle_tpu.inference.serving import _causal_mask

    # the first shape runs twice: the program's first call of all leaves its
    # RNG state in the scope in another type, which compiles that shape again
    for s in prefills + prefills[:1]:
        feed = {"tokens": np.zeros((1, s), np.int32),
                "positions": np.minimum(np.arange(s, dtype=np.int32),
                                        cfg.max_seq_len - 1)[None],
                "attn_mask": _causal_mask(s),
                "slot_mapping": np.full(s, pad, np.int32),
                "last_index": np.zeros(1, np.int32)}
        with env.span("first_call"):
            core.exe.run(core.prefill_prog, feed=feed,
                         fetch_list=core.prefill_fetch, scope=core.scope)
    def decode_feed(b, w):
        return {"tokens": np.zeros(b, np.int32),
                "positions": np.zeros(b, np.int32),
                "block_tables": np.zeros((b, w), np.int32),
                "context_lens": np.ones(b, np.int32),
                "slot_mapping": np.full(b, pad, np.int32)}

    combos = [(b, w) for b in batches for w in widths]
    for b, w in combos[:1] + combos:
        with env.span("first_call"):
            core.exe.run(core.decode_prog, feed=decode_feed(b, w),
                         fetch_list=core.decode_fetch, scope=core.scope)
    return {"prefill": prefills, "decode_batch": batches,
            "decode_width": widths}


def build(cell, env):
    """The engine on the cell's device, in the types the configuration
    states, with weights from the seed."""
    import paddle_tpu as pt
    from paddle_tpu.inference.serving import (DecoderConfig, ServingEngine,
                                              decoder_param_specs)

    size, deploy = cell.config, cell.config["deployment"]
    cfg = DecoderConfig(
        vocab_size=size["vocab_size"], hidden=size["n_embd"],
        num_heads=size["n_head"], num_layers=size["n_layer"],
        max_seq_len=size["n_positions"])
    with env.span("weights"):
        weights = make_weights(env.jax, decoder_param_specs(cfg), cell.seed,
                               env.devices[0], size["weights_dtype"])
    with env.span("build"):
        eng = ServingEngine(
            cfg=cfg, weights=weights, kv_dtype=size["kv_dtype"],
            place=pt.CPUPlace() if cell.rehearsal else pt.TPUPlace(0),
            num_pages=deploy["num_pages"], page_size=deploy["page_size"],
            max_batch=deploy["max_batch"], token_budget=deploy["token_budget"])
    return eng, cfg, weights


def run(cell, env, reference) -> dict:
    from paddle_tpu.inference.serving import Request

    eng, cfg, weights = build(cell, env)
    traffic = cell.traffic
    # the plan first: warm-up runs the shapes this plan reaches, no others
    planned = plan(cell, cfg)
    shapes = warm_up(eng, planned, env)
    found = require_kernels(env.watch, cell.config["kernels"],
                            env.interpreted)
    warm = env.watch.mark()
    setup_counters = env.watch.since()
    say(warmed=shapes, planned=len(planned),
        due_in_window=sum(p.measured(cell.seconds) for p in planned))

    # ---- lead-in, the measured window, the drain --------------------------
    marks = {}
    decode_ctx = []          # per traced decode step: its context lengths
    t_replay = time.perf_counter()
    lead = -min(planned[0].due, 0.0)

    def between_steps(t, engine):
        if "open" not in marks and t >= 0.0:
            marks["open"] = dict(engine.stats)
        if "close" not in marks and t >= cell.seconds:
            marks["close"] = dict(engine.stats)
        if env.tracer.active and engine.running:
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
    marks.setdefault("open", dict.fromkeys(eng.stats, 0))
    marks.setdefault("close", dict(eng.stats))
    rows = loadgen.request_table(raw, lambda p: p.handle.admitted_at)

    # ---- correctness --------------------------------------------------------
    done = [p for p in raw["requests"] if p.finished is not None]
    short = [p.req_id for p in done if len(p.handle.out_tokens) != p.want]
    rng = np.random.RandomState(cell.seed % (2 ** 32))
    sample = [done[i] for i in rng.permutation(len(done))
              [:cell.config["check"]["sample"]]]
    tol = cell.config["check"]["logit_tie_tol"]
    worst, finite, served, agreed = 0.0, True, 0, 0
    with env.span("reference"):
        for p in sample:
            gaps, ok = reference.served_token_gaps(
                weights, p.prompt, p.handle.out_tokens, cfg.num_layers,
                cfg.num_heads, cfg.max_seq_len)
            worst = max(worst, float(gaps.max()))
            served += gaps.size
            agreed += int((gaps == 0).sum())
            finite = finite and ok
    # precision is held by type, not by tolerance: the near-tie rule cannot
    # tell float32 from bfloat16 (see the configuration's ``check``)
    kv = eng.kv.stats()
    types = {"kv": kv["dtype"],
             "weights": sorted({str(w.dtype) for w in weights.values()})}
    as_stated = types == {"kv": cell.config["kv_dtype"],
                          "weights": [cell.config["weights_dtype"]]}
    correct = bool(sample) and finite and worst <= tol and not short \
        and as_stated
    memory = device_lib.memory_peak_bytes(env.devices)
    # the window's work: what was due in it, and what it inherited unfinished
    carried = [p for p in raw["requests"] if p.due < 0.0
               and (p.finished is None or p.finished >= 0.0)]
    attempted = len(rows) + len(carried)
    failed = sum(r["failed"] for r in rows) \
        + sum(p.refused is not None for p in carried)
    say(window="serve", due=len(rows),
        samples_beyond={q: samples_beyond(len(rows), q) for q in (90, 95)},
        completed_of_due=sum(1 for r in rows if r["finished"] is not None
                             and r["finished"] <= raw["closed_at"]),
        latency=loadgen.latency_note(raw, rows),
        carried_into_window=len(carried), failed=failed,
        cut_by_drain=sum(1 for r in rows
                         if not r["failed"] and r["finished"] is None),
        ended_at=raw["ended_at"], **loadgen.window_note(raw),
        queue_half=raw["queue_half"], queue_end=raw["queue_end"],
        engine_steps=len(raw["steps"]), steps=longest(raw["steps"]),
        gc=env.gc_watch.since(t_replay), scheduler=eng.stats,
        kv=kv, kernel_calls=found, checked=len(sample),
        worst_logit_gap=worst, logit_tie_tol=tol, served_tokens_checked=served,
        reference_argmax_share=agreed / served if served else None,
        types=types, types_as_stated=as_stated,
        wrong_token_count=short, memory_peak_bytes=memory,
        memory_stats=device_lib.memory_stats(env.devices),
        **{f"window_{k}": v for k, v in in_window.items()})
    return {
        "setup_s": setup_s, "window_s": raw["closed_at"], "rows": rows,
        "raw": raw, "stats_open": marks["open"], "stats_close": marks["close"],
        "decode_ctx": decode_ctx, "kv": kv,
        "kv_bytes_per_token_per_layer": 2 * cfg.num_heads * cfg.head_dim
        * np.dtype(eng.kv_dtype).itemsize,
        "num_layers": cfg.num_layers,
        "attempted": attempted, "failed": failed,
        "correct": correct, "compiles_in_window": in_window["compilations"],
        "memory_peak_bytes": memory,
        "setup_counters": setup_counters,
    }


def plan(cell, cfg, traffic=None):
    """The cell's requests, from the generator its traffic file names."""
    traffic = traffic or cell.traffic
    generator = importlib.import_module(
        f"benchmark.generators.{traffic['generator']}")
    return generator.plan(traffic, cell.seed, cell.seconds, cfg.vocab_size,
                          cfg.max_seq_len)
