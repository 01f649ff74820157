"""Runner ``serve_gdn``: a decoder of Gated DeltaNet layers (a float32 state
slot a sequence: ``paddle_tpu.inference.gqa_decoder`` with ``linear`` layers)
beside full multi-head layers over paged K/V pools, dense throughout, served
by ``ServingEngine`` on one chip, driven open-loop by the plan of the traffic
file's generator.

As ``serve_hybrid``, whose warm-up (the two models' serving forms take the
same feeds: block tables and ``state_slots``), ``Reference`` and weights it
uses, with ``serve_mla``'s comparison and ``serve_decoder``'s plan: weights
made on the device from ``--seed`` in the type the configuration states (the
linear layers' under the names ``serve_hybrid.make_weights`` seeds: ``A_log``,
``dt_bias`` and the convolution's taps as Kimi-Linear's are); warm-up of every
program shape the plan can reach with all-padding feeds, which write no K/V
row and of the state pools the padding's slot alone; the replay; then the
comparison that decides ``correct`` (``serve_mla.compare``: the timed
programs' logit and log-sum-exp of every served token of a seeded sample
against the reference, teacher-forced, the delta rule one token at a time).
The model routes nothing, so nothing of the comparison follows the engine.
Types are held as types: weights and K/V pools bfloat16, state pools float32.

A program without such a decoder cannot run the cell: the runner says so and
exits before it builds anything.

Two things keep a run the same run (PERF.md 7.13a: the plan is one schedule
and the model routes nothing, so two runs' steps are the same work and a step
that ends later is the host's doing).  The weights are awaited before the
engine is built, so the pools' place on the device is not a race between the
weights' call handing back its temporaries and the pools' allocation; and the
interpreter's collector is told, as a serving process tells it after warm-up,
that what set-up built stays (``gc.freeze``): otherwise one full collection a
run stops the host for a quarter of a second wherever it falls.  The run's
log carries every step's end (``step_ends_ms``), by which two runs are
compared step for step.

For the per-layer readers the record carries ``decode_ctx``, ``kv`` (with its
``state_slots``), ``device_parts``, ``model`` (the sizes ``rooflines/gqa_*``
and ``rooflines/gdn_*`` read) and, from the engine's own count over the
traced steps (``eng.stats["kernels"]``), ``gqa_traced`` and ``gdn_traced``.

The builder's control, ``python3 -m benchmark.runners.serve_gdn`` with
``benchmark/run.py``'s arguments: the same run, with the comparison made a
second time against the reference in the nearest precision below (``LOWER``:
weights and K/V rows through float8_e4m3fn, the state through bfloat16 after
every token), which the limits must refuse; the run's log line then carries
``check_lower`` beside ``check``.
"""
from __future__ import annotations

import gc
import sys
import time

import numpy as np

from benchmark.lib import device as device_lib
from benchmark.lib import loadgen
from benchmark.lib import scopes
from benchmark.lib.harness import longest, say
from benchmark.lib.stats import samples_beyond
from benchmark.lib.watch import require_kernels
from benchmark.runners.serve_decoder import plan
from benchmark.runners.serve_hybrid import Reference, make_weights, warm_up
from benchmark.runners.serve_mla import compare

LOWER = "float8_e4m3fn"       # the reference's next precision down
lower_control = False         # set by ``main``: compare against it too


def model_config(size: dict):
    """The program's description of the configuration file's model."""
    try:
        from paddle_tpu.inference.gqa_decoder import GQADecoderConfig
        if not hasattr(GQADecoderConfig, "state_pool_specs"):
            raise ImportError("GQADecoderConfig describes no linear layer")
    except ImportError as e:
        sys.exit(f"benchmark: this program has no grouped-query decoder "
                 f"with linear (Gated DeltaNet) layers ({e}); the cell "
                 f"cannot run on it")
    return GQADecoderConfig.from_source(
        size, max_seq_len=size["deployment"]["max_context"],
        weights_dtype=size["weights_dtype"])


def seeded_weights(jax, specs: dict, seed: int, device, dtype: str) -> dict:
    """``serve_hybrid.make_weights`` over this model's parameters, a linear
    layer's (``gdn_*``) under the names it seeds a KDA layer's by."""
    seeded_as = {name: name.replace("gdn_", "kda_") for name in specs}
    made = make_weights(jax, {seeded_as[n]: s for n, s in specs.items()},
                        seed, device, dtype)
    return {name: made[seeded_as[name]] for name in specs}


def build(cell, env):
    import paddle_tpu as pt
    from paddle_tpu.inference.serving import ServingEngine

    size, deploy = cell.config, cell.config["deployment"]
    cfg = model_config(size)
    with env.span("weights"):
        weights = seeded_weights(env.jax, cfg.param_specs(), cell.seed,
                                 env.devices[0], size["weights_dtype"])
        # the call's temporaries are back before a pool asks for room: where
        # a pool lies on the device is no race's to decide (PERF.md 7.13a)
        env.jax.block_until_ready(weights)
    with env.span("build"):
        eng = ServingEngine(
            cfg=cfg, weights=weights, kv_dtype=size["kv_dtype"],
            place=pt.CPUPlace() if cell.rehearsal else pt.TPUPlace(0),
            num_pages=deploy["num_pages"], page_size=deploy["page_size"],
            max_batch=deploy["max_batch"], token_budget=deploy["token_budget"],
            pipeline=deploy["pipeline"])
    eng.core.keep_scores = True
    return eng, cfg, weights


def kernel_counts(eng) -> dict:
    """The four kernels' counts so far, prefill and decode in one dict."""
    return {key: value for phase in ("prefill", "decode")
            for key, value in eng.stats["kernels"].get(phase, {}).items()}


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
    marks, decode_ctx, traced = {}, [], {}
    # what set-up built (the programs, their compiled forms, the plan) stays
    # for the life of the process: out of the collector's sight, as a serving
    # process puts it after its warm-up.  Left in, one full collection a run
    # walks it for a quarter of a second wherever it falls (PERF.md 7.13a)
    gc.collect()
    gc.freeze()
    t_replay = time.perf_counter()
    lead = -min(planned[0].due, 0.0)

    def between_steps(t, engine):
        if "open" not in marks and t >= 0.0:
            marks["open"] = dict(engine.stats)
        if "close" not in marks and t >= cell.seconds:
            marks["close"] = dict(engine.stats)
        if env.tracer.active:
            traced.setdefault("from", kernel_counts(engine))
            traced["to"] = kernel_counts(engine)
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
    gc.unfreeze()
    setup_s = (t_replay - env.t_start) + lead
    in_window = env.watch.since(warm)
    marks.setdefault("open", dict.fromkeys(eng.stats, 0))
    marks.setdefault("close", dict(eng.stats))
    rows = loadgen.request_table(raw, lambda p: p.handle.admitted_at)
    memory = device_lib.memory_peak_bytes(env.devices)
    memory_stats = device_lib.memory_stats(env.devices)
    kv = eng.kv.stats()
    device_parts = scopes.of_trace(env.tracer.dir) if cell.trace else None

    # ---- correctness --------------------------------------------------------
    done = [p for p in raw["requests"] if p.finished is not None]
    short = [p.req_id for p in done if len(p.handle.out_tokens) != p.want]
    pools, state_pools = cfg.cache_pool_names(), list(cfg.state_pool_specs(1))
    types = {"kv": kv["dtype"],
             "pools": sorted({str(core.scope.get(n).dtype) for n in pools}),
             "weights": sorted({str(w.dtype) for w in weights.values()}),
             "state": sorted({str(core.scope.get(n).dtype)
                              for n in state_pools})}
    as_stated = types == {"kv": cell.config["kv_dtype"],
                          "pools": [cell.config["kv_dtype"]],
                          "weights": [cell.config["weights_dtype"]],
                          "state": [cell.config["state_dtype"]]}
    # the pools have served: their room is the reference's
    for name in pools + state_pools:
        core.scope.erase([name])
    verdict = compare(cell, env, eng, weights,
                      Reference(reference, cell.config), done)
    correct = verdict["within"] and not short and as_stated
    lower = compare(cell, env, eng, weights,
                    Reference(reference, cell.config, lower=LOWER),
                    done) if lower_control else None

    carried = [p for p in raw["requests"] if p.due < 0.0
               and (p.finished is None or p.finished >= 0.0)]
    attempted = len(rows) + len(carried)
    failed = sum(r["failed"] for r in rows) \
        + sum(p.refused is not None for p in carried)
    counted = {key: value - traced.get("from", {}).get(key, 0)
               for key, value in traced.get("to", {}).items()}
    gqa_traced = {k: v for k, v in counted.items() if k.startswith("gqa_")}
    gdn_traced = {k: v for k, v in counted.items() if k.startswith("gdn_")}
    say(window="serve", due=len(rows),
        samples_beyond={q: samples_beyond(len(rows), q) for q in (90, 95)},
        carried_into_window=len(carried), failed=failed,
        ended_at=raw["ended_at"], **loadgen.window_note(raw),
        queue_half=raw["queue_half"], queue_end=raw["queue_end"],
        engine_steps=len(raw["steps"]), steps=longest(raw["steps"]),
        gc=env.gc_watch.since(t_replay), scheduler=eng.stats,
        kv=kv, kernel_calls=found, check=verdict, check_lower=lower,
        types=types, types_as_stated=as_stated, wrong_token_count=short,
        state_slot_bytes=cfg.state_slot_bytes(),
        memory_peak_bytes=memory, memory_stats=memory_stats,
        traced_decode_steps=len(decode_ctx), gqa_traced=gqa_traced,
        gdn_traced=gdn_traced, device_parts=device_parts,
        **{f"window_{k}": v for k, v in in_window.items()})
    # the plan is one schedule whatever the seed and the model routes nothing:
    # two runs' steps are the same work, so a host's stall shows as the step
    # whose end moved (PERF.md 7.13a)
    say(step_ends_ms=[round(t1 * 1e3, 1) for _, t1 in raw["steps"]])
    size = cell.config
    return {
        "setup_s": setup_s, "window_s": raw["closed_at"], "rows": rows,
        "raw": raw, "stats_open": marks["open"],
        "stats_close": marks["close"], "decode_ctx": decode_ctx,
        "gqa_traced": gqa_traced, "gdn_traced": gdn_traced,
        "device_parts": device_parts, "kv": kv,
        "model": {
            "layers": cfg.num_layers,
            "full_layers": len(cfg.full_layers), "window_layers": 0,
            "window": 0, "heads_full": cfg.heads_full,
            "heads_window": cfg.heads_window, "kv_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim, "hidden": cfg.hidden,
            "item_bytes": np.dtype(eng.core.scope.get("dec_head").dtype)
            .itemsize,
            "cache_item_bytes": 2 if size["kv_dtype"] == "bfloat16" else 4,
            "gdn_layers": len(cfg.linear_layers),
            "gdn_heads": cfg.linear_heads,
            "gdn_key_dim": cfg.linear_key_dim,
            "gdn_value_dim": cfg.linear_value_dim,
            "gdn_item_bytes": 4, "state_item_bytes": 4,
        },
        "attempted": attempted, "failed": failed, "correct": correct,
        "compiles_in_window": in_window["compilations"],
        "memory_peak_bytes": memory, "setup_counters": setup_counters,
    }


def main(argv=None):
    """The control: ``benchmark/run.py``'s run of the cell, the comparison
    made against the reference in the next precision down as well."""
    from benchmark import run as bench
    from benchmark.runners import serve_gdn      # the copy ``run`` loads

    serve_gdn.lower_control = True
    bench.main(argv)


if __name__ == "__main__":
    main(sys.argv[1:])
