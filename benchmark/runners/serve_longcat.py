"""Runner ``serve_longcat``: a shortcut-connected latent-attention decoder with
zero-computation experts (``paddle_tpu.inference.mla_decoder`` with
``shortcut``; the configuration's ``zero_expert_num``) served by
``ServingEngine`` on one chip, driven open-loop by the plan of the traffic
file's generator.

As ``serve_mla``, whose description, warm-up and comparison it uses: weights
made on the device from ``--seed`` in the type the configuration states;
warm-up of every program shape the plan can reach with all-padding feeds; the
replay; then the comparison that decides ``correct`` (``serve_mla.compare``:
the timed programs' logit and log-sum-exp of every served token of a seeded
sample against the reference, teacher-forced; the reference given the same
share of experts and vocabulary and routed as the engine was on the served
rows, after holding each such choice to its own scores).  It is a runner of
its own for three things ``serve_mla`` cannot give without an edit:

* **the router's correction bias.**  ``serve_mla`` seeds it normal x 0.01,
  small against sigmoid scores of about a half.  This router's scores are a
  softmax over 768 outputs, of order 1/768: a bias of 0.01 would outweigh
  every score and route every token to the same twelve outputs.  Here it is
  normal x ``0.1 / outputs``: it moves some choices and no weight.
* **a steady host.**  As ``serve_gdn``: the weights are awaited before the
  engine is built and the collector is frozen over the replay.
* **the choices by kind.**  The program counts, a layer and call, the real
  tokens' choices on held experts, on identity experts and all of them
  (``core.moe_stats``); the record carries them by phase (``choices``), which
  ``zero_expert_choice_pct`` and ``held_expert_choice_pct`` read.

The record's ``model.layers`` counts the latent pools (two a layer), what
``mla_decode_roofline`` divides by.  A program without such a decoder cannot
run the cell: ``build`` says so and exits before anything is built.

The builder's control, ``python3 -m benchmark.runners.serve_longcat`` with
``benchmark/run.py``'s arguments: the same run, with the comparison made
three more times, each of which the limits must refuse: against the
reference in the nearest precision below (``lower``: weights and latent rows
through float8_e4m3fn), against the reference without the held experts' sum
(``no_routed``) and without the identity term (``no_identity``); the run's
log line then carries ``check_controls`` beside ``check``.
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
from benchmark.runners.serve_decoder import plan  # noqa: F401 (sweep.py)
from benchmark.runners.serve_mla import (compare, model_config,  # noqa: F401
                                         warm_up)

#: the comparisons the limits must refuse, by the reference's own arguments
CONTROLS = {"lower": {"lower": "float8_e4m3fn"},
            "no_routed": {"drop": "routed"},
            "no_identity": {"drop": "identity"}}
controls = False              # set by ``main``: compare against them too


def make_weights(jax, specs: dict, seed: int, device, dtype: str):
    """Each weight in a jitted call of its own on ``device`` (one call for
    all would hold the float32 draws of 10 GB of parameters at once): norm
    scales one, the router's correction bias normal x 0.1 / outputs, the
    embedding normal, every matrix normal over sqrt(fan-in), where the
    fan-in of the two matrices that read a scaled low-rank stream (``wq_b``,
    ``wkv_b``; the configuration's ``mla_scale_*``) is the hidden size, the
    width the scales align their variance with: ``q``, ``k_nope``, ``v`` and
    ``k_r`` of unit variance, logits of unit scale."""
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype)
    root = jax.random.PRNGKey(seed % (2 ** 31))

    def draw(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32) * scale) \
            .astype(dtype)

    draw = jax.jit(draw, static_argnums=(1, 2))
    out, hidden = {}, specs["dec_embed"][1]
    with jax.default_device(device):
        for i, name in enumerate(sorted(specs)):
            shape = tuple(specs[name])
            if name.endswith("_scale"):
                out[name] = jnp.ones(shape, dtype)
                continue
            scale = 0.1 / shape[0] if name.endswith("router_bias") else \
                1.0 if name == "dec_embed" else \
                float(hidden) ** -0.5 if name.endswith(("wq_b", "wkv_b")) \
                else float(shape[-2]) ** -0.5
            out[name] = draw(jax.random.fold_in(root, i), shape, scale)
    return out


def build(cell, env):
    import paddle_tpu as pt
    from paddle_tpu.inference.serving import ServingEngine

    size, deploy = cell.config, cell.config["deployment"]
    try:
        cfg = model_config(size)
        if not cfg.zero_experts:
            raise AttributeError("zero_experts is 0")
    except (KeyError, AttributeError) as e:
        sys.exit(f"benchmark: this program's MLA decoder describes no "
                 f"shortcut-connected layer with zero-computation experts "
                 f"({e!r}); the cell cannot run on it")
    with env.span("weights"):
        weights = make_weights(env.jax, cfg.param_specs(), cell.seed,
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


class Reference:
    """The plain reference with a control's arguments (``CONTROLS``), under
    the call ``serve_mla.compare`` makes."""

    def __init__(self, module, **control):
        self.module, self.control = module, control

    def served_token_scores(self, *args):
        return self.module.served_token_scores(*args, **self.control)


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
    # what set-up built stays for the life of the process: out of the
    # collector's sight, as ``serve_gdn`` puts it (PERF.md 7.13a).  Left in,
    # one full collection a run stopped the host for 0.37-0.40 s at 9 s
    gc.collect()
    gc.freeze()
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
    gc.unfreeze()
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
    refused = {name: compare(cell, env, eng, weights,
                             Reference(reference, **control), done)
               for name, control in CONTROLS.items()} if controls else None

    carried = [p for p in raw["requests"] if p.due < 0.0
               and (p.finished is None or p.finished >= 0.0)]
    attempted = len(rows) + len(carried)
    failed = sum(r["failed"] for r in rows) \
        + sum(p.refused is not None for p in carried)
    moe_stats = core.moe_stats    # folds what is pending into moe_calls
    calls = core.moe_calls[traced.get("calls_from", 0):
                           traced.get("calls_to", 0)]
    choices = {phase: {key: value for key, value in sums.items()
                       if key.startswith("choices_")}
               for phase, sums in moe_stats.items()}
    say(window="serve", due=len(rows),
        samples_beyond={q: samples_beyond(len(rows), q) for q in (90, 95)},
        carried_into_window=len(carried), failed=failed,
        ended_at=raw["ended_at"], **loadgen.window_note(raw),
        queue_half=raw["queue_half"], queue_end=raw["queue_end"],
        engine_steps=len(raw["steps"]), steps=longest(raw["steps"]),
        gc=env.gc_watch.since(t_replay), scheduler=eng.stats,
        moe=moe_stats, kv=kv, kernel_calls=found, check=verdict,
        check_controls=refused,
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
        "decode_ctx": decode_ctx, "moe_calls": calls, "choices": choices,
        "device_parts": device_parts, "kv": kv,
        "model": {
            "layers": len(cfg.mla_layers), "expert_layers": cfg.num_layers,
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


def main(argv=None):
    """The control: ``benchmark/run.py``'s run of the cell, the comparison
    made against each of ``CONTROLS`` as well."""
    from benchmark import run as bench
    from benchmark.runners import serve_longcat     # the copy ``run`` loads

    serve_longcat.controls = True
    bench.main(argv)


if __name__ == "__main__":
    main(sys.argv[1:])
