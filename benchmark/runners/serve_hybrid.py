"""Runner ``serve_hybrid``: a hybrid decoder (gated delta-rule KDA layers with
a per-sequence state slot beside latent-attention layers with a paged latent
pool, an expert layer that holds its chip's share of the experts:
``paddle_tpu.inference.mla_decoder`` with ``mixers``) served by
``ServingEngine`` on one chip, driven open-loop by the plan of the traffic
file's generator.

As ``serve_mla`` (whose comparison and whose plan it uses): weights made on
the device from ``--seed`` in the type the configuration states; warm-up of
every program shape the plan can reach with all-padding feeds, which write
nothing into the latent pools and the padding's slot alone of the state
pools; the replay; then the comparison that decides ``correct``
(``serve_mla.compare``: the timed programs' logit and log-sum-exp of every
served token of a seeded sample against the reference, teacher-forced; the
reference given the same share of experts and vocabulary).  The reference is
routed as the engine was, after holding each such choice to its own scores
(``slack``), on the served rows as in ``serve_mla`` AND on the prompts' rows
(the prefill form returns them, ``core.prompt_routes``): in this model an
expert flipped on a prompt's last rows by the served precision's rounding
reaches the first served rows undiluted, through the convolution's taps and
the fast-decaying channels of a state (measured: the worst rows were the
first sixteen served, PERF.md section 6).  Types are held as types: weights
and latent pools bfloat16, state pools float32.

A program without such a decoder cannot run the cell: the runner says so and
exits before it builds anything.

For the per-layer readers the record carries what ``serve_mla``'s does
(``decode_ctx``, ``moe_calls`` over the HELD experts, ``moe_open`` /
``moe_close``, ``kv``, ``device_parts``, ``model``: its ``layers`` the MLA
layers, what ``mla_decode_roofline`` divides by) and ``kda_traced``: the KDA
kernels' calls, real tokens and live sequences over the traced steps, from the
engine's own count (``eng.stats["kernels"]``).

The builder's control, ``python3 -m benchmark.runners.serve_hybrid`` with
``benchmark/run.py``'s arguments: the same run, with the comparison made a
second time against the reference in the nearest precision below
(``LOWER``: weights and latent rows through float8_e4m3fn, the state through
bfloat16), which the limits must refuse; the run's log line then carries
``check_lower`` beside ``check``.  How much of the comparison the engine's
routing replaces is in every run's line (``routing_followed``).
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
from benchmark.runners.serve_mla import compare

LOWER = "float8_e4m3fn"       # the reference's next precision down
lower_control = False         # set by ``main``: compare against it too


def model_config(size: dict):
    """The program's description of the configuration file's model."""
    try:
        from paddle_tpu.inference.mla_decoder import MLADecoderConfig
        if not hasattr(MLADecoderConfig, "state_pool_specs"):
            raise ImportError("MLADecoderConfig describes no KDA layer")
    except ImportError as e:
        sys.exit(f"benchmark: this program has no hybrid (KDA + MLA) decoder "
                 f"({e}); the cell cannot run on it")
    return MLADecoderConfig.from_source(
        size, max_seq_len=size["deployment"]["max_context"],
        weights_dtype=size["weights_dtype"])


def make_weights(jax, specs: dict, seed: int, device, dtype: str):
    """Each weight in a jitted call of its own on ``device``: norm scales
    one, the router's correction bias normal x 0.01, the embedding normal,
    every matrix normal over sqrt(fan-in) (the convolution's taps over
    sqrt(taps)); the decay's ``A_log`` the log of a rate uniform in [1, 16]
    and ``dt_bias`` the inverse softplus of a step log-uniform in [0.001,
    0.1] (the configuration's ``assumed``)."""
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype)
    root = jax.random.PRNGKey(seed % (2 ** 31))

    def normal(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32) * scale) \
            .astype(dtype)

    def a_log(key, shape):
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                          16.0)).astype(dtype)

    def dt_bias(key, shape):
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        np.log(1e-3), np.log(0.1)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)

    normal = jax.jit(normal, static_argnums=(1, 2))
    a_log, dt_bias = (jax.jit(f, static_argnums=1) for f in (a_log, dt_bias))
    out = {}
    with jax.default_device(device):
        for i, name in enumerate(sorted(specs)):
            shape, key = tuple(specs[name]), jax.random.fold_in(root, i)
            if name.endswith("_scale"):
                out[name] = jnp.ones(shape, dtype)
            elif name.endswith("kda_a_log"):
                out[name] = a_log(key, shape)
            elif name.endswith("kda_dt_bias"):
                out[name] = dt_bias(key, shape)
            else:
                scale = 0.01 if name.endswith("router_bias") else \
                    1.0 if name == "dec_embed" else \
                    float(shape[-1]) ** -0.5 if name.endswith("kda_conv") \
                    else float(shape[-2]) ** -0.5
                out[name] = normal(key, shape, scale)
    return out


def warm_up(eng, planned, env):
    """Run each program shape the plan can reach once, through the engine's
    own call: prefill buckets from the shortest prompt to the longest, decode
    batch buckets up to ``max_batch`` by block-table widths up to the longest
    context.  Every row is padding: no latent row is written, and of the
    state pools the padding's slot alone."""
    core, cfg, kvc = eng.core, eng.cfg, eng.core.kv_config
    pad, page, idle = kvc.pad_slot, kvc.page_size, kvc.pad_state_slot
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
                "last_index": np.zeros(1, np.int32),
                "state_slots": np.full(1, idle, np.int32)}

    def decode_feed(b, w):
        return {"tokens": np.zeros(b, np.int32),
                "positions": np.zeros(b, np.int32),
                "block_tables": np.zeros((b, w), np.int32),
                "context_lens": np.ones(b, np.int32),
                "slot_mapping": np.full(b, pad, np.int32),
                "state_slots": np.full(b, idle, np.int32)}

    # the first shape of each form runs twice (its first call of all leaves
    # the program's RNG state in the scope in another type, which compiles
    # that shape again), and each call's tokens are read: a pipelined
    # engine's calls return before the device has run them
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


class Reference:
    """The plain reference as ``serve_mla.compare`` calls it, handed the
    whole configuration (the layers' kinds lie in a nested group, which the
    comparison's own flat reading of the file leaves out), the routing of
    each checked request's PROMPT rows as the engine's prefill made it (the
    reference holds every such choice to its own scores, then follows it:
    see its docstring) and, for the builder's reading of the next precision
    down, ``lower``."""

    def __init__(self, module, size: dict, core=None, done=(), lower=None):
        self.module, self.size, self.lower = module, size, lower
        self.core = core
        self.req_of = {id(p.prompt): p.req_id for p in done}
        self.followed = []        # a request: what following replaced

    def served_token_scores(self, weights, _flat, prompt, *args):
        rows = None if self.core is None else \
            self.core.prompt_routes(self.req_of[id(prompt)])
        out = self.module.served_token_scores(
            weights, self.size, prompt, *args, lower=self.lower,
            prompt_routes=rows)
        if rows is not None:
            # a row's slack is over 0 where the engine chose an expert this
            # reference, routed alone, would not have: the rows whose
            # routing is the engine's and not the reference's own
            worst = out["slack"][:len(prompt) - 1].max(axis=1)
            self.followed.append({
                "prompt_rows": int(worst.size),
                "rows_routed_otherwise": int((worst > 0).sum()),
                "worst_slack": float(worst.max(initial=0.0))})
        return out


def kernel_counts(eng) -> dict:
    """The KDA kernels' counts so far, prefill and decode in one dict."""
    return {key: value for phase in ("prefill", "decode")
            for key, value in eng.stats["kernels"].get(phase, {}).items()
            if key.startswith("kda_")}


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
            traced.setdefault("kda_from", kernel_counts(engine))
            traced["calls_to"] = calls_seen()
            traced["kda_to"] = kernel_counts(engine)
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
    state_pools = list(cfg.state_pool_specs(1))
    types = {"kv": kv["dtype"],
             "weights": sorted({str(w.dtype) for w in weights.values()}),
             "state": sorted({str(core.scope.get(n).dtype)
                              for n in state_pools})}
    as_stated = types == {"kv": cell.config["kv_dtype"],
                          "weights": [cell.config["weights_dtype"]],
                          "state": [cell.config["state_dtype"]]}
    # the pools have served: their room is the reference's
    for name in cfg.cache_pool_names() + state_pools:
        core.scope.erase([name])
    served = Reference(reference, cell.config, core, done)
    verdict = compare(cell, env, eng, weights, served, done)
    correct = verdict["within"] and not short and as_stated
    lower = compare(cell, env, eng, weights,
                    Reference(reference, cell.config, core, done, LOWER),
                    done) if lower_control else None

    carried = [p for p in raw["requests"] if p.due < 0.0
               and (p.finished is None or p.finished >= 0.0)]
    attempted = len(rows) + len(carried)
    failed = sum(r["failed"] for r in rows) \
        + sum(p.refused is not None for p in carried)
    moe_stats = core.moe_stats    # folds what is pending into moe_calls
    calls = core.moe_calls[traced.get("calls_from", 0):
                           traced.get("calls_to", 0)]
    kda_traced = {key: value - traced.get("kda_from", {}).get(key, 0)
                  for key, value in traced.get("kda_to", {}).items()}
    say(window="serve", due=len(rows),
        samples_beyond={q: samples_beyond(len(rows), q) for q in (90, 95)},
        carried_into_window=len(carried), failed=failed,
        ended_at=raw["ended_at"], **loadgen.window_note(raw),
        queue_half=raw["queue_half"], queue_end=raw["queue_end"],
        engine_steps=len(raw["steps"]), steps=longest(raw["steps"]),
        gc=env.gc_watch.since(t_replay), scheduler=eng.stats,
        moe=moe_stats, kv=kv, kernel_calls=found, check=verdict,
        routing_followed=served.followed, check_lower=lower,
        types=types, types_as_stated=as_stated, wrong_token_count=short,
        memory_peak_bytes=memory, memory_stats=memory_stats,
        traced_decode_steps=len(decode_ctx), traced_moe_calls=len(calls),
        kda_traced=kda_traced, device_parts=device_parts,
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
        "kda_traced": kda_traced,
        "device_parts": device_parts, "kv": kv,
        "model": {
            "layers": len(cfg.mla_layers),
            "kda_layers": len(cfg.kda_layers),
            "expert_layers": cfg.num_layers - cfg.first_k_dense,
            "heads": cfg.num_heads, "latent_values": cfg.latent_width,
            "kv_lora_rank": cfg.kv_lora_rank,
            "hidden": cfg.hidden, "expert_width": cfg.moe_intermediate,
            "item_bytes": np.dtype(eng.core.scope.get("dec_head").dtype)
            .itemsize,
            "cache_item_bytes": 2 if size["kv_dtype"] == "bfloat16" else 4,
            "kda_heads": cfg.kda_heads, "kda_head_dim": cfg.kda_head_dim,
            "kda_item_bytes": 4, "state_item_bytes": 4,
        },
        "attempted": attempted, "failed": failed, "correct": correct,
        "compiles_in_window": in_window["compilations"],
        "memory_peak_bytes": memory, "setup_counters": setup_counters,
    }


def main(argv=None):
    """The control: ``benchmark/run.py``'s run of the cell, the comparison
    made against the reference in the next precision down as well."""
    from benchmark import run as bench
    from benchmark.runners import serve_hybrid      # the copy ``run`` loads

    serve_hybrid.lower_control = True
    bench.main(argv)


if __name__ == "__main__":
    main(sys.argv[1:])
