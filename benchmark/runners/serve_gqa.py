"""Runner ``serve_gqa``: a grouped-query decoder with window layers beside
full ones (``paddle_tpu.inference.gqa_decoder``: K and V pools in two groups
of pages, the window layers' freed behind the window; a gate a head; an expert
layer that holds its chip's share of the experts) served by ``ServingEngine``
on one chip, driven open-loop by the plan of the traffic file's generator.

As ``serve_hybrid`` (whose ``Reference`` it uses, with ``serve_mla``'s
weights and comparison): weights made on the device from ``--seed`` in the
type the configuration states; warm-up of every program shape the plan can
reach with all-padding feeds, which write nothing into either group's pools;
the replay; then the comparison that decides ``correct``
(``serve_mla.compare``: the timed programs' logit and log-sum-exp of every
served token of a sample against the reference, teacher-forced; the reference
given the same share of experts and vocabulary and routed as the engine was,
on the served rows and on the prompts', after holding each choice to its own
scores).  The sample is drawn by ``--seed`` as ever, except that its first
two places go to a request that ENDED INSIDE THE WINDOW (prompt + served <=
window: its window layers freed nothing), where the run completed one, and
to one whose context crossed it so that window pages were freed behind it:
the two lifetimes of the cache.  Types are held as types: weights and both
groups' pools bfloat16.

A program without such a decoder cannot run the cell: the runner says so and
exits before it builds anything.

For the per-layer readers the record carries what ``serve_mla``'s does
(``decode_ctx``, ``moe_calls`` over the HELD experts, ``moe_open`` /
``moe_close``, ``kv`` with its ``groups``, ``device_parts``, ``model``) and
``gqa_traced``: the attention kernels' calls, tokens, pairs, blocks and pages
over the traced steps, from the engine's own count
(``eng.stats["kernels"]``).

The builder's control, ``python3 -m benchmark.runners.serve_gqa`` with
``benchmark/run.py``'s arguments: the same run, with the comparison made a
second time against the reference in the nearest precision below (``LOWER``:
weights and K/V rows through float8_e4m3fn), which the limits must refuse;
the run's log line then carries ``check_lower`` beside ``check``.
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
from benchmark.runners.serve_hybrid import Reference
from benchmark.runners.serve_mla import compare, make_weights

LOWER = "float8_e4m3fn"       # the reference's next precision down
lower_control = False         # set by ``main``: compare against it too


def model_config(size: dict):
    """The program's description of the configuration file's model."""
    try:
        from paddle_tpu.inference.gqa_decoder import GQADecoderConfig
    except ImportError as e:
        sys.exit(f"benchmark: this program has no grouped-query decoder "
                 f"with window layers ({e}); the cell cannot run on it")
    return GQADecoderConfig.from_source(
        size, max_seq_len=size["deployment"]["max_context"],
        weights_dtype=size["weights_dtype"])


def warm_up(eng, planned, env):
    """Run each program shape the plan can reach once, through the engine's
    own call: prefill buckets from the shortest prompt to the longest, decode
    batch buckets up to ``max_batch`` by block-table widths up to the longest
    context (the window group's table has one width).  Every row is padding:
    nothing is written into either group's pools."""
    core, cfg, kvc = eng.core, eng.cfg, eng.core.kv_config
    pad, page = kvc.pad_slot, kvc.page_size
    wpad, wwidth = kvc.window_pad_slot, kvc.window_pages_per_seq
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
                "window_slot_mapping": np.full(s, wpad, np.int32)}

    def decode_feed(b, w):
        return {"tokens": np.zeros(b, np.int32),
                "positions": np.zeros(b, np.int32),
                "block_tables": np.zeros((b, w), np.int32),
                "context_lens": np.ones(b, np.int32),
                "slot_mapping": np.full(b, pad, np.int32),
                "window_slot_mapping": np.full(b, wpad, np.int32),
                "window_tables": np.zeros((b, wwidth), np.int32),
                "window_first": np.zeros(b, np.int32)}

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


def kernel_counts(eng) -> dict:
    """The attention kernels' counts so far, prefill and decode in one
    dict."""
    return {key: value for phase in ("prefill", "decode")
            for key, value in eng.stats["kernels"].get(phase, {}).items()
            if key.startswith("gqa_")}


def sample_of(done, cell, cfg, page: int):
    """The requests the comparison takes: ``check.sample`` of ``done`` in the
    order ``--seed`` draws, the first two places given to one that ended
    inside the window (where there is one) and one whose window pages were
    freed behind it; and what kinds the sample holds."""
    rng = np.random.RandomState(cell.seed % (2 ** 32))
    order = [done[i] for i in rng.permutation(len(done))]

    def end(p):
        return len(p.prompt) + len(p.handle.out_tokens)

    inside = [p for p in order if end(p) <= cfg.window]
    crossed = [p for p in order if end(p) > cfg.window + 2 * page]
    first = inside[:1] + crossed[:1]
    rest = [p for p in order if all(p is not q for q in first)]
    sample = (first + rest)[:cell.config["check"]["sample"]]
    return sample, {
        "contexts": [end(p) for p in sample],
        "ended_inside_window": sum(end(p) <= cfg.window for p in sample),
        "window_pages_freed": sum(end(p) > cfg.window + 2 * page
                                  for p in sample),
        "completed_inside_window": len(inside)}


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
            traced.setdefault("gqa_from", kernel_counts(engine))
            traced["calls_to"] = calls_seen()
            traced["gqa_to"] = kernel_counts(engine)
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
    pools = cfg.cache_pool_names()
    types = {"kv": kv["dtype"],
             "pools": sorted({str(core.scope.get(n).dtype) for n in pools}),
             "weights": sorted({str(w.dtype) for w in weights.values()})}
    as_stated = types == {"kv": cell.config["kv_dtype"],
                          "pools": [cell.config["kv_dtype"]],
                          "weights": [cell.config["weights_dtype"]]}
    # the pools have served: their room is the reference's
    for name in pools:
        core.scope.erase([name])
    sample, kinds = sample_of(done, cell, cfg, core.kv_config.page_size)
    served = Reference(reference, cell.config, core, sample)
    verdict = compare(cell, env, eng, weights, served, sample)
    # a run that served past the window must have checked a request whose
    # window pages were freed
    two_lifetimes = kinds["window_pages_freed"] > 0 or not any(
        len(p.prompt) + p.want > cfg.window for p in done)
    correct = verdict["within"] and not short and as_stated \
        and two_lifetimes
    lower = compare(cell, env, eng, weights,
                    Reference(reference, cell.config, core, sample, LOWER),
                    sample) if lower_control else None

    carried = [p for p in raw["requests"] if p.due < 0.0
               and (p.finished is None or p.finished >= 0.0)]
    attempted = len(rows) + len(carried)
    failed = sum(r["failed"] for r in rows) \
        + sum(p.refused is not None for p in carried)
    moe_stats = core.moe_stats    # folds what is pending into moe_calls
    calls = core.moe_calls[traced.get("calls_from", 0):
                           traced.get("calls_to", 0)]
    gqa_traced = {key: value - traced.get("gqa_from", {}).get(key, 0)
                  for key, value in traced.get("gqa_to", {}).items()}
    say(window="serve", due=len(rows),
        samples_beyond={q: samples_beyond(len(rows), q) for q in (90, 95)},
        carried_into_window=len(carried), failed=failed,
        ended_at=raw["ended_at"], **loadgen.window_note(raw),
        queue_half=raw["queue_half"], queue_end=raw["queue_end"],
        engine_steps=len(raw["steps"]), steps=longest(raw["steps"]),
        gc=env.gc_watch.since(t_replay), scheduler=eng.stats,
        moe=moe_stats, kv=kv, kernel_calls=found, check=verdict,
        sample=kinds, routing_followed=served.followed, check_lower=lower,
        types=types, types_as_stated=as_stated, wrong_token_count=short,
        memory_peak_bytes=memory, memory_stats=memory_stats,
        traced_decode_steps=len(decode_ctx), traced_moe_calls=len(calls),
        gqa_traced=gqa_traced, device_parts=device_parts,
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
        "gqa_traced": gqa_traced,
        "device_parts": device_parts, "kv": kv,
        "model": {
            "layers": cfg.num_layers,
            "full_layers": len(cfg.full_layers),
            "window_layers": len(cfg.window_layers),
            "window": cfg.window, "heads_full": cfg.heads_full,
            "heads_window": cfg.heads_window, "kv_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim,
            "expert_layers": cfg.num_layers - cfg.first_k_dense,
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
    made against the reference in the next precision down as well."""
    from benchmark import run as bench
    from benchmark.runners import serve_gqa      # the copy ``run`` loads

    serve_gqa.lower_control = True
    bench.main(argv)


if __name__ == "__main__":
    main(sys.argv[1:])
