"""Per-request SLO / goodput report over a traced serving run.

Drives the continuous-batching engine with a seeded open-loop Poisson
trace under ``FLAGS_trace_requests=1`` and reports the signal layer the
SLO-aware-admission rung will stand on:

* a **per-request span table** — queue / prefill / decode / preempt
  breakdown recomputed from each request's recorded span tree
  (utils/tracing.py), with TTFT, token count, preemption cycles and the
  admission OUTCOME (admitted / shed / rejected — the r18 overload-
  protection outcomes);
* **SLO accounting** — declared TTFT / per-token targets, the
  rolling-window error-budget burn rate and goodput (requests/tokens
  served within SLO vs total) from utils/telemetry.py's SLOTracker;
* a **cross-check**: the tracker's goodput is recomputed from
  loadgen's INDEPENDENT per-request latencies
  (utils/loadgen.py per_request_latency) — both views judge the same
  logical token times, so the counts must agree exactly
  (``agrees_with_loadgen``), and the recorded spans must reconcile
  with the engine's admit/preempt/finish counters
  (``spans_reconcile``).

The last line is the stable one-line ``SLO={json}``
(``utils/loadgen.py`` ``emit_json``).

Usage:
  python tools/slo_report.py [--requests 16] [--rate 50] [--seed 0]
      [--slo-ttft-ms 200] [--slo-token-ms 100] [--objective 0.99]
      [--window 256] [--json]
  python tools/slo_report.py --quick   # bounded tier-1 smoke: exit 1
      when the tracker disagrees with loadgen or spans fail to
      reconcile with the scheduler counters
"""
from __future__ import annotations

import argparse
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def build_args():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=50.0,
                    help="Poisson arrival rate, req/s")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--vocab", type=int, default=64)
    ap.add_argument("--hidden", type=int, default=32)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--num-pages", type=int, default=64)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--token-budget", type=int, default=128)
    ap.add_argument("--prompt-min", type=int, default=4)
    ap.add_argument("--prompt-max", type=int, default=16)
    ap.add_argument("--new-min", type=int, default=4)
    ap.add_argument("--new-max", type=int, default=8)
    ap.add_argument("--warmup", type=int, default=1)
    ap.add_argument("--policy", default="fifo",
                    help="admission policy (fifo | slo_aware) — shed "
                         "outcomes only appear under slo_aware with an "
                         "armed TTFT target")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="arm the CoW KV prefix cache (r19); the "
                         "cached/chunks columns light up")
    ap.add_argument("--chunk-tokens", type=int, default=0,
                    help="chunked-prefill budget (0 = monolithic)")
    ap.add_argument("--prefix-len", type=int, default=0,
                    help="shared-prefix tokens in the seeded trace")
    ap.add_argument("--prefix-share", type=float, default=0.8)
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding draft length (r21); the "
                         "accepted column + spec accept-rate section "
                         "light up")
    ap.add_argument("--kv-dtype", default="",
                    help="KV pool storage dtype (float32 | bfloat16 | "
                         "int8; '' = FLAGS_kv_cache_dtype) — reported "
                         "in the payload so traces from quantized-vs-"
                         "f32 A/B runs are distinguishable")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree for the engine (r24); "
                         "reported in the payload so TP-vs-single "
                         "traces are distinguishable")
    ap.add_argument("--slo-ttft-ms", type=float, default=200.0,
                    help="TTFT target in ms (0 = unset)")
    ap.add_argument("--slo-token-ms", type=float, default=100.0,
                    help="per-token latency target in ms (0 = unset)")
    ap.add_argument("--objective", type=float, default=0.99)
    ap.add_argument("--window", type=int, default=256)
    ap.add_argument("--json", action="store_true",
                    help="machine output only (the SLO= line)")
    ap.add_argument("--quick", action="store_true",
                    help="bounded tier-1 smoke mode")
    return ap


#: root-span status -> admission-outcome column value
_OUTCOMES = {"finished": "admitted", "shed": "shed", "rejected": "rejected"}


def trace_rows(traces):
    """Per-request breakdown from the span trees: queue/preempt waits
    in LOGICAL time (the driver's clock — the only one waits exist
    in), prefill/decode in wall time (real compute durations).  Every
    TERMINAL request appears, with its admission outcome (admitted /
    shed / rejected)."""
    rows = []
    for tr in traces:
        root = next((s for s in tr.spans if s.name == "request"), None)
        if root is None:
            continue
        outcome = _OUTCOMES.get(root.attrs.get("status"))
        if outcome is None:
            continue
        queue_s = sum((s.t1 or s.t0) - s.t0 for s in tr.spans
                      if s.name in ("queue_wait", "preempted")
                      and s.t1 is not None)
        prefills = tr.spans_named("prefill")
        rows.append({
            "trace": tr.trace_id,
            "req": str(tr.req_id),
            "outcome": outcome,
            "queue_s": round(queue_s, 6),
            "prefill_ms": round(sum(
                s.wall_duration() for s in prefills) * 1e3, 3),
            "decode_ms": round(sum(
                s.wall_duration() for s in tr.spans_named("decode_step"))
                * 1e3, 3),
            "decode_steps": len(tr.spans_named("decode_step")),
            "preempt_cycles": len(tr.spans_named("preempted")),
            # r19 columns: prompt tokens the LAST prefill served from
            # cached prefix pages, and how many chunks it ran in
            # (attrs only exist when the features engaged — 0/1 means
            # cold monolithic)
            "cached_tokens": int(prefills[-1].attrs.get(
                "cached_tokens", 0)) if prefills else 0,
            "prefill_chunks": int(prefills[-1].attrs.get(
                "chunks", 1)) if prefills else 0,
            # r21 column: draft tokens the verify calls accepted (the
            # accepted attr only exists when spec-decode engaged — a
            # monolithic decode_step counts 0)
            "accepted_tokens": sum(
                int(s.attrs.get("accepted", 0))
                for s in tr.spans_named("decode_step")),
            "ttft_s": root.attrs.get("ttft_s"),
            "tokens": root.attrs.get("tokens"),
        })
    rows.sort(key=lambda r: -(r["ttft_s"] or 0.0))
    return rows


def independent_goodput(per_req, ttft_s, token_s):
    """Recompute the SLOTracker's counts from loadgen's per-request
    view — the agreement oracle (same judging rules, independent
    data path).  Shed requests are excluded from the denominators on
    BOTH sides: the tracker never observes them (the engine sheds
    before finish), and this recomputation skips them explicitly."""
    req_total = req_within = tok_total = tok_within = 0
    for r in per_req.values():
        if not r["finished"] or r.get("shed"):
            continue
        has_first = r["ttft_s"] == r["ttft_s"]
        ok_ttft = ttft_s is None or (has_first and r["ttft_s"] <= ttft_s)
        if token_s is None:
            gap_ok = len(r["decode_gaps"])
        else:
            gap_ok = sum(1 for g in r["decode_gaps"] if g <= token_s)
        within = ok_ttft and gap_ok == len(r["decode_gaps"])
        req_total += 1
        req_within += bool(within)
        tok_total += (1 if has_first else 0) + len(r["decode_gaps"])
        tok_within += (1 if (has_first and ok_ttft) else 0) + gap_ok
    return {"requests_total": req_total, "requests_within_slo": req_within,
            "tokens_total": tok_total, "tokens_within_slo": tok_within}


def main(argv=None) -> int:
    args = build_args().parse_args(argv)
    if args.quick:
        args.requests = min(args.requests, 8)
        args.rate = 100.0
        args.vocab, args.hidden, args.layers = 64, 32, 1
        args.max_seq, args.num_pages, args.page_size = 64, 64, 8
        args.prompt_max, args.new_max = 10, 6
        args.warmup = max(args.warmup, 1)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if args.tp > 1 and "xla_force_host_platform_device_count" \
            not in os.environ.get("XLA_FLAGS", ""):
        # the TP engine needs tp devices; force a virtual CPU mesh
        # before jax initializes (no-op on a real multi-chip host)
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + f" --xla_force_host_platform_device"
                                     f"_count={max(args.tp, 8)}").strip()
    from paddle_tpu.inference.serving import DecoderConfig, ServingEngine
    from paddle_tpu.utils import flags as _flags
    from paddle_tpu.utils import telemetry, tracing
    from paddle_tpu.utils.loadgen import (emit_json, latency_report,
                                          per_request_latency,
                                          poisson_trace, replay_trace)

    _flags.set_flags({"trace_requests": 1})
    ttft_s = (args.slo_ttft_ms / 1e3) or None
    token_s = (args.slo_token_ms / 1e3) or None
    telemetry.slo_tracker().configure(
        ttft_s=ttft_s, token_s=token_s,
        objective=args.objective, window=args.window)

    cfg = DecoderConfig(vocab_size=args.vocab, hidden=args.hidden,
                        num_heads=args.heads, num_layers=args.layers,
                        max_seq_len=args.max_seq)
    eng = ServingEngine(cfg, num_pages=args.num_pages,
                        page_size=args.page_size,
                        max_batch=args.max_batch,
                        token_budget=args.token_budget,
                        prefill_bucket_min=4, seed=args.seed,
                        admission_policy=args.policy,
                        prefix_cache=args.prefix_cache or None,
                        prefill_chunk=args.chunk_tokens,
                        spec_k=args.spec_k or None,
                        kv_dtype=args.kv_dtype or None,
                        tp=args.tp)
    trace = poisson_trace(
        args.requests, args.rate, cfg.vocab_size,
        prompt_len_range=(args.prompt_min, args.prompt_max),
        max_new_range=(args.new_min, args.new_max), seed=args.seed,
        prefix_len=args.prefix_len, prefix_share=args.prefix_share)

    for _ in range(args.warmup):
        replay_trace(eng, trace)
    # measured window: everything (spans, registry, SLO accounting,
    # scheduler counters) describes ONLY the measured replay
    eng.stats = {k: 0 for k in eng.stats}
    tracing.reset()
    telemetry.registry().reset()
    telemetry.slo_tracker().reset()
    raw = replay_trace(eng, trace)

    rep = latency_report(raw)
    per_req = per_request_latency(raw)
    slo = telemetry.slo_tracker().report()
    traces = tracing.store().finished_traces()
    rows = trace_rows(traces)

    ind = independent_goodput(per_req, ttft_s, token_s)
    g = slo["goodput"]
    agrees = all(g[k] == ind[k] for k in ind)

    admitted_rows = [r for r in rows if r["outcome"] == "admitted"]
    shed_rows = [r for r in rows if r["outcome"] == "shed"]
    recon = {
        "prefill_spans": sum(len(t.spans_named("prefill"))
                             for t in traces),
        "admitted": eng.stats["admitted"],
        "preempted_spans": sum(len(t.spans_named("preempted"))
                               for t in traces),
        "preempted": eng.stats["preempted"],
        "finished_traces": len(admitted_rows),
        "finished": eng.stats["finished"],
        "shed_traces": len(shed_rows),
        "shed": eng.stats["shed"],
    }
    reconciles = (recon["prefill_spans"] == recon["admitted"]
                  and recon["preempted_spans"] == recon["preempted"]
                  and recon["finished_traces"] == recon["finished"]
                  and recon["shed_traces"] == recon["shed"])

    if not args.json:
        print(f"{'req':>6} {'outcome':>9} {'queue_s':>9} "
              f"{'prefill_ms':>11} {'decode_ms':>10} {'steps':>6} "
              f"{'preempt':>8} {'cached':>7} {'chunks':>7} "
              f"{'accepted':>9} {'ttft_s':>9} {'tokens':>7}")
        for r in rows[:20]:
            ttft = ("-" if r["ttft_s"] is None
                    else f"{r['ttft_s']:.5f}")
            print(f"{r['req']:>6} {r['outcome']:>9} {r['queue_s']:>9.4f} "
                  f"{r['prefill_ms']:>11.3f} {r['decode_ms']:>10.3f} "
                  f"{r['decode_steps']:>6} {r['preempt_cycles']:>8} "
                  f"{r['cached_tokens']:>7} {r['prefill_chunks']:>7} "
                  f"{r['accepted_tokens']:>9} "
                  f"{ttft:>9} {r['tokens'] if r['tokens'] is not None else '-':>7}")
        if len(rows) > 20:
            print(f"... {len(rows) - 20} more")
        print(f"targets: ttft<={slo['targets']['ttft_s']}s "
              f"token<={slo['targets']['token_s']}s "
              f"objective={slo['targets']['objective']}")
        print(f"goodput: {g['requests_within_slo']}/{g['requests_total']} "
              f"requests, {g['tokens_within_slo']}/{g['tokens_total']} "
              f"tokens within SLO; burn rate {slo['burn_rate']}")
        print(f"shed: {eng.stats['shed']}/{args.requests} "
              f"(policy={args.policy}; shed requests excluded from the "
              f"goodput denominators)")
        print(f"kv_pool: dtype={eng.kv_dtype} "
              f"pages={eng.core.kv_config.num_pages}")
        print(f"agrees_with_loadgen={agrees} spans_reconcile={reconciles}")

    payload = {
        "mode": "quick" if args.quick else "full",
        "requests": args.requests, "rate_req_s": args.rate,
        "seed": args.seed,
        "policy": args.policy,
        # r24: the engine's tensor-parallel degree — TP-vs-single
        # traces are otherwise indistinguishable in this report
        "tp": int(eng.core.tp),
        # r23: the pool's storage dtype — quantized-vs-f32 A/B traces
        # are otherwise indistinguishable in this report
        "kv_pool": {"dtype": eng.kv_dtype,
                    "num_pages": int(eng.core.kv_config.num_pages),
                    "scale_bytes": int(eng.kv.stats()["scale_bytes"])},
        "slo": slo,
        "latency": rep,
        "per_request": rows[:50],
        "independent": ind,
        "shed": {"count": eng.stats["shed"],
                 "rate": round(eng.stats["shed"] / max(args.requests, 1),
                               6)},
        # r21: verify-call acceptance over the measured replay (zeros
        # with spec off — the keys are unconditional, like the stats)
        "spec": {"spec_k": args.spec_k,
                 "proposed": int(eng.stats["spec_proposed"]),
                 "accepted": int(eng.stats["spec_accepted"]),
                 "accept_rate": round(
                     eng.stats["spec_accepted"]
                     / eng.stats["spec_proposed"], 4)
                 if eng.stats["spec_proposed"] else 0.0},
        "agrees_with_loadgen": bool(agrees),
        "spans_reconcile": bool(reconciles),
        "reconciliation": recon,
    }
    emit_json("SLO", payload)
    if args.quick and not (agrees and reconciles):
        print("FAIL: SLO accounting did not reconcile "
              f"(agrees={agrees}, spans={recon})", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
