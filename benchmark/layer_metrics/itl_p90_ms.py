"""Scheduler: 90th percentile, over requests, of each request's mean gap
between its output tokens (the first token excluded).  A request with fewer
than two tokens has no gap and is left out; a failed one sorts beyond every
gap.  An end-to-end metric until PR 49 (see ``ttft_p90_ms.py``): runs of one
tree read it 4-9 % apart at every rate tried (one run in nine at 44 ms for 26
at 5.957 req/s); the median of the same gaps, ``end_to_end/itl_p50_ms.py``,
is what a bound holds."""
from benchmark.lib.stats import percentile


def read(record, trace, cell):
    rows = record.get("rows", [])
    real = [r["mean_gap_s"] for r in rows
            if not r["failed"] and r["mean_gap_s"] is not None]
    lost = [r["waited_s"] for r in rows if r["failed"]]
    if not real and not lost:
        return None
    return 1e3 * percentile(real, 90, failed=len(lost),
                            censored=max(lost) if lost else None)
