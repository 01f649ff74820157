"""The middle request's token gap: the median, over every request due in the
window, of the request's mean gap between its output tokens (the first token
excluded).  A request with fewer than two tokens has no gap and is left out;
a failed one sorts beyond every gap, so failures push the reading up.

The 90th percentile of the same gaps (``layer_metrics/itl_p90_ms.py``) was
the end-to-end metric until PR 49; it is a per-layer reading since, because
runs of one tree read it 4-7 % apart at every rate tried and no bound under
the 10 % a bound may be held it (``PERF.md`` sections 2 and 7.1)."""
from benchmark.lib.stats import percentile


def read(record, cell):
    rows = record["rows"]
    real = [r["mean_gap_s"] for r in rows
            if not r["failed"] and r["mean_gap_s"] is not None]
    lost = [r["waited_s"] for r in rows if r["failed"]]
    return 1e3 * percentile(real, 50, failed=len(lost),
                            censored=max(lost) if lost else None)
