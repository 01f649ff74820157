"""90th percentile, over requests, of each request's mean gap between its
output tokens (the first token excluded).  A request with fewer than two
tokens has no gap and is left out; a failed one sorts beyond every gap."""
from benchmark.lib.stats import percentile


def read(record, cell):
    rows = record["rows"]
    real = [r["mean_gap_s"] for r in rows
            if not r["failed"] and r["mean_gap_s"] is not None]
    lost = [r["waited_s"] for r in rows if r["failed"]]
    return 1e3 * percentile(real, 90, failed=len(lost),
                            censored=max(lost) if lost else None)
