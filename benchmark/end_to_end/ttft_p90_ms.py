"""90th percentile of the time to the first token, measured from the instant
each request was due, over every request due in the window.  A request that
was refused, failed or had no first token by the end of the drain counts as
attempted and sorts beyond every real time; where the percentile falls among
those, the value is the longest any of them was known to have waited."""
from benchmark.lib.stats import percentile


def read(record, cell):
    rows = record["rows"]
    real = [r["ttft_s"] for r in rows if not r["failed"]]
    lost = [r["waited_s"] for r in rows if r["failed"]]
    return 1e3 * percentile(real, 90, failed=len(lost),
                            censored=max(lost) if lost else None)
