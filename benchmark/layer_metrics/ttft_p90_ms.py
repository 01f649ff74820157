"""Scheduler: 90th percentile of the time to the first token, measured from
the instant each request was due, over every request due in the window.  A
request that was refused, failed or had no first token by the end of the drain
counts as attempted and sorts beyond every real time; where the percentile
falls among those, the value is the longest any of them was known to have
waited.  An end-to-end metric until PR 49: a first token waits for the rest
of the step in flight, which the host's microseconds decide, and runs of one
tree read it 7-9 % apart at every rate tried (and its median 3-7 %) where a
bound may be 10 % at the most and has to be twice the spread."""
from benchmark.lib.stats import percentile


def read(record, trace, cell):
    rows = record.get("rows", [])
    real = [r["ttft_s"] for r in rows if not r["failed"]]
    lost = [r["waited_s"] for r in rows if r["failed"]]
    if not real and not lost:
        return None
    return 1e3 * percentile(real, 90, failed=len(lost),
                            censored=max(lost) if lost else None)
