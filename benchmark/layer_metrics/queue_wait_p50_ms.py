"""Scheduler: median wait for admission, ``Request.admitted_at`` (the host
clock the harness hands ``step``) minus the due instant."""
from benchmark.lib.stats import percentile


def read(record, trace, cell):
    waits = [r["queue_wait_s"] for r in record.get("rows", [])
             if r["queue_wait_s"] is not None]
    return 1e3 * percentile(waits, 50) if waits else None
