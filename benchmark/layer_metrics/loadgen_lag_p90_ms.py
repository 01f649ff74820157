"""Entry point: how late the generator submitted, submit instant minus due
instant, 90th percentile over the requests due in the window.  A starved
generator must not be read as a fast server."""
from benchmark.lib.stats import percentile


def read(record, trace, cell):
    lags = [r["lag_s"] for r in record.get("rows", [])
            if r["lag_s"] is not None]
    return 1e3 * percentile(lags, 90) if lags else None
