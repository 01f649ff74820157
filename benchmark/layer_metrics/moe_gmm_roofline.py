"""Kernels: ``moe_gmm``'s share of its roofline: for every program call of
the traced steps and every expert layer, the least time the chip could take
for the experts that received a token and the tokens routed to them
(``rooflines/moe_gmm.py``; the counts come back with each call), over the
kernel's summed device time.  Two kernel calls a layer and program call;
where the trace holds another number of calls than the host logged, the
logged need is scaled to the calls seen."""
from benchmark.lib import trace as trace_lib
from benchmark.rooflines import moe_gmm


def read(record, trace, cell):
    calls = record.get("moe_calls")
    if not trace or not calls or "model" not in record:
        return None
    events = trace_lib.name_events(trace["rows"], trace["devices"][0],
                                   trace["window"], "moe_gmm")
    if not events:
        return None
    model, peaks = record["model"], record["harness"]["peaks"]
    least = [moe_gmm.least_seconds(layer, model, peaks)
             for _phase, counts in calls for layer in counts]
    live = [s for s in least if s > 0]
    if not live:
        return None
    calls_seen = len(events) / 2
    least_s = sum(live) * calls_seen / len(least)
    return 100.0 * least_s / (sum(events) / 1e9)
