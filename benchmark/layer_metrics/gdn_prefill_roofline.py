"""Kernels: ``gdn_prefill``'s share of its roofline: the least time the chip
could take for the delta rule over the REAL tokens of the traced prefill
calls (``rooflines/gdn_prefill.py``; the engine counts a call's tokens from
its feed, ``eng.stats["kernels"]``), over the kernel's summed device time.
One kernel call a linear layer and prompt; where the trace holds another
number of calls than the host logged, the logged need is scaled to the calls
seen.  A program with no such kernel reads nothing."""
from benchmark.lib import trace as trace_lib
from benchmark.rooflines import gdn_prefill


def read(record, trace, cell):
    gdn = record.get("gdn_traced")
    if not trace or not gdn or not gdn.get("gdn_prefill_calls"):
        return None
    events = trace_lib.name_events(trace["rows"], trace["devices"][0],
                                   trace["window"], "gdn_prefill")
    if not events:
        return None
    least_s = gdn_prefill.least_seconds(
        gdn["gdn_prefill_tokens"], gdn["gdn_prefill_calls"], record["model"],
        record["harness"]["peaks"]) * len(events) / gdn["gdn_prefill_calls"]
    return 100.0 * least_s / (sum(events) / 1e9)
