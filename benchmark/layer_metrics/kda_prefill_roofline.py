"""Kernels: ``kda_prefill``'s share of its roofline: the least time the chip
could take for the delta rule over the REAL tokens of the traced prefill
calls (``rooflines/kda_prefill.py``; the engine counts a call's tokens from
its feed, ``eng.stats["kernels"]``), over the kernel's summed device time.
One kernel call a KDA layer and prompt; where the trace holds another number
of calls than the host logged, the logged need is scaled to the calls seen.
A program with no such kernel reads nothing."""
from benchmark.lib import trace as trace_lib
from benchmark.rooflines import kda_prefill


def read(record, trace, cell):
    kda = record.get("kda_traced")
    if not trace or not kda or not kda.get("kda_prefill_calls"):
        return None
    events = trace_lib.name_events(trace["rows"], trace["devices"][0],
                                   trace["window"], "kda_prefill")
    if not events:
        return None
    least_s = kda_prefill.least_seconds(
        kda["kda_prefill_tokens"], kda["kda_prefill_calls"], record["model"],
        record["harness"]["peaks"]) * len(events) / kda["kda_prefill_calls"]
    return 100.0 * least_s / (sum(events) / 1e9)
