"""Kernels: ``gdn_decode``'s share of its roofline: the least time the chip
could take to read and rewrite the states of the live sequences of the traced
decode calls (``rooflines/gdn_decode.py``; the engine counts a call's live
sequences from its feed, ``eng.stats["kernels"]``), over the kernel's summed
device time.  One kernel call a linear layer and decode step; where the trace
holds another number of calls than the host logged, the logged need is scaled
to the calls seen.  A program with no such kernel reads nothing."""
from benchmark.lib import trace as trace_lib
from benchmark.rooflines import gdn_decode


def read(record, trace, cell):
    gdn = record.get("gdn_traced")
    if not trace or not gdn or not gdn.get("gdn_decode_calls"):
        return None
    events = trace_lib.name_events(trace["rows"], trace["devices"][0],
                                   trace["window"], "gdn_decode")
    if not events:
        return None
    least_s = gdn_decode.least_seconds(
        gdn["gdn_decode_sequences"], record["model"],
        record["harness"]["peaks"]) * len(events) / gdn["gdn_decode_calls"]
    return 100.0 * least_s / (sum(events) / 1e9)
