"""Kernels: ``kda_decode``'s share of its roofline: the least time the chip
could take to read and rewrite the states of the live sequences of the traced
decode calls (``rooflines/kda_decode.py``; the engine counts a call's live
sequences from its feed, ``eng.stats["kernels"]``), over the kernel's summed
device time.  One kernel call a KDA layer and decode step; where the trace
holds another number of calls than the host logged, the logged need is scaled
to the calls seen.  A program with no such kernel reads nothing."""
from benchmark.lib import trace as trace_lib
from benchmark.rooflines import kda_decode


def read(record, trace, cell):
    kda = record.get("kda_traced")
    if not trace or not kda or not kda.get("kda_decode_calls"):
        return None
    events = trace_lib.name_events(trace["rows"], trace["devices"][0],
                                   trace["window"], "kda_decode")
    if not events:
        return None
    least_s = kda_decode.least_seconds(
        kda["kda_decode_sequences"], record["model"],
        record["harness"]["peaks"]) * len(events) / kda["kda_decode_calls"]
    return 100.0 * least_s / (sum(events) / 1e9)
