"""Kernels: ``gqa_prefill``'s share of its roofline: the least time the chip
could take for the unmasked pairs of the REAL tokens of the traced prefill
calls (``rooflines/gqa_prefill.py``; the engine counts a call's tokens and
pairs from its feed, ``eng.stats["kernels"]``), over the kernel's summed
device time.  One kernel call an attention layer and prompt; where the trace
holds another number of calls than the host logged, the logged need is scaled
to the calls seen.  A program with no such kernel reads nothing."""
from benchmark.lib import trace as trace_lib
from benchmark.rooflines import gqa_prefill


def read(record, trace, cell):
    gqa = record.get("gqa_traced")
    if not trace or not gqa or not gqa.get("gqa_prefill_calls"):
        return None
    events = trace_lib.name_events(trace["rows"], trace["devices"][0],
                                   trace["window"], "gqa_prefill")
    if not events:
        return None
    least_s = gqa_prefill.least_seconds(
        gqa, record["model"], record["harness"]["peaks"]) \
        * len(events) / gqa["gqa_prefill_calls"]
    return 100.0 * least_s / (sum(events) / 1e9)
