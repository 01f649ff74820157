"""Kernels: ``gqa_decode``'s share of its roofline: over the traced decode
steps, the least time the chip could take to read the K and V rows the running
sequences attend in every attention layer (``rooflines/gqa_decode.py``: a
full layer's whole context, a window layer's last 512), over the kernel's
summed device time.  One kernel call a layer and decode step; where the trace
holds another number of calls than the host logged steps, the logged need is
scaled to the calls seen.  A program with no such kernel reads nothing."""
from benchmark.lib import trace as trace_lib
from benchmark.rooflines import gqa_decode


def read(record, trace, cell):
    steps, model = record.get("decode_ctx"), record.get("model") or {}
    if not trace or not steps or "full_layers" not in model:
        return None
    events = trace_lib.name_events(trace["rows"], trace["devices"][0],
                                   trace["window"], "gqa_decode")
    if not events:
        return None
    peaks = record["harness"]["peaks"]
    calls = len(steps) * (model["full_layers"] + model["window_layers"])
    least_s = sum(gqa_decode.least_seconds(ctx, model, peaks)
                  for ctx in steps) * len(events) / calls
    return 100.0 * least_s / (sum(events) / 1e9)
