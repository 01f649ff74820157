"""Kernels: ``paged_decode``'s share of its memory roofline: the keys and
values of the running sequences' true contexts (read from the engine after
each traced step) over the chip's memory bandwidth, over the kernel's summed
device time.  Sequences that finished in a step are no longer running when
it is read, so the share is understated by their part (under 2 %)."""
from benchmark.lib import trace as trace_lib
from benchmark.rooflines import paged_decode


def read(record, trace, cell):
    steps = record.get("decode_ctx")
    if not trace or not steps:
        return None
    events = trace_lib.name_events(trace["rows"], trace["devices"][0],
                                   trace["window"], "paged_decode")
    if not events:
        return None
    per_layer = sum(paged_decode.needed_bytes(
        ctx, record["kv_bytes_per_token_per_layer"]) for ctx in steps)
    # the traced steps' calls: one per layer and step; events of steps the
    # host did not see whole are dropped from neither side
    layers = record["num_layers"]
    calls_seen = len(events) / layers
    need = per_layer * layers * calls_seen / len(steps)
    least_s = need / record["harness"]["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (sum(events) / 1e9)
