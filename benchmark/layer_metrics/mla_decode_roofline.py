"""Kernels: ``mla_decode``'s share of its roofline: the least time the chip
could take for the latent rows of the running sequences' true contexts (read
from the engine after each traced step; ``rooflines/mla_decode.py``), over
the kernel's summed device time.  One call a layer and decode step; events of
steps the host did not see whole are dropped from neither side, as
``paged_decode_roofline`` does.  Sequences that finished in a step are no
longer running when it is read, so the share is understated by their part."""
from benchmark.lib import trace as trace_lib
from benchmark.rooflines import mla_decode


def read(record, trace, cell):
    steps = record.get("decode_ctx")
    if not trace or not steps or "model" not in record:
        return None
    events = trace_lib.name_events(trace["rows"], trace["devices"][0],
                                   trace["window"], "mla_decode")
    if not events:
        return None
    model, peaks = record["model"], record["harness"]["peaks"]
    per_layer = sum(mla_decode.least_seconds(ctx, model, peaks)
                    for ctx in steps)
    calls_seen = len(events) / model["layers"]
    least_s = per_layer * model["layers"] * calls_seen / len(steps)
    return 100.0 * least_s / (sum(events) / 1e9)
