"""Scheduler: mean sequences per decode step over the window, from the
engine's own counters: the change of ``decode_tokens`` over the change of
``decode_steps`` between the window's opening and its close."""


def read(record, trace, cell):
    if "stats_open" not in record:
        return None
    a, b = record["stats_open"], record["stats_close"]
    steps = b["decode_steps"] - a["decode_steps"]
    return (b["decode_tokens"] - a["decode_tokens"]) / steps if steps else None
