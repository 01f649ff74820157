"""Step execution: the share of device 0's busy time in the traced window
that the KDA mixers' own kernels and named operations took: device events
whose name holds ``kda_`` or ``short_conv`` (``lib/trace.name_events``;
``lib/scopes.py`` knows no such part).  As the other parts' shares, these are
the kernels': an XLA fusion carries no scope in the profile, so the mixers'
projections, convolution and gates are not in it."""
from benchmark.lib import trace as trace_lib


def read(record, trace, cell):
    if not trace or not trace.get("busy_s"):
        return None
    events = trace_lib.name_events(trace["rows"], trace["devices"][0],
                                   trace["window"], r"kda_|short_conv")
    if not events:
        return None
    return 100.0 * (sum(events) / 1e9) / trace["busy_s"]
