"""Step execution: the share of the traced window in which device 0 is idle
while the step is issued: from the start of the program's ``executor/call``
to the end of its ``executor/step`` (inputs in flight, launch latency, the
write-back, the wait for the fetch).  With ``idle_before_call_pct`` it adds up
to ``device_idle_pct.train``."""
from benchmark.lib import program_spans


def read(record, trace, cell):
    found = program_spans.of_run(record, trace)
    return None if found is None else 100.0 * found["idle_after_call_share"]
