"""Step execution: the share of the traced window in which device 0 is idle
while the host has not yet issued the step: it is converting and staging the
feed, binding state, looking the program up, or between two steps.  With
``idle_after_call_pct`` it adds up to ``device_idle_pct.train``."""
from benchmark.lib import program_spans


def read(record, trace, cell):
    found = program_spans.of_run(record, trace)
    return None if found is None else 100.0 * found["idle_before_call_share"]
