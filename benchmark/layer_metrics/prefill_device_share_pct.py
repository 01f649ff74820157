"""Step execution: device 0's busy time (the union of its operations) that
falls inside the program's ``engine/prefill`` spans, over its busy time in the
traced window.  Every prefill ends in a host read of its token, so the
device's operations inside the span are the prefill's."""
from benchmark.lib import program_spans


def read(record, trace, cell):
    found = program_spans.of_run(record, trace)
    if found is None or found["prefill_device_share"] is None:
        return None
    return 100.0 * found["prefill_device_share"]
