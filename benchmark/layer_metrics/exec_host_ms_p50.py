"""Step execution: the median over the traced steps of the program's
``executor/step`` span less the ``executor/fetch`` inside it: what the host
spends on a step before it waits on the device (look-up, feed conversion and
staging, state binding, the call, the write-back)."""
from benchmark.lib import program_spans


def read(record, trace, cell):
    return program_spans.host_ms_p50(program_spans.program_events(),
                                     "executor/step")
