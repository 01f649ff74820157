"""Scheduler: the median over the traced engine steps of the program's
``engine/step`` span less the ``executor/fetch`` time inside it: scheduling,
feed and block-table building, dispatch and token handling, without the host's
waits on the device."""
from benchmark.lib import program_spans


def read(record, trace, cell):
    return program_spans.host_ms_p50(program_spans.program_events(),
                                     "engine/step")
