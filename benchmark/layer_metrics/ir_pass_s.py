"""Program build: seconds in the program's own build step on a compile
cache miss (the IR passes and the construction of the step function), summed
over the process from its ``executor_compile_build_s`` histogram."""
from benchmark.lib import program_spans


def read(record, trace, cell):
    series = program_spans.counter_series("executor_compile_build_s")
    return None if series is None else sum(s["sum"] for s in series)
