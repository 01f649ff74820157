"""Program build: seconds JAX spent tracing the op lowerings to a jaxpr and
lowering the jaxpr to MLIR, summed over the process from the program's two
counters.  They take JAX's events named after a compiled step (``pt_<label>``),
which leaves the plain reference's tracing out."""
from benchmark.lib import program_spans

COUNTERS = ("executor_jax_trace_seconds_total",
            "executor_jax_lower_seconds_total")


def read(record, trace, cell):
    series = [program_spans.counter_series(name) for name in COUNTERS]
    if any(s is None for s in series):
        return None
    return sum(row["value"] for s in series for row in s)
