"""Compile: seconds in XLA:TPU and Mosaic during set-up, from
``jax.monitoring``'s backend-compile durations (0 where every program was
read from the persistent cache)."""


def read(record, trace, cell):
    return record["setup_counters"]["backend_compile_s"]
