"""Compile: programs the persistent cache did not hold during set-up, from
``jax.monitoring``.  After a cell's first run in a checkout it should be 0."""


def read(record, trace, cell):
    return record["setup_counters"]["cache_misses"]
