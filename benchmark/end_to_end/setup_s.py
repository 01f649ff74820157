"""Process start to the first measured step or request: import, program
build, compile (or cache read), weights from the seed, warm-up of the cell's
own shapes and, where the traffic asks for it, the lead-in."""


def read(record, cell):
    return record["setup_s"]
