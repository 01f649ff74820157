"""Step execution: the share of the traced window in which no operation ran
on device 0: 1 - (union of its operation intervals / window)."""


def read(record, trace, cell):
    if not trace:
        return None
    return 100.0 * trace["idle_share"]
