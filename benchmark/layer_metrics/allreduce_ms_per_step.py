"""Step execution, several chips: summed duration of the all-reduce
operations on device 0 over the traced steps.  It says how much all-reduce
there is, not how much of it is exposed."""
import re

PATTERN = re.compile(r"all-reduce")


def read(record, trace, cell):
    steps = record.get("traced_steps")
    if not trace or not steps:
        return None
    seconds = sum(s for name, s in trace["ops"].items()
                  if PATTERN.search(name))
    return 1e3 * seconds / steps
