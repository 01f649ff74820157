"""Step execution: the median time of one optimizer step over the window, on
the host's clock, from the same step ends the rate is taken from.  The rate
counts every stall of the host inside the window; the median of some three
hundred steps does not, so it stands beside the rate as the steadier figure
(each step's own timing is off by up to half a millisecond; the median is not).
"""
import statistics


def read(record, trace, cell):
    ends = record.get("step_ends")
    if not ends or len(ends) < 2:
        return None
    starts = [0.0] + ends[:-1]
    return 1e3 * statistics.median(b - a for a, b in zip(starts, ends))
