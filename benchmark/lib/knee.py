"""The knee rule: the highest offered rate the system sustained in a sweep."""
from __future__ import annotations

from typing import Optional, Sequence


ADMITTED_SHARE = 0.98


def sustained(row: dict) -> bool:
    """One row of a sweep table sustained its rate when, at the end of the
    window, the waiting queue is no longer than at half-time or, if it is,
    still holds no more than ``1 - ADMITTED_SHARE`` of the requests that had
    come due by then (and at least one: a single request waiting for the next
    step is not a backlog).

    The issue asked for *completed* >= 0.98 x due over the window, and a
    queue no longer at the end than at half-time.  A request of this traffic
    lives 7 to 50 s (64 to 256 tokens at 0.1 to 0.2 s a step), so completions
    in a window lag its arrivals by that much and by the Poisson noise of
    both: at 1.5 requests/s, with an empty queue all through, 19 of 26 had
    completed; and at 2.44 requests/s one request waiting at the closing
    instant against none at half-time read as growth, with 3.05 and 3.81
    sustained above it (my chip runs, PR 23).  Admission has no such lag,
    and the allowance takes the single waiter out."""
    allowance = max(1.0, (1.0 - ADMITTED_SHARE) * row["due_by_end"])
    return row["queue_end"] <= max(row["queue_half"], allowance) \
        and row["queue_end"] <= 2 * allowance


def knee(rows: Sequence[dict]) -> Optional[float]:
    """The highest rate of an ascending sweep below which every rate was
    sustained; None where even the lowest was not.  A rate that is sustained
    above one that was not does not count: past the knee a lucky draw is not
    capacity."""
    best = None
    for row in sorted(rows, key=lambda r: r["rate_per_s"]):
        if not sustained(row):
            break
        best = row["rate_per_s"]
    return best
