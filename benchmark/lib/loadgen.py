"""The open-loop replay of a traffic plan, and its per-request reduction.

Repaired copy of ``paddle_tpu/utils/loadgen.py`` (the yardstick may not live
in the program).  What the copy changes: time to first token and the gaps
between tokens are kept apart, per request; a request that fails is counted
against the attempts; how late the generator ran is recorded per request; an
idle generator sleeps until the next request is due, not in 50 ms slices; and
every time is measured from the instant a request was *due*, so a stalled
engine cannot hide the wait it imposes on later requests.

The plan itself (arrival times, lengths, tokens) is drawn by the generator
the traffic file names, ``benchmark/generators/<name>.py``.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence


@dataclass
class Planned:
    """One request of a traffic plan; ``due`` is in seconds from the opening
    of the measured window (negative: lead-in)."""
    req_id: int
    due: float
    prompt: List[int]
    want: int
    # filled by the replay
    submitted: Optional[float] = None
    refused: Optional[str] = None
    token_times: List[float] = field(default_factory=list)
    finished: Optional[float] = None
    handle: object = None

    def measured(self, window_s: float) -> bool:
        return 0.0 <= self.due < window_s


# ---------------------------------------------------------------------------
# replay
# ---------------------------------------------------------------------------
def replay(engine, planned: Sequence[Planned], window_s: float,
           drain_s: float, make_request: Callable[[Planned, float], object],
           clock: Callable[[], float] = time.perf_counter,
           sleep: Callable[[float], None] = time.sleep,
           span=None, between_steps: Optional[Callable] = None) -> dict:
    """Offer ``planned`` to ``engine`` on its schedule and step the engine
    whenever it has work, in one thread.  ``engine`` has ``submit(request)``
    (raising ``ValueError`` on a refusal), ``step(now)`` returning events with
    ``req_id``, ``token`` and ``finished``, ``has_work()`` and ``waiting``.

    The clock's zero is the opening of the window: the replay starts at the
    first request's due time (the lead-in) and ends when everything due has
    finished or ``drain_s`` after the window closed.  A token's time is the
    clock after the step that made it returned, which is when a client could
    have it.  ``between_steps(now, engine)`` runs after every step and, where
    the engine has nothing to do, before and after the sleep (the harness
    reads counters and turns the tracer on and off there, whether or not a
    step ran).

    ``opened_at`` and ``closed_at`` bound what the window's rate counts (see
    ``delivered``): both are the end of a step or an instant at which the
    engine was idle, so the interval between them holds whole steps only.  A
    step astride 0 is left out whole (``opened_at`` is its end); the step
    astride ``window_s`` is kept whole (``closed_at`` is the end of the first
    step that ends at or after ``window_s``, or the first look at the clock
    past ``window_s`` where no step was running)."""
    span = span or _no_span
    by_id = {p.req_id: p for p in planned}
    first_due = planned[0].due if planned else 0.0
    t_zero = clock() - min(first_due, 0.0)     # clock value at window open
    now = lambda: clock() - t_zero             # noqa: E731
    i, steps, queue_half, queue_end, closed_at = 0, [], None, None, None
    deadline = window_s + drain_s
    while True:
        t = now()
        with span("submit"):
            while i < len(planned) and planned[i].due <= t:
                p = planned[i]
                p.submitted = now()
                p.handle = make_request(p, p.due)
                try:
                    engine.submit(p.handle)
                except ValueError as e:
                    p.refused = str(e)
                i += 1
        if queue_half is None and t >= window_s / 2:
            queue_half = len(engine.waiting)
        if closed_at is None and t >= window_s:
            # the closing step's own end, not the clock after the hook that
            # followed it (which, traced, stops the profiler for a second)
            closed_at = steps[-1][1] if steps and steps[-1][1] >= window_s \
                else t
            queue_end = len(engine.waiting)
        if t >= deadline or (i >= len(planned) and not engine.has_work()
                             and closed_at is not None):
            break
        if not engine.has_work():
            nxt = planned[i].due if i < len(planned) else window_s
            if between_steps is not None:
                between_steps(t, engine)
            with span("sleep"):
                sleep(max(nxt - now(), 0.0))
            if between_steps is not None:
                between_steps(now(), engine)
            continue
        t0 = now()
        with span("step"):
            events = engine.step(t0)
        t1 = now()
        steps.append((t0, t1))
        for ev in events:
            p = by_id[ev.req_id]
            p.token_times.append(t1)
            if ev.finished:
                p.finished = t1
        if between_steps is not None:
            between_steps(t1, engine)
    return {"window_s": window_s, "opened_at": opened_at(steps),
            "closed_at": closed_at, "ended_at": now(), "steps": steps,
            "queue_half": queue_half, "queue_end": queue_end,
            "requests": list(planned)}


def opened_at(steps: Sequence[tuple]) -> float:
    """Where the counted interval opens: the end of the step that was running
    at 0, or 0 itself where the engine was idle then."""
    for t0, t1 in steps:
        if t1 >= 0.0:
            return t1 if t0 < 0.0 else 0.0
    return 0.0


class _NoSpan:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _no_span(_name):
    return _NoSpan()


# ---------------------------------------------------------------------------
# the window's tokens
# ---------------------------------------------------------------------------
def final_stamps(p: Planned) -> List[float]:
    """The stamps of the tokens a request ended up with.  A preempted
    request's tokens are those of its final run (the engine resets
    ``out_tokens``), so only the last ``n_out`` stamps count."""
    n_out = len(getattr(p.handle, "out_tokens", p.token_times))
    return p.token_times[-n_out:] if n_out else []


def delivered(raw: dict) -> dict:
    """Tokens the steps that returned in ``(opened_at, closed_at]`` handed to
    clients: an output token at its stamp, a prompt's tokens (the client's
    count, so a prefix cache cannot shrink it) at the stamp of the request's
    first output token, which is when the prefill that read them returned.
    A refused or failed request has no stamp and delivers nothing."""
    lo, hi = raw["opened_at"], raw["closed_at"]
    tokens = 0
    for p in raw["requests"]:
        stamps = final_stamps(p)
        if not stamps:
            continue
        tokens += sum(1 for t in stamps if lo < t <= hi)
        if lo < stamps[0] <= hi:
            tokens += len(p.prompt)
    return {"delivered_tokens": tokens, "opened_at": lo, "closed_at": hi,
            "delivered_tokens_per_s": tokens / (hi - lo)}


def completed(raw: dict) -> List[Planned]:
    """The requests that finished in ``[0, closed_at]``."""
    return [p for p in raw["requests"] if p.finished is not None
            and 0.0 <= p.finished <= raw["closed_at"]]


def completed_tokens_per_s(raw: dict) -> float:
    """The reading before PR 49, kept as a note for one PR: prompt plus
    output tokens of the requests that finished in ``[0, closed_at]``, over
    ``closed_at``.  Its grain is a whole request."""
    return sum(len(p.prompt) + len(p.handle.out_tokens)
               for p in completed(raw)) / raw["closed_at"]


def window_note(raw: dict) -> dict:
    """What a serving runner's note line says of the window's count: the
    metric's reading with its edges, and the old reading beside it with the
    completions it counts (what a backlog's depth is sized from)."""
    return dict(delivered(raw), completed_requests=len(completed(raw)),
                completed_tokens_per_s=completed_tokens_per_s(raw))


# ---------------------------------------------------------------------------
# per-request reduction
# ---------------------------------------------------------------------------
def token_gaps(raw: dict) -> List[float]:
    """Every gap between two successive output tokens of every request that
    was due inside the window, pooled: the first token's wait is not a gap.
    A failed or refused request has no tokens and so no gap."""
    return [b - a for p in raw["requests"] if p.measured(raw["window_s"])
            for times in (final_stamps(p),)
            for a, b in zip(times, times[1:])]


def latency_note(raw: dict, rows: Sequence[dict]) -> dict:
    """What an open-loop runner's note line says of the window's latencies
    beside the metrics: the middle, the 90th percentile and the mean of the
    three populations a latency can be read from (first-token times and mean
    gaps a request, ``rows``; gaps a token, pooled), so that every run shows
    how far each statistic moves from run to run.  Empty where nothing was
    due."""
    from .stats import percentile

    def of(values, name):
        values = [v for v in values if v is not None]
        if not values:
            return {}
        return {f"{name}_p50_ms": 1e3 * percentile(values, 50),
                f"{name}_p90_ms": 1e3 * percentile(values, 90),
                f"{name}_mean_ms": 1e3 * sum(values) / len(values)}
    return {**of([r["ttft_s"] for r in rows], "ttft"),
            **of([r["mean_gap_s"] for r in rows], "itl"),
            **of(token_gaps(raw), "gap")}


def request_table(raw: dict, admitted_at: Callable[[Planned], Optional[float]]
                  ) -> List[dict]:
    """One row per request that was due inside the window, its tokens those
    of its final run (``final_stamps``)."""
    rows = []
    for p in raw["requests"]:
        if not p.measured(raw["window_s"]):
            continue
        times = final_stamps(p)
        n_out = len(times)
        first = times[0] if times else None
        gaps = [b - a for a, b in zip(times, times[1:])]
        adm = admitted_at(p)
        rows.append({
            "req_id": p.req_id, "due": p.due, "prompt_len": len(p.prompt),
            "want": p.want, "n_out": n_out, "refused": p.refused,
            "lag_s": None if p.submitted is None else p.submitted - p.due,
            "queue_wait_s": None if adm is None else adm - p.due,
            "ttft_s": None if first is None else first - p.due,
            "mean_gap_s": sum(gaps) / len(gaps) if gaps else None,
            "finished": p.finished,
            "failed": p.refused is not None or first is None,
            "waited_s": raw["ended_at"] - p.due,
        })
    return rows
