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
    have it.  ``between_steps(now, engine)`` runs after every step (the
    harness reads counters and turns the tracer on and off there)."""
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
            closed_at, queue_end = t, len(engine.waiting)
        if t >= deadline or (i >= len(planned) and not engine.has_work()
                             and closed_at is not None):
            break
        if not engine.has_work():
            nxt = planned[i].due if i < len(planned) else window_s
            with span("sleep"):
                sleep(max(nxt - now(), 0.0))
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
    return {"window_s": window_s, "closed_at": closed_at, "ended_at": now(),
            "steps": steps, "queue_half": queue_half, "queue_end": queue_end,
            "requests": list(planned)}


class _NoSpan:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _no_span(_name):
    return _NoSpan()


# ---------------------------------------------------------------------------
# per-request reduction
# ---------------------------------------------------------------------------
def request_table(raw: dict, admitted_at: Callable[[Planned], Optional[float]]
                  ) -> List[dict]:
    """One row per request that was due inside the window.  A preempted
    request's tokens are those of its final run (the engine resets
    ``out_tokens``), so only the last ``n_out`` stamps count."""
    rows = []
    for p in raw["requests"]:
        if not p.measured(raw["window_s"]):
            continue
        n_out = len(getattr(p.handle, "out_tokens", p.token_times))
        times = p.token_times[-n_out:] if n_out else []
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
