"""Generator ``open_loop``: seeded open-loop arrivals of independent requests.

A traffic file names its generator (``"generator": "open_loop"``) and the
runner imports ``benchmark/generators/<name>.py`` and calls its ``plan``.  A
mix this one cannot draw (bursts, sessions over a shared prefix, repeated
n-grams) brings a generator file of its own; none that is here is edited.

Parameters read from the traffic file: ``arrivals`` (``poisson`` with
``rate_per_s``, or ``at_once`` with ``count``), ``prompt_len`` and
``output_len`` (``lognormal`` with ``median`` and ``sigma``, or ``uniform``,
both clipped to ``[min, max]``), ``lead_in_s`` and ``population_seed``.

Arrival times and lengths are drawn from ``population_seed``; ``--seed`` draws
the token values (and, in the runner, the weights).  So every seed offers one
schedule of the same sizes, and a tail measured under it is the tail of that
one schedule.  An earlier draft let ``--seed`` permute the order: on the chip
the 95th percentile of first-token time then ranged over 16 % across seeds
against 2.5 % between two runs of one seed (my chip runs, PR 23), and a tail
that the order of arrivals moves that far cannot be held to a bound of 10 %.
"""
from __future__ import annotations

import math
from typing import List

import numpy as np

from benchmark.lib.loadgen import Planned


def draw_lengths(spec: dict, rng: np.random.RandomState, n: int) -> np.ndarray:
    """``n`` whole lengths from ``{"dist": lognormal|uniform, ...}``, clipped
    to ``[min, max]``."""
    dist = spec["dist"]
    if dist == "lognormal":
        x = np.exp(rng.normal(math.log(spec["median"]), spec["sigma"], n))
    elif dist == "uniform":
        x = rng.randint(spec["min"], spec["max"] + 1, n).astype(float)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def arrival_times(arrivals: dict, rng: np.random.RandomState, start: float,
                  end: float) -> np.ndarray:
    """Due instants in ``[start, end)``: everything at ``start``, or a
    Poisson process of ``rate_per_s`` from ``start`` on."""
    if arrivals["process"] == "at_once":
        return np.full(int(arrivals["count"]), start)
    if arrivals["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    out, t = [], start
    while True:
        t += float(rng.exponential(1.0 / float(arrivals["rate_per_s"])))
        if t >= end:
            return np.asarray(out)
        out.append(t)


def plan(traffic: dict, seed: int, window_s: float, vocab: int,
         max_context: int) -> List[Planned]:
    """The requests of one run, by due time, over ``[-lead_in_s, window_s)``.
    A prompt is cut where prompt plus output would outgrow the context."""
    pop = np.random.RandomState(int(traffic["population_seed"]))
    rng = np.random.RandomState(seed % (2 ** 32))
    due = arrival_times(traffic["arrivals"], pop,
                        -float(traffic["lead_in_s"]), window_s)
    prompts = draw_lengths(traffic["prompt_len"], pop, len(due))
    wants = draw_lengths(traffic["output_len"], pop, len(due))
    out = []
    for i, (t, n, want) in enumerate(zip(due, prompts, wants)):
        n = min(int(n), max_context - int(want))
        out.append(Planned(i, float(t),
                           rng.randint(0, vocab, size=n).astype(int).tolist(),
                           int(want)))
    return out
