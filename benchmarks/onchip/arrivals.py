"""The one traffic generator: reads a traffic file's parameters.

A traffic file (``traffic/<name>.json``) is one of two loops:

* ``{"loop": "closed", "callers": C}``: C callers, each with one request
  outstanding; a caller sends its next request when its answer arrives.
* ``{"loop": "open", "states": [...]}``: arrivals on a schedule that does
  not wait for answers. One state is a Poisson process; several are a
  Markov-modulated Poisson process that cycles through them (calm,
  burst, calm, ...). Each state gives ``rate_x_knee``, its rate as a
  multiple of the configuration's ``knee_per_s``, and ``mean_dwell_s``.

Every seed gets the same set of dwell times and the same set of gaps
between arrivals, in an order drawn from the seed: exponential
quantiles, scaled to fill the window exactly. So every run offers the
same amount of work and the same burst lengths, and the seed changes
only where they fall. (The arrival model follows the seeded Poisson
generator of ``benchmarks/serve_slo.py``.)

Both loops also name ``buckets`` (``"full"``: the engine's full batch
only; ``"auto"``: its power-of-two ladder) and ``pool``, the number of
seeded images the requests cycle through.
"""
from __future__ import annotations

import numpy as np


def exp_quantiles(n: int, total: float) -> np.ndarray:
    """``n`` exponential quantiles at the mid-ranks, scaled to sum to
    ``total``."""
    q = -np.log1p(-(np.arange(n) + 0.5) / n)
    return q * (total / q.sum())


def open_schedule(states: list[dict], knee_per_s: float, seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Sorted due times, in seconds from the window's start, in
    ``[0, seconds)``."""
    if len(states) == 1:
        periods = [(0, 0.0, seconds)]
    else:
        cycle = sum(s["mean_dwell_s"] for s in states)
        n_cycles = max(1, round(seconds / cycle))
        dwells = [rng.permutation(exp_quantiles(
            n_cycles, seconds * s["mean_dwell_s"] / cycle)) for s in states]
        periods, t = [], 0.0
        for c in range(n_cycles):
            for i in range(len(states)):
                periods.append((i, t, dwells[i][c]))
                t += dwells[i][c]
    due = []
    for i, state in enumerate(states):
        mine = [(start, length) for j, start, length in periods if j == i]
        span = sum(length for _, length in mine)
        n = round(state["rate_x_knee"] * knee_per_s * span)
        if n == 0:
            continue
        gaps = rng.permutation(exp_quantiles(n, span))
        local = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        # lay the state's own time end to end over its periods
        ends = np.cumsum([length for _, length in mine])
        idx = np.minimum(np.searchsorted(ends, local, side="right"),
                         len(mine) - 1)
        starts = np.array([s for s, _ in mine])
        offset = local - np.concatenate([[0.0], ends[:-1]])[idx]
        due.append(starts[idx] + offset)
    out = np.sort(np.concatenate(due)) if due else np.zeros(0)
    return out[out < seconds]

