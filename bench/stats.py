"""Percentiles and the latency samples of one run.

Every tail is over all requests or all gaps, with nothing dropped: a request
due in the window that has no first token when the window closes counts the
wait it has had so far.
"""

from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float | None:
    """The q-th percentile (linear interpolation between order statistics,
    numpy's default); None for no values."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def ttft_samples(requests, t_open: float, t_end: float,
                 t_stop: float | None = None) -> list[float]:
    """Due time to first token of every request due in [t_open, t_end); one
    unanswered at ``t_stop`` (by default t_end; later where the run followed
    the window's requests on to their first tokens) counts its wait until
    then. ``requests`` have ``due`` (absolute seconds) and ``times`` (the
    emission time of each token)."""
    stop = t_end if t_stop is None else t_stop
    out = []
    for r in requests:
        if not (t_open <= r.due < t_end):
            continue
        first = r.times[0] if r.times else None
        out.append((first if first is not None and first < stop else stop) - r.due)
    return out


def itl_samples(requests, t_open: float, t_end: float) -> list[float]:
    """Every gap between consecutive tokens of one request, both emitted in
    [t_open, t_end)."""
    out = []
    for r in requests:
        ts = [t for t in r.times if t_open <= t < t_end]
        out.extend(b - a for a, b in zip(ts, ts[1:]))
    return out


def tokens_in(requests, t_open: float, t_end: float) -> int:
    return sum(1 for r in requests for t in r.times if t_open <= t < t_end)
