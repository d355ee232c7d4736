"""Window arithmetic: rates over a window that closes on a completion, and
latency tails over every sample due in the window.

All times are seconds on one host clock, relative to the window's open.
"""

from __future__ import annotations

import statistics
from typing import Iterable, Optional, Sequence, Tuple


def close_on_completion(done: Iterable[Tuple[float, int]], seconds: float
                        ) -> Optional[Tuple[float, int]]:
    """(close, units) for completions (t_done, units): the window closes at
    the first completion at or after `seconds`, and counts every unit that
    completed by then.  None when nothing completed at or after `seconds`
    (the window never closed)."""
    done = sorted(done)
    close = next((t for t, _ in done if t >= seconds), None)
    if close is None:
        return None
    return close, sum(n for t, n in done if t <= close)


def rate(done: Iterable[Tuple[float, int]], seconds: float) -> Optional[float]:
    """Units per second over the window that closes on a completion."""
    got = close_on_completion(done, seconds)
    if got is None or got[0] <= 0:
        return None
    close, units = got
    return units / close


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0 < q < 100), linear between order statistics
    (numpy's default), over every value given."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of nothing")
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    if pos == lo or v[hi] == v[lo]:
        return v[lo]
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def latency_tail(due: Sequence[float], done: Sequence[Optional[float]], q: float
                 ) -> float:
    """q-th percentile of done - due over every sample due in the window; a
    sample that failed or never completed counts as infinitely late."""
    lat = [(d - s) if d is not None else float("inf") for s, d in zip(due, done)]
    return percentile(lat, q)


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartiles as a share of the
    median (statistics.quantiles, n=4)."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
