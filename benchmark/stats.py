"""Arithmetic of the benchmark's own: percentiles, rates over a window, spreads.

Nothing here reads the program. ``percentile`` is the nearest-rank rule
(the smallest sample with at least q of the samples at or below it), so a
p95 over n requests is a request that happened, not an interpolation.
"""

from __future__ import annotations

import math
import statistics
from typing import Iterable, Optional, Sequence


def percentile(samples: Sequence[float], q: float) -> float:
    """Nearest-rank percentile, q in (0, 100]. Raises on no samples."""
    if not samples:
        raise ValueError("percentile of no samples")
    if not 0.0 < q <= 100.0:
        raise ValueError(f"q must be in (0, 100], got {q}")
    xs = sorted(samples)
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return float(xs[rank - 1])


def latency_percentile_ms(latencies_s: Iterable[Optional[float]], q: float) -> float:
    """Percentile over ALL requests; a failed one (None) counts as the worst
    that was seen, so failures can only push the tail up."""
    lat = list(latencies_s)
    ok = [x for x in lat if x is not None]
    if not ok:
        raise ValueError("no request finished")
    worst = max(ok)
    return 1e3 * percentile([worst if x is None else x for x in lat], q)


def rate_per_s(amount: float, t_start: float, t_end: float) -> float:
    """Work over all the time of the window (a stall inside it counts)."""
    if t_end <= t_start:
        raise ValueError("window has no length")
    return amount / (t_end - t_start)


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile over the median, by
    ``statistics.quantiles(values, n=4)`` as the driver reads it."""
    q = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q[2] - q[0]) / abs(med) if med else float("inf")
