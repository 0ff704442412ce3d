"""Order statistics of the benchmark.

``percentile`` is a copy of ``repro.core.serve.percentile`` (linear
interpolation between the two nearest ranks), kept here so that a change
to the program cannot change how the benchmark reads a tail.
"""

from __future__ import annotations

import math

__all__ = ["percentile", "mean"]


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile of ``values``; ``q`` in [0, 100]."""
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile q must be in [0, 100], got {q!r}")
    if not values:
        return math.nan
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(v) - 1)
    frac = pos - lo
    return v[lo] * (1 - frac) + v[hi] * frac


def mean(values) -> float | None:
    """Arithmetic mean, or None for an empty sequence."""
    values = list(values)
    return sum(values) / len(values) if values else None
