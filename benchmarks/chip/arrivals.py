"""The one traffic generator: a cell's requests from its traffic file.

A traffic file (``traffic/<name>.json``) gives the offered rate, the mix
of prompt lengths and of output lengths as ``[[tokens, share], ...]``, and
how many served tokens the correctness check samples.  From it this module
makes a fixed schedule: ``n = round(rate * seconds)`` requests whose
lengths are the mix's shares of ``n`` (largest remainder) and whose ``n -
1`` inter-arrival gaps are the midpoint quantiles of the exponential
distribution (stratified exponential gaps), scaled to mean ``1/rate``.
Lengths and gaps are shuffled by a fixed key, not by the run's seed, so
every seed sends the same requests at the same times: where a burst falls
does not move the tails from seed to seed.  The seed draws the weights and
the prompts' token ids (``deploy.prompts``).

Arrivals are open-loop: request ``i`` is due ``arrival`` seconds after the
window opens, whether or not earlier requests have finished.  The first
is due at 0, the last at ``(n - 1) / rate``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

__all__ = ["Request", "counts", "exponential_gaps", "make_requests"]


@dataclass(frozen=True)
class Request:
    index: int
    arrival: float          # seconds after the window opens
    prompt_len: int
    output_len: int


def counts(mix, n: int) -> list[int]:
    """Split ``n`` over ``mix`` ([[value, share], ...]) by largest
    remainder, so the counts sum to ``n`` exactly."""
    total = sum(share for _, share in mix)
    raw = [n * share / total for _, share in mix]
    out = [int(math.floor(r)) for r in raw]
    by_rest = sorted(range(len(mix)), key=lambda j: out[j] - raw[j])
    for j in by_rest[:n - sum(out)]:
        out[j] += 1
    return out


def _expand(mix, n: int) -> list[int]:
    return [value for (value, _), c in zip(mix, counts(mix, n))
            for _ in range(c)]


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """The ``n`` midpoint quantiles of Exp(rate), rescaled to mean
    ``1/rate`` exactly."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    return gaps / gaps.mean() / rate


SCHEDULE_KEY = 0


def make_requests(traffic: dict, seconds: float) -> list[Request]:
    rate = float(traffic["rate_per_s"])
    if rate <= 0 or seconds <= 0:
        raise ValueError("rate and seconds must be positive")
    n = max(1, round(rate * seconds))
    rng = np.random.default_rng([SCHEDULE_KEY, 1])
    prompts = rng.permutation(_expand(traffic["prompt_tokens"], n))
    outputs = rng.permutation(_expand(traffic["output_tokens"], n))
    gaps = rng.permutation(exponential_gaps(rate, n - 1)) if n > 1 else []
    arrivals = np.concatenate([[0.0], np.cumsum(gaps)])
    return [Request(i, float(arrivals[i]), int(prompts[i]), int(outputs[i]))
            for i in range(n)]
