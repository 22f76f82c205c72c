"""The arithmetic of the latency metrics, apart from any clock so that
``grid/tests`` can pin it on hand-made records."""

from __future__ import annotations

import statistics
from typing import List, Optional, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics; ``q == 50`` is the median."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def mean_gap_ms(stamps: Sequence[Tuple[float, int]], min_tokens: int
                ) -> Optional[float]:
    """One request's MEAN gap between output tokens, in ms.

    ``stamps`` are (clock in seconds, tokens emitted so far), one for each
    engine cycle in the window after which the count had grown, the first
    being the cycle that brought the first token. The mean gap is the time
    from the first stamp to the last over the tokens emitted between them:
    gaps of 45, 45, 66 and 45 ms read 50.25, not the 45 a median over
    single gaps would give. None for a request that showed fewer than
    ``min_tokens`` tokens in the window: too few gaps for a mean.
    """
    if not stamps or stamps[-1][1] < min_tokens:
        return None
    (t0, n0), (t1, n1) = stamps[0], stamps[-1]
    if n1 <= n0:
        return None
    return (t1 - t0) * 1e3 / (n1 - n0)


def request_gaps_ms(all_stamps: Sequence[Sequence[Tuple[float, int]]],
                    min_tokens: int) -> List[float]:
    """:func:`mean_gap_ms` of every request that has one."""
    gaps = (mean_gap_ms(s, min_tokens) for s in all_stamps)
    return [g for g in gaps if g is not None]


def spread(values: Sequence[float]) -> float:
    """The contract's spread: the distance between the first and the third
    quartile (``statistics.quantiles(values, n=4)``) over the median."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)
