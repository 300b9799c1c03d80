"""Window arithmetic of the benchmark: time-to-solution (paper Eq. 32),
percentiles and spreads. Plain Python, so a test can pin every edge case.

Eq. 32 is copied here (not imported from the program's ``core/tts.py``) so
that no change to the program can move the yardstick.
"""
from __future__ import annotations

import math
import statistics
from typing import Sequence

#: The success probability TTS is quoted at (TTS(0.99)).
TTS_TARGET = 0.99


def smoothed_success(hits: int, trials: int) -> float:
    """Pooled replica success share p = (hits + 1/2) / (trials + 1).

    The half keeps a run with no hit at a finite TTS and a run with every
    replica a hit below 1, so TTS stays a number either way."""
    if trials < 0 or hits < 0 or hits > trials:
        raise ValueError(f"need 0 <= hits <= trials, got {hits}/{trials}")
    return (hits + 0.5) / (trials + 1.0)


def tts(time_per_solve: float, p_replica: float, replicas: int,
        target: float = TTS_TARGET) -> float:
    """Eq. 32: TTS = t_a · ln(1 − target) / ln(1 − P), where one solve runs
    ``replicas`` independent replicas, each succeeding with ``p_replica``,
    so P = 1 − (1 − p)^R. A solve that already succeeds with P ≥ target
    needs one run: TTS = t_a."""
    if not 0.0 < target < 1.0:
        raise ValueError(f"target must lie in (0, 1), got {target}")
    if not 0.0 < p_replica < 1.0:
        raise ValueError(f"p_replica must lie in (0, 1), got {p_replica}")
    if replicas < 1:
        raise ValueError(f"replicas must be >= 1, got {replicas}")
    log_miss = replicas * math.log1p(-p_replica)      # ln(1 − P)
    if -math.expm1(log_miss) >= target:                # P ≥ target
        return time_per_solve
    return time_per_solve * math.log1p(-target) / log_miss


def percentile(values: Sequence[float], q: int) -> float:
    """The q-th percentile (1 ≤ q ≤ 99) of all ``values``, linearly
    interpolated between order statistics (``statistics.quantiles``,
    inclusive method). Taken over every sample given: callers pass every
    solve of the window, never per-chunk or per-batch summaries."""
    if not 1 <= q <= 99:
        raise ValueError(f"q must lie in [1, 99], got {q}")
    vals = list(values)
    if len(vals) < 2:
        raise ValueError(f"a percentile needs at least 2 samples, got "
                         f"{len(vals)}")
    return statistics.quantiles(vals, n=100, method="inclusive")[q - 1]


def spread(values: Sequence[float]) -> float:
    """Inter-quartile distance over the median (``statistics.quantiles``,
    n=4, default method) — the spread the bounds are set from."""
    q1, med, q3 = statistics.quantiles(list(values), n=4)
    return (q3 - q1) / abs(med)
