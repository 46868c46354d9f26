"""Latency statistics, copied from the engine's ``core/metrics.py`` so that
no change to the program can move the yardstick.

Percentiles interpolate linearly between order statistics (numpy's
default), over every sample given: the tail of all queries, not of chunks.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


@dataclasses.dataclass
class LatencyStats:
    p50: float
    p95: float
    p99: float
    max: float
    mean: float
    n: int


def latency_stats(samples_s: List[float]) -> LatencyStats:
    a = np.asarray(samples_s, dtype=np.float64)
    return LatencyStats(
        p50=float(np.percentile(a, 50)),
        p95=float(np.percentile(a, 95)),
        p99=float(np.percentile(a, 99)),
        max=float(a.max()),
        mean=float(a.mean()),
        n=len(a),
    )
