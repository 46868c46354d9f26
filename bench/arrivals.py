"""Seeded open-loop arrival times, copied from the engine's ``core/slo.py``
so that no change to the program can move the yardstick.

A traffic mix with an ``"arrivals"`` entry is served open loop: its
queries arrive at these times, whether or not earlier ones have finished.
With only ``rate_qps`` the process is Poisson; with ``phases``, a list of
``[duration_s, rate_qps]`` segments cycled over the run, it is
piecewise-constant (bursts).  The same seed gives the same schedule.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np


class ArrivalProcess:
    def __init__(self, rate_qps: float = 1.0,
                 phases: Optional[Sequence[Tuple[float, float]]] = None,
                 seed: int = 0):
        if phases is not None:
            phases = [(float(d), float(r)) for d, r in phases]
            if not phases:
                raise ValueError("phases must be non-empty when given")
            for d, r in phases:
                if d <= 0:
                    raise ValueError(f"phase duration must be positive, got {d}")
                if r < 0:
                    raise ValueError(f"phase rate must be >= 0, got {r}")
        elif rate_qps < 0:
            raise ValueError(f"rate_qps must be >= 0, got {rate_qps}")
        self.rate_qps = float(rate_qps)
        self.phases = phases
        self.seed = int(seed)

    def times(self, duration_s: float, max_n: int = 1_000_000) -> np.ndarray:
        """Sorted arrival offsets in ``[0, duration_s)``."""
        rng = np.random.default_rng(self.seed)
        phases = (list(self.phases) if self.phases is not None
                  else [(float(duration_s) or 1.0, self.rate_qps)])
        out = []
        seg_start = 0.0
        i = 0
        while seg_start < duration_s:
            dur, rate = phases[i % len(phases)]
            i += 1
            seg_end = min(float(duration_s), seg_start + dur)
            if rate > 0:
                t = seg_start
                while True:
                    t += rng.exponential(1.0 / rate)
                    if t >= seg_end:
                        break
                    out.append(t)
                    if len(out) > max_n:
                        raise ValueError(
                            f"arrival process exceeded max_n={max_n} "
                            f"arrivals before t={t:.1f}s; check the rate")
            seg_start = seg_end
        return np.asarray(out, dtype=np.float64)
