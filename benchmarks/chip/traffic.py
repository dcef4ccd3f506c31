"""The benchmark's one traffic generator: a day of jobs from a mix file.

It is an independent copy of the paper's workload model (arXiv 2606.25082
Sec. V-A, the repo's ``repro.core.workload``): arrivals by thinning a
non-homogeneous Poisson process whose hourly rate is the mix's Fig. 5
curve times ``load_scale``, then per job, in this draw order: kind,
duration, elasticity class (and its cap or curve), deadline slack.  The
draw order is what makes its days equal to the program's for one seed, so
a changed program generator shows as ``jobs_differ`` in a run's check.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable, List

import numpy as np


@dataclass(frozen=True)
class Day:
    """One rollout's jobs in arrival order (float64, unpadded)."""

    arrival: np.ndarray
    deadline: np.ndarray
    work: np.ndarray
    rates: np.ndarray  # (n, max_slots + 1): work depleted per minute on a k-slot slice

    @property
    def num_jobs(self) -> int:
        return int(self.arrival.shape[0])


def _curve(mix: dict, klass: int, rng: np.random.Generator) -> Callable[[float], float]:
    if klass == 0:
        return lambda k: k
    if klass == 1:
        cap = float(int(rng.choice(mix["capped_at"])))
        return lambda k: min(k, cap)
    kind, a = mix["sublinear"][int(rng.integers(0, len(mix["sublinear"])))]
    if kind == "exp":
        denom = 1.0 - math.exp(-a)
        return lambda k: (1.0 - math.exp(-a * k)) / denom
    return lambda k: 1.0 + a * math.log2(k) if k >= 1.0 else k


def _rate(mix: dict, t: float) -> float:
    pattern = mix["rate_per_min_by_hour"]
    hod = (t / 60.0) % 24.0
    lo = int(hod) % 24
    frac = hod - int(hod)
    return pattern[lo] * (1.0 - frac) + pattern[(lo + 1) % 24] * frac


def generate_day(mix: dict, seed: int, max_slots: int, mig_enabled: bool = True) -> Day:
    """The day the mix gives for ``seed``."""
    rng = np.random.default_rng(seed)
    load = mix["load_scale"]
    horizon = mix["horizon_min"]
    lam_max = max(mix["rate_per_min_by_hour"]) * load
    arrivals: List[float] = []
    t = 0.0
    while True:
        t += rng.exponential(1.0 / lam_max)
        if t >= horizon:
            break
        if rng.uniform() * lam_max <= _rate(mix, t) * load:
            arrivals.append(t)

    n = len(arrivals)
    deadline = np.empty(n)
    work = np.empty(n)
    rates = np.zeros((n, max_slots + 1))
    lo_t, hi_t = mix["training_min"]
    lo_s, hi_s = mix["deadline_slack"]
    for i, a in enumerate(arrivals):
        if rng.uniform() < mix["inference_share"]:
            w = max(rng.exponential(mix["inference_mean_min"]), mix["inference_floor_min"])
        else:
            w = rng.uniform(lo_t, hi_t)
        klass = int(rng.integers(0, 3))
        tp = _curve(mix, klass, rng)
        slack = rng.uniform(lo_s, hi_s)
        deadline[i] = a + slack * (w / tp(7.0))
        work[i] = w
        speed = 1.0 if mig_enabled or klass != 0 else mix["linear_no_mig_speedup"]
        for k in range(1, max_slots + 1):
            rates[i, k] = tp(float(k)) * speed
    return Day(np.asarray(arrivals, dtype=float), deadline, work, rates)
