"""Workload generation (paper §V-A).

Jobs arrive by a non-homogeneous Poisson process whose rate follows the
diurnal pattern derived from the Alibaba MLaaS traces (Fig. 5): low overnight,
ramping from ~3:00, peak 5:00–17:00, falling to the overnight level by ~19:00.

Per-job attributes (trace does not include them; §V-A assumptions):
* kind: inference w.p. ``inference_split`` (default 0.8) else training,
* duration ("work", on a 1g slice): inference ~ Exp(rate=3) minutes,
  training ~ U(10, 40) minutes,
* elasticity: one of {linear, capped, sublinear} equally likely;
  capped jobs cap at 2g/3g/4g uniformly; sublinear jobs draw one of the four
  curves uniformly,
* deadline: the paper leaves deadlines unspecified ("user-specified or
  best-effort"); we use ``arrival + slack * dur_on_7g`` with
  slack ~ U(slack_lo, slack_hi) (documented free parameter, DESIGN.md §2).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Iterator, List, Optional, Sequence

import numpy as np

# lint: waive[VG001] spans and named scopes only: no semantic change; batched bit-identity suites pin it
from repro import obs
from repro.core.jobs import (
    SUBLINEAR_CURVES,
    Elasticity,
    Job,
    JobKind,
    LINEAR,
    capped,
)

__all__ = [
    "WorkloadSpec",
    "DIURNAL_RATE_PER_MIN",
    "arrival_rate",
    "generate_jobs",
    "sample_poisson_arrivals",
    "jobs_from_arrivals",
    "DurationSampler",
]

MINUTES_PER_DAY = 24 * 60

# Fig. 5 — arrival rate (jobs/min) by hour of day, linearly interpolated.
# Peak plateau 5:00-17:00 at ~0.5/min, trough overnight ~0.1/min.
DIURNAL_RATE_PER_MIN: Sequence[float] = (
    0.10, 0.08, 0.08, 0.10, 0.22,  # 0..4h (ramp starts ~3-4h)
    0.38, 0.44, 0.48, 0.50, 0.52,  # 5..9h
    0.54, 0.55, 0.54, 0.52, 0.50,  # 10..14h
    0.48, 0.45, 0.40, 0.28, 0.18,  # 15..19h (falls 17-19h)
    0.14, 0.12, 0.10, 0.10,        # 20..23h
)


def arrival_rate(t_min: float, pattern: Sequence[float] = DIURNAL_RATE_PER_MIN) -> float:
    """Interpolated arrival rate (jobs/min) at absolute time ``t_min``."""
    hod = (t_min / 60.0) % 24.0
    lo = int(hod) % 24
    hi = (lo + 1) % 24
    frac = hod - int(hod)
    return pattern[lo] * (1.0 - frac) + pattern[hi] * frac


@dataclasses.dataclass(frozen=True)
class WorkloadSpec:
    """All knobs of the §V-A workload model."""

    horizon_min: float = float(MINUTES_PER_DAY)
    constant_rate: Optional[float] = None  # jobs/min; None => diurnal Fig. 5
    inference_split: float = 0.8
    # §V-A: inference duration "exponentially distributed with a lambda value
    # of 3".  We read this as scale (mean) = 3 minutes: with mean 1/3 min the
    # system never saturates at the paper's arrival rates and tardiness — half
    # of the ET objective — would be identically ~0, contradicting Figs. 7-10.
    inference_mean_min: float = 3.0
    training_lo_min: float = 10.0
    training_hi_min: float = 40.0
    slack_lo: float = 1.2
    slack_hi: float = 4.0
    linear_no_mig_speedup: float = 1.06  # §V-A: full GPU 6% faster for linear jobs

    def rate(self, t_min: float) -> float:
        if self.constant_rate is not None:
            return self.constant_rate
        return arrival_rate(t_min)

    @property
    def peak_rate(self) -> float:
        if self.constant_rate is not None:
            return self.constant_rate
        return max(DIURNAL_RATE_PER_MIN)


def sample_poisson_arrivals(
    horizon_min: float,
    rate_fn: Callable[[float], float],
    lam_max: float,
    rng: np.random.Generator,
) -> List[float]:
    """Thinning sampler for a (non-)homogeneous Poisson process.

    ``rate_fn(t)`` must never exceed ``lam_max`` on [0, horizon_min); the
    returned arrival times are strictly increasing by construction.  The
    scenario library (:mod:`repro.core.scenarios`) reuses this for rate
    patterns the :class:`WorkloadSpec` cannot express (MMPP bursts, scaled
    traces); the RNG draw sequence is identical to the original in-spec
    sampler, so the default diurnal path is bit-stable across the refactor.
    """
    t = 0.0
    out: List[float] = []
    with obs.span("scenario.arrivals") as counts:
        candidates = 0
        while True:
            t += rng.exponential(1.0 / lam_max)
            if t >= horizon_min:
                break
            candidates += 1
            if rng.uniform() * lam_max <= rate_fn(t):
                out.append(t)
        counts["candidates"], counts["accepted"] = candidates, len(out)
    return out


def _sample_arrivals(spec: WorkloadSpec, rng: np.random.Generator) -> List[float]:
    return sample_poisson_arrivals(spec.horizon_min, spec.rate, spec.peak_rate, rng)


def _sample_elasticity(rng: np.random.Generator) -> Elasticity:
    u = rng.integers(0, 3)
    if u == 0:
        return LINEAR
    if u == 1:
        return capped(int(rng.choice([2, 3, 4])))
    label = list(SUBLINEAR_CURVES)[int(rng.integers(0, len(SUBLINEAR_CURVES)))]
    return SUBLINEAR_CURVES[label]


#: Optional per-job duration override: ``(kind, rng) -> work`` in 1g-minutes.
#: Used by heavy-tailed scenarios; must perform exactly one bounded draw so
#: job attributes stay deterministic per seed.
DurationSampler = Callable[[JobKind, np.random.Generator], float]


def _sample_work(
    spec: WorkloadSpec,
    kind: JobKind,
    rng: np.random.Generator,
    duration_sampler: Optional[DurationSampler] = None,
) -> float:
    if duration_sampler is not None:
        return duration_sampler(kind, rng)
    if kind is JobKind.INFERENCE:
        # Exp(lambda=3): duration on a 1g slice, minutes.
        work = rng.exponential(spec.inference_mean_min)
        return max(work, 1.0 / 60.0)  # floor at one second
    return rng.uniform(spec.training_lo_min, spec.training_hi_min)


def jobs_from_arrivals(
    spec: WorkloadSpec,
    arrivals: Sequence[float],
    rng: np.random.Generator,
    duration_sampler: Optional[DurationSampler] = None,
) -> List[Job]:
    """Draw per-job attributes (§V-A) for pre-sampled arrival times.

    The RNG call sequence per job — split, duration, elasticity, slack — is
    exactly the legacy ``generate_jobs`` order, so the default path is
    bit-identical across the refactor.  ``duration_sampler`` swaps only the
    duration draw (heavy-tailed scenarios).
    """
    with obs.span("scenario.jobs", jobs=len(arrivals)):
        jobs: List[Job] = []
        for i, t in enumerate(arrivals):
            is_inf = rng.uniform() < spec.inference_split
            kind = JobKind.INFERENCE if is_inf else JobKind.TRAINING
            work = _sample_work(spec, kind, rng, duration_sampler)
            elast = _sample_elasticity(rng)
            slack = rng.uniform(spec.slack_lo, spec.slack_hi)
            dur_fastest = elast.duration(work, 7)
            deadline = t + slack * dur_fastest
            jobs.append(
                Job(
                    job_id=i,
                    kind=kind,
                    arrival=t,
                    work=work,
                    deadline=deadline,
                    elasticity=elast,
                    speedup_no_mig=spec.linear_no_mig_speedup
                    if elast is LINEAR
                    else 1.0,
                )
            )
        return jobs


def generate_jobs(
    spec: WorkloadSpec,
    seed: int,
    max_jobs: Optional[int] = None,
) -> List[Job]:
    """Generate one simulation's job queue (sorted by arrival)."""
    rng = np.random.default_rng(seed)
    arrivals = _sample_arrivals(spec, rng)
    if max_jobs is not None:
        arrivals = arrivals[:max_jobs]
    return jobs_from_arrivals(spec, arrivals, rng)
