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
    Job,
    JobKind,
    LINEAR,
    capped,
)

__all__ = [
    "WorkloadSpec",
    "DIURNAL_RATE_PER_MIN",
    "DiurnalRate",
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


def arrival_rate(
    t_min: float | np.ndarray, pattern: Sequence[float] = DIURNAL_RATE_PER_MIN
) -> float | np.ndarray:
    """Interpolated arrival rate (jobs/min) at absolute time ``t_min``.

    ``t_min`` is one time or an array of times: the same float operations
    run on either (``//`` and ``%`` on floats are floor and modulo in Python
    and numpy alike), so an array gives, element by element, what the times
    give one at a time.
    """
    hod = (t_min / 60.0) % 24.0
    whole = hod // 1.0
    frac = hod - whole
    if isinstance(hod, np.ndarray):
        lo, table = whole.astype(np.intp) % 24, np.asarray(pattern, dtype=np.float64)
    else:
        lo, table = int(whole) % 24, pattern
    return table[lo] * (1.0 - frac) + table[(lo + 1) % 24] * frac


@dataclasses.dataclass(frozen=True)
class DiurnalRate:
    """The Fig. 5 rate times ``load_scale``, at one time or an array of times.

    :func:`sample_poisson_arrivals` evaluates it over all its candidates at
    once, and accepts the same ones a per-candidate call would.
    """

    load_scale: float = 1.0

    def __call__(self, t_min: float | np.ndarray) -> float | np.ndarray:
        return arrival_rate(t_min) * self.load_scale


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
    traces).

    The loop only draws: an ``exponential`` gap, then one ``random()`` for
    the candidate, until a gap crosses the horizon, so the draw sequence
    does not depend on which candidates are accepted.  Acceptance,
    ``u * lam_max <= rate_fn(t)``, is evaluated after the loop: at once
    for a :class:`DiurnalRate`, else candidate by candidate.
    """
    with obs.span("scenario.arrivals") as counts:
        exponential, uniform = rng.exponential, rng.random
        gap = 1.0 / lam_max
        times: List[float] = []
        draws: List[float] = []
        t = 0.0
        while True:
            t += exponential(gap)
            if t >= horizon_min:
                break
            times.append(t)
            draws.append(uniform())
        ts = np.array(times, dtype=np.float64)
        rates = (rate_fn(ts) if isinstance(rate_fn, DiurnalRate)
                 else np.array([rate_fn(c) for c in times], dtype=np.float64))
        out = ts[np.array(draws, dtype=np.float64) * lam_max <= rates].tolist()
        counts["candidates"], counts["accepted"] = len(times), len(out)
    return out


def _sample_arrivals(spec: WorkloadSpec, rng: np.random.Generator) -> List[float]:
    rate = DiurnalRate() if spec.constant_rate is None else spec.rate
    return sample_poisson_arrivals(spec.horizon_min, rate, spec.peak_rate, rng)


# lint: waive[VG001] the same draws in the same order and the same float ops; tests/test_front_end_bit_identity.py pins every job and the generator's state
#: every curve a job can draw, shared by all the jobs that draw it, indexed
#: by the draw: linear, capped at 2g/3g/4g, then the sublinear curves in
#: ``SUBLINEAR_CURVES`` order
_CURVES = (LINEAR, capped(2), capped(3), capped(4), *SUBLINEAR_CURVES.values())
#: each curve's throughput on the fastest (7g) slice, which sets the deadline
_TP_FASTEST = np.array([e.throughput(7) for e in _CURVES], dtype=np.float64)


#: Optional per-job duration override: ``(kind, rng) -> work`` in 1g-minutes.
#: Used by heavy-tailed scenarios; must perform exactly one bounded draw so
#: job attributes stay deterministic per seed.
DurationSampler = Callable[[JobKind, np.random.Generator], float]


def jobs_from_arrivals(
    spec: WorkloadSpec,
    arrivals: Sequence[float],
    rng: np.random.Generator,
    duration_sampler: Optional[DurationSampler] = None,
) -> List[Job]:
    """Draw per-job attributes (§V-A) for pre-sampled arrival times.

    Per job, in this order: split, duration, elasticity class (and its cap
    or sublinear curve), slack.  Each draw is the cheapest numpy call that
    consumes and returns exactly what the legacy call did: ``random()`` for
    ``uniform()``, ``lo + (hi - lo) * random()`` for ``uniform(lo, hi)``,
    ``integers(0, 3)`` indexing the caps for ``choice([2, 3, 4])``
    (docs/BATCHED_SIM.md §2).  ``duration_sampler`` swaps only the duration
    draw (heavy-tailed scenarios).
    """
    n = len(arrivals)
    with obs.span("scenario.jobs", jobs=n):
        random, exponential, integers = rng.random, rng.exponential, rng.integers
        split, inf_mean = spec.inference_split, spec.inference_mean_min
        train_lo = spec.training_lo_min
        train_span = spec.training_hi_min - spec.training_lo_min
        slack_lo, slack_span = spec.slack_lo, spec.slack_hi - spec.slack_lo
        inference = [False] * n
        work = [0.0] * n
        curve = [0] * n
        slack = [0.0] * n
        for i in range(n):
            is_inf = inference[i] = random() < split
            if duration_sampler is not None:
                kind = JobKind.INFERENCE if is_inf else JobKind.TRAINING
                work[i] = duration_sampler(kind, rng)
            elif is_inf:
                # Exp(lambda=3): duration on a 1g slice, minutes, floored at one second
                work[i] = max(exponential(inf_mean), 1.0 / 60.0)
            else:
                work[i] = train_lo + train_span * random()
            klass = integers(0, 3)
            curve[i] = 0 if klass == 0 else (
                1 + integers(0, 3) if klass == 1 else 4 + integers(0, len(SUBLINEAR_CURVES)))
            slack[i] = slack_lo + slack_span * random()
        fastest = np.array(work, dtype=np.float64) / _TP_FASTEST[curve]
        deadline = np.asarray(arrivals, dtype=np.float64) + np.array(slack) * fastest
        no_mig = spec.linear_no_mig_speedup
        inf_kind, train_kind = JobKind.INFERENCE, JobKind.TRAINING
        return [
            Job(i, inf_kind if is_inf else train_kind, t, w, d, _CURVES[c],
                no_mig if c == 0 else 1.0)
            for i, (t, is_inf, w, c, d) in enumerate(
                zip(arrivals, inference, work, curve, deadline.tolist()))
        ]


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
