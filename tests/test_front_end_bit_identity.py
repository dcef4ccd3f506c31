"""The sweep front end (scenario generation and padding) against a frozen
copy of its scalar form.

The reference below is the per-job generator and per-element padding as
they stood before the front end drew its jobs by column: the same draws
made with ``uniform``/``choice`` one call at a time, acceptance tested
candidate by candidate, a fresh capped curve per job, and every padded
element stored on its own.  The front end must give the same jobs, leave
the generator in the same state, and pad the same bytes.
"""

from __future__ import annotations

import bisect
from typing import List

import jax
import numpy as np
import pytest

from repro import obs
from repro.core.batched.state import BatchedJobs
from repro.core.jobs import (
    LINEAR,
    SUBLINEAR_CURVES,
    Elasticity,
    ElasticityClass,
    Job,
    JobKind,
    elasticity_from_label,
)
from repro.core.scenarios import (
    _lognormal_sampler,
    _pareto_sampler,
    generate_scenario,
    resolve_scenario_kwargs,
)
from repro.core.workload import DIURNAL_RATE_PER_MIN, DiurnalRate, WorkloadSpec, arrival_rate
from repro.sweep.cells import cell_jobs

# ----------------------------------------------------------------------
# the frozen scalar reference


def ref_rate(t_min, pattern=DIURNAL_RATE_PER_MIN):
    hod = (t_min / 60.0) % 24.0
    lo = int(hod) % 24
    hi = (lo + 1) % 24
    frac = hod - int(hod)
    return pattern[lo] * (1.0 - frac) + pattern[hi] * frac


def ref_arrivals(horizon_min, rate_fn, lam_max, rng, seen=None):
    t = 0.0
    out: List[float] = []
    candidates = 0
    while True:
        t += rng.exponential(1.0 / lam_max)
        if t >= horizon_min:
            break
        candidates += 1
        if rng.uniform() * lam_max <= rate_fn(t):
            out.append(t)
    if seen is not None:
        seen.append((candidates, len(out)))
    return out


def ref_capped(cap):
    return Elasticity(
        ElasticityClass.CAPPED, f"capped@{cap}g", lambda k, c=cap: min(k, float(c)), cap=cap
    )


def ref_elasticity(rng):
    u = rng.integers(0, 3)
    if u == 0:
        return LINEAR
    if u == 1:
        return ref_capped(int(rng.choice([2, 3, 4])))
    label = list(SUBLINEAR_CURVES)[int(rng.integers(0, len(SUBLINEAR_CURVES)))]
    return SUBLINEAR_CURVES[label]


def ref_work(spec, kind, rng, duration_sampler):
    if duration_sampler is not None:
        return duration_sampler(kind, rng)
    if kind is JobKind.INFERENCE:
        return max(rng.exponential(spec.inference_mean_min), 1.0 / 60.0)
    return rng.uniform(spec.training_lo_min, spec.training_hi_min)


def ref_jobs(spec, arrivals, rng, duration_sampler=None):
    jobs = []
    for i, t in enumerate(arrivals):
        is_inf = rng.uniform() < spec.inference_split
        kind = JobKind.INFERENCE if is_inf else JobKind.TRAINING
        work = ref_work(spec, kind, rng, duration_sampler)
        elast = ref_elasticity(rng)
        slack = rng.uniform(spec.slack_lo, spec.slack_hi)
        deadline = t + slack * elast.duration(work, 7)
        jobs.append(Job(job_id=i, kind=kind, arrival=t, work=work, deadline=deadline,
                        elasticity=elast,
                        speedup_no_mig=spec.linear_no_mig_speedup if elast is LINEAR else 1.0))
    return jobs


def ref_generate_jobs(spec, seed, seen):
    rng = np.random.default_rng(seed)
    rate = ref_rate if spec.constant_rate is None else spec.rate
    arrivals = ref_arrivals(spec.horizon_min, rate, spec.peak_rate, rng, seen)
    return ref_jobs(spec, arrivals, rng)


def ref_diurnal(seed, load_scale, horizon_min, seen, duration_sampler=None):
    spec = WorkloadSpec(horizon_min=horizon_min)
    rng = np.random.default_rng(seed)
    lam_max = max(DIURNAL_RATE_PER_MIN) * load_scale
    arrivals = ref_arrivals(horizon_min, lambda t: ref_rate(t) * load_scale, lam_max,
                            rng, seen)
    return ref_jobs(spec, arrivals, rng, duration_sampler)


def ref_bursty(seed, seen, burst_mult, quiet_mult, mean_burst_min, mean_quiet_min,
               load_scale, horizon_min):
    spec = WorkloadSpec(horizon_min=horizon_min)
    rng = np.random.default_rng(seed)
    boundaries, mults, in_burst, t = [0.0], [], False, 0.0
    while t < horizon_min:
        mean = mean_burst_min if in_burst else mean_quiet_min
        mults.append(burst_mult if in_burst else quiet_mult)
        t += rng.exponential(mean)
        boundaries.append(t)
        in_burst = not in_burst

    def rate(at):
        i = bisect.bisect_right(boundaries, at) - 1
        return ref_rate(at) * mults[min(i, len(mults) - 1)] * load_scale

    lam_max = max(DIURNAL_RATE_PER_MIN) * max(burst_mult, quiet_mult) * load_scale
    return ref_jobs(spec, ref_arrivals(horizon_min, rate, lam_max, rng, seen), rng)


def ref_scenario(name, seed, seen, **kw):
    if name == "paper-diurnal":
        if kw["load_scale"] == 1.0:
            return ref_generate_jobs(WorkloadSpec(horizon_min=kw["horizon_min"]), seed, seen)
        return ref_diurnal(seed, kw["load_scale"], kw["horizon_min"], seen)
    if name == "trace-scaled":
        return ref_diurnal(seed, kw["load_scale"], kw["horizon_min"], seen)
    if name == "bursty-mmpp":
        return ref_bursty(seed, seen, **kw)
    if name == "heavy-tail-lognormal":
        sampler = _lognormal_sampler(kw["inf_mean"], kw["inf_sigma"], kw["train_mean"],
                                     kw["train_sigma"], kw["cap_min"])
        return ref_diurnal(seed, kw["load_scale"], kw["horizon_min"], seen, sampler)
    if name == "heavy-tail-pareto":
        sampler = _pareto_sampler(kw["inf_xm"], kw["inf_alpha"], kw["train_xm"],
                                  kw["train_alpha"], kw["cap_min"])
        return ref_diurnal(seed, kw["load_scale"], kw["horizon_min"], seen, sampler)
    assert name == "weekend-flat"
    spec = WorkloadSpec(horizon_min=kw["horizon_min"],
                        constant_rate=kw["rate_per_min"] * kw["load_scale"])
    return ref_generate_jobs(spec, seed, seen)


def ref_pad(job_lists, *, max_slots, mig_enabled=True, pad_multiple=32, min_jobs=1):
    B = len(job_lists)
    longest = max((len(js) for js in job_lists), default=0)
    want = max(longest, int(min_jobs), 1)
    J = max(pad_multiple, -(-want // pad_multiple) * pad_multiple)
    K = max_slots + 1
    arrival = np.full((B, J), np.inf, dtype=np.float32)
    deadline = np.full((B, J), np.inf, dtype=np.float32)
    work = np.zeros((B, J), dtype=np.float32)
    rates = np.zeros((B, J, K), dtype=np.float32)
    valid = np.zeros((B, J), dtype=bool)
    num_jobs = np.zeros((B,), dtype=np.int32)
    for b, jobs in enumerate(job_lists):
        num_jobs[b] = len(jobs)
        for j, job in enumerate(jobs):
            if abs(job.remaining - job.work) > 1e-9:
                raise ValueError(
                    f"rollout {b} job {job.job_id}: partially-run jobs "
                    "cannot enter a batched rollout"
                )
            arrival[b, j] = job.arrival
            deadline[b, j] = job.deadline
            work[b, j] = job.work
            valid[b, j] = True
            for k in range(1, K):
                rates[b, j, k] = job.rate_on(float(k), mig_enabled)
    edf_order = np.argsort(deadline, axis=1, kind="stable").astype(np.int32)
    return {"arrival": arrival, "deadline": deadline, "work": work, "rate_by_slots": rates,
            "valid": valid, "num_jobs": num_jobs, "edf_order": edf_order}


# ----------------------------------------------------------------------
# the cases

SCENARIOS = ("paper-diurnal", "trace-scaled", "bursty-mmpp", "heavy-tail-lognormal",
             "heavy-tail-pareto", "weekend-flat")
SEEDS = (0, 7, 3_000_000_019)
LOADS = (1.0, 4.0, 8.0)


@pytest.fixture
def generators(monkeypatch):
    """Every ``np.random.default_rng`` made while the test runs, in order."""
    made = []
    real = np.random.default_rng

    def recording(*args, **kwargs):
        made.append(real(*args, **kwargs))
        return made[-1]

    monkeypatch.setattr(np.random, "default_rng", recording)
    return made


def _fields(jobs):
    return {
        "job_id": [j.job_id for j in jobs],
        "kind": [j.kind for j in jobs],
        "label": [j.elasticity.label for j in jobs],
        **{f: np.asarray([getattr(j, f) for j in jobs], dtype=np.float64).tobytes()
           for f in ("arrival", "work", "deadline", "speedup_no_mig", "remaining")},
    }


@pytest.mark.parametrize("load", LOADS)
@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", SCENARIOS)
def test_scenario_days_equal_the_scalar_generator(name, seed, load, generators):
    kw = resolve_scenario_kwargs(name, {"load_scale": load})
    ref = ref_scenario(name, seed, [], **kw)
    ref_state = generators[-1].bit_generator.state
    got = generate_scenario(name, seed=seed, **kw)
    got_state = generators[-1].bit_generator.state
    assert len(generators) == 2 and ref
    assert _fields(got) == _fields(ref)
    assert got_state == ref_state  # has_uint32 and uinteger included
    assert all(type(j.arrival) is float and type(j.deadline) is float for j in got)


#: whole hours, the day's ends, times a float unit either side of an hour,
#: and random times over a week
RATE_TIMES = {
    "hours": np.arange(0.0, 2 * 1440.0 + 1.0, 60.0),
    "edges": np.array([0.0, -1e-20, 1e-300, 1439.9999999999998, 1440.0, 5e-324]),
    "near-hours": np.nextafter(np.arange(60.0, 1440.0, 60.0)[:, None],
                               np.array([-np.inf, np.inf])).ravel(),
    "week": np.random.default_rng(11).uniform(0.0, 7 * 1440.0, 50_000),
}


@pytest.mark.parametrize("load", LOADS)
@pytest.mark.parametrize("times", sorted(RATE_TIMES))
def test_the_rate_over_an_array_equals_the_scalar_rate(times, load):
    ts = RATE_TIMES[times]
    want = np.array([ref_rate(float(t)) * load for t in ts], dtype=np.float64)
    assert DiurnalRate(load)(ts).tobytes() == want.tobytes()
    assert all(type(arrival_rate(float(t))) is float and arrival_rate(float(t)) == ref_rate(float(t))
               for t in ts[:500])


def _served(job_id, label, arrival, work, deadline, **kw):
    return Job(job_id=job_id, kind=JobKind.INFERENCE, arrival=arrival, work=work,
               deadline=deadline, elasticity=elasticity_from_label(label), **kw)


def _hand_built():
    """Serving curves (capped@1g, capped@7g), a shared and a fresh capped@2g,
    one linear curve under two no-MIG speedups, and an empty row."""
    return [
        [_served(0, "capped@1g", 0.5, 0.25, 1.0, tenant="a", slo_min=0.5),
         _served(1, "capped@7g", 1.0, 3.0, 2.5, tenant="b", slo_min=1.5),
         _served(2, "capped@2g", 1.5, 2.0, 9.0),
         Job(3, JobKind.TRAINING, 2.0, 20.0, 30.0, ref_capped(2)),
         Job(4, JobKind.TRAINING, 2.5, 20.0, 30.0, LINEAR, speedup_no_mig=1.06),
         Job(5, JobKind.TRAINING, 3.0, 1e-3, 30.0, LINEAR, speedup_no_mig=1.2)],
        [],
        [Job(0, JobKind.INFERENCE, 0.1, 1.0 / 3.0, 2.0 / 3.0,
             SUBLINEAR_CURVES["log-0.45"])],
    ]


def _partially_run():
    jobs = _hand_built()
    jobs[0][4].remaining = 5.0
    return jobs


def _scenario_batch(name, load):
    kw = resolve_scenario_kwargs(name, {"load_scale": load})
    return [generate_scenario(name, seed=s, **kw) for s in SEEDS]


PAD_CASES = {
    "paper-diurnal-x4": lambda: _scenario_batch("paper-diurnal", 4.0),
    "bursty-mmpp-x1": lambda: _scenario_batch("bursty-mmpp", 1.0),
    "heavy-tail-pareto-x8": lambda: _scenario_batch("heavy-tail-pareto", 8.0),
    "hand-built": _hand_built,
    "partially-run": _partially_run,
}


def _pad(pad, job_lists, mig_enabled, min_jobs):
    try:
        return pad(job_lists, max_slots=7, mig_enabled=mig_enabled, min_jobs=min_jobs)
    except ValueError as err:
        return str(err)


@pytest.mark.parametrize("min_jobs", (1, 2112))
@pytest.mark.parametrize("mig_enabled", (True, False))
@pytest.mark.parametrize("case", sorted(PAD_CASES))
def test_padded_batches_equal_the_per_element_padding(case, mig_enabled, min_jobs):
    job_lists = PAD_CASES[case]()
    want = _pad(ref_pad, job_lists, mig_enabled, min_jobs)
    got = _pad(BatchedJobs.from_job_lists, job_lists, mig_enabled, min_jobs)
    if isinstance(want, str):
        assert case == "partially-run" and got == want
        return
    for field, ref in want.items():
        arr = getattr(got, field)
        assert (arr.dtype, arr.shape) == (ref.dtype, ref.shape), field
        assert arr.tobytes() == ref.tobytes(), field


# ----------------------------------------------------------------------
# the spans the sweep front end's metrics read


@pytest.fixture
def session(tmp_path):
    obs.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        yield
    finally:
        if obs.profiling():
            jax.profiler.stop_trace()
        obs.clear()


@pytest.mark.parametrize("load", (1.0, 4.0))
def test_a_traced_day_keeps_its_front_end_spans(load, session):
    kw = resolve_scenario_kwargs("paper-diurnal", {"load_scale": load})
    seed = 2_718_281_828
    seen = []
    ref = ref_scenario("paper-diurnal", seed, seen, **kw)
    day = cell_jobs({"scenario": {"name": "paper-diurnal", "kwargs": {"load_scale": load}},
                     "seed": seed})
    batch = BatchedJobs.from_job_lists([day, day[:5]], max_slots=7)
    jax.profiler.stop_trace()
    rec = obs.records()
    assert [(s.name, s.parent) for s in rec] == [
        ("scenario", -1), ("scenario.arrivals", 0), ("scenario.jobs", 0), ("batched.pad", -1)]
    (candidates, accepted), = seen
    assert rec[1].counts == {"candidates": candidates, "accepted": accepted}
    assert rec[2].counts == {"jobs": len(ref)} and len(ref) == accepted
    # the day's jobs share the §V-A curves: one rate row per curve drawn
    curves = len({j.elasticity.label for j in day})
    assert rec[3].counts == {"jobs": len(day) + 5, "curves": curves} and curves <= 8
    assert int(batch.num_jobs.sum()) == len(day) + 5
