"""Batched backend: table/padding units + batched-vs-oracle agreement.

The two-backend contract (docs/BATCHED_SIM.md): the event-driven
:class:`SimulationEngine` is the bit-exact oracle, and the batched
fixed-timestep backend must reproduce its aggregates within the documented
tolerances.  The agreement matrix here *is* that contract's enforcement —
scenario × policy × repartition-mode combos, each batching several seeds
into one vectorized rollout and comparing per-seed against fresh oracle
runs.  Tolerance values mirror BATCHED_SIM.md §4; tightening them requires
re-measuring, loosening them requires a documented divergence source
(the table lives in :mod:`repro.core.batched.agreement`).
"""

import dataclasses

import hypothesis
import numpy as np
import pytest
from hypothesis import given
from hypothesis import strategies as st

from repro.core.batched import (
    PAD_MULTIPLE,
    BatchedJobs,
    BatchedRepartitionEnv,
    UnsupportedPolicyError,
    agreement_failures,
    build_tables,
    compile_policy,
    held_policy,
    simulate_batch,
)
from repro.core.engine import SimulationEngine
from repro.core.power import A100_250W
from repro.core.scenarios import generate_scenario
from repro.core.schedulers import make_scheduler
from repro.core.simulator import (
    DayNightPolicy,
    MIGSimulator,
    NoMIGPolicy,
    RepartitionPolicy,
    StaticPolicy,
)
from repro.core.slices import MIG_CONFIGS, transition

_SETTINGS = {"max_examples": 6, "deadline": None}
if hasattr(hypothesis, "HealthCheck"):  # the stub has no HealthCheck
    _SETTINGS["suppress_health_check"] = list(hypothesis.HealthCheck)


def _oracle(jobs, policy, repartition_mode="partial"):
    sim = MIGSimulator(
        make_scheduler("EDF-FS"), repartition_mode=repartition_mode
    )
    engine = SimulationEngine(sim, policy=policy, jobs=jobs)
    engine.drain()
    return engine.result()


def _assert_agreement(b, o, label=""):
    """One rollout's batched aggregates vs its oracle run (BATCHED_SIM.md §4)."""
    failures = agreement_failures(b, o)
    assert not failures, f"{label}: {failures}"


@pytest.mark.parametrize(
    "field,value,column",
    [
        (None, None, None),
        ("num_jobs", 101, "num_jobs"),
        ("repartitions", 5, "repartitions"),
        ("energy_wh", 1000.0 * 1.029, None),
        ("energy_wh", 1000.0 * 1.031, "energy_wh"),
        ("avg_tardiness", 2.0 + 1.0, None),  # 50% of 2.0
        ("avg_tardiness", 2.0 + 1.01, "avg_tardiness"),
        ("busy_slot_minutes", 400.0 + 9.9, None),  # 2.5% of 400 is 10
        ("busy_slot_minutes", 400.0 + 10.1, "busy_slot_minutes"),
        ("preemptions", 50 + 19, None),  # 40% of 50 is 20
        ("preemptions", 50 + 21, "preemptions"),
    ],
)
def test_agreement_failures_names_each_column(field, value, column):
    """The §4 table: each column fails just past its tolerance, not before."""
    from repro.core.metrics import SimResult

    oracle = SimResult(
        energy_wh=1000.0, avg_tardiness=2.0, num_jobs=100,
        total_tardiness=200.0, preemptions=50, repartitions=4,
        max_tardiness=9.0, deadline_misses=20, busy_slot_minutes=400.0,
    )
    batched = dataclasses.replace(oracle, **({field: value} if field else {}))
    failures = agreement_failures(batched, oracle)
    if column is None:
        assert failures == []
    else:
        assert len(failures) == 1 and failures[0].startswith(column), failures


def test_agreement_floors_for_light_days():
    """Near-idle rollouts compare tardiness and preemptions above a floor."""
    from repro.core.metrics import SimResult

    oracle = SimResult(
        energy_wh=10.0, avg_tardiness=0.0, num_jobs=3, total_tardiness=0.0,
        preemptions=0, repartitions=0, max_tardiness=0.0, deadline_misses=0,
        busy_slot_minutes=0.5,
    )
    ok = dataclasses.replace(
        oracle, avg_tardiness=0.14, preemptions=3, busy_slot_minutes=1.4
    )
    assert agreement_failures(ok, oracle) == []
    bad = dataclasses.replace(ok, avg_tardiness=0.16, preemptions=5)
    assert [f.split()[0] for f in agreement_failures(bad, oracle)] == [
        "avg_tardiness", "preemptions",
    ]


def _policy_of(name):
    return {
        "static": lambda: StaticPolicy(3),
        "nomig": lambda: NoMIGPolicy(),
        "daynight": lambda: DayNightPolicy(),
    }[name]()


# ----------------------------------------------------------------------
# DeviceTables: the flattened slot-placement model


def test_tables_match_partition_model():
    t = build_tables()
    assert t.config_ids.tolist() == sorted(MIG_CONFIGS)
    for c, cid in enumerate(t.config_ids):
        p = MIG_CONFIGS[int(cid)]
        assert t.num_slices[c] == p.num_slices
        assert t.slice_slots[c, : p.num_slices].tolist() == [
            s.slots for s in p.slices
        ]
        assert (t.slice_slots[c, p.num_slices:] == 0).all()
        ranked = p.sorted_indices(descending=True)
        assert t.slice_rank[c, : len(ranked)].tolist() == ranked
        assert (t.slice_rank[c, len(ranked):] == -1).all()


def test_tables_match_transition_survivors():
    t = build_tables()
    for a, ca in enumerate(t.config_ids):
        for b, cb in enumerate(t.config_ids):
            surv = transition(MIG_CONFIGS[int(ca)], MIG_CONFIGS[int(cb)]).survivor_map
            expect = {s: -1 for s in range(int(t.num_slices[a]))}
            expect.update(surv)
            got = {s: int(t.old_to_new[a, b, s]) for s in expect}
            assert got == expect, (ca, cb)


def test_tables_power_curve_and_index():
    t = build_tables()
    for k in range(t.max_slots + 1):
        assert t.watts_by_busy[k] == pytest.approx(
            A100_250W.power_watts(float(k)), rel=1e-6
        )
    for cid in t.config_ids.tolist():
        assert t.config_ids[t.index_of(cid)] == cid
    with pytest.raises(KeyError):
        t.index_of(99)


# ----------------------------------------------------------------------
# BatchedJobs: padding and shape invariants


def test_batched_jobs_padding_and_masks():
    t = build_tables()
    lists = [
        generate_scenario("paper-diurnal", seed=s, load_scale=0.1)
        for s in range(3)
    ]
    jobs = BatchedJobs.from_job_lists(lists, max_slots=t.max_slots)
    B, J = jobs.arrival.shape
    assert B == 3 and J % PAD_MULTIPLE == 0
    assert J >= max(len(js) for js in lists)
    for b, js in enumerate(lists):
        n = len(js)
        assert jobs.num_jobs[b] == n
        assert jobs.valid[b, :n].all() and not jobs.valid[b, n:].any()
        assert np.isinf(jobs.arrival[b, n:]).all()
        assert (jobs.work[b, n:] == 0).all()
    # level 0 depletes nothing; valid rows have positive 1-slot rates
    assert (jobs.rate_by_slots[..., 0] == 0).all()
    assert (jobs.rate_by_slots[jobs.valid, 1] > 0).all()


def test_batched_jobs_edf_order_stable():
    t = build_tables()
    lists = [generate_scenario("paper-diurnal", seed=0, load_scale=0.1)]
    jobs = BatchedJobs.from_job_lists(lists, max_slots=t.max_slots)
    order = jobs.edf_order[0]
    d = jobs.deadline[0][order]
    assert (d[:-1] <= d[1:]).all()  # sorted; +inf padding lands at the end
    # stable tie-break: equal deadlines keep ascending job-id order
    ties = d[:-1] == d[1:]
    assert (order[:-1][ties] < order[1:][ties]).all()


def test_batched_jobs_rejects_partial_and_empty():
    t = build_tables()
    js = generate_scenario("paper-diurnal", seed=0, load_scale=0.05)
    js[0].remaining = js[0].work / 2
    with pytest.raises(ValueError, match="partially-run"):
        BatchedJobs.from_job_lists([js], max_slots=t.max_slots)
    with pytest.raises(ValueError, match="empty"):
        BatchedJobs.from_job_lists([], max_slots=t.max_slots)


# ----------------------------------------------------------------------
# policy compilation


def test_compile_policy_kinds_and_rejection():
    t = build_tables()
    p = compile_policy(StaticPolicy(3), t, batch=2)
    assert p.kind == "static" and p.batch == 2
    assert (p.initial == t.index_of(3)).all()
    p = compile_policy(NoMIGPolicy(), t, batch=1)
    assert p.kind == "static" and p.initial[0] == t.index_of(1)
    p = compile_policy(DayNightPolicy(), t, batch=3, initial_config=4)
    assert p.kind == "daynight"
    assert (p.initial == t.index_of(4)).all()
    assert (p.primary == t.index_of(6)).all()
    assert (p.secondary == t.index_of(2)).all()

    class Stateful(RepartitionPolicy):
        initial_config = 2

    with pytest.raises(UnsupportedPolicyError, match="oracle"):
        compile_policy(Stateful(), t, batch=1)


def test_held_policy_charges_only_real_switches():
    p = held_policy(np.array([2, 3]), np.array([2, 2]))
    assert p.kind == "static"
    assert p.initial.tolist() == [2, 2] and p.primary.tolist() == [2, 3]


# ----------------------------------------------------------------------
# agreement matrix: scenario × policy × mode, seeds batched into one run


@pytest.mark.parametrize(
    "scenario,policy,mode",
    [
        ("paper-diurnal", "daynight", "partial"),
        ("paper-diurnal", "static", "drain"),
        ("bursty-mmpp", "static", "partial"),
        ("bursty-mmpp", "daynight", "drain"),
        ("weekend-flat", "nomig", "partial"),
        ("weekend-flat", "daynight", "drain"),
        ("heavy-tail-lognormal", "static", "drain"),
        ("heavy-tail-lognormal", "nomig", "partial"),
    ],
)
def test_batched_matches_oracle(scenario, policy, mode):
    seeds = range(6)
    tables = build_tables()
    lists = [
        generate_scenario(scenario, seed=s, load_scale=0.2) for s in seeds
    ]
    jobs = BatchedJobs.from_job_lists(lists, max_slots=tables.max_slots)
    res = simulate_batch(
        jobs,
        compile_policy(_policy_of(policy), tables, len(lists)),
        tables=tables,
        repartition_mode=mode,
    )
    batched = res.to_sim_results()
    for s in seeds:
        fresh = generate_scenario(scenario, seed=s, load_scale=0.2)
        oracle = _oracle(fresh, _policy_of(policy), repartition_mode=mode)
        _assert_agreement(
            batched[s], oracle, label=f"{scenario}/{policy}/{mode}/seed{s}"
        )


@hypothesis.settings(**_SETTINGS)
@given(
    st.integers(min_value=0, max_value=10_000),
    st.sampled_from(["static", "nomig", "daynight"]),
    st.booleans(),
)
def test_batched_matches_oracle_property(seed, policy, drain):
    """Random (seed, policy, mode) draws hold the same agreement bounds."""
    mode = "drain" if drain else "partial"
    tables = build_tables()
    lists = [generate_scenario("paper-diurnal", seed=seed, load_scale=0.1)]
    jobs = BatchedJobs.from_job_lists(lists, max_slots=tables.max_slots)
    res = simulate_batch(
        jobs,
        compile_policy(_policy_of(policy), tables, 1),
        tables=tables,
        repartition_mode=mode,
    )
    fresh = generate_scenario("paper-diurnal", seed=seed, load_scale=0.1)
    oracle = _oracle(fresh, _policy_of(policy), repartition_mode=mode)
    _assert_agreement(
        res.to_sim_result(0), oracle, label=f"seed{seed}/{policy}/{mode}"
    )


def test_batched_completion_times_and_makespan():
    tables = build_tables()
    lists = [generate_scenario("paper-diurnal", seed=0, load_scale=0.1)]
    jobs = BatchedJobs.from_job_lists(lists, max_slots=tables.max_slots)
    res = simulate_batch(
        jobs, compile_policy(StaticPolicy(3), tables, 1), tables=tables
    )
    comp = res.completion[0]
    n = int(res.num_jobs[0])
    assert np.isfinite(comp[:n]).all()  # every real job finished
    assert np.isinf(comp[n:]).all()  # padding rows never complete
    assert (comp[:n] >= jobs.arrival[0, :n] - 1e-6).all()
    assert res.makespan_min[0] >= comp[:n].max() - 1e-3


# ----------------------------------------------------------------------
# vectorized RL env smoke


def test_batched_env_steps_and_results():
    env = BatchedRepartitionEnv(
        scenario="paper-diurnal",
        scenario_kwargs={"load_scale": 0.1},
        decision_interval_min=60.0,
        max_decisions=40,
    )
    obs = env.reset(seeds=[0, 1])
    assert obs.shape == (2, 2 + 2 * env.m)
    assert ((obs >= 0.0) & (obs <= 1.0)).all()
    steps = 0
    while not env.done:
        obs, reward, terminated, truncated, info = env.step([2, 5])
        steps += 1
        assert obs.shape == (2, 2 + 2 * env.m)
        assert reward.shape == (2,) and np.isfinite(reward).all()
        assert (info["queue_depth"] >= 0).all()
    assert steps > 1
    results = env.results()
    assert len(results) == 2
    assert all(r.num_jobs > 0 and r.energy_wh > 0 for r in results)
    with pytest.raises(RuntimeError, match="over"):
        env.step([2, 5])


def test_batched_env_rejects_bad_cadence_and_scheduler():
    with pytest.raises(ValueError, match="EDF-FS"):
        BatchedRepartitionEnv(scheduler_name="EDF-SS")
    with pytest.raises(ValueError, match="multiple"):
        BatchedRepartitionEnv(decision_interval_min=0.7)
