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
    UnsupportedPolicyError,
    agreement_failures,
    build_tables,
    compile_policy,
    held_policy,
    simulate_batch,
)
from repro.core.engine import SimulationEngine
from repro.core.power import A100_250W
from repro.core.rl.batched_env import BatchedRepartitionEnv
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


def _assert_agreement(b, o, label="", devices=1):
    """One rollout's batched aggregates vs its oracle run (BATCHED_SIM.md §4)."""
    failures = agreement_failures(b, o, devices)
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


def test_fleet_preemptions_have_their_own_tolerance():
    """A fleet's preemptions: 50% (dispatch on the grid, §4 D6), not 40%."""
    from repro.core.metrics import SimResult

    oracle = SimResult(
        energy_wh=1000.0, avg_tardiness=2.0, num_jobs=100, total_tardiness=200.0,
        preemptions=50, repartitions=4, max_tardiness=9.0, deadline_misses=20,
        busy_slot_minutes=400.0,
    )
    batched = dataclasses.replace(oracle, preemptions=50 - 24)
    assert agreement_failures(batched, oracle)[0].startswith("preemptions")
    assert agreement_failures(batched, oracle, devices=8) == []
    batched = dataclasses.replace(oracle, preemptions=50 - 26)
    assert agreement_failures(batched, oracle, devices=8)[0].startswith("preemptions")


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


def test_edf_layout_permutes_every_job_array():
    """``in_edf_order`` holds the batch in (deadline, id) order along the job
    axis, with ``edf_order`` the identity; the caller's batch is untouched."""
    t = build_tables()
    lists = [generate_scenario("paper-diurnal", seed=s, load_scale=0.2) for s in range(2)]
    jobs = BatchedJobs.from_job_lists(lists, max_slots=t.max_slots)
    before = {f: np.copy(getattr(jobs, f)) for f in ("arrival", "deadline", "edf_order")}
    lay = jobs.in_edf_order()
    B, J = jobs.arrival.shape
    assert (lay.edf_order == np.arange(J)).all()
    for b in range(B):
        order = jobs.edf_order[b]
        assert (order != np.arange(J)).any()  # deadlines out of arrival order
        for f in ("arrival", "deadline", "work", "rate_by_slots", "valid"):
            assert np.array_equal(getattr(lay, f)[b], getattr(jobs, f)[b][order]), f
    assert np.array_equal(lay.num_jobs, jobs.num_jobs)
    assert (lay.deadline[:, 1:] >= lay.deadline[:, :-1]).all()  # +inf padding last
    for f, v in before.items():
        assert np.array_equal(getattr(jobs, f), v), f


def test_run_steps_refuses_a_batch_not_in_edf_layout():
    from repro.core.batched.backend import device_constants, init_state, run_steps

    t = build_tables()
    jobs = BatchedJobs.from_job_lists(
        [generate_scenario("paper-diurnal", seed=0, load_scale=0.2)], max_slots=t.max_slots
    )
    policy = compile_policy(StaticPolicy(3), t, 1)
    with pytest.raises(ValueError, match="EDF layout"):
        run_steps(init_state(jobs, policy.initial), jobs, policy,
                  device_constants(t), t0_min=0.0, n_steps=1)


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


def test_batched_per_job_results_in_caller_order():
    """The scan runs on the EDF layout; ``simulate_batch`` hands per-job
    completions back in the caller's job order, each within one grid step of
    the oracle's on average (§4 D2: a job starts up to ``dt`` late)."""
    from repro.core.batched import DEFAULT_DT_MIN

    tables = build_tables()
    seeds = range(4)
    lists = [generate_scenario("paper-diurnal", seed=s, load_scale=0.2) for s in seeds]
    jobs = BatchedJobs.from_job_lists(lists, max_slots=tables.max_slots)
    assert all((jobs.edf_order[b] != np.arange(jobs.padded_jobs)).any() for b in seeds)
    res = simulate_batch(
        jobs, compile_policy(DayNightPolicy(), tables, len(lists)), tables=tables
    )
    assert np.array_equal(res.deadline, jobs.deadline.astype(np.float64))
    assert np.array_equal(res.valid, jobs.valid)
    assert np.array_equal(np.isfinite(res.completion), jobs.valid)
    batched = res.to_sim_results()
    for s in seeds:
        fresh = generate_scenario("paper-diurnal", seed=s, load_scale=0.2)
        oracle = _oracle(fresh, DayNightPolicy())
        _assert_agreement(batched[s], oracle, label=f"seed{s}")
        n = len(fresh)
        comp = res.completion[s, :n]
        assert (comp >= jobs.arrival[s, :n] - 1e-6).all()
        gap = np.abs(comp - np.array([j.completion for j in fresh]))
        assert gap.mean() <= DEFAULT_DT_MIN, (s, gap.mean())


#: ``simulate_batch`` on ``_identity_order_batch`` before the EDF layout
#: (the step permuted its in-system mask by ``edf_order`` every step)
_IDENTITY_ORDER_RESULT = {
    "energy_wh": [4193.0732421875, 4383.82421875, 4414.876953125],
    "tardiness_integral": [1.57916259765625, 14.116744995117188, 16.1956787109375],
    "busy_slot_minutes": [3704.81005859375, 4129.62890625, 4257.07568359375],
    "preemptions": [165, 156, 178],
    "repartitions": [3, 3, 3],
    "makespan_min": [1740.0, 1740.0, 1740.0],
    "completion_sum": 957851.085381031,
}


def _identity_order_batch(tables):
    """Three loaded days whose deadlines follow arrival order."""
    lists = [
        [dataclasses.replace(j, deadline=j.arrival + 20.0)
         for j in generate_scenario("paper-diurnal", seed=s, load_scale=1.0)]
        for s in range(3)
    ]
    return lists, BatchedJobs.from_job_lists(lists, max_slots=tables.max_slots)


def test_batched_identity_order_result_unchanged_by_the_layout():
    """Where ``edf_order`` is the identity the layout is too, and the result
    is what it was before the layout (float32 rounding aside) and agrees with
    the oracle under the §4 tolerances."""
    tables = build_tables()
    lists, jobs = _identity_order_batch(tables)
    assert (jobs.edf_order == np.arange(jobs.padded_jobs)).all()
    res = simulate_batch(jobs, compile_policy(DayNightPolicy(), tables, 3), tables=tables)
    want = _IDENTITY_ORDER_RESULT
    for f in ("preemptions", "repartitions"):
        assert getattr(res, f).tolist() == want[f], f
    for f in ("energy_wh", "tardiness_integral", "busy_slot_minutes", "makespan_min"):
        np.testing.assert_allclose(getattr(res, f), want[f], rtol=1e-6, err_msg=f)
    np.testing.assert_allclose(res.completion[res.valid].sum(), want["completion_sum"],
                               rtol=1e-6)
    for b, js in enumerate(lists):
        _assert_agreement(res.to_sim_result(b), _oracle(js, DayNightPolicy()), f"row{b}")


def _gathers(jaxpr):
    """Every ``gather`` equation of a jaxpr, nested jaxprs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            yield eqn
        for p in eqn.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _gathers(inner)


def _mask_gathers(jaxpr, B, J):
    """Gathers whose operand is a job-axis boolean mask, ``(B, J)``."""
    return [e for e in _gathers(jaxpr)
            if e.invars[0].aval.dtype == np.bool_ and e.invars[0].aval.shape == (B, J)]


def test_scan_step_has_no_per_step_mask_permutation():
    """The vmapped step and the device observations read EDF priority off
    the job index: no gather permutes a (B, J) in-system or queued mask."""
    import jax
    import jax.numpy as jnp

    from repro.core.batched.backend import _chunk_fn, device_constants, init_state
    from repro.core.rl.batched_train import device_observations

    tables = build_tables()
    lists = [generate_scenario("paper-diurnal", seed=s, load_scale=0.2) for s in range(2)]
    jobs = BatchedJobs.from_job_lists(lists, max_slots=tables.max_slots).in_edf_order()
    B, J = jobs.arrival.shape
    policy = compile_policy(DayNightPolicy(), tables, B)
    consts = device_constants(tables)
    state = init_state(jobs, policy.initial)
    chunk = _chunk_fn(policy.kind, 0.5, 2, float(tables.penalty_min),
                      policy.day_start, policy.day_end)
    step_jaxpr = jax.make_jaxpr(chunk)(
        state, jobs.arrival, jobs.deadline, jobs.rate_by_slots, jobs.valid,
        policy.primary, policy.secondary, np.float32(0.0),
        consts["slice_slots"], consts["slice_rank"], consts["num_slices"],
        consts["old_to_new"], consts["watts"],
    )
    obs_jaxpr = jax.make_jaxpr(device_observations)(
        state, jobs.arrival, jobs.deadline, jobs.valid, np.ones((B, J), np.float32),
        jnp.asarray(tables.config_ids), np.float32(30.0),
    )
    assert list(_gathers(step_jaxpr.jaxpr))  # the walk reaches the scan body
    assert _mask_gathers(step_jaxpr.jaxpr, B, J) == []
    assert _mask_gathers(obs_jaxpr.jaxpr, B, J) == []


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


# ----------------------------------------------------------------------
# fleets: a device axis in the scan, against the oracle's FleetSimulator


def _fleet_oracle(jobs, devices, dispatcher):
    from repro.fleet import FleetSimulator, FleetSpec

    fleet = FleetSimulator(FleetSpec.of(["a100-250w"] * devices, dispatcher=dispatcher,
                                        scheduler="EDF-FS"))
    return fleet.run(jobs, lambda i, prof: DayNightPolicy())


@pytest.mark.parametrize("dispatcher", ["least-loaded", "round-robin"])
def test_batched_fleet_matches_fleet_simulator(dispatcher):
    """B=4 rollouts of a 3-GPU fleet agree with the oracle's online fleet
    within the §4 tolerances (the dispatch grid is divergence D6)."""
    tables = build_tables()
    lists = [generate_scenario("paper-diurnal", seed=s, load_scale=3.0, horizon_min=180.0)
             for s in range(4)]
    jobs = BatchedJobs.from_job_lists(lists, max_slots=tables.max_slots)
    res = simulate_batch(jobs, compile_policy(DayNightPolicy(), tables, len(lists)),
                         tables=tables, devices=3, dispatcher=dispatcher)
    routed = res.dispatch_counts()
    for b, js in enumerate(lists):
        oracle = _fleet_oracle(js, 3, dispatcher)
        _assert_agreement(res.to_sim_result(b), oracle.aggregate, label=f"{dispatcher}/{b}",
                          devices=3)
        assert routed[b].sum() == len(js) and (routed[b] > 0).all()
        if dispatcher == "round-robin":  # the arrival rank alone decides
            assert routed[b].tolist() == oracle.dispatch_counts


def _hand_made_day():
    """Three arrivals in the first step, two in the step at 10.5 min: linear
    jobs on the night configuration's 4- and 3-slot slices."""
    from repro.core.jobs import LINEAR, Job, JobKind

    spec = [(0.1, 70.0), (0.2, 7.0), (0.3, 1.0), (10.2, 2.0), (10.3, 1.0)]
    return [Job(job_id=i, kind=JobKind.TRAINING, arrival=a, work=w, deadline=a + 60.0,
                elasticity=LINEAR) for i, (a, w) in enumerate(spec)]


@pytest.mark.parametrize("dispatcher,want", [
    # least-loaded: job 0 takes GPU 0 (a tie, to the lower index); jobs 1
    # and 2 see job 0's 70 on GPU 0 and job 1's 7, routed in the same step,
    # on GPU 1; at 10.5 min GPU 0 still holds 30 of job 0, GPU 1 nothing
    ("least-loaded", [0, 1, 1, 1, 1]),
    ("round-robin", [0, 1, 0, 1, 0]),  # arrival rank mod 2
])
def test_fleet_dispatch_order_on_a_hand_made_day(dispatcher, want):
    tables = build_tables()
    day = _hand_made_day()
    jobs = BatchedJobs.from_job_lists([day, day], max_slots=tables.max_slots)
    res = simulate_batch(jobs, compile_policy(DayNightPolicy(), tables, 2), tables=tables,
                         devices=2, dispatcher=dispatcher)
    for b in range(2):
        assert res.device[b, :5].tolist() == want
    oracle = _fleet_oracle(_hand_made_day(), 2, dispatcher)
    assert oracle.dispatch_counts == np.bincount(want, minlength=2).tolist()


def test_by_arrival_indexes_the_edf_layout_in_arrival_order():
    t = build_tables()
    lists = [generate_scenario("paper-diurnal", seed=s, load_scale=0.3) for s in range(2)]
    jobs = BatchedJobs.from_job_lists(lists, max_slots=t.max_slots)
    lay, order = jobs.in_edf_order(), jobs.by_arrival()
    for b, js in enumerate(lists):
        n = len(js)
        assert np.array_equal(lay.arrival[b][order[b, :n]], jobs.arrival[b, :n])
        assert sorted(order[b].tolist()) == list(range(jobs.padded_jobs))


def test_fleet_spans_count_devices_and_jobs_routed(tmp_path):
    """While a profiler session runs, ``batched.simulate`` counts the fleet's
    GPUs and ``batched.result`` the most and fewest jobs any GPU was routed."""
    import jax

    from repro import obs

    tables = build_tables()
    day = _hand_made_day()
    jobs = BatchedJobs.from_job_lists([day, day[:2]], max_slots=tables.max_slots)
    obs.clear()
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        simulate_batch(jobs, compile_policy(DayNightPolicy(), tables, 2), tables=tables,
                       devices=2, dispatcher="least-loaded")
    finally:
        jax.profiler.stop_trace()
    spans = {s.name: s for s in obs.records() if s is not None}
    obs.clear()
    assert spans["batched.simulate"].counts["devices"] == 2
    # row 0 routes [0, 1, 1, 1, 1], row 1 its first two jobs [0, 1]
    assert spans["batched.result"].counts == {"jobs_routed_max": 4, "jobs_routed_min": 1}
