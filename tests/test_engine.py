"""SimulationEngine: step-wise == one-shot bit-identity, streaming, fixes.

The headline invariant of the engine extraction: driving the event loop
step-by-step (or injecting the same arrivals online) produces a
bit-identical ``SimResult``, config trace, and preemption count to the
one-shot ``MIGSimulator.run()`` for every policy family × scheduler ×
scenario.  Plus regression tests for the two event-loop fixes that rode
along: the spurious-completion recompute and the policy-timer set pruning.
"""

import math

import pytest

from repro.core.engine import EventKind, SimulationEngine
from repro.core.jobs import Job, JobKind, LINEAR
from repro.core.scenarios import generate_scenario
from repro.core.schedulers import make_scheduler
from repro.core.simulator import (
    DayNightPolicy,
    MIGSimulator,
    NoMIGPolicy,
    StaticPolicy,
    queue_heuristic_policy,
)
from repro.core.workload import WorkloadSpec, generate_jobs

SHORT = WorkloadSpec(horizon_min=180.0, constant_rate=0.4)

#: the four deterministic repartitioning-policy families (the DQN needs
#: trained weights and the forecast controller is pinned by
#: tests/test_forecast.py's own bit-identity test)
POLICY_FAMILIES = {
    "nomig": (lambda: NoMIGPolicy(), False),
    "static": (lambda: StaticPolicy(3), True),
    "daynight": (lambda: DayNightPolicy(), True),
    "heuristic": (lambda: queue_heuristic_policy(), True),
}

SCHEDULERS = ("EDF-FS", "EDF-SS", "LLF", "LALF")

#: (scenario, seed) triples the property matrix runs over — kept short
#: (3-hour horizons) so the full 4 × 4 × 3 grid stays in the fast tier
SCENARIO_SEEDS = (
    ("trace-scaled", 3),
    ("bursty-mmpp", 5),
    ("weekend-flat", 11),
)
SCENARIO_KW = {"horizon_min": 180.0}


@pytest.mark.parametrize("family", sorted(POLICY_FAMILIES))
@pytest.mark.parametrize("scheduler", SCHEDULERS)
def test_stepwise_bit_identical_to_one_shot(family, scheduler):
    """Property: for every policy family × scheduler × scenario/seed, the
    step-wise engine run equals one-shot run() on the full SimResult, the
    config trace, and the preemption count — bit for bit."""
    factory, mig_enabled = POLICY_FAMILIES[family]
    for scenario, seed in SCENARIO_SEEDS:
        jobs_a = generate_scenario(scenario, seed=seed, **SCENARIO_KW)
        jobs_b = generate_scenario(scenario, seed=seed, **SCENARIO_KW)

        sim_a = MIGSimulator(make_scheduler(scheduler), mig_enabled=mig_enabled)
        res_a = sim_a.run(jobs_a, policy=factory())

        sim_b = MIGSimulator(make_scheduler(scheduler), mig_enabled=mig_enabled)
        engine = SimulationEngine(sim_b, policy=factory(), jobs=jobs_b)
        steps = 0
        while engine.step() is not None:
            steps += 1
        res_b = engine.result()

        assert res_a == res_b, (family, scheduler, scenario, seed)
        assert sim_a.config_trace == sim_b.config_trace
        assert sim_a.preemptions == sim_b.preemptions
        assert sim_a.util_histogram == sim_b.util_histogram
        # events_processed counts heap pops incl. stale predictions, so it
        # bounds the number of step() returns from above
        assert steps <= engine.events_processed <= sim_b.max_events


def test_online_injection_bit_identical_to_preloaded():
    """Injecting the arrival stream online (stream_open + inject per job)
    replays the exact event sequence of a pre-loaded engine."""
    jobs_a = generate_jobs(SHORT, seed=13)
    jobs_b = generate_jobs(SHORT, seed=13)

    sim_a = MIGSimulator(make_scheduler("EDF-SS"))
    res_a = sim_a.run(jobs_a, policy=DayNightPolicy())

    sim_b = MIGSimulator(make_scheduler("EDF-SS"))
    engine = SimulationEngine(sim_b, policy=DayNightPolicy(), stream_open=True)
    for job in jobs_b:
        engine.run_until(job.arrival, inclusive=False)
        engine.inject(job)
    engine.close_stream()
    engine.drain()
    assert engine.result() == res_a
    assert sim_b.config_trace == sim_a.config_trace


def test_run_until_is_resumable_and_monotone():
    jobs = generate_jobs(SHORT, seed=21)
    sim = MIGSimulator(make_scheduler("EDF-SS"))
    engine = SimulationEngine(sim, policy=StaticPolicy(3), jobs=jobs)
    n1 = engine.run_until(60.0)
    t_mid = sim.t
    assert t_mid <= 60.0
    snap = engine.snapshot()
    assert snap.sim.t == t_mid
    assert snap.events_processed == engine.events_processed
    n2 = engine.run_until(60.0)
    assert n2 == 0  # idempotent at the same bound
    engine.drain()
    assert engine.finished
    assert engine.result().num_jobs == len(jobs)
    assert n1 > 0


def test_stream_open_engine_is_never_finished_while_idle():
    """An idle stream-open engine is merely between injections: finished
    must stay False (and result() must refuse) until close_stream()."""
    sim = MIGSimulator(make_scheduler("EDF-SS"))
    engine = SimulationEngine(sim, policy=StaticPolicy(3), stream_open=True)
    assert not engine.finished  # empty heap, but the stream is open
    with pytest.raises(RuntimeError, match="open stream"):
        engine.result()
    engine.inject(Job(0, JobKind.INFERENCE, 1.0, 1.0, 10.0, LINEAR))
    engine.drain()
    assert not engine.finished  # drained, still open
    engine.close_stream()
    assert engine.finished
    assert engine.result().num_jobs == 1


def test_inject_rejects_past_arrivals():
    sim = MIGSimulator(make_scheduler("EDF-SS"))
    engine = SimulationEngine(sim, policy=StaticPolicy(3), stream_open=True)
    engine.inject(Job(0, JobKind.INFERENCE, 0.0, 1.0, 10.0, LINEAR))
    engine.run_until(50.0)
    with pytest.raises(ValueError, match="cannot inject"):
        engine.inject(Job(1, JobKind.INFERENCE, 0.5, 1.0, 10.0, LINEAR))
    with pytest.raises(ValueError, match="already injected"):
        engine.inject(Job(0, JobKind.INFERENCE, 60.0, 1.0, 70.0, LINEAR))


def test_trace_sink_sees_every_event():
    jobs = generate_jobs(WorkloadSpec(horizon_min=60.0, constant_rate=0.3), seed=4)
    events = []
    sim = MIGSimulator(make_scheduler("EDF-SS"))
    engine = SimulationEngine(
        sim, policy=StaticPolicy(3), jobs=jobs, trace_sink=events.append
    )
    steps = engine.drain()
    assert len(events) == steps <= engine.events_processed
    arrivals = [e for e in events if e.kind == EventKind.ARRIVAL]
    completions = [e for e in events if e.kind == EventKind.COMPLETION and e.decision]
    assert len(arrivals) == len(jobs)
    assert len(completions) == len(jobs)
    ts = [e.t for e in events]
    assert ts == sorted(ts)


def test_interactive_mode_pauses_at_decisions():
    jobs = generate_jobs(WorkloadSpec(horizon_min=60.0, constant_rate=0.3), seed=4)
    sim = MIGSimulator(make_scheduler("EDF-SS"))
    engine = SimulationEngine(sim, interactive=True, initial_config=2, jobs=jobs)
    decisions = 0
    while engine.run_to_decision():
        assert engine.awaiting_decision
        with pytest.raises(RuntimeError, match="decision pending"):
            engine.step()
        engine.provide_decision(None)
        decisions += 1
    assert decisions > 0
    assert engine.finished
    assert engine.result().num_jobs == len(jobs)
    with pytest.raises(RuntimeError, match="no decision pending"):
        engine.provide_decision(None)


def test_spurious_completion_recomputes_finish_time():
    """Regression (satellite fix): a completion event that fires before the
    job's float depletion reaches zero must be re-predicted from current
    assignments, not blindly re-pushed at t + 1e-6 until the event budget
    burns."""
    job = Job(0, JobKind.INFERENCE, 0.0, work=7.0, deadline=10.0, elasticity=LINEAR)
    sim = MIGSimulator(make_scheduler("EDF-SS"))
    engine = SimulationEngine(sim, policy=StaticPolicy(1), jobs=[job])
    # process the arrival (assigns the job to the 7g slice; finish at t=1.0)
    ev = engine.step()
    assert ev.kind == EventKind.ARRIVAL
    # manufacture the numerical race: force a completion event far before
    # the true finish time, carrying the current (valid) version
    engine._push(0.25, EventKind.COMPLETION, job.job_id, engine._version)
    ev = engine.step()
    assert ev.kind == EventKind.COMPLETION and not ev.decision  # spurious
    # the fix: the follow-up completion is recomputed from the remaining
    # work at the device's current rate — NOT t + 1e-6
    pending = [
        (t, EventKind(k), ver)
        for (t, k, _, _, ver) in engine._heap
        if EventKind(k) == EventKind.COMPLETION and ver == engine._version
    ]
    assert pending, "recomputed completion must be scheduled"
    # (the arrival's original prediction may coexist at the same version;
    # every live completion must sit at the true finish, not t + 1e-6)
    for t_next, _, _ in pending:
        assert t_next == pytest.approx(1.0)
        assert not math.isclose(t_next, 0.25 + 1e-6)
    engine.drain()
    res = engine.result()
    assert res.num_jobs == 1
    assert job.completion == pytest.approx(1.0)
    # the whole run stays within a handful of events (no re-push storm)
    assert engine.events_processed < 10


def test_timer_set_is_pruned_on_pop():
    """Regression (satellite fix): the policy-timer dedup set must not grow
    with every timer ever fired — multi-day streaming runs would otherwise
    leak memory linearly in simulated time."""

    class MinutelyTimer(StaticPolicy):
        def __init__(self):
            super().__init__(config_id=3)

        def next_timer(self, t):
            return math.floor(t) + 1.0

    # one long job keeps the system active for 200 minutes of timer chain
    job = Job(0, JobKind.TRAINING, 0.0, work=600.0, deadline=300.0, elasticity=LINEAR)
    sim = MIGSimulator(make_scheduler("EDF-SS"))
    engine = SimulationEngine(sim, policy=MinutelyTimer(), jobs=[job])
    max_pending = 0
    while engine.step() is not None:
        max_pending = max(max_pending, len(engine._timer_scheduled))
    assert engine.result().num_jobs == 1
    # ~200 timers fired; the pruned set only ever holds the pending one(s)
    assert engine.events_processed > 150
    assert max_pending <= 2


def test_snapshot_fields_are_consistent():
    jobs = generate_jobs(SHORT, seed=30)
    sim = MIGSimulator(make_scheduler("EDF-SS"))
    engine = SimulationEngine(sim, policy=queue_heuristic_policy(), jobs=jobs)
    engine.run_until(90.0)
    snap = engine.snapshot()
    s = snap.sim
    assert s.t == sim.t
    assert s.config_id == sim.partition.config_id
    assert s.jobs_in_system == len([j for j in sim.active.values() if not j.done])
    assert s.active_jobs == len(sim.active)
    assert s.backlog_1g_min == pytest.approx(
        sum(j.remaining for j in sim.active.values() if not j.done)
    )
    assert s.inference_backlog_1g_min + s.training_backlog_1g_min == pytest.approx(
        s.backlog_1g_min
    )
    assert s.running == len(sim.assignment)
    assert snap.pending_arrivals == engine.arrivals_pending
    if not engine.finished:
        assert snap.next_event_time is not None


def test_one_shot_run_still_validates_policy_choice():
    class BadPolicy(StaticPolicy):
        def __init__(self):
            super().__init__(config_id=3)

        def decide(self, t, sim):
            return 99

    with pytest.raises(KeyError, match="not in this device's table"):
        MIGSimulator(make_scheduler("EDF-SS")).run(
            generate_jobs(WorkloadSpec(horizon_min=30.0, constant_rate=0.2), 1),
            policy=BadPolicy(),
        )
