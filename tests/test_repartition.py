"""Slot-placed partitions, TransitionPlan, and partial repartitioning.

Pins the mig-sim-4 transition model:

* every Fig. 1 / A30 configuration sits on the NVIDIA placement grid, and
  ``validate_config_table`` rejects misaligned/overlapping layouts;
* ``transition`` matches slice instances by placement identity — identity
  transitions survive everything, disjoint layouts are full turnover;
* under ``repartition_mode="partial"`` jobs on surviving instances run
  through the 4 s stall (and may even complete inside it), the stall is
  charged only against the affected slots, and survivors keep their seat
  across the index renumbering without a phantom preemption;
* drain-compat: ``"partial"`` and ``"drain"`` are bit-identical whenever
  every transition a run performs is a full turnover, and across the policy
  family × scenario matrix partial never exceeds drain on preemptions;
* satellites: zero-work jobs complete without ever holding a slice (per
  scheduler family), and an out-of-table initial configuration fails at
  engine construction with a clear error.
"""

import pytest

from repro.core.engine import SimulationEngine
from repro.core.jobs import Job, JobKind, LINEAR
from repro.core.power import A30_165W
from repro.core.scenarios import generate_scenario
from repro.core.schedulers import make_scheduler, remap_assignment
from repro.core.simulator import (
    CallbackPolicy,
    DayNightPolicy,
    MIGSimulator,
    REPARTITION_PENALTY_MIN,
    StaticPolicy,
    queue_heuristic_policy,
)
from repro.core.slices import (
    A30_CONFIGS,
    MIG_CONFIGS,
    Partition,
    SliceType,
    auto_starts,
    placement_alignment,
    transition,
    validate_config_table,
)
from repro.core.workload import WorkloadSpec, generate_jobs

SCHEDULER_NAMES = ("EDF-FS", "EDF-SS", "EDF-SS-unrestricted", "LLF", "LALF")


def _sim(mode="partial", name="EDF-SS", **kw):
    return MIGSimulator(make_scheduler(name), repartition_mode=mode, **kw)


# ----------------------------------------------------------------------
# placement grid


def test_fig1_placements_match_nvidia_grid():
    """Auto-layout reproduces the documented A100 placements for all 12."""
    expected_starts = {
        1: (0,),
        2: (0, 4),
        3: (0, 4, 6),
        4: (0, 4, 5, 6),
        5: (0, 4),  # 1-slot hole at 3: the second 3g aligns to 4
        6: (0, 2, 4),
        7: (0, 2, 3, 4),
        8: (0, 1, 2, 3, 4),
        9: (0, 2, 4, 6),
        10: (0, 2, 4, 5, 6),
        11: (0, 2, 3, 4, 5, 6),
        12: (0, 1, 2, 3, 4, 5, 6),
    }
    for cid, part in MIG_CONFIGS.items():
        assert part.starts == expected_starts[cid], cid
    assert A30_CONFIGS[3].starts == (0, 2, 3)


def test_placement_alignment_rule():
    assert placement_alignment(1) == 1
    assert placement_alignment(2) == 2
    assert placement_alignment(3) == 4
    assert placement_alignment(4) == 4
    # left-packed layout skips to the alignment boundary
    assert auto_starts((3, 3)) == (0, 4)
    assert auto_starts((1, 2)) == (0, 2)
    assert auto_starts((1, 3)) == (0, 4)


def test_validate_config_table_rejects_bad_placements():
    s2, s3 = SliceType(2, 10), SliceType(3, 20)
    with pytest.raises(AssertionError, match="placement alignment"):
        validate_config_table(
            {1: Partition(1, (s2,), starts=(1,))}, 7, 40
        )
    with pytest.raises(AssertionError, match="overlaps"):
        validate_config_table(
            {1: Partition(1, (s3, s2), starts=(0, 2))}, 7, 40
        )
    with pytest.raises(AssertionError, match="grid"):
        validate_config_table(
            {1: Partition(1, (s3,), starts=(4,))}, 6, 40
        )
    with pytest.raises(ValueError, match="starts"):
        Partition(1, (s2, s3), starts=(0,))


# ----------------------------------------------------------------------
# transition plans


def test_transition_identity_and_full_turnover():
    for part in MIG_CONFIGS.values():
        plan = transition(part, part)
        assert not plan.destroyed and not plan.created
        assert plan.stalled_slots == 0
        assert len(plan.surviving) == part.num_slices
        assert not plan.full_turnover or part.num_slices == 0
    # 7g@0 shares nothing with any split layout
    plan = transition(MIG_CONFIGS[1], MIG_CONFIGS[2])
    assert plan.full_turnover
    assert plan.stalled_slots == 7


def test_transition_survivors_are_placement_identical():
    # cfg5 (3g@0 + 3g@4) -> cfg2 (4g@0 + 3g@4): the 3g@4 instance survives
    plan = transition(MIG_CONFIGS[5], MIG_CONFIGS[2])
    assert plan.surviving == ((1, 1),)
    assert plan.destroyed == (0,)
    assert plan.created == (0,)
    assert plan.stalled_slots == 4  # cells 0-3 are rebuilt
    # cfg3 -> cfg2: the 4g@0 survives, 2g@4 + 1g@6 collapse into 3g@4
    plan = transition(MIG_CONFIGS[3], MIG_CONFIGS[2])
    assert plan.survivor_map == {0: 0}
    assert plan.stalled_slots == 3
    # every survivor pair is the identical placed instance
    for old_cid in MIG_CONFIGS:
        for new_cid in MIG_CONFIGS:
            old, new = MIG_CONFIGS[old_cid], MIG_CONFIGS[new_cid]
            plan = transition(old, new)
            for i, j in plan.surviving:
                assert old.slice_instances()[i] == new.slice_instances()[j]


def test_remap_assignment_is_identity_stable():
    assert remap_assignment({7: 1, 9: 0}, {0: 0, 1: 1}) == {7: 1, 9: 0}
    assert remap_assignment({7: 1}, {1: 0}) == {7: 0}
    with pytest.raises(AssertionError, match="non-surviving"):
        remap_assignment({7: 2}, {1: 0})


# ----------------------------------------------------------------------
# partial repartition semantics


class _SwitchOnceAt:
    """Switch to ``target`` at the first decision point at/after ``t_at``."""

    def __init__(self, initial, target, t_at):
        self.initial_config = initial
        self.target = target
        self.t_at = t_at
        self.done = False

    def decide(self, t, sim):
        if not self.done and t >= self.t_at:
            self.done = True
            return self.target
        return None

    def next_timer(self, t):
        return None if self.done else max(self.t_at, t + 1e-3)


def test_survivor_runs_through_stall_and_busy_slots_are_charged():
    # one job on the 4g@0 of cfg3 (EDF-FS: fastest slice); switch cfg3 ->
    # cfg2 mid-run: the 4g instance survives, the job keeps depleting
    # through the 4 s window, and the busy-slot accounting never stalls
    job = Job(0, JobKind.TRAINING, 0.0, work=30.0, deadline=100.0, elasticity=LINEAR)
    sim = _sim("partial", "EDF-FS")
    engine = SimulationEngine(
        sim, policy=_SwitchOnceAt(3, 2, 1.0), jobs=[job]
    )
    engine.run_until(1.0)
    assert sim.assignment[0] == 0  # seated on the surviving 4g@0
    engine.drain()
    res = engine.result()
    assert res.repartitions == 1
    assert res.preemptions == 0  # survivor never preempted, even renumbered
    assert job.completion == pytest.approx(7.5)  # 30 1g-min on 4g, no stall
    assert res.busy_slot_minutes == pytest.approx(30.0)


def test_survivor_can_complete_inside_the_stall_window():
    # job finishes 2 s into the 4 s stall: its completion event must fire
    # inside the window, not be deferred to REPART_DONE
    job = Job(0, JobKind.INFERENCE, 0.0, work=3.0, deadline=50.0, elasticity=LINEAR)
    switch_at = 0.75 - REPARTITION_PENALTY_MIN / 2.0
    sim = _sim("partial", "EDF-FS")
    engine = SimulationEngine(sim, policy=_SwitchOnceAt(3, 2, switch_at), jobs=[job])
    engine.drain()
    res = engine.result()
    assert res.repartitions == 1
    assert job.completion == pytest.approx(0.75)  # 3 1g-min on 4g
    assert res.preemptions == 0


def test_stalled_slots_in_snapshot_partial_vs_drain():
    job = Job(0, JobKind.TRAINING, 0.0, work=30.0, deadline=100.0, elasticity=LINEAR)
    for mode, expected in (("partial", 4), ("drain", 6)):
        sim = _sim(mode)
        engine = SimulationEngine(sim, policy=_SwitchOnceAt(5, 2, 1.0), jobs=[job])
        engine.run_until(1.0 + REPARTITION_PENALTY_MIN / 2.0)
        snap = sim.snapshot()
        assert snap.repartitioning
        assert snap.stalled_slots == expected, mode
        engine.drain()
        assert sim.snapshot().stalled_slots == 0


def test_occupied_slices_snapshot_field():
    job = Job(0, JobKind.TRAINING, 0.0, work=30.0, deadline=100.0, elasticity=LINEAR)
    sim = _sim("partial")
    engine = SimulationEngine(sim, policy=StaticPolicy(5), jobs=[job])
    engine.run_until(1.0)
    assert sim.snapshot().occupied_slices == tuple(sorted(set(sim.assignment.values())))
    engine.drain()
    assert sim.snapshot().occupied_slices == ()


# ----------------------------------------------------------------------
# drain-compat properties (satellite)

#: policies whose every transition is a full turnover on the A100 grid
#: (cfg1's 7g@0 shares no instance with cfg6's 2+2+3 layout)
_FULL_TURNOVER_POLICIES = {
    "daynight-1-6": lambda: DayNightPolicy(day_config=6, night_config=1),
    "switch-once-5-1": lambda: _SwitchOnceAt(5, 1, 60.0),
}

_PROPERTY_SCENARIOS = (
    ("trace-scaled", 3),
    ("bursty-mmpp", 5),
    ("weekend-flat", 11),
)
_SCENARIO_KW = {"horizon_min": 180.0}


@pytest.mark.parametrize("policy_name", sorted(_FULL_TURNOVER_POLICIES))
@pytest.mark.parametrize("scheduler", ("EDF-FS", "EDF-SS", "LLF", "LALF"))
def test_partial_equals_drain_on_full_turnover(policy_name, scheduler):
    """Property: when no transition shares a slice instance, the partial
    model degenerates to the drain model bit for bit."""
    factory = _FULL_TURNOVER_POLICIES[policy_name]
    for scenario, seed in _PROPERTY_SCENARIOS:
        results = {}
        for mode in ("partial", "drain"):
            jobs = generate_scenario(scenario, seed=seed, **_SCENARIO_KW)
            sim = _sim(mode, scheduler)
            results[mode] = (
                sim.run(jobs, policy=factory()),
                sim.config_trace,
                sim.util_histogram,
            )
        assert results["partial"] == results["drain"], (
            policy_name, scheduler, scenario, seed,
        )


@pytest.mark.slow
def test_partial_never_exceeds_drain_preemptions_across_matrix():
    """Across the policy-family × scenario matrix on identical job streams,
    the partial transition model's preemption total never exceeds drain's
    (per-family, summed over the scenario/seed matrix: single-run ties can
    go either way through trajectory divergence, the family totals must
    not)."""
    families = {
        "daynight": lambda: DayNightPolicy(),
        "heuristic": lambda: queue_heuristic_policy(),
    }
    for fname, factory in families.items():
        totals = {"partial": 0, "drain": 0}
        for scenario, seed in _PROPERTY_SCENARIOS:
            for mode in totals:
                jobs = generate_scenario(scenario, seed=seed, **_SCENARIO_KW)
                sim = _sim(mode)
                totals[mode] += sim.run(jobs, policy=factory()).preemptions
        assert totals["partial"] <= totals["drain"], (fname, totals)


# ----------------------------------------------------------------------
# forecast controller under the partial transition model


def test_forecast_partial_defers_displacing_switches(monkeypatch):
    """Opportunistic switch timing: a wanted transition that would tear a
    slice out from under a running job is deferred (bounded), and lands
    immediately at a displacement-free instant."""
    from repro.forecast import ForecastPolicy

    def rigged(policy):
        monkeypatch.setattr(
            policy, "_best_config", lambda *a, **k: (2, {2: 0.0, 3: 1.0})
        )
        return policy

    job = Job(0, JobKind.TRAINING, 0.0, work=30.0, deadline=100.0, elasticity=LINEAR)

    # job on cfg3's 2g@4 (destroyed by 3 -> 2): defer, then force after the
    # window expires
    policy = rigged(ForecastPolicy(
        repartition_mode="partial", min_dwell_min=0.0, eval_interval_min=0.0,
    ))
    sim = MIGSimulator(make_scheduler("EDF-FS"))
    sim.reset(3)
    sim.active[0] = job
    sim.assignment = {0: 1}
    assert policy.decide(1.0, sim) is None  # displaced runner: deferred
    assert policy.decide(1.0 + policy.max_defer_min + 0.1, sim) == 2

    # same state but the job sits on the surviving 4g@0: switch immediately
    policy2 = rigged(ForecastPolicy(
        repartition_mode="partial", min_dwell_min=0.0, eval_interval_min=0.0,
    ))
    sim.assignment = {0: 0}
    assert policy2.decide(1.0, sim) == 2

    # drain pricing never defers (legacy decision sequence preserved)
    policy3 = rigged(ForecastPolicy(
        repartition_mode="drain", min_dwell_min=0.0, eval_interval_min=0.0,
    ))
    sim.assignment = {0: 1}
    assert policy3.decide(1.0, sim) == 2


def test_legacy_cell_without_mode_key_replays_as_drain():
    """A pre-mig-sim-4 cell (no repartition_mode anywhere) must replay
    bit-identically to an explicit drain cell with drain pricing — the
    compatibility rule behind the checked-in-baseline reproducibility."""
    from repro.sweep.cells import make_scenario_cell, run_cell

    explicit = make_scenario_cell(
        experiment="t", group="g", scheduler="EDF-SS",
        scenario="weekend-flat", scenario_kwargs={"horizon_min": 240.0},
        seed=5, policy="forecast",
        policy_kwargs={"scenario": "weekend-flat", "repartition_mode": "drain"},
        repartition_mode="drain",
    )
    legacy = {k: v for k, v in explicit.items() if k != "repartition_mode"}
    legacy["policy_kwargs"] = {
        k: v for k, v in explicit["policy_kwargs"].items()
        if k != "repartition_mode"
    }
    assert run_cell(explicit) == run_cell(legacy)


def test_baseline_partial_beats_drain_for_forecast_on_paper_diurnal():
    """The PR's acceptance row, pinned against the checked-in baseline: on
    paper-diurnal the forecast policy under partial strictly reduces
    preemptions at an equal-or-better ET vs drain."""
    import json
    import os

    from repro.sweep.grids import GRIDS

    baseline = os.path.join(
        os.path.dirname(__file__), "..", "benchmarks", "baselines",
        "repartition_modes.jsonl",
    )
    assert os.path.exists(baseline), "repartition_modes baseline missing"
    cells, results = [], []
    with open(baseline) as f:
        for line in f:
            if line.strip():
                rec = json.loads(line)
                cells.append(rec["cell"])
                results.append(rec["result"])
    rows = GRIDS["repartition_modes"].aggregate(cells, results)
    by_key = {(r["scenario"], r["family"]): r for r in rows}
    fc = by_key[("paper-diurnal", "Forecast")]
    assert fc["partial_cuts_preemptions"], fc
    assert fc["preemptions_partial"] < fc["preemptions_drain"]
    assert fc["ET_partial"] <= fc["ET_drain"], fc
    # the heuristic family shows the raw physics win (hundreds of switches)
    hr = by_key[("paper-diurnal", "Heuristic")]
    assert hr["preemptions_partial"] < hr["preemptions_drain"]


# ----------------------------------------------------------------------
# zero-work jobs complete without ever holding a slice (satellite)


@pytest.mark.parametrize("scheduler", SCHEDULER_NAMES)
def test_zero_work_job_completes_at_arrival(scheduler):
    jobs = [
        Job(0, JobKind.TRAINING, 0.0, work=10.0, deadline=40.0, elasticity=LINEAR),
        Job(1, JobKind.INFERENCE, 2.0, work=0.0, deadline=5.0, elasticity=LINEAR),
    ]
    sim = MIGSimulator(make_scheduler(scheduler))
    res = sim.run(jobs, policy=StaticPolicy(1))
    assert res.num_jobs == 2
    assert jobs[1].completion == pytest.approx(2.0)
    assert jobs[1].tardiness() == 0.0
    assert not sim.active


@pytest.mark.parametrize("scheduler", SCHEDULER_NAMES)
def test_zero_work_job_injected_into_open_stream_drains(scheduler):
    """Regression: an injected zero-work arrival used to leak in ``active``
    forever and drain() on the closed stream never terminated."""
    sim = MIGSimulator(make_scheduler(scheduler))
    engine = SimulationEngine(sim, policy=StaticPolicy(3), stream_open=True)
    engine.inject(Job(0, JobKind.INFERENCE, 1.0, 1.0, 10.0, LINEAR))
    engine.run_until(5.0)
    engine.inject(Job(1, JobKind.INFERENCE, 6.0, 0.0, 7.0, LINEAR))
    engine.close_stream()
    engine.drain()
    assert engine.finished
    res = engine.result()
    assert res.num_jobs == 2
    assert res.deadline_misses == 0


# ----------------------------------------------------------------------
# initial-config validation (satellite)


def test_out_of_table_initial_config_fails_at_construction():
    """CallbackPolicy's hard-coded initial_config=2 on a table lacking id 2
    must produce a clear construction-time error, not a bare KeyError."""
    table = {1: A30_CONFIGS[1]}  # a device exposing only the full layout
    sim = MIGSimulator(
        make_scheduler("EDF-SS"), power_model=A30_165W, config_table=table
    )
    policy = CallbackPolicy(lambda t, s: None)  # initial_config=2 default
    with pytest.raises(ValueError, match="CallbackPolicy.*valid ids \\[1\\]"):
        SimulationEngine(sim, policy=policy, jobs=[])
    # the explicit override path is validated identically
    with pytest.raises(ValueError, match="initial_config override"):
        SimulationEngine(sim, policy=StaticPolicy(1), initial_config=9, jobs=[])


def test_device_adapted_policy_maps_initial_config_onto_a30():
    """DeviceAdaptedPolicy translation keeps an A100-space policy usable on
    the A30 table end to end (the PR-3 guard's mirror for initial configs)."""
    from repro.fleet import DeviceAdaptedPolicy

    inner = CallbackPolicy(lambda t, s: None, initial_config=12)
    adapted = DeviceAdaptedPolicy(inner, A30_CONFIGS)
    assert adapted.initial_config in A30_CONFIGS
    sim = MIGSimulator(
        make_scheduler("EDF-SS"), power_model=A30_165W, config_table=A30_CONFIGS
    )
    jobs = generate_jobs(WorkloadSpec(horizon_min=120.0, constant_rate=0.3), 4)
    res = sim.run(jobs, policy=adapted)
    assert res.num_jobs == len(jobs)
