"""Sweep integration of the batched backend: hashing, routing, caching.

The backend key is part of the content-hash contract: an oracle cell's hash
must be byte-identical to what it was before the batched backend existed
(no ``backend`` key at all), and a batched cell of the same physics must
hash differently — the two backends agree only within tolerance, so their
results may never alias one cache entry.
"""

import numpy as np
import pytest

from repro.core.batched import UnsupportedPolicyError
from repro.sweep.batched import (
    batched_group_key,
    is_batched_cell,
    run_batched_cells,
    validate_batched_cell,
)
from repro.sweep.cells import (
    cell_hash,
    make_cell,
    make_fleet_cell,
    make_scenario_cell,
    result_to_sim_result,
    run_cell,
)
from repro.sweep.runner import run_cells

_KW = {"load_scale": 0.1}


def _cell(seed=0, backend="batched", policy="daynight", **kw):
    return make_scenario_cell(
        experiment="t", group="g", scheduler="EDF-FS",
        scenario="paper-diurnal", seed=seed, scenario_kwargs=_KW,
        policy=policy, backend=backend, **kw,
    )


# ----------------------------------------------------------------------
# cell construction + hashing


def test_oracle_cells_carry_no_backend_key():
    cell = _cell(backend="oracle")
    assert "backend" not in cell and "backend_kwargs" not in cell
    assert not is_batched_cell(cell)


def test_batched_cells_hash_apart_from_oracle():
    oracle = _cell(backend="oracle")
    batched = _cell(backend="batched")
    assert batched["backend"] == "batched"
    assert batched["backend_kwargs"] == {"dt_min": 0.5}
    assert is_batched_cell(batched)
    assert cell_hash(oracle) != cell_hash(batched)
    # a different timestep is different physics: different hash
    coarse = _cell(backend="batched", backend_kwargs={"dt_min": 1.0})
    assert cell_hash(coarse) != cell_hash(batched)


def test_backend_validation_errors():
    with pytest.raises(ValueError, match="unknown backend"):
        _cell(backend="gpu")
    with pytest.raises(ValueError, match="backend_kwargs"):
        _cell(backend="oracle", backend_kwargs={"dt_min": 1.0})
    # workload-spec cells take the same backend parameters
    from repro.core.workload import WorkloadSpec

    cell = make_cell(
        experiment="t", group="g", scheduler="EDF-FS",
        workload=WorkloadSpec(), seed=0, backend="batched",
    )
    assert cell["backend"] == "batched"


def test_group_key_collapses_seeds_only():
    a, b = _cell(seed=0), _cell(seed=1)
    assert batched_group_key(a) == batched_group_key(b)
    assert batched_group_key(a) != batched_group_key(
        _cell(seed=0, backend_kwargs={"dt_min": 1.0})
    )
    assert batched_group_key(a) != batched_group_key(_cell(seed=0, policy="nomig"))


# ----------------------------------------------------------------------
# routing + rejection


def test_validate_rejects_wrong_scheduler_and_fleet():
    bad = dict(_cell())
    bad["scheduler"] = "EDF-SS"
    with pytest.raises(UnsupportedPolicyError, match="EDF-FS"):
        validate_batched_cell(bad)
    fleet = make_fleet_cell(
        experiment="t", group="g", profiles=["a100"], dispatcher="jsq",
        scheduler="EDF-FS", scenario="paper-diurnal", seed=0,
        scenario_kwargs=_KW,
    )
    fleet["backend"] = "batched"
    with pytest.raises(UnsupportedPolicyError, match="fleet"):
        run_cell(fleet)


def _fleet(profiles=("a100-250w",) * 2, dispatcher="least-loaded", seed=0,
           backend="batched", **kw):
    return make_fleet_cell(
        experiment="t", group="g", profiles=list(profiles), dispatcher=dispatcher,
        scheduler="EDF-FS", scenario="paper-diurnal", seed=seed,
        scenario_kwargs={"load_scale": 0.6}, policy="daynight", backend=backend, **kw,
    )


@pytest.mark.parametrize("profiles,dispatcher,info,why", [
    (("a100-250w", "a30-165w"), "least-loaded", "online", "mixed profiles"),
    (("a30-165w",) * 2, "least-loaded", "online", "A100 partition table"),
    (("a100-250w",) * 2, "energy-greedy", "online", "dispatcher 'energy-greedy'"),
    (("a100-250w",) * 2, "state-aware", "online", "dispatcher 'state-aware'"),
    (("a100-250w",) * 2, "least-loaded", "fluid", "dispatches online"),
])
def test_unsupported_fleets_are_refused_on_both_paths(profiles, dispatcher, info, why):
    """The refusal names what the batched backend lacks and points to the
    oracle, both when the cell is built and when a hand-made one is run."""
    with pytest.raises(UnsupportedPolicyError, match=why) as built:
        _fleet(profiles, dispatcher, dispatch_info=info)
    assert "oracle backend" in str(built.value)
    cell = _fleet(profiles, dispatcher, backend="oracle", dispatch_info=info)
    cell["backend"], cell["backend_kwargs"] = "batched", {"dt_min": 0.5}
    with pytest.raises(UnsupportedPolicyError, match=why):
        run_batched_cells([cell])
    cell = _fleet()
    cell["fleet"]["devices"][1]["initial_config"] = 3
    with pytest.raises(UnsupportedPolicyError, match="overrides"):
        validate_batched_cell(cell)


@pytest.mark.parametrize("dispatcher", ["least-loaded", "round-robin"])
def test_supported_fleets_run_batched_through_the_runner(dispatcher):
    """Homogeneous A100 fleets run on the scan, one batch per physics."""
    cells = [_fleet(dispatcher=dispatcher, seed=s) for s in range(3)]
    assert is_batched_cell(cells[0])
    assert batched_group_key(cells[0]) == batched_group_key(cells[2])
    out = run_batched_cells(cells)
    oracle = run_cell(_fleet(dispatcher=dispatcher, backend="oracle"))
    assert set(out[0]) == set(oracle) - {"devices"}
    for r in out:
        assert sum(r["dispatch_counts"]) == r["num_jobs"] and len(r["dispatch_counts"]) == 2
    assert out[0]["num_jobs"] == oracle["num_jobs"]
    assert out[0]["repartitions"] == oracle["repartitions"]
    assert out[0]["energy_wh"] == pytest.approx(oracle["energy_wh"], rel=0.03)


def test_one_device_fleet_is_the_single_gpu_run_bit_for_bit():
    fleet = run_batched_cells([_fleet(profiles=["a100-250w"], seed=s) for s in (4, 5)])
    single = run_batched_cells([_cell(seed=s, policy="daynight") | {
        "scenario": _fleet(seed=s)["scenario"]} for s in (4, 5)])
    for f, g in zip(fleet, single, strict=True):
        assert f == g


def test_stateful_policy_rejected_with_guidance():
    with pytest.raises(UnsupportedPolicyError, match="oracle backend|oracle"):
        run_batched_cells([_cell(policy="heuristic")])


def test_policy_factory_rejected_on_batched_cells():
    with pytest.raises(ValueError, match="policy_factory"):
        run_cell(_cell(), policy_factory=lambda: None)


# ----------------------------------------------------------------------
# execution: result schema, oracle agreement, runner grouping + cache


def test_run_cell_schema_matches_oracle_backend():
    oracle = run_cell(_cell(backend="oracle"))
    batched = run_cell(_cell(backend="batched"))
    assert set(batched) == set(oracle)
    assert batched["config_trace"] == []  # documented: no switch trace
    assert batched["num_jobs"] == oracle["num_jobs"]
    assert batched["repartitions"] == oracle["repartitions"]
    assert batched["energy_wh"] == pytest.approx(oracle["energy_wh"], rel=0.03)
    # the sweep aggregation path reconstructs a SimResult from either
    sr = result_to_sim_result(batched)
    assert sr.energy_wh == batched["energy_wh"]
    assert sr.extra["makespan_min"] > 0


def test_runner_groups_and_caches_batched_cells(tmp_path):
    cells = [_cell(seed=s) for s in range(4)]
    out = run_cells(
        "batched_grid", cells, cache=str(tmp_path / "cache"),
        artifacts_dir=str(tmp_path / "art"),
    )
    assert out.computed_count == 4 and out.cached_count == 0
    assert all(r["num_jobs"] > 0 for r in out.results)
    # per-seed rows must differ (a grouping bug that replays one seed B
    # times would make them identical)
    energies = [r["energy_wh"] for r in out.results]
    assert len(set(energies)) == len(energies)
    # vectorized grouping serves exactly what one-cell run_cell computes
    solo = run_cell(cells[2])
    assert out.results[2]["energy_wh"] == pytest.approx(
        solo["energy_wh"], rel=1e-6
    )
    again = run_cells(
        "batched_grid", cells, cache=str(tmp_path / "cache"),
        artifacts_dir=str(tmp_path / "art"),
    )
    assert again.cached_count == 4 and again.computed_count == 0
    assert again.results == out.results


def test_runner_mixes_backends_in_one_grid(tmp_path):
    cells = [
        _cell(seed=0, backend="oracle"),
        _cell(seed=0, backend="batched"),
        _cell(seed=1, backend="batched"),
    ]
    out = run_cells(
        "mixed_grid", cells, cache=False,
        artifacts_dir=str(tmp_path / "art"),
    )
    assert out.computed_count == 3
    assert out.results[0]["config_trace"] != []  # oracle keeps its trace
    assert out.results[1]["config_trace"] == []
    assert out.results[1]["energy_wh"] == pytest.approx(
        out.results[0]["energy_wh"], rel=0.03
    )


def test_batched_seed_determinism():
    a = run_batched_cells([_cell(seed=3)])[0]
    b = run_batched_cells([_cell(seed=3)])[0]
    for k in ("energy_wh", "avg_tardiness", "busy_slot_minutes",
              "preemptions", "repartitions", "util_histogram"):
        assert a[k] == b[k], k


def test_make_batched_env_factory():
    from repro.core.rl.env import make_batched_env

    env = make_batched_env(
        scenario="paper-diurnal", scenario_kwargs=_KW,
        decision_interval_min=120.0, max_decisions=2,
    )
    obs = env.reset(seeds=[0])
    assert obs.shape == (1, 2 + 2 * env.m)
    _, reward, _, _, _ = env.step([1])
    assert np.isfinite(reward).all()
