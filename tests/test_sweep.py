"""Sweep engine: hashing determinism, cache behavior, worker independence."""

import concurrent.futures
import json
import multiprocessing
import os

import pytest

from repro.core.schedulers import make_scheduler
from repro.core.simulator import MIGSimulator, StaticPolicy
from repro.core.workload import WorkloadSpec, generate_jobs
from repro.sweep import (
    GRIDS,
    StaleCacheError,
    SweepCache,
    cell_hash,
    make_cell,
    make_scenario_cell,
    result_to_sim_result,
    run_cell,
    run_cells,
    run_grid,
)

TINY = WorkloadSpec(horizon_min=90.0, constant_rate=0.2)


def _tiny_cells(n_seeds=4, experiment="t", group="EDF-SS"):
    return [
        make_cell(
            experiment=experiment,
            group=group,
            scheduler="EDF-SS",
            workload=TINY,
            seed=s,
            policy="static",
            policy_kwargs={"config_id": 3},
        )
        for s in range(n_seeds)
    ]


# ----------------------------------------------------------------------
# hashing


def test_cell_hash_deterministic_and_content_addressed():
    a, b = _tiny_cells(1)[0], _tiny_cells(1)[0]
    assert cell_hash(a) == cell_hash(b)
    c = dict(a, seed=99)
    assert cell_hash(c) != cell_hash(a)
    d = dict(a, scheduler="LLF")
    assert cell_hash(d) != cell_hash(a)
    e = dict(a, policy_kwargs={"config_id": 4})
    assert cell_hash(e) != cell_hash(a)


def test_dqn_cells_hash_weights_content_not_just_path(tmp_path):
    params = tmp_path / "dqn_params.npz"
    params.write_bytes(b"weights-v1")
    kw = {"params_path": str(params)}
    cell_v1 = make_cell(
        experiment="t", group="dqn", scheduler="EDF-SS", workload=TINY,
        seed=0, policy="dqn", policy_kwargs=kw,
    )
    params.write_bytes(b"weights-v2-retrained")
    cell_v2 = make_cell(
        experiment="t", group="dqn", scheduler="EDF-SS", workload=TINY,
        seed=0, policy="dqn", policy_kwargs=kw,
    )
    assert cell_hash(cell_v1) != cell_hash(cell_v2), (
        "retrained weights at the same path must invalidate the cache"
    )
    # the digest is a hash-only annotation; factories never see it
    from repro.sweep import make_policy

    assert make_policy("static", {"config_id": 2, "_params_digest": "x"}).initial_config == 2


def test_cell_hash_ignores_grid_labels_but_not_sim_version():
    a = _tiny_cells(1, experiment="x", group="g1")[0]
    b = _tiny_cells(1, experiment="y", group="g2")[0]
    assert cell_hash(a) == cell_hash(b)  # same physics, different labels
    assert cell_hash(a, sim_version="other") != cell_hash(a)


# ----------------------------------------------------------------------
# run_cell matches a direct simulator run


def test_run_cell_matches_direct_simulation():
    cell = _tiny_cells(1)[0]
    got = result_to_sim_result(run_cell(cell))
    sim = MIGSimulator(make_scheduler("EDF-SS"))
    want = sim.run(generate_jobs(TINY, seed=0), policy=StaticPolicy(3))
    assert got.energy_wh == want.energy_wh
    assert got.avg_tardiness == want.avg_tardiness
    assert got.preemptions == want.preemptions
    assert got.num_jobs == want.num_jobs
    assert got.extra["makespan_min"] == want.extra["makespan_min"]


# ----------------------------------------------------------------------
# cache


def test_cache_hit_miss_and_resume(tmp_path):
    cache_dir = str(tmp_path / "cache")
    cells = _tiny_cells(3)

    out1 = run_cells("t", cells, cache=cache_dir, artifacts_dir=None)
    assert (out1.cached_count, out1.computed_count) == (0, 3)

    out2 = run_cells("t", cells, cache=cache_dir, artifacts_dir=None)
    assert (out2.cached_count, out2.computed_count) == (3, 0)
    assert out2.results == out1.results

    # --no-resume recomputes but results stay identical
    out3 = run_cells("t", cells, cache=cache_dir, resume=False, artifacts_dir=None)
    assert (out3.cached_count, out3.computed_count) == (0, 3)
    assert out3.results == out1.results

    # a new cell is a miss; old cells still hit
    out4 = run_cells("t", _tiny_cells(4), cache=cache_dir, artifacts_dir=None)
    assert (out4.cached_count, out4.computed_count) == (3, 1)


def test_cache_rejects_torn_and_foreign_entries(tmp_path):
    cache = SweepCache(str(tmp_path))
    cell = _tiny_cells(1)[0]
    h = cell_hash(cell)
    assert cache.get(h) is None  # miss on empty

    cache.put(h, cell, {"energy_wh": 1.0})
    assert cache.get(h) == {"energy_wh": 1.0}

    # torn write -> treated as a miss, not a crash
    with open(cache._path(h), "w") as f:
        f.write('{"sim_version": "mig-sim')
    assert cache.get(h) is None

    # hand-copied entry from a different simulator version at the current
    # version's path -> miss (the payload check backs up the filename)
    with open(cache._path(h), "w") as f:
        json.dump({"sim_version": "ancient", "cell": cell, "result": {}}, f)
    assert cache.get(h) is None


def test_ad_hoc_policy_bypasses_cache(tmp_path):
    cache_dir = str(tmp_path / "cache")
    cells = _tiny_cells(2)
    out = run_cells(
        "t", cells, cache=cache_dir, artifacts_dir=None,
        policy_factory=lambda: StaticPolicy(3),
    )
    assert out.computed_count == 2
    assert len(SweepCache(cache_dir)) == 0  # nothing persisted


def test_resume_refuses_stale_sim_version(tmp_path):
    """Regression: --resume after a semantics change must refuse, not mix.

    A cache directory holding cells recorded under a different SIM_VERSION
    (e.g. populated before a bump, or hand-copied) raises StaleCacheError on
    resume; --no-resume and purge_stale() are the documented ways out.
    """
    cache_dir = str(tmp_path / "cache")
    cells = _tiny_cells(2)
    run_cells("t", cells, cache=cache_dir, artifacts_dir=None)

    # plant entries from a pre-bump version and from the pre-versioned-
    # filename era; both must trip the refusal
    with open(os.path.join(cache_dir, "0" * 64 + ".mig-sim-0.json"), "w") as f:
        json.dump({"sim_version": "mig-sim-0", "cell": {}, "result": {}}, f)
    with open(os.path.join(cache_dir, "1" * 64 + ".json"), "w") as f:
        json.dump({"sim_version": "mig-sim-0", "cell": {}, "result": {}}, f)

    with pytest.raises(StaleCacheError, match="different\\s+simulator version"):
        run_cells("t", cells, cache=cache_dir, artifacts_dir=None)
    # the error names the escape hatches
    with pytest.raises(StaleCacheError, match="purge-stale-cache"):
        run_cells("t", cells, cache=cache_dir, artifacts_dir=None)

    # --no-resume bypasses the cache read and still completes — and must NOT
    # disarm the refusal on the next resume
    out = run_cells("t", cells, cache=cache_dir, artifacts_dir=None, resume=False)
    assert out.computed_count == 2
    with pytest.raises(StaleCacheError):
        run_cells("t", cells, cache=cache_dir, artifacts_dir=None)

    # purging removes exactly the two foreign entries, then resume works
    assert SweepCache(cache_dir).purge_stale() == 2
    out2 = run_cells("t", cells, cache=cache_dir, artifacts_dir=None)
    assert (out2.cached_count, out2.computed_count) == (2, 0)


def test_clean_cache_resume_still_works(tmp_path):
    """The version check must not break ordinary warm-cache resumes."""
    cache_dir = str(tmp_path / "cache")
    cells = _tiny_cells(3)
    run_cells("t", cells, cache=cache_dir, artifacts_dir=None)
    out = run_cells("t", cells, cache=cache_dir, artifacts_dir=None)
    assert (out.cached_count, out.computed_count) == (3, 0)


def test_cli_purge_without_grid_is_purge_only(tmp_path, capsys):
    """The StaleCacheError remediation command must purge and exit, not
    launch the default full-scale sweep."""
    from repro.sweep.__main__ import main

    cache_dir = str(tmp_path / "cache")
    os.makedirs(cache_dir)
    with open(os.path.join(cache_dir, "a" * 64 + ".mig-sim-0.json"), "w") as f:
        json.dump({"sim_version": "mig-sim-0", "cell": {}, "result": {}}, f)
    rc = main(["--purge-stale-cache", "--cache-dir", cache_dir])
    assert rc == 0
    assert len(SweepCache(cache_dir)) == 0
    out = capsys.readouterr()
    assert "purged 1" in out.err
    assert "###" not in out.out, "no grid must have run"


def test_cli_check_baseline_rejects_multiple_grids(tmp_path):
    from repro.sweep.__main__ import main

    baseline = tmp_path / "b.jsonl"
    baseline.write_text("")
    with pytest.raises(SystemExit):
        main(["smoke", "fleet_scaling", "--check-baseline", str(baseline)])


# ----------------------------------------------------------------------
# scenario cells


def test_scenario_cell_resolves_defaults_and_hashes_on_them():
    a = make_scenario_cell(
        experiment="t", group="g", scheduler="EDF-SS",
        scenario="weekend-flat", seed=0,
    )
    assert a["scenario"]["kwargs"]["rate_per_min"] == 0.15  # default resolved
    b = make_scenario_cell(
        experiment="t", group="g", scheduler="EDF-SS",
        scenario="weekend-flat", seed=0, scenario_kwargs={"rate_per_min": 0.3},
    )
    assert cell_hash(a) != cell_hash(b)
    with pytest.raises(KeyError):
        make_scenario_cell(
            experiment="t", group="g", scheduler="EDF-SS",
            scenario="weekend-flat", seed=0, scenario_kwargs={"bogus": 1},
        )


def test_paper_diurnal_scenario_cell_matches_workload_cell_results():
    """Scenario cells and raw-spec cells describe the same physics for the
    paper workload — their results must agree exactly."""
    spec_cell = make_cell(
        experiment="t", group="g", scheduler="EDF-SS",
        workload=WorkloadSpec(), seed=4,
        policy="static", policy_kwargs={"config_id": 3},
    )
    scen_cell = make_scenario_cell(
        experiment="t", group="g", scheduler="EDF-SS",
        scenario="paper-diurnal", seed=4,
        policy="static", policy_kwargs={"config_id": 3},
    )
    a, b = run_cell(spec_cell), run_cell(scen_cell)
    for k in ("energy_wh", "avg_tardiness", "num_jobs", "preemptions", "extra"):
        assert a[k] == b[k], k


# ----------------------------------------------------------------------
# worker-count independence + artifacts


def test_worker_count_independence_and_jsonl_artifact(tmp_path):
    cells = [
        make_cell(
            experiment="t",
            group=n,
            scheduler=n,
            workload=TINY,
            seed=s,
            policy="static",
            policy_kwargs={"config_id": cfg},
        )
        for n in ("EDF-SS", "LLF")
        for cfg in (2, 3)
        for s in range(2)
    ]
    a1 = str(tmp_path / "a1")
    a4 = str(tmp_path / "a4")
    out1 = run_cells("grid", cells, workers=1, cache=False, artifacts_dir=a1)
    out4 = run_cells("grid", cells, workers=4, cache=False, artifacts_dir=a4)

    assert out1.results == out4.results
    b1 = open(os.path.join(a1, "grid.jsonl"), "rb").read()
    b4 = open(os.path.join(a4, "grid.jsonl"), "rb").read()
    assert b1 == b4, "JSONL artifact must not depend on worker count"

    lines = [json.loads(x) for x in b1.decode().splitlines()]
    assert len(lines) == len(cells)
    assert all(set(rec) == {"hash", "cell", "result"} for rec in lines)
    # grid order is preserved
    assert [rec["cell"]["seed"] for rec in lines] == [c["seed"] for c in cells]
    # volatile timing never leaks into the artifact
    assert all("elapsed_s" not in rec["result"] for rec in lines)


def _worker_jax_platforms(cell, policy_factory=None):
    import jax

    return {"env": os.environ.get("JAX_PLATFORMS"),
            "config": jax.config.jax_platforms}


def test_pool_workers_hold_jax_to_cpu(monkeypatch):
    """Pool workers run host work: their JAX must not ask for the
    accelerator the parent process holds (a dqn cell builds a learner).
    The workers' cell runner is swapped for one that reports the platform
    its JAX was given."""
    from repro.sweep import runner

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(runner, "run_cell", _worker_jax_platforms)
    out = run_cells("t", _tiny_cells(2), workers=2, cache=False,
                    artifacts_dir=None)
    assert out.results == [{"env": "cpu", "config": "cpu"}] * 2


def test_parallel_failure_reports_cell(tmp_path):
    bad = _tiny_cells(2)
    bad[1]["policy"] = "nonexistent-policy"
    with pytest.raises(Exception, match="nonexistent-policy"):
        run_cells("t", bad, workers=2, cache=False, artifacts_dir=None)


# ----------------------------------------------------------------------
# baseline gate (CI)


def test_check_baseline_detects_drift(tmp_path):
    from repro.sweep.__main__ import check_baseline

    cells = _tiny_cells(2)
    out = run_cells("base", cells, cache=False, artifacts_dir=str(tmp_path))
    baseline = str(tmp_path / "baseline.jsonl")
    import shutil

    shutil.copy(out.jsonl_path, baseline)
    assert check_baseline(out.jsonl_path, baseline, rtol=1e-9) == 0

    # perturb one result -> exactly one mismatch
    lines = [json.loads(x) for x in open(baseline)]
    lines[0]["result"]["energy_wh"] *= 1.001
    with open(baseline, "w") as f:
        for rec in lines:
            f.write(json.dumps(rec) + "\n")
    assert check_baseline(out.jsonl_path, baseline, rtol=1e-9) == 1
    # ...which a loose tolerance forgives
    assert check_baseline(out.jsonl_path, baseline, rtol=0.01) == 0


# ----------------------------------------------------------------------
# grids registry


def test_grids_build_and_smoke_aggregates(tmp_path):
    for name, grid in GRIDS.items():
        cells = grid.build(0.1)
        assert cells, name
        hashes = {cell_hash(c) for c in cells}
        assert len(hashes) == len(cells), f"{name}: duplicate cells"

    rows, outcome = run_grid(
        "smoke", scale=0.05, workers=0,
        cache=str(tmp_path / "c"), artifacts_dir=str(tmp_path / "a"),
    )
    assert [r["algorithm"] for r in rows] == ["EDF-FS", "EDF-SS", "LLF", "LALF"]
    assert all(r["ET"] >= 0 for r in rows)
    assert os.path.exists(outcome.jsonl_path)

    # warm rerun serves everything from cache
    rows2, outcome2 = run_grid(
        "smoke", scale=0.05, workers=0,
        cache=str(tmp_path / "c"), artifacts_dir=str(tmp_path / "a"),
    )
    assert rows2 == rows
    assert outcome2.computed_count == 0


# ----------------------------------------------------------------------
# CellSpec: the unified cell constructor must not move a single hash


def test_cellspec_preserves_baseline_hashes():
    """Every checked-in baseline cell must be rebuildable through the
    ``CellSpec`` path at exactly its recorded hash — the regression pin
    behind collapsing the three legacy constructors into one dataclass."""
    base = os.path.join(
        os.path.dirname(__file__), "..", "benchmarks", "baselines"
    )
    grid_for = {
        "smoke_sweep.jsonl": "smoke",
        "fleet_scaling.jsonl": "fleet_scaling",
        "scenario_matrix.jsonl": "scenario_matrix",
        "repartition_policies.jsonl": "repartition_policies",
        "dispatchers.jsonl": "dispatchers",
        "repartition_modes.jsonl": "repartition_modes",
        "serving_matrix.jsonl": "serving_matrix",
    }
    checked = 0
    for fname, grid in grid_for.items():
        path = os.path.join(base, fname)
        assert os.path.exists(path), f"baseline {fname} missing"
        with open(path) as f:
            want = {
                json.loads(line)["hash"] for line in f if line.strip()
            }
        built = {cell_hash(c) for c in GRIDS[grid].build(0.1)}
        missing = want - built
        assert not missing, f"{fname}: {len(missing)} baseline hashes moved"
        checked += len(want)
    assert checked >= 100  # the pin is only meaningful on the full basket


def test_cellspec_validates_field_combinations():
    from repro.sweep.cells import CellSpec

    ok = CellSpec(
        experiment="t", group="g", scheduler="EDF-SS", seed=1,
        workload=TINY,
    )
    legacy = make_cell(
        experiment="t", group="g", scheduler="EDF-SS", seed=1, workload=TINY,
    )
    assert ok.to_cell() == legacy  # wrappers and direct spec agree exactly

    with pytest.raises(ValueError, match="exactly one job stream"):
        CellSpec(experiment="t", group="g", scheduler="EDF-SS", seed=1).to_cell()
    with pytest.raises(ValueError, match="exactly one job stream"):
        CellSpec(
            experiment="t", group="g", scheduler="EDF-SS", seed=1,
            workload=TINY, scenario="weekend-flat",
        ).to_cell()
    with pytest.raises(ValueError, match="scenario_kwargs"):
        CellSpec(
            experiment="t", group="g", scheduler="EDF-SS", seed=1,
            workload=TINY, scenario_kwargs={"load_scale": 2.0},
        ).to_cell()
    with pytest.raises(ValueError, match="dispatcher"):
        CellSpec(
            experiment="t", group="g", scheduler="EDF-SS", seed=1,
            scenario="weekend-flat", fleet_profiles=["a100-250w"],
        ).to_cell()
    with pytest.raises(ValueError, match="fleet cells"):
        CellSpec(
            experiment="t", group="g", scheduler="EDF-SS", seed=1,
            workload=TINY, dispatcher="round-robin",
        ).to_cell()
    with pytest.raises(ValueError, match="oracle"):
        CellSpec(
            experiment="t", group="g", scheduler="EDF-SS", seed=1,
            scenario="weekend-flat", fleet_profiles=["a100-250w"],
            dispatcher="energy-greedy", backend="batched",
        ).to_cell()
    batched_fleet = CellSpec(  # a homogeneous round-robin fleet runs batched
        experiment="t", group="g", scheduler="EDF-SS", seed=1,
        scenario="weekend-flat", fleet_profiles=["a100-250w"],
        dispatcher="round-robin", backend="batched",
    ).to_cell()
    assert batched_fleet["backend"] == "batched" and "fleet" in batched_fleet
