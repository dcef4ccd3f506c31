"""Tests of the chip benchmark (benchmarks/chip), on the CPU at tiny sizes.

The harness refuses to measure without a TPU; tests that drive a run steer
its platform check here (``run.PLATFORM``) and shrink a cell by writing a
copy of the benchmark's data files into a temporary root.
"""

from __future__ import annotations

import copy
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.chip import reference, run, sweep, tracing, traffic  # noqa: E402

BENCH = os.path.join(ROOT, "BENCHMARK.json")
CHIP = os.path.join(ROOT, "benchmarks", "chip")


def _bench() -> dict:
    with open(BENCH) as f:
        return json.load(f)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _config() -> dict:
    return _json(os.path.join(CHIP, "configs", "a100-mig-daynight-sweep.json"))


def _mix(name: str) -> dict:
    return _json(os.path.join(CHIP, "mixes", name + ".json"))


SWEEP_CELLS = [w["name"] for w in _bench()["workloads"]]


def _tiny_root(tmp_path, edit=None) -> str:
    """A root holding BENCHMARK.json and a copy of the benchmark's data with
    every mix cut to the first two hours of a day and every batch to 4 rows."""
    root = tmp_path / "root"
    shutil.copytree(CHIP, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = _bench()
    for path in (root / "benchmarks" / "chip" / "mixes").iterdir():
        mix = json.loads(path.read_text())
        mix["horizon_min"] = 120.0
        mix["program_scenario"]["kwargs"]["horizon_min"] = 120.0
        mix["padded_jobs"] = 96
        path.write_text(json.dumps(mix))
    for c in bench["configs"]:
        path = root / c["file"]
        cfg = json.loads(path.read_text())
        cfg["batch"] = 4
        path.write_text(json.dumps(cfg))
    if edit:
        edit(root, bench)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def _run_cell(monkeypatch, capsys, root, workload, trace=0, seed=2**33 + 5):
    monkeypatch.setattr(run, "PLATFORM", "cpu")
    rc = run.main(["--workload", workload, "--seed", str(seed), "--seconds", "0",
                   "--trace", str(trace)], root=root, program_root=ROOT)
    out = capsys.readouterr()
    return rc, json.loads(out.out.strip().splitlines()[-1]), out.err


# ------------------------------ refusals ------------------------------------


def test_command_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", SWEEP_CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "needs 1 tpu" in proc.stderr


def test_command_refuses_without_the_program(tmp_path):
    for p in _bench()["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(BENCH, tmp_path / "BENCHMARK.json")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", SWEEP_CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(env, JAX_PLATFORMS="cpu"), capture_output=True, text=True,
        timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout == ""


# ------------------------------ whole runs ----------------------------------


@pytest.mark.parametrize("workload", SWEEP_CELLS)
def test_cell_runs_end_to_end_at_a_tiny_size(tmp_path, monkeypatch, capsys, workload):
    root = _tiny_root(tmp_path)
    rc, result, err = _run_cell(monkeypatch, capsys, root, workload)
    assert rc == 0, err
    assert result["correct"] is True
    assert result["attempted"] == 4 and result["failed"] == 0
    assert set(result["metrics"]) == {"rollout_days_per_s", "setup_s"}
    assert result["metrics"]["rollout_days_per_s"]["value"] > 0
    assert result["device"]["platform"] == "cpu" and result["device"]["count"] == 1
    assert list(result)[-1] == "checks"
    limits = _json(os.path.join(CHIP, "cells", workload + ".json"))["limits"]
    assert set(result["checks"]) == set(limits)
    assert err.strip().splitlines()[-1].startswith("check ")


def test_files_added_to_the_benchmark_are_found_by_name(tmp_path, monkeypatch, capsys):
    """A configuration, a mix, a cell and a per-layer metric added as files."""

    def add(root, bench):
        chip = root / "benchmarks" / "chip"
        cfg = json.loads((chip / "configs" / "a100-mig-daynight-sweep.json").read_text())
        cfg["policy"]["day_config"] = 3
        (chip / "configs" / "a100-mig-daynight-3.json").write_text(json.dumps(cfg))
        mix = json.loads((chip / "mixes" / "paper-diurnal-x1.json").read_text())
        mix["load_scale"] = mix["program_scenario"]["kwargs"]["load_scale"] = 2.0
        (chip / "mixes" / "paper-diurnal-x2.json").write_text(json.dumps(mix))
        (chip / "cells" / "sweep-new.json").write_text(
            (chip / "cells" / "sweep-paper-load.json").read_text())
        (chip / "metrics" / "units_seen.sweep.py").write_text(
            "def read(run):\n    return float(run.units)\n")
        bench["configs"].append(dict(bench["configs"][0], name="a100-mig-daynight-3",
                                     file="benchmarks/chip/configs/a100-mig-daynight-3.json"))
        bench["workloads"].append({"name": "sweep-new", "config": "a100-mig-daynight-3",
                                   "traffic": "paper-diurnal-x2", "chips": 1, "why": "test"})
        bench["per_layer"].append({"name": "units_seen.sweep", "unit": "batches",
                                   "better": "higher", "source": "program_counter",
                                   "layer": "sweep front end", "moves": "rollout_days_per_s",
                                   "workloads": ["sweep-new"]})

    root = _tiny_root(tmp_path, add)
    rc, result, err = _run_cell(monkeypatch, capsys, root, "sweep-new", trace=1)
    assert rc == 0, err
    assert result["correct"] is True
    assert result["metrics"]["units_seen.sweep"] == {"value": 1.0, "unit": "batches"}
    assert result["device"]["window_s"] > 0 and "breakdown" in result


# ---------------------- the check: control and faults -----------------------


def _tiny_runner(mix_name: str, seed: int = 11):
    mix = copy.deepcopy(_mix(mix_name))
    mix["horizon_min"] = 180.0
    mix["program_scenario"]["kwargs"]["horizon_min"] = 180.0
    mix["padded_jobs"] = 128
    cfg = _config()
    cfg["batch"] = 4
    drv = sweep.Runner(cfg, mix, seed, tracing_annotation())
    drv.setup()
    drv.unit(0)
    return drv


def tracing_annotation():
    import contextlib

    return lambda name: contextlib.nullcontext()


@pytest.mark.parametrize("workload", SWEEP_CELLS)
def test_control_fails_the_check(workload):
    """The reference with EDF replaced by arrival order, in the program's place."""
    cell = next(w for w in _bench()["workloads"] if w["name"] == workload)
    limits = _json(os.path.join(CHIP, "cells", workload + ".json"))["limits"]
    drv = _tiny_runner(cell["traffic"])
    control = drv.reference_rows("fifo")
    for row in control:
        row["inputs"] = sweep.inputs_of(row["day"])
    worst, failed = sweep.gaps(control, drv.reference_rows("edf"), limits)
    assert failed > 0, worst
    sound, sound_failed = sweep.gaps(drv.program_rows(), drv.reference_rows("edf"), limits)
    assert sound_failed == 0, sound


def _unchanged_state(monkeypatch):
    from repro.core.batched import backend

    monkeypatch.setattr(backend, "run_steps", lambda state, *a, **k: state)


def _half_batch_left_out(monkeypatch):
    from repro.core.batched import state

    real = state.BatchedJobs.from_job_lists.__func__

    def half(cls, job_lists, **kw):
        job_lists = list(job_lists)
        h = len(job_lists) // 2
        return real(cls, job_lists[:h] * 2, **kw)

    monkeypatch.setattr(state.BatchedJobs, "from_job_lists", classmethod(half))


def _energy_altered(monkeypatch):
    from repro.core.batched import backend

    real = backend.run_steps

    def altered(*a, **k):
        st = real(*a, **k)
        return st._replace(energy_wh=st.energy_wh * 1.01)

    monkeypatch.setattr(backend, "run_steps", altered)


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch_left_out, _energy_altered])
def test_a_broken_timed_path_is_not_correct(tmp_path, monkeypatch, capsys, fault):
    root = _tiny_root(tmp_path)
    fault(monkeypatch)
    rc, result, _ = _run_cell(monkeypatch, capsys, root, SWEEP_CELLS[0])
    assert rc != 0
    assert result["correct"] is False


# ------------------------------ traffic -------------------------------------


@pytest.mark.parametrize("mix_name", sorted({w["traffic"] for w in _bench()["workloads"]}))
def test_fixed_job_axis_holds_every_checked_day(mix_name):
    """Every day of the seeds the limits were set from fits the mix's J."""
    mix = _mix(mix_name)
    limits = _json(os.path.join(CHIP, "cells", next(
        w["name"] for w in _bench()["workloads"] if w["traffic"] == mix_name) + ".json"))
    longest = 0
    for seed in limits["seeds"][:2]:
        for k in range(2):
            for b in range(_config()["batch"]):
                day = traffic.generate_day(mix, sweep.row_seed(seed, k, b), 7)
                longest = max(longest, day.num_jobs)
    assert 0 < longest <= mix["padded_jobs"]


@pytest.mark.parametrize("mix_name", ["paper-diurnal-x1", "paper-diurnal-x4"])
def test_generator_makes_the_programs_days(mix_name):
    from repro.core.batched import BatchedJobs
    from repro.sweep.cells import cell_jobs

    mix = _mix(mix_name)
    for seed in (3, 2**40 + 1):
        day = traffic.generate_day(mix, seed, 7)
        scen = mix["program_scenario"]
        jobs = BatchedJobs.from_job_lists(
            [cell_jobs({"scenario": scen, "seed": seed})], max_slots=7)
        got = {"arrival": jobs.arrival[0], "deadline": jobs.deadline[0],
               "work": jobs.work[0], "rate_by_slots": jobs.rate_by_slots[0]}
        want = sweep.inputs_of(day)
        for f, arr in want.items():
            np.testing.assert_array_equal(got[f][: day.num_jobs], arr)


# ----------------------------- the reference --------------------------------

# docs/BATCHED_SIM.md sec. 4: how far a grid rollout may drift from the
# event-driven oracle (a copy of repro.core.batched.agreement's table)
ENERGY_RTOL = 0.03
TARDINESS_ATOL_MIN, TARDINESS_RTOL, TARDINESS_FLOOR = 0.15, 0.5, 0.25
BUSY_RTOL, BUSY_ATOL_MIN = 0.025, 1.0
PREEMPTIONS_RTOL, PREEMPTIONS_FLOOR = 0.4, 10.0


def test_reference_agrees_with_the_oracle_engine():
    """A second witness for the reference: the repo's event-driven oracle."""
    from repro.sweep.cells import CellSpec, result_to_sim_result, run_cell

    cfg = _config()
    dev = reference.build_device(cfg)
    mix = _mix("paper-diurnal-x1")
    for seed in (0, 1):
        ref = reference.simulate(traffic.generate_day(mix, seed, 7), cfg, dev)
        cell = CellSpec(experiment="t", group="oracle", scheduler="EDF-FS", seed=seed,
                        scenario="paper-diurnal", scenario_kwargs={"load_scale": 1.0},
                        policy="daynight", repartition_mode="partial").to_cell()
        o = result_to_sim_result(run_cell(cell))
        avg = ref["total_tardiness"] / ref["num_jobs"]
        assert ref["num_jobs"] == o.num_jobs
        assert ref["repartitions"] == o.repartitions
        assert abs(ref["energy_wh"] - o.energy_wh) <= ENERGY_RTOL * o.energy_wh
        assert abs(avg - o.avg_tardiness) <= max(
            TARDINESS_ATOL_MIN, TARDINESS_RTOL * max(o.avg_tardiness, TARDINESS_FLOOR))
        assert abs(ref["busy_slot_minutes"] - o.busy_slot_minutes) <= max(
            BUSY_RTOL * o.busy_slot_minutes, BUSY_ATOL_MIN)
        assert abs(ref["preemptions"] - o.preemptions) <= PREEMPTIONS_RTOL * max(
            o.preemptions, PREEMPTIONS_FLOOR)


def test_device_tables_match_the_programs():
    from repro.core.batched import build_tables

    dev = reference.build_device(_config())
    t = build_tables()
    for c, cid in enumerate(t.config_ids.tolist()):
        n = int(t.num_slices[c])
        assert dev.slots[cid] == t.slice_slots[c, :n].tolist()
        assert dev.rank[cid] == t.slice_rank[c, :n].tolist()
        for b, bid in enumerate(t.config_ids.tolist()):
            want = {s: int(t.old_to_new[c, b, s]) for s in range(n) if t.old_to_new[c, b, s] >= 0}
            assert dev.survivor[(cid, bid)] == want
    assert dev.watts == t.watts_by_busy.tolist()


# ----------------------------- trace reduction ------------------------------


def test_trace_reduction_on_a_small_trace():
    raw = tracing.load_json(os.path.join(CHIP, "testdata", "trace_small.json"))
    trace = tracing.reduce(raw, sweep.SPANS)
    assert trace.window == (0, 10_000_000)
    assert trace.busy_ns(0) == 4_800_000
    info = {"chunk_steps": 512, "chunk_program": "run_chunk",
            "host_prep_spans": ("generate", "pad", "compile_policy")}
    traced = run.TracedRun(trace, units=1, work=64, window_s=0.01, info=info)
    expect = {
        "scan_step_device_us.sweep": 4700.0 / (3 * 512),
        "chunk_boundary_gap_ms.sweep": 0.35,
        "host_prep_ms_per_batch.sweep": 3.5,
        "device_idle_share.sweep": 52.0,
    }
    for name, value in expect.items():
        assert run.read_metric(CHIP, name, traced) == pytest.approx(value)
    bd = tracing.breakdown(trace)
    # fusion.1 at 4.0-5.0 ms encloses another op, so only its leaves count
    assert bd["device_ops"] == [["fusion.1", pytest.approx(0.0029)],
                                ["scatter.2", pytest.approx(0.001)],
                                ["convert", pytest.approx(0.0001)]]
    assert [n for n, _ in bd["idle_gaps"]] == ["generate", "result", "simulate", "simulate",
                                               "simulate"]
    assert bd["idle_gaps"][0][1] == pytest.approx(0.0036)


def test_readers_return_nothing_without_device_events():
    raw = tracing.load_json(os.path.join(CHIP, "testdata", "trace_small.json"))
    raw["planes"] = [p for p in raw["planes"] if not p["name"].startswith("/device:")]
    trace = tracing.reduce(raw, sweep.SPANS)
    traced = run.TracedRun(trace, 1, 64, 0.01, {"chunk_steps": 512, "chunk_program": "run_chunk",
                                               "host_prep_spans": ("generate",)})
    for name in ("scan_step_device_us.sweep", "chunk_boundary_gap_ms.sweep",
                 "device_idle_share.sweep"):
        assert run.read_metric(CHIP, name, traced) is None


def test_benchmark_file_keeps_the_contracts_names_and_units():
    import re

    bench = _bench()
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    metrics = bench["end_to_end"] + bench["per_layer"]
    for m in metrics:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert set(m["workloads"]) <= cells
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
        assert os.path.isfile(os.path.join(CHIP, "metrics", m["name"] + ".py"))
    for w in bench["workloads"]:
        assert name.match(w["name"]) and name.match(w["traffic"])
        assert os.path.isfile(os.path.join(CHIP, "mixes", w["traffic"] + ".json"))
        assert os.path.isfile(os.path.join(CHIP, "cells", w["name"] + ".json"))
