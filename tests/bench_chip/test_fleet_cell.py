"""The fleet cell of the chip benchmark (``fleet8-paper-load``), on the CPU.

The cell runs at a tiny size (the first two hours of a day, 4 rows of
J=96, eight GPUs); its check holds the program to the fleet reference
(``reference_fleet.py``), fails the round-robin control, a program that
routes by GPU index alone and one that sends equal backlogs to the highest
index; its per-layer readers read a synthetic trace and record, and
nothing where there is nothing to read.
"""

from __future__ import annotations

import contextlib
import copy
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.chip import fleet_sweep, reference, reference_fleet, run, tracing, traffic  # noqa: E402
from repro import obs  # noqa: E402
from repro.core.batched import backend  # noqa: E402

CHIP = os.path.join(ROOT, "benchmarks", "chip")
CELL = "fleet8-paper-load"
#: the fleet readers that read what their ``.sweep`` twin reads, in the fleet cell
TWINS = ("scan_step_device_us", "device_idle_share", "host_prep_ms_per_batch",
         "job_build_ms_per_batch", "writeback_device_us", "chunk_upload_ms", "h2d_mb_per_chunk",
         "chunk_boundary_gap_ms")
READERS = ("dispatch_device_us.fleet", "edf_rank_device_us.fleet",
           *(t + ".fleet" for t in TWINS))
MS = 1_000_000


def _json(*parts):
    with open(os.path.join(CHIP, *parts)) as f:
        return json.load(f)


def _config():
    return _json("configs", "a100x8-mig-daynight-fleet.json")


def _limits():
    return _json("cells", CELL + ".json")["limits"]


def _runner(seed=11, horizon=120.0, batch=4):
    mix = copy.deepcopy(_json("mixes", "paper-diurnal-x8.json"))
    mix["horizon_min"] = mix["program_scenario"]["kwargs"]["horizon_min"] = horizon
    mix["padded_jobs"] = 128
    cfg = _config()
    cfg["batch"] = batch
    drv = fleet_sweep.Runner(cfg, mix, seed, lambda name: contextlib.nullcontext())
    drv.setup()
    drv.unit(0)
    return drv


@pytest.fixture
def fresh_step():
    """Step programs built inside the test, and dropped after it."""
    backend.make_step_fn.cache_clear()
    backend._chunk_fn.cache_clear()
    yield
    backend.make_step_fn.cache_clear()
    backend._chunk_fn.cache_clear()


def _routing_by_index_alone(monkeypatch):
    import jax.numpy as jnp

    monkeypatch.setattr(backend, "least_loaded", lambda backlog, peak: jnp.int32(0))


def _equal_backlogs_to_the_highest_index(monkeypatch):
    import jax.numpy as jnp

    def highest(backlog, peak):
        return (backlog.shape[0] - 1 - jnp.argmin((backlog / peak)[::-1])).astype(jnp.int32)

    monkeypatch.setattr(backend, "least_loaded", highest)


# ------------------------------ whole runs ----------------------------------


def test_fleet_cell_is_correct_at_a_tiny_size(tmp_path, monkeypatch, capsys):
    from test_chipbench import _run_cell, _tiny_root

    rc, result, err = _run_cell(monkeypatch, capsys, _tiny_root(tmp_path), CELL)
    assert rc == 0, err
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["checks"]) == set(_limits()) >= {"dispatch_differ"}
    assert result["checks"]["dispatch_differ"]["value"] == 0


def test_planted_fault_routing_by_index_alone_is_not_correct(tmp_path, monkeypatch, capsys,
                                                             fresh_step):
    from test_chipbench import _run_cell, _tiny_root

    _routing_by_index_alone(monkeypatch)
    rc, result, _ = _run_cell(monkeypatch, capsys, _tiny_root(tmp_path), CELL)
    assert rc != 0 and result["correct"] is False
    assert result["checks"]["dispatch_differ"]["value"] > 0.5


def test_planted_fault_equal_backlogs_to_the_highest_index_is_not_correct(
        tmp_path, monkeypatch, capsys, fresh_step):
    """Idle GPUs tie at backlog 0 all through the night: the lowest index
    takes them, and the reference does not follow the program there."""
    from test_chipbench import _run_cell, _tiny_root

    _equal_backlogs_to_the_highest_index(monkeypatch)
    rc, result, _ = _run_cell(monkeypatch, capsys, _tiny_root(tmp_path), CELL)
    assert rc != 0 and result["correct"] is False
    assert result["checks"]["dispatch_differ"]["value"] > 0.1


def test_round_robin_control_is_not_correct():
    """The fleet reference with round-robin dispatch, in the program's place."""
    drv = _runner()
    worst, failed = drv.control_check(_limits())
    assert failed == len(drv.samples) and worst["dispatch_differ"] > 0.1, worst
    sound, sound_failed = drv.check(_limits())
    assert sound_failed == 0, sound
    for got in drv.program_rows():
        assert len(set(got["device"].tolist())) > 1


# ----------------------------- the reference --------------------------------


def test_near_tie_is_a_float32_rounding_of_the_backlogs_and_never_an_equal_one():
    unit = reference_fleet.TIE_ULPS * reference_fleet.EPS32
    tie = reference_fleet.near_tie
    assert not tie([10.0, 10.0], [1, 1], 1, 0)  # equal: the lowest index takes them
    assert not tie([0.0, 0.0], [0, 0], 1, 0)  # two idle GPUs
    assert tie([10.0, 10.0 + 15 * unit], [1, 1], 1, 0)  # within 10 + 10 units
    assert not tie([10.0, 10.0 + 25 * unit], [1, 1], 1, 0)
    assert tie([10.0, 10.0 + 25 * unit], [1, 2], 1, 0)  # within 10 + 20 units
    assert not tie([10.0, 12.0], [1, 1], 1, 0)


def test_fleet_reference_on_one_gpu_is_the_single_gpu_reference():
    cfg = dict(_config(), devices=1)
    dev = reference.build_device(cfg)
    mix = _json("mixes", "paper-diurnal-x1.json")
    day = traffic.generate_day(mix, 5, dev.max_slots)
    one, fleet = reference.simulate(day, cfg, dev), reference_fleet.simulate(day, cfg, dev)
    assert (fleet["device"] == 0).all()
    for k, v in one.items():
        assert np.array_equal(fleet[k], v), k


def test_fleet_reference_agrees_with_the_oracle_fleet():
    """A second witness for the fleet reference: the repo's FleetSimulator,
    within the agreement table of docs/BATCHED_SIM.md sec. 4."""
    from repro.core.batched.agreement import agreement_failures
    from repro.core.metrics import SimResult
    from repro.fleet import FleetSimulator, FleetSpec
    from repro.sweep.cells import cell_jobs, make_policy

    cfg = dict(_config(), devices=3)
    dev = reference.build_device(cfg)
    mix = copy.deepcopy(_json("mixes", "paper-diurnal-x8.json"))
    mix["horizon_min"] = 180.0
    mix["load_scale"] = 3.0
    scen = {"name": "paper-diurnal", "kwargs": {"load_scale": 3.0, "horizon_min": 180.0}}
    pol = cfg["policy"]
    for seed in (0, 1):
        ref = reference_fleet.simulate(traffic.generate_day(mix, seed, 7), cfg, dev)
        fleet = FleetSimulator(FleetSpec.of(["a100-250w"] * 3, dispatcher="least-loaded",
                                            scheduler="EDF-FS"))
        o = fleet.run(cell_jobs({"scenario": scen, "seed": seed}), lambda i, p: make_policy(
            "daynight", {"day_config": pol["day_config"], "night_config": pol["night_config"]}
        )).aggregate
        n = ref["num_jobs"]
        got = SimResult(
            energy_wh=ref["energy_wh"], avg_tardiness=ref["total_tardiness"] / n, num_jobs=n,
            total_tardiness=ref["total_tardiness"], preemptions=ref["preemptions"],
            repartitions=ref["repartitions"], max_tardiness=0.0, deadline_misses=0,
            busy_slot_minutes=ref["busy_slot_minutes"])
        assert agreement_failures(got, o) == [], seed


# ----------------------------- the readers ----------------------------------


INFO = {"chunk_steps": 512, "chunk_program": "run_chunk",
        "host_prep_spans": ("generate", "pad", "compile_policy")}
SCOPES = {"fusion.1": "dispatch", "while.9": "dispatch", "fusion.2": "edf_rank",
          "fusion.3": "writeback", "while.1": ""}


def _trace(device=True) -> tracing.Trace:
    trace = tracing.Trace(window=(0, 10 * MS))
    if device:
        ops = [  # two chunk executions; the scan's loop op encloses its body
            ("%while.1", 1 * MS, 2 * MS), ("%fusion.1", 1 * MS, MS // 10),
            ("%fusion.2", 1.2 * MS, MS // 2), ("%fusion.3", 1.8 * MS, MS // 10),
            ("%while.1", 4 * MS, 2 * MS), ("%fusion.1", 4 * MS, 0.3 * MS),
            ("%fusion.2", 4.5 * MS, 0.7 * MS),
            ("%fusion.1", 8 * MS, MS // 2),  # another program's op of the same name
        ]
        trace.ops[0] = tracing.Ops.of(ops)
        trace.modules[0] = [tracing.Event("jit_run_chunk", 1 * MS, 3 * MS),
                            tracing.Event("jit_run_chunk", 4 * MS, 6 * MS),
                            tracing.Event("jit_other", 8 * MS, 9 * MS)]
        trace.spans = [tracing.Event("batch", 0, 9 * MS), tracing.Event("generate", 0, MS // 2),
                       tracing.Event("pad", MS // 2, MS * 0.6),
                       tracing.Event("simulate", 0.7 * MS, 7 * MS)]
    return trace


RECORD = [obs.Span("batched.simulate", -1, 0, MS, {}),
          obs.Span("chunk.upload", -1, 0, MS // 2, {"bytes": 9_000_000}),
          obs.Span("chunk.upload", -1, MS, 2 * MS, {"bytes": 9_000_000}),
          obs.Span("scenario.jobs", -1, 2 * MS, 5 * MS, {"jobs": 3700})]


@pytest.fixture
def program(monkeypatch):
    monkeypatch.setattr(obs, "records", lambda: list(RECORD))
    monkeypatch.setattr(backend, "chunk_op_scopes", lambda: dict(SCOPES))


def _read(name, trace):
    return run.read_metric(CHIP, name, run.TracedRun(trace, 1, 32, 0.01, INFO))


def test_fleet_readers_on_a_synthetic_trace(program):
    expect = {
        "dispatch_device_us.fleet": (0.1 + 0.3) * 1e3 / (2 * 512),
        "edf_rank_device_us.fleet": (0.5 + 0.7) * 1e3 / (2 * 512),
        "scan_step_device_us.fleet": 4.0 * 1e3 / (2 * 512),
        "device_idle_share.fleet": 100.0 * (1 - 4.5 / 10),
    }
    for name, value in expect.items():
        assert _read(name, _trace()) == pytest.approx(value), name


@pytest.mark.parametrize("twin", TWINS)
def test_fleet_reader_reads_what_its_sweep_twin_reads(program, twin):
    value = _read(twin + ".fleet", _trace())
    assert value is not None and value == _read(twin + ".sweep", _trace())


def test_fleet_readers_return_nothing_without_device_events(program):
    for name in READERS:
        assert _read(name, _trace(device=False)) is None, name


def test_dispatch_reader_returns_nothing_for_a_program_without_the_phase(program, monkeypatch):
    monkeypatch.setattr(backend, "chunk_op_scopes",
                        lambda: {k: v for k, v in SCOPES.items() if v != "dispatch"})
    assert _read("dispatch_device_us.fleet", _trace()) is None
    monkeypatch.setitem(sys.modules, "repro.obs", None)  # a program without repro.obs
    for name in READERS[:2]:
        assert _read(name, _trace()) is None, name


def test_fleet_cell_is_in_the_benchmark_with_its_readers():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "paper-diurnal-x8"
    cfg = _config()
    assert (cfg["devices"], cfg["dispatcher"], cfg["batch"], cfg["runner"]) == (
        8, "least-loaded", 32, "fleet_sweep")
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())}
    assert listed == set(READERS)
