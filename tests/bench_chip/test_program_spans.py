"""The per-layer metrics that read the program's own spans, counters and
step-phase scopes (``benchmarks/chip/program_spans.py``), on the CPU."""

from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.chip import run, tracing  # noqa: E402
from repro import obs  # noqa: E402
from repro.core.batched import backend  # noqa: E402

CHIP = os.path.join(ROOT, "benchmarks", "chip")
INFO = {"chunk_steps": 512, "chunk_program": "run_chunk",
        "host_prep_spans": ("generate", "pad", "compile_policy")}
NEW = ("edf_rank_device_us.sweep", "writeback_device_us.sweep", "chunk_upload_ms.sweep",
       "h2d_mb_per_chunk.sweep", "job_build_ms_per_batch.sweep")
MS = 1_000_000


def _trace(device=True) -> tracing.Trace:
    trace = tracing.Trace(window=(0, 10 * MS))
    if device:
        ops = [  # two chunk executions, each a loop op enclosing its body's ops
            ("%while.1", 1 * MS, 2 * MS), ("%fusion.167", 1 * MS, MS // 2),
            ("%fusion.200", 1.5 * MS, MS // 10), ("%fusion.202", 1.6 * MS, MS // 10),
            ("%copy.3", 1.7 * MS, MS // 10),
            ("%while.1", 5 * MS, 2 * MS), ("%fusion.167", 5 * MS, 0.4 * MS),
            ("%fusion.200", 5.4 * MS, MS // 10),
            ("%fusion.167", 8 * MS, MS // 2),  # another program's op of the same name
        ]
        trace.ops[0] = tracing.Ops.of(ops)
        trace.modules[0] = [tracing.Event("jit_run_chunk", 1 * MS, 3 * MS),
                            tracing.Event("jit_run_chunk", 5 * MS, 7 * MS),
                            tracing.Event("jit_other", 8 * MS, 9 * MS),
                            tracing.Event("jit_run_chunk", 11 * MS, 12 * MS)]  # after the window
    return trace


def _span(name, start_ms, dur_ms, **counts):
    return obs.Span(name, -1, int(start_ms * MS), int((start_ms + dur_ms) * MS), counts)


RECORD = [
    _span("batched.simulate", 0, 4),
    _span("chunk.upload", 0, 0.5, bytes=6_083_072),
    _span("chunk.upload", 1, 1.0, bytes=6_083_072),
    _span("chunk.upload", 2, 1.5, bytes=6_083_072),
    _span("batched.simulate", 5, 4),
    *(_span("scenario.jobs", 10 + k, 100 * (k + 1), jobs=500) for k in range(4)),
    None,  # a span still open
]
SCOPES = {"fusion.167": "edf_rank", "fusion.200": "writeback", "fusion.202": "writeback",
          "copy.3": "", "while.1": ""}


@pytest.fixture
def program(monkeypatch):
    monkeypatch.setattr(obs, "records", lambda: list(RECORD))
    monkeypatch.setattr(backend, "chunk_op_scopes", lambda: dict(SCOPES))


def _read(name, trace):
    return run.read_metric(CHIP, name, run.TracedRun(trace, 2, 128, 0.01, INFO))


def test_each_reader_on_a_synthetic_trace_and_record(program):
    expect = {
        "edf_rank_device_us.sweep": (0.5 + 0.4) * 1e3 / (2 * 512),
        "writeback_device_us.sweep": 0.3 * 1e3 / (2 * 512),
        "chunk_upload_ms.sweep": 1.0,
        "h2d_mb_per_chunk.sweep": 6.083072,
        "job_build_ms_per_batch.sweep": 500.0,
    }
    for name, value in expect.items():
        assert _read(name, _trace()) == pytest.approx(value), name


def test_readers_return_nothing_without_the_programs_instruments(program, monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.obs", None)  # a program without repro.obs
    for name in NEW:
        assert _read(name, _trace()) is None, name


def test_readers_return_nothing_for_an_empty_record(program, monkeypatch):
    monkeypatch.setattr(obs, "records", lambda: [])
    for name in NEW:
        assert _read(name, _trace()) is None, name


def test_readers_return_nothing_without_device_events(program):
    for name in NEW:
        assert _read(name, _trace(device=False)) is None, name


def test_device_readers_return_nothing_without_op_scopes(program, monkeypatch):
    monkeypatch.delattr(backend, "chunk_op_scopes")  # a program without the scopes
    for name in NEW[:2]:
        assert _read(name, _trace()) is None, name
    monkeypatch.setattr(backend, "chunk_op_scopes", lambda: {}, raising=False)
    for name in NEW[:2]:
        assert _read(name, _trace()) is None, name


def test_a_traced_tiny_run_leaves_the_programs_record(tmp_path, monkeypatch, capsys):
    """The harness's window is the program's profiler session: its spans are
    recorded with their nesting and counts (4 rows of J=96 a batch here)."""
    from test_chipbench import _run_cell, _tiny_root

    obs.clear()
    rc, result, err = _run_cell(monkeypatch, capsys, _tiny_root(tmp_path), "sweep-overload",
                                trace=1)
    assert rc == 0, err
    rec = obs.records()
    obs.clear()
    names = [s.name for s in rec]
    sims = [i for i, n in enumerate(names) if n == "batched.simulate"]
    assert len(sims) == result["attempted"] // 4 >= 1
    for name in ("scenario", "scenario.arrivals", "scenario.jobs"):
        assert names.count(name) == 4 * len(sims), name
    assert {rec[s.parent].name for s in rec if s.name.startswith("scenario.")} == {"scenario"}
    uploads = [s for s in rec if s.name == "chunk.upload"]
    assert {rec[s.parent].name for s in uploads} == {"batched.simulate"}
    assert len(uploads) == names.count("chunk.dispatch") == names.count("chunk.sync")
    assert len(uploads) == sum(rec[i].counts["chunks"] for i in sims)
    assert {s.counts["bytes"] for s in uploads} == {4 * 96 * 45 + 2 * 4 * 4}
    # no chip: nothing on chip 0 to read, so the new metrics stay out of the line
    assert not set(NEW) & set(result["metrics"])
