"""Program spans and counters (repro.obs), on the CPU."""

from __future__ import annotations

import glob
import os
import subprocess
import sys

import jax
import pytest

from repro import obs


@pytest.fixture
def session(tmp_path):
    """A profiler session on the CPU, and an empty record around it."""
    obs.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        yield tmp_path
    finally:
        if obs.profiling():
            jax.profiler.stop_trace()
        obs.clear()


def test_spans_record_nothing_without_a_profiler_session():
    obs.clear()
    assert not obs.profiling()
    with obs.span("outer", rows=3) as counts:
        counts["late"] = 1
        with obs.span("inner"):
            pass
    assert counts == {"rows": 3, "late": 1}
    assert obs.records() == [] and obs.dropped() == 0


def test_spans_record_name_parent_and_counts_in_a_session(session):
    assert obs.profiling()
    with obs.span("outer", rows=3) as counts:
        with obs.span("inner", bytes=10):
            pass
        with obs.span("inner", bytes=20):
            pass
        counts["chunks"] = 2
    with obs.span("after"):
        pass
    jax.profiler.stop_trace()
    with obs.span("not recorded"):
        pass
    rec = obs.records()
    assert [(s.name, s.parent, s.counts) for s in rec] == [
        ("outer", -1, {"rows": 3, "chunks": 2}),
        ("inner", 0, {"bytes": 10}),
        ("inner", 0, {"bytes": 20}),
        ("after", -1, {}),
    ]
    outer, first, second, after = rec
    assert outer.start_ns <= first.start_ns <= first.end_ns <= second.start_ns
    assert second.end_ns <= outer.end_ns <= after.start_ns <= after.end_ns


def test_the_cap_holds_and_counts_what_it_dropped(session, monkeypatch):
    monkeypatch.setattr(obs, "MAX_RECORDS", 3)
    with obs.span("a"):
        for _ in range(4):
            with obs.span("b"):
                pass
    assert [s.name for s in obs.records()] == ["a", "b", "b"]
    assert [s.parent for s in obs.records()] == [-1, 0, 0]
    assert obs.dropped() == 2
    obs.clear()
    assert obs.records() == [] and obs.dropped() == 0


def test_a_span_open_across_clear_is_let_go(session):
    with obs.span("open across clear"):
        obs.clear()
        with obs.span("after clear"):
            pass
    assert [(s.name, s.parent) for s in obs.records()] == [("after clear", -1)]


def test_trace_events_carry_the_counts_as_stats(session):
    from jax.profiler import ProfileData

    with obs.span("chunk.upload", bytes=6083072) as counts:
        counts["chunks"] = 13
    jax.profiler.stop_trace()
    files = glob.glob(os.path.join(str(session), "**", "*.xplane.pb"), recursive=True)
    events = [e for f in files for p in ProfileData.from_file(f).planes for line in p.lines
              for e in line.events if e.name == "chunk.upload"]
    assert len(events) == 1
    assert dict(events[0].stats) == {"bytes": 6083072, "chunks": 13}


def test_spans_do_not_import_jax():
    """Sweep workers import the generator without JAX; a span must not load it."""
    code = ("import sys; from repro import obs\n"
            "with obs.span('scenario', jobs=1) as c: c['x'] = 2\n"
            "assert 'jax' not in sys.modules and obs.records() == [] and c == {'jobs': 1, 'x': 2}\n")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(sys.path))
    subprocess.run([sys.executable, "-c", code], check=True, env=env, timeout=60)
