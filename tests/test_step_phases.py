"""Step-phase scopes of the batched scan and the map from its ops to them.

``make_step_fn`` wraps each phase of a step in a ``jax.named_scope``
(``STEP_PHASES``); ``chunk_op_scopes`` names the phase of each op of the
chunk programs run while a profiler session was on.  On the CPU at a tiny
size: the scopes leave the program and its results as they were, and the
map holds where JAX's persistent cache served an executable built without
them.  One GPU has no ``dispatch`` op (every job is on device 0); a fleet's
chunk has every phase.
"""

from __future__ import annotations

import contextlib
import re

import jax
import numpy as np
import pytest

from repro import obs
from repro.core.batched import BatchedJobs, backend, build_tables, compile_policy, simulate_batch
from repro.core.batched.backend import STEP_PHASES, chunk_op_scopes, op_phases
from repro.core.scenarios import generate_scenario
from repro.sweep.cells import make_policy


def _batch():
    tables = build_tables()
    days = [generate_scenario("paper-diurnal", seed=s, load_scale=2.0, horizon_min=120.0)
            for s in range(3)]
    jobs = BatchedJobs.from_job_lists(days, max_slots=tables.max_slots)
    policy = compile_policy(make_policy("daynight", {"day_config": 6, "night_config": 2}),
                            tables, batch=3)
    return jobs, policy, tables


def _rebuild_step() -> None:
    backend.make_step_fn.cache_clear()
    backend._chunk_fn.cache_clear()


@pytest.fixture
def no_scopes(monkeypatch):
    """Build the step with every named scope taken out, and put them back after."""
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    _rebuild_step()
    yield
    monkeypatch.undo()
    _rebuild_step()


#: the phases a one-GPU chunk has: ``dispatch`` folds to device 0 and leaves no op
ONE_GPU_PHASES = set(STEP_PHASES) - {"dispatch"}


def _chunk_text(jobs, policy, tables, devices=1) -> str:
    """Compiled HLO text of the chunk program ``simulate_batch`` runs."""
    consts = backend.device_constants(tables)
    by_arrival = (jobs.by_arrival(),) if devices > 1 else ()
    jobs = jobs.in_edf_order()
    state = backend.init_state(jobs, policy.initial, devices)
    fleet = (devices, "least-loaded") if devices > 1 else ()
    fn = backend._chunk_fn(policy.kind, backend.DEFAULT_DT_MIN, backend.DEFAULT_CHUNK_STEPS,
                           float(tables.penalty_min), policy.day_start, policy.day_end, *fleet)
    args = (state, jobs.arrival, jobs.deadline, jobs.rate_by_slots, jobs.valid,
            policy.primary, policy.secondary, np.float32(0.0),
            consts["slice_slots"], consts["slice_rank"], consts["num_slices"],
            consts["old_to_new"], consts["watts"], *by_arrival)
    return fn.lower(*args).compile().as_text()


def _without_metadata(text: str) -> str:
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    return text.split("\n\n\n", 1)[-1]  # the file and stack-frame tables go too


@pytest.fixture
def no_persistent_cache():
    """Both builds compile here: metadata is not part of the cache's key."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_scopes_change_op_metadata_only(no_persistent_cache):
    jobs, policy, tables = _batch()
    scoped_text = _chunk_text(jobs, policy, tables)
    scoped = simulate_batch(jobs, policy, tables=tables)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        _rebuild_step()
        bare_text = _chunk_text(jobs, policy, tables)
        bare = simulate_batch(jobs, policy, tables=tables)
    _rebuild_step()
    assert set(op_phases(bare_text).values()) == {""}
    assert set(op_phases(scoped_text).values()) == ONE_GPU_PHASES | {""}
    assert _without_metadata(scoped_text) == _without_metadata(bare_text)
    for field in ("energy_wh", "tardiness_integral", "busy_slot_minutes", "preemptions",
                  "repartitions", "completion", "makespan_min", "util_histogram"):
        assert np.array_equal(getattr(scoped, field), getattr(bare, field)), field


@pytest.fixture
def persistent_cache(tmp_path):
    """JAX's persistent compilation cache on, in an empty directory, for one test."""
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes", "jax_enable_compilation_cache")
    was = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()
    yield tmp_path / "cache"
    for k, v in was.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_op_scopes_hold_over_a_cache_filled_without_scopes(persistent_cache, no_scopes,
                                                          monkeypatch, tmp_path):
    jobs, policy, tables = _batch()
    simulate_batch(jobs, policy, tables=tables)  # fills the cache without scopes
    assert any(persistent_cache.iterdir())
    monkeypatch.undo()  # the scopes are back; the in-memory programs are new
    _rebuild_step()
    monkeypatch.setattr(backend, "_PROFILED_CHUNKS", {})

    hits = []
    jax.monitoring.register_event_listener(
        lambda event, **kw: hits.append(event) if event.endswith("cache_hits") else None)
    obs.clear()
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        simulate_batch(jobs, policy, tables=tables)
    finally:
        jax.profiler.stop_trace()
    assert hits, "the chunk program did not come from the persistent cache"
    ran = _chunk_text(jobs, policy, tables)  # the executable that ran, from the cache
    assert set(op_phases(ran).values()) == {""}

    scopes = chunk_op_scopes()
    assert set(scopes.values()) >= ONE_GPU_PHASES
    assert set(scopes) == set(op_phases(ran))
    assert jax.config.jax_enable_compilation_cache  # the setting is back
    assert len(backend._PROFILED_CHUNKS) == 1
    obs.clear()


#: ops that move or hold values and compute nothing: a step's are shared
#: across its phases, so they carry no phase of their own
_STRUCTURAL = re.compile(r" (parameter|get-tuple-element|tuple|constant|copy|bitcast)\("
                         r"|calls=%?wrapped_broadcast")


@pytest.mark.parametrize("devices", [1, 8])
def test_every_computing_op_of_the_step_lands_in_a_named_phase(no_persistent_cache, devices):
    """Each op the step's code makes (its ``op_name`` inside the step's call)
    is named by a phase, at one GPU and on an eight-GPU fleet."""
    jobs, policy, tables = _batch()
    text = _chunk_text(jobs, policy, tables, devices)
    phases = op_phases(text)
    unnamed = []
    for line in text.splitlines():
        m = re.match(r"^\s+(?:ROOT )?%?(\S+) = ", line)
        name = re.search(r'op_name="([^"]*)"', line)
        if (m and name and "closed_call" in name.group(1) and m.group(1) in phases
                and not phases[m.group(1)] and not _STRUCTURAL.search(line)):
            unnamed.append(m.group(1))
    assert unnamed == []
    assert set(phases.values()) == (ONE_GPU_PHASES if devices == 1 else set(STEP_PHASES)) | {""}


def test_chunks_are_remembered_only_while_profiling(monkeypatch):
    monkeypatch.setattr(backend, "_PROFILED_CHUNKS", {})
    jobs, policy, tables = _batch()
    simulate_batch(jobs, policy, tables=tables)
    assert backend._PROFILED_CHUNKS == {} and chunk_op_scopes() == {}


def test_op_phases_reads_top_level_instructions_and_fused_roots():
    text = """HloModule jit_run_chunk

%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %m = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(run_chunk)/while/body/closed_call/vmap(advance)/mul"}
}

%region_0 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.1 = f32[] add(%a, %b), metadata={op_name="jit(run_chunk)/while/body/closed_call/vmap(accounting)/reduce_sum"}
}

%body (t: (f32[4])) -> (f32[4]) {
  %t = (f32[4]{0}) parameter(0)
  %gte = f32[4]{0} get-tuple-element(%t), index=0
  %fusion.7 = f32[4]{0} fusion(%gte), kind=kLoop, calls=%fused_computation
  %gather.2 = f32[4]{0} gather(%fusion.7, %gte), metadata={op_name="jit(run_chunk)/while/body/closed_call/vmap(edf_rank)/jit(searchsorted)/vmap()/gather"}
  %reduce.3 = f32[] reduce(%gather.2, %c), dimensions={0}, to_apply=%region_0, metadata={op_name="jit(run_chunk)/while/body/closed_call/vmap(policy)/reduce_sum"}
  ROOT %tuple = (f32[4]{0}) tuple(%gather.2)
}

ENTRY %main.9 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0), metadata={op_name="x"}
  ROOT %while.1 = (f32[4]{0}) while(%x), condition=%cond, body=%body, metadata={op_name="jit(run_chunk)/while"}
}
"""
    assert op_phases(text) == {
        "t": "", "gte": "", "fusion.7": "advance", "gather.2": "edf_rank",
        "reduce.3": "policy", "tuple": "", "x": "", "while.1": "",
    }


def test_op_phases_names_a_fused_root_without_metadata_by_its_latest_phase():
    """The compiler rebuilds some scatters and multi-output fusions without
    metadata on their root (the fleet chunk's merged write-back on a v5e)."""
    scope = 'metadata={op_name="jit(run_chunk)/while/body/closed_call/vmap(%s)/scatter"}'
    text = f"""HloModule jit_run_chunk

%fused_computation.1 (p: f32[4], i: s32[4]) -> f32[4] {{
  %p = f32[4]{{0}} parameter(0)
  %i = s32[4]{{0}} parameter(1)
  %tr = f32[4]{{0}} transpose(%p), dimensions={{0}}, {scope % "writeback"}
  ROOT %scatter.8 = f32[4]{{0}} scatter(%p, %i, %tr), to_apply=%region_0
}}

%fused_computation.2 (p: s32[4]) -> (s32[4], s32[4]) {{
  %p.1 = s32[4]{{0}} parameter(0)
  %a = s32[4]{{0}} add(%p.1, %p.1), {scope % "repartition"}
  %b = s32[4]{{0}} add(%a, %p.1), {scope % "edf_rank"}
  ROOT %tuple.2 = (s32[4]{{0}}, s32[4]{{0}}) tuple(%a, %b)
}}

%fused_computation.3 (p: s32[4]) -> s32[4] {{
  %p.2 = s32[4]{{0}} parameter(0)
  ROOT %c = s32[4]{{0}} copy(%p.2)
}}

ENTRY %main.9 (x: f32[4], j: s32[4]) -> f32[4] {{
  %x = f32[4]{{0}} parameter(0)
  %j = s32[4]{{0}} parameter(1)
  %fusion.237 = f32[4]{{0}} fusion(%x, %j), kind=kCustom, calls=%fused_computation.1
  %multiply_reduce_fusion.11 = (s32[4]{{0}}, s32[4]{{0}}) fusion(%j), kind=kLoop, calls=%fused_computation.2
  ROOT %fusion.9 = s32[4]{{0}} fusion(%j), kind=kLoop, calls=%fused_computation.3
}}
"""
    assert op_phases(text) == {
        "x": "", "j": "", "fusion.237": "writeback", "multiply_reduce_fusion.11": "edf_rank",
        "fusion.9": "",
    }
