"""Free-slot geometry, multi-tenant serving, and the serving dispatch stack.

Covers the DESIGN.md §9 layer end-to-end: the fragmentation metric on
``repro.core.slices``, the ``multi-tenant-serving`` scenario family, the
tenant/SLO accounting threaded through ``SimResult`` and the sweep cells,
the ``fragmentation-aware`` dispatcher, and the checked-in
``serving_matrix`` acceptance row.
"""

import itertools
import json
import os
import warnings

import pytest

from repro.configs import ALIASES, ARCH_IDS, get_config
from repro.core.jobs import Job, JobKind
from repro.core.metrics import TenantSLOStats, merge_tenant_stats, slo_attainment
from repro.core.scenarios import generate_scenario, scenario_names
from repro.core.serving import (
    MEMORY_OVERHEAD,
    MODEL_PARAM_COUNTS,
    SERVING_MIXES,
    SLICE_CLASSES,
    generate_serving_jobs,
    model_footprint_gb,
    model_slice_class,
    serving_mix,
)
from repro.core.slices import (
    A30_CONFIGS,
    MIG_CONFIGS,
    TOTAL_SLOTS,
    FreeSlotGeometry,
    Partition,
    SliceType,
    fleet_fragmentation,
    free_slot_geometry,
    table_slice_sizes,
    transition,
    validate_config_table,
)

A100_SIZES = table_slice_sizes(MIG_CONFIGS)
A30_SIZES = table_slice_sizes(A30_CONFIGS)
TABLES = [
    ("A100", MIG_CONFIGS, TOTAL_SLOTS, A100_SIZES),
    ("A30", A30_CONFIGS, 4, A30_SIZES),
]


# ----------------------------------------------------------------------
# free-slot geometry: invariants over every layout and occupancy


@pytest.mark.parametrize("label,configs,slots,sizes", TABLES)
def test_fragmentation_zero_on_empty_and_full_devices(label, configs, slots, sizes):
    for part in configs.values():
        empty = free_slot_geometry(part, (), total_slots=slots, slice_sizes=sizes)
        assert empty.free_slots == slots
        assert empty.max_placeable_slots == max(sizes)
        assert empty.fragmentation == 0.0
        full = free_slot_geometry(
            part, range(part.num_slices), total_slots=slots, slice_sizes=sizes
        )
        # fully occupied: free cells are placement holes only (config 5's
        # slot 3), always placeable as 1g — never counted as fragmented
        assert full.free_slots == slots - part.total_slots
        assert full.fragmentation == 0.0


@pytest.mark.parametrize("label,configs,slots,sizes", TABLES)
def test_geometry_invariants_over_all_occupancies(label, configs, slots, sizes):
    """Exhaustive occupancy sweep: every subset of every layout's slices."""
    for part in configs.values():
        for k in range(part.num_slices + 1):
            for occ in itertools.combinations(range(part.num_slices), k):
                geo = free_slot_geometry(
                    part, occ, total_slots=slots, slice_sizes=sizes
                )
                busy = sum(part.slices[i].slots for i in occ)
                assert geo.free_slots == slots - busy
                assert 0 <= geo.max_placeable_slots <= geo.free_slots
                assert 0.0 <= geo.fragmentation <= 1.0
                # runs are disjoint, ordered, in-grid, and non-empty
                end = -1
                for start, length in geo.runs:
                    assert length > 0
                    assert start > end
                    end = start + length - 1
                    assert end < slots
                # every placeable start is aligned and inside a free run
                free_cells = {
                    c for start, length in geo.runs
                    for c in range(start, start + length)
                }
                for w in sizes:
                    for s in geo.placeable_starts(w):
                        assert all(c in free_cells for c in range(s, s + w))


@pytest.mark.parametrize("label,configs,slots,sizes", TABLES)
def test_transition_created_instances_are_placeable(label, configs, slots, sizes):
    """Geometry is consistent with ``transition()`` over all layout pairs:

    occupy exactly the slices that survive an ``old -> new`` reconfiguration;
    every instance the transition *creates* must then be placeable in the
    free geometry (aligned start, fully inside a free run).
    """
    for old, new in itertools.product(configs.values(), repeat=2):
        plan = transition(old, new)
        survivors = tuple(i for i, _ in plan.surviving)
        geo = free_slot_geometry(
            old, survivors, total_slots=slots, slice_sizes=sizes
        )
        for j in plan.created:
            start, width = new.starts[j], new.slices[j].slots
            assert start in geo.placeable_starts(width), (
                f"{old} -> {new}: created {new.slices[j].name}@{start} "
                f"not placeable in {geo.runs}"
            )
            assert geo.max_placeable_slots >= width


def test_fragmentation_detects_shredded_free_region():
    # cfg 10 = 2g@0 + 2g@2 + 1g@4 + 1g@5 + 1g@6: occupy the two 2g slices
    # and the middle 1g -> free cells {4, 6} are two isolated 1g holes
    part = MIG_CONFIGS[10]
    geo = free_slot_geometry(
        part, (0, 1, 3), total_slots=TOTAL_SLOTS, slice_sizes=A100_SIZES
    )
    assert geo.free_slots == 2
    assert geo.max_placeable_slots == 1
    assert geo.fragmentation == 0.5


def test_fleet_fragmentation_weights_by_free_capacity():
    whole = FreeSlotGeometry(total_slots=7, runs=((0, 7),), slice_sizes=A100_SIZES)
    shredded = FreeSlotGeometry(
        total_slots=7, runs=((0, 1), (2, 1), (4, 1)), slice_sizes=A100_SIZES
    )
    assert fleet_fragmentation([]) == 0.0
    assert fleet_fragmentation([whole]) == 0.0
    assert fleet_fragmentation([shredded]) == pytest.approx(1.0 - 1.0 / 3.0)
    # 7 + 3 free, 7 + 1 placeable
    assert fleet_fragmentation([whole, shredded]) == pytest.approx(1.0 - 8.0 / 10.0)


def test_validate_config_table_errors_name_profile_and_config():
    bad = {1: Partition(config_id=1, slices=(SliceType(4, 20), SliceType(4, 20)))}
    with pytest.raises(AssertionError) as ei:
        validate_config_table(bad, 7, 40, name="test-gpu")
    msg = str(ei.value)
    assert "test-gpu" in msg and "config 1" in msg


# ----------------------------------------------------------------------
# serving workload: model -> slice class mapping and the scenario family


def test_model_slice_class_is_memory_first():
    assert model_slice_class("whisper-base", 1.0) == (1, 5)
    assert model_slice_class("gemma3-1b", 1.0) == (1, 5)
    assert model_slice_class("gemma3-12b", 1.0) == (4, 20)
    assert model_slice_class("gemma3-12b", 0.5) == (2, 10)  # int4 halves it
    assert model_slice_class("mixtral-8x7b", 0.5) == (7, 40)
    with pytest.raises(ValueError):
        model_slice_class("mixtral-8x7b", 2.0)  # bf16 exceeds the device


def test_model_footprint_includes_overhead():
    # overhead multiplier keeps the footprint strictly above raw weights
    raw_gb = 1.0e9 * 1.0 / 1e9
    assert model_footprint_gb("gemma3-1b", 1.0) > raw_gb


@pytest.mark.parametrize("name", ARCH_IDS + sorted(ALIASES))
def test_footprint_matches_the_config_registry(name):
    # the scheduler keeps its own copy of the counts so it never imports
    # the model runtime; every id and alias gives the registry's footprint
    params = get_config(name).param_count()
    for b in (0.5, 1.0, 2.0):
        assert model_footprint_gb(name, b) == params * b * MEMORY_OVERHEAD / 1e9


def test_param_table_is_the_registry_and_refuses_what_it_refuses():
    assert MODEL_PARAM_COUNTS == {a: get_config(a).param_count() for a in ARCH_IDS}
    with pytest.raises(ModuleNotFoundError):
        get_config("no-such-model")
    with pytest.raises(ModuleNotFoundError):
        model_footprint_gb("no-such-model", 1.0)


def test_serving_mixes_are_well_formed():
    assert set(SERVING_MIXES) == {"balanced", "small-heavy", "large-heavy"}
    for name, tenants in SERVING_MIXES.items():
        assert serving_mix(name) == tenants
        assert len({t.name for t in tenants}) == len(tenants)
        for t in tenants:
            assert t.slice_class in SLICE_CLASSES
            assert t.demand_slots == t.slice_class[0]
    with pytest.raises(KeyError):
        serving_mix("nope")


def test_generate_serving_jobs_deterministic_and_tagged():
    jobs = generate_serving_jobs(7, mix="balanced", horizon_min=360.0)
    again = generate_serving_jobs(7, mix="balanced", horizon_min=360.0)
    assert jobs == again
    assert jobs != generate_serving_jobs(8, mix="balanced", horizon_min=360.0)
    assert jobs
    names = {t.name: t for t in SERVING_MIXES["balanced"]}
    for i, j in enumerate(jobs):
        assert j.job_id == i
        assert j.kind is JobKind.INFERENCE
        assert j.tenant in names
        assert j.slo_min is not None and j.slo_min > 0.0
        assert j.deadline == pytest.approx(j.arrival + j.slo_min)
        spec = names[j.tenant]
        assert j.elasticity.cap == spec.demand_slots
        # work is sized for the demand class: service time x demand slots
        assert j.work == pytest.approx((j.work / spec.demand_slots) * spec.demand_slots)
    arrivals = [j.arrival for j in jobs]
    assert arrivals == sorted(arrivals)


def test_serving_scenario_registered_and_matches_generator():
    assert "multi-tenant-serving" in scenario_names()
    via_registry = generate_scenario(
        "multi-tenant-serving", 3, mix="small-heavy", horizon_min=240.0
    )
    direct = generate_serving_jobs(3, mix="small-heavy", horizon_min=240.0)
    assert via_registry == direct


def test_job_latency_and_slo_attained():
    from repro.core.jobs import LINEAR

    j = Job(0, JobKind.INFERENCE, arrival=10.0, work=1.0, deadline=15.0,
            elasticity=LINEAR, tenant="t", slo_min=5.0)
    assert j.latency() == 0.0 and not j.slo_attained()  # incomplete
    j.completion = 14.0
    assert j.latency() == pytest.approx(4.0)
    assert j.slo_attained()
    j.completion = 15.5
    assert not j.slo_attained()
    # no SLO declared -> vacuously attained once complete
    free = Job(1, JobKind.INFERENCE, arrival=0.0, work=1.0, deadline=9.0,
               elasticity=LINEAR)
    free.completion = 99.0
    assert free.slo_attained()


# ----------------------------------------------------------------------
# tenant accounting: SimResult, cell result dicts, merging


def test_merge_tenant_stats_is_exact():
    a = {"x": TenantSLOStats(jobs=3, attained=2, latency_sum_min=6.0)}
    b = {"x": TenantSLOStats(jobs=1, attained=1, latency_sum_min=2.0),
         "y": TenantSLOStats(jobs=2, attained=0, latency_sum_min=9.0)}
    merged = merge_tenant_stats([a, b])
    assert merged["x"] == TenantSLOStats(jobs=4, attained=3, latency_sum_min=8.0)
    assert merged["y"] == b["y"]
    assert slo_attainment(merged) == pytest.approx(3.0 / 6.0)
    assert slo_attainment({}) == 1.0
    assert merged["x"].attainment == pytest.approx(0.75)
    assert merged["x"].mean_latency_min == pytest.approx(2.0)


def _serving_cell(**overrides):
    from repro.sweep.cells import make_scenario_cell

    kw = dict(
        experiment="t", group="g", scheduler="EDF-SS", seed=11,
        scenario="multi-tenant-serving",
        scenario_kwargs={"horizon_min": 240.0, "load_scale": 0.5},
        policy="static", policy_kwargs={"config_id": 3},
    )
    kw.update(overrides)
    return make_scenario_cell(**kw)


def test_serving_cell_threads_tenants_through_result_dict():
    from repro.sweep.cells import result_to_sim_result, run_cell

    out = run_cell(_serving_cell())
    assert "tenants" in out and "slo_attainment" in out
    res = result_to_sim_result(out)
    assert res.tenants
    assert set(res.tenants) <= {t.name for t in SERVING_MIXES["balanced"]}
    assert 0.0 <= res.slo_attainment <= 1.0
    assert out["slo_attainment"] == pytest.approx(res.slo_attainment)
    for st in res.tenants.values():
        assert isinstance(st, TenantSLOStats)
        assert 0 <= st.attained <= st.jobs


def test_non_serving_cell_emits_no_tenant_keys():
    from repro.sweep.cells import make_scenario_cell, result_to_sim_result, run_cell

    cell = make_scenario_cell(
        experiment="t", group="g", scheduler="EDF-SS", seed=1,
        scenario="weekend-flat", scenario_kwargs={"horizon_min": 120.0},
        policy="static", policy_kwargs={"config_id": 3},
    )
    out = run_cell(cell)
    # absent, not empty: baseline comparison requires exact key equality
    assert "tenants" not in out and "slo_attainment" not in out
    assert result_to_sim_result(out).tenants == {}
    assert result_to_sim_result(out).slo_attainment == 1.0


def test_batched_backend_rejects_serving_cells():
    from repro.core.batched import UnsupportedPolicyError
    from repro.sweep.batched import validate_batched_cell

    cell = _serving_cell(scheduler="EDF-FS", backend="batched")
    with pytest.raises(UnsupportedPolicyError, match="tenant"):
        validate_batched_cell(cell)


# ----------------------------------------------------------------------
# dispatchers: the fragmentation-aware score and the legacy shim


class _FakeState:
    """Minimal structural DeviceState for dispatcher unit tests."""

    def __init__(self, index, profile, geometry, load=0.0):
        self.index = index
        self.profile = profile
        self.dispatched = 0
        self.backlog_1g_min = load * profile.total_slots
        self._geometry = geometry

    @property
    def normalized_load(self):
        return self.backlog_1g_min / self.profile.total_slots

    def est_busy_slots(self):
        return 0.0

    queue_depth = 0
    repartition_remaining_min = 0.0
    stalled_fraction = 0.0
    free_slices = 1

    def free_geometry(self):
        return self._geometry


def _capped_job(slots, work=4.0):
    from repro.core.serving import class_elasticity

    return Job(0, JobKind.INFERENCE, arrival=0.0, work=work, deadline=60.0,
               elasticity=class_elasticity(slots))


def test_fragmentation_aware_prefers_contiguous_free_region():
    from repro.fleet.devices import device_profile
    from repro.fleet.dispatch import DispatchContext, FragmentationAwareDispatcher

    prof = device_profile("a100-250w")
    shredded = FreeSlotGeometry(
        total_slots=7, runs=((0, 2), (4, 2)), slice_sizes=A100_SIZES
    )
    whole = FreeSlotGeometry(total_slots=7, runs=((0, 4),), slice_sizes=A100_SIZES)
    states = [_FakeState(0, prof, shredded), _FakeState(1, prof, whole)]
    ctx = DispatchContext(t=0.0, job=_capped_job(4), devices=states)
    # only device 1 can place the 4g request now; misfit drives the choice
    assert FragmentationAwareDispatcher().pick(ctx) == 1


def test_fragmentation_aware_spares_the_large_hole_for_small_jobs():
    from repro.fleet.devices import device_profile
    from repro.fleet.dispatch import DispatchContext, FragmentationAwareDispatcher

    prof = device_profile("a100-250w")
    # both devices can place a 1g request; carving it out of the lone 4g
    # run shreds nothing on device 0 (leftover 2g+1g is still placeable),
    # while device 1 keeps a pristine 4-run either way -> equal frag terms
    # break on load, but a *fragmenting* placement is avoided:
    big_hole = FreeSlotGeometry(total_slots=7, runs=((0, 4),), slice_sizes=A100_SIZES)
    small_holes = FreeSlotGeometry(
        total_slots=7, runs=((0, 1), (2, 1)), slice_sizes=A100_SIZES
    )
    states = [_FakeState(0, prof, big_hole), _FakeState(1, prof, small_holes)]
    ctx = DispatchContext(t=0.0, job=_capped_job(1, work=1.0), devices=states)
    # placing 1g into the 4-run leaves a 3g-placeable region (frag 1/3);
    # placing into a 1g hole leaves the other intact (frag 0) -> device 1
    assert FragmentationAwareDispatcher().pick(ctx) == 1


def test_legacy_dispatcher_shim_warns_and_forwards():
    from repro.fleet.devices import device_profile
    from repro.fleet.dispatch import (
        DeviceLoadState,
        DispatchContext,
        as_context_dispatcher,
        make_dispatcher,
    )

    class Legacy:
        name = "legacy-first"

        def pick(self, job, t, states):
            return 0

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        wrapped = as_context_dispatcher(Legacy())
    assert any(issubclass(x.category, DeprecationWarning) for x in w)
    assert wrapped.name == "legacy-first"
    prof = device_profile("a100-250w")
    states = [DeviceLoadState(index=0, profile=prof)]
    ctx = DispatchContext(
        t=0.0, job=_capped_job(1), devices=states, online=False
    )
    assert wrapped.pick(ctx) == 0

    # registry dispatchers already speak the context API: no wrapping
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        d = make_dispatcher("least-loaded")
        assert as_context_dispatcher(d) is d
    assert not w


def test_fleet_serving_run_merges_tenants_across_devices():
    from repro.sweep.cells import make_fleet_cell, result_to_sim_result, run_cell

    cell = make_fleet_cell(
        experiment="t", group="g",
        profiles=["a100-250w", "a30-165w"], dispatcher="fragmentation-aware",
        scheduler="EDF-SS", scenario="multi-tenant-serving",
        scenario_kwargs={"horizon_min": 240.0, "load_scale": 0.5},
        seed=5, policy="static", policy_kwargs={"config_id": 3},
    )
    out = run_cell(cell)
    res = result_to_sim_result(out)
    assert res.tenants
    total = sum(st.jobs for st in res.tenants.values())
    per_device = sum(
        sum(st["jobs"] for st in d.get("tenants", {}).values())
        for d in out["devices"]
    )
    assert total == per_device  # merge is exact, nothing dropped


# ----------------------------------------------------------------------
# the acceptance row, pinned against the checked-in baseline


def test_baseline_fragmentation_aware_beats_least_loaded_on_serving():
    """On the checked-in ``serving_matrix`` baseline the fragmentation-aware
    dispatcher beats least-loaded on fleet SLO attainment at equal-or-better
    energy on the large-heavy mix — on both fleets."""
    from repro.sweep.grids import GRIDS

    baseline = os.path.join(
        os.path.dirname(__file__), "..", "benchmarks", "baselines",
        "serving_matrix.jsonl",
    )
    assert os.path.exists(baseline), "serving_matrix baseline missing"
    cells, results = [], []
    with open(baseline) as f:
        for line in f:
            if line.strip():
                rec = json.loads(line)
                cells.append(rec["cell"])
                results.append(rec["result"])
    rows = GRIDS["serving_matrix"].aggregate(cells, results)
    by_key = {(r["fleet"], r["mix"], r["dispatcher"]): r for r in rows}
    wins = 0
    for fleet in ("4xA100", "2xA100+2xA30"):
        frag = by_key[(fleet, "large-heavy", "fragmentation-aware")]
        ll = by_key[(fleet, "large-heavy", "least-loaded")]
        assert frag["slo_attainment"] > ll["slo_attainment"], (fleet, frag, ll)
        assert frag["energy_wh"] <= ll["energy_wh"], (fleet, frag, ll)
        wins += 1
    assert wins >= 1
    # every row carries the per-tenant breakdown the nightly artifact reads
    for r in rows:
        assert r["tenant_attainment"]
        assert all(0.0 <= v <= 1.0 for v in r["tenant_attainment"].values())
