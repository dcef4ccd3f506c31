"""Sweep cells: the unit of work of the parallel sweep engine.

A *cell* is one simulator run, described entirely by JSON-serializable data:

``{experiment, group, scheduler, policy, policy_kwargs, workload, seed,
mig_enabled, initial_config}``

* ``experiment`` names the grid (e.g. ``table2_schedulers``) and ``group``
  the aggregation bucket inside it (e.g. the algorithm name);
* ``policy`` + ``policy_kwargs`` name a registered repartitioning policy so
  cells can cross process boundaries (a :class:`RepartitionPolicy` instance
  is not picklable in general, a spec always is);
* ``workload`` is the fully-resolved :class:`WorkloadSpec` field dict;
* ``seed`` drives :func:`generate_jobs`, making the cell deterministic.

``cell_hash`` is a content hash over the cell params plus the simulator
version tag (:data:`repro.core.simulator.SIM_VERSION`); the on-disk cache
keys on it, so a semantics bump invalidates every memoized result at once.

This module deliberately imports only the numpy-based core (no jax) so
worker processes start fast; the DQN policy imports ``repro.core.rl``
lazily inside its factory.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence

from repro import obs
from repro.core.metrics import SimResult, TenantSLOStats
from repro.core.scenarios import generate_scenario, resolve_scenario_kwargs
from repro.core.schedulers import make_scheduler
from repro.core.simulator import (
    REPARTITION_MODES,
    SIM_VERSION,
    DayNightPolicy,
    MIGSimulator,
    NoMIGPolicy,
    RepartitionPolicy,
    StaticPolicy,
    queue_heuristic_policy,
)
from repro.core.workload import WorkloadSpec, generate_jobs

__all__ = [
    "POLICIES",
    "CellSpec",
    "batched_fleet_refusal",
    "canonical_json",
    "cell_hash",
    "cell_jobs",
    "cell_repartition_mode",
    "make_cell",
    "make_fleet_cell",
    "make_policy",
    "make_scenario_cell",
    "result_to_sim_result",
    "run_cell",
    "workload_to_dict",
]

Cell = Dict[str, Any]

def batched_fleet_refusal(fleet: Mapping[str, Any]) -> Optional[str]:
    """Why the batched backend cannot run a cell's ``fleet``, or None.

    It runs fleets of one device profile on the A100 partition table, every
    device as the fleet default, behind online ``least-loaded`` or
    ``round-robin`` dispatch (docs/BATCHED_SIM.md §2-§3).  Mixed tables and
    the dispatchers that read more than the backlog need the co-advanced
    dispatcher loop of the oracle.
    """
    from repro.core.batched.backend import DISPATCHERS
    from repro.core.slices import MIG_CONFIGS
    from repro.fleet.devices import DEVICE_PROFILES

    devices = list(fleet.get("devices") or [])
    profiles = sorted({d.get("profile") for d in devices})
    if fleet.get("dispatcher") not in DISPATCHERS:
        why = f"dispatcher {fleet.get('dispatcher')!r}; it runs {' and '.join(DISPATCHERS)} only"
    elif len(profiles) != 1:
        why = f"a fleet of mixed profiles {profiles}; it runs one profile per fleet"
    elif profiles[0] not in DEVICE_PROFILES or (
            dict(DEVICE_PROFILES[profiles[0]].configs) != dict(MIG_CONFIGS)):
        why = f"profile {profiles[0]!r}; it runs profiles on the A100 partition table"
    elif any(set(d) - {"profile"} for d in devices):
        why = "per-device scheduler or initial_config overrides"
    elif fleet.get("info", "online") != "online":
        why = f"dispatch_info {fleet.get('info')!r}; it dispatches online"
    else:
        return None
    return (f"the batched backend cannot run this fleet cell ({why}); the other "
            "fleets need the co-advanced dispatcher loop: run them on the oracle backend")


def cell_repartition_mode(cell: Cell) -> str:
    """The transition model a cell runs under.

    Cells built since ``mig-sim-4`` carry the key explicitly; a cell without
    it predates slot placement and replays under the legacy full-drain model
    (that compatibility rule is what lets the drain path reproduce old
    baselines bit-identically).
    """
    return cell.get("repartition_mode", "drain")


def _cell_policy_kwargs(cell: Cell) -> Dict[str, Any]:
    """The cell's policy kwargs, with mode-coupled defaults resolved.

    The forecast controller's MPC lookahead must price the same transition
    physics the simulator charges, so unless the cell pins the policy's
    ``repartition_mode`` explicitly it inherits the cell's simulator mode —
    in particular, legacy (pre-mig-sim-4) forecast cells replay with drain
    pricing, exactly as they originally ran.
    """
    kwargs = dict(cell.get("policy_kwargs") or {})
    if cell.get("policy") == "forecast":
        kwargs.setdefault("repartition_mode", cell_repartition_mode(cell))
    return kwargs


# ----------------------------------------------------------------------
# policy registry (name -> factory taking the cell's policy_kwargs)

def _dqn_policy(
    params_path: str,
    initial_config: int = 2,
    decision_interval_min: Optional[float] = None,
) -> RepartitionPolicy:
    """Greedy DQN policy; ``decision_interval_min`` evaluates on the fixed
    cadence the batched trainer trains under (repro.core.rl.batched_train)."""
    from repro.core.rl import DQNConfig, DQNLearner, greedy_policy
    from repro.core.rl.env import FEATURE_DIM

    learner = DQNLearner(DQNConfig(state_dim=FEATURE_DIM))
    learner.load(params_path)
    return greedy_policy(
        learner,
        initial_config=initial_config,
        decision_interval_min=decision_interval_min,
    )


def _forecast_policy(
    scenario: str = "paper-diurnal",
    train_seeds: int = 8,
    harmonics: int = 3,
    scenario_kwargs: Optional[Mapping[str, Any]] = None,
    **policy_kwargs: Any,
) -> RepartitionPolicy:
    """Predictive MPC controller, forecaster fitted on ``scenario``.

    The Fourier day-model fit is deterministic and cached per process
    (:func:`repro.forecast.fit_scenario_forecaster`), so sweep workers pay
    the training-day generation once; the policy instance itself is fresh
    per cell (it carries EWMA/dwell state).
    """
    from repro.forecast import ArrivalForecaster, ForecastPolicy, fit_scenario_forecaster

    model = fit_scenario_forecaster(
        scenario=scenario,
        train_seeds=train_seeds,
        harmonics=harmonics,
        scenario_kwargs=tuple(sorted(dict(scenario_kwargs or {}).items())),
    )
    return ForecastPolicy(ArrivalForecaster(model), **policy_kwargs)


POLICIES: Dict[str, Callable[..., RepartitionPolicy]] = {
    "static": lambda config_id=3: StaticPolicy(config_id),
    "nomig": lambda: NoMIGPolicy(),
    "daynight": lambda day_config=6, night_config=2: DayNightPolicy(
        day_config, night_config
    ),
    "heuristic": queue_heuristic_policy,
    "dqn": _dqn_policy,
    "forecast": _forecast_policy,
}


def make_policy(name: str, kwargs: Optional[Mapping[str, Any]] = None) -> RepartitionPolicy:
    """Fresh policy instance from the registry (instances carry run state)."""
    if name not in POLICIES:
        raise KeyError(f"unknown policy {name!r}; registered: {sorted(POLICIES)}")
    # underscore-prefixed kwargs are hash-only annotations (e.g. the weights
    # digest), not factory arguments
    clean = {k: v for k, v in dict(kwargs or {}).items() if not k.startswith("_")}
    return POLICIES[name](**clean)


# ----------------------------------------------------------------------
# cell construction + hashing

def file_digest(path: str) -> str:
    """Content digest of an auxiliary input file ('' when absent)."""
    try:
        with open(path, "rb") as f:
            return hashlib.sha256(f.read()).hexdigest()
    except OSError:
        return ""


def workload_to_dict(spec: WorkloadSpec) -> Dict[str, Any]:
    """All WorkloadSpec fields, fully resolved (defaults included).

    Resolving defaults into the cell means the hash captures the *values* the
    simulation saw — a changed default can never alias a stale cache entry.
    """
    return dataclasses.asdict(spec)


def _base_cell(
    *,
    experiment: str,
    group: str,
    scheduler: str,
    seed: int,
    policy: str,
    policy_kwargs: Optional[Mapping[str, Any]],
    mig_enabled: bool,
    repartition_mode: str,
    backend: str = "oracle",
    backend_kwargs: Optional[Mapping[str, Any]] = None,
) -> Cell:
    """The fields every cell shares; workload/scenario keys are added on top.

    ``backend`` selects the simulation engine: ``"oracle"`` (the event-driven
    :class:`MIGSimulator`, the default) adds *no* keys — existing cell hashes
    and baselines are untouched — while ``"batched"`` stamps the cell with
    ``backend`` plus its resolved ``backend_kwargs`` (``dt_min``), so oracle
    and batched runs of the same physics never alias one cache entry.
    """
    if repartition_mode not in REPARTITION_MODES:
        raise ValueError(
            f"unknown repartition_mode {repartition_mode!r}; "
            f"valid: {REPARTITION_MODES}"
        )
    if backend not in ("oracle", "batched"):
        raise ValueError(
            f"unknown backend {backend!r}; valid: ('oracle', 'batched')"
        )
    if backend == "oracle" and backend_kwargs:
        raise ValueError("backend_kwargs only apply to the batched backend")
    policy_kwargs = dict(policy_kwargs or {})
    # Policies that load weights from disk are only content-addressable if the
    # weights themselves enter the hash: a retrained checkpoint at the same
    # path must miss the cache, not silently serve stale results.
    if "params_path" in policy_kwargs:
        policy_kwargs["_params_digest"] = file_digest(policy_kwargs["params_path"])
    cell: Cell = {
        "experiment": experiment,
        "group": group,
        "scheduler": scheduler,
        "policy": policy,
        "policy_kwargs": policy_kwargs,
        "seed": int(seed),
        "mig_enabled": bool(mig_enabled),
        # resolved explicitly into the cell (the hash must capture the mode
        # the simulator ran under); cells *without* the key are pre-mig-sim-4
        # and replay under the legacy drain model (see run_cell)
        "repartition_mode": repartition_mode,
    }
    if backend == "batched":
        # resolved like workload defaults: the hash must capture the timestep
        # the discretization ran at (jax-free import; see batched.__init__)
        from repro.core.batched import DEFAULT_DT_MIN

        kw = dict(backend_kwargs or {})
        kw["dt_min"] = float(kw.get("dt_min", DEFAULT_DT_MIN))
        cell["backend"] = "batched"
        cell["backend_kwargs"] = kw
    return cell


@dataclasses.dataclass(frozen=True)
class CellSpec:
    """One declarative description of any sweep cell — the single build path.

    Historically three keyword-sprawl constructors (``make_cell`` /
    ``make_scenario_cell`` / ``make_fleet_cell``) each assembled cell dicts
    with overlapping-but-divergent parameter lists.  ``CellSpec`` holds the
    union once, validates the combinations, and :meth:`to_cell` emits the
    dict with exactly the historical key-presence rules — so every
    pre-existing cell hash is unchanged (pinned by
    ``tests/test_sweep.py::test_cellspec_preserves_baseline_hashes``).  The
    legacy constructors survive as thin wrappers.

    Job stream: exactly one of ``workload`` (a raw :class:`WorkloadSpec`)
    or ``scenario`` (a registered scenario name; ``scenario_kwargs`` are
    resolved against its defaults into the cell).  Fleet cells
    (``fleet_profiles`` set) require a scenario stream and a dispatcher;
    ``dispatch_info`` enters the cell under the legacy ``fleet.info`` key.
    """

    experiment: str
    group: str
    scheduler: str
    seed: int
    # --- job stream (exactly one) -------------------------------------
    workload: Optional[WorkloadSpec] = None
    scenario: Optional[str] = None
    scenario_kwargs: Optional[Mapping[str, Any]] = None
    # --- policy + physics ---------------------------------------------
    policy: str = "static"
    policy_kwargs: Optional[Mapping[str, Any]] = None
    mig_enabled: bool = True
    repartition_mode: str = "partial"
    # --- execution backend --------------------------------------------
    backend: str = "oracle"
    backend_kwargs: Optional[Mapping[str, Any]] = None
    # --- fleet ----------------------------------------------------------
    fleet_profiles: Optional[Sequence[str]] = None
    dispatcher: Optional[str] = None
    dispatch_info: str = "online"

    def to_cell(self) -> Cell:
        """Build the JSON cell dict (validates field combinations)."""
        if (self.workload is None) == (self.scenario is None):
            raise ValueError(
                "CellSpec needs exactly one job stream: workload or scenario"
            )
        if self.scenario_kwargs is not None and self.scenario is None:
            raise ValueError("scenario_kwargs require a scenario stream")
        is_fleet = self.fleet_profiles is not None
        if is_fleet and not self.fleet_profiles:
            raise ValueError("fleet_profiles must name at least one device")
        if is_fleet and self.scenario is None:
            raise ValueError("fleet cells take a scenario stream, not a raw workload")
        if is_fleet and self.dispatcher is None:
            raise ValueError("fleet cells require a dispatcher")
        if not is_fleet and self.dispatcher is not None:
            raise ValueError("dispatcher only applies to fleet cells")
        cell = _base_cell(
            experiment=self.experiment,
            group=self.group,
            scheduler=self.scheduler,
            seed=self.seed,
            policy=self.policy,
            policy_kwargs=self.policy_kwargs,
            mig_enabled=self.mig_enabled,
            repartition_mode=self.repartition_mode,
            backend=self.backend,
            backend_kwargs=self.backend_kwargs,
        )
        if self.workload is not None:
            cell["workload"] = workload_to_dict(self.workload)
        else:
            cell["scenario"] = {
                "name": self.scenario,
                "kwargs": resolve_scenario_kwargs(self.scenario, self.scenario_kwargs),
            }
        if is_fleet:
            cell["fleet"] = {
                "devices": [{"profile": p} for p in self.fleet_profiles],
                "dispatcher": self.dispatcher,
                "info": self.dispatch_info,
            }
            refusal = self.backend == "batched" and batched_fleet_refusal(cell["fleet"])
            if refusal:
                from repro.core.batched import UnsupportedPolicyError

                raise UnsupportedPolicyError(refusal)
        return cell


def make_cell(
    *,
    experiment: str,
    group: str,
    scheduler: str,
    workload: WorkloadSpec,
    seed: int,
    policy: str = "static",
    policy_kwargs: Optional[Mapping[str, Any]] = None,
    mig_enabled: bool = True,
    repartition_mode: str = "partial",
    backend: str = "oracle",
    backend_kwargs: Optional[Mapping[str, Any]] = None,
) -> Cell:
    """A single-GPU cell whose jobs come from a raw :class:`WorkloadSpec`.

    Thin wrapper over :class:`CellSpec` (the one build path).
    """
    return CellSpec(
        experiment=experiment,
        group=group,
        scheduler=scheduler,
        seed=seed,
        workload=workload,
        policy=policy,
        policy_kwargs=policy_kwargs,
        mig_enabled=mig_enabled,
        repartition_mode=repartition_mode,
        backend=backend,
        backend_kwargs=backend_kwargs,
    ).to_cell()


def make_scenario_cell(
    *,
    experiment: str,
    group: str,
    scheduler: str,
    scenario: str,
    seed: int,
    scenario_kwargs: Optional[Mapping[str, Any]] = None,
    policy: str = "static",
    policy_kwargs: Optional[Mapping[str, Any]] = None,
    mig_enabled: bool = True,
    repartition_mode: str = "partial",
    backend: str = "oracle",
    backend_kwargs: Optional[Mapping[str, Any]] = None,
) -> Cell:
    """A cell whose jobs come from a registered scenario, not a raw spec.

    Thin wrapper over :class:`CellSpec`; the scenario's knobs are resolved
    against its defaults into the cell — the content hash must capture the
    values the generator saw, exactly as ``workload_to_dict`` resolves
    :class:`WorkloadSpec` defaults.
    """
    return CellSpec(
        experiment=experiment,
        group=group,
        scheduler=scheduler,
        seed=seed,
        scenario=scenario,
        scenario_kwargs=scenario_kwargs,
        policy=policy,
        policy_kwargs=policy_kwargs,
        mig_enabled=mig_enabled,
        repartition_mode=repartition_mode,
        backend=backend,
        backend_kwargs=backend_kwargs,
    ).to_cell()


def make_fleet_cell(
    *,
    experiment: str,
    group: str,
    profiles: Sequence[str],
    dispatcher: str,
    scheduler: str,
    scenario: str,
    seed: int,
    scenario_kwargs: Optional[Mapping[str, Any]] = None,
    policy: str = "static",
    policy_kwargs: Optional[Mapping[str, Any]] = None,
    mig_enabled: bool = True,
    dispatch_info: str = "online",
    repartition_mode: str = "partial",
    backend: str = "oracle",
    backend_kwargs: Optional[Mapping[str, Any]] = None,
) -> Cell:
    """A fleet cell: N devices (by profile name) behind a dispatcher.

    Thin wrapper over :class:`CellSpec`; the extra ``fleet`` key routes
    :func:`run_cell` through :class:`repro.fleet.FleetSimulator`.  Every
    device runs ``scheduler`` and an independent instance of the cell's
    repartitioning policy.  ``dispatch_info`` selects what the dispatcher
    observes — ``"online"`` (real co-advanced engine state, the default) or
    ``"fluid"`` (the legacy backlog-estimate pre-split); the resolved value
    always enters the cell so the content hash captures it.
    ``backend="batched"`` runs the fleets :func:`batched_fleet_refusal`
    accepts on the batched scan and refuses the rest.
    """
    return CellSpec(
        experiment=experiment,
        group=group,
        scheduler=scheduler,
        seed=seed,
        scenario=scenario,
        scenario_kwargs=scenario_kwargs,
        policy=policy,
        policy_kwargs=policy_kwargs,
        mig_enabled=mig_enabled,
        repartition_mode=repartition_mode,
        fleet_profiles=tuple(profiles),
        dispatcher=dispatcher,
        dispatch_info=dispatch_info,
        backend=backend,
        backend_kwargs=backend_kwargs,
    ).to_cell()


def canonical_json(obj: Any) -> str:
    """Byte-stable JSON: sorted keys, no whitespace, repr round-trip floats."""
    return json.dumps(obj, sort_keys=True, separators=(",", ":"))


#: cell keys that label the grid rather than the simulation — excluded from
#: the hash so identical physics shares one cache entry across experiments.
_META_KEYS = frozenset({"experiment", "group"})


def cell_hash(cell: Cell, sim_version: str = SIM_VERSION) -> str:
    """Content hash of the cell's physics + simulator version (cache key)."""
    physics = {k: v for k, v in cell.items() if k not in _META_KEYS}
    payload = canonical_json({"cell": physics, "sim_version": sim_version})
    return hashlib.sha256(payload.encode("utf-8")).hexdigest()


# ----------------------------------------------------------------------
# execution

def cell_jobs(cell: Cell) -> List[Any]:
    """Materialize the cell's job stream (scenario cells or raw-spec cells)."""
    with obs.span("scenario"):
        if "scenario" in cell:
            sc = cell["scenario"]
            return generate_scenario(sc["name"], seed=cell["seed"], **sc.get("kwargs", {}))
        spec = WorkloadSpec(**cell["workload"])
        return generate_jobs(spec, seed=cell["seed"])


def _tenants_dict(res: SimResult) -> Dict[str, Dict[str, Any]]:
    return {
        name: {
            "jobs": st.jobs,
            "attained": st.attained,
            "latency_sum_min": st.latency_sum_min,
        }
        for name, st in sorted(res.tenants.items())
    }


def _result_dict(
    res: SimResult,
    util_histogram: Mapping[int, float],
    config_trace: Sequence[Any],
) -> Dict[str, Any]:
    out = {
        "energy_wh": res.energy_wh,
        "avg_tardiness": res.avg_tardiness,
        "num_jobs": res.num_jobs,
        "total_tardiness": res.total_tardiness,
        "preemptions": res.preemptions,
        "repartitions": res.repartitions,
        "max_tardiness": res.max_tardiness,
        "deadline_misses": res.deadline_misses,
        "busy_slot_minutes": res.busy_slot_minutes,
        "extra": dict(res.extra),
        # side-channel state some figures aggregate over:
        "util_histogram": {str(k): v for k, v in util_histogram.items()},
        "config_trace": [[t, c] for t, c in config_trace],
    }
    # only serving workloads emit tenant stats — batch cells keep the exact
    # historical key set, so pre-serving baselines compare byte-identically
    if res.tenants:
        out["tenants"] = _tenants_dict(res)
        out["slo_attainment"] = res.slo_attainment
    return out


def _run_fleet_cell(
    cell: Cell,
    policy_factory: Optional[Callable[[], RepartitionPolicy]] = None,
) -> Dict[str, Any]:
    # lazy import: plain single-GPU sweeps never pay for the fleet layer
    from repro.fleet import FleetDeviceSpec, FleetSimulator, FleetSpec

    f = cell["fleet"]
    spec = FleetSpec(
        devices=tuple(
            FleetDeviceSpec(
                profile=d["profile"],
                scheduler=d.get("scheduler"),
                initial_config=d.get("initial_config"),
            )
            for d in f["devices"]
        ),
        dispatcher=f["dispatcher"],
        scheduler=cell["scheduler"],
        dispatch_info=f.get("info", "online"),
        repartition_mode=cell_repartition_mode(cell),
    )
    if policy_factory is not None:
        def per_device_policy(i, prof):
            return policy_factory()
    else:
        def per_device_policy(i, prof):
            # independent instance per device: policies carry run state
            return make_policy(cell["policy"], _cell_policy_kwargs(cell))

    jobs = cell_jobs(cell)
    fsim = FleetSimulator(spec, mig_enabled=cell["mig_enabled"])
    fres = fsim.run(jobs, policy_factory=per_device_policy)

    util: Dict[int, float] = {}
    for sim in fsim.sims:
        for k, v in sim.util_histogram.items():
            util[k] = util.get(k, 0.0) + v
    out = _result_dict(fres.aggregate, util, [])
    out["dispatch_counts"] = list(fres.dispatch_counts)
    devices = []
    for d, r in zip(f["devices"], fres.per_device, strict=True):
        entry = {
            "profile": d["profile"],
            "num_jobs": r.num_jobs,
            "energy_wh": r.energy_wh,
            "avg_tardiness": r.avg_tardiness,
            "repartitions": r.repartitions,
        }
        if r.tenants:  # serving cells: per-device SLO breakdown
            entry["tenants"] = _tenants_dict(r)
            entry["slo_attainment"] = r.slo_attainment
        devices.append(entry)
    out["devices"] = devices
    return out


def run_cell(
    cell: Cell,
    policy_factory: Optional[Callable[[], RepartitionPolicy]] = None,
) -> Dict[str, Any]:
    """Execute one cell; returns a JSON-serializable result dict.

    ``policy_factory`` overrides the registry lookup for in-process runs with
    unpicklable ad-hoc policies (e.g. a live DQN agent mid-training); such
    cells bypass the cache at the runner layer.  Cells with a ``fleet`` key
    run through :class:`repro.fleet.FleetSimulator` and report the fleet
    aggregate in the standard result fields.  Cells with ``backend ==
    "batched"`` run through :mod:`repro.sweep.batched` (a one-cell batch
    here; :func:`repro.sweep.runner.run_cells` groups them for real
    vectorization).
    """
    if cell.get("backend") == "batched":
        if policy_factory is not None:
            raise ValueError(
                "ad-hoc policy_factory cells cannot run on the batched "
                "backend (policies must compile; see repro.core.batched)"
            )
        from repro.sweep.batched import run_batched_cells

        return run_batched_cells([cell])[0]
    if "fleet" in cell:
        return _run_fleet_cell(cell, policy_factory)
    jobs = cell_jobs(cell)
    if policy_factory is not None:
        policy = policy_factory()
    else:
        policy = make_policy(cell["policy"], _cell_policy_kwargs(cell))
    sim = MIGSimulator(
        make_scheduler(cell["scheduler"]),
        mig_enabled=cell["mig_enabled"],
        repartition_mode=cell_repartition_mode(cell),
    )
    res = sim.run(jobs, policy=policy)
    return _result_dict(res, sim.util_histogram, sim.config_trace)


_RESULT_FIELDS = (
    "energy_wh",
    "avg_tardiness",
    "num_jobs",
    "total_tardiness",
    "preemptions",
    "repartitions",
    "max_tardiness",
    "deadline_misses",
    "busy_slot_minutes",
)


def result_to_sim_result(result: Mapping[str, Any]) -> SimResult:
    """Reconstruct the :class:`SimResult` a cell's simulator run returned.

    ``tenants`` is optional: pre-serving results (and every batch cell)
    simply lack the key and round-trip with an empty mapping.
    """
    tenants = {
        name: TenantSLOStats(**st)
        for name, st in dict(result.get("tenants") or {}).items()
    }
    return SimResult(
        **{k: result[k] for k in _RESULT_FIELDS},
        extra=dict(result["extra"]),
        tenants=tenants,
    )


def group_results(
    cells: Sequence[Cell], results: Sequence[Mapping[str, Any]]
) -> Dict[str, List[SimResult]]:
    """Bucket per-cell results by ``cell['group']``, preserving cell order.

    Order preservation matters: float summation is order-sensitive, and the
    legacy serial benchmarks accumulated results in grid order — grouping in
    the same order keeps aggregate numbers bit-identical to the serial path.
    """
    out: Dict[str, List[SimResult]] = {}
    for cell, result in zip(cells, results, strict=True):
        out.setdefault(cell["group"], []).append(result_to_sim_result(result))
    return out
