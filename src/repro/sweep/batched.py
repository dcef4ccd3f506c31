"""Sweep-layer glue for the batched backend: group, vectorize, split.

The runner hands this module every pending cell tagged ``backend ==
"batched"``.  Cells are grouped by their physics-minus-seed fingerprint
(same scenario/policy/mode/backend knobs, different seeds) and each group
runs as ONE :func:`repro.core.batched.simulate_batch` call — the whole
point of the backend: seeds become rows of a ``(B, J)`` array instead of
independent processes.

The per-cell result dicts come back in the oracle vocabulary
(:func:`repro.sweep.cells._result_dict` fields) so caching, artifacts and
aggregation are backend-agnostic; ``config_trace`` is empty for batched
cells (documented in docs/BATCHED_SIM.md §5).

Fleet cells run as one batch of fleets: homogeneous fleets behind
``least-loaded`` or ``round-robin`` dispatch (:func:`repro.sweep.cells.
batched_fleet_refusal`), on the scan's device axis.

Unsupported combinations fail loudly *before* any simulation runs:
schedulers other than EDF-FS, other fleets, and policies that need
per-event simulator state all raise :class:`UnsupportedPolicyError` with a
pointer back to the oracle backend.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence

from repro.sweep.cells import (
    Cell,
    batched_fleet_refusal,
    canonical_json,
    cell_jobs,
    cell_repartition_mode,
    make_policy,
)

__all__ = [
    "batched_group_key",
    "is_batched_cell",
    "run_batched_cells",
    "validate_batched_cell",
]


def is_batched_cell(cell: Cell) -> bool:
    """True when the cell asks for the batched backend."""
    return cell.get("backend") == "batched"


def batched_group_key(cell: Cell) -> str:
    """Fingerprint of everything but the seed (and grid labels).

    Cells sharing a key are physically identical rollouts under different
    seeds, so they can advance lock-step in one ``simulate_batch`` call.
    """
    skip = ("experiment", "group", "seed")
    return canonical_json({k: v for k, v in cell.items() if k not in skip})


def validate_batched_cell(cell: Cell) -> None:
    """Reject cells the batched backend cannot run, with guidance.

    Raises :class:`repro.core.batched.UnsupportedPolicyError` so callers can
    distinguish "wrong backend for this cell" from genuine failures.
    """
    from repro.core.batched import UnsupportedPolicyError

    if "fleet" in cell:
        refusal = batched_fleet_refusal(cell["fleet"])
        if refusal:
            raise UnsupportedPolicyError(refusal)
    if cell.get("scheduler") != "EDF-FS":
        raise UnsupportedPolicyError(
            f"batched backend implements only EDF-FS "
            f"(got {cell.get('scheduler')!r}); run this cell on the oracle"
        )
    if (cell.get("scenario") or {}).get("name") == "multi-tenant-serving":
        raise UnsupportedPolicyError(
            "serving cells carry per-job tenant/SLO metadata the batched "
            "state arrays do not represent; run them on the oracle backend"
        )


def _resolve_dt(cell: Cell) -> float:
    from repro.core.batched import DEFAULT_DT_MIN

    return float((cell.get("backend_kwargs") or {}).get("dt_min", DEFAULT_DT_MIN))


def run_batched_cells(cells: Sequence[Cell]) -> List[Dict[str, Any]]:
    """Run batched cells grouped by physics; results in input order.

    Each group compiles its policy once (:func:`compile_policy` on a fresh
    registry instance, so batched cells honour exactly the defaults oracle
    cells get) and runs one vectorized rollout over its seeds; a fleet
    group runs each seed on its ``D`` devices, every device the policy's
    own instance of the same compiled targets.
    """
    from repro.core.batched import (
        BatchedJobs,
        build_tables,
        compile_policy,
        simulate_batch,
    )

    cells = list(cells)
    groups: Dict[str, List[int]] = {}
    for i, cell in enumerate(cells):
        validate_batched_cell(cell)
        groups.setdefault(batched_group_key(cell), []).append(i)

    one_gpu = build_tables()
    results: List[Dict[str, Any]] = [{} for _ in cells]
    for idx in groups.values():
        head = cells[idx[0]]
        fleet = head.get("fleet")
        if fleet:
            from repro.fleet.devices import device_profile

            prof = device_profile(fleet["devices"][0]["profile"])
            tables = build_tables(prof.configs, prof.power)
            devices, dispatcher = len(fleet["devices"]), fleet["dispatcher"]
        else:
            tables, devices, dispatcher = one_gpu, 1, "least-loaded"
        job_lists = [cell_jobs(cells[i]) for i in idx]
        jobs = BatchedJobs.from_job_lists(
            job_lists, max_slots=tables.max_slots,
            mig_enabled=head["mig_enabled"],
        )
        policy = compile_policy(
            make_policy(head["policy"], head.get("policy_kwargs")),
            tables, batch=len(idx),
        )
        res = simulate_batch(
            jobs, policy, tables=tables,
            repartition_mode=cell_repartition_mode(head),
            dt_min=_resolve_dt(head), devices=devices, dispatcher=dispatcher,
        )
        for i, out in zip(idx, res.to_result_dicts(), strict=True):
            results[i] = out
    return results
