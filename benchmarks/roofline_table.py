"""Roofline table (§Roofline) — reads the dry-run artifacts."""

from __future__ import annotations

from typing import Dict, List

from benchmarks.common import emit
from repro.analysis.roofline import roofline_row
from repro.launch.shapes import SHAPES, all_cells


def roofline_table(scale: float = 1.0) -> List[Dict]:
    rows: List[Dict] = []
    for arch, shape in all_cells():
        row = roofline_row(arch, shape.name)
        if row is None:
            rows.append({"arch": arch, "shape": shape.name, "status": "missing"})
            continue
        if row.get("skipped"):
            rows.append(
                {"arch": arch, "shape": shape.name, "status": "skipped",
                 "note": row.get("reason", "")}
            )
            continue
        if row.get("failed"):
            rows.append({"arch": arch, "shape": shape.name, "status": "failed"})
            continue
        rows.append(
            {
                "arch": arch,
                "shape": shape.name,
                "status": "ok",
                "t_compute_s": row["t_compute_s"],
                "t_memory_s": row["t_memory_s"],
                "t_collective_s": row["t_collective_s"],
                "dominant": row["dominant"],
                "model_flops": row["model_flops"],
                "useful_ratio": row["useful_ratio"],
                "roofline_fraction": row["roofline_fraction"],
                "temp_gb_per_device": (row.get("temp_bytes_per_device") or 0) / 1e9,
            }
        )
    emit("roofline_table", rows)
    return rows


def cluster_benchmark(scale: float = 1.0) -> List[Dict]:
    """Cluster-day benchmark: paper's policies on the TPU pod (DESIGN.md §2)."""
    from benchmarks.common import summarize
    from repro.core.metrics import et_table
    from repro.core.simulator import DayNightPolicy, StaticPolicy, queue_heuristic_policy
    from repro.launch.cluster_sim import run_days
    from repro.distributed.fault_tolerance import FailureModel

    iters = max(int(5 * scale), 2)
    per = {
        "static": run_days(lambda: StaticPolicy(3), iterations=iters),
        "daynight": run_days(DayNightPolicy, iterations=iters),
        "dynamic": run_days(queue_heuristic_policy, iterations=iters),
    }
    table, _ = et_table(per)
    rows = []
    for k in per:
        rows.append({"policy": k, "ET": table[k], **summarize(per[k])})
    # fault drill
    fr = run_days(
        queue_heuristic_policy, iterations=max(iters // 2, 1),
        failures=FailureModel(mtbf_minutes=12 * 60.0, seed=7),
    )
    rows.append({"policy": "dynamic+failures", "ET": float("nan"), **summarize(fr)})
    emit("cluster_day", rows)
    return rows
