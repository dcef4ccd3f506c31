"""Benchmark runner: one function per paper table/figure + substrate benches.

``PYTHONPATH=src python -m benchmarks.run [--scale S] [--only NAME] [--workers N]``

Prints ``name,us_per_call,derived`` style CSV blocks per benchmark and saves
them under artifacts/bench/.  --scale grows iteration counts (1.0 = CI-sized;
the EXPERIMENTS.md numbers used --scale 4).  --workers fans the paper-table
sweeps out over worker processes (see repro.sweep); substrate benches stay
single-process.
"""

from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--only", default=None)
    ap.add_argument("--workers", type=int, default=0)
    args = ap.parse_args()

    from benchmarks.paper_tables import (
        fig4_preemption,
        fig6_utilization,
        fig7_fig8_arrival,
        fig9_fig10_split,
        fig11_preferences,
        fleet_scaling,
        scenario_matrix,
        table2_schedulers,
        table3_repartitioning,
    )
    from benchmarks.roofline_table import cluster_benchmark, roofline_table

    sweep_benches = {
        "table2_schedulers": table2_schedulers,
        "fig4_preemption": fig4_preemption,
        "fig6_utilization": fig6_utilization,
        "fig7_fig8_arrival": fig7_fig8_arrival,
        "fig9_fig10_split": fig9_fig10_split,
        "table3_repartitioning": table3_repartitioning,
        "fig11_preferences": fig11_preferences,
        "fleet_scaling": fleet_scaling,
        "scenario_matrix": scenario_matrix,
    }
    benches = {
        **sweep_benches,
        "roofline_table": roofline_table,
        "cluster_day": cluster_benchmark,
    }
    failures = 0
    for name, fn in benches.items():
        if args.only and args.only != name:
            continue
        t0 = time.time()
        try:
            if name in sweep_benches:
                fn(scale=args.scale, workers=args.workers)
            else:
                fn(scale=args.scale)
            print(f"# {name} done in {time.time()-t0:.1f}s\n")
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"# {name} FAILED: {type(e).__name__}: {e}\n")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
