"""Cluster-day demo: the paper's scheduler running a TPU pod serving the 10
assigned architectures, with failure injection.

    PYTHONPATH=src python examples/cluster_day.py [--failures]
"""

import argparse

from repro.core.metrics import et_table
from repro.core.simulator import DayNightPolicy, StaticPolicy, queue_heuristic_policy
from repro.distributed.fault_tolerance import FailureModel
from repro.launch.cluster_sim import run_days


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iterations", type=int, default=5)
    ap.add_argument("--failures", action="store_true")
    args = ap.parse_args()

    per = {
        "static": run_days(lambda: StaticPolicy(3), iterations=args.iterations),
        "daynight": run_days(DayNightPolicy, iterations=args.iterations),
        "dynamic": run_days(queue_heuristic_policy, iterations=args.iterations),
    }
    table, _ = et_table(per)
    print("TPU pod, diurnal (arch x shape) job mix:")
    for k, v in sorted(table.items(), key=lambda kv: kv[1]):
        rs = per[k]
        n = len(rs)
        print(
            f"  {k:9s} ET={v:9.3f} energy={sum(r.energy_wh for r in rs)/n/1000:7.1f}kWh/day "
            f"tardiness={sum(r.avg_tardiness for r in rs)/n:7.3f}min "
            f"repartitions={sum(r.repartitions for r in rs)/n:6.1f}"
        )
    if args.failures:
        fm = FailureModel(mtbf_minutes=12 * 60.0, seed=7)
        rs = run_days(queue_heuristic_policy, iterations=args.iterations, failures=fm)
        n = len(rs)
        print(
            f"  with slice failures (MTBF 12h): "
            f"tardiness={sum(r.avg_tardiness for r in rs)/n:7.3f}min "
            f"(jobs all complete: {all(r.num_jobs > 0 for r in rs)})"
        )


if __name__ == "__main__":
    main()
