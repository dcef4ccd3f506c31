"""Quickstart: the paper in 60 seconds.

Simulates one diurnal day on an A100-40GB under four policies and prints the
Table-III-style comparison, then shows the in-configuration scheduler ranking
(Table II, reduced basket).

    PYTHONPATH=src python examples/quickstart.py
"""

from repro.core import (
    DayNightPolicy,
    MIGSimulator,
    NoMIGPolicy,
    StaticPolicy,
    WorkloadSpec,
    et_table,
    generate_jobs,
    make_scheduler,
    queue_heuristic_policy,
)


def main() -> None:
    spec = WorkloadSpec()  # §V-A diurnal day, 80% inference

    print("=== Dynamic repartitioning vs benchmarks (Table III style) ===")
    per = {}
    for name, factory, mig in [
        ("NoMIG", NoMIGPolicy, False),
        ("StaticMIG(cfg3)", lambda: StaticPolicy(3), True),
        ("DayNightMIG", DayNightPolicy, True),
        ("DynamicMIG", queue_heuristic_policy, True),
    ]:
        sim = MIGSimulator(make_scheduler("EDF-SS"), mig_enabled=mig)
        per[name] = [
            sim.run(generate_jobs(spec, seed=s), policy=factory()) for s in range(4)
        ]
    table, a = et_table(per)
    for k, v in sorted(table.items(), key=lambda kv: kv[1]):
        rs = per[k]
        print(
            f"  {k:16s} ET={v:7.3f}  energy={sum(r.energy_wh for r in rs)/4:7.1f} Wh"
            f"  tardiness={sum(r.avg_tardiness for r in rs)/4:6.3f} min"
            f"  repartitions={sum(r.repartitions for r in rs)/4:5.1f}"
        )

    print("\n=== In-configuration schedulers (Table II style, config 3) ===")
    per = {}
    for name in ("EDF-FS", "EDF-SS", "LLF", "LALF"):
        sim = MIGSimulator(make_scheduler(name))
        per[name] = [
            sim.run(generate_jobs(spec, seed=100 + s), policy=StaticPolicy(3))
            for s in range(3)
        ]
    table, _ = et_table(per)
    for k, v in sorted(table.items(), key=lambda kv: kv[1]):
        print(f"  {k:8s} ET={v:7.3f}  preemptions={sum(r.preemptions for r in per[k])/3:6.1f}")


if __name__ == "__main__":
    main()
