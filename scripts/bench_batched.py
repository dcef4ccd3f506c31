"""Benchmark: batched-backend throughput in oracle events/sec-equivalent.

The two backends do different amounts of work per unit of simulated time —
the oracle processes discrete events, the batched backend fixed grid steps
— so raw "steps/sec" comparisons are meaningless.  The common currency is
*events/sec-equivalent*: how many oracle events the batched backend retires
per wall-second, i.e.

    ev_eq/s = (mean oracle events per rollout) * batch / batched wall time

measured on the *same workload*.  Dividing by the oracle's own events/sec
on that workload gives the wall-clock speedup ratio the two-backend
contract gates on (docs/BATCHED_SIM.md §6): the oracle's per-event cost
grows with queue depth (O(queue) scheduler passes) while the batched
per-step cost is load-flat, so the ratio rises with ``load_scale`` — the
curve below measures exactly that, and the headline is its best point.

::

    PYTHONPATH=src python scripts/bench_batched.py               # full curve
    PYTHONPATH=src python scripts/bench_batched.py --quick       # CI smoke
    PYTHONPATH=src python scripts/bench_batched.py --min-ratio 20
    PYTHONPATH=src python scripts/bench_batched.py --write-agreement

Writes ``artifacts/bench/batched_events.json`` (collected into the
BENCH_nightly.json trajectory by ``scripts/bench_nightly.py``);
``--write-agreement`` additionally refreshes the checked-in agreement
baseline ``benchmarks/baselines/batched_agreement.json`` that
``scripts/render_experiments.py`` renders into EXPERIMENTS.md.

``--min-ratio`` is the CI/nightly gate: machine-portable (both backends
run on the same box) where an absolute ev_eq/s floor is not.  The floor is
set far below the measured headline — it catches structural regressions
(a reintroduced per-step sort, a broken scatter merge), not timer noise.
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

DEFAULT_OUT = os.path.join("artifacts", "bench", "batched_events.json")
AGREEMENT_OUT = os.path.join("benchmarks", "baselines", "batched_agreement.json")

#: the measured curve: heavier load -> deeper queues -> slower oracle, while
#: the batched per-step cost stays flat.  Batch sizes keep each point a few
#: seconds of wall time; oracle seeds shrink as its per-rollout cost explodes
#: (35 s/rollout at load 12) — the reference only needs a stable mean.
FULL_POINTS = (
    {"load_scale": 1.0, "batch": 64, "oracle_seeds": 3},
    {"load_scale": 4.0, "batch": 32, "oracle_seeds": 2},
    {"load_scale": 8.0, "batch": 16, "oracle_seeds": 1},
    {"load_scale": 12.0, "batch": 16, "oracle_seeds": 1},
)
QUICK_POINTS = ({"load_scale": 2.0, "batch": 8, "oracle_seeds": 2},)


def _git_sha() -> str:
    sha = os.environ.get("GITHUB_SHA")
    if sha:
        return sha
    try:
        return (
            subprocess.run(
                ["git", "rev-parse", "HEAD"], capture_output=True, text=True, check=True
            ).stdout.strip()
        )
    except (OSError, subprocess.CalledProcessError):
        return "unknown"


def measure_point(
    load_scale: float,
    batch: int,
    oracle_seeds: int,
    dt_min: float = 0.5,
    scenario: str = "paper-diurnal",
) -> dict:
    """One curve point: oracle reference + batched run + agreement check.

    The oracle reference replays seeds ``0..oracle_seeds-1``; the batched run
    covers seeds ``0..batch-1``, so the reference seeds are a prefix and the
    per-seed agreement columns compare identical job streams.
    """
    from repro.core.batched import BatchedJobs, build_tables, compile_policy, simulate_batch
    from repro.core.engine import SimulationEngine
    from repro.core.scenarios import generate_scenario
    from repro.core.schedulers import make_scheduler
    from repro.core.simulator import DayNightPolicy, MIGSimulator

    def day(seed):
        return generate_scenario(scenario, seed=seed, load_scale=load_scale)

    # --- oracle reference (fresh jobs per run: jobs carry mutable state)
    events = 0
    oracle_results = []
    t0 = time.perf_counter()
    for s in range(oracle_seeds):
        sim = MIGSimulator(make_scheduler("EDF-FS"))
        engine = SimulationEngine(sim, policy=DayNightPolicy(), jobs=day(s))
        engine.drain()
        oracle_results.append(engine.result())
        events += engine.events_processed
    oracle_wall = time.perf_counter() - t0
    oracle_eps = events / oracle_wall if oracle_wall > 0 else float("inf")
    ev_per_rollout = events / oracle_seeds

    # --- batched run over the same scenario, seeds 0..batch-1
    tables = build_tables()
    jobs = BatchedJobs.from_job_lists(
        [day(s) for s in range(batch)], max_slots=tables.max_slots
    )
    policy = compile_policy(DayNightPolicy(), tables, batch)
    # warm-up: one chunk compiles the scan for these shapes, so the timed
    # run below measures steady-state throughput, not XLA compile time
    from repro.core.batched import DEFAULT_CHUNK_STEPS
    from repro.core.batched.backend import device_constants, init_state, run_steps

    layout = jobs.in_edf_order()
    run_steps(
        init_state(layout, policy.initial), layout, policy,
        device_constants(tables, "partial"),
        t0_min=0.0, n_steps=DEFAULT_CHUNK_STEPS, dt_min=dt_min,
    )
    t0 = time.perf_counter()
    res = simulate_batch(jobs, policy, tables=tables, dt_min=dt_min)
    batched_wall = time.perf_counter() - t0
    ev_eq = ev_per_rollout * batch / batched_wall if batched_wall > 0 else float("inf")

    # --- agreement on the shared seed prefix (render_experiments renders it)
    b_results = res.to_sim_results()
    agree_rows = []
    for s, o in enumerate(oracle_results):
        b = b_results[s]
        agree_rows.append(
            {
                "seed": s,
                "energy_rel": abs(b.energy_wh - o.energy_wh) / max(o.energy_wh, 1e-9),
                "tardiness_abs": abs(b.avg_tardiness - o.avg_tardiness),
                "tardiness_rel": abs(b.avg_tardiness - o.avg_tardiness)
                / max(o.avg_tardiness, 0.25),
                "repartitions_oracle": o.repartitions,
                "repartitions_batched": b.repartitions,
                "busy_rel": abs(b.busy_slot_minutes - o.busy_slot_minutes)
                / max(o.busy_slot_minutes, 1e-9),
            }
        )
    agreement = {
        "seeds": oracle_seeds,
        "energy_rel_max": max(r["energy_rel"] for r in agree_rows),
        "tardiness_abs_max": max(r["tardiness_abs"] for r in agree_rows),
        "tardiness_rel_max": max(r["tardiness_rel"] for r in agree_rows),
        "busy_rel_max": max(r["busy_rel"] for r in agree_rows),
        "repartitions_exact": all(
            r["repartitions_oracle"] == r["repartitions_batched"] for r in agree_rows
        ),
        "rows": agree_rows,
    }
    return {
        "load_scale": load_scale,
        "batch": batch,
        "padded_jobs": jobs.padded_jobs,
        "oracle_seeds": oracle_seeds,
        "oracle_events_per_rollout": round(ev_per_rollout, 1),
        "oracle_seconds_per_rollout": round(oracle_wall / oracle_seeds, 4),
        "oracle_events_per_sec": round(oracle_eps, 1),
        "batched_seconds": round(batched_wall, 4),
        "batched_seconds_per_rollout": round(batched_wall / batch, 4),
        "events_equiv_per_sec": round(ev_eq, 1),
        "ratio_vs_oracle": round(ev_eq / oracle_eps, 2),
        "agreement": agreement,
    }


def measure(points, dt_min: float = 0.5, scenario: str = "paper-diurnal",
            verbose: bool = True) -> dict:
    """The full curve; the headline is the best-ratio point."""
    from repro.core.simulator import SIM_VERSION

    measured = []
    for p in points:
        m = measure_point(dt_min=dt_min, scenario=scenario, **p)
        if verbose:
            print(
                f"load {m['load_scale']:>4}: oracle "
                f"{m['oracle_events_per_sec']:>8.0f} ev/s, batched "
                f"{m['events_equiv_per_sec']:>8.0f} ev_eq/s "
                f"({m['ratio_vs_oracle']:.1f}x)",
                file=sys.stderr,
            )
        measured.append(m)
    head = max(measured, key=lambda m: m["ratio_vs_oracle"])
    return {
        "date": datetime.datetime.now(datetime.timezone.utc).strftime("%Y-%m-%d"),
        "git_sha": _git_sha(),
        "sim_version": SIM_VERSION,
        "scenario": scenario,
        "policy": "daynight",
        "dt_min": dt_min,
        "points": measured,
        "headline_load_scale": head["load_scale"],
        "events_equiv_per_sec": head["events_equiv_per_sec"],
        "ratio_vs_oracle": head["ratio_vs_oracle"],
    }


def write_agreement(entry: dict, path: str = AGREEMENT_OUT) -> None:
    """The checked-in agreement/speedup baseline EXPERIMENTS.md renders."""
    payload = {
        k: entry[k]
        for k in (
            "date", "git_sha", "sim_version", "scenario", "policy", "dt_min",
            "points", "headline_load_scale", "events_equiv_per_sec",
            "ratio_vs_oracle",
        )
    }
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"wrote {path}", file=sys.stderr)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--dt-min", type=float, default=0.5)
    ap.add_argument("--quick", action="store_true",
                    help="one small point (CI smoke) instead of the curve")
    ap.add_argument("--min-ratio", type=float, default=None,
                    help="fail (exit 1) when the headline speedup vs the "
                         "oracle falls below this — the nightly gate")
    ap.add_argument("--min-events-equiv-per-sec", type=float, default=None,
                    help="absolute ev_eq/s floor (machine-specific)")
    ap.add_argument("--write-agreement", action="store_true",
                    help=f"also refresh {AGREEMENT_OUT}")
    ap.add_argument("--dry-run", action="store_true", help="print, don't write")
    args = ap.parse_args(argv)

    points = QUICK_POINTS if args.quick else FULL_POINTS
    entry = measure(points, dt_min=args.dt_min)
    print(json.dumps(entry, indent=2))
    if not args.dry_run:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(entry, f, indent=2)
            f.write("\n")
        print(f"wrote {args.out}", file=sys.stderr)
        if args.write_agreement:
            write_agreement(entry)

    failures = []
    if args.min_ratio is not None and entry["ratio_vs_oracle"] < args.min_ratio:
        failures.append(
            f"BATCHED SPEEDUP REGRESSION: {entry['ratio_vs_oracle']:.1f}x "
            f"< floor {args.min_ratio:.1f}x"
        )
    if (
        args.min_events_equiv_per_sec is not None
        and entry["events_equiv_per_sec"] < args.min_events_equiv_per_sec
    ):
        failures.append(
            f"BATCHED THROUGHPUT REGRESSION: "
            f"{entry['events_equiv_per_sec']:.0f} ev_eq/s < floor "
            f"{args.min_events_equiv_per_sec:.0f} ev_eq/s"
        )
    for msg in failures:
        print(msg, file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
