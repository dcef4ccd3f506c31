"""Online streaming: inject jobs into a *running* simulation engine.

    PYTHONPATH=src python examples/streaming_day.py [--scenario paper-diurnal]
        [--load-scale 0.25] [--seed 0] [--policy heuristic]

The paper's simulator ran one pre-known job list to completion; the
steppable :class:`~repro.core.engine.SimulationEngine` decouples the
producer from the event loop.  This example plays a scenario day as a live
stream — each arrival is ``inject()``-ed only when its time comes, exactly
as an online controller would receive it — and prints queue/partition
telemetry at every simulated hour boundary read off live engine snapshots.
A trace sink counts events per hour on the side.

This is the single-device version of what :class:`repro.fleet.FleetSimulator`
does fleet-wide in online dispatch mode (one engine per device co-advanced
on the merged arrival clock).
"""

import argparse

from repro.core.engine import SimulationEngine
from repro.core.scenarios import generate_scenario
from repro.core.schedulers import make_scheduler
from repro.core.simulator import DayNightPolicy, MIGSimulator, queue_heuristic_policy


def make_policy(name: str):
    if name == "heuristic":
        return queue_heuristic_policy()
    if name == "daynight":
        return DayNightPolicy()
    raise SystemExit(f"unknown policy {name!r} (heuristic|daynight)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", default="paper-diurnal")
    ap.add_argument("--load-scale", type=float, default=0.25)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--policy", default="heuristic")
    args = ap.parse_args()

    jobs = generate_scenario(
        args.scenario, seed=args.seed, load_scale=args.load_scale
    )
    print(f"streaming {len(jobs)} arrivals of '{args.scenario}' "
          f"(load x{args.load_scale}, seed {args.seed}) under {args.policy}\n")

    hour_events = {"n": 0}

    sim = MIGSimulator(make_scheduler("EDF-SS"))
    engine = SimulationEngine(
        sim,
        policy=make_policy(args.policy),
        stream_open=True,  # arrivals come online, not up front
        trace_sink=lambda ev: hour_events.__setitem__("n", hour_events["n"] + 1),
    )

    print("hour   queue  running  config  backlog(1g-min)  energy(Wh)  events/h")
    next_report = 60.0

    def report():
        s = engine.snapshot().sim
        print(
            f"{int(next_report) // 60:02d}:00  "
            f"{s.queue_depth:5d}  {s.running:7d}  {s.config_id:6d}  "
            f"{s.backlog_1g_min:15.1f}  {s.energy_wh:10.1f}  {hour_events['n']:8d}"
        )
        hour_events["n"] = 0

    for job in jobs:
        # advance the live engine to this arrival, reporting at each
        # crossed hour boundary from the running engine's snapshot
        while next_report <= job.arrival:
            engine.run_until(next_report)
            report()
            next_report += 60.0
        engine.inject(job)
        engine.run_until(job.arrival)
    engine.close_stream()
    while not engine.finished:
        engine.run_until(next_report)
        report()
        next_report += 60.0

    res = engine.result()
    print(
        f"\ndrained at {sim.t:.1f} min: {res.num_jobs} jobs, "
        f"{res.energy_wh:.1f} Wh, avg tardiness {res.avg_tardiness:.3f} min, "
        f"{res.repartitions} repartitions, "
        f"{engine.events_processed} events processed"
    )


if __name__ == "__main__":
    main()
