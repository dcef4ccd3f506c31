"""Predictive repartitioning walkthrough: forecast + MPC over a simulated day.

    PYTHONPATH=src python examples/predictive_day.py [--seeds 8] [--scenario paper-diurnal]

1. fits the diurnal Fourier day-model on training days of the scenario and
   prints it against the Fig. 5 ground truth;
2. runs one day under the predictive ForecastPolicy and prints the
   configuration timeline it chose (the paper's closing conjecture —
   "specific preferred configurations at different times of the day" —
   made executable);
3. compares ForecastPolicy against NoMIG / Static / DayNight / queue
   heuristic on the ET metric over ``--seeds`` evaluation days;
4. optionally warm-starts a small DQN from the controller
   (``--warm-start-episodes N`` — the ``train_dqn(guide=...)`` hook).
"""

import argparse

from repro.core.metrics import et_table
from repro.core.scenarios import generate_scenario
from repro.core.schedulers import make_scheduler
from repro.core.simulator import (
    DayNightPolicy,
    MIGSimulator,
    NoMIGPolicy,
    StaticPolicy,
    queue_heuristic_policy,
)
from repro.core.workload import arrival_rate
from repro.forecast import ArrivalForecaster, ForecastPolicy, fit_scenario_forecaster


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", default="paper-diurnal")
    ap.add_argument("--seeds", type=int, default=8, help="evaluation days per policy")
    ap.add_argument("--train-seeds", type=int, default=8, help="days the forecaster fits on")
    ap.add_argument("--warm-start-episodes", type=int, default=0,
                    help="also train a DQN for N episodes guided by the controller")
    args = ap.parse_args()

    # 1. fit the day model ------------------------------------------------
    model = fit_scenario_forecaster(scenario=args.scenario, train_seeds=args.train_seeds)
    print(f"Fourier day-model ({model.harmonics} harmonics) vs Fig. 5 pattern:")
    for h in range(0, 24, 3):
        print(f"  {h:02d}:00  fitted {model.rate(h * 60.0):.3f} jobs/min"
              f"   true {arrival_rate(h * 60.0):.3f}")

    # 2. one predictive day ----------------------------------------------
    sim = MIGSimulator(make_scheduler("EDF-SS"))
    policy = ForecastPolicy(ArrivalForecaster(model))
    res = sim.run(generate_scenario(args.scenario, seed=0), policy=policy)
    print(f"\nConfig timeline (seed 0, {res.repartitions} repartitions):")
    for t, cfg in sim.config_trace:
        print(f"  {int(t) // 60:02d}:{int(t) % 60:02d}  -> config {cfg}")

    # 3. policy-family comparison ----------------------------------------
    def run_days(policy_factory, mig_enabled=True):
        out = []
        for k in range(args.seeds):
            s = MIGSimulator(make_scheduler("EDF-SS"), mig_enabled=mig_enabled)
            out.append(s.run(generate_scenario(args.scenario, seed=10_000 + k),
                             policy=policy_factory()))
        return out

    per = {
        # NoMIG disables MIG so linear jobs get the §V-A 6 % full-GPU
        # speedup — same definition as the repartition_policies grid
        "NoMIG": run_days(NoMIGPolicy, mig_enabled=False),
        "StaticMIG": run_days(lambda: StaticPolicy(3)),
        "DayNightMIG": run_days(DayNightPolicy),
        "Heuristic": run_days(queue_heuristic_policy),
        "Forecast": run_days(lambda: ForecastPolicy(ArrivalForecaster(model))),
    }
    table, a = et_table(per)
    print(f"\nET comparison over {args.seeds} days (a={a:.2e}):")
    for name, et in sorted(table.items(), key=lambda kv: kv[1]):
        rs = per[name]
        n = len(rs)
        print(f"  {name:12s} ET={et:8.4f} energy={sum(r.energy_wh for r in rs)/n:7.1f}Wh"
              f" tardiness={sum(r.avg_tardiness for r in rs)/n:6.3f}min"
              f" repartitions={sum(r.repartitions for r in rs)/n:6.1f}")

    # 4. optional: warm-start the DQN from the controller -----------------
    if args.warm_start_episodes > 0:
        from repro.core.rl import train_dqn

        guide = ForecastPolicy(ArrivalForecaster(model))
        learner, stats = train_dqn(
            num_episodes=args.warm_start_episodes,
            guide=guide,
            guide_episodes=max(args.warm_start_episodes // 4, 1),
            scenario=args.scenario,
        )
        tail = stats.episode_rewards[-10:]
        print(f"\nDQN warm-started from the controller: {stats.episodes} episodes,"
              f" final-{len(tail)} reward {sum(tail) / max(len(tail), 1):.2f}")


if __name__ == "__main__":
    main()
