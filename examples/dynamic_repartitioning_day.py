"""The paper's headline experiment end-to-end: train the repartitioning DQN,
then run Table III (Dynamic vs DayNight vs Static vs NoMIG).

    PYTHONPATH=src python examples/dynamic_repartitioning_day.py \
        [--episodes 400] [--eval-iterations 20] [--backend host|batched]

Short trainings underperform; EXPERIMENTS.md used 900+ episodes.
``--backend batched`` trains with the fused on-device scan
(repro.core.rl.batched_train): EDF-FS, fixed 15-min decision cadence,
orders of magnitude more env-steps/sec (scripts/bench_rl.py measures it).
"""

import argparse

from repro.core.metrics import et_table
from repro.core.rl import evaluate_policy, greedy_policy, train_dqn
from repro.core.rl.dqn import DQNConfig
from repro.core.rl.env import FEATURE_DIM
from repro.core.simulator import (
    DayNightPolicy,
    NoMIGPolicy,
    StaticPolicy,
    queue_heuristic_policy,
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--episodes", type=int, default=400)
    ap.add_argument("--eval-iterations", type=int, default=20)
    ap.add_argument("--save", default=None)
    ap.add_argument("--backend", choices=("host", "batched"), default="host")
    args = ap.parse_args()

    cfg = DQNConfig(
        state_dim=FEATURE_DIM,
        eps_decay_episodes=max(args.episodes // 2, 1),
        n_step=8,
        lr=3e-4,
        target_sync_every=2000,
    )
    if args.backend == "batched":
        learner, stats = train_dqn(
            num_episodes=args.episodes,
            dqn_config=cfg,
            verbose=True,
            backend="batched",
            scheduler_name="EDF-FS",
        )
        print(
            f"batched training: {stats.env_steps} env steps in "
            f"{stats.wall_seconds:.1f}s ({stats.env_steps_per_sec:.0f}/s)"
        )
    else:
        learner, stats = train_dqn(
            num_episodes=args.episodes,
            dqn_config=cfg,
            verbose=True,
            guide=queue_heuristic_policy(),
            guide_episodes=max(args.episodes // 10, 10),
        )
    if args.save:
        learner.save(args.save)

    per = {
        "NoMIG": evaluate_policy(
            NoMIGPolicy, num_iterations=args.eval_iterations, mig_enabled=False
        ),
        "StaticMIG": evaluate_policy(
            lambda: StaticPolicy(3), num_iterations=args.eval_iterations
        ),
        "DayNightMIG": evaluate_policy(
            DayNightPolicy, num_iterations=args.eval_iterations
        ),
        # cadence-trained policies evaluate on the same 15-min cadence
        "DynamicMIG(DQN)": evaluate_policy(
            lambda: greedy_policy(
                learner,
                decision_interval_min=(
                    15.0 if args.backend == "batched" else None
                ),
            ),
            num_iterations=args.eval_iterations,
        ),
    }
    table, a = et_table(per)
    print(f"\nTable III (a={a:.2e}):")
    for k, v in sorted(table.items(), key=lambda kv: kv[1]):
        rs = per[k]
        n = len(rs)
        print(
            f"  {k:16s} ET={v:7.3f} energy={sum(r.energy_wh for r in rs)/n:7.1f}Wh "
            f"tardiness={sum(r.avg_tardiness for r in rs)/n:6.3f}min "
            f"repartitions={sum(r.repartitions for r in rs)/n:6.1f}"
        )


if __name__ == "__main__":
    main()
