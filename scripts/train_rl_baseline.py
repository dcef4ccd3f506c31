"""Train the batched DQN and check in the RL-vs-forecast baseline.

ROADMAP item 4's gating rule: the batch-trained policy must beat the
predictive forecast controller on at least one scenario family before the
RL track counts as ahead of the hand-built policies.  This script is that
gate's producer and its re-checker:

::

    PYTHONPATH=src python scripts/train_rl_baseline.py           # retrain + eval + write
    PYTHONPATH=src python scripts/train_rl_baseline.py --check   # re-eval checked-in params
    PYTHONPATH=src python scripts/train_rl_baseline.py --scale 0.1

Training runs the fused on-device trainer (repro.core.rl.batched_train)
at the configuration in repro.core.rl.baseline, with fixed seeds over a
scenario × load-scale randomized episode stream; the greedy policy is
then evaluated on its 15-min training cadence against the forecast
controller over every registered scenario family (same seeds
→ identical job streams per family) at the standard ``--scale 0.1``
sizing, and the summary lands in ``benchmarks/baselines/rl_batched.json``
next to the params (``rl_dqn_params.npz``).  The DQN side evaluates
through an ad-hoc factory (inline, uncached) so a retrain can never be
served stale memoized cells recorded under the same params path.

``--check`` skips training and re-evaluates the *checked-in* params: the
nightly workflow runs it so a simulator or forecast change that erases
the recorded win fails loudly instead of letting the baseline rot.  CI
gates the cheap half (tests/test_batched_train.py pins the params file
against recorded greedy actions and asserts the baseline's claim).
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

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PARAMS_OUT = os.path.join(REPO_ROOT, "benchmarks", "baselines", "rl_dqn_params.npz")
BASELINE_OUT = os.path.join(REPO_ROOT, "benchmarks", "baselines", "rl_batched.json")

from repro.core.rl.baseline import (
    DECISION_INTERVAL_MIN,
    LOAD_SCALE_RANGE,
    TRAIN_EPISODES,
    TRAIN_SCENARIOS,
    TRAIN_SEED,
    dqn_config,
    train,
)

EVAL_SEED = 90_000


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


def evaluate(params_path: str, scale: float = 0.1, workers: int = 0) -> list:
    """Race the saved policy against the forecast controller per family.

    Same seeds on both sides → identical job streams; the DQN runs
    uncached (ad-hoc factory) so the results always reflect the params
    file on disk, the forecast side goes through the registered (cached,
    deterministic) sweep policy.
    """
    from repro.core.metrics import et_table
    from repro.core.rl import DQNLearner, evaluate_policy, greedy_policy
    from repro.sweep.grids import SCENARIO_ORDER, _iters

    learner = DQNLearner(dqn_config())
    learner.load(params_path)
    iters = _iters(40, scale, floor=4)
    rows = []
    for sname in SCENARIO_ORDER:
        common = dict(
            num_iterations=iters,
            scheduler_name="EDF-SS",
            seed=EVAL_SEED,
            scenario=sname,
        )
        per = {
            "DQN": evaluate_policy(
                lambda: greedy_policy(
                    learner, decision_interval_min=DECISION_INTERVAL_MIN
                ),
                **common,
            ),
            "Forecast": evaluate_policy(
                ("forecast", {"scenario": sname}), workers=workers, **common
            ),
        }
        t, a = et_table(per)
        rows.append(
            {
                "scenario": sname,
                "et_a": a,
                "ET_DQN": round(t["DQN"], 4),
                "ET_Forecast": round(t["Forecast"], 4),
                "dqn_beats_forecast": bool(t["DQN"] < t["Forecast"]),
                "repartitions_DQN": round(
                    sum(r.repartitions for r in per["DQN"]) / iters, 1
                ),
                "energy_wh_DQN": round(
                    sum(r.energy_wh for r in per["DQN"]) / iters, 1
                ),
                "iterations": iters,
            }
        )
        print(
            f"{sname:22s} ET DQN={t['DQN']:8.4f}  Forecast={t['Forecast']:8.4f}"
            f"  {'WIN' if t['DQN'] < t['Forecast'] else ''}",
            file=sys.stderr,
        )
    return rows


def _params_probe(params_path: str, seed: int = 123, n: int = 16) -> dict:
    """Greedy actions on a fixed pseudo-random observation batch.

    A cheap determinism pin for CI: tests/test_batched_train.py recomputes
    the probe from the checked-in params and compares — a silently
    corrupted or stale params file fails there without re-running a single
    simulated day.
    """
    import numpy as np
    from repro.core.rl import DQNLearner
    from repro.core.rl.env import FEATURE_DIM

    learner = DQNLearner(dqn_config())
    learner.load(params_path)
    rng = np.random.default_rng(seed)
    obs = rng.uniform(0.0, 1.0, size=(n, FEATURE_DIM))
    return {
        "seed": seed,
        "actions": [
            int(learner.greedy_action(o.astype(np.float32))) for o in obs
        ],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scale", type=float, default=0.1,
                    help="evaluation sizing, as in the sweep grids")
    ap.add_argument("--episodes", type=int, default=TRAIN_EPISODES)
    ap.add_argument("--check", action="store_true",
                    help="skip training: re-evaluate the checked-in params "
                         "and gate on the recorded win still holding")
    ap.add_argument("--workers", type=int, default=0)
    ap.add_argument("--params", default=PARAMS_OUT)
    ap.add_argument("--out", default=BASELINE_OUT)
    args = ap.parse_args(argv)

    if not args.check:
        t0 = time.time()
        learner, stats = train(args.episodes)
        print(
            f"trained {stats.episodes} episodes / {stats.env_steps} env steps "
            f"in {stats.wall_seconds:.1f}s ({stats.env_steps_per_sec:.0f}/s), "
            f"{stats.updates} updates, final eps {stats.final_epsilon:.3f}",
            file=sys.stderr,
        )
        os.makedirs(os.path.dirname(args.params), exist_ok=True)
        learner.save(args.params)
        print(f"wrote {args.params} ({time.time() - t0:.1f}s)", file=sys.stderr)
    elif not os.path.exists(args.params):
        print(f"--check: no params at {args.params}", file=sys.stderr)
        return 1

    rows = evaluate(args.params, scale=args.scale, workers=args.workers)
    wins = [r["scenario"] for r in rows if r["dqn_beats_forecast"]]
    probe = _params_probe(args.params)
    entry = {
        "date": datetime.datetime.now(datetime.timezone.utc).strftime("%Y-%m-%d"),
        "git_sha": _git_sha(),
        "scale": args.scale,
        "train": {
            "backend": "batched",
            "episodes": args.episodes,
            "seed": TRAIN_SEED,
            "scenarios": list(TRAIN_SCENARIOS),
            "load_scale_range": list(LOAD_SCALE_RANGE),
            "decision_interval_min": DECISION_INTERVAL_MIN,
        },
        "eval_seed": EVAL_SEED,
        "rows": rows,
        "families_beaten": wins,
        "params_probe": probe,
    }
    if args.check:
        print(json.dumps(entry, indent=2))
    else:
        from repro.core.simulator import SIM_VERSION

        entry["sim_version"] = SIM_VERSION
        with open(args.out, "w") as f:
            json.dump(entry, f, indent=2)
            f.write("\n")
        print(f"wrote {args.out}", file=sys.stderr)
    if not wins:
        print(
            "RL BASELINE GATE: batch-trained policy beats the forecast "
            "controller on 0 scenario families (need >=1)",
            file=sys.stderr,
        )
        return 1
    print(f"beats forecast on: {', '.join(wins)}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
