"""Benchmark: RL training throughput, host loop vs fused on-device trainer.

The common currency is *env-steps/sec*: one env step = one repartitioning
decision (observe -> act -> advance one interval -> store -> train).
Both loops run the identical ``DQNConfig`` on the same scenario family at
the same fixed 15-min decision cadence, with ``min_buffer`` set so the
per-decision TD update runs from (nearly) the first step — steady
*training* throughput, not untrained env stepping.  The host side is
:func:`repro.core.rl.train.train_dqn` stepping one cadence-mode
:class:`repro.core.rl.env.RepartitionEnv` episode at a time; the batched
side is the fused trainer (:mod:`repro.core.rl.batched_train`) advancing
B rollouts plus the learner update inside one jitted scan.

::

    PYTHONPATH=src python scripts/bench_rl.py            # full measurement
    PYTHONPATH=src python scripts/bench_rl.py --quick    # CI smoke
    PYTHONPATH=src python scripts/bench_rl.py --min-ratio 50

Writes ``artifacts/bench/rl_bench.json`` (collected into the
BENCH_nightly.json trajectory by ``scripts/bench_nightly.py``).  The entry
also records the host-oracle *agreement* check: one jitted TD update
through the trainer's scan-embedded path vs the host ``DQNLearner``'s own
update on an identical replay batch — they share
:func:`repro.core.rl.dqn.make_td_update`, so the max parameter difference
must sit at float32 noise (documented tolerance 1e-5; DESIGN.md §11).

``--min-ratio`` is the machine-portable gate (both loops run on the same
box): the acceptance floor is 50x, set far below the measured headline so
it catches structural regressions (a de-fused training step, a host
round-trip reintroduced into the scan), not timer noise.
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

DEFAULT_OUT = os.path.join("artifacts", "bench", "rl_bench.json")

#: documented float tolerance for one jitted training step vs DQNLearner
AGREEMENT_TOL = 1e-5

# the measured curve: heavier load -> deeper queues -> the host env's
# per-decision event processing slows superlinearly (O(queue) scheduler
# passes per event, more events per decision) while the batched per-step
# cost grows only linearly in the padded job count — the ratio rises with
# load_scale and the headline is the best point (same shape as
# scripts/bench_batched.py).  Two high-load points give the >=50x gate
# redundancy against single-point timer noise.  Host episodes shrink as
# its per-episode cost grows; the batched run times rounds after the
# first (compile) round.  Batch 64 sits at the compute-bound plateau on
# one CPU device (B=32..512 measure within ~15% of each other).
FULL_POINTS = (
    {"load_scale": 1.0, "host_episodes": 2, "batch": 64, "rounds": 2},
    {"load_scale": 4.0, "host_episodes": 1, "batch": 64, "rounds": 2},
    {"load_scale": 12.0, "host_episodes": 1, "batch": 64, "rounds": 2},
    {"load_scale": 16.0, "host_episodes": 1, "batch": 64, "rounds": 2},
)
QUICK_POINTS = (
    {"load_scale": 0.2, "host_episodes": 2, "batch": 8, "rounds": 2},
)

#: both loops decide on this cadence (the batched trainer's default)
DECISION_INTERVAL_MIN = 15.0

#: scan length per round; high-load days do not drain inside it, which is
#: fine for a throughput measurement (every step is a full live step)
HORIZON_DECISIONS = 104


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


def _dqn_config(seed: int = 0):
    """The shared learner config — identical on both sides by construction."""
    from repro.core.rl.dqn import DQNConfig
    from repro.core.rl.env import FEATURE_DIM

    return DQNConfig(
        state_dim=FEATURE_DIM,
        # train from (nearly) the first decision: the bench measures steady
        # training throughput, not buffer warm-up
        min_buffer=128,
        buffer_capacity=50_000,
        target_sync_every=500,
        eps_decay_steps=10_000,
        seed=seed,
    )


_HOST_WARM = [False]


def measure_host(load_scale: float, episodes: int, scenario: str) -> dict:
    """Host loop env-steps/sec: jit warmed by one cheap low-load episode."""
    from repro.core.rl.train import train_dqn

    def kwargs(ls):
        return dict(
            scheduler_name="EDF-FS",
            dqn_config=_dqn_config(),
            scenario=scenario,
            scenario_kwargs={"load_scale": ls},
            decision_interval_min=DECISION_INTERVAL_MIN,
        )

    if not _HOST_WARM[0]:
        # the jitted update/q-forward shapes are load-independent, so one
        # cheap low-load episode warms the cache for every curve point
        train_dqn(num_episodes=1, seed=999, **kwargs(0.1))
        _HOST_WARM[0] = True
    t0 = time.perf_counter()
    _, stats = train_dqn(num_episodes=episodes, seed=0, **kwargs(load_scale))
    wall = time.perf_counter() - t0
    return {
        "episodes": episodes,
        "env_steps": stats.env_steps,
        "seconds": round(wall, 4),
        "env_steps_per_sec": round(stats.env_steps / wall, 1)
        if wall > 0 else float("inf"),
    }


def measure_batched(
    load_scale: float, batch: int, rounds: int, scenario: str
) -> dict:
    """Fused trainer env-steps/sec, steady state (first round = compile)."""
    from repro.core.rl.batched_train import BatchedTrainConfig, train_dqn_batched

    tcfg = BatchedTrainConfig(
        batch=batch,
        scenarios=(scenario,),
        scenario_kwargs={"load_scale": load_scale},
        decision_interval_min=DECISION_INTERVAL_MIN,
        horizon_decisions=HORIZON_DECISIONS,
    )
    _, stats = train_dqn_batched(
        num_episodes=batch * rounds,
        dqn_config=_dqn_config(),
        train_config=tcfg,
        seed=0,
    )
    steady_steps = sum(stats.round_env_steps[1:])
    steady_wall = sum(stats.round_wall_seconds[1:])
    if rounds < 2:  # degenerate: no compile-free round to time
        steady_steps, steady_wall = stats.env_steps, stats.wall_seconds
    return {
        "batch": batch,
        "rounds": rounds,
        "episodes": stats.episodes,
        "env_steps": stats.env_steps,
        "updates": stats.updates,
        "compile_round_seconds": round(stats.round_wall_seconds[0], 4),
        "steady_env_steps": steady_steps,
        "steady_seconds": round(steady_wall, 4),
        "env_steps_per_sec": round(steady_steps / steady_wall, 1)
        if steady_wall > 0 else float("inf"),
    }


def check_agreement() -> dict:
    """One scan-embedded jitted TD update vs the host learner's update.

    Both call :func:`make_td_update`'s function; embedding one side in a
    ``lax.scan`` (as the trainer does) must not change the result beyond
    float32 noise.  Returns the max parameter/loss deltas.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.rl.dqn import DQNLearner, make_td_update

    cfg = _dqn_config()
    learner = DQNLearner(cfg)
    rng = np.random.default_rng(42)
    bs, d = cfg.batch_size, cfg.state_dim
    batch = (
        jnp.asarray(rng.normal(size=(bs, d)).astype(np.float32)),
        jnp.asarray(rng.integers(0, cfg.num_actions, bs).astype(np.int32)),
        jnp.asarray(rng.normal(size=bs).astype(np.float32)),
        jnp.asarray(rng.normal(size=(bs, d)).astype(np.float32)),
        jnp.asarray((rng.uniform(size=bs) < 0.1).astype(np.float32)),
        jnp.full((bs,), cfg.gamma ** cfg.n_step, jnp.float32),
    )
    # host side: the learner's own jitted update
    host_params, _, host_loss = learner._update(
        learner.params, learner.target, learner.opt_state, *batch
    )
    # trainer side: the same shared step, embedded in a one-step scan
    _, td_update = make_td_update(cfg)

    @jax.jit
    def scan_once(params, target, opt_state, batch):
        def body(carry, _):
            p, o = carry
            p2, o2, loss = td_update(p, target, o, *batch)
            return (p2, o2), loss

        (p, o), losses = jax.lax.scan(
            body, (params, opt_state), jnp.arange(1)
        )
        return p, losses[0]

    scan_params, scan_loss = scan_once(
        learner.params, learner.target, learner.opt_state, batch
    )
    param_diff = max(
        float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(
            jax.tree_util.tree_leaves(host_params),
            jax.tree_util.tree_leaves(scan_params),
            strict=True,
        )
    )
    return {
        "max_param_diff": param_diff,
        "loss_diff": abs(float(host_loss) - float(scan_loss)),
        "tolerance": AGREEMENT_TOL,
        "within_tolerance": param_diff <= AGREEMENT_TOL,
    }


def measure_point(config: dict, scenario: str, verbose: bool = True) -> dict:
    host = measure_host(config["load_scale"], config["host_episodes"], scenario)
    batched = measure_batched(
        config["load_scale"], config["batch"], config["rounds"], scenario
    )
    ratio = (
        batched["env_steps_per_sec"] / host["env_steps_per_sec"]
        if host["env_steps_per_sec"] > 0 else float("inf")
    )
    if verbose:
        print(
            f"load {config['load_scale']:>4}: host "
            f"{host['env_steps_per_sec']:>7.1f} steps/s, batched "
            f"{batched['env_steps_per_sec']:>7.1f} steps/s "
            f"({ratio:.1f}x)",
            file=sys.stderr,
        )
    return {
        "load_scale": config["load_scale"],
        "host": host,
        "batched": batched,
        "ratio_vs_host": round(ratio, 2),
    }


def measure(points, scenario: str = "paper-diurnal",
            verbose: bool = True) -> dict:
    """The full curve; the headline is the best-ratio point."""
    from repro.core.simulator import SIM_VERSION

    measured = [measure_point(p, scenario, verbose=verbose) for p in points]
    agreement = check_agreement()
    head = max(measured, key=lambda m: m["ratio_vs_host"])
    return {
        "date": datetime.datetime.now(datetime.timezone.utc).strftime("%Y-%m-%d"),
        "git_sha": _git_sha(),
        "sim_version": SIM_VERSION,
        "scenario": scenario,
        "decision_interval_min": DECISION_INTERVAL_MIN,
        "points": measured,
        "headline_load_scale": head["load_scale"],
        "env_steps_per_sec_host": head["host"]["env_steps_per_sec"],
        "env_steps_per_sec_batched": head["batched"]["env_steps_per_sec"],
        "ratio_vs_host": head["ratio_vs_host"],
        "agreement": agreement,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--quick", action="store_true",
                    help="small point (CI smoke) instead of the full config")
    ap.add_argument("--min-ratio", type=float, default=None,
                    help="fail (exit 1) when batched/host env-steps/sec "
                         "falls below this — the nightly gate")
    ap.add_argument("--dry-run", action="store_true", help="print, don't write")
    args = ap.parse_args(argv)

    entry = measure(QUICK_POINTS if args.quick else FULL_POINTS)
    print(json.dumps(entry, indent=2))
    if not args.dry_run:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(entry, f, indent=2)
            f.write("\n")
        print(f"wrote {args.out}", file=sys.stderr)

    failures = []
    if args.min_ratio is not None and entry["ratio_vs_host"] < args.min_ratio:
        failures.append(
            f"RL THROUGHPUT REGRESSION: {entry['ratio_vs_host']:.1f}x "
            f"< floor {args.min_ratio:.1f}x"
        )
    if not entry["agreement"]["within_tolerance"]:
        failures.append(
            "RL AGREEMENT REGRESSION: jitted training step differs from "
            f"DQNLearner by {entry['agreement']['max_param_diff']:.2e} "
            f"(tolerance {AGREEMENT_TOL:.0e})"
        )
    for msg in failures:
        print(msg, file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
