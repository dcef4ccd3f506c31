"""The training configuration of the checked-in RL baseline.

``scripts/train_rl_baseline.py`` trains ``benchmarks/baselines/
rl_dqn_params.npz`` with it and races the result against the forecast
controller; ``chip_smoke.py`` runs a few of its rounds on the chip.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

from repro.core.rl.batched_train import BatchedTrainConfig, train_dqn_batched
from repro.core.rl.dqn import DQNConfig
from repro.core.rl.env import FEATURE_DIM

__all__ = [
    "DECISION_INTERVAL_MIN",
    "LOAD_SCALE_RANGE",
    "TRAIN_BATCH",
    "TRAIN_EPISODES",
    "TRAIN_SCENARIOS",
    "TRAIN_SEED",
    "dqn_config",
    "train",
    "train_config",
]

#: evaluation cadence = the batched trainer's decision cadence
DECISION_INTERVAL_MIN = 15.0

#: scenario families the trained policy is raced on (fixed order, as in
#: the sweep grids); training draws episodes from the same families so
#: the policy sees every arrival shape it is evaluated under
TRAIN_SCENARIOS = (
    "paper-diurnal",
    "bursty-mmpp",
    "heavy-tail-lognormal",
    "heavy-tail-pareto",
)

TRAIN_SEED = 7
TRAIN_BATCH = 64  # rollouts per fused round
TRAIN_EPISODES = 2048
LOAD_SCALE_RANGE = (0.8, 1.2)


def dqn_config() -> DQNConfig:
    return DQNConfig(
        state_dim=FEATURE_DIM,
        n_step=8,
        lr=3e-4,
        target_sync_every=2000,
        min_buffer=2000,
        eps_decay_steps=100_000,
        seed=TRAIN_SEED,
    )


def train_config() -> BatchedTrainConfig:
    return BatchedTrainConfig(
        batch=TRAIN_BATCH,
        scenarios=TRAIN_SCENARIOS,
        load_scale_range=LOAD_SCALE_RANGE,
        decision_interval_min=DECISION_INTERVAL_MIN,
        horizon_decisions=104,
    )


def train(
    episodes: int = TRAIN_EPISODES,
    verbose: bool = True,
    devices: Optional[Sequence[Any]] = None,
) -> tuple:
    """Fixed-seed batched training over the scenario × load-scale mix;
    returns ``(learner, stats)``.

    ``devices`` (default: all) are the devices the rollouts are sharded over.
    """
    return train_dqn_batched(
        num_episodes=episodes,
        dqn_config=dqn_config(),
        train_config=train_config(),
        seed=TRAIN_SEED,
        verbose=verbose,
        devices=devices,
    )
