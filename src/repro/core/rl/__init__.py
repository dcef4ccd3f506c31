"""Reinforcement-learning repartitioning (paper §IV-D): DQN in pure JAX."""

from repro.core.rl.dqn import DQNConfig, DQNLearner, ReplayBuffer
from repro.core.rl.env import (
    FEATURE_DIM,
    RepartitionEnv,
    RewardWeights,
    state_features,
)
from repro.core.rl.agent import DQNAgent, NStepAccumulator, greedy_policy
from repro.core.rl.train import train_dqn, evaluate_policy
from repro.core.rl.batched_env import BatchedRepartitionEnv
from repro.core.rl.batched_train import (
    BatchedTrainConfig,
    BatchedTrainStats,
    train_dqn_batched,
)

__all__ = [
    "DQNConfig",
    "DQNLearner",
    "ReplayBuffer",
    "state_features",
    "FEATURE_DIM",
    "RepartitionEnv",
    "RewardWeights",
    "DQNAgent",
    "NStepAccumulator",
    "greedy_policy",
    "train_dqn",
    "evaluate_policy",
    "BatchedTrainConfig",
    "BatchedTrainStats",
    "train_dqn_batched",
    "BatchedRepartitionEnv",
]
