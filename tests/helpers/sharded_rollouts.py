"""Subprocess helper: the fused trainer's rollouts sharded over 4 CPU devices.

Must run in its own process (forces the device count before jax init).
Runs one training round with its rollouts sharded by ``shard_rollouts``
over four devices and the same round pinned to one device that is not the
default one, and checks that they agree: per-episode rewards exactly,
parameters to 1e-5 relative.  Also checks that the learner's parameters end
up on all four devices in the sharded run and on the pinned device in the
other, and that a batch that does not divide the device count is refused.
Exits nonzero on any failure.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax
import numpy as np


def main() -> int:
    from repro.core.rl.batched_train import (
        BatchedTrainConfig,
        shard_rollouts,
        train_dqn_batched,
    )
    from repro.core.rl.dqn import DQNConfig
    from repro.core.rl.env import FEATURE_DIM

    devices = jax.devices()
    assert len(devices) == 4, devices
    cfg = DQNConfig(
        state_dim=FEATURE_DIM, min_buffer=16, batch_size=16,
        eps_decay_steps=200, seed=0,
    )
    tcfg = BatchedTrainConfig(
        batch=8, horizon_decisions=24, scenario_kwargs={"load_scale": 0.2},
    )
    runs = {}
    for name, devs in (("sharded", None), ("one", devices[1:2])):
        learner, stats = train_dqn_batched(
            num_episodes=8, dqn_config=cfg, train_config=tcfg, seed=5,
            devices=devs,
        )
        assert stats.updates > 0, stats.updates
        runs[name] = (learner, stats)

    (l4, s4), (l1, s1) = runs["sharded"], runs["one"]
    assert s4.episode_rewards == s1.episode_rewards, (
        s4.episode_rewards, s1.episode_rewards,
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(l4.params),
        jax.tree_util.tree_leaves(l1.params), strict=True,
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=0)
        assert a.sharding.device_set == set(devices), a.sharding
        assert b.sharding.device_set == {devices[1]}, b.sharding

    split = shard_rollouts(np.zeros((8, 3), np.float32))
    assert [s.data.shape for s in split.addressable_shards] == [(2, 3)] * 4
    try:
        shard_rollouts(np.zeros((6, 3), np.float32))
    except ValueError as e:
        assert "does not divide" in str(e), e
    else:
        raise AssertionError("a batch of 6 over 4 devices was not refused")
    print("SHARDED_ROLLOUTS_OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
