"""The device path compiles for a described TPU v5e (no chip attached).

Ahead-of-time compiles of the two jitted programs at the sizes the chip
runs: the simulator's scan chunk for a 512-seed load-12 sweep and for the
eight-GPU fleet cell, the fused
training round at the baseline trainer's batch on one chip, and the same
round with its rollouts sharded over a 2x2 slice.  Each must fit one chip's
16 GiB, and every fusion of a scan chunk that runs step code must map to a
step phase, as the chip benchmark's per-phase readers need.  The TPU compiler refuses here what it would refuse on the chip,
at no chip time; nothing runs, so this says nothing about results or speed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test workers each
import every test file.
"""

import os
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core.batched import build_tables, compile_policy
from repro.core.batched.backend import (
    STEP_PHASES,
    RolloutState,
    _chunk_fn,
    device_constants,
    op_phases,
)
from repro.core.rl.batched_train import BatchedTrainConfig, _make_round_fn
from repro.core.rl.dqn import DQNConfig, DQNLearner
from repro.core.rl.env import FEATURE_DIM, RewardWeights
from repro.core.simulator import DayNightPolicy

CHIP_BYTES = 16 * 2**30
SWEEP_B, SWEEP_J = 512, 5760  # 512 seeds of a load-12 paper-diurnal day
TRAIN_B, TRAIN_J = 64, 736  # the baseline trainer's batch and job axis
FLEET_B, FLEET_J, FLEET_D = 32, 4096, 8  # the eight-GPU fleet cell's batch


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described-chip compile cannot be read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _fits_one_chip(compiled):
    m = compiled.memory_analysis()
    total = (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes + m.generated_code_size_in_bytes
        - m.alias_size_in_bytes
    )
    assert 0 < total <= CHIP_BYTES, m
    return total


def _unnamed_phased_fusions(text):
    """Top-level fusions that :func:`op_phases` leaves without a phase
    while the code fused into them names one."""
    phases, phased, comp = op_phases(text), set(), None
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY )?%?(\S+) \(.*\{$", line)
        if m:
            comp = m.group(1)
            continue
        name = re.search(r'op_name="([^"]*)"', line)
        if comp and name and set(re.split(r"[/()]", name.group(1))) & set(STEP_PHASES):
            phased.add(comp)
    fusions = re.findall(r"^\s+(?:ROOT )?%?(\S+) = .* fusion\(.*\bcalls=%?([^\s,]+)", text, re.M)
    return [op for op, callee in fusions if callee in phased and op in phases and not phases[op]]


def _rollout_state(B, J, S, K, sharding):
    f32, i32 = jnp.float32, jnp.int32
    shapes = RolloutState(
        remaining=((B, J), f32), completion=((B, J), f32),
        slice_job=((B, S), i32), cfg=((B,), i32), pending=((B,), i32),
        stall_left=((B,), f32), stop_time=((B,), f32),
        energy_wh=((B,), f32), tardiness_integral=((B,), f32),
        busy_slot_minutes=((B,), f32), preemptions=((B,), i32),
        repartitions=((B,), i32), util_hist=((B, K), f32),
    )
    return RolloutState(  # one GPU: no device axis, no ``device``
        *(None if sd is None else jax.ShapeDtypeStruct(*sd, sharding=sharding)
          for sd in shapes)
    )


def _jobs(B, J, K, sharding):
    f32 = jnp.float32
    return (
        jax.ShapeDtypeStruct((B, J), f32, sharding=sharding),  # arrival
        jax.ShapeDtypeStruct((B, J), f32, sharding=sharding),  # deadline
        jax.ShapeDtypeStruct((B, J, K), f32, sharding=sharding),  # rates
        jax.ShapeDtypeStruct((B, J), jnp.bool_, sharding=sharding),  # valid
    )


def _like(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=sharding),
        tree,
    )


def test_scan_chunk_compiles_for_one_v5e(topo, no_persistent_cache):
    one = SingleDeviceSharding(topo.devices[0])
    tables = build_tables()
    S, K = tables.max_slots, tables.max_slots + 1
    policy = compile_policy(DayNightPolicy(), tables, batch=1)
    run_chunk = _chunk_fn(
        policy.kind, 0.5, 512, float(tables.penalty_min),
        float(policy.day_start), float(policy.day_end),
    )
    consts = _like(device_constants(tables, "partial"), one)
    i32 = jnp.int32
    compiled = run_chunk.lower(
        _rollout_state(SWEEP_B, SWEEP_J, S, K, one),
        *_jobs(SWEEP_B, SWEEP_J, K, one),
        jax.ShapeDtypeStruct((SWEEP_B,), i32, sharding=one),  # primary
        jax.ShapeDtypeStruct((SWEEP_B,), i32, sharding=one),  # secondary
        jax.ShapeDtypeStruct((), jnp.float32, sharding=one),  # t0
        consts["slice_slots"], consts["slice_rank"], consts["num_slices"],
        consts["old_to_new"], consts["watts"],
    ).compile()
    _fits_one_chip(compiled)
    assert _unnamed_phased_fusions(compiled.as_text()) == []


def test_fleet_chunk_compiles_for_one_v5e(topo, no_persistent_cache):
    """The eight-GPU fleet's chunk at the benchmark's size: 32 server-days
    of J=4096 on a device axis of 8 (benchmarks/chip, fleet8-paper-load)."""
    one = SingleDeviceSharding(topo.devices[0])
    tables = build_tables()
    S, K = tables.max_slots, tables.max_slots + 1
    B, J, D = FLEET_B, FLEET_J, FLEET_D
    policy = compile_policy(DayNightPolicy(), tables, batch=1)
    run_chunk = _chunk_fn(
        policy.kind, 0.5, 512, float(tables.penalty_min),
        float(policy.day_start), float(policy.day_end), D, "least-loaded",
    )
    f32, i32 = jnp.float32, jnp.int32
    lanes = {"slice_job": ((B, D, S), i32), "cfg": ((B, D), i32), "pending": ((B, D), i32),
             "stall_left": ((B, D), f32), "device": ((B, J), i32)}
    state = _rollout_state(B, J, S, K, one)._replace(**{
        k: jax.ShapeDtypeStruct(s, d, sharding=one) for k, (s, d) in lanes.items()})
    consts = _like(device_constants(tables, "partial"), one)
    compiled = run_chunk.lower(
        state, *_jobs(B, J, K, one),
        jax.ShapeDtypeStruct((B,), i32, sharding=one),  # primary
        jax.ShapeDtypeStruct((B,), i32, sharding=one),  # secondary
        jax.ShapeDtypeStruct((), jnp.float32, sharding=one),  # t0
        consts["slice_slots"], consts["slice_rank"], consts["num_slices"],
        consts["old_to_new"], consts["watts"],
        jax.ShapeDtypeStruct((B, J), i32, sharding=one),  # by_arrival
    ).compile()
    _fits_one_chip(compiled)
    assert _unnamed_phased_fusions(compiled.as_text()) == []


def _round_args(B, J, rollout_sharding, other_sharding):
    tables = build_tables()
    S, K = tables.max_slots, tables.max_slots + 1
    cfg = DQNConfig(state_dim=FEATURE_DIM, seed=7)
    tcfg = BatchedTrainConfig(batch=B)
    # the round closes over its tables: they compile in as constants
    round_fn = _make_round_fn(
        cfg, tcfg, RewardWeights(), tables,
        device_constants(tables, tcfg.repartition_mode),
    )
    learner = DQNLearner(cfg)
    D, cap = cfg.state_dim, tcfg.replay_capacity
    f32, i32 = jnp.float32, jnp.int32
    replay = (
        ((cap, D), f32), ((cap,), i32), ((cap,), f32), ((cap, D), f32),
        ((cap,), f32), ((cap,), f32), ((), i32), ((), i32),
    )
    args = (
        _rollout_state(B, J, S, K, rollout_sharding),
        _like(learner.params, other_sharding),
        _like(learner.target, other_sharding),
        _like(learner.opt_state, other_sharding),
        tuple(
            jax.ShapeDtypeStruct(s, d, sharding=other_sharding)
            for s, d in replay
        ),
        jax.ShapeDtypeStruct((), i32, sharding=other_sharding),  # env steps
        jax.ShapeDtypeStruct((), i32, sharding=other_sharding),  # updates
        _like(jax.random.PRNGKey(0), other_sharding),
        *_jobs(B, J, K, rollout_sharding),
        jax.ShapeDtypeStruct((B, J), f32, sharding=rollout_sharding),  # 1/dur
    )
    return round_fn, args


def test_training_round_compiles_for_one_v5e(topo, no_persistent_cache):
    one = SingleDeviceSharding(topo.devices[0])
    round_fn, args = _round_args(TRAIN_B, TRAIN_J, one, one)
    _fits_one_chip(round_fn.lower(*args).compile())


def test_sharded_training_round_compiles_for_v5e_2x2(topo, no_persistent_cache):
    mesh = Mesh(np.asarray(topo.devices), ("rollout",))
    assert mesh.size == 4
    split = NamedSharding(mesh, PartitionSpec("rollout"))
    whole = NamedSharding(mesh, PartitionSpec())
    round_fn, args = _round_args(TRAIN_B, TRAIN_J, split, whole)
    compiled = round_fn.lower(*args).compile()
    _fits_one_chip(compiled)
    # the rollout axis stays split: each chip holds a quarter of the batch
    env_out = compiled.output_shardings[0]
    assert env_out.remaining.shard_shape((TRAIN_B, TRAIN_J)) == (
        TRAIN_B // 4, TRAIN_J,
    )
