"""Fused on-device DQN training: B rollouts + the learner in one jitted scan.

This is the RL analogue of the batched simulation backend
(docs/BATCHED_SIM.md): instead of stepping one host
:class:`~repro.core.rl.env.RepartitionEnv` episode at a time and shuttling
every transition through numpy, a *round* of ``B`` episodes advances
lock-step inside a single ``lax.scan`` over decision steps.  Each scan step

1. computes the §IV-D-1 observations on device (a JAX mirror of
   ``BatchedRepartitionEnv._obs``),
2. acts epsilon-greedily with the *global env-step* schedule
   (:func:`repro.core.rl.dqn.epsilon_by_step` — B rollouts advance B env
   steps per decision, so an episode-indexed schedule would decay B× fast),
3. advances every rollout one decision interval by vmapping exactly the
   physics function the simulation backend runs
   (:func:`repro.core.batched.backend.make_step_fn`),
4. emits n-step transitions into an on-device ring replay buffer (masked
   scatters — terminating rollouts flush their pending tail with shortened
   returns, mirroring :class:`repro.core.rl.agent.NStepAccumulator`),
5. runs one TD update sampled from that buffer via the *shared* update step
   (:func:`repro.core.rl.dqn.make_td_update` — the same function the host
   :class:`~repro.core.rl.dqn.DQNLearner` jits, so one training step here
   agrees with the host learner on an identical batch to float tolerance
   by construction; DESIGN.md §11 states the contract), and
6. syncs the target network by update count, exactly like the host loop.

The host stays the orchestrator: an outer Python loop generates each
round's workloads (seed × scenario × load-scale randomized per episode),
pads them to one global shape so every round reuses one compiled program,
and finally installs the trained parameters into a plain
:class:`DQNLearner` — downstream evaluation/persistence is unchanged.

Rollout-batched arrays are sharded across the devices with
``jax.sharding`` (:func:`shard_rollouts`); given one device, they are
pinned to it.  tests/test_batched_train.py checks on four virtual CPU
devices that a sharded round equals the one-device round.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.batched.backend import (
    DEFAULT_DT_MIN,
    device_constants,
    init_state,
    make_step_fn,
    result_of,
)
from repro.core.batched.state import BatchedJobs
from repro.core.batched.tables import DeviceTables, build_tables
from repro.core.jobs import ALL_SLICE_SIZES
from repro.core.rl.dqn import (
    DQNConfig,
    DQNLearner,
    epsilon_by_step,
    make_td_update,
    q_forward,
)
from repro.core.rl.env import (
    _BIN_EDGES,
    _NUM_BINS,
    _TIME_BINS,
    FEATURE_DIM,
    M_JOBS,
    RewardWeights,
)

__all__ = [
    "BatchedTrainConfig",
    "BatchedTrainStats",
    "device_observations",
    "shard_rollouts",
    "train_dqn_batched",
]

_EPS = 1e-6
# held_policy() defaults — reusing them keys make_step_fn's cache to the
# exact entry BatchedRepartitionEnv already compiled
_DAY_START = 5 * 60.0
_DAY_END = 17 * 60.0


@dataclasses.dataclass(frozen=True)
class BatchedTrainConfig:
    """Knobs of the fused trainer (everything episode-shaped lives here).

    ``horizon_decisions`` is the fixed scan length per round; rollouts that
    terminate earlier are masked out (no actions, no transitions, no env
    steps), rollouts still live at the horizon are truncated — their pending
    n-step tail is dropped (a bootstrapped continuation, the standard
    truncation treatment).  ``load_scale_range`` draws one uniform load
    scale per episode; ``scenarios`` round-robins per episode.
    """

    batch: int = 32
    scenarios: Tuple[str, ...] = ("paper-diurnal",)
    scenario_kwargs: Optional[Dict[str, Any]] = None
    load_scale_range: Tuple[float, float] = (1.0, 1.0)
    decision_interval_min: float = 15.0
    dt_min: float = DEFAULT_DT_MIN
    horizon_decisions: int = 104  # a 24h day at 15-min cadence + drain tail
    replay_capacity: int = 16_384
    repartition_mode: str = "partial"
    initial_config: int = 2
    lr_schedule: str = "constant"  # "constant" | "cosine"


@dataclasses.dataclass
class BatchedTrainStats:
    """Mirrors :class:`~repro.core.rl.train.TrainStats` plus throughput.

    ``episode_rewards`` holds exact per-episode cumulative rewards (summed
    host-side from the per-step scan outputs); ``env_steps`` counts live
    decisions across all rollouts — the currency ``scripts/bench_rl.py``
    compares against the host loop.  ``round_wall_seconds[0]`` includes
    compilation; steady-state throughput should be read from later rounds.
    """

    episode_rewards: List[float]
    episode_et_proxy: List[float]
    losses: List[float]
    episodes: int
    wall_seconds: float
    env_steps: int = 0
    env_steps_per_sec: float = 0.0
    updates: int = 0
    final_epsilon: float = 0.0
    rounds: int = 0
    batch: int = 0
    truncated_episodes: int = 0
    round_wall_seconds: List[float] = dataclasses.field(default_factory=list)
    round_env_steps: List[int] = dataclasses.field(default_factory=list)


# ---------------------------- device observations --------------------------


def device_observations(
    state, arrival, deadline, valid, inv_mean_dur, config_ids,
    t, m: int = M_JOBS,
):
    """§IV-D-1 features for every rollout, on device: ``(B, 2+2m)`` float32.

    Jit-compatible mirror of ``BatchedRepartitionEnv._obs`` (the host
    reference; tests/test_batched_train.py pins the parity): same bin
    edges, same sentinels, same EDF-stable ordering, read off the job index
    of the EDF layout (:meth:`BatchedJobs.in_edf_order`) the per-job
    arrays are in.  The only divergence is float32 arithmetic in the bin
    inputs, which can flip a binned feature on exact bin edges.
    """
    import jax
    import jax.numpy as jnp

    B, J = arrival.shape
    i32 = jnp.int32
    edges = jnp.asarray(_BIN_EDGES, jnp.float32)

    # running mask from the slice->job lanes: scatter-max so the clipped
    # padding lanes (-1 -> 0) can never set a spurious True on job 0
    sj = state.slice_job
    bidx = jnp.arange(B, dtype=i32)[:, None]
    running = jnp.zeros((B, J), bool).at[
        bidx, jnp.clip(sj, 0, J - 1)
    ].max(sj >= 0)

    queued = (
        (arrival <= t + _EPS) & (state.remaining > _EPS) & (~running) & valid
    )
    # first-m selection in EDF order (the job axis's order): find the i-th
    # set bit of the queued mask with a per-row searchsorted over the
    # running count (J if fewer than i jobs are queued)
    cs = jnp.cumsum(queued.astype(i32), axis=1)
    ranks = jnp.arange(1, m + 1, dtype=i32)
    sel = jax.vmap(lambda c: jnp.searchsorted(c, ranks))(cs)  # (B, m)
    has = sel < J
    jobsel = jnp.clip(sel, 0, J - 1)

    dl = jnp.take_along_axis(deadline, jobsel, axis=1)
    rem = jnp.take_along_axis(state.remaining, jobsel, axis=1)
    inv = jnp.take_along_axis(inv_mean_dur, jobsel, axis=1)
    slack = jnp.maximum(dl - t, 0.0)
    mean_dur = rem * inv
    sbin = jnp.searchsorted(edges, slack, side="right") / (_NUM_BINS - 1)
    dbin = jnp.searchsorted(edges, mean_dur, side="right") / (_NUM_BINS - 1)
    sfeat = jnp.where(has, sbin, 1.0)  # "no job" sentinel: max slack
    dfeat = jnp.where(has, dbin, 0.0)
    jobfeat = jnp.stack([sfeat, dfeat], axis=2).reshape(B, 2 * m)

    cfg_col = (config_ids[state.cfg].astype(jnp.float32) - 1.0) / 11.0
    tod = jnp.mod(t / 60.0, 24.0)
    tod_col = jnp.mod(jnp.floor(tod * 2.0), _TIME_BINS) / (_TIME_BINS - 1)
    tod_col = jnp.broadcast_to(tod_col, (B,))
    return jnp.concatenate(
        [cfg_col[:, None], tod_col[:, None], jobfeat], axis=1
    ).astype(jnp.float32)


# ------------------------------- sharding ----------------------------------


def shard_rollouts(tree, devices=None):
    """Place rollout-batched arrays across devices on a 1-D ``rollout`` mesh.

    Leaves whose leading axis equals the batch size (the first leaf's) get a
    ``NamedSharding(P("rollout"))``; every other leaf is left as it is.  One
    device holds the whole batch, so ``devices=[d]`` pins the rollouts to
    ``d``; with ``devices`` left out and one device in all, the tree is
    returned as it is.  A batch that does not divide the device count raises
    ``ValueError``: a silent fallback would put every rollout on the first
    device.
    """
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves or (devices is None and jax.device_count() == 1):
        # left uncommitted: committed rollouts would commit the round's
        # outputs, and round two would compile again for the new placement
        return tree
    devices = list(jax.devices()) if devices is None else list(devices)
    B = int(leaves[0].shape[0])
    if B % len(devices) != 0:
        raise ValueError(
            f"batch {B} does not divide over {len(devices)} devices"
        )
    mesh = Mesh(np.asarray(devices), ("rollout",))
    sharding = NamedSharding(mesh, PartitionSpec("rollout"))
    return jax.tree_util.tree_map(
        lambda x: (
            jax.device_put(x, sharding)
            if getattr(x, "ndim", 0) >= 1 and x.shape[0] == B
            else x
        ),
        tree,
    )


def _episode_sums(rew_hb) -> np.ndarray:
    """Per-episode sums of a round's ``(H, B)`` step rewards, added step by
    step in order.

    The order is fixed by making the array row-major first.  A one-device
    TPU array can come back from the device column-major, and numpy then
    sums its contiguous step axis pairwise: one float32 ulp away from the
    same rewards sharded over four chips, which arrive row-major.
    """
    return np.ascontiguousarray(rew_hb).sum(axis=0)


# ----------------------------- the fused round -----------------------------


def _make_round_fn(
    cfg: DQNConfig,
    tcfg: BatchedTrainConfig,
    rewards: RewardWeights,
    tables: DeviceTables,
    consts: Dict[str, Any],
    lr=None,
):
    """Build the jitted round program: scan over ``horizon_decisions``.

    Carry = (env RolloutState, params, target, opt state, replay ring,
    n-step recency rings, global env-step count, update count, PRNG key).
    The per-step physics is exactly the simulation backend's
    :func:`make_step_fn` under the ``held_policy`` cache key, so training
    rollouts obey the very dynamics evaluation runs.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    if cfg.num_actions != tables.num_configs:
        raise ValueError(
            f"num_actions={cfg.num_actions} != {tables.num_configs} device "
            "configs; the action space is the dense config index"
        )
    if cfg.state_dim != 2 + 2 * M_JOBS:
        raise ValueError(
            f"state_dim={cfg.state_dim} != feature dim {2 + 2 * M_JOBS}"
        )
    interval = float(tcfg.decision_interval_min)
    spd = int(round(interval / tcfg.dt_min))
    if abs(spd * tcfg.dt_min - interval) > 1e-9 or spd < 1:
        raise ValueError(
            f"decision_interval_min={interval} must be a positive multiple "
            f"of dt_min={tcfg.dt_min}"
        )
    dt = float(tcfg.dt_min)
    step_one = make_step_fn(
        "static", dt, float(tables.penalty_min), _DAY_START, _DAY_END
    )
    step_b = jax.vmap(
        step_one,
        in_axes=(0, None, 0, 0, 0, 0, 0, 0, None, None, None, None, None),
    )
    _, td_update = make_td_update(cfg, lr=lr)

    n = int(cfg.n_step)
    gamma = float(cfg.gamma)
    cap = int(tcfg.replay_capacity)
    H = int(tcfg.horizon_decisions)
    B = int(tcfg.batch)
    A = int(cfg.num_actions)
    D = int(cfg.state_dim)
    bs = int(cfg.batch_size)
    min_buffer = int(cfg.min_buffer)
    sync_every = int(cfg.target_sync_every)
    w_a, w_norm = float(rewards.a), float(rewards.tardiness_norm)
    w_scale = float(rewards.scale)
    w_switch = float(rewards.switch_penalty_min)
    cfg_ids = jnp.asarray(tables.config_ids)
    i32 = jnp.int32
    f32 = jnp.float32

    def dec_step(carry, k, arrival, deadline, rates, valid, inv_md):
        (env, obs, params, target, opt_state, replay, rings,
         gstep, updates, key) = carry
        rs, ra, rr, rs2, rdone, rg, pos, size = replay
        obs_h, act_h, rew_h = rings
        t = k.astype(f32) * interval

        # `obs` (the pre-step observation) rides the carry: obs(k) is
        # exactly obs2(k-1) — same state, same time — so each decision
        # computes the feature pass once, not twice
        live = env.stop_time > t + _EPS
        key, k_expl, k_act, k_samp = jax.random.split(key, 4)
        eps = epsilon_by_step(cfg, gstep)
        greedy = jnp.argmax(q_forward(params, obs), axis=1).astype(i32)
        randa = jax.random.randint(k_act, (B,), 0, A, dtype=i32)
        explore = jax.random.uniform(k_expl, (B,)) < eps
        # dense config index == action id (asserted against the tables);
        # halted rollouts hold their configuration and emit nothing
        action = jnp.where(live, jnp.where(explore, randa, greedy), env.cfg)

        # §IV-D-3 switch penalty, priced on jobs currently in system
        in_sys = jnp.sum(
            (arrival <= t + _EPS) & (env.remaining > _EPS) & valid, axis=1
        )
        pen_y = w_switch * jnp.maximum(in_sys, 1) / w_norm
        penalty = jnp.where(
            (action != env.cfg) & live, (pen_y / (w_a + 1.0)) / w_scale, 0.0
        )

        e0, td0 = env.energy_wh, env.tardiness_integral

        def inner(c, i):
            ti = t + i.astype(f32) * f32(dt)
            return (
                step_b(c, ti, arrival, deadline, rates, valid,
                       action, action,
                       consts["slice_slots"], consts["slice_rank"],
                       consts["num_slices"], consts["old_to_new"],
                       consts["watts"]),
                None,
            )

        env2, _ = lax.scan(inner, env, jnp.arange(spd, dtype=i32))
        d_e = env2.energy_wh - e0
        d_t = env2.tardiness_integral - td0
        reward = -((w_a * d_e + d_t / w_norm) / (w_a + 1.0)) / w_scale - penalty
        reward = jnp.where(live, reward, 0.0).astype(f32)

        t_next = t + interval
        obs2 = device_observations(
            env2, arrival, deadline, valid, inv_md, cfg_ids, t_next
        )
        done_next = env2.stop_time <= t_next + _EPS

        # -- n-step recency rings: newest at index 0 --------------------
        obs_h = jnp.roll(obs_h, 1, axis=1).at[:, 0].set(obs)
        act_h = jnp.roll(act_h, 1, axis=1).at[:, 0].set(action)
        rew_h = jnp.roll(rew_h, 1, axis=1).at[:, 0].set(reward)

        # candidate transitions: recency o originated at step k-o.  Normal
        # maturation emits only o = n-1 (done flag = done_next); a rollout
        # terminating this step flushes o = 0..n-2 too, with shortened
        # returns — exactly NStepAccumulator's flush-on-done.  A rollout is
        # live at k-o whenever it is live at k (liveness is monotone), so
        # one mask covers the whole ring.
        flush = live & done_next
        s_c, a_c, r_c, g_c, v_c = [], [], [], [], []
        for o in range(n):
            ret = rew_h[:, 0] * (gamma ** o)
            for d in range(1, o + 1):
                ret = ret + rew_h[:, d] * (gamma ** (o - d))
            s_c.append(obs_h[:, o])
            a_c.append(act_h[:, o])
            r_c.append(ret)
            g_c.append(jnp.full((B,), gamma ** (o + 1), f32))
            ok = live & (k >= o) if o == n - 1 else flush & (k >= o)
            v_c.append(ok)
        s_flat = jnp.concatenate(s_c, axis=0)  # (n*B, D)
        a_flat = jnp.concatenate(a_c, axis=0)
        r_flat = jnp.concatenate(r_c, axis=0)
        g_flat = jnp.concatenate(g_c, axis=0)
        v_flat = jnp.concatenate(v_c, axis=0)
        s2_flat = jnp.tile(obs2, (n, 1))
        d_flat = jnp.tile(done_next.astype(f32), (n,))

        rank = jnp.cumsum(v_flat.astype(i32)) - 1
        widx = jnp.where(v_flat, jnp.mod(pos + rank, cap), cap)  # cap = drop
        rs = rs.at[widx].set(s_flat, mode="drop")
        ra = ra.at[widx].set(a_flat, mode="drop")
        rr = rr.at[widx].set(r_flat, mode="drop")
        rs2 = rs2.at[widx].set(s2_flat, mode="drop")
        rdone = rdone.at[widx].set(d_flat, mode="drop")
        rg = rg.at[widx].set(g_flat, mode="drop")
        emitted = jnp.sum(v_flat.astype(i32))
        pos = jnp.mod(pos + emitted, cap)
        size = jnp.minimum(size + emitted, cap)

        # -- one TD update per decision step (the host loop's cadence) --
        can_train = size >= min_buffer

        def _do(op):
            p, o_s = op
            idx = jax.random.randint(
                k_samp, (bs,), 0, jnp.maximum(size, 1)
            )
            return td_update(
                p, target, o_s,
                rs[idx], ra[idx], rr[idx], rs2[idx], rdone[idx], rg[idx],
            )

        def _skip(op):
            p, o_s = op
            return p, o_s, jnp.float32(jnp.nan)

        params, opt_state, loss = lax.cond(
            can_train, _do, _skip, (params, opt_state)
        )
        updates = updates + can_train.astype(i32)
        sync = can_train & (jnp.mod(updates, sync_every) == 0)
        target = jax.tree_util.tree_map(
            lambda tp, pp: jnp.where(sync, pp, tp), target, params
        )
        gstep = gstep + jnp.sum(live.astype(i32))

        carry = (
            env2, obs2, params, target, opt_state,
            (rs, ra, rr, rs2, rdone, rg, pos, size),
            (obs_h, act_h, rew_h), gstep, updates, key,
        )
        return carry, (reward, live, loss, eps)

    def round_fn(env0, params, target, opt_state, replay, gstep, updates,
                 key, arrival, deadline, rates, valid, inv_md):
        rings = (
            jnp.zeros((B, n, D), f32),
            jnp.zeros((B, n), i32),
            jnp.zeros((B, n), f32),
        )
        obs0 = device_observations(
            env0, arrival, deadline, valid, inv_md, cfg_ids,
            jnp.float32(0.0),
        )
        carry0 = (env0, obs0, params, target, opt_state, replay, rings,
                  gstep, updates, key)

        def body(carry, k):
            return dec_step(
                carry, k, arrival, deadline, rates, valid, inv_md
            )

        carry, outs = lax.scan(body, carry0, jnp.arange(H, dtype=i32))
        (env, _obs, params, target, opt_state, replay, _rings,
         gstep, updates, key) = carry
        return (env, params, target, opt_state, replay, gstep, updates,
                key, outs)

    import jax as _jax

    return _jax.jit(round_fn)


# ------------------------------ the outer loop -----------------------------


def train_dqn_batched(
    num_episodes: int = 128,
    dqn_config: Optional[DQNConfig] = None,
    train_config: Optional[BatchedTrainConfig] = None,
    rewards: RewardWeights = RewardWeights(),
    seed: int = 0,
    verbose: bool = False,
    tables: Optional[DeviceTables] = None,
    devices: Optional[Sequence[Any]] = None,
) -> tuple:
    """Train the repartitioning DQN on device; returns (learner, stats).

    Episodes are grouped into rounds of ``train_config.batch`` rollouts;
    episode ``i`` draws seed ``seed * 100_003 + i`` (the host loop's seed
    line), scenario ``scenarios[i % len]`` and a uniform load scale from
    ``load_scale_range``.  All rounds are padded to one global job-axis
    shape so the scan compiles once.  The returned learner is a regular
    :class:`DQNLearner` with the trained parameters, target network,
    optimizer state and update count installed — save/eval paths are
    identical to host training (the on-device replay ring is not carried
    over).  ``devices`` (default: all) are the devices the rollouts are
    sharded over by :func:`shard_rollouts`; one device pins the trainer.
    """
    import jax
    import jax.numpy as jnp

    tcfg = train_config or BatchedTrainConfig()
    B = int(tcfg.batch)
    rounds = max(1, -(-int(num_episodes) // B))
    cfg = dqn_config or DQNConfig(state_dim=FEATURE_DIM, seed=seed)
    if cfg.eps_decay_steps is None:
        # default the step schedule to the same exploration budget the host
        # schedule spends: eps_decay_episodes × the per-episode horizon
        cfg = dataclasses.replace(
            cfg,
            eps_decay_steps=cfg.eps_decay_episodes * tcfg.horizon_decisions,
        )
    if tables is None:
        tables = build_tables()
    consts = device_constants(tables, tcfg.repartition_mode)

    lr = None
    if tcfg.lr_schedule == "cosine":
        from repro.optim.schedule import cosine_schedule

        lr = cosine_schedule(
            cfg.lr, total_steps=rounds * tcfg.horizon_decisions,
            final_frac=0.1,
        )
    elif tcfg.lr_schedule != "constant":
        raise ValueError(f"unknown lr_schedule {tcfg.lr_schedule!r}")

    # -- generate every episode's workload up front (one padded shape) ----
    from repro.core.scenarios import generate_scenario

    rng = np.random.default_rng(seed)
    skw = dict(tcfg.scenario_kwargs or {})
    episodes: List[List[Any]] = []
    for i in range(rounds * B):
        scen = tcfg.scenarios[i % len(tcfg.scenarios)]
        lo, hi = tcfg.load_scale_range
        kw = dict(skw)
        if (lo, hi) != (1.0, 1.0) or "load_scale" not in kw:
            scale = float(rng.uniform(lo, hi))
            kw.setdefault("load_scale", scale)
        episodes.append(
            generate_scenario(scen, seed=seed * 100_003 + i, **kw)
        )
    max_jobs = max((len(js) for js in episodes), default=1)

    round_jobs: List[BatchedJobs] = []
    round_inv: List[np.ndarray] = []
    for r in range(rounds):
        chunk = episodes[r * B:(r + 1) * B]
        jobs = BatchedJobs.from_job_lists(
            chunk, max_slots=tables.max_slots, min_jobs=max_jobs
        )
        inv = np.zeros(jobs.arrival.shape, dtype=np.float32)
        for b, js in enumerate(chunk):
            for j, job in enumerate(js):
                inv[b, j] = sum(
                    1.0 / job.rate_on(float(k), True) for k in ALL_SLICE_SIZES
                ) / len(ALL_SLICE_SIZES)
        round_jobs.append(jobs)
        # in the EDF layout of the round's arrays (below)
        round_inv.append(np.take_along_axis(inv, jobs.edf_order, axis=1))

    round_fn = _make_round_fn(cfg, tcfg, rewards, tables, consts, lr=lr)

    # learner-side carry: init through DQNLearner so host/batched training
    # start from the identical network for a given DQNConfig
    learner = DQNLearner(cfg)
    params, target = learner.params, learner.target
    opt_state = learner.opt_state
    D, capacity = cfg.state_dim, int(tcfg.replay_capacity)
    f32, i32 = jnp.float32, jnp.int32
    replay = (
        jnp.zeros((capacity, D), f32), jnp.zeros((capacity,), i32),
        jnp.zeros((capacity,), f32), jnp.zeros((capacity, D), f32),
        jnp.zeros((capacity,), f32), jnp.zeros((capacity,), f32),
        jnp.zeros((), i32), jnp.zeros((), i32),
    )
    gstep = jnp.zeros((), i32)
    updates = jnp.zeros((), i32)
    key = jax.random.PRNGKey(seed + 17)

    t_start = time.time()  # lint: waive[DT002] wall-seconds telemetry only
    ep_rewards: List[float] = []
    ep_proxy: List[float] = []
    all_losses: List[float] = []
    round_walls: List[float] = []
    round_steps: List[int] = []
    truncated = 0
    init_idx = np.full(
        (B,), tables.index_of(tcfg.initial_config), dtype=np.int32
    )
    for r in range(rounds):
        jobs = round_jobs[r]
        # the round runs on the EDF layout; result_of maps back to `jobs`
        lay = jobs.in_edf_order()
        env0 = shard_rollouts(init_state(lay, init_idx), devices)
        batch_arrays = shard_rollouts(
            tuple(
                jnp.asarray(a)
                for a in (lay.arrival, lay.deadline, lay.rate_by_slots,
                          lay.valid, round_inv[r])
            ),
            devices,
        )
        t_r = time.time()  # lint: waive[DT002] per-round wall telemetry only
        (env, params, target, opt_state, replay, gstep, updates, key,
         outs) = round_fn(
            env0, params, target, opt_state, replay, gstep, updates, key,
            *batch_arrays,
        )
        rew_hb = np.asarray(outs[0])  # (H, B)
        live_hb = np.asarray(outs[1])
        loss_h = np.asarray(outs[2])
        round_walls.append(time.time() - t_r)  # lint: waive[DT002] wall telemetry only
        round_steps.append(int(live_hb.sum()))

        ep_rewards.extend(_episode_sums(rew_hb).tolist())
        # ET proxy from the rollout accumulators, like the host loop's
        # per-episode `a * energy + avg_tardiness`
        for res in result_of(env, jobs, tables).to_sim_results():
            ep_proxy.append(rewards.a * res.energy_wh + res.avg_tardiness)
        all_losses.extend(loss_h[~np.isnan(loss_h)].tolist())
        truncated += int(live_hb[-1].sum())
        if verbose:  # pragma: no cover
            print(
                f"round {r + 1}/{rounds} episodes={B} "
                f"mean_reward={_episode_sums(rew_hb).mean():.2f} "
                f"env_steps={int(gstep)} updates={int(updates)} "
                f"wall={round_walls[-1]:.1f}s"
            )

    # install the trained state into the host learner (same OptState type)
    learner.params = params
    learner.target = target
    learner.opt_state = opt_state
    learner.updates = int(updates)

    wall = time.time() - t_start  # lint: waive[DT002] wall telemetry only
    env_steps = int(gstep)
    stats = BatchedTrainStats(
        episode_rewards=ep_rewards,
        episode_et_proxy=ep_proxy,
        losses=all_losses,
        episodes=rounds * B,
        wall_seconds=wall,
        env_steps=env_steps,
        env_steps_per_sec=env_steps / wall if wall > 0 else 0.0,
        updates=int(updates),
        final_epsilon=float(epsilon_by_step(cfg, env_steps)),
        rounds=rounds,
        batch=B,
        truncated_episodes=truncated,
        round_wall_seconds=round_walls,
        round_env_steps=round_steps,
    )
    return learner, stats
