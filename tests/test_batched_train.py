"""The fused on-device RL trainer vs its host oracles (DESIGN.md §11).

Four layers, mirroring the two-backend discipline of test_batched.py:

* observation parity — ``device_observations`` against the batched env's
  host-side ``_obs`` (the reference implementation),
* the batch-of-1 property — a ``BatchedRepartitionEnv`` rollout driven by
  a fixed action trace must reproduce the host cadence-mode
  ``RepartitionEnv`` (obs layout, reward scale, termination) within the
  documented physics tolerances, across scenarios × repartition modes,
* learner agreement — one scan-embedded jitted TD update equals the host
  ``DQNLearner``'s update on an identical replay batch (1e-5),
* the trainer itself — n-step/replay accounting, a training smoke, and
  the checked-in RL baseline's claim + params probe.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.rl.dqn import DQNConfig, DQNLearner, make_td_update
from repro.core.rl.env import FEATURE_DIM, RepartitionEnv, RewardWeights, make_batched_env
from repro.core.rl.batched_train import (
    BatchedTrainConfig,
    device_observations,
    shard_rollouts,
    train_dqn_batched,
)

BASELINES = os.path.join(os.path.dirname(__file__), "..", "benchmarks", "baselines")
SHARDED_HELPER = os.path.join(
    os.path.dirname(__file__), "helpers", "sharded_rollouts.py"
)
SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _cfg(**kw):
    kw.setdefault("state_dim", FEATURE_DIM)
    kw.setdefault("seed", 0)
    return DQNConfig(**kw)


def _obs_via_device(env):
    """Run device_observations on the batched env's internals."""
    return np.asarray(
        device_observations(
            env._state,
            jnp.asarray(env._jobs.arrival, jnp.float32),
            jnp.asarray(env._jobs.deadline, jnp.float32),
            jnp.asarray(env._jobs.valid),
            jnp.asarray(env._inv_mean_dur, jnp.float32),
            jnp.asarray(env.tables.config_ids),
            jnp.float32(env._t),
        )
    )


@pytest.mark.parametrize("scenario", ["paper-diurnal", "bursty-mmpp"])
def test_device_observations_match_host_obs(scenario):
    """The jit mirror reproduces ``BatchedRepartitionEnv._obs`` everywhere
    along an episode (float32 bin inputs may flip an exact-edge bin, so a
    tiny mismatch budget is allowed; measured: zero mismatches)."""
    env = make_batched_env(
        scenario=scenario, scenario_kwargs={"load_scale": 0.3}
    )
    host = env.reset(seeds=(11, 12, 13))
    mism, total = 0, 0
    dev = _obs_via_device(env)
    assert dev.shape == host.shape == (3, FEATURE_DIM)
    mism += int((np.abs(dev - host) > 1e-6).sum())
    total += dev.size
    rng = np.random.default_rng(0)
    for _ in range(40):
        if env.done:
            break
        obs, _, _, _, _ = env.step(rng.integers(0, 12, size=3))
        dev = _obs_via_device(env)
        mism += int((np.abs(dev - obs) > 1e-6).sum())
        total += dev.size
    assert total > 3 * FEATURE_DIM  # the episode actually ran
    assert mism / total <= 0.01


@pytest.mark.slow
@pytest.mark.parametrize("scenario", ["paper-diurnal", "bursty-mmpp"])
@pytest.mark.parametrize("mode", ["drain", "partial"])
def test_batch_of_one_reproduces_host_env(scenario, mode):
    """Batch-of-1 property (DESIGN.md §11): same seed, same fixed action
    trace -> the batched rollout tracks the host cadence-mode env's obs,
    rewards and termination within the documented physics tolerances
    (docs/BATCHED_SIM.md §4 — dt-grid completion vs exact event times)."""
    seed, interval, load = 21, 15.0, 0.3
    kw = dict(scenario=scenario, scenario_kwargs={"load_scale": load})
    henv = RepartitionEnv(
        scheduler_name="EDF-FS", repartition_mode=mode,
        decision_interval_min=interval, **kw,
    )
    benv = make_batched_env(
        repartition_mode=mode, decision_interval_min=interval, **kw,
    )
    hobs = henv.reset(seed=seed)
    bobs = benv.reset(seeds=(seed,))
    np.testing.assert_allclose(bobs[0], hobs, atol=1e-6)

    rng = np.random.default_rng(3)
    h_cum = b_cum = 0.0
    h_steps = b_steps = 0
    obs_mismatch = obs_total = 0
    h_done = b_done = False
    for _ in range(200):
        if h_done and b_done:
            break
        a = int(rng.integers(0, 12))
        if not h_done:
            hobs, hr, ht, htr, _ = henv.step(a)
            h_cum += hr
            h_steps += 1
            h_done = ht or htr
        if not b_done:
            bobs, br, bt, btr, _ = benv.step([a])
            b_cum += float(br[0])
            b_steps += 1
            b_done = bool((bt | btr)[0])
        if not (h_done or b_done):
            obs_mismatch += int((np.abs(bobs[0] - hobs) > 1e-6).sum())
            obs_total += hobs.size
    # identical decision grid -> near-identical episode length (the dt
    # grid can move the drain across one interval boundary)
    assert abs(h_steps - b_steps) <= 1
    assert h_done and b_done
    # binned features agree except for occasional edge flips
    assert obs_total > 0
    assert obs_mismatch / obs_total <= 0.02
    # reward scale: cumulative returns within the backend tolerance band
    assert b_cum == pytest.approx(h_cum, rel=0.25, abs=0.5)
    # physics accumulators at the end of the day
    hres = henv.result()
    bres = benv.results()[0]
    assert bres.energy_wh == pytest.approx(hres.energy_wh, rel=0.02)
    assert bres.avg_tardiness == pytest.approx(hres.avg_tardiness, abs=0.5)


def test_jitted_training_step_matches_learner():
    """The agreement rule: a scan-embedded ``make_td_update`` step equals
    ``DQNLearner._update`` on an identical batch to 1e-5 (measured 0.0 —
    both jit the same function)."""
    cfg = _cfg(min_buffer=1)
    learner = DQNLearner(cfg)
    rng = np.random.default_rng(7)
    bs, d = cfg.batch_size, cfg.state_dim
    batch = (
        jnp.asarray(rng.normal(size=(bs, d)).astype(np.float32)),
        jnp.asarray(rng.integers(0, cfg.num_actions, bs).astype(np.int32)),
        jnp.asarray(rng.normal(size=bs).astype(np.float32)),
        jnp.asarray(rng.normal(size=(bs, d)).astype(np.float32)),
        jnp.asarray((rng.uniform(size=bs) < 0.1).astype(np.float32)),
        jnp.full((bs,), cfg.gamma**cfg.n_step, jnp.float32),
    )
    host_params, _, host_loss = learner._update(
        learner.params, learner.target, learner.opt_state, *batch
    )
    _, td_update = make_td_update(cfg)

    @jax.jit
    def scan_once(params, target, opt_state):
        def body(carry, _):
            p, o = carry
            p2, o2, loss = td_update(p, target, o, *batch)
            return (p2, o2), loss

        (p, _), losses = jax.lax.scan(body, (params, opt_state), jnp.arange(1))
        return p, losses[0]

    scan_params, scan_loss = scan_once(
        learner.params, learner.target, learner.opt_state
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(host_params),
        jax.tree_util.tree_leaves(scan_params), strict=True,
    ):
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-5
    assert abs(float(host_loss) - float(scan_loss)) <= 1e-5


@pytest.mark.slow
def test_nstep_replay_accounting_one_transition_per_live_step():
    """Replay semantics: with no truncation, every live decision step emits
    exactly one n-step transition (maturation at lag n-1 + the terminal
    flush of the shorter lags) — the same count NStepAccumulator produces.
    Checked through the real round program on a drained round."""
    from repro.core.batched.backend import device_constants, init_state
    from repro.core.batched.state import BatchedJobs
    from repro.core.batched.tables import build_tables
    from repro.core.jobs import ALL_SLICE_SIZES
    from repro.core.rl.batched_train import _make_round_fn
    from repro.core.scenarios import generate_scenario

    cfg = _cfg(n_step=4, min_buffer=10_000_000)  # never train: pure emission
    tcfg = BatchedTrainConfig(batch=3, horizon_decisions=120)
    tables = build_tables()
    consts = device_constants(tables, tcfg.repartition_mode)
    round_fn = _make_round_fn(cfg, tcfg, RewardWeights(), tables, consts)

    chunks = [
        generate_scenario("paper-diurnal", seed=s, load_scale=0.2)
        for s in (1, 2, 3)
    ]
    batch = BatchedJobs.from_job_lists(chunks, max_slots=tables.max_slots)
    inv = np.zeros(batch.arrival.shape, np.float32)
    for b, js in enumerate(chunks):
        for j, job in enumerate(js):
            inv[b, j] = sum(
                1.0 / job.rate_on(float(k), True) for k in ALL_SLICE_SIZES
            ) / len(ALL_SLICE_SIZES)

    D, cap = cfg.state_dim, tcfg.replay_capacity
    replay = (
        jnp.zeros((cap, D), jnp.float32), jnp.zeros((cap,), jnp.int32),
        jnp.zeros((cap,), jnp.float32), jnp.zeros((cap, D), jnp.float32),
        jnp.zeros((cap,), jnp.float32), jnp.zeros((cap,), jnp.float32),
        jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
    )
    learner = DQNLearner(cfg)
    jobs = batch.in_edf_order()
    inv = np.take_along_axis(inv, batch.edf_order, axis=1)
    env0 = init_state(jobs, np.full((3,), tables.index_of(2), np.int32))
    arrays = tuple(
        jnp.asarray(a)
        for a in (jobs.arrival, jobs.deadline, jobs.rate_by_slots,
                  jobs.valid, inv)
    )
    (env, _p, _t, _o, replay, gstep, updates, _k, outs) = round_fn(
        env0, learner.params, learner.target, learner.opt_state, replay,
        jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
        jax.random.PRNGKey(5), *arrays,
    )
    live = np.asarray(outs[1])
    assert not live[-1].any(), "episodes must drain inside the horizon"
    size = int(replay[7])
    assert size == int(gstep) == int(live.sum())
    assert int(updates) == 0  # min_buffer gate held


@pytest.mark.slow
def test_train_dqn_batched_smoke_and_stats():
    """End-to-end smoke: two rounds train, update, and report stats whose
    pieces are mutually consistent."""
    cfg = _cfg(min_buffer=64, batch_size=32, eps_decay_steps=500)
    tcfg = BatchedTrainConfig(
        batch=4, horizon_decisions=110,
        scenario_kwargs={"load_scale": 0.2},
    )
    learner, stats = train_dqn_batched(
        num_episodes=8, dqn_config=cfg, train_config=tcfg, seed=3
    )
    assert stats.episodes == 8 and stats.rounds == 2 and stats.batch == 4
    assert len(stats.episode_rewards) == 8
    assert len(stats.episode_et_proxy) == 8
    assert stats.env_steps > 0
    assert stats.env_steps == sum(stats.round_env_steps)
    assert stats.updates > 0 and len(stats.losses) > 0
    assert np.isfinite(stats.losses).all()
    assert 0.0 <= stats.final_epsilon <= 1.0
    for w, b in learner.params:
        assert np.isfinite(np.asarray(w)).all()
        assert np.isfinite(np.asarray(b)).all()
    # the trained learner is a regular host learner: greedy path works
    a = learner.greedy_action(np.zeros(FEATURE_DIM, np.float32))
    assert 0 <= a < cfg.num_actions
    # epsilon advanced along the *global step* schedule
    assert stats.final_epsilon == pytest.approx(
        learner.epsilon_at_step(stats.env_steps)
    )


def test_train_dqn_backend_dispatch_validation():
    from repro.core.rl.train import train_dqn

    with pytest.raises(ValueError, match="EDF-FS"):
        train_dqn(num_episodes=1, backend="batched", scheduler_name="EDF-SS")
    with pytest.raises(ValueError, match="unknown backend"):
        train_dqn(num_episodes=1, backend="nope")
    with pytest.raises(ValueError, match="host-backend only"):
        train_dqn(
            num_episodes=1, backend="batched", scheduler_name="EDF-FS",
            guide=object(),
        )


def test_shard_rollouts_single_device_noop():
    dev = jax.devices()[0]
    tree = {"a": jnp.arange(12.0).reshape(4, 3), "b": jnp.zeros((7,))}
    out = shard_rollouts(tree, devices=[dev])
    # one device holds the whole batch; values and other leaves unchanged
    assert out["a"].sharding.device_set == {dev}
    np.testing.assert_array_equal(np.asarray(out["a"]), np.asarray(tree["a"]))
    assert out["b"] is tree["b"]


def test_episode_sums_do_not_depend_on_memory_layout():
    """A device may hand a round's (H, B) rewards back column-major; numpy
    would then sum the step axis pairwise instead of in step order."""
    from repro.core.rl.batched_train import _episode_sums

    rng = np.random.default_rng(0)
    rew = (rng.normal(size=(104, 64)) * 1e3).astype(np.float32)
    col_major = np.asfortranarray(rew)
    # the hazard is real for these rewards ...
    assert not np.array_equal(col_major.sum(axis=0), rew.sum(axis=0))
    # ... and the trainer's sums take one order for both layouts
    np.testing.assert_array_equal(_episode_sums(col_major), _episode_sums(rew))
    np.testing.assert_array_equal(_episode_sums(rew), rew.sum(axis=0))


def test_sharded_rollouts_match_one_device():
    """On four virtual CPU devices (own process: the device count is fixed
    before jax starts), a training round with its rollouts sharded by
    ``shard_rollouts`` equals the one-device round, and a batch that does
    not divide the device count is refused."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, SHARDED_HELPER],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert proc.returncode == 0, (
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr[-3000:]}"
    )
    assert "SHARDED_ROLLOUTS_OK" in proc.stdout


def test_rl_baseline_claim_and_params_probe():
    """The checked-in RL baseline: the batch-trained policy beats the
    forecast controller on >=1 scenario family, and the params file still
    produces the greedy actions recorded at train time (probe pin)."""
    path = os.path.join(BASELINES, "rl_batched.json")
    with open(path) as f:
        entry = json.load(f)
    assert entry["families_beaten"], "baseline must record >=1 family win"
    for row in entry["rows"]:
        assert row["dqn_beats_forecast"] == (
            row["scenario"] in entry["families_beaten"]
        )
    probe = entry["params_probe"]
    learner = DQNLearner(_cfg())
    learner.load(os.path.join(BASELINES, "rl_dqn_params.npz"))
    rng = np.random.default_rng(probe["seed"])
    obs = rng.uniform(0.0, 1.0, size=(len(probe["actions"]), FEATURE_DIM))
    acts = [learner.greedy_action(o.astype(np.float32)) for o in obs]
    assert acts == probe["actions"]
