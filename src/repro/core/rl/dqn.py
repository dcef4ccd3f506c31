"""Deep Q-Network in pure JAX (paper §IV-D).

Epsilon-greedy exploration, experience-replay buffer, target network, Huber
TD loss — no external NN library.  The Q-network is a small MLP over the
``2+2m`` binned state features; the action space is the 12 MIG
configurations of Fig. 1.  The optimizer is the repo's own
:class:`repro.optim.adamw.AdamW` configured down to classic Adam
(``weight_decay=0``, no clipping, ``b2=0.999``) so the host loop and the
fused on-device trainer (:mod:`repro.core.rl.batched_train`) share one
update rule — :func:`make_td_update` is that shared jit-compatible step.

Epsilon has two equivalent parameterizations: the host loop's per-episode
linear decay (``eps_decay_episodes``, unchanged semantics) and the
global-env-step decay (``eps_decay_steps``) that vectorized training needs —
B parallel rollouts advance B env steps per decision, so an episode-indexed
schedule would decay B× too fast.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.slices import NUM_CONFIGS
from repro.optim.adamw import AdamW, AdamWConfig

__all__ = [
    "DQNConfig",
    "ReplayBuffer",
    "DQNLearner",
    "make_td_update",
    "epsilon_by_step",
]

Params = List[Tuple[jnp.ndarray, jnp.ndarray]]


@dataclasses.dataclass(frozen=True)
class DQNConfig:
    state_dim: int = 8
    num_actions: int = NUM_CONFIGS
    hidden: Tuple[int, ...] = (256, 256)
    gamma: float = 0.99
    n_step: int = 8  # n-step TD targets (credit over event chains)
    lr: float = 5e-4
    batch_size: int = 128
    buffer_capacity: int = 200_000
    min_buffer: int = 2_000
    target_sync_every: int = 1_000
    huber_delta: float = 1.0
    eps_start: float = 1.0
    eps_end: float = 0.05
    eps_decay_episodes: int = 150
    # global-env-step epsilon decay for vectorized training (None = unset;
    # the host loop keeps its per-episode schedule either way)
    eps_decay_steps: Optional[int] = None
    seed: int = 0


def init_mlp(key: jax.Array, sizes: Tuple[int, ...]) -> Params:
    params: Params = []
    for i in range(len(sizes) - 1):
        key, sub = jax.random.split(key)
        fan_in = sizes[i]
        w = jax.random.normal(sub, (sizes[i], sizes[i + 1]), jnp.float32)
        w = w * jnp.sqrt(2.0 / fan_in)
        b = jnp.zeros((sizes[i + 1],), jnp.float32)
        params.append((w, b))
    return params


#: f32 matmuls at full precision.  A TPU's default is one bf16 pass, which
#: put a TD step about 1e-3 off the CPU's on a v5e; at HIGHEST the two agree
#: to about 1e-6 (the CPU ignores the setting).
_MATMUL = jax.lax.Precision.HIGHEST


def q_forward(params: Params, x: jnp.ndarray) -> jnp.ndarray:
    h = x
    for w, b in params[:-1]:
        h = jax.nn.relu(jnp.dot(h, w, precision=_MATMUL) + b)
    w, b = params[-1]
    return jnp.dot(h, w, precision=_MATMUL) + b


class ReplayBuffer:
    """Circular numpy replay buffer."""

    def __init__(self, capacity: int, state_dim: int) -> None:
        self.capacity = capacity
        self.s = np.zeros((capacity, state_dim), np.float32)
        self.a = np.zeros((capacity,), np.int32)
        self.r = np.zeros((capacity,), np.float32)
        self.s2 = np.zeros((capacity, state_dim), np.float32)
        self.done = np.zeros((capacity,), np.float32)
        self.g = np.zeros((capacity,), np.float32)  # bootstrap discount gamma^k
        self.size = 0
        self.pos = 0

    def add(self, s, a, r, s2, done, g) -> None:
        i = self.pos
        self.s[i] = s
        self.a[i] = a
        self.r[i] = r
        self.s2[i] = s2
        self.done[i] = float(done)
        self.g[i] = g
        self.pos = (self.pos + 1) % self.capacity
        self.size = min(self.size + 1, self.capacity)

    def sample(self, rng: np.random.Generator, batch: int):
        idx = rng.integers(0, self.size, size=batch)
        return (
            self.s[idx], self.a[idx], self.r[idx], self.s2[idx],
            self.done[idx], self.g[idx],
        )


# ------------------------ shared TD update step ----------------------------


def make_optimizer(cfg: DQNConfig, lr=None) -> AdamW:
    """The DQN optimizer: :class:`repro.optim.adamw.AdamW` as classic Adam.

    ``weight_decay=0`` / no clipping / ``b2=0.999`` reproduce the previous
    hand-rolled Adam bit-for-bit (same bias-corrected update); ``lr`` may be
    a schedule callable (step -> lr), defaulting to the constant
    ``cfg.lr`` the host loop uses.
    """
    return AdamW(AdamWConfig(
        lr=cfg.lr if lr is None else lr,
        b1=0.9, b2=0.999, eps=1e-8,
        weight_decay=0.0, grad_clip_norm=None,
    ))


def make_td_update(cfg: DQNConfig, lr=None):
    """Build ``(optimizer, update_fn)`` — the one double-DQN training step.

    ``update_fn(params, target, opt_state, s, a, r, s2, done, g)`` returns
    ``(new_params, new_opt_state, loss)`` and is pure/jit-compatible: the
    host :class:`DQNLearner` jits it directly and the fused batched trainer
    calls it inside its rollout scan, so the two loops agree on an identical
    replay batch to float tolerance by construction (the contract
    DESIGN.md §11 states and tests/test_batched_train.py pins).
    """
    delta = cfg.huber_delta
    opt = make_optimizer(cfg, lr)

    def update(params, target, opt_state, s, a, r, s2, done, g):
        def loss_fn(p):
            q = q_forward(p, s)
            q_sa = jnp.take_along_axis(q, a[:, None], axis=1)[:, 0]
            # Double DQN: online net picks the argmax, target net evaluates
            a2 = jnp.argmax(q_forward(p, s2), axis=1)
            q_next = jnp.take_along_axis(
                q_forward(target, s2), a2[:, None], axis=1
            )[:, 0]
            # n-step target: r is the discounted n-step sum, g = gamma^k
            tgt = r + g * (1.0 - done) * q_next
            td = q_sa - jax.lax.stop_gradient(tgt)
            # Huber
            abs_td = jnp.abs(td)
            quad = jnp.minimum(abs_td, delta)
            lin = abs_td - quad
            return jnp.mean(0.5 * quad**2 + delta * lin)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        new_params, new_opt = opt.update(grads, opt_state, params)
        return new_params, new_opt, loss

    return opt, update


def epsilon_by_step(cfg: DQNConfig, env_step):
    """Linear ``eps_start -> eps_end`` over ``cfg.eps_decay_steps`` env steps.

    Works on Python scalars and jnp arrays alike (the batched trainer calls
    it inside the scan); invariant to how many rollouts advance in parallel,
    because the clock is *global* env steps, not episodes.
    """
    decay = max(int(cfg.eps_decay_steps or 1), 1)
    frac = jnp.minimum(jnp.asarray(env_step, jnp.float32) / decay, 1.0)
    return cfg.eps_start + (cfg.eps_end - cfg.eps_start) * frac


# ------------------------------- learner ----------------------------------


class DQNLearner:
    """Holds online/target params + optimizer state; jitted TD update."""

    def __init__(self, cfg: DQNConfig) -> None:
        self.cfg = cfg
        key = jax.random.PRNGKey(cfg.seed)
        sizes = (cfg.state_dim, *cfg.hidden, cfg.num_actions)
        self.params = init_mlp(key, sizes)
        self.target = jax.tree_util.tree_map(jnp.copy, self.params)
        self._opt, update = make_td_update(cfg)
        self.opt_state = self._opt.init(self.params)
        self.updates = 0
        self.buffer = ReplayBuffer(cfg.buffer_capacity, cfg.state_dim)
        self._rng = np.random.default_rng(cfg.seed + 1)

        @jax.jit
        def q_values(params, s):
            return q_forward(params, s)

        self._update = jax.jit(update)
        self._q_values = q_values

    # -- acting ----------------------------------------------------------
    def q(self, state: np.ndarray) -> np.ndarray:
        out = self._q_values(self.params, jnp.asarray(state[None, :]))
        return np.asarray(out)[0]

    def act(self, state: np.ndarray, epsilon: float) -> int:
        if self._rng.uniform() < epsilon:
            return int(self._rng.integers(0, self.cfg.num_actions))
        return int(np.argmax(self.q(state)))

    def greedy_action(self, state: np.ndarray) -> int:
        return int(np.argmax(self.q(state)))

    # -- learning ---------------------------------------------------------
    def observe(self, s, a, r, s2, done, g=None) -> None:
        self.buffer.add(s, a, r, s2, done, self.cfg.gamma if g is None else g)

    def maybe_train(self, steps: int = 1) -> float:
        if self.buffer.size < self.cfg.min_buffer:
            return float("nan")
        loss = float("nan")
        for _ in range(steps):
            batch = self.buffer.sample(self._rng, self.cfg.batch_size)
            self.params, self.opt_state, loss_j = self._update(
                self.params, self.target, self.opt_state, *map(jnp.asarray, batch)
            )
            loss = float(loss_j)
            self.updates += 1
            if self.updates % self.cfg.target_sync_every == 0:
                self.target = jax.tree_util.tree_map(jnp.copy, self.params)
        return loss

    def epsilon(self, episode: int) -> float:
        """Host-loop schedule: linear decay over ``eps_decay_episodes``."""
        c = self.cfg
        frac = min(episode / max(c.eps_decay_episodes, 1), 1.0)
        return c.eps_start + (c.eps_end - c.eps_start) * frac

    def epsilon_at_step(self, env_step: int) -> float:
        """Vectorized-training schedule: decay in *global* env steps."""
        return float(epsilon_by_step(self.cfg, env_step))

    # -- persistence -------------------------------------------------------
    def save(self, path: str) -> None:
        arrays: Dict[str, np.ndarray] = {}
        for i, (w, b) in enumerate(self.params):
            arrays[f"w{i}"] = np.asarray(w)
            arrays[f"b{i}"] = np.asarray(b)
        arrays["n_layers"] = np.asarray(len(self.params))
        np.savez(path, **arrays)

    def load(self, path: str) -> None:
        data = np.load(path)
        n = int(data["n_layers"])
        self.params = [
            (jnp.asarray(data[f"w{i}"]), jnp.asarray(data[f"b{i}"])) for i in range(n)
        ]
        self.target = jax.tree_util.tree_map(jnp.copy, self.params)
