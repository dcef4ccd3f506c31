"""Drive the device path once on a TPU and check it against its references.

::

    python chip_smoke.py             # one chip: seed sweep, then the trainer
    python chip_smoke.py --chips 4   # four chips: sharded trainer vs one chip

One chip (the default) runs two phases in this one process, which holds
the chip:

1. **Seed sweep.**  512 seeds of ``paper-diurnal`` at load 12 (about 5.7k
   jobs per day), EDF-FS under DayNight, partial repartitioning, dt 0.5,
   as batched sweep cells through ``repro.sweep.run_cells``.  Oracle cells
   for seeds 0 and 1 run in the same call, in pool workers held to the CPU;
   each must agree with its batched row within docs/BATCHED_SIM.md §4.
2. **Trainer.**  ``train_dqn_batched`` at the configuration of the
   checked-in RL baseline (``repro.core.rl.baseline``) for three rounds:
   losses finite and the parameters moved; one TD step on the chip equals
   the same step on the CPU to 1e-5; the greedy probe of
   ``rl_dqn_params.npz`` on the chip gives the actions the checked-in
   baseline recorded.

``--chips 4`` runs only the trainer with its rollouts sharded over four
chips, and the same rounds on one chip as the comparison: first-round
rewards must be equal, later rewards and the final parameters equal to
1e-5 relative.

Times printed are set-up and wall time of one run, not benchmark results.
Without a TPU the script exits non-zero before any work and prints no
result.  The last line of standard output is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: the platform the device path must run on (a CPU rehearsal steers this)
PLATFORM = "tpu"

SWEEP_SEEDS = 512
SWEEP_LOAD = 12.0
ORACLE_SEEDS = (0, 1)
TRAIN_ROUNDS = 3
TD_ATOL = 1e-5
SHARDED_RTOL = 1e-5

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


def log(msg: str) -> None:
    print(msg, flush=True)


class Checks:
    """Collects failed checks, so one run reports all of them."""

    def __init__(self) -> None:
        self.failed: list = []

    def __call__(self, ok: bool, what: str) -> None:
        log(f"{'ok  ' if ok else 'FAIL'} {what}")
        if not ok:
            self.failed.append(what)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or loading a
    cached executable), summed from its monitoring events."""

    def __init__(self) -> None:
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event in _COMPILE_EVENTS:
            self.seconds += duration


def tpu_devices():
    """The devices JAX found; exits non-zero unless they are ``PLATFORM``."""
    import jax

    devices = jax.devices()
    if devices[0].platform != PLATFORM:
        print(
            f"chip_smoke: needs a {PLATFORM} device; JAX found "
            f"{devices[0].platform} ({devices[0].device_kind})",
            file=sys.stderr,
        )
        raise SystemExit(2)
    return devices


# ------------------------------- seed sweep --------------------------------


def sweep_phase(device, clock: CompileClock, check: Checks) -> None:
    from repro.core.batched import PAD_MULTIPLE, agreement_failures
    from repro.sweep import run_cells
    from repro.sweep.cells import CellSpec, result_to_sim_result

    def cell(seed: int, backend: str):
        return CellSpec(
            experiment="chip_smoke", group=backend, scheduler="EDF-FS",
            seed=seed, scenario="paper-diurnal",
            scenario_kwargs={"load_scale": SWEEP_LOAD}, policy="daynight",
            repartition_mode="partial", backend=backend,
            backend_kwargs={"dt_min": 0.5} if backend == "batched" else None,
        ).to_cell()

    cells = [cell(s, "batched") for s in range(SWEEP_SEEDS)]
    cells += [cell(s, "oracle") for s in ORACLE_SEEDS]
    compile0 = clock.seconds
    t0 = time.perf_counter()
    out = run_cells(
        "chip_smoke", cells, workers=len(ORACLE_SEEDS), cache=False,
        artifacts_dir=None,
    )
    wall = time.perf_counter() - t0
    compile_s = clock.seconds - compile0
    batched = [result_to_sim_result(r) for r in out.results[:SWEEP_SEEDS]]
    oracle = [result_to_sim_result(r) for r in out.results[SWEEP_SEEDS:]]
    max_jobs = max(r.num_jobs for r in batched)
    J = -(-max_jobs // PAD_MULTIPLE) * PAD_MULTIPLE
    peak = (device.memory_stats() or {}).get("peak_bytes_in_use")
    log(
        f"sweep: device={device.device_kind} B={SWEEP_SEEDS} J={J} "
        f"load={SWEEP_LOAD} compile_s={compile_s} "
        f"run_s={wall - compile_s} wall_s={wall} "
        f"(run_s includes host scenario generation and the oracle cells) "
        f"peak_bytes_in_use={peak}"
    )
    check(
        all(r.num_jobs > 0 and r.energy_wh > 0 for r in batched),
        f"sweep: all {SWEEP_SEEDS} batched rows finished with energy",
    )
    for seed, o in zip(ORACLE_SEEDS, oracle, strict=True):
        b = batched[seed]
        log(
            f"sweep: seed {seed} batched/oracle energy_wh={b.energy_wh}/"
            f"{o.energy_wh} avg_tardiness={b.avg_tardiness}/"
            f"{o.avg_tardiness} busy_slot_minutes={b.busy_slot_minutes}/"
            f"{o.busy_slot_minutes} preemptions={b.preemptions}/"
            f"{o.preemptions} repartitions={b.repartitions}/"
            f"{o.repartitions} num_jobs={b.num_jobs}/{o.num_jobs}"
        )
        failures = agreement_failures(b, o)
        check(not failures, f"sweep: seed {seed} agrees with the oracle "
                            f"within BATCHED_SIM.md §4 {failures}")


# -------------------------------- trainer ----------------------------------


def _train(devices):
    from repro.core.rl import baseline

    return baseline.train(
        TRAIN_ROUNDS * baseline.TRAIN_BATCH, verbose=False, devices=devices
    )


def _td_batch(cfg, seed: int = 7):
    import numpy as np

    rng = np.random.default_rng(seed)
    bs, d = cfg.batch_size, cfg.state_dim
    return (
        rng.normal(size=(bs, d)).astype(np.float32),
        rng.integers(0, cfg.num_actions, bs).astype(np.int32),
        rng.normal(size=bs).astype(np.float32),
        rng.normal(size=(bs, d)).astype(np.float32),
        (rng.uniform(size=bs) < 0.1).astype(np.float32),
        np.full((bs,), cfg.gamma**cfg.n_step, np.float32),
    )


def trainer_phase(device, clock: CompileClock, check: Checks) -> None:
    import jax
    import numpy as np

    from repro.core.rl.baseline import dqn_config
    from repro.core.rl.dqn import DQNLearner, make_td_update
    from repro.core.rl.env import FEATURE_DIM

    cfg = dqn_config()
    init = [np.asarray(x) for x in jax.tree_util.tree_leaves(DQNLearner(cfg).params)]
    compile0 = clock.seconds
    learner, stats = _train([device])
    log(
        f"trainer: B={stats.batch} rounds={stats.rounds} "
        f"env_steps={stats.env_steps} updates={stats.updates} "
        f"compile_s={clock.seconds - compile0} wall_s={stats.wall_seconds} "
        f"round_wall_s={stats.round_wall_seconds}"
    )
    check(
        stats.rounds == TRAIN_ROUNDS and stats.updates > 0
        and len(stats.losses) > 0 and bool(np.isfinite(stats.losses).all()),
        f"trainer: {len(stats.losses)} losses, all finite",
    )
    trained = [np.asarray(x) for x in jax.tree_util.tree_leaves(learner.params)]
    check(
        any(not np.array_equal(a, b) for a, b in zip(init, trained, strict=True)),
        "trainer: the parameters moved",
    )

    # one TD step from the trained state, on the chip and on the CPU
    _, td = make_td_update(cfg)
    args = (learner.params, learner.target, learner.opt_state, *_td_batch(cfg))
    step = jax.jit(td)
    on_chip = step(*jax.device_put(args, device))
    on_cpu = step(*jax.device_put(args, jax.devices("cpu")[0]))
    d_params = max(
        float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
        for a, b in zip(
            jax.tree_util.tree_leaves(on_chip[0]),
            jax.tree_util.tree_leaves(on_cpu[0]), strict=True,
        )
    )
    d_loss = abs(float(on_chip[2]) - float(on_cpu[2]))
    log(f"trainer: TD step chip vs cpu max|dparam|={d_params} |dloss|={d_loss}")
    check(
        d_params <= TD_ATOL and d_loss <= TD_ATOL,
        f"trainer: TD step on the chip equals the CPU's to {TD_ATOL}",
    )

    # greedy probe of the checked-in parameters, as the baseline records it
    with open(os.path.join(ROOT, "benchmarks", "baselines", "rl_batched.json")) as f:
        probe = json.load(f)["params_probe"]
    probe_learner = DQNLearner(cfg)
    probe_learner.load(
        os.path.join(ROOT, "benchmarks", "baselines", "rl_dqn_params.npz")
    )
    rng = np.random.default_rng(probe["seed"])
    obs = rng.uniform(0.0, 1.0, size=(len(probe["actions"]), FEATURE_DIM))
    acts = [probe_learner.greedy_action(o.astype(np.float32)) for o in obs]
    log(f"trainer: probe actions on the chip {acts}")
    check(acts == probe["actions"],
          "trainer: greedy probe equals the recorded CPU actions")


# ------------------------------- four chips ---------------------------------


def four_chip_phase(devices, check: Checks) -> None:
    import jax
    import numpy as np

    from repro.core.rl.baseline import TRAIN_BATCH as B
    from repro.core.rl.batched_train import shard_rollouts

    if len(devices) < 4:
        check(False, f"four chips: JAX found {len(devices)} devices")
        return
    devs = list(devices[:4])
    layout = shard_rollouts(np.arange(B), devs)
    for shard in layout.addressable_shards:
        log(
            f"four chips: {shard.device} holds rollouts "
            f"{shard.index[0].start}:{shard.index[0].stop} "
            f"shard shape {shard.data.shape}"
        )
    sharded, s4 = _train(devs)
    one, s1 = _train(devs[:1])
    log(f"four chips: wall_s sharded={s4.wall_seconds} one={s1.wall_seconds}")

    r4, r1 = np.asarray(s4.episode_rewards), np.asarray(s1.episode_rewards)
    differ = r4[:B] != r1[:B]
    check(
        not differ.any(),
        f"four chips: first-round rewards equal ({int(differ.sum())} of {B} "
        f"differ, max diff {float(np.max(np.abs(r4[:B] - r1[:B])))})",
    )
    rel = np.abs(r4[B:] - r1[B:]) / np.maximum(np.abs(r1[B:]), 1e-30)
    check(
        bool(np.all(rel <= SHARDED_RTOL)),
        f"four chips: later rewards to {SHARDED_RTOL} relative "
        f"(max {float(rel.max(initial=0.0))})",
    )
    worst = 0.0
    for a, b in zip(
        jax.tree_util.tree_leaves(sharded.params),
        jax.tree_util.tree_leaves(one.params), strict=True,
    ):
        a, b = np.asarray(a), np.asarray(b)
        worst = max(worst, float(np.max(
            np.abs(a - b) / np.maximum(np.abs(b), 1e-30)
        )))
    check(worst <= SHARDED_RTOL,
          f"four chips: final parameters to {SHARDED_RTOL} relative "
          f"(max {worst})")
    spread = {len(x.sharding.device_set)
              for x in jax.tree_util.tree_leaves(sharded.params)}
    check(spread == {4}, f"four chips: parameters live on {spread} devices")
    pinned = {d for x in jax.tree_util.tree_leaves(one.params)
              for d in x.sharding.device_set}
    check(pinned == {devs[0]},
          f"four chips: one-chip parameters live on {sorted(map(str, pinned))}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded trainer against one chip")
    args = ap.parse_args(argv)

    devices = tpu_devices()
    from repro.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    dev = devices[0]
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    check = Checks()
    clock = CompileClock()
    if args.chips == 4:
        four_chip_phase(devices, check)
    else:
        sweep_phase(dev, clock, check)
        trainer_phase(dev, clock, check)
    if check.failed:
        print(f"chip_smoke: {len(check.failed)} checks failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
