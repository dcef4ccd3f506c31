"""Training driver: end-to-end on whatever devices exist.

``python -m repro.launch.train --arch gemma3-1b --smoke --steps 200`` trains
the reduced config on CPU; on a TPU pod the full config + production mesh
apply.  Features exercised here: deterministic restart-safe data, pjit'd
train step, async checkpointing + elastic resume, loss logging.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager, latest_step, restore_checkpoint
from repro.configs import get_config, smoke_config
from repro.data.pipeline import SyntheticLM
from repro.distributed.sharding import batch_shardings, param_shardings
from repro.distributed.step import make_train_step
from repro.launch.mesh import make_production_mesh, make_smoke_mesh
from repro.models import init_params
from repro.optim import AdamW, AdamWConfig, linear_warmup_cosine

__all__ = ["train", "main"]


def train(
    arch: str,
    steps: int = 100,
    smoke: bool = True,
    global_batch: int = 8,
    seq_len: int = 256,
    accum_steps: int = 1,
    lr: float = 3e-4,
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 50,
    seed: int = 0,
    production_mesh: bool = False,
    log_every: int = 10,
    verbose: bool = True,
):
    cfg = smoke_config(arch) if smoke else get_config(arch)
    cfg = dataclasses.replace(cfg, scan_layers=True, remat="block")
    mesh = (
        make_production_mesh() if production_mesh else make_smoke_mesh()
    )
    jax.sharding.set_mesh(mesh)

    opt = AdamW(
        AdamWConfig(lr=linear_warmup_cosine(lr, max(steps // 20, 1), steps))
    )
    step_fn = make_train_step(cfg, opt, accum_steps=accum_steps, impl="ref")

    params = init_params(cfg, seed=seed)
    opt_state = opt.init(params)
    p_shard = param_shardings(params, mesh)
    params = jax.device_put(params, p_shard)

    data = SyntheticLM(cfg, global_batch, seq_len, seed=seed)
    start_step = 0
    manager = None
    if ckpt_dir:
        manager = CheckpointManager(ckpt_dir)
        last = latest_step(ckpt_dir)
        if last is not None:
            state = restore_checkpoint(
                ckpt_dir, last, jax.eval_shape(lambda: {"params": params, "opt": opt_state})
            )
            params, opt_state = state["params"], state["opt"]
            start_step = last
            if verbose:
                print(f"resumed from step {last}")

    jitted = jax.jit(step_fn, donate_argnums=(0, 1))
    losses = []
    t0 = time.time()
    with mesh:
        for step in range(start_step, steps):
            batch = jax.device_put(
                data.batch_for_step(step), batch_shardings(
                    jax.tree_util.tree_map(np.asarray, data.batch_for_step(step)), mesh
                )
            )
            params, opt_state, metrics = jitted(params, opt_state, batch)
            losses.append(float(metrics["loss"]))
            if verbose and (step + 1) % log_every == 0:
                dt = (time.time() - t0) / max(step + 1 - start_step, 1)
                print(
                    f"step {step + 1}/{steps} loss={losses[-1]:.4f} "
                    f"gnorm={float(metrics['grad_norm']):.3f} ({dt * 1e3:.0f} ms/step)"
                )
            if manager and (step + 1) % ckpt_every == 0:
                manager.save_async(step + 1, {"params": params, "opt": opt_state})
    if manager:
        manager.wait()
    return params, losses


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--accum-steps", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--production-mesh", action="store_true")
    args = ap.parse_args()
    _, losses = train(
        args.arch,
        steps=args.steps,
        smoke=args.smoke,
        global_batch=args.global_batch,
        seq_len=args.seq_len,
        accum_steps=args.accum_steps,
        lr=args.lr,
        ckpt_dir=args.ckpt_dir,
        production_mesh=args.production_mesh,
    )
    n = max(len(losses) // 10, 1)
    print(f"first-{n} loss {np.mean(losses[:n]):.4f} -> last-{n} {np.mean(losses[-n:]):.4f}")


if __name__ == "__main__":
    main()
