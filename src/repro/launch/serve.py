"""Serving driver: batched prefill+decode on whatever devices exist.

``python -m repro.launch.serve --arch mixtral-8x7b --smoke`` serves the
reduced config on CPU; on a TPU pod the full config + production mesh apply
(decode cells of the dry-run lower exactly this step).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke_config
from repro.distributed.sharding import cache_shardings, param_shardings
from repro.launch.mesh import make_production_mesh, make_smoke_mesh
from repro.models import decode_step, init_cache, init_params


def serve(
    arch: str,
    smoke: bool = True,
    batch: int = 4,
    steps: int = 32,
    max_len: int = 128,
    production_mesh: bool = False,
    seed: int = 0,
    verbose: bool = True,
) -> float:
    cfg = smoke_config(arch) if smoke else get_config(arch)
    mesh = make_production_mesh() if production_mesh else make_smoke_mesh()
    jax.sharding.set_mesh(mesh)

    params = init_params(cfg, seed=seed)
    params = jax.device_put(params, param_shardings(params, mesh))
    cache = init_cache(cfg, batch, max_len)
    cache = jax.device_put(cache, cache_shardings(cache, mesh, batch))

    step = jax.jit(
        lambda p, c, t, i: decode_step(cfg, p, c, t, i, impl="ref"),
        donate_argnums=(1,),
    )
    rng = np.random.default_rng(seed)
    tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, 1)), jnp.int32)
    with mesh:
        logits, cache = step(params, cache, tok, jnp.asarray(0, jnp.int32))  # compile
        t0 = time.time()
        for i in range(1, steps):
            tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
            logits, cache = step(params, cache, tok, jnp.asarray(i, jnp.int32))
        jax.block_until_ready(logits)
    dt = time.time() - t0
    tps = batch * (steps - 1) / dt
    if verbose:
        print(f"{arch}: {tps:.1f} tok/s (batch={batch}, {dt/(steps-1)*1e3:.1f} ms/step)")
    return tps


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--production-mesh", action="store_true")
    args = ap.parse_args()
    serve(
        args.arch,
        smoke=args.smoke,
        batch=args.batch,
        steps=args.steps,
        production_mesh=args.production_mesh,
    )


if __name__ == "__main__":
    main()
