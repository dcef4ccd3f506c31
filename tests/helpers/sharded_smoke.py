"""Subprocess helper: exercise the sharding machinery on 8 fake CPU devices.

Must run in its own process (forces the device count before jax init).
Lowers + compiles + EXECUTES a smoke-config train step and a serve step on a
4x2 (data, model) mesh, and checks elastic checkpoint restore onto a
different mesh layout.  Exits nonzero on any failure.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np


def main() -> int:
    from repro.checkpoint import restore_checkpoint, save_checkpoint
    from repro.configs import smoke_config
    from repro.data.pipeline import SyntheticLM
    from repro.distributed.sharding import (
        batch_shardings,
        cache_shardings,
        param_shardings,
    )
    from repro.distributed.step import make_serve_step, make_train_step
    from repro.launch.mesh import make_smoke_mesh
    from repro.models import init_cache, init_params
    from repro.optim import AdamW, AdamWConfig

    assert jax.device_count() == 8, jax.device_count()
    mesh = make_smoke_mesh(4, 2)
    jax.sharding.set_mesh(mesh)

    cfg = smoke_config("mixtral_8x7b")  # MoE + SWA exercises EP + ring caches
    params = init_params(cfg, seed=0)
    p_shard = param_shardings(params, mesh)
    params = jax.device_put(params, p_shard)
    opt = AdamW(AdamWConfig(lr=1e-3))
    opt_state = opt.init(params)

    data = SyntheticLM(cfg, global_batch=8, seq_len=64, seed=0)
    batch = data.batch_for_step(0)
    b_shard = batch_shardings(batch, mesh)
    batch = jax.device_put(batch, b_shard)

    step = jax.jit(make_train_step(cfg, opt, accum_steps=2, impl="ref"),
                   donate_argnums=(0, 1))
    with mesh:
        params, opt_state, metrics = step(params, opt_state, batch)
        loss0 = float(metrics["loss"])
        params, opt_state, metrics = step(params, opt_state, batch)
        loss1 = float(metrics["loss"])
    assert np.isfinite(loss0) and np.isfinite(loss1), (loss0, loss1)
    assert loss1 < loss0 + 1.0  # sane

    # serve step on the mesh with sharded caches
    cache = init_cache(cfg, batch=8, max_len=64)
    cache = jax.device_put(cache, cache_shardings(cache, mesh, 8))
    serve = jax.jit(make_serve_step(cfg, impl="ref"), donate_argnums=(1,))
    tok = jnp.zeros((8, 1), jnp.int32)
    with mesh:
        logits, cache = serve(params, cache, tok, jnp.asarray(0, jnp.int32))
    assert logits.shape == (8, 1, cfg.vocab_size)
    assert not bool(jnp.any(jnp.isnan(logits)))

    # elastic restore: save from the 4x2 mesh, restore onto 2x4
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, 1, {"params": params})
        mesh2 = make_smoke_mesh(2, 4)
        tgt = jax.eval_shape(lambda: {"params": params})
        shard2 = {"params": param_shardings(params, mesh2)}
        out = restore_checkpoint(d, 1, tgt, shardings=shard2)
        x = jax.tree_util.tree_leaves(out)[0]
        assert x.sharding.mesh.shape == {"data": 2, "model": 4}
    print("SHARDED_SMOKE_OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
