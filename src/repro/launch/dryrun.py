import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove every (arch x shape x mesh) cell lowers, shards
and compiles on the production mesh — and extract its roofline terms.

The two lines above MUST stay first: jax locks the device count at first
init, and the dry-run needs 512 placeholder host devices for the 2x16x16
multi-pod mesh.  (Smoke tests/benches import repro.* without this module and
keep seeing 1 device.)

Per cell this produces (cached incrementally under artifacts/dryrun/):
* compile success + ``memory_analysis()``   (does it fit 16 GB/chip?)
* ``cost_analysis()`` FLOPs/bytes           (§Roofline compute/memory terms)
* collective bytes parsed from the compiled HLO (§Roofline collective term)

``lax.scan`` bodies are counted ONCE by XLA's cost analysis, so scanned
models would under-report by ~n_layers.  The extractor therefore also lowers
two unscanned mini-models (1 and 2 pattern units) and composites:
``total = outer + unit x repeats`` with ``unit = mini2 - mini1`` — exact for
per-layer costs, and it localizes collectives correctly (gradient
all-reduces of a unit's params appear in the diff).  See EXPERIMENTS.md
§Dry-run for the methodology notes.
"""

import argparse
import dataclasses
import json
import re
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data.pipeline import make_batch_specs
from repro.distributed.sharding import (
    batch_shardings,
    cache_shardings,
    param_shardings,
)
from repro.distributed.step import make_prefill_step, make_serve_step, make_train_step
from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import SHAPES, ShapeSpec, accum_steps_for, cell_applicable
from repro.models import abstract_params, init_cache
from repro.models.config import ArchConfig
from repro.optim import AdamW, AdamWConfig

ARTIFACTS = os.path.join(os.path.dirname(__file__), "..", "..", "..", "artifacts", "dryrun")

_COLLECTIVE_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
)
_SHAPE_RE = re.compile(r"(f32|bf16|f16|s32|u32|s8|u8|pred|s64|f64)\[([0-9,]*)\]")
_DTYPE_BYTES = {
    "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
    "s8": 1, "u8": 1, "pred": 1, "s64": 8, "f64": 8,
}


def parse_collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum result-shape bytes of every collective op (per-device program)."""
    out: Dict[str, float] = {}
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if not m or "=" not in line:
            continue
        kind = m.group(1)
        lhs = line.split("=")[0]
        # result shape(s) appear on the lhs of "name = shape op(...)"
        rhs_head = line.split("=", 1)[1]
        shapes = _SHAPE_RE.findall(rhs_head.split(m.group(1))[0])
        if not shapes:
            shapes = _SHAPE_RE.findall(lhs)
        nbytes = 0.0
        for dt, dims in shapes:
            numel = 1
            if dims:
                for d in dims.split(","):
                    if d:
                        numel *= int(d)
            nbytes += numel * _DTYPE_BYTES[dt]
        out[kind] = out.get(kind, 0.0) + nbytes
    return out


def runtime_config(arch: str, for_cost: bool = False, repeats: Optional[int] = None) -> ArchConfig:
    cfg = get_config(arch)
    if not for_cost:
        return dataclasses.replace(cfg, scan_layers=True, remat="block")
    unit_len = len(cfg.pattern_unit())
    assert repeats is not None
    changes: Dict[str, Any] = dict(
        n_layers=unit_len * repeats, scan_layers=False, remat="none"
    )
    if cfg.encoder is not None:
        changes["encoder"] = dataclasses.replace(cfg.encoder, n_layers=repeats)
    return dataclasses.replace(cfg, **changes)


def make_optimizer(cfg: ArchConfig) -> AdamW:
    # bf16 optimizer states for the giant models (EXPERIMENTS.md memory table)
    state_dtype = "bfloat16" if cfg.d_model >= 8_000 else None
    return AdamW(AdamWConfig(lr=3e-4, state_dtype=state_dtype))


# --------------------------- abstract inputs ------------------------------


def input_specs(arch: str, shape: ShapeSpec, mesh, cfg: Optional[ArchConfig] = None):
    """ShapeDtypeStruct stand-ins + shardings for one cell (no allocation)."""
    cfg = cfg or runtime_config(arch)
    params_abs = abstract_params(cfg)
    # resident-weight (serve) sharding only pays when the batch amortises the
    # per-device weight reads; at batch 1 (long_500k) 2-D sharding reads 16x
    # less weight per device and the activation psums are tiny (§Perf log)
    serve_mode = shape.kind != "train" and shape.global_batch >= 32
    p_shard = param_shardings(
        params_abs, mesh, mode="serve" if serve_mode else "train"
    )

    if shape.kind == "train":
        opt = make_optimizer(cfg)
        opt_abs = jax.eval_shape(opt.init, params_abs)
        o_shard = param_shardings_like(opt_abs, p_shard)
        batch = make_batch_specs(cfg, shape.global_batch, shape.seq_len, True)
        b_shard = batch_shardings(batch, mesh)
        return (params_abs, opt_abs, batch), (p_shard, o_shard, b_shard), opt
    if shape.kind == "prefill":
        batch = make_batch_specs(cfg, shape.global_batch, shape.seq_len, False)
        b_shard = batch_shardings(batch, mesh)
        return (params_abs, batch), (p_shard, b_shard), None
    # decode
    cache_abs = jax.eval_shape(lambda: init_cache(cfg, shape.global_batch, shape.seq_len))
    c_shard = cache_shardings(cache_abs, mesh, shape.global_batch)
    token = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    index = jax.ShapeDtypeStruct((), jnp.int32)
    from jax.sharding import NamedSharding, PartitionSpec as P

    t_shard = jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P()), token
    )
    i_shard = NamedSharding(mesh, P())
    args = [params_abs, cache_abs, token, index]
    shards = [p_shard, c_shard, t_shard, i_shard]
    if cfg.encoder is not None:
        enc = jax.ShapeDtypeStruct(
            (shape.global_batch, cfg.encoder.n_frames, cfg.d_model), jnp.bfloat16
        )
        args.append(enc)
        shards.append(NamedSharding(mesh, P()))
    return tuple(args), tuple(shards), None


def param_shardings_like(opt_abs, p_shard):
    """Optimizer state shardings: m/v mirror the params; step replicated."""
    import jax.tree_util as jtu
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = jtu.tree_leaves(p_shard)[0].mesh
    flat_p = jtu.tree_leaves(p_shard)

    def build(tree):
        leaves = jtu.tree_leaves(tree)
        # m and v have the same structure as params
        return jtu.tree_unflatten(jtu.tree_structure(tree), flat_p[: len(leaves)])

    return type(opt_abs)(
        m=build(opt_abs.m),
        v=build(opt_abs.v),
        step=NamedSharding(mesh, P()),
    )


# ------------------------------ lowering -----------------------------------


def lower_cell(
    arch: str,
    shape: ShapeSpec,
    mesh,
    cfg: Optional[ArchConfig] = None,
    donate: bool = True,
    compile_: bool = True,
) -> Dict[str, Any]:
    cfg = cfg or runtime_config(arch)
    t0 = time.time()
    args, shards, opt = input_specs(arch, shape, mesh, cfg)

    if shape.kind == "train":
        accum = accum_steps_for(arch, shape, int(np.prod([mesh.shape[a] for a in mesh.axis_names if a != "model"])))
        if os.environ.get("REPRO_ACCUM_OVERRIDE"):
            accum = int(os.environ["REPRO_ACCUM_OVERRIDE"])
        if not cfg.scan_layers:  # cost mode: no accumulation scan
            accum = 1
        g_dt = "bfloat16" if cfg.d_model >= 8_000 else "float32"
        step = make_train_step(
            cfg, opt, accum_steps=accum, impl="ref", grad_accum_dtype=g_dt
        )
        jitted = jax.jit(
            step,
            in_shardings=shards,
            donate_argnums=(0, 1) if donate else (),
        )
    elif shape.kind == "prefill":
        step = make_prefill_step(cfg, impl="ref")
        jitted = jax.jit(step, in_shardings=shards)
    else:
        step = make_serve_step(cfg, impl="ref")
        jitted = jax.jit(
            step, in_shardings=shards, donate_argnums=(1,) if donate else ()
        )

    jax.sharding.set_mesh(mesh)  # populates the abstract mesh for hints
    with mesh:
        lowered = jitted.lower(*args)
        rec: Dict[str, Any] = {"lower_seconds": time.time() - t0}
        if compile_:
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_seconds"] = time.time() - t1
            mem = compiled.memory_analysis()
            if mem is not None:
                for attr in (
                    "argument_size_in_bytes",
                    "output_size_in_bytes",
                    "temp_size_in_bytes",
                    "generated_code_size_in_bytes",
                    "alias_size_in_bytes",
                ):
                    rec[attr] = getattr(mem, attr, None)
            cost = compiled.cost_analysis()
            if isinstance(cost, list):
                cost = cost[0]
            rec["flops"] = float(cost.get("flops", 0.0)) if cost else None
            rec["bytes_accessed"] = float(cost.get("bytes accessed", 0.0)) if cost else None
            rec["collectives"] = parse_collective_bytes(compiled.as_text())
    return rec


def composite_cost(arch: str, shape: ShapeSpec, mesh) -> Dict[str, Any]:
    """Scan-free cost: lower 0- and 1-unit mini-models, composite per-unit.

    mini0 = embed + head only (compiles in seconds even for 340B shapes);
    unit = mini1 - mini0; total = mini0 + unit x repeats.
    """
    full_cfg = get_config(arch)
    repeats = full_cfg.num_pattern_repeats
    mini1 = lower_cell(arch, shape, mesh, cfg=runtime_config(arch, True, 1), donate=False)
    if repeats == 1:
        out = dict(mini1)
        out["composite"] = {
            "flops": mini1["flops"],
            "bytes_accessed": mini1["bytes_accessed"],
            "collectives": mini1["collectives"],
            "repeats": 1,
        }
        return out
    mini0 = lower_cell(arch, shape, mesh, cfg=runtime_config(arch, True, 0), donate=False)

    def comp(key):
        u = (mini1[key] or 0.0) - (mini0[key] or 0.0)
        return (mini0[key] or 0.0) + max(u, 0.0) * repeats

    coll: Dict[str, float] = {}
    kinds = set(mini1["collectives"]) | set(mini0["collectives"])
    for k in kinds:
        a = mini0["collectives"].get(k, 0.0)
        b = mini1["collectives"].get(k, 0.0)
        u = b - a
        coll[k] = a + max(u, 0.0) * repeats
    return {
        "mini0": mini0,
        "mini1": mini1,
        "composite": {
            "flops": comp("flops"),
            "bytes_accessed": comp("bytes_accessed"),
            "collectives": coll,
            "repeats": repeats,
        },
    }


# ------------------------------ runner -------------------------------------


def run_cell(arch: str, shape_name: str, multi_pod: bool, with_cost: bool) -> Dict[str, Any]:
    shape = SHAPES[shape_name]
    ok, reason = cell_applicable(arch, shape_name)
    if not ok:
        return {"skipped": True, "reason": reason}
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec = lower_cell(arch, shape, mesh)
    rec["devices"] = int(np.prod(list(mesh.shape.values())))
    if with_cost and not multi_pod:
        rec["cost"] = composite_cost(arch, shape, mesh)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--no-cost", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    key = f"{args.arch}__{args.shape}__{'multipod' if args.multi_pod else 'pod'}"
    out_dir = args.out or os.path.abspath(ARTIFACTS)
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, key + ".json")
    try:
        rec = run_cell(args.arch, args.shape, args.multi_pod, with_cost=not args.no_cost)
        rec["ok"] = not rec.get("skipped", False)
    except Exception as e:  # noqa: BLE001 - recorded, rerun after fix
        rec = {"ok": False, "error": f"{type(e).__name__}: {e}", "trace": traceback.format_exc()}
    rec["arch"] = args.arch
    rec["shape"] = args.shape
    rec["multi_pod"] = args.multi_pod
    with open(out_path, "w") as f:
        json.dump(rec, f, indent=2, default=float)
    status = "SKIP" if rec.get("skipped") else ("OK" if rec["ok"] else "FAIL")
    print(f"[{status}] {key}")
    if rec.get("error"):
        print(rec["error"])
    if rec.get("temp_size_in_bytes") is not None:
        print(f"  temp bytes/device: {rec['temp_size_in_bytes']:.3e}")
    if rec.get("flops") is not None:
        print(f"  scanned-HLO flops (per device): {rec['flops']:.3e}")
    if "cost" in rec:
        c = rec["cost"]["composite"]
        print(f"  composite flops (per device): {c['flops']:.3e}  collectives: { {k: f'{v:.2e}' for k, v in c['collectives'].items()} }")


if __name__ == "__main__":
    main()
