"""Grouped matmul (MoE expert FFN) in Pallas (TPU).

After MoE routing, tokens are sorted by expert: row-block ``m`` of the sorted
activation matrix belongs to exactly one expert (the dispatcher pads each
group to a multiple of the row-block size).  The expert id per row-block is
delivered through scalar prefetch so the ``rhs`` BlockSpec can select the
right expert's weights — no (tokens, experts) one-hot and no weight gather
ever materializes in HBM.

Grid = (M/bm, N/bn, K/bk); K is innermost/sequential with an (bm, bn) fp32
VMEM accumulator; 128-aligned tiles keep the MXU busy.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["gmm"]


def _gmm_kernel(
    gid_ref,  # scalar prefetch: (M/bm,) int32 group id per row block
    lhs_ref,  # (bm, bk)
    rhs_ref,  # (1, bk, bn)
    out_ref,  # (bm, bn)
    acc_scr,  # (bm, bn) f32
    *,
    k_steps: int,
):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += jax.lax.dot_general(
        lhs_ref[...].astype(jnp.float32),
        rhs_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == k_steps - 1)
    def _done():
        out_ref[...] = acc_scr[...].astype(out_ref.dtype)


def gmm(
    lhs: jnp.ndarray,  # (M, K) rows sorted by group, groups padded to bm
    rhs: jnp.ndarray,  # (G, K, N)
    group_ids: jnp.ndarray,  # (M // bm,) int32: group of each row block
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Pallas grouped matmul; see :func:`repro.kernels.ref.gmm_ref`.

    The caller guarantees every row block is homogeneous (group boundaries
    aligned to ``block_m``) and passes the per-block group ids.
    """
    M, K = lhs.shape
    G, K2, N = rhs.shape
    assert K == K2, (K, K2)
    bm = min(block_m, M)
    bn = min(block_n, N)
    bk = min(block_k, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, bm, N, bn, K, bk)
    assert group_ids.shape == (M // bm,), group_ids.shape

    grid = (M // bm, N // bn, K // bk)
    kernel = functools.partial(_gmm_kernel, k_steps=K // bk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k, gid: (m, k)),
            pl.BlockSpec((1, bk, bn), lambda m, n, k, gid: (gid[m], k, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k, gid: (m, n)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(group_ids.astype(jnp.int32), lhs, rhs)
