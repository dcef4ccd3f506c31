"""Mamba selective-SSM scan in Pallas (TPU).

The recurrence h_t = exp(dt_t*A) h_{t-1} + (dt_t x_t) B_t,  y_t = C_t.h_t + D x_t
is sequential in T but embarrassingly parallel over (batch, channel).  TPU
mapping:

* grid = (B, Di/bDi, T/chunk); the chunk dimension is sequential
  ("arbitrary") and the carried state h (bDi, N) lives in VMEM scratch.
* Each grid step streams a (chunk, bDi) slab of x/dt and (chunk, N) slabs of
  B/C through VMEM and walks the chunk with a fori_loop of VPU elementwise
  ops — the (bDi, N) state update is rank-1 and memory-resident.
* channels are blocked at bDi (lane-aligned multiples of 128) so the state
  and slabs fit VMEM comfortably: bDi=512, N=16, chunk=128 -> ~0.6 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["mamba_scan"]


def _mamba_kernel(
    x_ref,  # (1, chunk, bDi)
    dt_ref,  # (1, chunk, bDi)
    a_ref,  # (bDi, N)
    b_ref,  # (1, chunk, N)
    c_ref,  # (1, chunk, N)
    d_ref,  # (1, bDi)
    y_ref,  # (1, chunk, bDi)
    h_scr,  # (bDi, N) f32
    *,
    chunk: int,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0].astype(jnp.float32)  # (chunk, bDi)
    dt = dt_ref[0].astype(jnp.float32)
    A = a_ref[...].astype(jnp.float32)  # (bDi, N)
    Bm = b_ref[0].astype(jnp.float32)  # (chunk, N)
    Cm = c_ref[0].astype(jnp.float32)
    Dv = d_ref[0].astype(jnp.float32)  # (bDi,)

    def step(t, h):
        dt_t = jax.lax.dynamic_slice_in_dim(dt, t, 1, 0)[0]  # (bDi,)
        x_t = jax.lax.dynamic_slice_in_dim(x, t, 1, 0)[0]
        b_t = jax.lax.dynamic_slice_in_dim(Bm, t, 1, 0)[0]  # (N,)
        c_t = jax.lax.dynamic_slice_in_dim(Cm, t, 1, 0)[0]
        dA = jnp.exp(dt_t[:, None] * A)  # (bDi, N)
        h = dA * h + (dt_t * x_t)[:, None] * b_t[None, :]
        y_t = jnp.sum(h * c_t[None, :], axis=1) + Dv * x_t  # (bDi,)
        y_ref[0, t, :] = y_t.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_scr[...])
    h_scr[...] = h


def mamba_scan(
    x: jnp.ndarray,  # (B, T, Di)
    dt: jnp.ndarray,  # (B, T, Di) post-softplus
    A: jnp.ndarray,  # (Di, N)
    Bmat: jnp.ndarray,  # (B, T, N)
    Cmat: jnp.ndarray,  # (B, T, N)
    D: jnp.ndarray,  # (Di,)
    *,
    block_channels: int = 512,
    chunk: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Pallas selective scan; see :func:`repro.kernels.ref.mamba_scan_ref`."""
    B, T, Di = x.shape
    N = A.shape[1]
    bDi = min(block_channels, Di)
    ch = min(chunk, T)
    assert Di % bDi == 0 and T % ch == 0, (Di, bDi, T, ch)

    grid = (B, Di // bDi, T // ch)
    kernel = functools.partial(_mamba_kernel, chunk=ch)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, ch, bDi), lambda b, di, c: (b, c, di)),
            pl.BlockSpec((1, ch, bDi), lambda b, di, c: (b, c, di)),
            pl.BlockSpec((bDi, N), lambda b, di, c: (di, 0)),
            pl.BlockSpec((1, ch, N), lambda b, di, c: (b, c, 0)),
            pl.BlockSpec((1, ch, N), lambda b, di, c: (b, c, 0)),
            pl.BlockSpec((1, bDi), lambda b, di, c: (0, di)),
        ],
        out_specs=pl.BlockSpec((1, ch, bDi), lambda b, di, c: (b, c, di)),
        out_shape=jax.ShapeDtypeStruct((B, T, Di), x.dtype),
        scratch_shapes=[pltpu.VMEM((bDi, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, dt, A, Bmat, Cmat, D[None, :])
