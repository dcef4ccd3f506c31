"""Flash attention for TPU in Pallas (GQA + causal + sliding window + softcap).

TPU-native design (DESIGN.md §2 hardware adaptation):
* grid = (batch*q_heads, Sq/bq, Sk/bk); the innermost (kv) dimension is
  sequential ("arbitrary") so the online-softmax state — running max ``m``,
  normalizer ``l`` and output accumulator ``acc`` — lives in VMEM scratch and
  persists across kv steps of one (bh, q-block).
* BlockSpecs tile q/k/v into VMEM as (bq, D) / (bk, D) slabs; matmul shapes
  (bq x D) @ (D x bk) and (bq x bk) @ (bk x D) keep the MXU fed with
  128-aligned dims.  fp32 accumulation; bf16 inputs.
* GQA is expressed through the k/v index_map (query head h reads kv head
  ``h // q_per_kv``) — no KV replication in HBM.
* causal + sliding-window masks are computed from block offsets with iota;
  fully-masked kv blocks are skipped via ``pl.when`` on block indices.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["flash_attention"]

NEG_INF = -1e30


def _fa_kernel(
    q_ref,  # (1, bq, D)
    k_ref,  # (1, bk, D)
    v_ref,  # (1, bk, D)
    o_ref,  # (1, bq, D)
    m_scr,  # (bq,) f32 scratch
    l_scr,  # (bq,) f32 scratch
    acc_scr,  # (bq, D) f32 scratch
    *,
    scale: float,
    causal: bool,
    window: Optional[int],
    softcap: Optional[float],
    bq: int,
    bk: int,
    kv_steps: int,
    q_offset: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * bq + q_offset  # absolute position of first query row
    k_start = ki * bk

    # block-level skip: entirely above the diagonal, or entirely left of the
    # sliding window
    run = jnp.bool_(True)
    if causal:
        run &= k_start <= q_start + bq - 1
    if window is not None:
        run &= k_start + bk - 1 > q_start - window

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)  # (bq, D)
        k = k_ref[0].astype(jnp.float32)  # (bk, D)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            ok &= k_pos <= q_pos
        if window is not None:
            ok &= k_pos > q_pos - window
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(ok, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[...] + jnp.sum(p, axis=1)
        acc = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new
        l_scr[...] = l_new
        acc_scr[...] = acc

    @pl.when(ki == kv_steps - 1)
    def _finalize():
        l = l_scr[...]
        safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0] = (acc_scr[...] / safe[:, None]).astype(o_ref.dtype)


def flash_attention(
    q: jnp.ndarray,  # (B, Sq, Hq, D)
    k: jnp.ndarray,  # (B, Sk, Hkv, D)
    v: jnp.ndarray,  # (B, Sk, Hkv, D)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    q_offset: int = 0,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Pallas flash attention; see :func:`repro.kernels.ref.attention_ref`."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    kv_steps = Sk // bk

    # (B, S, H, D) -> (B*H, S, D) layout for clean 2-D blocks
    qt = q.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, D)
    kt = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, D)
    vt = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, D)

    grid = (B * Hq, Sq // bq, kv_steps)

    kernel = functools.partial(
        _fa_kernel,
        scale=scale,
        causal=causal,
        window=window,
        softcap=softcap,
        bq=bq,
        bk=bk,
        kv_steps=kv_steps,
        q_offset=q_offset,
    )

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, qi, ki, g=group: (bh // g, ki, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, qi, ki, g=group: (bh // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qt, kt, vt)
    return out.reshape(B, Hq, Sq, D).transpose(0, 2, 1, 3)
