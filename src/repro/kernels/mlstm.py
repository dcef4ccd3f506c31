"""mLSTM (xLSTM matrix-memory) chunkwise kernel in Pallas (TPU).

Chunkwise-parallel formulation: the sequence is split into chunks of length
L; within a chunk the output is a masked, gate-decayed attention-like product
(MXU matmuls); across chunks a matrix state C (D x D), normalizer n (D) and
max-tracker m (scalar) are carried in VMEM scratch — the chunk grid dimension
is sequential ("arbitrary").

Stabilized recurrences per head (b = inclusive cumsum of logsigmoid(f),
g = b[L-1], i = input-gate preactivation):

  state:  m' = max(g + m, max_j(g - b_j + i_j))
          C' = e^{g+m-m'} C + sum_j e^{g-b_j+i_j-m'} k_j v_j^T   (k scaled 1/sqrt(D))
          n' = e^{g+m-m'} n + sum_j e^{g-b_j+i_j-m'} k_j
  output: m_t = max(b_t + m, max_{s<=t}(b_t - b_s + i_s))
          h_t = [e^{b_t+m-m_t} q_t C + sum_s e^{b_t-b_s+i_s-m_t}(q_t.k_s) v_s]
                / max(|e^{b_t+m-m_t} q_t.n + sum_s e^{...}(q_t.k_s)|, e^{-m_t})

Matches :func:`repro.kernels.ref.mlstm_chunkwise_ref` (quadratic oracle).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["mlstm_chunkwise"]

NEG_INF = -1e30


def _mlstm_kernel(
    q_ref,  # (1, L, D)
    k_ref,  # (1, L, D)
    v_ref,  # (1, L, D)
    i_ref,  # (1, L)
    f_ref,  # (1, L)
    o_ref,  # (1, L, D)
    c_scr,  # (D, D) f32
    n_scr,  # (1, D) f32
    m_scr,  # (1, 1) f32
    *,
    L: int,
    scale: float,
):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        c_scr[...] = jnp.zeros_like(c_scr)
        n_scr[...] = jnp.zeros_like(n_scr)
        # empty state: max-tracker = -inf so inter terms vanish exactly
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)

    q = q_ref[0].astype(jnp.float32)  # (L, D)
    k = k_ref[0].astype(jnp.float32) * scale
    v = v_ref[0].astype(jnp.float32)
    ig = i_ref[0].astype(jnp.float32)  # (L,)
    lf = jax.nn.log_sigmoid(f_ref[0].astype(jnp.float32))  # (L,)

    b = jnp.cumsum(lf)  # (L,)
    g = b[L - 1]
    m_prev = m_scr[0, 0]
    C_prev = c_scr[...]
    n_prev = n_scr[0]

    # --- intra-chunk decay matrix -----------------------------------
    Dm = b[:, None] - b[None, :] + ig[None, :]  # (L_t, L_s)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    causal = s_idx <= t_idx
    Dm = jnp.where(causal, Dm, NEG_INF)

    m_inter = b + m_prev  # (L,)
    m_comb = jnp.maximum(jnp.max(Dm, axis=1), m_inter)  # (L,)

    dexp = jnp.exp(Dm - m_comb[:, None])  # (L, L)
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (L, L)
    w = scores * dexp
    inter_w = jnp.exp(m_inter - m_comb)  # (L,)

    num = jax.lax.dot_general(
        w, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ) + inter_w[:, None] * jax.lax.dot_general(
        q, C_prev, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    den = jnp.sum(w, axis=1) + inter_w * jnp.sum(q * n_prev[None, :], axis=1)
    den = jnp.maximum(jnp.abs(den), jnp.exp(-m_comb))
    o_ref[0] = (num / den[:, None]).astype(o_ref.dtype)

    # --- state update -------------------------------------------------
    key_w = g - b + ig  # (L,)
    m_new = jnp.maximum(g + m_prev, jnp.max(key_w))
    kw = jnp.exp(key_w - m_new)  # (L,)
    decay = jnp.exp(g + m_prev - m_new)
    kscaled = k * kw[:, None]
    c_scr[...] = decay * C_prev + jax.lax.dot_general(
        kscaled, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    n_scr[0] = decay * n_prev + jnp.sum(kscaled, axis=0)
    m_scr[0, 0] = m_new


def mlstm_chunkwise(
    q: jnp.ndarray,  # (B, T, H, D)
    k: jnp.ndarray,
    v: jnp.ndarray,
    i_gate: jnp.ndarray,  # (B, T, H)
    f_gate: jnp.ndarray,  # (B, T, H)
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Pallas chunkwise mLSTM; see :func:`repro.kernels.ref.mlstm_chunkwise_ref`."""
    B, T, H, D = q.shape
    L = min(chunk, T)
    assert T % L == 0, (T, L)
    scale = 1.0 / math.sqrt(D)

    qt = q.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    kt = k.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    vt = v.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    it = i_gate.transpose(0, 2, 1).reshape(B * H, T)
    ft = f_gate.transpose(0, 2, 1).reshape(B * H, T)

    grid = (B * H, T // L)
    kernel = functools.partial(_mlstm_kernel, L=L, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, L, D), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, L, D), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, L, D), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, L), lambda bh, c: (bh, c)),
            pl.BlockSpec((1, L), lambda bh, c: (bh, c)),
        ],
        out_specs=pl.BlockSpec((1, L, D), lambda bh, c: (bh, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, T, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((D, D), jnp.float32),
            pltpu.VMEM((1, D), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qt, kt, vt, it, ft)
    return out.reshape(B, H, T, D).transpose(0, 2, 1, 3)
