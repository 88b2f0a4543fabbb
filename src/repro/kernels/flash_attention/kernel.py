"""Pallas TPU flash attention (blockwise online softmax) with causal +
sliding-window masks and GQA head mapping.

Grid: (B*H, n_q_blocks, n_kv_blocks) with the kv dimension innermost
("arbitrary" semantics) revisiting the output block; running max / sum /
accumulator live in VMEM scratch.  Block shapes are MXU-aligned
(block_q x head_dim and block_k x head_dim tiles).

VMEM working set per step: q (bq x D) + k,v (bk x D each) + acc (bq x D)
+ stats (2 x bq) — for bq=bk=128, D=128 in bf16/f32 well under the ~16 MB
v5e VMEM budget, leaving room for double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform import pallas_interpret

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int | None,
                  block_q: int, block_k: int, n_kv: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale          # [bq, D]
    k = k_ref[0].astype(jnp.float32)                  # [bk, D]
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [bq, bk]

    q_idx = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_idx = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask &= q_idx >= k_idx
    if window is not None:
        mask &= k_idx > q_idx - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                               # [bq]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(mask, p, 0.0)                       # guard exp(NEG_INF-m)
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ik == n_kv - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    block_q: int = 128, block_k: int = 128) -> jax.Array:
    """q: [B, H, S, D]; k/v: [B, KH, T, D] (H % KH == 0).  Returns [B,H,S,D].

    The mode follows the platform (`repro.kernels.platform`): compiled on
    a TPU, interpreted on the CPU.
    """
    B, H, S, D = q.shape
    KH, T = k.shape[1], k.shape[2]
    assert H % KH == 0
    G = H // KH
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    assert S % block_q == 0 and T % block_k == 0
    n_q, n_kv = S // block_q, T // block_k
    scale = 1.0 / np.sqrt(D)

    qf = q.reshape(B * H, S, D)
    kf = k.reshape(B * KH, T, D)
    vf = v.reshape(B * KH, T, D)

    def q_map(bh, iq, ik):
        return (bh, iq, 0)

    def kv_map(bh, iq, ik):
        b = bh // H
        h = bh % H
        return (b * KH + h // G, ik, 0)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, n_kv=n_kv)

    out = pl.pallas_call(
        kernel,
        grid=(B * H, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, D), q_map),
            pl.BlockSpec((1, block_k, D), kv_map),
            pl.BlockSpec((1, block_k, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), q_map),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=pallas_interpret(),
    )(qf, kf, vf)
    return out.reshape(B, H, S, D)
