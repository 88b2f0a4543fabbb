"""Pallas TPU kernel for fused backpressure MoE gating: softmax over the
expert axis, subtract the H-queue bias (paper eq. 9), iterative top-k
selection, and renormalized combine weights — one VMEM pass per token tile.

Grid: (T // block_t,); block [block_t, E] score panels on the VPU, k static
(<= 8 in our archs), so the top-k is a k-step argmax/mask loop, unrolled.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..platform import pallas_interpret

NEG = -1e30


def _bp_topk_kernel(s_ref, bias_ref, idx_ref, w_ref, *, k: int):
    s = s_ref[...].astype(jnp.float32)              # [bt, E]
    bias = bias_ref[...].astype(jnp.float32)        # [E]
    # row softmax
    m = s.max(axis=1, keepdims=True)
    e = jnp.exp(s - m)
    probs = e / e.sum(axis=1, keepdims=True)
    sel = probs - bias[None, :]

    wsum = jnp.zeros((s.shape[0],), jnp.float32)
    work = sel
    for j in range(k):                              # unrolled static top-k
        best = jnp.argmax(work, axis=1).astype(jnp.int32)
        pbest = jnp.take_along_axis(probs, best[:, None], axis=1)[:, 0]
        idx_ref[:, j] = best
        w_ref[:, j] = pbest
        wsum = wsum + pbest
        work = jnp.where(
            jax.nn.one_hot(best, s.shape[1], dtype=jnp.bool_), NEG, work)
    # renormalize combine weights over the selected experts
    wsum = jnp.maximum(wsum, 1e-9)
    w_ref[...] = w_ref[...] / wsum[:, None]


def bp_topk(scores: jax.Array, bias: jax.Array, k: int, *,
            block_t: int = 256):
    """scores: [T, E] gate logits; bias: [E] (beta*H/C).  Returns
    (idx [T, k] i32, weights [T, k] f32, renormalized)."""
    T, E = scores.shape
    block_t = min(block_t, T)
    pad = (-T) % block_t
    if pad:
        scores = jnp.concatenate(
            [scores, jnp.zeros((pad, E), scores.dtype)], axis=0)
    Tp = scores.shape[0]

    idx, w = pl.pallas_call(
        functools.partial(_bp_topk_kernel, k=k),
        grid=(Tp // block_t,),
        in_specs=[
            pl.BlockSpec((block_t, E), lambda i: (i, 0)),
            pl.BlockSpec((E,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((block_t, k), lambda i: (i, 0)),
            pl.BlockSpec((block_t, k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Tp, k), jnp.int32),
            jax.ShapeDtypeStruct((Tp, k), jnp.float32),
        ],
        interpret=pallas_interpret(),
    )(scores, bias)
    return idx[:T], w[:T]
