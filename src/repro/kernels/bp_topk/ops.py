"""Jit'd wrapper for fused backpressure gating."""
from __future__ import annotations

import functools

import jax

from .kernel import bp_topk
from .ref import bp_topk_ref


@functools.partial(jax.jit, static_argnames=("k", "block_t"))
def bp_topk_op(scores, bias, k, *, block_t=256):
    return bp_topk(scores, bias, k, block_t=block_t)


__all__ = ["bp_topk_op", "bp_topk_ref"]
