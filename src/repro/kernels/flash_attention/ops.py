"""Jit'd public wrapper for the flash attention kernel."""
from __future__ import annotations

import functools

import jax

from .kernel import flash_attention
from .ref import attention_ref


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k"))
def flash_attention_op(q, k, v, *, causal=True, window=None, block_q=128,
                       block_k=128):
    return flash_attention(q, k, v, causal=causal, window=window,
                           block_q=block_q, block_k=block_k)


__all__ = ["flash_attention_op", "attention_ref"]
