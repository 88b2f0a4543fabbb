"""Jit'd wrapper: gather endpoint backlogs and run the BP decision kernel."""
from __future__ import annotations

import functools

import jax

from .kernel import bp_route_decide
from .ref import bp_route_ref


@functools.partial(jax.jit, static_argnames=("block_e",))
def bp_route_op(Q: jax.Array, edges: jax.Array, cap: jax.Array, *,
                block_e: int = 256):
    """Q: [N, C] per-node class backlogs; edges: [E, 2]; cap: [E]."""
    qm = Q[edges[:, 0]]
    ql = Q[edges[:, 1]]
    return bp_route_decide(qm, ql, cap, block_e=block_e)


__all__ = ["bp_route_op", "bp_route_ref", "bp_route_decide"]
