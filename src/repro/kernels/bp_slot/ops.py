"""Wrappers for the fused slot-decision kernels (bp_slot).

`slot_route_decide` / `comp_balance_decide` (kernel.py) are plain traced
functions — `repro.core.policies` calls them inside the scan body when
`PolicyConfig.backend == "pallas"`.  `slot_route_op` is the standalone
jit'd entry used by benchmarks/tests: it takes the raw [N, 3, NC] queue
tensor plus edge list and emits the full per-edge decision tuple
(best_class, best_comp, direction, rate), mirroring `bp_route.ops`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import comp_balance_decide, slot_route_decide
from .ref import (balance_score, combine_amount, comp_balance_ref,
                  pair_count, slot_route_ref)


@functools.partial(jax.jit,
                   static_argnames=("block_e", "block_c"))
def slot_route_op(Q: jax.Array, edges: jax.Array, cap: jax.Array, *,
                  block_e: int = 128, block_c: int | None = None):
    """Q: [N, 3, NC] per-node class backlogs; edges: [E, 2]; cap: [E].

    Returns (best_class [E] i32 in 0..2, best_comp [E] i32, direction [E]
    i32 with +1 = m->l, rate [E] f32) — the full routing decision of
    `repro.core.policies.bp_route_slot` without materializing [E, 3, NC].
    """
    NC = Q.shape[-1]
    Qf = Q.reshape(Q.shape[0], -1)
    best, dmax = slot_route_decide(Qf, edges[:, 0], edges[:, 1],
                                   block_e=block_e, block_c=block_c)
    rate = jnp.where(jnp.abs(dmax) > 0, cap.astype(Qf.dtype), 0.0)
    dirn = jnp.where(dmax > 0, 1, -1).astype(jnp.int32)
    return best // NC, best % NC, dirn, rate


def slot_route_op_ref(Q: jax.Array, edges: jax.Array, cap: jax.Array):
    """Pure-jnp oracle for `slot_route_op` (materializes [E, 3*NC])."""
    NC = Q.shape[-1]
    Qf = Q.reshape(Q.shape[0], -1)
    best, dmax = slot_route_ref(Qf, edges[:, 0], edges[:, 1])
    rate = jnp.where(jnp.abs(dmax) > 0, cap.astype(Qf.dtype), 0.0)
    dirn = jnp.where(dmax > 0, 1, -1).astype(jnp.int32)
    return best // NC, best % NC, dirn, rate


__all__ = [
    "slot_route_decide", "comp_balance_decide", "slot_route_op",
    "slot_route_op_ref", "slot_route_ref", "comp_balance_ref",
    "pair_count", "combine_amount", "balance_score",
]
