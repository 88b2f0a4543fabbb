"""Pallas TPU kernel for the backpressure routing decision (the paper's BP
box): for every link, scan all 3*N_C class backlogs at both endpoints, pick
the class with maximum |differential backlog| and emit (class, direction,
rate).

At fleet scale this is the control-plane hot loop: |E| links x C classes
every slot.  The kernel tiles links x classes into VMEM ([block_e, C]
endpoint-backlog panels), does the argmax reduction on the VPU in one pass,
and never re-reads HBM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..platform import pallas_interpret


def _bp_route_kernel(qm_ref, ql_ref, cap_ref, cls_ref, rate_ref, dir_ref):
    qm = qm_ref[...].astype(jnp.float32)       # [be, C]
    ql = ql_ref[...].astype(jnp.float32)
    cap = cap_ref[...].astype(jnp.float32)     # [be]
    diff = qm - ql
    adiff = jnp.abs(diff)
    best = jnp.argmax(adiff, axis=1).astype(jnp.int32)          # [be]
    dmax = jnp.take_along_axis(diff, best[:, None], axis=1)[:, 0]
    cls_ref[...] = best
    rate_ref[...] = jnp.where(jnp.abs(dmax) > 0, cap, 0.0)
    dir_ref[...] = jnp.where(dmax > 0, 1, -1).astype(jnp.int32)


def bp_route_decide(qm: jax.Array, ql: jax.Array, cap: jax.Array, *,
                    block_e: int = 256):
    """qm/ql: [E, C] backlogs at the two endpoints of each link; cap: [E].

    Returns (best_class [E] i32, rate [E] f32, direction [E] i32 with +1 =
    m->l).  Links are padded to a block multiple.
    """
    E, C = qm.shape
    block_e = min(block_e, max(E, 1))
    pad = (-E) % block_e
    if pad:
        zf = lambda t: jnp.concatenate(
            [t, jnp.zeros((pad,) + t.shape[1:], t.dtype)], axis=0)
        qm, ql, cap = zf(qm), zf(ql), zf(cap)
    Ep = qm.shape[0]
    grid = (Ep // block_e,)

    cls, rate, dirn = pl.pallas_call(
        _bp_route_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_e, C), lambda i: (i, 0)),
            pl.BlockSpec((block_e, C), lambda i: (i, 0)),
            pl.BlockSpec((block_e,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((block_e,), lambda i: (i,)),
            pl.BlockSpec((block_e,), lambda i: (i,)),
            pl.BlockSpec((block_e,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Ep,), jnp.int32),
            jax.ShapeDtypeStruct((Ep,), jnp.float32),
            jax.ShapeDtypeStruct((Ep,), jnp.int32),
        ],
        interpret=pallas_interpret(),
    )(qm, ql, cap)
    return cls[:E], rate[:E], dirn[:E]
