"""Fused Pallas kernels for the per-slot control decision (DESIGN.md §7).

Two kernels cover the paper's whole inner loop:

  * `slot_route_decide` — max-differential-backlog routing.  The grid is
    (edge blocks, class blocks); each step loads a [N, block_c] panel of
    the flattened per-node backlogs plus a [block_e, 1] slab of each edge
    endpoint, gathers the endpoint rows as a one-hot [block_e, N] x
    [N, block_c] MXU product at HIGHEST precision (exact: every output is
    1*x plus zeros), and folds the tile's best |Q_m - Q_l| into a running
    argmax held in the output refs — the [E, 3*NC] differential tensor of
    the XLA path is never materialized.

  * `comp_balance_decide` — the per-comp-node decision: available pairs,
    the (optionally thresholded) combine amount Z, and the masked
    join-shortest-sum-of-queues argmin, fused into one pass over NC tiles
    of a single stacked [12, NC] panel with a running argmin.  eps_B enters
    as a traced [1, 1] operand (per-job data under vmap — an eps_B sweep
    shares one kernel).

Every block is 2-D, so under the engine's vmap the lane axis becomes a
leading grid axis and the last two block dims still satisfy the TPU's
(8, 128) rule (a full array dim, or a multiple of the tile).

Tie-break contract: later tiles only win on a *strictly* better value, and
the in-tile argmax/argmin take the first occurrence (the lowest index that
attains the tile's max/min) — so both kernels resolve ties to the lowest
flat index, exactly like `jnp.argmax`/`argmin` (the `ref.py` oracle).
The mode follows the platform (`repro.kernels.platform`): compiled on a
TPU, the Pallas interpreter inside the jitted program on the CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..platform import pallas_interpret
from .ref import balance_score, combine_amount, pair_count

_BIG = jnp.iinfo(jnp.int32).max


def _first_arg(values, target):
    """Lowest lane index (axis 1) where ``values == target``: the
    first-occurrence argmax/argmin without a dynamic gather."""
    lane = jax.lax.broadcasted_iota(jnp.int32, values.shape, 1)
    return jnp.min(jnp.where(values == target, lane, _BIG), axis=1,
                   keepdims=True)


def _route_kernel(q_ref, m_ref, l_ref, best_ref, dmax_ref, *, block_c: int):
    j = pl.program_id(1)
    q = q_ref[...]                                  # [N, block_c]
    node = jax.lax.broadcasted_iota(jnp.int32, (m_ref.shape[0], q.shape[0]),
                                    1)

    def gather(idx_ref):                            # one-hot MXU row gather
        onehot = (idx_ref[...] == node).astype(q.dtype)        # [be, N]
        return jnp.dot(onehot, q, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=q.dtype)

    diff = gather(m_ref) - gather(l_ref)            # [be, block_c]
    adiff = jnp.abs(diff)
    loc = _first_arg(adiff, jnp.max(adiff, axis=1, keepdims=True))
    lane = jax.lax.broadcasted_iota(jnp.int32, diff.shape, 1)
    dloc = jnp.max(jnp.where(lane == loc, diff, -jnp.inf), axis=1,
                   keepdims=True)                   # diff at loc, [be, 1]
    glob = loc + j * block_c

    @pl.when(j == 0)
    def _init():
        best_ref[...] = glob
        dmax_ref[...] = dloc

    @pl.when(j > 0)
    def _fold():
        # strictly-better only: ties keep the earlier (lower) class index
        better = jnp.abs(dloc) > jnp.abs(dmax_ref[...])
        best_ref[...] = jnp.where(better, glob, best_ref[...])
        dmax_ref[...] = jnp.where(better, dloc, dmax_ref[...])


def slot_route_decide(Qf: jax.Array, m_idx: jax.Array, l_idx: jax.Array, *,
                      block_e: int = 128, block_c: int | None = None):
    """Qf: [N, C] flattened per-node class backlogs (C = 3*NC, i-major);
    m_idx/l_idx: [E] int32 endpoints.  Returns (best [E] i32 flat class
    index, dmax [E] signed differential) == `ref.slot_route_ref` bit-exact.

    Edges pad to a block multiple with (0, 0) self-loops (zero diff, never
    win); classes pad with zero columns (|0| never beats a real diff
    strictly, and an all-zero row correctly keeps flat index 0).  With the
    defaults an edge list of at most 128 and the whole class axis are one
    block each (full array dims); longer edge lists tile by 128.
    """
    N, C = Qf.shape
    E = m_idx.shape[0]
    block_e = min(block_e, max(E, 1))
    block_c = C if block_c is None else min(block_c, C)

    m_idx = m_idx.astype(jnp.int32)
    l_idx = l_idx.astype(jnp.int32)
    pad_e = (-E) % block_e
    if pad_e:
        zi = jnp.zeros((pad_e,), jnp.int32)
        m_idx = jnp.concatenate([m_idx, zi])
        l_idx = jnp.concatenate([l_idx, zi])
    pad_c = (-C) % block_c
    if pad_c:
        Qf = jnp.concatenate(
            [Qf, jnp.zeros((N, pad_c), Qf.dtype)], axis=1)
    Ep, Cp = m_idx.shape[0], Qf.shape[1]
    grid = (Ep // block_e, Cp // block_c)

    edge = pl.BlockSpec((block_e, 1), lambda i, j: (i, 0))
    best, dmax = pl.pallas_call(
        functools.partial(_route_kernel, block_c=block_c),
        grid=grid,
        in_specs=[pl.BlockSpec((N, block_c), lambda i, j: (0, j)),
                  edge, edge],
        out_specs=[edge, edge],
        out_shape=[
            jax.ShapeDtypeStruct((Ep, 1), jnp.int32),
            jax.ShapeDtypeStruct((Ep, 1), Qf.dtype),
        ],
        interpret=pallas_interpret(),
    )(Qf, m_idx[:, None], l_idx[:, None])
    return best[:E, 0], dmax[:E, 0]


#: Row order of the stacked comp/balance panel.
_PANELS = ("q0", "q1", "q2", "H", "caps", "mask", "x1", "x2", "ca1", "ca2",
           "cc", "x_net")


def _comp_balance_kernel(eps_ref, p_ref, z_ref, nstar_ref, smin_ref, *,
                         block_n: int, pairing: str, thresholded: bool,
                         threshold: float):
    i = pl.program_id(0)
    eps = eps_ref[...]                              # [1, 1]
    p = p_ref[...]                                  # [12, block_n]
    (q0, q1, q2, H, caps, mask, x1, x2, ca1, ca2, cc,
     x_net) = (p[r:r + 1] for r in range(len(_PANELS)))
    capm = caps * mask
    P = pair_count(x1, x2, ca1, ca2, cc, x_net, pairing)
    z_ref[...] = combine_amount(P, capm, x1 + x2, thresholded, threshold)
    score = balance_score(eps, q0, q1, q2, H, mask)  # [1, block_n]
    sloc = jnp.min(score, axis=1, keepdims=True)     # [1, 1]
    glob = _first_arg(score, sloc) + i * block_n

    @pl.when(i == 0)
    def _init():
        nstar_ref[...] = glob
        smin_ref[...] = sloc

    @pl.when(i > 0)
    def _fold():
        better = sloc < smin_ref[...]               # strict: first tile wins ties
        nstar_ref[...] = jnp.where(better, glob, nstar_ref[...])
        smin_ref[...] = jnp.where(better, sloc, smin_ref[...])


def comp_balance_decide(eps, q0, q1, q2, H, caps, mask, x1, x2, ca1, ca2,
                        cc, x_net, *, pairing: str = "fifo",
                        thresholded: bool = False, threshold: float = 0.0,
                        block_n: int = 128):
    """Fused comp/balance decision over [NC] panels (ref.comp_balance_ref
    bit-exact): returns (Z [NC] f32, n_star [] i32).

    The twelve panels ride one stacked [12, NC] operand (one DMA, one
    block).  `eps` is a traced scalar (per-job data under vmap).  NC pads
    to a block multiple with mask-0 slots: their score is +inf (never win
    the strict-< fold) and their Z is 0 (sliced off anyway).
    """
    NC = q0.shape[0]
    block_n = min(block_n, max(NC, 1))
    panels = jnp.stack([q0, q1, q2, H, caps, mask, x1, x2, ca1, ca2, cc,
                        x_net]).astype(q0.dtype)
    pad = (-NC) % block_n
    if pad:
        panels = jnp.concatenate(
            [panels, jnp.zeros((len(_PANELS), pad), panels.dtype)], axis=1)
    NCp = panels.shape[1]

    one = pl.BlockSpec((1, 1), lambda i: (0, 0))
    Z, n_star, _ = pl.pallas_call(
        functools.partial(_comp_balance_kernel, block_n=block_n,
                          pairing=pairing, thresholded=thresholded,
                          threshold=threshold),
        grid=(NCp // block_n,),
        in_specs=[one,
                  pl.BlockSpec((len(_PANELS), block_n), lambda i: (0, i))],
        out_specs=[pl.BlockSpec((1, block_n), lambda i: (0, i)), one, one],
        out_shape=[
            jax.ShapeDtypeStruct((1, NCp), q0.dtype),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), q0.dtype),
        ],
        interpret=pallas_interpret(),
    )(jnp.reshape(eps, (1, 1)).astype(q0.dtype), panels)
    return Z[0, :NC], n_star[0, 0]
