"""Pure-jnp oracle for the fused per-slot decision (bp_slot kernel family).

This module IS the XLA backend: `repro.core.policies` calls these functions
on the `backend="xla"` path, and the Pallas kernels in `kernel.py` reuse the
small algebra helpers (`pair_count`, `combine_amount`, `balance_score`)
inside their kernel bodies — parity between the two backends is therefore
*by construction*: identical f32 expressions evaluated on identical panels,
so `slot_step(backend="pallas")` is bit-identical to
`backend="xla"` (asserted by tests/test_bp_slot.py).

Tie-break contract (DESIGN.md §7): both the routing argmax and the
load-balance argmin resolve ties to the *lowest flat index*, exactly like
`jnp.argmax`/`jnp.argmin` — the tiled kernels preserve this by only
accepting a strictly better candidate from a later tile.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Shared algebra (used verbatim inside the Pallas kernel bodies)
# ---------------------------------------------------------------------------

def pair_count(x1, x2, ca1, ca2, cc, x_net, pairing: str):
    """P_n(t): combinable same-tag pairs at each comp node (paper eq. (7) /
    FIFO counting — DESIGN.md §1), from per-node panels.

    x1/x2: X[:, 0] / X[:, 1]; ca1/ca2: cum_arr[:, 0] / cum_arr[:, 1];
    cc: cum_comb; x_net: raw packets in flight (bound pairing only, may be
    None for fifo).
    """
    if pairing == "fifo":
        P = jnp.minimum(ca1, ca2) - cc
    elif pairing == "bound":
        P = (x1 + x2 - x_net) / 2.0
    else:
        raise ValueError(f"unknown pairing model {pairing!r}")
    # Physical caps: cannot exceed either side's backlog, never negative.
    return jnp.clip(P, 0.0, jnp.minimum(x1, x2))


def combine_amount(P, caps, xsum, thresholded: bool, threshold: float):
    """Z_n(t): pairs actually combined — capped by capacity, optionally
    gated by the pi1' proof-device threshold X̄ (Lemma 1)."""
    if thresholded:
        gate = xsum >= 2.0 * caps + threshold
        return jnp.minimum(jnp.where(gate, caps, 0.0), P)
    return jnp.minimum(P, caps)


def balance_score(eps, q0, q1, q2, H, mask):
    """Join-shortest-sum-of-queues score (paper eq. (9)), +inf on masked
    (padded / failed) comp nodes so they never win the argmin."""
    score = (1.0 + eps) * q0 + q1 + q2 + H
    if mask is None:
        return score
    return jnp.where(mask > 0, score, jnp.inf)


# ---------------------------------------------------------------------------
# Full-decision oracles (the parity reference for the Pallas kernels)
# ---------------------------------------------------------------------------

def slot_route_ref(Qf: jax.Array, m_idx: jax.Array, l_idx: jax.Array):
    """BP routing decision over the flattened class axis.

    Qf: [N, 3*NC] per-node backlogs with classes flattened in (i, n) order
    (i major — `Q.reshape(N, -1)`); m_idx/l_idx: [E] endpoint indices.
    Returns (best [E] i32 flat class index, dmax [E] signed differential).
    Materializes the full [E, 3*NC] differential — the tensor the Pallas
    kernel streams in tiles instead.
    """
    diff = Qf[m_idx] - Qf[l_idx]
    best = jnp.argmax(jnp.abs(diff), axis=1).astype(jnp.int32)
    dmax = jnp.take_along_axis(diff, best[:, None], axis=1)[:, 0]
    return best, dmax


def comp_balance_ref(eps, q0, q1, q2, H, caps, mask, x1, x2, ca1, ca2, cc,
                     x_net, *, pairing: str, thresholded: bool,
                     threshold: float):
    """Fused per-comp-node decision: combinable pairs -> combine amount Z,
    plus the masked load-balance argmin n_star, from one set of panels.

    All inputs are [NC] panels except the scalar `eps`.  Returns
    (Z [NC] f32, n_star [] i32).
    """
    capm = caps * mask
    P = pair_count(x1, x2, ca1, ca2, cc, x_net, pairing)
    Z = combine_amount(P, capm, x1 + x2, thresholded, threshold)
    score = balance_score(eps, q0, q1, q2, H, mask)
    return Z, jnp.argmin(score).astype(jnp.int32)
