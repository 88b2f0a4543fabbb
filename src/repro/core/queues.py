"""Queue state for the backpressure network-computation system (paper §II-C).

All quantities are JAX arrays so the whole network steps inside `lax.scan`.
Class index convention: i=0 processed, i=1 raw from s1, i=2 raw from s2.
Queues are *fluid* (float) — see DESIGN.md §1.

State components (paper notation):
  Q[k, i, n]   : data queue at node k, class (i, n)    (Q_k^{(i,n)})
  Ddum[k, n]   : dummy-packet content of Q[k, 0, n]    (regulator tracking)
  X[n, i]      : raw packets of source i+1 at computation node n (X_n^{(i)})
  Y[n]         : regulator queue of computed results   (Y_n)
  H[n]         : virtual admission queue               (H_n)
  cum_arr[n,i] : cumulative raw arrivals into X[n, i]  (for FIFO pairing)
  cum_comb[n]  : cumulative pairs combined at n
  delivered / delivered_useful : cumulative processed packets at d

The delivery counters are *compensated* (Kahan) float32 sums: `delivered`
carries the running total and `delivered_c` the rounding residue, so
per-slot increments survive far past the naive float32 saturation point
(~2^24 ≈ 1.7e7 packets, where `big + 1.0 == big`).  Read them through
`state.delivered`; update them only through `state.credit_delivery`
(DESIGN.md §4).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .graph import ComputeProblem


def kahan_add(s: jax.Array, c: jax.Array, x: jax.Array):
    """One compensated-summation step: returns (new_sum, new_compensation).

    Keeps float32 running sums exact to ~1 ulp of the *increments* instead
    of 1 ulp of the total — the difference between losing every packet past
    ~10^7 delivered and losing none (ROADMAP numerics note)."""
    y = x - c
    t = s + y
    return t, (t - s) - y


# ---------------------------------------------------------------------------
# Streaming stability verdict: windowed backlog-drift accumulators
# (DESIGN.md §8).  Pure scalar state + one update per slot, so the whole
# verdict machinery rides the fleet engine's donated scan carry.
# ---------------------------------------------------------------------------

VERDICT_UNDECIDED, VERDICT_STABLE, VERDICT_UNSTABLE = 0, 1, 2
VERDICT_NAMES = ("UNDECIDED", "STABLE", "UNSTABLE")


class DriftStats(NamedTuple):
    """Per-sim drift statistics for the streaming stability verdict.

    ``q_mark``/``useful_mark`` anchor the backlog and delivery counters at
    the end of the burn-in; every verdict-window boundary after it scores
    the *anchored* per-slot backlog slope ``(Q(t) - q_mark)/(t - anchor)``
    (a Lyapunov drift estimate whose noise shrinks as the horizon grows)
    and the anchored useful delivery rate against the offered rate.
    Consecutive boundaries of agreeing evidence latch ``verdict`` to
    STABLE/UNSTABLE at ``decided_at`` (DESIGN.md §8).  All fields are
    scalars — the accumulator is O(1) per sim.
    """

    q_mark: jax.Array        # [] total backlog at the burn-in anchor
    useful_mark: jax.Array   # [] delivered_useful at the burn-in anchor
    last_drift: jax.Array    # [] anchored per-slot drift at the last boundary
    last_rate: jax.Array     # [] anchored useful rate at the last boundary
    stable_run: jax.Array    # [] int32: consecutive stable-evidence windows
    unstable_run: jax.Array  # [] int32: consecutive unstable-evidence windows
    verdict: jax.Array       # [] int32: VERDICT_UNDECIDED/STABLE/UNSTABLE
    decided_at: jax.Array    # [] int32: slot count at which verdict latched

    @staticmethod
    def zero() -> "DriftStats":
        z = jnp.zeros((), jnp.float32)
        zi = jnp.zeros((), jnp.int32)
        return DriftStats(z, z, z, z, zi, zi, zi, zi)


def drift_verdict_update(d: DriftStats, t: jax.Array, total_q: jax.Array,
                         delivered_useful: jax.Array, lam: jax.Array, *,
                         window: int, burn_in: int, k_stable: int,
                         k_unstable: int, drift_tol: float,
                         gap_tol: float) -> DriftStats:
    """One slot of the streaming stability verdict (DESIGN.md §8).

    Called with the *post-slot* backlog and cumulative useful deliveries of
    slot ``t``.  The burn-in end (``t + 1 == burn_in``) anchors the
    counters, discarding the fill-up transient; at every later window
    boundary (``(t+1) % window == 0``) two tests are scored against the
    offered rate ``lam``, both with thresholds scaled by ``max(lam, 1)``
    so one tolerance spans the rate sweep:

      * Lyapunov-style drift test — the anchored per-slot backlog slope
        ``drift_a`` at most ``drift_tol`` (stable evidence) / at least it
        (unstable evidence);
      * delivered-vs-offered gap check — ``lam - rate_a`` within
        ``gap_tol`` of zero (stable) / at least the full ``gap_tol``
        (unstable: a genuinely diverging queue loses throughput *and*
        grows, so rates just above capacity — drift without much gap —
        stay UNDECIDED).

    ``k_stable``/``k_unstable`` consecutive boundaries of agreeing
    evidence latch the verdict; a latched verdict never changes
    (``decide`` requires ``verdict == UNDECIDED``), which is what makes
    per-sim freezing safe.
    """
    boundary = (t + 1) % window == 0
    anchor = (t + 1) == burn_in
    # Evidence only counts once the anchored horizon spans >= 2 windows:
    # the first post-anchor boundary estimates the slope from `window`
    # slots, where one unlucky anchor instant dominates the statistic.
    counted = boundary & (t + 1 >= burn_in + 2 * window)
    scale = jnp.maximum(lam, 1.0)
    elapsed = jnp.maximum((t + 1 - burn_in).astype(jnp.float32), 1.0)
    drift_a = (total_q - d.q_mark) / elapsed
    rate_a = (delivered_useful - d.useful_mark) / elapsed
    gap_a = lam - rate_a
    stable_ev = (drift_a <= drift_tol * scale) & (gap_a <= gap_tol * scale)
    # Instability must clear a *wider* bar than stability loses: 2x the
    # drift tolerance and the full gap tolerance, so boundary noise that
    # merely breaks a stable streak cannot latch UNSTABLE — the region
    # in between stays UNDECIDED (conservative for the frontier search).
    unstable_ev = (drift_a >= 2.0 * drift_tol * scale) & \
        (gap_a >= gap_tol * scale)
    s_run = jnp.where(counted,
                      jnp.where(stable_ev, d.stable_run + 1, 0),
                      d.stable_run)
    u_run = jnp.where(counted,
                      jnp.where(unstable_ev, d.unstable_run + 1, 0),
                      d.unstable_run)
    newly = jnp.where(s_run >= k_stable, VERDICT_STABLE,
                      jnp.where(u_run >= k_unstable, VERDICT_UNSTABLE,
                                VERDICT_UNDECIDED)).astype(jnp.int32)
    decide = counted & (d.verdict == VERDICT_UNDECIDED) & \
        (newly != VERDICT_UNDECIDED)
    return DriftStats(
        q_mark=jnp.where(anchor, total_q, d.q_mark),
        useful_mark=jnp.where(anchor, delivered_useful, d.useful_mark),
        last_drift=jnp.where(counted, drift_a, d.last_drift),
        last_rate=jnp.where(counted, rate_a, d.last_rate),
        stable_run=s_run, unstable_run=u_run,
        verdict=jnp.where(decide, newly, d.verdict),
        decided_at=jnp.where(decide, (t + 1).astype(jnp.int32),
                             d.decided_at),
    )


class NetState(NamedTuple):
    Q: jax.Array            # [N, 3, NC]
    Ddum: jax.Array         # [N, NC]
    X: jax.Array            # [NC, 2]
    Y: jax.Array            # [NC]
    H: jax.Array            # [NC]
    cum_arr: jax.Array      # [NC, 2]
    cum_comb: jax.Array     # [NC]
    delivered: jax.Array    # [] total processed packets (incl. dummies) at d
    delivered_useful: jax.Array  # []
    delivered_c: jax.Array       # [] Kahan compensation for `delivered`
    delivered_useful_c: jax.Array  # [] ... and for `delivered_useful`

    def total_queue(self) -> jax.Array:
        """Total backlog tracked for stability (paper §II-D)."""
        return (self.Q.sum() + self.X.sum() + self.Y.sum())

    def credit_delivery(self, dlv: jax.Array,
                        dlv_useful: jax.Array) -> "NetState":
        """Compensated update of the cumulative delivery counters."""
        d, dc = kahan_add(self.delivered, self.delivered_c, dlv)
        du, duc = kahan_add(self.delivered_useful, self.delivered_useful_c,
                            dlv_useful)
        return self._replace(delivered=d, delivered_c=dc,
                             delivered_useful=du, delivered_useful_c=duc)


@dataclasses.dataclass(frozen=True)
class StaticProblem:
    """Device-ready constant arrays describing a ComputeProblem.

    `edge_mask` / `comp_mask` support *padded* instances (fleet batching,
    DESIGN: src/repro/fleet/batching.py): entries with mask 0.0 are inert —
    masked edges carry no traffic and masked computation nodes are never
    selected by load balancing and never combine pairs.  `None` (the seed
    default) means every edge/comp node is active.
    """

    n_nodes: int
    n_comp: int
    edges: np.ndarray          # [E,2] int32
    edge_cap: np.ndarray       # [E] float32
    s1: int
    s2: int
    dest: int
    comp_nodes: np.ndarray     # [NC] int32
    comp_caps: np.ndarray      # [NC] float32
    # sink mask: sink[k, i, n] == True when Q_k^{(i,n)} is 0 by convention
    sink: np.ndarray           # [N, 3, NC] bool
    edge_mask: np.ndarray | None = None   # [E] float32, 1.0 = active
    comp_mask: np.ndarray | None = None   # [NC] float32, 1.0 = active

    @property
    def max_degree(self) -> int:
        """The most links that meet at one node: the places the route's
        apply half gives each node (`policies._apply`)."""
        return max_node_degree(np.asarray(self.edges))

    @property
    def edge_places(self) -> np.ndarray:
        """[E, 2]: each link's place at its m end and at its l end
        (`link_places`)."""
        return link_places(np.asarray(self.edges))

    @staticmethod
    def build(problem: ComputeProblem) -> "StaticProblem":
        N = problem.graph.n_nodes
        NC = problem.n_comp
        sink = np.zeros((N, 3, NC), dtype=bool)
        for j, n in enumerate(problem.comp_nodes):
            sink[n, 1, j] = True          # raw packets terminate at their comp node
            sink[n, 2, j] = True
            sink[problem.dest, 0, j] = True   # processed packets terminate at d
        return StaticProblem(
            n_nodes=N,
            n_comp=NC,
            edges=problem.graph.edges.astype(np.int32),
            edge_cap=problem.graph.capacity.astype(np.float32),
            s1=problem.s1,
            s2=problem.s2,
            dest=problem.dest,
            comp_nodes=np.asarray(problem.comp_nodes, dtype=np.int32),
            comp_caps=np.asarray(problem.comp_caps, dtype=np.float32),
            sink=sink,
        )


def link_places(edges: np.ndarray) -> np.ndarray:
    """[E, 2] int32: each link's place, in link order, among the links
    that meet its m end, and among those that meet its l end.  The links
    at one node hold the places 0, 1, ... (a self-loop one place)."""
    seen = np.zeros(int(edges.max(initial=0)) + 1, np.int32)
    places = np.zeros(edges.shape, np.int32)
    for e, (m, l) in enumerate(edges):
        places[e] = seen[m], seen[l]
        seen[m] += 1
        if l != m:
            seen[l] += 1
    return places


def max_node_degree(edges: np.ndarray) -> int:
    """The most links of `edges` that meet at one node, at least 1."""
    return int(link_places(edges).max(initial=0)) + 1


def init_state(sp: StaticProblem) -> NetState:
    N, NC = sp.n_nodes, sp.n_comp
    z = jnp.zeros
    return NetState(
        Q=z((N, 3, NC), jnp.float32),
        Ddum=z((N, NC), jnp.float32),
        X=z((NC, 2), jnp.float32),
        Y=z((NC,), jnp.float32),
        H=z((NC,), jnp.float32),
        cum_arr=z((NC, 2), jnp.float32),
        cum_comb=z((NC,), jnp.float32),
        delivered=jnp.zeros((), jnp.float32),
        delivered_useful=jnp.zeros((), jnp.float32),
        delivered_c=jnp.zeros((), jnp.float32),
        delivered_useful_c=jnp.zeros((), jnp.float32),
    )
