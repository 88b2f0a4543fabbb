"""The paper's control policies as pure JAX slot-step functions.

Implemented policies (paper §III-IV):
  pi1    — single comp node, BP routing, combine all available pairs.
  pi1p   — pi1 with the proof-device computation threshold X̄ (Lemma 1).
  pi2    — pi1 + regulator/dummy randomization (overlapping networks, Thm 3).
  pi3    — multiple comp nodes: join-shortest-sum-of-queues load balancing
           (eq. 9), H_n virtual queues (eq. 10), BP routing over 3·N_C
           classes, all-possible computation, regulator randomization.
  pi3bar — pi3 without the regulator (the conjectured-optimal variant of §V).

Every step is `slot_step(sp, cfg, state, arrivals, key) -> (state, metrics)`
and is jit/scan/vmap friendly.  Constants from `StaticProblem` are closed
over as numpy arrays (become XLA constants).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.bp_slot.kernel import (comp_balance_decide,
                                          slot_route_decide)
from repro.kernels.bp_slot.ref import (balance_score, combine_amount,
                                       pair_count, slot_route_ref)

from .queues import NetState, StaticProblem
from .regulator import regulator_push


#: Policies that route computation output through the dummy-packet regulator.
#: ``pi2_reg``/``pi3_reg`` are the fleet-facing names for the regulated
#: variants: identical slot dynamics to pi2/pi3, but kept distinct so one
#: sweep can carry both a plain and an explicitly-regulated entry and the
#: report layer scores the ``_reg`` rows against the rho0-adjusted bound
#: lam*/(1+eps_B) (DESIGN.md §2).
REGULATED_POLICIES = ("pi2", "pi2_reg", "pi3", "pi3_reg")

#: Every implemented policy name.  `PolicyConfig` rejects anything else at
#: construction: the behavior flags below are exact-string membership
#: tests, so a typo ("pi3reg") would otherwise silently run unregulated
#: pi1-like dynamics and be scored against the wrong bound.
KNOWN_POLICIES = ("pi1", "pi1p", "pi2", "pi2_reg", "pi3", "pi3_reg",
                  "pi3bar")

#: Decision backends for the per-slot hot loop (DESIGN.md §7): "xla" runs
#: the pure-jnp oracle (`repro.kernels.bp_slot.ref`), "pallas" the fused
#: tiled kernels (`repro.kernels.bp_slot.kernel`) — bit-identical by
#: construction, selected via `PolicyConfig.backend`.
KNOWN_BACKENDS = ("xla", "pallas")


@dataclasses.dataclass(frozen=True)
class PolicyConfig:
    name: str = "pi3"            # pi1 | pi1p | pi2[_reg] | pi3[_reg] | pi3bar
    eps_b: float = 0.01          # regulator Bernoulli parameter
    pairing: str = "fifo"        # fifo | bound   (DESIGN.md §1)
    threshold: float = 0.0       # X̄ for the primed (proof-device) variants
    fixed_node: int = 0          # comp-node index used by pi1/pi1p/pi2
    wireless: bool = False       # §IV-C: node-exclusive interference; links
                                 # activated by greedy maximal matching
                                 # weighted by differential backlog [17,18]
    backend: str = "xla"         # "xla" | "pallas" — slot-decision kernels
                                 # (DESIGN.md §7); bit-identical outputs

    def __post_init__(self):
        if self.name not in KNOWN_POLICIES:
            raise ValueError(
                f"unknown policy {self.name!r}; known: {KNOWN_POLICIES}")
        if self.backend not in KNOWN_BACKENDS:
            raise ValueError(
                f"unknown backend {self.backend!r}; known: {KNOWN_BACKENDS}")

    @property
    def use_regulator(self) -> bool:
        return self.name in REGULATED_POLICIES

    @property
    def load_balance(self) -> bool:
        return self.name in ("pi3", "pi3_reg", "pi3bar")

    @property
    def thresholded(self) -> bool:
        return self.name == "pi1p"

    @property
    def rho0(self) -> float:
        """Output-rate inflation rho0 = 1 + eps_B (paper eq. (8), Thm 3/5).

        The operative throughput bound of a regulated policy is
        lam*/rho0; unregulated policies are bounded by lam* itself.
        """
        return 1.0 + self.eps_b if self.use_regulator else 1.0


# ---------------------------------------------------------------------------
# Backpressure routing (paper's BP box + constraint (1) conventions)
# ---------------------------------------------------------------------------

def greedy_maximal_matching(edges: jnp.ndarray, weights: jnp.ndarray,
                            n_nodes: int) -> jnp.ndarray:
    """Greedy maximal matching under the node-exclusive interference model
    (paper refs [17, 18]): visit links in decreasing weight order (ties by
    lower index), activate a link iff its weight is positive and neither
    endpoint is already busy.  Returns a [E] bool mask.

    Computed without a sort or a serial visit, as the unique fixpoint of:
    link e is on iff w_e > 0 and no link f that shares an endpoint with e
    and precedes it (w_f > w_e, or w_f == w_e and f < e) is on.  A link is
    on in the greedy visit exactly when that holds, since every link that
    precedes it has been decided by then.  Iterating the rule from "every
    positive link on" makes each link exact once every link that precedes
    it and shares an endpoint is, so a chain of k such links is exact
    after k rounds and the fixpoint comes within E rounds (E - 1 for a
    path whose weights fall along it).  `n_nodes` is not needed: conflicts
    are read off the endpoints themselves.
    """
    del n_nodes
    return _matching_fixpoint(edges, weights)[0]


def _matching_fixpoint(edges: jnp.ndarray, weights: jnp.ndarray
                       ) -> Tuple[jax.Array, jax.Array]:
    """`greedy_maximal_matching` and its rounds to the fixpoint (1..E).

    Dense over [E, E] link pairs: with static edges the conflict table
    folds to a constant; with traced per-lane edges (the fleet's
    `PaddedProblem`) it is E² compares a lane.  Padded (0, 0) self-loops
    conflict with the links at node 0, but weigh 0 and so are never on.
    """
    E = edges.shape[0]
    m, l = edges[:, 0], edges[:, 1]
    idx = jnp.arange(E)
    conflict = ((m[:, None] == m[None, :]) | (m[:, None] == l[None, :])
                | (l[:, None] == m[None, :]) | (l[:, None] == l[None, :]))
    conflict &= idx[:, None] != idx[None, :]
    w_e, w_f = weights[:, None], weights[None, :]
    precedes = (w_f > w_e) | ((w_f == w_e) & (idx[None, :] < idx[:, None]))
    blocks = conflict & precedes          # [e, f]: f, if on, keeps e off
    pos = weights > 0

    def rule(x):
        return pos & ~jnp.any(blocks & x[None, :], axis=-1)

    def unsettled(carry):
        _, changed, rounds = carry
        return changed & (rounds <= E)

    def again(carry):
        x, _, rounds = carry
        nx = rule(x)
        return nx, jnp.any(nx != x), rounds + 1

    x, _, rounds = jax.lax.while_loop(
        unsettled, again, (rule(pos), jnp.asarray(True), jnp.asarray(1)))
    # The last round only confirmed that nothing changed.
    return x, rounds - 1


def bp_route_slot(sp: StaticProblem, state: NetState,
                  wireless: bool = False, backend: str = "xla"
                  ) -> Tuple[NetState, Dict]:
    """One slot of max-differential-backlog routing over every link.

    Per undirected link, the class (i, n) maximizing |Q_m - Q_k| gets the full
    link rate R in the decreasing direction; fluid outflows from a queue are
    capped at its content and split proportionally across links (the paper's
    "zero packets" convention in expectation).

    wireless=True (paper §IV-C): links interfere node-exclusively; only a
    greedy maximal matching weighted by |differential backlog| transmits,
    and the metrics add `match_rounds`, the matching's rounds to its
    fixpoint (`greedy_maximal_matching`).

    backend="pallas" computes the (class, comp, direction) decision with the
    fused tiled kernel `repro.kernels.bp_slot.slot_route_decide` — the
    [E, 3*NC] differential tensor is streamed through VMEM instead of
    materialized — bit-identical to the "xla" oracle (DESIGN.md §7).
    """
    Q, Ddum, X = state.Q, state.Ddum, state.X
    m_idx = jnp.asarray(sp.edges[:, 0])
    l_idx = jnp.asarray(sp.edges[:, 1])
    cap = jnp.asarray(sp.edge_cap)
    NC = sp.n_comp

    Qf = Q.reshape(Q.shape[0], -1)                 # [N, 3*NC] (i-major)
    if backend == "pallas":
        best, dmax = slot_route_decide(Qf, m_idx, l_idx)
    else:
        best, dmax = slot_route_ref(Qf, m_idx, l_idx)
    best_i = best // NC
    best_n = best % NC

    alloc = cap * (jnp.abs(dmax) > 0)
    # A link that cannot carry traffic this slot — padded (edge_mask 0) or
    # with zero current capacity (event-model outage) — must not occupy a
    # matching slot in the wireless interference model either.
    weight = jnp.abs(dmax) * (cap > 0)
    if sp.edge_mask is not None:
        emask = jnp.asarray(sp.edge_mask, jnp.float32)
        alloc = alloc * emask
        weight = weight * emask
    metrics = {}
    if wireless:
        with jax.named_scope("matching"):
            active, metrics["match_rounds"] = _matching_fixpoint(
                jnp.asarray(sp.edges), weight)
        alloc = alloc * active
    src = jnp.where(dmax > 0, m_idx, l_idx)
    dst = jnp.where(dmax > 0, l_idx, m_idx)

    # Cap total outflow of each (node, class) at its queue content.
    total_out = jnp.zeros_like(Q).at[src, best_i, best_n].add(alloc)
    scale = jnp.where(total_out > Q, Q / jnp.maximum(total_out, 1e-20), 1.0)
    actual = alloc * scale[src, best_i, best_n]    # [E]

    # Dummy share of moved processed packets (proportional composition).
    q0_src = Q[src, 0, best_n]
    frac_dummy = jnp.where(q0_src > 0, Ddum[src, best_n] / jnp.maximum(q0_src, 1e-20), 0.0)
    moved_dummy = actual * frac_dummy * (best_i == 0)

    # Departures.
    Q = Q.at[src, best_i, best_n].add(-actual)
    Ddum = Ddum.at[src, best_n].add(-moved_dummy)

    # Arrivals: sinks absorb (raw -> X at its comp node; processed -> d).
    is_sink = jnp.asarray(sp.sink)[dst, best_i, best_n]          # [E]
    to_net = actual * (~is_sink)
    Q = Q.at[dst, best_i, best_n].add(to_net)
    Ddum = Ddum.at[dst, best_n].add(moved_dummy * (~is_sink))

    raw_sink = is_sink & (best_i >= 1)
    to_X = actual * raw_sink
    X = X.at[best_n, jnp.maximum(best_i - 1, 0)].add(to_X)
    cum_arr = state.cum_arr.at[best_n, jnp.maximum(best_i - 1, 0)].add(to_X)

    proc_sink = is_sink & (best_i == 0)
    dlv = jnp.sum(actual * proc_sink)
    dlv_useful = jnp.sum((actual - moved_dummy) * proc_sink)

    new = state._replace(Q=Q, Ddum=Ddum, X=X, cum_arr=cum_arr)
    new = new.credit_delivery(dlv, dlv_useful)
    return new, {"routed": jnp.sum(actual), **metrics}


# ---------------------------------------------------------------------------
# Pairing / computation (constraint (3) handling — DESIGN.md §1)
# ---------------------------------------------------------------------------

def _x_net(state: NetState, pairing: str) -> jax.Array | None:
    """Raw packets in flight (paper eq. (7)) — only the "bound" pairing
    model consumes it; None keeps the fifo path free of the [N] reduction."""
    if pairing != "bound":
        return None
    return state.Q[:, 1, :].sum(axis=0) + state.Q[:, 2, :].sum(axis=0)  # [NC]


def available_pairs(sp: StaticProblem, state: NetState, pairing: str) -> jax.Array:
    """P_n(t): pairs of same-tag raw packets present at each comp node.

    Delegates to `repro.kernels.bp_slot.ref.pair_count` — the same algebra
    the fused Pallas kernel evaluates in-tile (DESIGN.md §7)."""
    return pair_count(state.X[:, 0], state.X[:, 1],
                      state.cum_arr[:, 0], state.cum_arr[:, 1],
                      state.cum_comb, _x_net(state, pairing), pairing)


def _comp_balance_kernel_call(sp: StaticProblem, cfg: PolicyConfig,
                              state: NetState, eps: jax.Array):
    """Invoke the fused comp/balance Pallas kernel on this state snapshot.

    Returns (Z [NC], n_star []) — `load_balance_slot` consumes n_star (on
    the pre-route state) and `computation_slot` consumes Z (post-route);
    the fused kernel computes both in one tiled pass either way
    (DESIGN.md §7)."""
    comp = jnp.asarray(sp.comp_nodes)
    nidx = jnp.arange(sp.n_comp)
    mask = (jnp.ones((sp.n_comp,), state.Q.dtype) if sp.comp_mask is None
            else jnp.asarray(sp.comp_mask))
    x_net = _x_net(state, cfg.pairing)
    if x_net is None:
        x_net = jnp.zeros((sp.n_comp,), state.X.dtype)
    return comp_balance_decide(
        jnp.asarray(eps, state.Q.dtype),
        state.Q[comp, 0, nidx], state.Q[sp.s1, 1, :], state.Q[sp.s2, 2, :],
        state.H, jnp.asarray(sp.comp_caps), mask,
        state.X[:, 0], state.X[:, 1],
        state.cum_arr[:, 0], state.cum_arr[:, 1], state.cum_comb, x_net,
        pairing=cfg.pairing, thresholded=cfg.thresholded,
        threshold=cfg.threshold)


def _inject_processed(sp: StaticProblem, state: NetState, amount: jax.Array,
                      dummy: jax.Array) -> NetState:
    """Push per-comp-node processed packets into Q_n^{(0,n)} (or deliver if n==d)."""
    comp = jnp.asarray(sp.comp_nodes)
    at_dest = comp == sp.dest                          # [NC]
    to_net = amount * (~at_dest)
    nidx = jnp.arange(sp.n_comp)
    Q = state.Q.at[comp, 0, nidx].add(to_net)
    Ddum = state.Ddum.at[comp, nidx].add(dummy * (~at_dest))
    dlv = jnp.sum(amount * at_dest)
    dlv_useful = jnp.sum((amount - dummy) * at_dest)
    return state._replace(Q=Q, Ddum=Ddum).credit_delivery(dlv, dlv_useful)


def computation_slot(sp: StaticProblem, cfg: PolicyConfig, state: NetState,
                     assigned: jax.Array, key: jax.Array,
                     eps_b: jax.Array | None = None) -> Tuple[NetState, Dict]:
    """Combine pairs at every computation node; route output via the
    regulator (pi2/pi3 and their ``_reg`` aliases) or directly (pi1/pi3bar).

    `eps_b` optionally overrides `cfg.eps_b` with a *traced* value: the fleet
    engine passes it per job so sweeping the regulator parameter does not
    fork compiled programs (only `cfg.use_regulator` changes control flow).
    """
    if cfg.backend == "pallas":
        # Fused pairs + threshold + combine (the argmin half of the kernel's
        # output is the load-balance side; unused on this snapshot).
        eps = cfg.eps_b if eps_b is None else eps_b
        Z, _ = _comp_balance_kernel_call(sp, cfg, state, eps)
    else:
        caps = jnp.asarray(sp.comp_caps)
        if sp.comp_mask is not None:
            caps = caps * jnp.asarray(sp.comp_mask, jnp.float32)
        P = available_pairs(sp, state, cfg.pairing)
        # pi1' (thresholded): combine C_n only when X1+X2 >= 2 C_n + X̄
        # (still physically capped by the pairs actually present).
        Z = combine_amount(P, caps, state.X.sum(axis=1), cfg.thresholded,
                           cfg.threshold)
    # (masked comp nodes have caps forced to 0 above, so Z == 0 there: P is
    # clipped non-negative in available_pairs)

    X = state.X - Z[:, None]
    cum_comb = state.cum_comb + Z
    state = state._replace(X=X, cum_comb=cum_comb)

    if cfg.use_regulator:
        eps = cfg.eps_b if eps_b is None else eps_b
        Y = state.Y + Z
        Y, F, dummy = regulator_push(Y, assigned, key, eps)
        state = state._replace(Y=Y)
        state = _inject_processed(sp, state, F, dummy)
    else:
        zeros = jnp.zeros_like(Z)
        state = _inject_processed(sp, state, Z, zeros)
    return state, {"computed": jnp.sum(Z)}


# ---------------------------------------------------------------------------
# Load balancing (eq. 9/10) and arrival injection
# ---------------------------------------------------------------------------

def load_balance_slot(sp: StaticProblem, cfg: PolicyConfig, state: NetState,
                      arrivals: jax.Array,
                      eps_b: jax.Array | None = None
                      ) -> Tuple[NetState, jax.Array, Dict]:
    """Assign this slot's A(t) queries to a computation node and inject the
    corresponding raw packets at the sources.  `eps_b` optionally overrides
    `cfg.eps_b` with a traced per-job value (see `computation_slot`)."""
    if cfg.load_balance:
        eps = cfg.eps_b if eps_b is None else eps_b
        if cfg.backend == "pallas":
            # Fused kernel on the pre-route snapshot; its Z half is unused
            # here — computation_slot re-invokes it post-route.
            _, n_star = _comp_balance_kernel_call(sp, cfg, state, eps)
        else:
            score = balance_score(                                 # eq. (9)
                eps,
                state.Q[jnp.asarray(sp.comp_nodes), 0,
                        jnp.arange(sp.n_comp)],
                state.Q[sp.s1, 1, :], state.Q[sp.s2, 2, :], state.H,
                # Masked-out (padded/failed) comp nodes never win the argmin.
                None if sp.comp_mask is None else jnp.asarray(sp.comp_mask))
            n_star = jnp.argmin(score)
    else:
        n_star = jnp.asarray(cfg.fixed_node, dtype=jnp.int32)

    assigned = jnp.zeros(sp.n_comp).at[n_star].set(arrivals)       # eq. (10)

    # Inject raw packets; a source that *is* the chosen comp node feeds X
    # directly (the sink convention).
    comp = jnp.asarray(sp.comp_nodes)
    caps = jnp.asarray(sp.comp_caps)
    Q, X, cum_arr = state.Q, state.X, state.cum_arr
    for i, s in ((0, sp.s1), (1, sp.s2)):
        direct = comp[n_star] == s
        Q = Q.at[s, i + 1, n_star].add(jnp.where(direct, 0.0, arrivals))
        X = X.at[n_star, i].add(jnp.where(direct, arrivals, 0.0))
        cum_arr = cum_arr.at[n_star, i].add(jnp.where(direct, arrivals, 0.0))

    H = jnp.maximum(state.H + assigned - caps, 0.0)                # H_n update
    state = state._replace(Q=Q, X=X, cum_arr=cum_arr, H=H)
    return state, assigned, {"n_star": n_star}


# ---------------------------------------------------------------------------
# Full slot step
# ---------------------------------------------------------------------------

def slot_step(sp: StaticProblem, cfg: PolicyConfig, state: NetState,
              arrivals: jax.Array, key: jax.Array,
              eps_b: jax.Array | None = None) -> Tuple[NetState, Dict]:
    """One slot: (i) admit+load-balance, (ii) BP routing, (iii) computation
    (+ regulator push).  `eps_b=None` uses the static `cfg.eps_b`; a traced
    array makes the regulator parameter per-job data (fleet sweeps).

    `cfg.backend` selects the decision implementation — "xla" (the pure-jnp
    oracle in `repro.kernels.bp_slot.ref`) or "pallas" (the fused tiled
    kernels, bit-identical; DESIGN.md §7).  Each sub-step runs under its
    named scope (`repro.obs.spans.SLOT_SCOPES`), which names its ops in a
    device trace and changes nothing else."""
    with jax.named_scope("balance"):
        state, assigned, m1 = load_balance_slot(sp, cfg, state, arrivals,
                                                eps_b)
    with jax.named_scope("route"):
        state, m2 = bp_route_slot(sp, state, wireless=cfg.wireless,
                                  backend=cfg.backend)
    with jax.named_scope("compute"):
        state, m3 = computation_slot(sp, cfg, state, assigned, key, eps_b)
    metrics = {
        "total_queue": state.total_queue(),
        "delivered": state.delivered,
        "delivered_useful": state.delivered_useful,
        **m1, **m2, **m3,
    }
    return state, metrics
