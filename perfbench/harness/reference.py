"""Plain reference of the network-computation semantics, for `correct`.

Written from the paper (arXiv:1601.03876 §III-IV) and the deployment file
alone: it imports nothing of the program under test and takes nothing the
program made.  One lane is a dense, straightforward `jax.numpy` transcription
of one slot:

  1. admission and load balancing: the slot's Poisson(λ) queries go to the
     computation node that minimises (1+ε)·Q_n^(0,n) + Q_s1^(1,n) +
     Q_s2^(2,n) + H_n (eq. 9, ties to the lowest index), raw packets enter
     at the two sources, and H_n grows by the assignment less C_n (eq. 10);
  2. backpressure routing: every link serves the class of largest absolute
     differential backlog (ties to the lowest (i, n) index, i major) at its
     full rate R toward the smaller queue; outflow of a queue is capped at
     its content, split in proportion; under node-exclusive interference
     only a greedy maximal matching of links, visited by decreasing weight,
     transmits;
  3. computation: FIFO pairs of same-tag raw packets combine up to C_n; with
     the regulator (π3) results leave as (1+Bernoulli(ε_B))·Ã packets,
     dummies filling any shortfall (eq. 8); without it (π3̄) the combined
     results leave directly.

The random draws are the traffic's own law: per slot,
``fold_in(PRNGKey(seed), t)`` split in three (arrivals, events, regulator),
Poisson arrivals drawn as a length-1 vector.  Metrics are computed on the
host in float64 from per-slot outputs.  The control runs the same dynamics
with the state held in bfloat16.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Iterator, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

UNDECIDED, STABLE, UNSTABLE = 0, 1, 2

#: Policies whose results leave through the dummy-packet regulator.
REGULATED = ("pi2", "pi3", "pi2_reg", "pi3_reg")


# ---------------------------------------------------------------------------
# Deployment
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Deployment:
    """One network as a configuration file states it."""

    n_nodes: int
    edges: Tuple[Tuple[int, int], ...]
    link_capacity: Tuple[float, ...]
    s1: int
    s2: int
    sink: int
    comp_nodes: Tuple[int, ...]
    comp_capacity: Tuple[float, ...]
    wireless: bool

    @staticmethod
    def from_config(cfg: dict) -> "Deployment":
        edges = tuple((int(a), int(b)) for a, b in cfg["edges"])
        caps = cfg["link_capacity"]
        if not isinstance(caps, list):
            caps = [caps] * len(edges)
        comp = tuple(int(n) for n in cfg["comp_nodes"])
        ccap = cfg["comp_capacity"]
        if not isinstance(ccap, list):
            ccap = [ccap] * len(comp)
        return Deployment(
            n_nodes=int(cfg["n_nodes"]), edges=edges,
            link_capacity=tuple(float(c) for c in caps),
            s1=int(cfg["sources"][0]), s2=int(cfg["sources"][1]),
            sink=int(cfg["sink"]), comp_nodes=comp,
            comp_capacity=tuple(float(c) for c in ccap),
            wireless=cfg["interference"] == "node_exclusive")


def lp_capacity(dep: Deployment, rho0: float = 1.0) -> float:
    """λ* of Theorem 4: the largest Σ_n λ_n that a multicommodity flow of
    raw data (s1→n, s2→n at λ_n) and results (n→d at ρ0·λ_n) can carry
    under the shared link capacities, with λ_n ≤ C_n."""
    from scipy.optimize import linprog
    from scipy.sparse import lil_matrix

    E, N, NC = len(dep.edges), dep.n_nodes, len(dep.comp_nodes)
    arcs = list(dep.edges) + [(b, a) for a, b in dep.edges]
    comms = []
    for n, node in enumerate(dep.comp_nodes):
        comms += [(dep.s1, node, 1.0, n), (dep.s2, node, 1.0, n),
                  (node, dep.sink, rho0, n)]
    K = len(comms)
    n_var = len(arcs) * K + NC

    def f(a, k):
        return a * K + k

    rows = [(k, v) for k, (_, dst, _, _) in enumerate(comms)
            for v in range(N) if v != dst]
    A_eq = lil_matrix((len(rows), n_var))
    for r, (k, v) in enumerate(rows):
        src, _, rate, n = comms[k]
        for a, (u, w) in enumerate(arcs):
            if u == v:
                A_eq[r, f(a, k)] += 1.0
            if w == v:
                A_eq[r, f(a, k)] -= 1.0
        if v == src:
            A_eq[r, len(arcs) * K + n] = -rate
    A_ub = lil_matrix((E, n_var))
    for e in range(E):
        for k in range(K):
            A_ub[e, f(e, k)] = 1.0
            A_ub[e, f(e + E, k)] = 1.0
    bounds = [(0.0, None)] * n_var
    for k, (_, dst, _, _) in enumerate(comms):
        for a, (u, _) in enumerate(arcs):
            if u == dst:
                bounds[f(a, k)] = (0.0, 0.0)
    for n, c in enumerate(dep.comp_capacity):
        bounds[len(arcs) * K + n] = (0.0, c)
    obj = np.zeros(n_var)
    obj[len(arcs) * K:] = -1.0
    res = linprog(obj, A_ub=A_ub.tocsr(), b_ub=np.array(dep.link_capacity),
                  A_eq=A_eq.tocsr(), b_eq=np.zeros(len(rows)),
                  bounds=bounds, method="highs")
    if not res.success:
        raise RuntimeError(f"reference LP failed: {res.message}")
    return float(np.sum(res.x[len(arcs) * K:]))


# ---------------------------------------------------------------------------
# Slot dynamics
# ---------------------------------------------------------------------------

def _greedy_matching(m, l, w, n_nodes):
    """Links visited by decreasing weight (ties by index); a link joins the
    matching iff its weight is positive and both endpoints are free."""
    order = jnp.argsort(-w, stable=True)

    def visit(k, carry):
        busy, on = carry
        e = order[k]
        ok = (~busy[m[e]]) & (~busy[l[e]]) & (w[e] > 0)
        busy = busy.at[m[e]].set(busy[m[e]] | ok)
        busy = busy.at[l[e]].set(busy[l[e]] | ok)
        return busy, on.at[e].set(ok)

    _, on = jax.lax.fori_loop(
        0, w.shape[0], visit,
        (jnp.zeros((n_nodes,), bool), jnp.zeros(w.shape, bool)))
    return on


def make_chunk(dep: Deployment, dtype=jnp.float32):
    """`chunk(state, lam, eps, regulated, key, t0, length)` for a batch of
    lanes: advances every lane `length` slots from slot `t0` and returns the new
    state and per-slot outputs [lanes, length, 3] (total backlog,
    packets delivered, useful packets delivered) as float32."""
    N, NC = dep.n_nodes, len(dep.comp_nodes)
    E = len(dep.edges)
    m = jnp.asarray([a for a, _ in dep.edges], jnp.int32)
    l = jnp.asarray([b for _, b in dep.edges], jnp.int32)
    cap = jnp.asarray(dep.link_capacity, dtype)
    comp = jnp.asarray(dep.comp_nodes, jnp.int32)
    C = jnp.asarray(dep.comp_capacity, dtype)
    nc = jnp.arange(NC)
    absorb = np.zeros((N, 3, NC), bool)       # where a class leaves the net
    for n, node in enumerate(dep.comp_nodes):
        absorb[node, 1, n] = absorb[node, 2, n] = True
        absorb[dep.sink, 0, n] = True
    absorb = jnp.asarray(absorb)
    s1, s2, sink = dep.s1, dep.s2, dep.sink
    zero = jnp.zeros((), dtype)

    def slot(state, lam, eps, reg, key, t):
        Q, D, X, Y, H, A, CC = state
        k_arr, _, k_reg = jax.random.split(jax.random.fold_in(key, t), 3)
        a = jax.random.poisson(k_arr, lam, shape=(1,))[0].astype(dtype)
        e = eps.astype(dtype)

        # (1) admission and join-shortest-sum-of-queues
        score = (1 + e) * Q[comp, 0, nc] + Q[s1, 1, :] + Q[s2, 2, :] + H
        n = jnp.argmin(score)
        assigned = jnp.where(nc == n, a, zero)
        for i, s in ((1, s1), (2, s2)):
            here = comp[n] == s
            Q = Q.at[s, i, n].add(jnp.where(here, zero, a))
            X = X.at[n, i - 1].add(jnp.where(here, a, zero))
            A = A.at[n, i - 1].add(jnp.where(here, a, zero))
        H = jnp.maximum(H + assigned - C, zero)

        # (2) backpressure routing
        flat = Q.reshape(N, 3 * NC)
        diff = flat[m] - flat[l]                             # [E, 3NC]
        best = jnp.argmax(jnp.abs(diff), axis=1)
        d = diff[jnp.arange(E), best]
        bi, bn = best // NC, best % NC
        rate = cap * (d != 0)
        if dep.wireless:
            rate = rate * _greedy_matching(m, l, jnp.abs(d) * (cap > 0), N)
        src = jnp.where(d > 0, m, l)
        dst = jnp.where(d > 0, l, m)
        asked = jnp.zeros_like(Q).at[src, bi, bn].add(rate)
        q_src, ask_src = Q[src, bi, bn], asked[src, bi, bn]
        sent = rate * jnp.where(ask_src > q_src,
                                q_src / jnp.maximum(ask_src, 1e-20), 1)
        q0 = Q[src, 0, bn]
        dummy_share = jnp.where(q0 > 0, D[src, bn] / jnp.maximum(q0, 1e-20),
                                zero)
        sent_dummy = sent * dummy_share * (bi == 0)
        Q = Q.at[src, bi, bn].add(-sent)
        D = D.at[src, bn].add(-sent_dummy)
        out = absorb[dst, bi, bn]
        Q = Q.at[dst, bi, bn].add(sent * ~out)
        D = D.at[dst, bn].add(sent_dummy * ~out)
        raw_in = sent * (out & (bi >= 1))
        side = jnp.maximum(bi - 1, 0)
        X = X.at[bn, side].add(raw_in)
        A = A.at[bn, side].add(raw_in)
        done = out & (bi == 0)
        dlv = jnp.sum(sent * done)
        dlv_u = jnp.sum((sent - sent_dummy) * done)

        # (3) computation, then the regulator
        pairs = jnp.clip(jnp.minimum(A[:, 0], A[:, 1]) - CC, zero,
                         jnp.minimum(X[:, 0], X[:, 1]))
        Z = jnp.minimum(pairs, C)
        X = X - Z[:, None]
        CC = CC + Z
        # with the regulator (per lane): Y holds computed results, and
        # (1 + Bernoulli(eps)) x the assignment leaves, dummies filling in
        Y_in = Y + Z
        B = jax.random.bernoulli(k_reg, eps, shape=(NC,)).astype(dtype)
        F_reg = assigned * (1 + B)
        useful = jnp.minimum(Y_in, F_reg)
        F = jnp.where(reg, F_reg, Z)
        fill = jnp.where(reg, F_reg - useful, zero)
        Y = jnp.where(reg, Y_in - useful, Y)
        at_sink = comp == sink
        Q = Q.at[comp, 0, nc].add(F * ~at_sink)
        D = D.at[comp, nc].add(fill * ~at_sink)
        dlv = dlv + jnp.sum(F * at_sink)
        dlv_u = dlv_u + jnp.sum((F - fill) * at_sink)

        backlog = Q.sum() + X.sum() + Y.sum()
        outs = jnp.stack([backlog, dlv, dlv_u]).astype(jnp.float32)
        return (Q, D, X, Y, H, A, CC), outs

    def lane(state, lam, eps, reg, key, t0, length):
        def body(s, t):
            return slot(s, lam, eps, reg, key, t)
        return jax.lax.scan(body, state, t0 + jnp.arange(length))

    @functools.partial(jax.jit, static_argnums=(6,))
    def chunk(state, lam, eps, reg, keys, t0, length):
        return jax.vmap(lane, in_axes=(0, 0, 0, 0, 0, None, None))(
            state, lam, eps, reg, keys, t0, length)

    def init(lanes: int):
        z = functools.partial(jnp.zeros, dtype=dtype)
        return (z((lanes, N, 3, NC)), z((lanes, N, NC)), z((lanes, NC, 2)),
                z((lanes, NC)), z((lanes, NC)), z((lanes, NC, 2)),
                z((lanes, NC)))

    chunk.init = init
    return chunk


def simulate(dep: Deployment, regulated: bool, lam, eps, seeds, T: int,
             dtype=jnp.float32, step: int | None = None) -> np.ndarray:
    """Per-slot outputs [lanes, T, 3] of `len(seeds)` independent lanes."""
    chunk = make_chunk(dep, dtype)
    reg = jnp.full((len(seeds),), regulated)
    lam = jnp.asarray(np.asarray(lam, np.float32))
    eps = jnp.asarray(np.asarray(eps, np.float32))
    keys = jax.vmap(jax.random.PRNGKey)(jnp.asarray(np.asarray(seeds,
                                                                np.int32)))
    state = chunk.init(len(seeds))
    step = step or T
    outs = []
    for t0 in range(0, T, step):
        state, o = chunk(state, lam, eps, reg, keys, jnp.int32(t0),
                         min(step, T - t0))
        outs.append(np.asarray(o))
    return np.concatenate(outs, axis=1)


# ---------------------------------------------------------------------------
# Metrics of a lane, in float64
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Verdict:
    """The streaming stability test (window, burn-in, k in a row)."""

    window: int
    burn_in: int
    k_stable: int = 3
    k_unstable: int = 3
    drift_tol: float = 0.02
    gap_tol: float = 0.05

    def of(self, backlog: np.ndarray, useful_cum: np.ndarray, lam: float,
           horizon: int) -> Tuple[int, int]:
        """(verdict, slot it latched at) from per-slot backlog and
        cumulative useful deliveries (index s-1 holds the value after s
        slots)."""
        scale = max(float(lam), 1.0)
        q0 = u0 = 0.0
        s_run = u_run = 0
        for s in range(self.window, horizon + 1, self.window):
            if s == self.burn_in:
                q0, u0 = backlog[s - 1], useful_cum[s - 1]
            if s < self.burn_in + 2 * self.window:
                continue
            el = float(s - self.burn_in)
            drift = (backlog[s - 1] - q0) / el
            gap = float(lam) - (useful_cum[s - 1] - u0) / el
            ok = drift <= self.drift_tol * scale and \
                gap <= self.gap_tol * scale
            bad = drift >= 2 * self.drift_tol * scale and \
                gap >= self.gap_tol * scale
            s_run = s_run + 1 if ok else 0
            u_run = u_run + 1 if bad else 0
            if s_run >= self.k_stable:
                return STABLE, s
            if u_run >= self.k_unstable:
                return UNSTABLE, s
        return UNDECIDED, horizon


def lane_metrics(out: np.ndarray) -> Dict:
    """Useful rate over the second half, packets delivered and mean
    backlog of one lane's [T, 3] outputs."""
    T = out.shape[0]
    o = out.astype(np.float64)
    useful = np.cumsum(o[:, 2])
    half = T // 2
    return {"useful_rate": (useful[-1] - useful[T - half - 1]) / half,
            "delivered": o[:, 1].sum(),
            "mean_queue": o[:, 0].sum() / T}


def lane_verdict(out: np.ndarray, lam: float, T: int,
                 verdict: "Verdict") -> int:
    """The streaming verdict one lane's [T, 3] outputs latch by slot T."""
    o = out.astype(np.float64)
    return verdict.of(o[:, 0], np.cumsum(o[:, 2]), float(lam), T)[0]


# ---------------------------------------------------------------------------
# λ_max search
# ---------------------------------------------------------------------------

_M64 = (1 << 64) - 1


def probe_seed(replica: int, k: int, attempt: int, seed: int) -> int:
    """The probe stream of grid rate k: a SplitMix64 fold of (replica,
    rate index, attempt, seed) to a non-negative int31."""
    h = (0x9E3779B97F4A7C15 * (replica & _M64)
         + 0xBF58476D1CE4E5B9 * (k & _M64)
         + 0x94D049BB133111EB * (attempt & _M64)
         + 0xD6E8FEB86659FD93 * (seed & _M64) + 0x2545F4914F6CDD1D) & _M64
    h ^= h >> 30
    h = (h * 0xBF58476D1CE4E5B9) & _M64
    h ^= h >> 27
    h = (h * 0x94D049BB133111EB) & _M64
    h ^= h >> 31
    return int(h & 0x7FFFFFFF)


def bisection(k_lo: int, k_hi: int, max_calls: int
              ) -> Iterator[int]:
    """Sequential λ_max search on the integer grid, as a generator: it
    yields the grid index to probe and is sent back (sustainable,
    undecided).  Returns (k_lo, k_hi, outcomes).

    Shrink the floor (halve k_lo until sustainable), push the ceiling
    (double k_hi while sustainable), then bisect; each index is probed at
    most once, and an index past the budget counts as unsustainable."""
    k_lo = max(int(k_lo), 0)
    k_hi = max(int(k_hi), k_lo + 1)
    outcomes: Dict[int, Tuple[bool, bool]] = {}

    def sustainable(k):
        if k <= 0:
            return True
        if k in outcomes:
            return outcomes[k][0]
        if len(outcomes) >= max_calls:
            return False
        outcomes[k] = yield k
        return outcomes[k][0]

    while k_lo > 0:
        if (yield from sustainable(k_lo)):
            break
        k_lo //= 2
    while (yield from sustainable(k_hi)) and len(outcomes) < max_calls:
        k_lo = max(k_lo, k_hi)
        k_hi *= 2
    while k_hi - k_lo > 1 and len(outcomes) < max_calls:
        mid = (k_lo + k_hi) // 2
        if (yield from sustainable(mid)):
            k_lo = mid
        else:
            k_hi = mid
    return k_lo, k_hi, outcomes


def cell_search(k_lo: int, k_hi: int, max_calls: int, max_requeues: int):
    """One cell: the search, restarted at a doubled horizon (attempt + 1)
    while it ends with its top blocked by undecided evidence only or with
    no sustainable rate at all.  Yields (k, attempt); returns the row, whose
    `undecided` says the final top was never proven unstable."""
    attempt = 0
    n_probes = 0
    while True:
        search = bisection(k_lo, k_hi, max_calls)
        answer = None
        try:
            k = next(search)
            while True:
                n_probes += 1
                answer = yield k, attempt
                k = search.send(answer)
        except StopIteration as stop:
            lo, hi, outcomes = stop.value
        top = outcomes.get(hi)
        blocked = top is not None and not top[0] and top[1]
        if (blocked or lo == 0) and attempt < max_requeues:
            attempt += 1
            continue
        return {"k_lo": lo, "k_hi": hi, "n_requeues": attempt,
                "n_calls": n_probes, "undecided": blocked}


def atlas_rows(dep: Deployment, cells: Sequence[Tuple[str, int]],
               traffic: dict, dtype=jnp.float32) -> List[dict]:
    """λ_max rows of `cells` ((policy, replica) pairs), every cell's search
    advanced in lockstep: each round runs every open cell's pending probe
    (one lane per seed) until all its lanes decide or reach the horizon."""
    T, chunk_len = int(traffic["T"]), int(traffic["chunk"])
    eps_b = float(traffic["eps_b"])
    rel_tol = float(traffic["rel_tol"])
    lo_frac, hi_frac = traffic["bracket"]
    verdict = Verdict(window=chunk_len, burn_in=2 * chunk_len,
                      **traffic.get("verdict", {}))
    regulated = {p: p in REGULATED for p, _ in cells}
    steps = {p: rel_tol * lp_capacity(dep, 1.0 + eps_b if reg else 1.0)
             for p, reg in regulated.items()}
    chunk = make_chunk(dep, dtype)

    searches, pending, rows = {}, {}, {}
    for c, (p, r) in enumerate(cells):
        bound = steps[p] / rel_tol
        searches[c] = cell_search(
            max(int(np.floor(lo_frac * bound / steps[p])), 0),
            max(int(np.ceil(hi_frac * bound / steps[p])), 1),
            int(traffic["max_calls"]), int(traffic["max_requeues"]))
        pending[c] = next(searches[c])
    while pending:
        lanes = [(c, cells[c][0], cells[c][1]) + pending[c] for c in pending]
        verdicts = _probe_round(chunk, lanes, steps, regulated, eps_b,
                                tuple(traffic["seeds"]), T, chunk_len,
                                verdict, len(cells))
        for c, names in verdicts.items():
            ok = all(x == STABLE for x in names)
            try:
                pending[c] = searches[c].send(
                    (ok, not ok and UNSTABLE not in names))
            except StopIteration as stop:
                p = cells[c][0]
                rows[c] = dict(stop.value, policy=p, replica=cells[c][1],
                               bound=steps[p] / rel_tol,
                               lam_max=stop.value["k_lo"] * steps[p],
                               hi=stop.value["k_hi"] * steps[p])
                del pending[c]
    return [rows[c] for c in range(len(cells))]


def _probe_round(chunk, probes, steps, regulated, eps_b, seeds, T, chunk_len,
                 verdict, width) -> Dict[int, Tuple[int, ...]]:
    """Run each probe (cell, policy, replica, k, attempt), one lane per
    seed, until every lane has decided or reached its horizon; verdicts
    per cell.  The batch is always `width` probes wide, so one compiled
    program serves every round; idle lanes repeat the first probe."""
    S = len(seeds)
    probes = probes + [probes[0]] * (width - len(probes))
    lam = np.zeros(width * S, np.float32)
    seed = np.zeros(width * S, np.int64)
    horizon = np.zeros(width * S, np.int64)
    reg = np.zeros(width * S, bool)
    for j, (_, p, replica, k, attempt) in enumerate(probes):
        for i, s in enumerate(seeds):
            lam[j * S + i] = np.float32(k * steps[p])
            seed[j * S + i] = probe_seed(replica, k, attempt, s)
            horizon[j * S + i] = T << attempt
            reg[j * S + i] = regulated[p]
    keys = jax.vmap(jax.random.PRNGKey)(jnp.asarray(seed.astype(np.int32)))
    eps = jnp.full((width * S,), eps_b, jnp.float32)
    state = chunk.init(width * S)
    args = (jnp.asarray(lam), eps, jnp.asarray(reg), keys)
    n = len({pr[0] for pr in probes}) * S
    backlog = np.zeros((n, 0))
    useful = np.zeros((n, 0))
    final: List[int | None] = [None] * n
    t = 0
    while any(f is None for f in final):
        state, o = chunk(state, *args, jnp.int32(t), chunk_len)
        o = np.asarray(o, np.float64)[:n]
        t += chunk_len
        backlog = np.concatenate([backlog, o[:, :, 0]], axis=1)
        last = useful[:, -1:] if useful.shape[1] else np.zeros((n, 1))
        useful = np.concatenate([useful, last + np.cumsum(o[:, :, 2], 1)],
                                axis=1)
        for i in range(n):
            if final[i] is None:
                v, _ = verdict.of(backlog[i], useful[i], float(lam[i]), t)
                if v != UNDECIDED or t >= horizon[i]:
                    final[i] = v
    return {pr[0]: tuple(final[j * S:(j + 1) * S])
            for j, pr in enumerate(probes[:n // S])}
