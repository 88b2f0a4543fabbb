"""Theorem 1 / Theorem 4 capacity upper bound via the multicommodity-flow LP.

For computation nodes N_C we build 3*N_C unicast commodities:
  (1,n): s1 -> n   rate lam_n      (raw data of source 1)
  (2,n): s2 -> n   rate lam_n      (raw data of source 2)
  (0,n): n  -> d   rate lam_n      (processed results)
subject to per-edge shared capacity (paper eq. (1)/(5)), flow conservation
(4), positivity and no-outflow-at-destination (6), and lam_n <= C_n.
lambda* = max sum_n lam_n.  Solved exactly with scipy/HiGHS.

The LP also supports an output-rate multiplier `rho0` on commodity (0,n)
(rate rho0*lam_n) which models the dummy-packet overhead (1+eps_B) of
policies pi2/pi3 (Theorem 3/5).

Every solve goes through one sparse builder (`_flow_lp`, DESIGN.md §6):
the COO indices of the directed incidence matrix are computed once with
numpy and repeated over the commodities, so a k=8 fat-tree (7,872
conservation rows x 49,760 columns) builds in well under a second where
dense rows would take gigabytes.  Each build and each solve is a span
(``capacity.build`` with its nonzeros, ``capacity.solve``) and adds to
always-on process counters, read by `lp_counters()`.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np
import scipy.sparse as sp
from scipy.optimize import linprog

from repro.obs.spans import span

from .graph import ComputeProblem, Graph

_COUNTERS = {"build_s": 0.0, "solve_s": 0.0, "nnz": 0, "solves": 0}


def lp_counters() -> dict:
    """Cumulative LP work since process start: seconds building and
    solving, constraint-matrix nonzeros built, and solves run."""
    return dict(_COUNTERS)


@dataclasses.dataclass(frozen=True)
class CapacityResult:
    lam_star: float                 # max total query rate
    lam_per_node: np.ndarray        # [N_C] optimal per-node shares
    flows: np.ndarray               # [2E, 3*N_C] optimal directed flows
    status: str

    def time_share(self) -> np.ndarray:
        tot = self.lam_per_node.sum()
        return self.lam_per_node / max(tot, 1e-12)


@dataclasses.dataclass
class _LP:
    """A flow LP in HiGHS form: min obj·x, A_ub x <= b_ub, A_eq x = b_eq,
    0 <= x <= hi.  Flow f[dir_edge, comm] is column dir_edge*K + comm;
    the rate variables follow the nf flow columns."""

    A_eq: sp.csr_matrix
    b_eq: np.ndarray
    A_ub: sp.csr_matrix
    b_ub: np.ndarray
    hi: np.ndarray
    obj: np.ndarray
    nf: int


def _flow_lp(g: Graph, src, dst, var, mult, n_rate: int) -> _LP:
    """The flow part for commodities c = (src[c] -> dst[c]) each carrying
    mult[c] x rate variable var[c], with `n_rate` rate variables after the
    flows (bounds [0, inf), objective 0, to be set by the caller).

    Rows: conservation at every node but the commodity's destination,
    commodity-major (row c*(N-1) + node, the destination skipped), with
    -mult at the source; then one shared-capacity row per undirected edge
    over both directions and every commodity.  Outflow at a commodity's
    destination is bounded to zero (6)."""
    src, dst, var = (np.asarray(a, np.int64) for a in (src, dst, var))
    mult = np.asarray(mult, np.float64)
    N, E, K = g.n_nodes, g.n_edges, len(src)
    de = g.directed_edges().astype(np.int64)           # [2E, 2]
    nf = 2 * E * K
    col = np.arange(2 * E)[:, None] * K + np.arange(K)  # [2E, K]
    base = np.arange(K) * (N - 1)

    def node_rows(nodes):                               # [.., K] rows
        return base + nodes - (nodes > dst)

    tail, head = de[:, :1], de[:, 1:]
    keep_t, keep_h = tail != dst, head != dst
    has_src = src != dst
    rows = np.concatenate([node_rows(tail)[keep_t], node_rows(head)[keep_h],
                           node_rows(src)[has_src]])
    cols = np.concatenate([col[keep_t], col[keep_h], nf + var[has_src]])
    vals = np.concatenate([np.ones(keep_t.sum()), -np.ones(keep_h.sum()),
                           -mult[has_src]])
    A_eq = sp.csr_matrix((vals, (rows, cols)),
                         shape=(K * (N - 1), nf + n_rate))
    ub_rows = np.repeat(np.arange(E), 2 * K)
    ub_cols = np.concatenate([col[:E], col[E:]], axis=1).ravel()
    A_ub = sp.csr_matrix((np.ones(ub_rows.size), (ub_rows, ub_cols)),
                         shape=(E, nf + n_rate))
    hi = np.full(nf + n_rate, np.inf)
    hi[col[tail == dst]] = 0.0
    return _LP(A_eq, np.zeros(K * (N - 1)), A_ub,
               np.asarray(g.capacity, np.float64), hi, np.zeros(nf + n_rate),
               nf)


def _solve(build):
    """Build (a ``capacity.build`` span) and solve (``capacity.solve``)
    one LP, adding both to the process counters."""
    t0 = time.perf_counter()
    with span("capacity.build") as s:
        lp = build()
        s.n = lp.A_eq.nnz + lp.A_ub.nnz
    t1 = time.perf_counter()
    bounds = np.column_stack([np.zeros_like(lp.hi), lp.hi])
    with span("capacity.solve"):
        res = linprog(lp.obj, A_ub=lp.A_ub, b_ub=lp.b_ub, A_eq=lp.A_eq,
                      b_eq=lp.b_eq, bounds=bounds, method="highs")
    t2 = time.perf_counter()
    _COUNTERS["build_s"] += t1 - t0
    _COUNTERS["solve_s"] += t2 - t1
    _COUNTERS["nnz"] += lp.A_eq.nnz + lp.A_ub.nnz
    _COUNTERS["solves"] += 1
    return lp, res


def _commodities(problem: ComputeProblem, rho0: float, first_var: int = 0):
    """(src, dst, rate variable, multiplier) per commodity; order: for each
    n: (1,n), (2,n), (0,n)."""
    out = []
    for i, n in enumerate(problem.comp_nodes):
        v = first_var + i
        out += [(problem.s1, n, v, 1.0), (problem.s2, n, v, 1.0),
                (n, problem.dest, v, rho0)]
    return out


def capacity_upper_bound(problem: ComputeProblem, rho0: float = 1.0) -> CapacityResult:
    g = problem.graph
    NC = problem.n_comp
    n_comm = 3 * NC

    def build():
        lp = _flow_lp(g, *zip(*_commodities(problem, rho0)), n_rate=NC)
        lp.hi[lp.nf:] = problem.comp_caps           # 0 <= lam_n <= C_n
        lp.obj[lp.nf:] = -1.0                       # maximize sum lam_n
        return lp

    lp, res = _solve(build)
    if not res.success:
        return CapacityResult(0.0, np.zeros(NC),
                              np.zeros((2 * g.n_edges, n_comm)),
                              status=res.message)
    lam_per_node = res.x[lp.nf:]
    flows = res.x[:lp.nf].reshape(2 * g.n_edges, n_comm)
    return CapacityResult(float(lam_per_node.sum()), lam_per_node, flows, "optimal")


def single_node_capacity(problem: ComputeProblem, node_index: int,
                         rho0: float = 1.0) -> CapacityResult:
    """Theorem 1: capacity when computation is pinned to one node."""
    sub = dataclasses.replace(
        problem,
        comp_nodes=(problem.comp_nodes[node_index],),
        comp_caps=(problem.comp_caps[node_index],),
    )
    return capacity_upper_bound(sub, rho0=rho0)


# ---------------------------------------------------------------------------
# Multi-stream (multiclass) extension — the generalization the paper names
# in §II-B/§VI: multiple query streams, each with its own sources and
# destination, sharing links AND computation-node capacity.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MultiStreamResult:
    lam_star: float                 # max total rate at the given mix
    lam_per_stream: np.ndarray      # [n_streams]
    lam_per_node: np.ndarray        # [n_streams, N_C]
    status: str


def multi_stream_capacity(problems: list[ComputeProblem],
                          weights: list[float] | None = None,
                          rho0: float = 1.0) -> MultiStreamResult:
    """Max-weighted-throughput LP for several query streams on one graph.

    Streams sigma share (i) every edge capacity and (ii) the computation
    capacity C_n of every node that appears in more than one stream's N_C.
    With `weights` w (sum 1), we maximize lambda s.t. stream sigma gets
    rate w_sigma * lambda — the boundary point of the multiclass capacity
    region along direction w (paper's time-share view, eq. after Thm 4).
    """
    g = problems[0].graph
    for p in problems[1:]:
        assert p.graph.n_nodes == g.n_nodes and \
            (p.graph.edges == g.edges).all(), "streams must share the graph"
    NS = len(problems)
    weights = np.full(NS, 1.0 / NS) if weights is None else \
        np.asarray(weights, dtype=np.float64)
    assert abs(weights.sum() - 1.0) < 1e-9 and (weights > 0).all()

    # commodity layout: for each stream sigma, for each of its comp nodes:
    # (1,n), (2,n), (0,n); rate variables lam^sigma_n in the same order,
    # then one global lam (last).
    comms, first = [], [0]
    for p in problems:
        comms += _commodities(p, rho0, first[-1])
        first.append(first[-1] + p.n_comp)
    n_lam = first[-1]

    def build():
        lp = _flow_lp(g, *zip(*comms), n_rate=n_lam + 1)
        nv = lp.nf + n_lam + 1
        # per-stream total: sum_n lam^sigma_n - w_sigma * lam = 0
        tot = sp.lil_matrix((NS, nv))
        for s_i in range(NS):
            tot[s_i, lp.nf + first[s_i]:lp.nf + first[s_i + 1]] = 1.0
            tot[s_i, nv - 1] = -weights[s_i]
        # shared computation capacity: sum over streams using node n
        node_caps = {}
        for s_i, p in enumerate(problems):
            for n_i, n in enumerate(p.comp_nodes):
                node_caps.setdefault(n, (p.comp_caps[n_i], []))[1].append(
                    first[s_i] + n_i)
        shared = sp.lil_matrix((len(node_caps), nv))
        for r, (_, lis) in enumerate(node_caps.values()):
            shared[r, [lp.nf + li for li in lis]] = 1.0
        lp.A_eq = sp.vstack([lp.A_eq, tot], format="csr")
        lp.b_eq = np.concatenate([lp.b_eq, np.zeros(NS)])
        lp.A_ub = sp.vstack([lp.A_ub, shared], format="csr")
        lp.b_ub = np.concatenate([lp.b_ub,
                                  [c for c, _ in node_caps.values()]])
        lp.obj[-1] = -1.0
        return lp

    lp, res = _solve(build)
    if not res.success:
        return MultiStreamResult(0.0, np.zeros(NS),
                                 np.zeros((NS, 1)), res.message)
    lam = float(res.x[-1])
    per_stream = weights * lam
    per_node = np.zeros((NS, max(p.n_comp for p in problems)))
    for s_i, p in enumerate(problems):
        per_node[s_i, :p.n_comp] = res.x[lp.nf + first[s_i]:
                                         lp.nf + first[s_i + 1]]
    return MultiStreamResult(lam, per_stream, per_node, "optimal")
