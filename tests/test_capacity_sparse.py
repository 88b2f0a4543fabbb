"""The sparse Theorem-4 LP builder against a dense build of the same LP.

`dense_capacity` below is the row-by-row dense construction the program
used before its builder went sparse, kept here as the reference: the same
variables, rows, bounds and HiGHS call, so λ* must agree to 1e-9.  The
k=8 fat-tree is too large for dense rows (3.1 GB), so there the program
is held to the benchmark's independent reference LP instead.  Spans and
counters: one build and one solve are recorded per LP actually solved,
none on a cache hit.
"""
import dataclasses
import pathlib
import sys

import numpy as np
import pytest
from scipy.optimize import linprog

from repro.core import capacity
from repro.core.capacity import capacity_upper_bound, single_node_capacity
from repro.fleet import get_scenario, list_scenarios
from repro.fleet.report import exact_lam_star
from repro.fleet.scenarios import kary_fat_tree
from repro.obs import spans

ROOT = pathlib.Path(__file__).resolve().parents[1]


def dense_capacity(problem, rho0=1.0):
    """λ* from dense constraint rows built node by node and edge by edge."""
    g = problem.graph
    NC, E = problem.n_comp, g.n_edges
    de = g.directed_edges()
    K = 3 * NC
    nf = 2 * E * K
    nv = nf + NC
    ends = []
    for n in problem.comp_nodes:
        ends += [(problem.s1, n), (problem.s2, n), (n, problem.dest)]
    mult = np.array([1.0, 1.0, rho0] * NC)
    A_eq = []
    for c, (src, dst) in enumerate(ends):
        for m in range(g.n_nodes):
            if m == dst:
                continue
            row = np.zeros(nv)
            for e, (a, b) in enumerate(de):
                if a == m:
                    row[e * K + c] += 1.0
                elif b == m:
                    row[e * K + c] -= 1.0
            if m == src:
                row[nf + c // 3] = -mult[c]
            A_eq.append(row)
    A_ub = []
    for e in range(E):
        row = np.zeros(nv)
        for c in range(K):
            row[e * K + c] = row[(e + E) * K + c] = 1.0
        A_ub.append(row)
    bounds = [(0.0, None)] * nv
    for c, (_, dst) in enumerate(ends):
        for e, (a, _) in enumerate(de):
            if a == dst:
                bounds[e * K + c] = (0.0, 0.0)
    for i, cap in enumerate(problem.comp_caps):
        bounds[nf + i] = (0.0, float(cap))
    obj = np.zeros(nv)
    obj[nf:] = -1.0
    res = linprog(obj, A_ub=np.array(A_ub), b_ub=g.capacity,
                  A_eq=np.array(A_eq), b_eq=np.zeros(len(A_eq)),
                  bounds=bounds, method="highs")
    return float(res.x[nf:].sum()) if res.success else 0.0


def _reference_lp(problem, rho0):
    """λ* from the benchmark's plain reference (imports nothing of the
    program's LP)."""
    sys.path.insert(0, str(ROOT / "perfbench"))
    try:
        from harness import reference
    finally:
        sys.path.remove(str(ROOT / "perfbench"))
    g = problem.graph
    dep = reference.Deployment(
        n_nodes=g.n_nodes, edges=tuple(map(tuple, g.edges.tolist())),
        link_capacity=tuple(g.capacity.tolist()), s1=problem.s1,
        s2=problem.s2, sink=problem.dest, comp_nodes=problem.comp_nodes,
        comp_capacity=problem.comp_caps, wireless=False)
    return reference.lp_capacity(dep, rho0)


CASES = ([(name, rho) for name in list_scenarios() if name != "fattree_k8"
          for rho in (1.0, 1.05)]
         + [("kary_fat_tree(4)", rho) for rho in (1.0, 1.05)]
         + [("kary_fat_tree(8)", rho) for rho in (1.0, 1.05)])


@pytest.mark.parametrize("name,rho0", CASES)
def test_sparse_lp_matches_dense_build(name, rho0):
    if name.startswith("kary_fat_tree"):
        k = int(name[-2])
        problems = [kary_fat_tree(k)]
    else:
        problems = [get_scenario(name).build(s) for s in (0, 1)]
    for p in problems:
        got = capacity_upper_bound(p, rho0)
        if name == "kary_fat_tree(8)":
            # all 32 aggregation switches compute R/32 in all, 5 = one
            # access link; the regulated stream makes the sink's link bind
            assert got.lam_star == pytest.approx(5.0 / rho0, abs=1e-9)
            assert got.lam_star == pytest.approx(_reference_lp(p, rho0),
                                                 abs=1e-9)
            continue
        assert got.lam_star == pytest.approx(dense_capacity(p, rho0),
                                             abs=1e-9)
        assert got.flows.shape == (2 * p.graph.n_edges, 3 * p.n_comp)
        assert got.lam_per_node.sum() == pytest.approx(got.lam_star)
        if rho0 == 1.0:
            for i in range(p.n_comp):
                sub = dataclasses.replace(p, comp_nodes=(p.comp_nodes[i],),
                                          comp_caps=(p.comp_caps[i],))
                assert single_node_capacity(p, i).lam_star == \
                    pytest.approx(dense_capacity(sub), abs=1e-9)


def test_paper_grid_is_eight():
    assert capacity_upper_bound(get_scenario("paper_grid").build(0)) \
        .lam_star == pytest.approx(8.0, abs=1e-9)


def test_spans_and_counters_once_per_solve_none_on_a_cache_hit():
    exact_lam_star.cache_clear()
    before = capacity.lp_counters()
    spans.enable()
    try:
        first = exact_lam_star("ring", 0, 1.0)
        again = exact_lam_star("ring", 0, 1.0)      # a cache hit
    finally:
        recorded = spans.collect()
    after = capacity.lp_counters()
    assert first == again
    assert [s.name for s in recorded] == ["capacity.build", "capacity.solve"]
    p = get_scenario("ring").build(0)
    E = p.graph.n_edges
    deg = np.bincount(p.graph.edges.ravel(), minlength=p.graph.n_nodes)
    ends = [(src, dst) for n in p.comp_nodes
            for src, dst in ((p.s1, n), (p.s2, n), (n, p.dest))]
    # conservation rows: a +1 at the tail and a -1 at the head of every
    # directed edge, except at the commodity's destination, and a -mult at
    # the source; capacity rows: both directions of every commodity
    nnz = sum(2 * (2 * E - deg[dst]) + (src != dst) for src, dst in ends)
    nnz += 2 * E * len(ends)
    build = recorded[0]
    assert build.n == nnz
    assert after["solves"] - before["solves"] == 1
    assert after["nnz"] - before["nnz"] == build.n
    assert after["build_s"] > before["build_s"]
    assert after["solve_s"] > before["solve_s"]
    assert all(k in after for k in ("build_s", "solve_s", "nnz", "solves"))
    # with recording off the counters still count
    capacity_upper_bound(p)
    assert capacity.lp_counters()["solves"] == after["solves"] + 1
