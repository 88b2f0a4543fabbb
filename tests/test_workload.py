"""Arrival-process statistics and randomized greedy-matching properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import grid_graph
from repro.core.policies import _matching_fixpoint, greedy_maximal_matching
from repro.fleet import PadDims, get_scenario, pad_problem
from repro.sim.workload import (bernoulli_batch_arrivals, constant_arrivals,
                                poisson_arrivals)


class TestBernoulliBatchArrivals:
    @pytest.mark.parametrize("lam,batch", [(0.5, 4), (1.0, 4), (2.0, 8),
                                           (3.5, 4)])
    def test_mean_rate(self, lam, batch):
        """E[A(t)] = lam as long as lam <= batch (p = lam/batch <= 1)."""
        T = 40_000
        arr = bernoulli_batch_arrivals(jax.random.key(0), lam, T, batch=batch)
        assert float(arr.mean()) == pytest.approx(lam, rel=0.05)

    def test_values_are_zero_or_batch(self):
        arr = bernoulli_batch_arrivals(jax.random.key(1), 1.0, 5000, batch=4)
        vals = set(np.unique(np.asarray(arr)).tolist())
        assert vals <= {0.0, 4.0}

    def test_rate_saturates_at_batch(self):
        """p is clipped at 1: requesting lam > batch delivers exactly batch
        every slot (the documented burst ceiling)."""
        arr = bernoulli_batch_arrivals(jax.random.key(2), 10.0, 1000, batch=4)
        assert float(arr.min()) == 4.0 and float(arr.max()) == 4.0

    def test_other_processes_match_rates(self):
        T = 40_000
        pois = poisson_arrivals(jax.random.key(3), 2.0, T)
        assert float(pois.mean()) == pytest.approx(2.0, rel=0.05)
        const = constant_arrivals(1.7, 100)
        assert float(const.min()) == float(const.max()) == pytest.approx(1.7)


class TestGreedyMatchingProperties:
    """Randomized invariants beyond the fixed cases in test_policies.py."""

    @pytest.mark.parametrize("seed", range(6))
    def test_node_exclusive_on_random_weights(self, seed):
        g = grid_graph(4, 4, 1.0)
        rng = np.random.default_rng(seed)
        w = jnp.asarray(rng.uniform(0.0, 10.0, size=g.n_edges))
        sel = np.asarray(greedy_maximal_matching(
            jnp.asarray(g.edges), w, g.n_nodes))
        used = np.zeros(g.n_nodes, int)
        for (m, l), s in zip(g.edges, sel):
            if s:
                used[m] += 1
                used[l] += 1
        assert used.max() <= 1, "two active links share a node"

    @pytest.mark.parametrize("seed", range(6))
    def test_matching_is_maximal(self, seed):
        """No positive-weight link with two free endpoints stays idle."""
        g = grid_graph(3, 5, 1.0)
        rng = np.random.default_rng(100 + seed)
        w_np = rng.uniform(0.1, 5.0, size=g.n_edges)
        sel = np.asarray(greedy_maximal_matching(
            jnp.asarray(g.edges), jnp.asarray(w_np), g.n_nodes))
        used = np.zeros(g.n_nodes, bool)
        for (m, l), s in zip(g.edges, sel):
            if s:
                used[m] = used[l] = True
        for (m, l), s in zip(g.edges, sel):
            assert s or used[m] or used[l], (
                f"link ({m},{l}) could have been activated")

    @pytest.mark.parametrize("seed", range(4))
    def test_zero_weight_links_never_activate(self, seed):
        g = grid_graph(4, 4, 1.0)
        rng = np.random.default_rng(200 + seed)
        w_np = rng.uniform(0.0, 5.0, size=g.n_edges)
        zero = rng.uniform(size=g.n_edges) < 0.5
        w_np[zero] = 0.0
        sel = np.asarray(greedy_maximal_matching(
            jnp.asarray(g.edges), jnp.asarray(w_np), g.n_nodes))
        assert not sel[zero].any()

    # -- the fixpoint against a plain sequential greedy ------------------

    @staticmethod
    def _sequential_greedy(edges, w):
        """Visit links by decreasing weight, ties by lower index; take a
        positive link whose endpoints are both free."""
        busy, sel = set(), np.zeros(len(w), bool)
        for e in sorted(range(len(w)), key=lambda e: (-w[e], e)):
            m, l = (int(v) for v in edges[e])
            if w[e] > 0 and m not in busy and l not in busy:
                sel[e] = True
                busy |= {m, l}
        return sel

    @staticmethod
    def _edge_list(name):
        if name == "grid4x4":
            return np.asarray(grid_graph(4, 4, 1.0).edges)
        if name == "grid3x5":
            return np.asarray(grid_graph(3, 5, 1.0).edges)
        return np.asarray(get_scenario(name).build(0).graph.edges)

    @staticmethod
    def _tied_weights(rng, shape, zero_share):
        """Integer weights, so ties are common, with a share of zeros."""
        w = rng.integers(1, 5, size=shape).astype(np.float32)
        w[rng.uniform(size=shape) < zero_share] = 0.0
        return w

    @pytest.mark.parametrize("pad", [0, 6], ids=["bare", "padded"])
    @pytest.mark.parametrize("zero_share", [0.3, 0.5])
    @pytest.mark.parametrize("graph", ["grid4x4", "grid3x5", "expander"])
    def test_fixpoint_equals_sequential_greedy(self, graph, zero_share, pad):
        """Static edges, tied weights, zeros and (0, 0) padded self-loops
        of weight 0: the same links as the greedy visit, lane by lane."""
        edges = self._edge_list(graph)
        edges = np.concatenate([edges, np.zeros((pad, 2), edges.dtype)])
        rng = np.random.default_rng(len(edges) * 10 + pad)
        w = self._tied_weights(rng, (64, len(edges)), zero_share)
        w[:, len(edges) - pad:] = 0.0
        n_nodes = int(edges.max()) + 1
        sel = np.asarray(jax.jit(jax.vmap(lambda ww: greedy_maximal_matching(
            jnp.asarray(edges), ww, n_nodes)))(w))
        for lane in range(len(w)):
            np.testing.assert_array_equal(
                sel[lane], self._sequential_greedy(edges, w[lane]))

    @pytest.mark.parametrize("zero_share", [0.3, 0.5])
    def test_fixpoint_equals_greedy_on_traced_lane_edges(self, zero_share):
        """A vmapped batch whose lanes carry their own padded edge lists,
        as the fleet's `PaddedProblem` passes them."""
        problems = [get_scenario(s).build(0)
                    for s in ("paper_grid", "expander", "ring", "tree")]
        dims = PadDims.of(problems)
        pads = [pad_problem(problems[i % len(problems)], dims)
                for i in range(32)]
        edges = np.stack([np.asarray(p.edges) for p in pads])
        mask = np.stack([np.asarray(p.edge_mask) for p in pads])
        rng = np.random.default_rng(7)
        w = self._tied_weights(rng, mask.shape, zero_share) * mask
        sel, rounds = jax.jit(jax.vmap(_matching_fixpoint))(edges, w)
        sel, rounds = np.asarray(sel), np.asarray(rounds)
        for lane in range(len(w)):
            np.testing.assert_array_equal(
                sel[lane], self._sequential_greedy(edges[lane], w[lane]))
        assert ((rounds >= 1) & (rounds <= dims.n_edges)).all()

    @pytest.mark.parametrize("E", [2, 7, 24])
    def test_chain_needs_e_minus_one_rounds(self, E):
        """Weights falling along a path: each link waits for the one
        before it, so the fixpoint takes E - 1 rounds, and every other
        link is on."""
        edges = np.stack([np.arange(E), np.arange(1, E + 1)], axis=1)
        w = np.arange(E, 0, -1).astype(np.float32)
        sel, rounds = _matching_fixpoint(jnp.asarray(edges), jnp.asarray(w))
        np.testing.assert_array_equal(np.asarray(sel), np.arange(E) % 2 == 0)
        np.testing.assert_array_equal(np.asarray(sel),
                                      self._sequential_greedy(edges, w))
        assert int(rounds) == E - 1
