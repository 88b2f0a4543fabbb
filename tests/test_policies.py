"""Behavioural tests for the paper's policies on small networks."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ComputeProblem, PolicyConfig, capacity_upper_bound,
                        paper_grid_problem, triangle_graph)
from repro.sim import simulate


def _stable(res, T):
    """Sub-linear backlog: final backlog far below lam*T growth and the
    trailing-window average close to the overall average."""
    q = np.asarray(res.total_queue)
    head, tail = q[: T // 2].mean(), q[T // 2:].mean()
    return tail < 2.0 * head + 50.0


TRI = ComputeProblem(triangle_graph(4.0), s1=0, s2=1, dest=2,
                     comp_nodes=(2,), comp_caps=(2.0,))


class TestSingleNode:
    def test_pi1_stable_below_capacity(self):
        # lam* = min(C=2, cut 4): 2.0
        assert capacity_upper_bound(TRI).lam_star == pytest.approx(2.0)
        res = simulate(TRI, PolicyConfig(name="pi1"), lam=1.6, T=4000, seed=0)
        assert _stable(res, 4000)
        assert float(res.useful_rate(1000)) == pytest.approx(1.6, abs=0.2)

    def test_pi1_unstable_above_capacity(self):
        res = simulate(TRI, PolicyConfig(name="pi1"), lam=2.6, T=4000, seed=0)
        q = np.asarray(res.total_queue)
        assert q[-1] > 0.4 * (2.6 - 2.0) * 4000   # linear-ish growth

    def test_pi1_throughput_saturates_at_capacity(self):
        res = simulate(TRI, PolicyConfig(name="pi1"), lam=3.5, T=4000, seed=0)
        assert float(res.useful_rate(1500)) == pytest.approx(2.0, abs=0.25)

    def test_pi1p_threshold_defers_computation(self):
        # With a huge threshold, pi1' never computes (dominance direction of
        # Lemma 2: pi1 backlog <=_st pi1' backlog).
        res_p = simulate(TRI, PolicyConfig(name="pi1p", threshold=1e6),
                         lam=1.5, T=1500, seed=0)
        res_1 = simulate(TRI, PolicyConfig(name="pi1"), lam=1.5, T=1500, seed=0)
        assert float(res_p.final_state.X.sum()) >= float(res_1.final_state.X.sum())
        assert float(res_p.delivered[-1]) == 0.0

    def test_pi1p_moderate_threshold_still_stable(self):
        res = simulate(TRI, PolicyConfig(name="pi1p", threshold=30.0),
                       lam=1.5, T=6000, seed=0)
        assert _stable(res, 6000)

    def test_pi2_regulator_delivers_dummies_but_counts_useful(self):
        res = simulate(TRI, PolicyConfig(name="pi2", eps_b=0.05),
                       lam=1.5, T=4000, seed=0)
        assert _stable(res, 4000)
        assert float(res.delivered[-1]) >= float(res.delivered_useful[-1])
        assert float(res.useful_rate(1500)) == pytest.approx(1.5, abs=0.2)


class TestMultiNode:
    def test_pi3_stable_below_lambda_star(self):
        p = paper_grid_problem(C=2.0)
        res = simulate(p, PolicyConfig(name="pi3", eps_b=0.01),
                       lam=6.0, T=3000, seed=1)
        assert _stable(res, 3000)
        assert float(res.useful_rate(1000)) == pytest.approx(6.0, abs=0.4)

    def test_pi3_unstable_above_lambda_star(self):
        p = paper_grid_problem(C=2.0)
        res = simulate(p, PolicyConfig(name="pi3"), lam=9.0, T=3000, seed=1)
        q = np.asarray(res.total_queue)
        assert q[-1] > q[len(q) // 4] + 0.3 * (9.0 - 8.0) * (3000 * 0.75)

    def test_pi3bar_matches_pi3_capacity(self):
        # §V conjecture: same capacity, fewer packets at light load.
        p = paper_grid_problem(C=2.0)
        r3 = simulate(p, PolicyConfig(name="pi3"), lam=5.0, T=3000, seed=2)
        rb = simulate(p, PolicyConfig(name="pi3bar"), lam=5.0, T=3000, seed=2)
        assert _stable(r3, 3000) and _stable(rb, 3000)
        assert float(rb.avg_queue) <= 1.15 * float(r3.avg_queue)

    def test_pi3_load_balances_across_nodes(self):
        p = paper_grid_problem(C=2.0)
        res = simulate(p, PolicyConfig(name="pi3"), lam=6.0, T=3000, seed=3)
        counts = np.bincount(np.asarray(res.n_star), minlength=4)
        assert counts.min() > 0.10 * counts.sum()   # every node used

    def test_pairing_models_agree_on_throughput(self):
        p = paper_grid_problem(C=2.0)
        fifo = simulate(p, PolicyConfig(name="pi3bar", pairing="fifo"),
                        lam=6.0, T=3000, seed=4)
        bnd = simulate(p, PolicyConfig(name="pi3bar", pairing="bound"),
                       lam=6.0, T=3000, seed=4)
        assert float(fifo.useful_rate(1000)) == pytest.approx(
            float(bnd.useful_rate(1000)), abs=0.5)


class TestInvariants:
    def test_no_negative_queues_and_conservation(self):
        p = paper_grid_problem(C=2.0)
        res = simulate(p, PolicyConfig(name="pi3", eps_b=0.02),
                       lam=7.0, T=1500, seed=5)
        s = res.final_state
        for arr in (s.Q, s.X, s.Y, s.H, s.Ddum):
            assert float(jnp.min(arr)) >= -1e-3
        # dummy content never exceeds its processed queue
        nidx = np.arange(4)
        assert np.all(np.asarray(s.Ddum) <= np.asarray(s.Q[:, 0, :]) + 1e-3)
        # pairs combined never exceed arrivals on either side
        assert np.all(np.asarray(s.cum_comb)[None].T <= np.asarray(s.cum_arr) + 1e-3)


class TestWireless:
    """Paper §IV-C: pi3 under node-exclusive interference with greedy
    maximal matching link activation (refs [17, 18])."""

    def test_matching_is_valid_and_maximal(self):
        import jax.numpy as jnp
        from repro.core.policies import greedy_maximal_matching
        edges = jnp.array([[0, 1], [1, 2], [2, 3], [3, 0], [0, 2]])
        w = jnp.array([5.0, 4.0, 3.0, 2.0, 1.0])
        sel = np.asarray(greedy_maximal_matching(edges, w, 4))
        # (0,1) picked first, blocks (1,2) and (3,0) and (0,2); (2,3) fits
        np.testing.assert_array_equal(sel, [True, False, True, False, False])
        # node-exclusive: no two selected edges share a node
        used = np.zeros(4, int)
        for e, s in zip(np.asarray(edges), sel):
            if s:
                used[e[0]] += 1
                used[e[1]] += 1
        assert used.max() <= 1

    def test_zero_weight_links_stay_idle(self):
        import jax.numpy as jnp
        from repro.core.policies import greedy_maximal_matching
        edges = jnp.array([[0, 1], [2, 3]])
        sel = np.asarray(greedy_maximal_matching(
            edges, jnp.array([0.0, 1.0]), 4))
        np.testing.assert_array_equal(sel, [False, True])

    @pytest.mark.parametrize("wireless", [True, False],
                             ids=["wireless", "wired"])
    def test_route_slot_reports_match_rounds(self, wireless):
        """`match_rounds`, the matching's rounds to its fixpoint, is in
        1..E on a wireless slot and absent on a wired one."""
        from repro.core import StaticProblem, init_state
        from repro.core.policies import bp_route_slot
        sp = StaticProblem.build(paper_grid_problem())
        rng = np.random.default_rng(3)
        state = init_state(sp)
        state = state._replace(Q=jnp.asarray(
            rng.integers(0, 20, size=state.Q.shape), state.Q.dtype))
        _, m = bp_route_slot(sp, state, wireless=wireless)
        if wireless:
            assert 1 <= int(m["match_rounds"]) <= sp.edges.shape[0]
        else:
            assert "match_rounds" not in m

    def test_wireless_pi3_stable_at_low_rate(self):
        p = paper_grid_problem(C=2.0)
        res = simulate(p, PolicyConfig(name="pi3", wireless=True),
                       lam=1.5, T=3000, seed=6)
        assert _stable(res, 3000)
        assert float(res.useful_rate(1000)) == pytest.approx(1.5, abs=0.3)

    def test_wireless_capacity_below_wired(self):
        """Interference shrinks the rate region: at a rate the wired system
        sustains, the wireless one saturates lower."""
        p = paper_grid_problem(C=3.0)
        wired = simulate(p, PolicyConfig(name="pi3bar"), lam=9.0, T=3000,
                         seed=7)
        wless = simulate(p, PolicyConfig(name="pi3bar", wireless=True),
                         lam=9.0, T=3000, seed=7)
        assert float(wless.useful_rate(1000)) < float(wired.useful_rate(1000))
        q = np.asarray(wless.total_queue)
        assert q[-1] > q[len(q) // 2]       # backlog grows: above wireless cap
