"""Behavioural tests for the paper's policies on small networks."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ComputeProblem, PolicyConfig, StaticProblem,
                        capacity_upper_bound, init_state, paper_grid_problem,
                        triangle_graph)
from repro.core.policies import _matching_fixpoint, bp_route_slot
from repro.fleet import PadDims, pad_problem
from repro.fleet.scenarios import kary_fat_tree
from repro.kernels.bp_slot.ref import slot_route_ref
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


# ---------------------------------------------------------------------------
# The route's apply half: incidence contractions against the scatter form
# ---------------------------------------------------------------------------

def _route(sp, state, wireless):
    """`bp_route_slot` with the scatter form of the apply half below: the
    decision as bp_route_slot takes it, then the fluid moved and the
    deliveries credited."""
    m_idx = jnp.asarray(sp.edges[:, 0])
    l_idx = jnp.asarray(sp.edges[:, 1])
    cap = jnp.asarray(sp.edge_cap)
    best, dmax = slot_route_ref(state.Q.reshape(state.Q.shape[0], -1),
                                m_idx, l_idx)
    alloc = cap * (jnp.abs(dmax) > 0)
    weight = jnp.abs(dmax) * (cap > 0)
    if sp.edge_mask is not None:
        emask = jnp.asarray(sp.edge_mask, jnp.float32)
        alloc, weight = alloc * emask, weight * emask
    if wireless:
        alloc = alloc * _matching_fixpoint(jnp.asarray(sp.edges), weight)[0]
    src = jnp.where(dmax > 0, m_idx, l_idx)
    dst = jnp.where(dmax > 0, l_idx, m_idx)
    Q, Ddum, X, cum_arr, actual, moved_dummy, is_sink = _scatter_apply(
        sp, state, src, dst, best, alloc)
    proc_sink = is_sink & (best // sp.n_comp == 0)
    dlv = jnp.sum(actual * proc_sink)
    dlv_useful = jnp.sum((actual - moved_dummy) * proc_sink)
    new = state._replace(Q=Q, Ddum=Ddum, X=X, cum_arr=cum_arr)
    return new.credit_delivery(dlv, dlv_useful)


def _scatter_apply(sp, state, src, dst, best, alloc):
    """The apply half as it was written with per-link scatter-adds and
    gathers: the form the slots must reproduce, to the bit."""
    Q, Ddum, X = state.Q, state.Ddum, state.X
    best_i, best_n = best // sp.n_comp, best % sp.n_comp
    total_out = jnp.zeros_like(Q).at[src, best_i, best_n].add(alloc)
    scale = jnp.where(total_out > Q, Q / jnp.maximum(total_out, 1e-20), 1.0)
    actual = alloc * scale[src, best_i, best_n]
    q0_src = Q[src, 0, best_n]
    frac_dummy = jnp.where(q0_src > 0,
                           Ddum[src, best_n] / jnp.maximum(q0_src, 1e-20),
                           0.0)
    moved_dummy = actual * frac_dummy * (best_i == 0)
    Q = Q.at[src, best_i, best_n].add(-actual)
    Ddum = Ddum.at[src, best_n].add(-moved_dummy)
    is_sink = jnp.asarray(sp.sink)[dst, best_i, best_n]
    Q = Q.at[dst, best_i, best_n].add(actual * (~is_sink))
    Ddum = Ddum.at[dst, best_n].add(moved_dummy * (~is_sink))
    to_X = actual * (is_sink & (best_i >= 1))
    X = X.at[best_n, jnp.maximum(best_i - 1, 0)].add(to_X)
    cum_arr = state.cum_arr.at[best_n, jnp.maximum(best_i - 1, 0)].add(to_X)
    return Q, Ddum, X, cum_arr, actual, moved_dummy, is_sink


def _route_case(case):
    """(problem, batched) for one case: a StaticProblem with constant
    edges, or a stack of padded lanes with traced per-lane edges."""
    if case == "grid":
        return StaticProblem.build(paper_grid_problem()), False
    if case.startswith("fattree_k"):
        return StaticProblem.build(kary_fat_tree(int(case[-1]))), False
    # Padded lanes: (0, 0) self-loops past the real links, padded comp
    # slots, and in the last lane a real link down (zero capacity).
    problems = [paper_grid_problem(), kary_fat_tree(4), paper_grid_problem()]
    dims = PadDims(max(p.graph.n_nodes for p in problems) + 2,
                   max(p.graph.n_edges for p in problems) + 5,
                   max(p.n_comp for p in problems) + 1)
    lanes = [pad_problem(p, dims) for p in problems]
    lanes[-1] = dataclasses.replace(
        lanes[-1], edge_cap=lanes[-1].edge_cap.at[7].set(0.0))
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *lanes), True


def _hops(edges, n_nodes):
    """Hop counts between every pair of nodes (0 where unreachable)."""
    adj = np.zeros((n_nodes, n_nodes), bool)
    adj[edges[:, 0], edges[:, 1]] = adj[edges[:, 1], edges[:, 0]] = True
    hops = np.zeros((n_nodes, n_nodes))
    seen = np.eye(n_nodes, dtype=bool)
    front = seen.copy()
    for d in range(1, n_nodes):
        front = (front.astype(int) @ adj.astype(int) > 0) & ~seen
        hops[front] = d
        seen |= front
    return hops


def _route_state(sp, rng, relay):
    """A backlog with every sink-bound class fed by its neighbours, and a
    dummy share in every processed queue.  `relay` adds a steep slope
    down to the sink of one processed class, so queues relay it: one
    queue both sends and receives in the slot.  `drain` keeps queues
    below a link's rate instead, so links empty them and the outflow
    cap binds."""
    sink = np.asarray(sp.sink)
    edges = np.asarray(sp.edges)
    if relay == "drain":
        Q = rng.uniform(0.0, 3.0, size=sink.shape).astype(np.float32)
        Q *= rng.random(sink.shape) < 0.5
        relay = False
    else:
        Q = rng.uniform(0.0, 40.0, size=sink.shape).astype(np.float32)
        Q *= rng.random(sink.shape) < 0.7
        Q[:, 0] += 1.0  # so a link into d outweighs its rivals (wireless)
        boost = np.array([400.0, 60.0, 60.0])[:, None]
        for m, l in edges:                   # sink-bound classes, backed up
            Q[m] += boost * sink[l]
            Q[l] += boost * sink[m]
    if relay:
        n = rng.integers(sink.shape[-1])
        for node in np.flatnonzero(sink[:, 0, n]):
            hops = _hops(edges, sink.shape[0])[node]
            Q[:, 0, n] += 120.0 * hops * rng.uniform(0.9, 1.1, len(hops))
    Q[sink] = 0.0
    Ddum = Q[:, 0, :] * rng.uniform(0.0, 0.5, size=Q[:, 0, :].shape)
    NC = sink.shape[-1]
    state = init_state(sp)
    return state._replace(
        Q=jnp.asarray(Q), Ddum=jnp.asarray(Ddum, jnp.float32),
        X=jnp.asarray(rng.integers(0, 9, (NC, 2)), jnp.float32),
        cum_arr=jnp.asarray(rng.integers(9, 30, (NC, 2)), jnp.float32))


def _relays(sp, state):
    """How many (node, class) queues both send and receive on the links
    the decision turns on (before any matching)."""
    Q = state.Q.reshape(state.Q.shape[0], -1)
    m, l = np.asarray(sp.edges[:, 0]), np.asarray(sp.edges[:, 1])
    best, dmax = map(np.asarray, slot_route_ref(Q, jnp.asarray(m),
                                                jnp.asarray(l)))
    on = np.abs(dmax) > 0
    src = np.where(dmax > 0, m, l)[on]
    dst = np.where(dmax > 0, l, m)[on]
    return len(set(zip(src, best[on])) & set(zip(dst, best[on])))


def _total(x):
    """A sum in float64, so a conservation check sees the apply half's
    rounding, not the sum's."""
    return float(np.sum(np.asarray(x, np.float64)))


def _mass(s):
    return _total(s.Q) + _total(s.X) + _total(s.delivered)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("wireless", [False, True],
                         ids=["wired", "wireless"])
@pytest.mark.parametrize("case", ["grid", "fattree_k4", "fattree_k8",
                                  "padded"])
@pytest.mark.parametrize("relay", [False, True, "drain"],
                         ids=["backlog", "relay", "drain"])
def test_route_apply_matches_scatter_form(relay, case, wireless, seed):
    """`bp_route_slot`'s apply half against the scatter form: the slots
    add the links that share a queue in link order, as the scatters do,
    so the two agree bit for bit, and mass is conserved: what leaves a
    queue reaches a queue, X (raw at its comp node) or `delivered`."""
    sp, batched = _route_case(case)
    rng = np.random.default_rng(seed)
    # Unequal link rates, so the order in which links that share a queue
    # are added shows in the last bit.
    cap = np.asarray(sp.edge_cap)
    sp = dataclasses.replace(sp, edge_cap=jnp.asarray(
        cap * rng.uniform(0.5, 1.5, cap.shape), jnp.float32))
    forms = {"got": lambda p, s: bp_route_slot(p, s, wireless=wireless)[0],
             "want": lambda p, s: _route(p, s, wireless)}
    if batched:
        lanes = sp.edges.shape[0]
        one = lambda i: jax.tree_util.tree_map(lambda x: x[i], sp)
        state = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs),
            *[_route_state(one(i), rng, relay) for i in range(lanes)])
        relays = sum(_relays(one(i), jax.tree_util.tree_map(
            lambda x: x[i], state)) for i in range(lanes))
        out = {k: jax.jit(jax.vmap(f))(sp, state) for k, f in forms.items()}
    else:
        state = _route_state(sp, rng, relay)
        relays = _relays(sp, state)
        out = {k: jax.jit(lambda s, f=f: f(sp, s))(state)
               for k, f in forms.items()}
    got, want = out["got"], out["want"]
    for name in ("Q", "Ddum", "X", "cum_arr", "delivered",
                 "delivered_useful"):
        np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                      np.asarray(getattr(want, name)),
                                      err_msg=name)
    # The state exercises what the slots must get right.
    moved = np.asarray(state.Q) - np.asarray(got.Q)
    assert np.abs(moved).max() > 0
    if relay == "drain":       # the cap binds: a queue sends all it holds
        q0, q1 = np.asarray(state.Q), np.asarray(got.Q)
        assert np.any((q0 > 0) & (np.abs(q1) <= 1e-5 * q0))
    elif relay:
        assert relays > 0
    else:
        assert float(jnp.sum(got.X - state.X)) > 0       # raw absorbed
        assert float(jnp.sum(got.delivered)) > 0         # processed at d
        assert float(jnp.sum(got.delivered - got.delivered_useful)) > 0
    for lane in range(state.Q.shape[0] if batched else 1):
        pick = (lambda t: jax.tree_util.tree_map(lambda x: x[lane], t)) \
            if batched else (lambda t: t)
        before, after = pick(state), pick(got)
        scale = float(jnp.max(before.Q))
        assert _mass(after) == pytest.approx(_mass(before), abs=1e-5 * scale
                                             * before.Q.size ** 0.5)
        dummies = lambda s: (_total(s.Ddum) + _total(s.delivered)
                             - _total(s.delivered_useful))
        assert dummies(after) == pytest.approx(dummies(before),
                                               abs=1e-5 * scale)


@pytest.mark.parametrize("case", ["grid", "fattree_k4", "fattree_k8",
                                  "padded"])
def test_link_places_follow_link_order_at_each_node(case):
    """`edge_places`: at every node, the real links that meet it hold the
    places 0, 1, ... in link order, below `max_degree`; the slots of the
    apply half rest on that."""
    sp, batched = _route_case(case)
    lanes = sp.edges.shape[0] if batched else 1
    for lane in range(lanes):
        one = (jax.tree_util.tree_map(lambda x: x[lane], sp) if batched
               else sp)
        edges = np.asarray(one.edges)
        real = (np.ones(len(edges), bool) if one.edge_mask is None
                else np.asarray(one.edge_mask) > 0)
        places = np.asarray(one.edge_places)
        for node in range(one.n_nodes):
            at = [places[e, 0] if edges[e, 0] == node else places[e, 1]
                  for e in np.flatnonzero(real) if node in edges[e]]
            assert at == list(range(len(at)))
            assert len(at) <= one.max_degree


def test_pad_dims_bound_the_places():
    """`PadDims.of` takes the most links at one node over its problems,
    `pad_problem` carries it as the lanes' static `max_degree`, and a
    bound below an instance's degree is refused, not truncated."""
    problems = [paper_grid_problem(), kary_fat_tree(4)]
    dims = PadDims.of(problems)
    assert dims.max_degree == max(StaticProblem.build(p).max_degree
                                  for p in problems)
    assert StaticProblem.build(paper_grid_problem()).max_degree == 4
    lanes = [pad_problem(p, dims) for p in problems]
    assert {lane.max_degree for lane in lanes} == {dims.max_degree}
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *lanes)
    assert stacked.max_degree == dims.max_degree
    assert dims.fits(kary_fat_tree(4))
    low = dataclasses.replace(dims, max_degree=3)
    assert not low.fits(kary_fat_tree(4))
    with pytest.raises(ValueError, match="max_degree"):
        pad_problem(kary_fat_tree(4), low)
    loose = dataclasses.replace(dims, max_degree=None)
    assert loose.places == dims.n_edges
    assert pad_problem(kary_fat_tree(4), loose).max_degree == dims.n_edges
