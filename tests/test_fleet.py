"""Fleet subsystem tests: padded batching equivalence, mask semantics,
streaming (chunked-scan) metrics vs the trace simulator, scenario registry,
and the sharded engine smoke run (marker: fleet_smoke)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ComputeProblem, PolicyConfig, paper_grid_problem,
                        triangle_graph)
from repro.core.policies import bp_route_slot, load_balance_slot
from repro.core.queues import StaticProblem, init_state
from repro.sim import SimResult, simulate
from repro.sim.simulator import make_trace_runner
from repro.sim.workload import poisson_arrivals
from repro.fleet import (FleetJob, ModState, PadDims, exact_lam_star,
                         get_scenario, list_scenarios, make_group_launch,
                         make_stream_runner, pad_problem, policy_bound,
                         policy_bound_exact, run_fleet, stack_problems,
                         stream_simulate, sweep_jobs)
from repro.fleet.batching import lane_batch, mirror_replicas, pad_topologies
from repro.fleet.scenarios import (ARRIVAL_MODELS, EVENT_MODELS,
                                   EVENT_MODEL_ORDER, GE_BAD_SCALE,
                                   GE_COMP_P_DU, GE_COMP_P_UD, GE_P_BG,
                                   GE_P_GB, MMPP_P_OFF_ON, MMPP_P_ON_OFF,
                                   SCENARIOS, event_code)

TRI = ComputeProblem(triangle_graph(4.0), s1=0, s2=1, dest=2,
                     comp_nodes=(2,), comp_caps=(2.0,))


# ---------------------------------------------------------------------------
# useful_rate regression (satellite: off-by-one / wraparound)
# ---------------------------------------------------------------------------

class TestUsefulRate:
    def _result(self, du):
        du = jnp.asarray(du, jnp.float32)
        zeros = jnp.zeros_like(du)
        return SimResult(None, zeros, du, du, zeros, zeros)

    def test_constant_rate_for_every_window(self):
        """With one delivery per slot, every window must report rate 1."""
        T = 16
        res = self._result(jnp.arange(1, T + 1))
        for w in [1, 2, T // 2, T - 2, T - 1, T, T + 5, None]:
            assert float(res.useful_rate(w)) == pytest.approx(1.0)

    def test_boundary_window_does_not_wrap(self):
        """A huge early value must not leak into a trailing window via
        negative-index wraparound."""
        d = np.zeros(10, np.float32)
        d[0] = 1e6                     # burst in slot 0
        d = np.cumsum(np.r_[d[:1], np.ones(9, np.float32)]) - 1 + d[0]
        res = self._result(d)
        # windows that exclude slot 0 only see the 1-per-slot tail
        for w in (1, 4, 8):
            assert float(res.useful_rate(w)) == pytest.approx(1.0)
        # the full trace includes the burst
        assert float(res.useful_rate(None)) > 1e4


# ---------------------------------------------------------------------------
# Padded batching
# ---------------------------------------------------------------------------

class TestPaddedBatching:
    def test_exact_dims_match_seed_all_policies(self):
        """Padding with the instance's own dims is a pure re-encoding: every
        policy reproduces the seed simulator bit-for-bit."""
        p = paper_grid_problem()
        T = 150
        key = jax.random.key(0)
        ak, sk = jax.random.split(key)
        arr = poisson_arrivals(ak, 5.0, T)
        pp = pad_problem(p, PadDims(p.graph.n_nodes, p.graph.n_edges, p.n_comp))
        for name in ("pi1", "pi2", "pi3", "pi3bar"):
            cfg = PolicyConfig(name=name)
            r_seed = simulate(p, cfg, 5.0, T, seed=0)
            r_pad = make_trace_runner(pp, cfg)(arr, sk)
            np.testing.assert_allclose(np.asarray(r_seed.total_queue),
                                       np.asarray(r_pad.total_queue),
                                       rtol=1e-6, err_msg=name)

    def test_padding_is_inert_for_keyfree_policies(self):
        """Extra padded nodes/edges/comp slots change nothing for policies
        that draw no randomness (the regulator's per-node draw is shape-
        sensitive, so pi2/pi3 are only statistically equivalent)."""
        p = paper_grid_problem()
        T = 150
        key = jax.random.key(1)
        ak, sk = jax.random.split(key)
        arr = poisson_arrivals(ak, 5.0, T)
        big = pad_problem(p, PadDims(24, 48, 7))
        for name in ("pi1", "pi3bar"):
            cfg = PolicyConfig(name=name)
            r_seed = simulate(p, cfg, 5.0, T, seed=1)
            r_pad = make_trace_runner(big, cfg)(arr, sk)
            np.testing.assert_allclose(np.asarray(r_seed.total_queue),
                                       np.asarray(r_pad.total_queue),
                                       rtol=1e-6, err_msg=name)
            np.testing.assert_allclose(
                float(r_seed.delivered_useful[-1]),
                float(r_pad.delivered_useful[-1]), rtol=1e-6)

    def test_stacked_batch_vmaps(self):
        problems = [TRI, paper_grid_problem(),
                    get_scenario("ring").build(0)]
        batch = stack_problems(problems)
        assert batch.edges.shape[0] == 3
        cfg = PolicyConfig(name="pi3bar")
        T = 64
        arr = jnp.ones((3, T), jnp.float32)
        keys = jax.random.split(jax.random.key(0), 3)

        def run_one(pp, a, k):
            return make_trace_runner(pp, cfg)(a, k).delivered_useful[-1]

        out = jax.vmap(run_one)(batch, arr, keys)
        assert out.shape == (3,)
        assert np.all(np.asarray(out) >= 0.0)

    @pytest.mark.parametrize("B,ndev", [(3, 1), (3, 4), (5, 2)])
    def test_lane_batch_pads_the_mesh_with_the_last_lane(self, B, ndev):
        """Every leaf and per-lane constant of a mesh-padding replica is
        the last real lane's; `mirror_replicas` keeps host tables so."""
        scen = ["paper_grid", "ring", "ge_grid", "paper_grid", "ring"]
        jobs = [FleetJob(scenario=scen[i], lam=1.0 + i, seed=2 ** 31 - 1 - i,
                         eps_b=0.01 * (i + 1)) for i in range(B)]
        padded_of, _, _ = pad_topologies(jobs)
        batch = lane_batch(jobs, padded_of, ndev)
        Bp = -(-B // ndev) * ndev
        assert (batch.B, batch.Bp) == (B, Bp)
        want = jobs + [jobs[-1]] * (Bp - B)
        np.testing.assert_array_equal(
            np.asarray(batch.lam), np.float32([j.lam for j in want]))
        np.testing.assert_array_equal(
            np.asarray(batch.eps), np.float32([j.eps_b for j in want]))
        np.testing.assert_array_equal(
            np.asarray(batch.ek),
            [event_code(get_scenario(j.scenario).events) for j in want])
        np.testing.assert_array_equal(
            np.asarray(batch.keys),
            np.stack([jax.random.PRNGKey(j.seed) for j in want]))
        for lane, j in enumerate(want):
            one = padded_of[(j.scenario, j.topo_seed)]
            for got, ref in zip(jax.tree_util.tree_leaves(batch.pp),
                                jax.tree_util.tree_leaves(one)):
                np.testing.assert_array_equal(np.asarray(got)[lane], ref)
        assert len(batch.step_args) == 6
        bare = lane_batch(jobs, padded_of, ndev, arrivals=False, rates=False)
        assert [a is b for a, b in zip(bare.step_args,
                                       (bare.pp, bare.eps, bare.ek))] == \
            [True] * 3
        table = np.arange(Bp)
        mirror_replicas(B, table)
        assert table.tolist() == list(range(B)) + [B - 1] * (Bp - B)

    def test_masked_edge_carries_no_flow(self):
        """Zeroing an edge's mask is equivalent to removing the link."""
        import dataclasses as _dc
        sp = StaticProblem.build(TRI)
        state = init_state(sp)
        # put backlog on node 0 so the (0,1) and (0,2) links want to fire
        state = state._replace(Q=state.Q.at[0, 1, 0].set(50.0))
        masked = _dc.replace(sp, edge_mask=np.array([0.0, 0.0, 1.0], np.float32))
        new_masked, _ = bp_route_slot(masked, state)
        new_open, _ = bp_route_slot(sp, state)
        # with links (0,1), (0,2) masked, node 0's raw backlog cannot move
        assert float(new_masked.Q[0, 1, 0]) == pytest.approx(50.0)
        assert float(new_open.Q[0, 1, 0]) < 50.0

    def test_zero_capacity_link_frees_wireless_matching_slot(self):
        """A link whose capacity an event model zeroed must not win a
        greedy-matching slot and idle its endpoints (reviewed regression)."""
        import dataclasses as _dc
        from repro.core import line_graph
        p = ComputeProblem(line_graph(3, 1.0), 0, 1, 2, (1,), (1.0,))
        sp = StaticProblem.build(p)
        down = _dc.replace(sp, edge_cap=np.array([0.0, 1.0], np.float32))
        state = init_state(sp)
        # edge (0,1) has the larger differential backlog but zero capacity;
        # edge (1,2) must still transmit even though it shares node 1
        state = state._replace(
            Q=state.Q.at[0, 1, 0].set(50.0).at[1, 2, 0].set(30.0))
        new, _ = bp_route_slot(down, state, wireless=True)
        assert float(new.Q[1, 2, 0]) < 30.0

    def test_masked_comp_node_never_selected(self):
        p = paper_grid_problem()
        sp = StaticProblem.build(p)
        import dataclasses as _dc
        mask = np.array([1.0, 0.0, 1.0, 0.0], np.float32)
        masked = _dc.replace(sp, comp_mask=mask)
        cfg = PolicyConfig(name="pi3")
        state = init_state(sp)
        picks = set()
        for a in range(20):
            _, _, m = load_balance_slot(masked, cfg, state,
                                        jnp.float32(1.0 + a))
            picks.add(int(m["n_star"]))
        assert picks <= {0, 2}

    def test_regulator_inert_on_padded_comp_slots(self):
        """Padded (masked-out) computation slots must never accumulate
        regulator state or push dummies: the regulator sees assigned == 0
        there, so Y and Ddum stay exactly zero (the regulator-as-padding
        correspondence, DESIGN.md §2/§3)."""
        p = paper_grid_problem()
        nc = p.n_comp
        big = pad_problem(p, PadDims(20, 30, nc + 3))
        out = stream_simulate(p, PolicyConfig(name="pi3_reg", eps_b=0.2),
                              lam=4.0, T=300, chunk=100, seed=5,
                              dims=PadDims(20, 30, nc + 3))
        assert float(out["delivered_useful"]) > 0.0
        # reach the final NetState through the reference trace path
        arr = poisson_arrivals(jax.random.key(0), 4.0, 300)
        res = make_trace_runner(big, PolicyConfig(name="pi3_reg", eps_b=0.2))(
            arr, jax.random.key(1))
        final = res.final_state
        assert np.all(np.asarray(final.Y[nc:]) == 0.0)
        assert np.all(np.asarray(final.H[nc:]) == 0.0)
        assert np.all(np.asarray(final.Ddum[:, nc:]) == 0.0)


# ---------------------------------------------------------------------------
# Streaming engine (chunked scan + online accumulators)
# ---------------------------------------------------------------------------

class TestStreaming:
    def test_matches_trace_simulator_100k_slots(self):
        """Acceptance: T=100k chunked-scan run matches the seed simulator's
        delivered_useful on an identical arrival trace to <= 1e-3 relative."""
        T = 100_000
        cfg = PolicyConfig(name="pi3bar")
        key = jax.random.key(3)
        arr = poisson_arrivals(key, 1.5, T)
        r_seed = simulate(TRI, cfg, 1.5, T, seed=3, arrivals=arr)
        out = stream_simulate(TRI, cfg, 1.5, T, chunk=1000, seed=3,
                              arrivals=arr)
        du_seed = float(r_seed.delivered_useful[-1])
        du_stream = float(out["delivered_useful"])
        assert abs(du_seed - du_stream) / max(du_seed, 1.0) <= 1e-3
        # windowed rate consistency with the trace-side computation
        assert float(out["useful_rate"]) == pytest.approx(
            float(r_seed.useful_rate(T // 2)), rel=1e-3)

    def test_no_T_shaped_metric_arrays(self):
        """The compiled streaming program must hold no array with a horizon-
        sized dimension: metrics are online accumulators only."""
        T, chunk = 100_000, 1000
        cfg = PolicyConfig(name="pi3")
        run = make_stream_runner(cfg, T, chunk=chunk)
        pp = pad_problem(TRI, PadDims.of([TRI]))
        jaxpr = jax.make_jaxpr(
            functools.partial(run, arrivals=None))(
                pp, jnp.float32(1.0), jnp.float32(0.01), jnp.int32(0),
                jnp.int32(0), jax.random.PRNGKey(0))

        def max_dim(jxp):
            dims = [0]
            for eqn in jxp.eqns:
                for v in list(eqn.outvars) + list(eqn.invars):
                    aval = getattr(v, "aval", None)
                    if aval is not None and getattr(aval, "shape", None):
                        dims.extend(d for d in aval.shape
                                    if isinstance(d, int))
                for p in eqn.params.values():
                    inner = getattr(p, "jaxpr", None)
                    if inner is not None:
                        dims.append(max_dim(inner))
            return max(dims)

        biggest = max_dim(jaxpr.jaxpr)
        assert biggest < chunk + 1, (
            f"streaming program materializes a {biggest}-sized axis")

    def test_stability_verdict(self):
        # far below capacity: stable; far above: unstable
        cfg = PolicyConfig(name="pi3bar")
        lo = stream_simulate(TRI, cfg, 1.0, 3000, chunk=500, seed=0)
        hi = stream_simulate(TRI, cfg, 4.0, 3000, chunk=500, seed=0)
        assert float(lo["stable"]) == 1.0
        assert float(hi["stable"]) == 0.0
        assert float(hi["mean_queue_tail"]) > float(hi["mean_queue_mid"])

    def test_horizon_rounds_up_to_chunks(self):
        run = make_stream_runner(PolicyConfig(name="pi1"), T=1001, chunk=100)
        assert run.T == 1100 and run.chunk == 100


# ---------------------------------------------------------------------------
# Scenario registry
# ---------------------------------------------------------------------------

class TestScenarios:
    def test_registry_contents(self):
        names = list_scenarios()
        for expected in ("paper_grid", "random_geometric", "ring", "tree",
                         "expander", "fat_tree", "wireless_grid"):
            assert expected in names

    @pytest.mark.parametrize("name", sorted(SCENARIOS))
    def test_every_scenario_builds_valid_problems(self, name):
        for seed in (0, 1):
            p = get_scenario(name).build(seed)
            assert isinstance(p, ComputeProblem)
            # endpoints distinct enough to pose a real routing problem
            assert p.s1 != p.s2
            assert p.n_comp >= 1
            # connected: BFS from s1 reaches everything
            adj = [[] for _ in range(p.graph.n_nodes)]
            for m, l in p.graph.edges:
                adj[m].append(int(l))
                adj[l].append(int(m))
            seen, stack = {p.s1}, [p.s1]
            while stack:
                for v in adj[stack.pop()]:
                    if v not in seen:
                        seen.add(v)
                        stack.append(v)
            assert len(seen) == p.graph.n_nodes, f"{name} disconnected"

    def test_topology_seeds_vary_random_graphs(self):
        a = get_scenario("random_geometric").build(0)
        b = get_scenario("random_geometric").build(1)
        assert (a.graph.n_edges != b.graph.n_edges or
                not np.array_equal(a.graph.edges, b.graph.edges))

    def test_event_models_shapes_and_ranges(self):
        pp = pad_problem(TRI, PadDims.of([TRI]))
        key = jax.random.key(0)
        mod0 = ModState.init(pp)
        for name in EVENT_MODEL_ORDER:
            es, cs, mod = EVENT_MODELS[name](pp, jnp.int32(17), key, mod0)
            assert es.shape == (pp.n_edges,)
            assert cs.shape == (pp.n_comp,)
            assert mod.link.shape == mod0.link.shape
            assert float(es.min()) >= 0.0 and float(es.max()) <= 1.0 + 1e-6
            assert float(cs.min()) >= 0.0 and float(cs.max()) <= 1.0 + 1e-6
        # static model is the identity and passes the state through untouched
        es, cs, mod = EVENT_MODELS["static"](pp, jnp.int32(0), key, mod0)
        assert float(es.min()) == 1.0 and float(cs.min()) == 1.0
        assert mod is mod0

    def test_gilbert_elliott_stationary_bad_fraction(self):
        """The per-link Good/Bad chain must mix to P(Bad) = P_GB/(P_GB+P_BG)
        and emit only the two scales {bad_scale, 1}."""
        pp = pad_problem(TRI, PadDims.of([TRI]))
        ge = EVENT_MODELS["gilbert_elliott"]

        def body(carry, k):
            es, _, mod = ge(pp, jnp.int32(0), k, carry)
            return mod, es

        T = 4000
        keys = jax.random.split(jax.random.key(7), T)
        _, scales = jax.lax.scan(body, ModState.init(pp), keys)
        vals = np.unique(np.asarray(scales).round(6))
        assert set(vals) <= {np.float32(GE_BAD_SCALE), np.float32(1.0)}
        # drop the burn-in, compare against the stationary distribution
        bad = np.asarray(scales[T // 4:] < 0.5).mean()
        pi_bad = GE_P_GB / (GE_P_GB + GE_P_BG)
        assert bad == pytest.approx(pi_bad, abs=0.03)

    def test_gilbert_elliott_outages_are_correlated(self):
        """Consecutive-slot Bad states must co-occur far more often than the
        i.i.d. square of the marginal (the point of the Markov model)."""
        pp = pad_problem(TRI, PadDims.of([TRI]))
        ge = EVENT_MODELS["gilbert_elliott"]

        def body(carry, k):
            es, _, mod = ge(pp, jnp.int32(0), k, carry)
            return mod, es < 0.5

        T = 4000
        keys = jax.random.split(jax.random.key(3), T)
        _, bad = jax.lax.scan(body, ModState.init(pp), keys)
        bad = np.asarray(bad[T // 4:])
        p_bad = bad.mean()
        p_joint = (bad[1:] & bad[:-1]).mean()
        # Markov chain: P(bad, bad) = pi_bad * (1 - P_BG) >> pi_bad^2
        assert p_joint > 3.0 * p_bad ** 2

    def test_ge_comp_chain_stationarity(self):
        """The per-comp-node Up/Down chain must mix to the chain's stationary
        distribution P(Up) = P_DU/(P_UD+P_DU), emit only {0, 1} scales, and
        produce multi-slot outages (the correlated regime)."""
        pp = pad_problem(paper_grid_problem(), PadDims(16, 24, 4))
        ge = EVENT_MODELS["ge_comp"]

        def body(carry, k):
            es, cs, mod = ge(pp, jnp.int32(0), k, carry)
            return mod, (es, cs)

        T = 8000
        keys = jax.random.split(jax.random.key(5), T)
        _, (es, cs) = jax.lax.scan(body, ModState.init(pp), keys)
        assert np.asarray(es).min() == 1.0          # links untouched
        vals = np.unique(np.asarray(cs))
        assert set(vals) <= {np.float32(0.0), np.float32(1.0)}
        up = np.asarray(cs[T // 4:])                # drop the burn-in
        pi_up = GE_COMP_P_DU / (GE_COMP_P_UD + GE_COMP_P_DU)
        assert up.mean() == pytest.approx(pi_up, abs=0.03)
        # outages persist: consecutive Down slots co-occur far above iid^2
        down = up < 0.5
        p_down = down.mean()
        p_joint = (down[1:] & down[:-1]).mean()
        assert p_joint > 3.0 * p_down ** 2

    def test_ge_full_advances_both_chains(self):
        pp = pad_problem(paper_grid_problem(), PadDims(16, 24, 4))
        ge = EVENT_MODELS["ge_full"]

        def body(carry, k):
            es, cs, mod = ge(pp, jnp.int32(0), k, carry)
            return mod, (es, cs)

        keys = jax.random.split(jax.random.key(2), 2000)
        _, (es, cs) = jax.lax.scan(body, ModState.init(pp), keys)
        assert np.asarray(es).min() == pytest.approx(GE_BAD_SCALE)  # links fade
        assert np.asarray(cs).min() == 0.0                          # nodes fail

    def test_markov_onoff_arrivals_preserve_mean_and_burst(self):
        """Long-run mean must equal lam; ON/OFF runs must be multi-slot."""
        lam = 2.0
        arr_fn = ARRIVAL_MODELS["markov_onoff"]
        pp = pad_problem(TRI, PadDims.of([TRI]))

        def body(carry, k):
            a, mod = arr_fn(k, jnp.float32(lam), carry)
            return mod, (a, mod.burst)

        T = 20000
        keys = jax.random.split(jax.random.key(11), T)
        _, (arr, on) = jax.lax.scan(body, ModState.init(pp), keys)
        arr, on = np.asarray(arr), np.asarray(on)
        assert arr.mean() == pytest.approx(lam, rel=0.05)
        pi_on = MMPP_P_OFF_ON / (MMPP_P_ON_OFF + MMPP_P_OFF_ON)
        assert on.mean() == pytest.approx(pi_on, abs=0.05)
        assert np.all(arr[on < 0.5] == 0.0)          # OFF slots are silent
        # mean ON-run length 1/P_OFF: count runs via transitions
        flips = np.abs(np.diff(on)).sum()
        mean_run = len(on) / max(flips, 1)
        assert mean_run > 3.0                        # i.i.d. would give ~1-2


# ---------------------------------------------------------------------------
# Sharded engine (CI smoke: works on 1 device; scripts/test.sh gives it 8)
# ---------------------------------------------------------------------------

@pytest.mark.fleet_smoke
class TestFleetEngine:
    def test_sweep_mixed_scenarios_one_program_per_policy(self):
        jobs = [FleetJob(scenario=s, policy=pol, lam=lam, seed=seed)
                for s in ("paper_grid", "ring", "fat_tree")
                for pol in ("pi3", "pi3bar")
                for lam in (1.0, 2.5)
                for seed in (0,)]
        res = run_fleet(jobs, T=256, chunk=64)
        assert res.n_sims == len(jobs) == 12
        # one compiled program per policy group, not per topology
        assert res.n_programs == 2
        useful = res.column("useful_rate")
        assert useful.shape == (12,)
        assert np.all(np.isfinite(useful)) and np.all(useful >= 0.0)
        assert np.all(np.isfinite(res.column("mean_queue")))

    def test_batch_not_divisible_by_mesh(self):
        """Odd job counts are padded onto the mesh and trimmed back."""
        n = len(jax.devices()) + 1 if len(jax.devices()) > 1 else 3
        jobs = [FleetJob(scenario="paper_grid", policy="pi3bar",
                         lam=1.0 + 0.5 * i, seed=i) for i in range(n)]
        res = run_fleet(jobs, T=128, chunk=64)
        assert res.n_sims == n
        assert len(res.metrics) == n
        offered = res.column("offered")
        np.testing.assert_allclose(offered, [1.0 + 0.5 * i for i in range(n)])

    def test_wireless_scenario_forms_own_group(self):
        jobs = [FleetJob(scenario="paper_grid", policy="pi3", lam=1.0),
                FleetJob(scenario="wireless_grid", policy="pi3", lam=1.0)]
        res = run_fleet(jobs, T=128, chunk=64)
        assert res.n_programs == 2

    def test_eps_b_sweep_and_reg_alias_share_one_program(self):
        """eps_B is traced per-job data and pi3/pi3_reg are semantically one
        policy: a sweep over both axes must compile exactly one program."""
        jobs = [FleetJob(scenario="paper_grid", policy=pol, lam=2.0,
                         eps_b=eps, seed=0)
                for pol in ("pi3", "pi3_reg")
                for eps in (0.01, 0.05, 0.2)]
        res = run_fleet(jobs, T=256, chunk=64)
        assert res.n_programs == 1
        np.testing.assert_allclose(res.column("eps_b"),
                                   [0.01, 0.05, 0.2] * 2, rtol=1e-6)
        # the traced eps_B must actually reach the regulator: with identical
        # seeds the Bernoulli draws are monotone-coupled in eps (uniform < p),
        # so eps 0.2 must deliver strictly more dummies than eps 0.01
        dummy = res.column("delivered_dummy")
        assert np.all(np.isfinite(dummy)) and np.all(dummy >= -1e-4)
        for base in (0, 3):                       # pi3 block, pi3_reg block
            assert dummy[base + 2] > dummy[base] + 1.0, dummy

    def test_pallas_backend_matches_xla_in_fleet(self):
        """backend="pallas" (interpret mode) through the full sharded
        engine: separate compiled program, bit-identical metrics
        (DESIGN.md §7)."""
        mk = lambda backend: [
            FleetJob(scenario="paper_grid", policy="pi3_reg", lam=3.0 + s,
                     eps_b=0.05, seed=s, backend=backend) for s in range(2)]
        res_x = run_fleet(mk("xla"), T=128, chunk=64)
        res_p = run_fleet(mk("pallas"), T=128, chunk=64)
        for k in ("useful_rate", "delivered", "mean_queue", "max_queue"):
            np.testing.assert_array_equal(res_x.column(k), res_p.column(k),
                                          err_msg=k)
        # mixing backends in one sweep forks the compiled program (backend
        # changes control flow, unlike eps_b)
        res_mix = run_fleet(mk("xla") + mk("pallas"), T=128, chunk=64)
        assert res_mix.n_programs == 2

    def test_markov_scenarios_run_in_fleet(self):
        """Gilbert–Elliott fading, comp-node failure chains, and bursty
        arrivals all ride the same compiled program as static scenarios
        (chain state lives in the scan carry)."""
        jobs = [FleetJob(scenario=s, policy="pi3_reg", lam=2.0, eps_b=0.05)
                for s in ("paper_grid", "ge_grid", "bursty_grid",
                          "ge_comp_grid", "ge_full_grid")]
        res = run_fleet(jobs, T=256, chunk=64)
        assert res.n_programs == 1
        useful = res.column("useful_rate")
        assert np.all(np.isfinite(useful)) and np.all(useful >= 0.0)
        # comp outages must cost throughput relative to the static grid at
        # identical load... but over 256 slots noise dominates; just check
        # the failing scenarios still deliver
        assert np.all(res.column("delivered_useful") > 0.0)


# ---------------------------------------------------------------------------
# Comp-node outage mask threading (event scale -> comp_mask -> argmin)
# ---------------------------------------------------------------------------

class TestCompOutageMasking:
    def test_zero_comp_scale_excluded_from_argmin(self):
        """A comp node whose event-model scale is 0 this slot must neither
        win the load-balance argmin nor combine pairs — the modulated mask
        path (with_capacity_scales gates comp_mask)."""
        p = paper_grid_problem()
        pp = pad_problem(p, PadDims.of([p]))
        cfg = PolicyConfig(name="pi3")
        state = init_state(pp)
        down = jnp.array([1.0, 0.0, 1.0, 0.0], jnp.float32)
        scaled = pp.with_capacity_scales(jnp.ones(pp.n_edges), down)
        picks = set()
        for a in range(16):
            _, _, m = load_balance_slot(scaled, cfg, state,
                                        jnp.float32(1.0 + a))
            picks.add(int(m["n_star"]))
        assert picks <= {0, 2}
        # and the mask composes with padding: a padded problem keeps its
        # padded slots masked after scaling
        big = pad_problem(p, PadDims(20, 30, 6))
        scaled_big = big.with_capacity_scales(
            jnp.ones(big.n_edges), jnp.ones(big.n_comp))
        assert np.asarray(scaled_big.comp_mask)[4:].max() == 0.0

    def test_downed_node_combines_nothing(self):
        from repro.core.policies import computation_slot
        p = paper_grid_problem()
        pp = pad_problem(p, PadDims.of([p]))
        state = init_state(pp)
        # give every comp node combinable pairs
        state = state._replace(
            X=jnp.full((4, 2), 5.0),
            cum_arr=jnp.full((4, 2), 5.0))
        down = jnp.array([1.0, 0.0, 1.0, 1.0], jnp.float32)
        scaled = pp.with_capacity_scales(jnp.ones(pp.n_edges), down)
        new, m = computation_slot(scaled, PolicyConfig(name="pi3bar"), state,
                                  jnp.zeros(4), jax.random.key(0))
        consumed = np.asarray(state.X - new.X)[:, 0]
        assert consumed[1] == 0.0                 # Down node combined nothing
        assert (consumed[[0, 2, 3]] > 0.0).all()  # Up nodes worked


# ---------------------------------------------------------------------------
# Donated chunked-scan carry (the engine's memory audit)
# ---------------------------------------------------------------------------

class TestDonation:
    def test_chunk_runner_carry_buffers_are_donated(self):
        """The engine's chunk step must donate its carry: after a launch the
        input carry buffers are deleted (reused in place), not left alive
        as a second copy of the fleet state."""
        from jax.sharding import Mesh
        cfg = PolicyConfig(name="pi3_reg", eps_b=0.05)
        runner = make_stream_runner(cfg, T=128, chunk=64)
        mesh = Mesh(np.array(jax.devices()), ("fleet",))
        ndev = len(jax.devices())
        pp = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs),
            *[pad_problem(TRI, PadDims.of([TRI]))] * ndev)
        lam = jnp.full((ndev,), 1.0, jnp.float32)
        eps = jnp.full((ndev,), 0.05, jnp.float32)
        ak = jnp.zeros((ndev,), jnp.int32)
        ek = jnp.zeros((ndev,), jnp.int32)
        keys = jnp.stack([jax.random.PRNGKey(i) for i in range(ndev)])

        init_fn, step_fn, fin_fn = make_group_launch(runner, mesh)
        carry = init_fn(pp)
        leaves = jax.tree_util.tree_leaves(carry)
        carry = step_fn(pp, lam, eps, ak, ek, keys, carry)
        assert all(leaf.is_deleted() for leaf in leaves), (
            "chunk-step carry was copied, not donated")
        # non-carry operands must NOT be donated (reused across chunks)
        assert not jax.tree_util.tree_leaves(pp)[0].is_deleted()
        carry2 = step_fn(pp, lam, eps, ak, ek, keys, carry)
        out = jax.device_get(fin_fn(lam, eps, carry2))
        assert np.all(np.isfinite(out["useful_rate"]))

    def test_chunked_launch_matches_single_program_run(self):
        """Driving chunk_step from Python (the donated path) must produce
        exactly the same metrics as the closed single-program `run`."""
        cfg = PolicyConfig(name="pi3bar")
        runner = make_stream_runner(cfg, T=256, chunk=64)
        pp = pad_problem(TRI, PadDims.of([TRI]))
        args = (jnp.float32(1.5), jnp.float32(0.01), jnp.int32(0),
                jnp.int32(0), jax.random.PRNGKey(3))
        ref = jax.jit(runner)(pp, *args)
        step = jax.jit(runner.chunk_step, donate_argnums=6)
        carry = jax.jit(runner.init_carry)(pp)
        for _ in range(runner.n_chunks):
            carry = step(pp, *args, carry)
        got = jax.jit(runner.finalize)(args[0], args[1], carry)
        for k in ref:
            np.testing.assert_allclose(np.asarray(ref[k]),
                                       np.asarray(got[k]), rtol=1e-6,
                                       err_msg=k)


# ---------------------------------------------------------------------------
# Chunk-loop compilation accounting (host-work hoisting, DESIGN.md §4/§7)
# ---------------------------------------------------------------------------

class TestNoRecompilation:
    def test_chunk_loop_compiles_step_exactly_once(self):
        """Driving many chunks through `make_group_launch`'s step_fn must
        hit one compiled program: all per-chunk operands (padded problem,
        rates, eps, model codes, keys) are built once per group, so no
        chunk-loop iteration may retrace."""
        from jax.sharding import Mesh
        # a threshold unique to this test keeps the memoized runner/launch
        # caches from aliasing other tests' entries
        cfg = PolicyConfig(name="pi3bar", threshold=0.060959)
        runner = make_stream_runner(cfg, T=256, chunk=32)
        mesh = Mesh(np.array(jax.devices()), ("fleet",))
        ndev = len(jax.devices())
        pp = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs),
            *[pad_problem(TRI, PadDims.of([TRI]))] * ndev)
        lam = jnp.full((ndev,), 1.0, jnp.float32)
        eps = jnp.full((ndev,), 0.01, jnp.float32)
        ak = jnp.zeros((ndev,), jnp.int32)
        ek = jnp.zeros((ndev,), jnp.int32)
        keys = jax.vmap(jax.random.PRNGKey)(
            jnp.arange(ndev, dtype=jnp.uint32))

        init_fn, step_fn, fin_fn = make_group_launch(runner, mesh)
        carry = init_fn(pp)
        for _ in range(runner.n_chunks):
            carry = step_fn(pp, lam, eps, ak, ek, keys, carry)
        assert step_fn._cache_size() == 1, (
            f"chunk loop retraced: {step_fn._cache_size()} compilations")
        out = jax.device_get(fin_fn(lam, eps, carry))
        assert np.all(np.isfinite(out["useful_rate"]))

    def test_runner_and_launch_are_memoized(self):
        """Same (cfg, T, chunk, window) must return the *same* runner and
        launch objects — re-sweeping a policy group reuses its compiled
        programs instead of re-tracing (the per-group host-work hoist)."""
        from jax.sharding import Mesh
        cfg = PolicyConfig(name="pi3", threshold=0.060959)
        r1 = make_stream_runner(cfg, T=128, chunk=32)
        r2 = make_stream_runner(cfg, T=128, chunk=32)
        assert r1 is r2
        mesh = Mesh(np.array(jax.devices()), ("fleet",))
        assert make_group_launch(r1, mesh) is make_group_launch(r2, mesh)
        # a different horizon is a different runner
        assert make_stream_runner(cfg, T=256, chunk=32) is not r1


# ---------------------------------------------------------------------------
# Exact regulated LP bounds (report layer)
# ---------------------------------------------------------------------------

class TestExactBounds:
    def test_bound_exact_between_approx_and_lam_star(self):
        """On the paper grid: bound_approx <= bound_exact <= bound_approx *
        (1 + eps_B), and since computation (not links) binds there, the
        dummy inflation is free: bound_exact == lam_star == 8."""
        for eps in (0.01, 0.05, 0.2):
            lam_star = exact_lam_star("paper_grid", 0, 1.0)
            be = policy_bound_exact("paper_grid", "pi3_reg", eps)
            ba = policy_bound(lam_star, "pi3_reg", eps)
            assert ba <= be * (1 + 1e-9)
            assert be <= ba * (1 + eps) * (1 + 1e-9)
            assert be == pytest.approx(lam_star)     # comp-capacity bound
        # link-bound topology: the approximation is tight
        ls_ft = exact_lam_star("fat_tree", 0, 1.0)
        assert policy_bound_exact("fat_tree", "pi3_reg", 0.05) == \
            pytest.approx(ls_ft / 1.05)
        # unregulated policies: exact bound degenerates to plain lam_star
        assert policy_bound_exact("paper_grid", "pi3bar", 0.05) == \
            pytest.approx(exact_lam_star("paper_grid", 0, 1.0))

    def test_exact_lp_solves_are_cached(self):
        exact_lam_star.cache_clear()
        policy_bound_exact("paper_grid", "pi3_reg", 0.05)
        before = exact_lam_star.cache_info()
        for _ in range(5):
            policy_bound_exact("paper_grid", "pi3_reg", 0.05)
            policy_bound_exact("paper_grid", "pi2_reg", 0.05)  # same rho0
        info = exact_lam_star.cache_info()
        assert info.misses == before.misses        # no new LP solves
        assert info.hits >= before.hits + 10
        # Report-layer accounting: a full job expansion plus the hoisted
        # one-lookup-per-(scenario, policy)-group bound table must solve
        # each distinct (scenario, rho0) LP exactly once — everything else
        # is cache hits.
        exact_lam_star.cache_clear()
        spec = {"paper_grid": ("pi3bar", "pi3_reg"), "ring": ("pi3_reg",)}
        sweep_jobs(spec, rate_fracs=(0.5, 0.8, 0.95), seeds=(0, 1),
                   eps_b=0.05)
        bounds = {(s, p): policy_bound_exact(s, p, 0.05)
                  for s, pols in spec.items() for p in pols}
        info = exact_lam_star.cache_info()
        # distinct (scenario, rho0) pairs: paper_grid x {1.0, 1.05},
        # ring x {1.05}
        assert info.misses == 3, info
        assert info.hits >= len(bounds), info

    def test_fingerprint_dedupes_seed_independent_topologies(self):
        """The cache keys on the canonical problem fingerprint, not
        (scenario, topo_seed): every topo_seed of a seed-independent
        family builds the same instance, so a thousand-seed atlas grid
        costs *one* LP solve for it."""
        from repro.fleet import problem_fingerprint
        from repro.fleet.scenarios import get_scenario
        fps = {problem_fingerprint(get_scenario("fat_tree").build(ts))
               for ts in (0, 1, 2)}
        assert len(fps) == 1
        # ... while a genuinely seed-varying family hashes apart
        fps_rg = {problem_fingerprint(
            get_scenario("random_geometric").build(ts)) for ts in (0, 1)}
        assert len(fps_rg) == 2
        exact_lam_star.cache_clear()
        for ts in range(4):
            exact_lam_star("fat_tree", ts, 1.0)
        info = exact_lam_star.cache_info()
        assert info.misses == 1 and info.hits == 3, info
        # rho0 is part of the fingerprint: a regulated solve is distinct
        exact_lam_star("fat_tree", 0, 1.05)
        assert exact_lam_star.cache_info().misses == 2

    def test_lp_cache_is_bounded(self, monkeypatch):
        """At thousands of topo_seeds the cache must evict, not grow:
        with the bound pinned to 2, three distinct LPs leave exactly two
        entries (LRU), and the evicted one re-solves on return."""
        from repro.fleet import report as report_mod
        exact_lam_star.cache_clear()
        monkeypatch.setattr(report_mod, "LP_CACHE_MAX", 2)
        for scen in ("paper_grid", "ring", "fat_tree"):
            exact_lam_star(scen, 0, 1.0)
        info = exact_lam_star.cache_info()
        assert info.misses == 3 and info.currsize == 2
        # paper_grid (least recently used) was evicted: a re-solve
        exact_lam_star("paper_grid", 0, 1.0)
        assert exact_lam_star.cache_info().misses == 4
        # ring's entry survived?  No — it was evicted by the re-solve;
        # fat_tree (most recent before it) still hits.
        exact_lam_star("fat_tree", 0, 1.0)
        assert exact_lam_star.cache_info().misses == 4
        exact_lam_star.cache_clear()
        assert exact_lam_star.cache_info() == (0, 0, 2, 0)


# ---------------------------------------------------------------------------
# rho0-adjusted bounds (report layer)
# ---------------------------------------------------------------------------

class TestRegulatedBounds:
    def test_policy_bound_adjusts_only_regulated(self):
        assert policy_bound(8.0, "pi3bar", 0.05) == pytest.approx(8.0)
        assert policy_bound(8.0, "pi1", 0.05) == pytest.approx(8.0)
        for pol in ("pi2", "pi2_reg", "pi3", "pi3_reg"):
            assert policy_bound(8.0, pol, 0.05) == pytest.approx(8.0 / 1.05)

    def test_sweep_jobs_scale_offered_by_policy_bound(self):
        # approx path: regulated rates scale by lam_star/rho0
        jobs = sweep_jobs({"paper_grid": ("pi3bar", "pi3_reg")},
                          rate_fracs=(0.5,), seeds=(0,), eps_b=0.05,
                          lam_star_of={"paper_grid": 8.0}, exact=False)
        lam = {j.policy: j.lam for j in jobs}
        assert lam["pi3bar"] == pytest.approx(4.0)
        assert lam["pi3_reg"] == pytest.approx(4.0 / 1.05)
        assert all(j.eps_b == 0.05 for j in jobs)

    def test_sweep_jobs_exact_uses_regulated_lp(self):
        """Default (exact) path: on the comp-bound paper grid the regulated
        LP equals lam_star, so pi3_reg is offered the same rates as pi3bar
        — the approximation would under-load it by 1/rho0 (DESIGN.md §6)."""
        jobs = sweep_jobs({"paper_grid": ("pi3bar", "pi3_reg")},
                          rate_fracs=(0.5,), seeds=(0,), eps_b=0.05)
        lam = {j.policy: j.lam for j in jobs}
        assert lam["pi3bar"] == pytest.approx(4.0)
        assert lam["pi3_reg"] == pytest.approx(4.0)


# ---------------------------------------------------------------------------
# Compensated delivery counters (ROADMAP numerics note)
# ---------------------------------------------------------------------------

class TestCompensatedCounters:
    def test_increments_survive_past_f32_saturation(self):
        """Plain float32 drops every sub-ulp increment once the total passes
        2^24; the compensated counters must keep them all."""
        sp = StaticProblem.build(TRI)
        st = init_state(sp)
        big = jnp.float32(2.0 ** 24)
        st = st._replace(delivered=big, delivered_useful=big)

        def body(s, _):
            return s.credit_delivery(jnp.float32(0.25), jnp.float32(0.25)), None

        st, _ = jax.lax.scan(body, st, xs=None, length=1000)
        # kahan_add keeps sum - compensation == exact total
        gained = (float(st.delivered) - float(st.delivered_c)) - 2.0 ** 24
        assert gained == pytest.approx(250.0, rel=1e-6)
        # the headline field alone is within one f32 ulp (2.0) of the truth
        assert float(st.delivered) - 2.0 ** 24 == pytest.approx(250.0, abs=2.0)
        # the naive sum loses everything — the failure mode being guarded
        naive = big
        for _ in range(10):
            naive = naive + jnp.float32(0.25)
        assert float(naive) == 2.0 ** 24


class _NoAnalysis:
    """Stands in for a jitted step whose compiled program reports no
    memory analysis."""

    def lower(self, *args):
        return self

    def compile(self):
        return self

    def memory_analysis(self):
        return None


def test_memory_analysis_missing_on_tpu_raises(monkeypatch):
    from repro.fleet import engine

    assert engine._memory_analysis(_NoAnalysis(), ()) is None  # host backend
    monkeypatch.setattr(engine.jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="no memory analysis"):
        engine._memory_analysis(_NoAnalysis(), ())
