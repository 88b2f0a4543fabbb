"""Parity suite for the fused Pallas slot-step kernels (bp_slot).

Every test runs the kernels in interpret mode (the CPU CI code path,
`scripts/test.sh` re-runs this module under `JAX_PLATFORMS=cpu`) and
asserts *bit-exact* agreement with the pure-jnp oracle `bp_slot/ref.py` —
the contract that lets `PolicyConfig.backend` switch the fleet's hot loop
freely (DESIGN.md §7).  Marker: `pallas`.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:        # property test below widens coverage when hypothesis exists;
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                   # the deterministic grid always runs
    HAVE_HYPOTHESIS = False

from repro.core import PolicyConfig, paper_grid_problem
from repro.core.policies import slot_step
from repro.core.queues import init_state
from repro.fleet import PadDims, get_scenario, pad_problem
from repro.kernels.bp_slot.kernel import comp_balance_decide, slot_route_decide
from repro.kernels.bp_slot.ops import slot_route_op, slot_route_op_ref
from repro.kernels.bp_slot.ref import comp_balance_ref, slot_route_ref

pytestmark = pytest.mark.pallas


def _state_leaves_equal(a, b):
    return all(bool(jnp.all(x == y)) for x, y in
               zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))


# ---------------------------------------------------------------------------
# Kernel-level parity (tiled passes vs the materializing oracle)
# ---------------------------------------------------------------------------

class TestRouteDecide:
    @pytest.mark.parametrize("block_e,block_c", [(128, None), (8, 4), (16, 3),
                                                 (7, 5)])
    def test_blocks_match_ref_bitwise(self, block_e, block_c):
        key = jax.random.key(0)
        N, C, E = 24, 15, 50
        Qf = jax.random.uniform(key, (N, C)) * 100
        m = jax.random.randint(jax.random.fold_in(key, 1), (E,), 0, N)
        l = (m + 1 + jax.random.randint(jax.random.fold_in(key, 2), (E,),
                                        0, N - 1)) % N
        best, dmax = slot_route_decide(Qf, m, l, block_e=block_e,
                                       block_c=block_c)
        rbest, rdmax = slot_route_ref(Qf, m, l)
        np.testing.assert_array_equal(np.asarray(best), np.asarray(rbest))
        np.testing.assert_array_equal(np.asarray(dmax), np.asarray(rdmax))

    def test_tie_break_matches_argmax_first_occurrence(self):
        """Regression (tie-break contract, DESIGN.md §7): duplicated class
        columns force exact ties across tiles; the kernel must keep the
        *lowest* flat index, like `jnp.argmax`, even when the duplicate
        lands in a later tile."""
        key = jax.random.key(3)
        base = jax.random.uniform(key, (10, 4)) * 50
        Qf = jnp.tile(base, (1, 3))                     # classes repeat x3
        m = jnp.arange(5, dtype=jnp.int32)
        l = jnp.arange(5, 10, dtype=jnp.int32)
        for block_c in (4, 3, 2, 12):
            best, dmax = slot_route_decide(Qf, m, l, block_e=5,
                                           block_c=block_c)
            rbest, rdmax = slot_route_ref(Qf, m, l)
            np.testing.assert_array_equal(np.asarray(best), np.asarray(rbest),
                                          err_msg=f"block_c={block_c}")
            assert np.all(np.asarray(rbest) < 4)        # ties resolve low
            np.testing.assert_array_equal(np.asarray(dmax), np.asarray(rdmax))

    def test_all_zero_diff_keeps_index_zero(self):
        Qf = jnp.ones((6, 9)) * 7.0
        m = jnp.array([0, 1], jnp.int32)
        l = jnp.array([2, 3], jnp.int32)
        best, dmax = slot_route_decide(Qf, m, l, block_e=2, block_c=3)
        np.testing.assert_array_equal(np.asarray(best), 0)
        np.testing.assert_array_equal(np.asarray(dmax), 0.0)

    def test_standalone_op_full_decision(self):
        key = jax.random.key(9)
        N, NC, E = 16, 4, 45
        Q = jax.random.uniform(key, (N, 3, NC)) * 100
        edges = jax.random.randint(jax.random.fold_in(key, 1), (E, 2), 0, N)
        edges = edges.at[:, 1].set((edges[:, 1] + 1 + edges[:, 0]) % N)
        cap = jax.random.uniform(jax.random.fold_in(key, 2), (E,)) * 5
        out = slot_route_op(Q, edges, cap)
        ref = slot_route_op_ref(Q, edges, cap)
        for got, want, name in zip(out, ref, ("class", "comp", "dir", "rate")):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                          err_msg=name)


class TestCompBalanceDecide:
    def _panels(self, key, NC, mask=None):
        r = lambda i, lo=0.0, hi=10.0: lo + jax.random.uniform(
            jax.random.fold_in(key, i), (NC,)) * (hi - lo)
        return dict(
            q0=r(0), q1=r(1), q2=r(2), H=r(3), caps=r(4, 0.5, 3.0),
            mask=jnp.ones((NC,)) if mask is None else mask,
            x1=r(5), x2=r(6), ca1=r(7, 5.0, 20.0), ca2=r(8, 5.0, 20.0),
            cc=r(9, 0.0, 5.0), x_net=r(10))

    @pytest.mark.parametrize("pairing", ["fifo", "bound"])
    @pytest.mark.parametrize("block_n", [128, 4, 3])
    def test_blocks_match_ref_bitwise(self, pairing, block_n):
        NC = 10
        p = self._panels(jax.random.key(1), NC)
        eps = jnp.float32(0.05)
        Z, n_star = comp_balance_decide(eps, *p.values(), pairing=pairing,
                                        block_n=block_n)
        rZ, rn = comp_balance_ref(eps, **p, pairing=pairing,
                                  thresholded=False, threshold=0.0)
        np.testing.assert_array_equal(np.asarray(Z), np.asarray(rZ))
        assert int(n_star) == int(rn)

    def test_thresholded_gate(self):
        NC = 6
        p = self._panels(jax.random.key(2), NC)
        eps = jnp.float32(0.01)
        for thr in (0.0, 5.0, 100.0):
            Z, n = comp_balance_decide(eps, *p.values(), thresholded=True,
                                       threshold=thr, block_n=3)
            rZ, rn = comp_balance_ref(eps, **p, pairing="fifo",
                                      thresholded=True, threshold=thr)
            np.testing.assert_array_equal(np.asarray(Z), np.asarray(rZ))
            assert int(n) == int(rn)

    def test_masked_nodes_never_win_even_all_masked(self):
        NC = 8
        key = jax.random.key(4)
        down = (jax.random.uniform(jax.random.fold_in(key, 99), (NC,))
                > 0.5).astype(jnp.float32)
        for mask in (down, jnp.zeros((NC,))):
            p = self._panels(key, NC, mask=mask)
            Z, n_star = comp_balance_decide(jnp.float32(0.1), *p.values(),
                                            block_n=4)
            rZ, rn = comp_balance_ref(jnp.float32(0.1), **p, pairing="fifo",
                                      thresholded=False, threshold=0.0)
            np.testing.assert_array_equal(np.asarray(Z), np.asarray(rZ))
            assert int(n_star) == int(rn)
            if bool(mask.any()):
                assert float(mask[int(n_star)]) == 1.0

    def test_eps_is_traced_per_job_data(self):
        """vmap over eps_B must not fork the kernel and must match the
        oracle per job."""
        NC = 5
        p = self._panels(jax.random.key(7), NC)
        epss = jnp.array([0.0, 0.05, 0.3], jnp.float32)
        Zs, ns = jax.vmap(lambda e: comp_balance_decide(
            e, *p.values(), block_n=2))(epss)
        for i, e in enumerate(epss):
            rZ, rn = comp_balance_ref(e, **p, pairing="fifo",
                                      thresholded=False, threshold=0.0)
            np.testing.assert_array_equal(np.asarray(Zs[i]), np.asarray(rZ))
            assert int(ns[i]) == int(rn)


# ---------------------------------------------------------------------------
# slot_step backend parity over random masked PaddedProblems
# ---------------------------------------------------------------------------

SCEN_NAMES = ("paper_grid", "ring", "fat_tree")


def _check_slot_step_parity(scen, policy, pad_extra, eps_b, fail_pattern,
                            seed):
    """`slot_step(backend="pallas")` (the interpreter, on the CPU) must equal
    `backend="xla"` bit-exactly on padded problems with failed comp nodes
    and a traced eps_B — every state leaf, every metric, every slot."""
    problem = get_scenario(scen).build(0)
    dims = PadDims(problem.graph.n_nodes + pad_extra,
                   problem.graph.n_edges + 2 * pad_extra,
                   problem.n_comp + pad_extra)
    pp = pad_problem(problem, dims)
    # knock out comp nodes by bit pattern (never all of the real ones)
    comp_scale = jnp.array(
        [0.0 if (fail_pattern >> (i % 3)) & 1 and i > 0 else 1.0
         for i in range(dims.n_comp)], jnp.float32)
    pp = pp.with_capacity_scales(jnp.ones(pp.n_edges), comp_scale)

    key = jax.random.key(seed)
    states, metrics = [], []
    for backend in ("xla", "pallas"):
        cfg = PolicyConfig(name=policy, eps_b=eps_b, threshold=1.5,
                           backend=backend)
        state = init_state(pp)
        ms = []
        for t in range(8):
            kt = jax.random.fold_in(key, t)
            arr = jnp.float32(1.0 + 0.5 * t)
            state, m = slot_step(pp, cfg, state, arr, kt,
                                 eps_b=jnp.float32(eps_b))
            ms.append(m)
        states.append(state)
        metrics.append(ms)
    assert _state_leaves_equal(states[0], states[1])
    for mx, mp in zip(metrics[0], metrics[1]):
        for k in mx:
            np.testing.assert_array_equal(np.asarray(mx[k]),
                                          np.asarray(mp[k]), err_msg=k)


@pytest.mark.parametrize(
    "scen,policy,pad_extra,eps_b,fail_pattern,seed",
    [("paper_grid", "pi3", 2, 0.05, 5, 0),
     ("paper_grid", "pi1p", 0, 0.0, 0, 1),
     ("ring", "pi3bar", 3, 0.2, 3, 2),
     ("fat_tree", "pi3", 1, 0.01, 6, 3)])
def test_slot_step_backend_parity_grid(scen, policy, pad_extra, eps_b,
                                       fail_pattern, seed):
    """Deterministic selection of the parity property (always runs, even
    without hypothesis)."""
    _check_slot_step_parity(scen, policy, pad_extra, eps_b, fail_pattern,
                            seed)


if HAVE_HYPOTHESIS:
    @settings(max_examples=8, deadline=None)
    @given(scen=st.sampled_from(SCEN_NAMES),
           policy=st.sampled_from(("pi3", "pi3bar", "pi1", "pi1p")),
           pad_extra=st.integers(0, 3),
           eps_b=st.sampled_from((0.0, 0.01, 0.2)),
           fail_pattern=st.integers(0, 7),
           seed=st.integers(0, 99))
    def test_slot_step_backend_parity_property(scen, policy, pad_extra,
                                               eps_b, fail_pattern, seed):
        _check_slot_step_parity(scen, policy, pad_extra, eps_b, fail_pattern,
                                seed)


def test_slot_step_parity_regulated_jitted_scan():
    """The fleet path: jitted scan over slots, regulated policy, padded
    problem, traced eps — bit-exact across backends."""
    p = paper_grid_problem()
    pp = pad_problem(p, PadDims(20, 30, 6))

    def run(backend):
        cfg = PolicyConfig(name="pi3_reg", eps_b=0.05, backend=backend)

        @jax.jit
        def go(key):
            def body(carry, t):
                state = carry
                kt = jax.random.fold_in(key, t)
                state, m = slot_step(pp, cfg, state, jnp.float32(3.0), kt,
                                     eps_b=jnp.float32(0.05))
                return state, m["delivered_useful"]
            return jax.lax.scan(body, init_state(pp), jnp.arange(64))
        return go(jax.random.key(5))

    sx, dx = run("xla")
    sp_, dp = run("pallas")
    assert _state_leaves_equal(sx, sp_)
    np.testing.assert_array_equal(np.asarray(dx), np.asarray(dp))
    assert float(np.asarray(dx)[-1]) > 0.0      # the run actually delivers


def test_backend_validation():
    with pytest.raises(ValueError, match="unknown backend"):
        PolicyConfig(name="pi3", backend="cuda")


@pytest.mark.parametrize("platform,expected", [("cpu", True), ("tpu", False),
                                               ("gpu", None)])
def test_kernel_mode_follows_the_platform(platform, expected, monkeypatch):
    """Interpreted on the CPU, compiled on a TPU, refused elsewhere."""
    from repro.kernels import platform as kp

    monkeypatch.setattr(kp, "default_backend", lambda: platform)
    if expected is None:
        with pytest.raises(RuntimeError, match="'gpu'"):
            kp.pallas_interpret()
    else:
        assert kp.pallas_interpret() is expected
