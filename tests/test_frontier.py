"""Frontier subsystem tests (DESIGN.md §8): the streaming stability verdict
as a pure unit, early-stop bit-equality against full runs, the golden
`find_lambda_max` bracket on the paper grid, bisection compile accounting,
and the (topo_seed, rate_index, call_index) seed-fold regression."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:        # property tests widen coverage when hypothesis exists;
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                   # the deterministic grid always runs
    HAVE_HYPOTHESIS = False

from repro.core.queues import (DriftStats, VERDICT_NAMES, VERDICT_STABLE,
                               VERDICT_UNDECIDED, VERDICT_UNSTABLE,
                               drift_verdict_update)
from repro.fleet import (FleetJob, VerdictConfig, find_lambda_max, fold_seed,
                         get_scenario, policy_bound_exact, run_fleet,
                         stream_simulate)
from repro.sim.workload import poisson_arrivals

# Verdict parameters shared by the unit tests: window 50, anchor at 100,
# three agreeing boundaries latch a verdict.
_VP = dict(window=50, burn_in=100, k_stable=3, k_unstable=3,
           drift_tol=0.02, gap_tol=0.05)


@functools.partial(jax.jit, static_argnames=tuple(_VP))
def _run_trace(qs, useful, lam, **vp):
    """Feed a synthetic (backlog, cumulative-useful) trace through the
    pure per-slot verdict update and return the final DriftStats."""

    def body(d, x):
        t, q, u = x
        return drift_verdict_update(d, t, q, u, lam, **vp), None

    T = qs.shape[0]
    xs = (jnp.arange(T, dtype=jnp.int32), qs.astype(jnp.float32),
          useful.astype(jnp.float32))
    d, _ = jax.lax.scan(body, DriftStats.zero(), xs)
    return d


def _mm1_trace(drift: float, lam: float, T: int, seed: int):
    """M/M/1-like synthetic totals: a backlog random walk with the given
    per-slot drift (reflected at 0) and the matching cumulative useful
    deliveries — undelivered work is what accumulates as backlog, so the
    delivered rate is lam - max(drift, 0)."""
    rng = np.random.default_rng(seed)
    steps = drift + rng.normal(0.0, np.sqrt(max(lam, 1.0)), size=T)
    q = np.zeros(T, np.float32)
    level = 10.0                                 # small initial fill
    for t in range(T):
        level = max(level + steps[t], 0.0)
        q[t] = level
    rate = lam - max(drift, 0.0)
    useful = np.cumsum(np.full(T, rate, np.float32)
                       + rng.normal(0.0, 0.1, size=T).astype(np.float32))
    return jnp.asarray(q), jnp.asarray(useful)


class TestVerdictUnit:
    def _verdict(self, drift, lam, seed, T=1200):
        qs, useful = _mm1_trace(drift, lam, T, seed)
        return _run_trace(qs, useful, jnp.float32(lam), **_VP)

    @pytest.mark.parametrize("drift,lam,seed", [
        (-0.5, 4.0, 0), (-0.1, 2.0, 1), (-1.0, 8.0, 2), (-0.2, 6.0, 3)])
    def test_negative_drift_eventually_stable(self, drift, lam, seed):
        d = self._verdict(drift, lam, seed)
        assert int(d.verdict) == VERDICT_STABLE, VERDICT_NAMES[int(d.verdict)]
        assert int(d.decided_at) >= _VP["burn_in"] + 2 * _VP["window"]

    @pytest.mark.parametrize("drift,lam,seed", [
        (1.0, 4.0, 0), (0.8, 2.0, 1), (2.0, 8.0, 2), (1.5, 6.0, 3)])
    def test_positive_drift_eventually_unstable(self, drift, lam, seed):
        d = self._verdict(drift, lam, seed)
        assert int(d.verdict) == VERDICT_UNSTABLE, \
            VERDICT_NAMES[int(d.verdict)]

    if HAVE_HYPOTHESIS:
        @settings(max_examples=20, deadline=None)
        @given(drift=st.floats(-2.0, 2.0).filter(lambda x: abs(x) >= 0.5),
               lam=st.floats(1.0, 10.0), seed=st.integers(0, 2 ** 16))
        def test_property_drift_sign_decides(self, drift, lam, seed):
            """Any M/M/1-like trace with clearly negative drift latches
            STABLE, clearly positive drift latches UNSTABLE."""
            d = self._verdict(drift, lam, seed)
            want = VERDICT_STABLE if drift < 0 else VERDICT_UNSTABLE
            assert int(d.verdict) == want, (
                f"drift={drift} lam={lam} -> {VERDICT_NAMES[int(d.verdict)]}")

    def test_undecided_near_boundary_never_flips_after_latching(self):
        """Regression: a verdict latched at decided_at must never change,
        even when later windows carry opposite evidence (the scenario of a
        near-boundary sim whose batch keeps running)."""
        lam, T = 4.0, 2000
        qs_stable, useful_stable = _mm1_trace(-0.5, lam, T, seed=7)
        qs_unst, useful_unst = _mm1_trace(1.5, lam, T, seed=7)
        # stable first half, violently unstable second half
        qs = jnp.concatenate([qs_stable[:T // 2],
                              qs_stable[T // 2 - 1] + qs_unst[:T // 2]])
        useful = jnp.concatenate([
            useful_stable[:T // 2],
            useful_stable[T // 2 - 1] + useful_unst[:T // 2]])
        d = _run_trace(qs, useful, jnp.float32(lam), **_VP)
        # latched STABLE during the first half and stayed latched
        assert int(d.verdict) == VERDICT_STABLE
        assert int(d.decided_at) <= T // 2
        # and with the halves swapped, UNSTABLE latches and survives calm
        qs2 = jnp.concatenate([qs_unst[:T // 2],
                               qs_unst[T // 2 - 1] + qs_stable[:T // 2]])
        useful2 = jnp.concatenate([
            useful_unst[:T // 2],
            useful_unst[T // 2 - 1] + useful_stable[:T // 2]])
        d2 = _run_trace(qs2, useful2, jnp.float32(lam), **_VP)
        assert int(d2.verdict) == VERDICT_UNSTABLE
        assert int(d2.decided_at) <= T // 2

    def test_borderline_trace_stays_undecided(self):
        """A trace living between the stable and unstable bars (drift just
        above tolerance, gap just below) must not latch either way."""
        lam, T = 4.0, 1500
        rng = np.random.default_rng(0)
        # drift ~ 3x drift_tol*scale but gap ~ 0: growing backlog with
        # full delivery — fails both the stable and the unstable test
        q = np.cumsum(np.full(T, 3 * _VP["drift_tol"] * lam)
                      + rng.normal(0, 0.01, T)).astype(np.float32)
        useful = np.cumsum(np.full(T, lam, np.float32))
        d = _run_trace(jnp.asarray(q), jnp.asarray(useful),
                       jnp.float32(lam), **_VP)
        assert int(d.verdict) == VERDICT_UNDECIDED
        assert int(d.decided_at) == 0


# ---------------------------------------------------------------------------
# Early-stop correctness: freezing is bit-exact, bisection is launch-only
# ---------------------------------------------------------------------------

@pytest.mark.fleet_smoke
class TestEarlyStopCorrectness:
    def test_frozen_metrics_bit_equal_full_run_at_decided_slot(self):
        """A sim frozen at decided_at inside an early-stopped batch must
        report exactly the metrics of the same sim run (without early
        stopping) for a horizon of decided_at slots: the freeze mask is a
        bit-exact pin, not an approximation."""
        scenario, lam, seed, chunk = "paper_grid", 10.0, 3, 128
        job = FleetJob(scenario=scenario, policy="pi3", lam=lam, eps_b=0.05,
                       seed=seed)
        res = run_fleet([job], T=2048, chunk=chunk, early_stop=True)
        m = res.metrics[0]
        s = int(m["decided_at_slot"])
        assert m["verdict"] != float(VERDICT_UNDECIDED), m
        assert 0 < s < 2048 and s % chunk == 0
        assert m["slots_saved"] == 2048 - s
        # reference: the plain streaming path, horizon exactly s, no freeze
        ref = stream_simulate(get_scenario(scenario).build(0),
                              job.policy_config(), lam, T=s, chunk=chunk,
                              seed=seed)
        for k in ("delivered", "delivered_useful", "delivered_dummy",
                  "max_queue", "mean_queue"):
            assert m[k] == float(ref[k]), (k, m[k], float(ref[k]))

    def test_undecided_sims_match_plain_run_exactly(self):
        """Sims that never decide ride the early-stopped batch to the full
        horizon and must equal a plain run bitwise (where(False, old, new)
        is `new`)."""
        job = FleetJob(scenario="paper_grid", policy="pi3bar", lam=7.9,
                       seed=0)
        a = run_fleet([job], T=1024, chunk=128, early_stop=True)
        b = run_fleet([job], T=1024, chunk=128, early_stop=False)
        if a.verdicts()[0] == "UNDECIDED":
            for k in ("useful_rate", "delivered", "mean_queue", "max_queue"):
                assert a.metrics[0][k] == b.metrics[0][k], k
        # decided or not, the state-level counters never diverge before
        # the decision slot; delivered totals of the plain run are >= the
        # frozen run's (frozen sims stop accumulating)
        assert b.metrics[0]["delivered"] >= a.metrics[0]["delivered"]

    def test_bisection_reuses_cached_compiled_program(self):
        """TestNoRecompilation, frontier edition: after the first launch,
        every bisection step must be launch-only — one compiled chunk-step
        program across all probes (memoized runner + group launch)."""
        r = find_lambda_max("paper_grid", "pi3", eps_b=0.051937,
                            seeds=(0,), T=768, chunk=128, rel_tol=0.1,
                            max_calls=10)
        assert r.n_calls >= 3                  # bracket + >= 1 bisection
        assert r.n_step_compiles == 1, (
            f"bisection retraced: {r.n_step_compiles} chunk-step programs")


# ---------------------------------------------------------------------------
# Golden frontier: paper grid, pi3, exact-bound bracket + invariance
# ---------------------------------------------------------------------------

@pytest.mark.fleet_smoke
class TestGoldenFrontier:
    KW = dict(eps_b=0.05, seeds=(0, 1), T=4096, chunk=256, rel_tol=0.025)

    def test_paper_grid_brackets_exact_bound(self):
        bound = policy_bound_exact("paper_grid", "pi3", 0.05)
        r = find_lambda_max("paper_grid", "pi3", **self.KW)
        assert r.bound_exact == pytest.approx(bound)
        assert r.lam_max <= bound * (1 + 1e-9)
        assert r.lam_max >= 0.9 * bound, (
            f"lam_max {r.lam_max:.3f} < 0.9 * bound {bound:.3f}")
        assert r.hi - r.lo == pytest.approx(self.KW["rel_tol"] * bound)
        assert r.slots_saved_frac > 0.0 and r.launch_slots_saved > 0

    def test_invariant_to_initial_bracket(self):
        """First probes of a grid index always draw the same folded seeds
        (call_index 0), so two searches from different brackets land on
        the same quantized lam_max exactly."""
        r1 = find_lambda_max("paper_grid", "pi3", **self.KW,
                             bracket=(0.5, 1.1))
        r2 = find_lambda_max("paper_grid", "pi3", **self.KW,
                             bracket=(0.6, 1.05))
        assert r1.lam_max == r2.lam_max
        assert r1.ratio == r2.ratio


# ---------------------------------------------------------------------------
# Seed decoupling: the (topo_seed, rate_index, call_index) fold
# ---------------------------------------------------------------------------

class TestSeedDecoupling:
    def test_fold_seed_decouples_every_axis(self):
        base = fold_seed(0, 3, 0, 0)
        assert base == fold_seed(0, 3, 0, 0)      # deterministic
        assert base != fold_seed(0, 4, 0, 0)      # rate_index
        assert base != fold_seed(0, 3, 1, 0)      # call_index (re-probe)
        assert base != fold_seed(1, 3, 0, 0)      # topo_seed
        assert base != fold_seed(0, 3, 0, 1)      # per-probe seed
        seen = {fold_seed(t, k, c, s) for t in range(3) for k in range(12)
                for c in range(2) for s in range(4)}
        assert len(seen) == 3 * 12 * 2 * 4        # no collisions on the grid
        assert all(0 <= s < 2 ** 31 for s in seen)

    def test_bisection_steps_never_share_arrival_streams(self):
        """Regression for the latent seed-coupling hazard: two bisection
        probes at different rates must not draw the same uniforms — with
        the raw job seed they would (PRNGKey(seed) ignores lam), coupling
        the noise at every probed rate."""
        T = 256
        # the hazard: two probes reusing the raw job seed start from the
        # *same* PRNGKey, so every derived stream coincides slot-for-slot
        uh = poisson_arrivals(jax.random.PRNGKey(0), 5.0, T)
        vh = poisson_arrivals(jax.random.PRNGKey(0), 5.0, T)
        assert np.array_equal(np.asarray(uh), np.asarray(vh))
        # the fix: rate_index enters the fold, streams decouple
        s_lo = fold_seed(0, rate_index=20, call_index=0, seed=0)
        s_hi = fold_seed(0, rate_index=32, call_index=0, seed=0)
        u = poisson_arrivals(jax.random.PRNGKey(s_lo), 5.0, T)
        v = poisson_arrivals(jax.random.PRNGKey(s_hi), 5.0, T)
        assert not np.array_equal(np.asarray(u), np.asarray(v))
        # and a re-probe of the same rate draws fresh noise
        s_again = fold_seed(0, rate_index=20, call_index=1, seed=0)
        w = poisson_arrivals(jax.random.PRNGKey(s_again), 5.0, T)
        assert not np.array_equal(np.asarray(u), np.asarray(w))


# ---------------------------------------------------------------------------
# Verdict metrics through the engine (no early stop: reporting only)
# ---------------------------------------------------------------------------

@pytest.mark.fleet_smoke
class TestFleetVerdictMetrics:
    def test_rows_gain_verdict_fields(self):
        # Verdict discipline (DESIGN.md §8): chunk >= 512 keeps the burn-in
        # past the gradient-fill transient, and T = 8 chunks leaves room
        # for the 3 stable windows after it.
        jobs = [FleetJob(scenario="paper_grid", policy="pi3", lam=lam,
                         eps_b=0.05, seed=0) for lam in (2.0, 14.0)]
        res = run_fleet(jobs, T=4096, chunk=512)      # early_stop off
        for m in res.metrics:
            assert {"verdict", "decided_at_slot", "slots_saved"} <= set(m)
            assert m["slots_saved"] == 0.0            # no freezing
        v = res.verdicts()
        assert v[0] in ("STABLE", "UNDECIDED")
        assert v[1] == "UNSTABLE"                     # far above capacity
        assert res.slots_saved == 0 and res.launch_slots_saved == 0

    def test_verdict_config_forks_runner_not_behavior(self):
        """A custom VerdictConfig reaches the runner (stricter evidence
        delays the decision) without touching the simulated dynamics."""
        job = FleetJob(scenario="paper_grid", policy="pi3", lam=2.0,
                       eps_b=0.05, seed=0)
        fast = run_fleet([job], T=2048, chunk=256, early_stop=True)
        slow = run_fleet([job], T=2048, chunk=256, early_stop=True,
                         verdict=VerdictConfig(k_stable=6, k_unstable=6))
        assert slow.metrics[0]["decided_at_slot"] >= \
            fast.metrics[0]["decided_at_slot"]
        # dynamics identical up to the earlier freeze: delivered monotone
        assert slow.metrics[0]["delivered"] >= fast.metrics[0]["delivered"]

    def test_capacity_report_points_carry_verdicts(self):
        from repro.fleet import capacity_report
        table = capacity_report({"paper_grid": ("pi3bar",)},
                                rate_fracs=(0.4,), seeds=(0,), T=512,
                                chunk=128, eps_b=0.05)
        pt = table["scenarios"]["paper_grid"]["policies"]["pi3bar"]["points"][0]
        assert pt["verdict"] in VERDICT_NAMES
        assert 0 < pt["decided_at_slot"] <= 512
