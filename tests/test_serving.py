"""Serving subsystem tests (DESIGN.md §9): trace generators, streaming
latency accumulators, admission hysteresis, chunked-vs-closed bit
equality, fairness across query classes, and the outage shed/recover loop
wired to the fault-tolerance planner (markers: fleet_smoke for engine
runs, pallas for backend parity)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import paper_grid_problem
from repro.core.latency import (LatencyStats, latency_mean,
                                latency_quantiles, latency_update)
from repro.core.queues import DriftStats
from repro.fleet import PadDims, pad_problem, policy_bound_exact
from repro.fleet.scenarios import event_code, get_scenario
from repro.runtime.fault import (StragglerConfig, StragglerDetector,
                                 plan_recovery)
from repro.serving import (AdmissionConfig, AdmissionState, QueryClass,
                           ServingJob, TraceSpec, TraceState,
                           admission_admit, admission_update, draw_arrivals,
                           get_trace, jsonl_line, list_traces,
                           make_serving_runner, run_serving, serving_report,
                           write_stream_jsonl)
from repro.serving.trace import envelope


# ---------------------------------------------------------------------------
# traces
# ---------------------------------------------------------------------------

class TestTrace:
    def test_registry(self):
        assert {"steady", "bursty", "diurnal_mix",
                "bursty_mix"} <= set(list_traces())
        with pytest.raises(KeyError, match="unknown trace"):
            get_trace("nope")

    def test_spec_validation(self):
        with pytest.raises(ValueError, match="sum to 1"):
            TraceSpec("bad", (QueryClass("a", "poisson", 0.5),))
        with pytest.raises(ValueError, match="unknown arrival"):
            QueryClass("a", "zipf")
        with pytest.raises(ValueError, match="at least one"):
            TraceSpec("empty", ())
        with pytest.raises(ValueError, match="diurnal_depth"):
            TraceSpec("deep", (QueryClass("a"),), diurnal_period=10,
                      diurnal_depth=1.5)

    def test_envelope_mean_one(self):
        spec = get_trace("diurnal_mix")
        t = jnp.arange(spec.diurnal_period)
        env = jax.vmap(lambda ti: envelope(spec, ti))(t)
        assert float(env.mean()) == pytest.approx(1.0, abs=1e-3)
        assert float(env.max()) == pytest.approx(1 + spec.diurnal_depth,
                                                 abs=1e-3)
        # no period -> constant 1
        assert float(envelope(get_trace("steady"), jnp.int32(7))) == 1.0

    def _scan_trace(self, spec, lam, T, seed=0):
        from repro.fleet.scenarios import ModState
        p = paper_grid_problem()
        pp = pad_problem(p, PadDims.of([p]))
        mod = ModState.init(pp)

        def body(tr, xs):
            t, key = xs
            arr, tr2 = draw_arrivals(spec, key, jnp.float32(lam), t, tr, mod)
            return tr2, arr

        keys = jax.random.split(jax.random.key(seed), T)
        _, arrs = jax.lax.scan(body, TraceState.init(spec),
                               (jnp.arange(T), keys))
        return np.asarray(arrs)                        # [T, K]

    def test_mixture_rates_and_determinism(self):
        spec = get_trace("bursty_mix")
        lam, T = 4.0, 4000
        arrs = self._scan_trace(spec, lam, T)
        assert arrs.shape == (T, 2)
        # long-run per-class rate matches lam * frac; total matches lam
        np.testing.assert_allclose(arrs.mean(0), [2.0, 2.0], rtol=0.1)
        assert arrs.sum(1).mean() == pytest.approx(lam, rel=0.07)
        np.testing.assert_array_equal(arrs, self._scan_trace(spec, lam, T))

    def test_markov_classes_burst_independently(self):
        spec = TraceSpec("two_bursts", (QueryClass("a", "markov_onoff", 0.5),
                                        QueryClass("b", "markov_onoff", 0.5)))
        arrs = self._scan_trace(spec, 4.0, 2000)
        # each class is silent during its own OFF phases, and the phases
        # are driven by independent keys -> the silence patterns differ
        off_a, off_b = arrs[:, 0] == 0.0, arrs[:, 1] == 0.0
        assert 0.1 < off_a.mean() < 0.9 and 0.1 < off_b.mean() < 0.9
        assert (off_a != off_b).mean() > 0.05


# ---------------------------------------------------------------------------
# latency accumulators
# ---------------------------------------------------------------------------

class TestLatency:
    HORIZON, BINS = 64, 32          # bin width 2 slots

    def _run(self, T, delay, rate=1.0):
        """Admit `rate`/slot; deliver the same fluid `delay` slots later."""
        lat = LatencyStats.zero(self.HORIZON, self.BINS)
        for t in range(T):
            adm = rate * (t + 1)
            dlv = rate * max(t + 1 - delay, 0)
            out = rate if t >= delay else 0.0
            lat = latency_update(lat, jnp.int32(t), jnp.float32(adm),
                                 jnp.float32(dlv), jnp.float32(out),
                                 horizon=self.HORIZON, n_bins=self.BINS)
        return lat

    def test_constant_lag_measures_exact_delay(self):
        d = 6
        lat = self._run(40, d)
        assert float(latency_mean(lat)) == pytest.approx(d)
        p50, p99 = np.asarray(latency_quantiles(
            lat.hist, (0.5, 0.99), horizon=self.HORIZON, n_bins=self.BINS))
        # quantiles report the bin's upper edge: conservative by < 1 bin
        assert d <= p50 <= d + 2 and d <= p99 <= d + 2

    def test_empty_histogram_reports_zero(self):
        lat = LatencyStats.zero(self.HORIZON, self.BINS)
        q = latency_quantiles(lat.hist, (0.5, 0.99), horizon=self.HORIZON,
                              n_bins=self.BINS)
        assert float(latency_mean(lat)) == 0.0
        np.testing.assert_array_equal(np.asarray(q), [0.0, 0.0])

    def test_delay_caps_at_horizon_in_overflow_bin(self):
        # admitted mass never delivered: once the ring wraps, the virtual
        # delay saturates at the cap and lands in the overflow bin
        lat = LatencyStats.zero(self.HORIZON, self.BINS)
        for t in range(self.HORIZON + 8):
            lat = latency_update(lat, jnp.int32(t), jnp.float32(t + 1.0),
                                 jnp.float32(0.0), jnp.float32(1.0),
                                 horizon=self.HORIZON, n_bins=self.BINS)
        assert float(lat.hist[-1]) > 0
        q = latency_quantiles(lat.hist, (0.99,), horizon=self.HORIZON,
                              n_bins=self.BINS)
        assert float(q[0]) == self.HORIZON


# ---------------------------------------------------------------------------
# admission gate
# ---------------------------------------------------------------------------

class TestAdmission:
    CFG = AdmissionConfig(shed_tol=0.10, gap_tol=0.05, readmit_tol=0.02,
                          k_shed=2, k_readmit=2)
    WIN, BURN = 64, 128

    def test_admit_applies_gate_and_counts(self):
        adm = AdmissionState.zero(2)
        arr = jnp.array([3.0, 1.0])
        adm, tot = admission_admit(adm, arr)
        assert float(tot) == 4.0
        adm = adm._replace(gate=jnp.float32(0.0))
        adm, tot = admission_admit(adm, arr)
        assert float(tot) == 0.0
        np.testing.assert_allclose(np.asarray(adm.admitted), [3.0, 1.0])
        np.testing.assert_allclose(np.asarray(adm.shed), [3.0, 1.0])

    def _drive(self, T, service=3.0, arrivals=5.0, lam=4.0,
               drift=None):
        """Closed loop: queue grows while the gate admits, drains shut.

        Returns the per-slot gate trace (numpy, length T)."""
        drift = drift or DriftStats.zero()
        adm = AdmissionState.zero(1)
        q = dlv = 0.0
        gates = []
        for t in range(T):
            adm, admitted = admission_admit(adm, jnp.array([arrivals]))
            q = max(q + float(admitted) - service, 0.0)
            dlv += service if q > 0 or admitted > 0 else 0.0
            adm = admission_update(self.CFG, adm, jnp.int32(t),
                                   jnp.float32(q), jnp.float32(dlv),
                                   jnp.float32(lam), drift,
                                   window=self.WIN, burn_in=self.BURN)
            gates.append(float(adm.gate))
        return np.asarray(gates), adm

    def test_gate_moves_only_at_window_boundaries(self):
        gates, _ = self._drive(8 * self.WIN)
        flips = np.nonzero(np.diff(gates))[0] + 1
        assert len(flips) > 0                       # overloaded: it closes
        # the gate re-evaluates at slot t with (t+1) % window == 0
        assert all((f + 1) % self.WIN == 0 for f in flips)

    def test_hysteresis_flip_spacing(self):
        """Consecutive flips are >= min(k_shed, k_readmit) windows apart —
        the gate cannot flip-flop inside one verdict window."""
        gates, adm = self._drive(32 * self.WIN)
        flips = np.nonzero(np.diff(gates))[0] + 1
        assert len(flips) >= 2                      # duty-cycles both ways
        k = min(self.CFG.k_shed, self.CFG.k_readmit)
        assert np.all(np.diff(flips) >= k * self.WIN), flips
        assert int(adm.flips) == len(flips)

    def test_underload_never_closes(self):
        gates, adm = self._drive(16 * self.WIN, service=7.0)
        assert np.all(gates == 1.0) and int(adm.flips) == 0

    def test_burn_in_suppresses_early_evidence(self):
        # with burn_in past the whole run, even hard overload can't close
        adm = AdmissionState.zero(1)
        for t in range(4 * self.WIN):
            adm, _ = admission_admit(adm, jnp.array([9.0]))
            adm = admission_update(self.CFG, adm, jnp.int32(t),
                                   jnp.float32(9.0 * (t + 1)),
                                   jnp.float32(0.0), jnp.float32(4.0),
                                   DriftStats.zero(), window=self.WIN,
                                   burn_in=100 * self.WIN)
        assert float(adm.gate) == 1.0 and int(adm.flips) == 0

    def test_unstable_run_corroborates_first_close_only(self):
        """The verdict's evidence streak can close a never-flipped gate on
        its own, but after any flip the windowed conjunction governs."""
        streak = DriftStats.zero()._replace(unstable_run=jnp.int32(1))
        adm = AdmissionState.zero(1)
        # flat backlog, no gap: only the streak supplies evidence
        for t in range(self.BURN + 2 * self.WIN):
            adm = admission_update(self.CFG, adm, jnp.int32(t),
                                   jnp.float32(0.0), jnp.float32(0.0),
                                   jnp.float32(4.0), streak,
                                   window=self.WIN, burn_in=self.BURN)
        assert float(adm.gate) == 0.0               # first close: streak
        # flat backlog reads as recovered -> it reopens ...
        for t in range(t + 1, t + 1 + 2 * self.WIN):
            adm = admission_update(self.CFG, adm, jnp.int32(t),
                                   jnp.float32(0.0), jnp.float32(0.0),
                                   jnp.float32(4.0), streak,
                                   window=self.WIN, burn_in=self.BURN)
        assert float(adm.gate) == 1.0
        # ... and the still-raised streak alone can never close it again
        for t in range(t + 1, t + 1 + 8 * self.WIN):
            adm = admission_update(self.CFG, adm, jnp.int32(t),
                                   jnp.float32(0.0), jnp.float32(0.0),
                                   jnp.float32(4.0), streak,
                                   window=self.WIN, burn_in=self.BURN)
        assert float(adm.gate) == 1.0 and int(adm.flips) == 2


# ---------------------------------------------------------------------------
# scheduler: chunked streaming == closed form, bit for bit
# ---------------------------------------------------------------------------

class TestSchedulerEquality:
    def test_chunked_equals_closed_bitwise(self):
        p = paper_grid_problem()
        pp = pad_problem(p, PadDims.of([p]))
        cfg = ServingJob(policy="pi3_reg").policy_config()
        runner = make_serving_runner(cfg, get_trace("bursty"), T=256,
                                     chunk=64)
        lam = jnp.float32(4.0)
        eps = jnp.float32(0.05)
        ek = jnp.int32(event_code(get_scenario("paper_grid").events))
        key = jax.random.PRNGKey(3)

        carry = runner.init_carry(pp)
        for _ in range(runner.n_chunks):
            carry = runner.chunk_step(pp, lam, eps, ek, key, carry)
        chunked = runner.finalize(lam, eps, carry)
        closed = runner(pp, lam, eps, ek, key)
        assert set(chunked) == set(closed)
        for k in chunked:
            np.testing.assert_array_equal(np.asarray(chunked[k]),
                                          np.asarray(closed[k]), err_msg=k)


# ---------------------------------------------------------------------------
# engine + report (CI smoke: works on 1 device; scripts/test.sh gives it 8)
# ---------------------------------------------------------------------------

@pytest.mark.fleet_smoke
class TestServingEngine:
    def test_light_load_admits_everything(self):
        bound = policy_bound_exact("paper_grid", "pi3_reg", 0.05, 0)
        jobs = [ServingJob(trace=tr, lam=0.6 * bound, seed=s)
                for tr in ("steady", "bursty") for s in (0, 1)]
        # admission windows of 512 slots, past the gradient-fill transient
        res = run_serving(jobs, T=4096, chunk=512)
        assert res.n_sims == 4
        assert res.n_programs == 2          # one program per (policy, trace)
        np.testing.assert_array_equal(res.column("shed_frac"), 0.0)
        np.testing.assert_array_equal(res.column("gate_flips"), 0.0)
        np.testing.assert_array_equal(res.column("gate"), 1.0)
        assert np.all(res.column("delivered_qps") >= 0.8 * 0.6 * bound)
        assert np.all(res.column("p99_sojourn") > 0)

    def test_overload_fairness_across_classes(self):
        """Class-uniform shedding: under 1.3x-bound overload of the
        half-bursty mixture, both classes keep the same admitted share."""
        bound = policy_bound_exact("paper_grid", "pi3_reg", 0.05, 0)
        jobs = [ServingJob(trace="bursty_mix", lam=1.3 * bound, seed=s)
                for s in (0, 1)]
        res = run_serving(jobs, T=4096, chunk=512)
        for m in res.metrics:
            fa, fb = m["class_admit_frac"]
            assert m["shed_frac"] > 0.1          # it actually shed
            assert abs(fa - fb) < 0.05, (fa, fb)
            assert 0.4 < fa < 0.9
        # hysteresis at engine scale: with k_shed = k_readmit = 2 the gate
        # can flip at most once per 2 admission windows
        n_windows = 4096 // 512
        assert np.all(res.column("gate_flips") <= n_windows // 2)

    def test_outage_sheds_then_recovers(self):
        """Comp-node outage mid-trace (outage_grid, slots [1024, 1536)):
        the gate sheds during the outage and re-admits after the Up
        transition, restoring delivered QPS to >= 0.9 x bound; the fault
        planner classifies the same outage as an evictable straggler."""
        bound = policy_bound_exact("outage_grid", "pi3_reg", 0.05, 0)
        jobs = [ServingJob(scenario="outage_grid", trace="bursty",
                           lam=0.95 * bound, seed=s) for s in (0, 1)]
        res = run_serving(jobs, T=4096, chunk=256, stream=True)
        shed = res.column("shed_frac")
        assert np.all(shed > 0.05), shed         # the outage forced shedding
        assert np.all(res.column("gate") == 1.0)  # ... and the gate reopened
        assert np.all(res.column("gate_flips") >= 2.0)
        # recovery: windowed delivered QPS back above 0.9 x bound for every
        # post-recovery chunk (the outage ends at t=1536; give the backlog
        # 3072 - 1536 slots to drain)
        tail = [r for r in res.stream_records if r["t"] > 3072]
        assert tail, "no post-recovery stream records"
        for r in tail:
            assert r["qps_med"] >= 0.9 * bound, r

        # the same incident through the fault-tolerance planner: the
        # outage node's step times blow up -> straggler -> rebalance plan
        det = StragglerDetector([f"n{i}" for i in range(4)],
                                StragglerConfig(window=8, factor=1.5,
                                                patience=2,
                                                heartbeat_timeout_s=60))
        for t in range(12):
            for h in ("n1", "n2", "n3"):
                det.record(h, 1.0, now=float(t))
            det.record("n0", 5.0, now=float(t))   # the Down comp node
            slow = det.stragglers()               # streak builds per check
        assert slow == ["n0"]
        plan = plan_recovery(n_hosts=4, devices_per_host=1, dead=[],
                             stragglers=slow, model_parallel=1)
        assert plan.action == "rebalance" and plan.evict == ("n0",)

    def test_report_and_stream_jsonl(self, tmp_path):
        rep = serving_report("paper_grid", "pi3_reg", "bursty",
                             rate_fracs=(0.6,), seeds=(0,), T=4096,
                             chunk=512, stream=True)
        row = rep["rows"]["0.6"]
        assert row["shed_frac"] == 0.0
        assert row["delivered_over_bound"] >= 0.5
        assert rep["bound_exact"] > 0
        res = rep["result"]
        assert len(res.stream_records) == res.T // 512
        path = tmp_path / "stream.jsonl"
        n = write_stream_jsonl(res, str(path))
        lines = path.read_text().splitlines()
        assert n == len(lines) == len(res.stream_records)
        assert lines[0] == jsonl_line(res.stream_records[0])
        rec = res.stream_records[-1]
        assert {"t", "qps_med", "shed_frac_med", "p99_med",
                "gate_open_frac", "verdicts"} <= set(rec)


# ---------------------------------------------------------------------------
# backend parity (marker: pallas — re-run under JAX_PLATFORMS=cpu)
# ---------------------------------------------------------------------------

@pytest.mark.pallas
class TestServingBackendParity:
    def test_pallas_serving_path_bit_identical(self):
        bound = policy_bound_exact("paper_grid", "pi3_reg", 0.05, 0)
        results = {}
        for backend in ("xla", "pallas"):
            jobs = [ServingJob(trace="bursty", lam=0.95 * bound, seed=s,
                               backend=backend) for s in (0, 1)]
            results[backend] = run_serving(jobs, T=512, chunk=128)
        for mx, mp in zip(results["xla"].metrics,
                          results["pallas"].metrics):
            assert set(mx) == set(mp)
            for k in mx:
                np.testing.assert_array_equal(np.asarray(mx[k]),
                                              np.asarray(mp[k]), err_msg=k)
