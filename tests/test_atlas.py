"""Capacity-atlas tests (DESIGN.md §10): the pure `Bisection` machine
against a reference reimplementation of the sequential loop (property
tests + deterministic grid), batched-vs-sequential bit-equivalence of the
mini-atlas, UNDECIDED-vs-UNSTABLE surfacing on the golden frontier, and
the early-stop interaction regression on a mixed multi-rate batch."""
import pytest

try:        # property tests widen coverage when hypothesis exists;
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                   # the deterministic grid always runs
    HAVE_HYPOTHESIS = False

import dataclasses

import numpy as np

from repro.fleet import (AtlasJob, Bisection, FleetJob, PadDims,
                         atlas_table, find_lambda_max, get_scenario,
                         make_buckets, pad_problem, policy_surface_table,
                         problem_shape, run_fleet, sweep_lambda_max,
                         sweep_policy_surface, validate_buckets)

# ---------------------------------------------------------------------------
# The pure bisection machine (satellite: in-place probe-rewrite properties)
# ---------------------------------------------------------------------------


def _reference_search(oracle, k_lo, k_hi, max_calls):
    """The PR-5 sequential control flow, verbatim: shrink the floor, push
    the ceiling, integer-bisect — with the memo and the conservative
    budget-exhausted pseudo-verdict inline.  The `Bisection` machine must
    reproduce this probe-for-probe."""
    probes, cache = [], {}

    def evaluate(k):
        if k <= 0:
            return True
        if k in cache:
            return cache[k]
        if len(probes) >= max_calls:
            return False
        sus, _ = oracle(k)
        cache[k] = sus
        probes.append(k)
        return sus

    while k_lo > 0 and not evaluate(k_lo):
        k_lo //= 2
    while evaluate(k_hi) and len(probes) < max_calls:
        k_lo = max(k_lo, k_hi)
        k_hi *= 2
    n_iters = 0
    while k_hi - k_lo > 1 and len(probes) < max_calls:
        mid = (k_lo + k_hi) // 2
        if evaluate(mid):
            k_lo = mid
        else:
            k_hi = mid
        n_iters += 1
    return probes, k_lo, k_hi, n_iters


def _drive(oracle, k_lo, k_hi, max_calls):
    """Pull probes from a `Bisection` until done; returns the machine and
    its probe order."""
    bis = Bisection(k_lo, k_hi, max_calls=max_calls)
    order = []
    for _ in range(4 * max_calls + 200):      # hard stop: must terminate
        k = bis.next_rate_index()
        if k is None:
            break
        order.append(k)
        bis.record(k, *oracle(k))
    else:
        pytest.fail("Bisection did not terminate")
    return bis, order


def _seeded_oracle(seed, p_sus=0.5, p_und=0.3):
    """Deterministic pseudo-random verdict oracle: same k -> same outcome."""
    def oracle(k):
        rng = np.random.default_rng((seed, k))
        sus = bool(rng.random() < p_sus)
        und = bool(not sus and rng.random() < p_und)
        return sus, und
    return oracle


def _monotone_oracle(k_star, und_above=()):
    """sustainable iff k <= k_star; indices in `und_above` block with
    UNDECIDED evidence instead of a proven UNSTABLE latch."""
    def oracle(k):
        sus = k <= k_star
        return sus, (not sus and k in und_above)
    return oracle


class TestBisectionMachine:
    # deterministic fallback grid, always run (hypothesis widens it below)
    GRID = [(s, lo, hi, mc) for s in (0, 1, 2, 3)
            for lo, hi in ((5, 11), (0, 4), (20, 21), (1, 64))
            for mc in (0, 1, 3, 8, 24)]

    @pytest.mark.parametrize("seed,k_lo,k_hi,max_calls", GRID)
    def test_matches_sequential_reference(self, seed, k_lo, k_hi, max_calls):
        oracle = _seeded_oracle(seed)
        bis, order = _drive(oracle, k_lo, k_hi, max_calls)
        ref_order, ref_lo, ref_hi, ref_iters = _reference_search(
            oracle, k_lo, k_hi, max_calls)
        assert order == ref_order
        assert (bis.k_lo, bis.k_hi, bis.n_iters) == (ref_lo, ref_hi,
                                                     ref_iters)
        assert bis.n_evals == len(order) <= max_calls

    if HAVE_HYPOTHESIS:
        @settings(max_examples=200, deadline=None)
        @given(seed=st.integers(0, 2 ** 16), k_lo=st.integers(0, 64),
               k_hi=st.integers(1, 128), max_calls=st.integers(0, 24),
               p_sus=st.floats(0.0, 1.0), p_und=st.floats(0.0, 1.0))
        def test_property_matches_reference(self, seed, k_lo, k_hi,
                                            max_calls, p_sus, p_und):
            oracle = _seeded_oracle(seed, p_sus, p_und)
            bis, order = _drive(oracle, k_lo, k_hi, max_calls)
            ref_order, ref_lo, ref_hi, ref_iters = _reference_search(
                oracle, max(k_lo, 0), max(k_hi, k_lo + 1, 1), max_calls)
            assert order == ref_order
            assert (bis.k_lo, bis.k_hi, bis.n_iters) == (ref_lo, ref_hi,
                                                         ref_iters)

        @settings(max_examples=100, deadline=None)
        @given(seed=st.integers(0, 2 ** 16), k_lo=st.integers(0, 64),
               k_hi=st.integers(1, 128), max_calls=st.integers(1, 24))
        def test_property_probes_on_grid_and_unique(self, seed, k_lo, k_hi,
                                                    max_calls):
            """Probes stay on the positive integer grid and a grid index is
            never re-probed (the sequential memo, machine edition)."""
            _, order = _drive(_seeded_oracle(seed), k_lo, k_hi, max_calls)
            assert all(isinstance(k, int) and k >= 1 for k in order)
            assert len(order) == len(set(order)) <= max_calls

        @settings(max_examples=100, deadline=None)
        @given(k_star=st.integers(0, 100), k_lo=st.integers(0, 64),
               k_hi=st.integers(1, 128))
        def test_property_monotone_oracle_converges(self, k_star, k_lo,
                                                    k_hi):
            """With a monotone oracle and ample budget the machine always
            localizes the boundary to (k_star, k_star + 1) — invariant to
            the starting bracket."""
            bis, _ = _drive(_monotone_oracle(k_star), k_lo, k_hi,
                            max_calls=64)
            assert bis.k_lo == k_star
            assert bis.k_hi == k_star + 1

    def test_brackets_narrow_monotonically(self):
        """Once the grow phase ends, every recorded probe shrinks the
        bracket: each (k_lo, k_hi) interval nests inside the previous."""
        oracle = _monotone_oracle(13)
        bis = Bisection(5, 11, max_calls=24)
        growing = True
        prev = None
        while (k := bis.next_rate_index()) is not None:
            bis.record(k, *oracle(k))
            if growing and bis._phase == "mid":
                growing = False
                prev = (bis.k_lo, bis.k_hi)
            elif not growing:
                lo, hi = bis.k_lo, bis.k_hi
                assert prev[0] <= lo <= hi <= prev[1]
                assert hi - lo < prev[1] - prev[0] or bis.done
                prev = (lo, hi)
        assert bis.k_lo == 13 and bis.k_hi == 14

    def test_decided_machine_never_gets_a_new_rate(self):
        """A finished machine returns None forever and rejects records —
        the atlas invariant that decided cells never get their lanes
        rewritten."""
        bis, _ = _drive(_monotone_oracle(7), 5, 11, max_calls=24)
        assert bis.done
        for _ in range(3):
            assert bis.next_rate_index() is None
        with pytest.raises(ValueError):
            bis.record(7, True)

    def test_undecided_at_horizon_widens_reported_bracket(self):
        """UNDECIDED blocking evidence keeps the conservative bracket but
        is surfaced: `undecided_hi` flags the upper end, `k_hi_certain`
        is the nearest *proven* UNSTABLE index (None when none exists)."""
        # boundary at 8; 9 and 10 blocked by horizon-limited evidence, 11
        # genuinely diverges.
        bis, _ = _drive(_monotone_oracle(8, und_above=(9, 10)), 5, 11,
                        max_calls=24)
        assert bis.k_lo == 8 and bis.k_hi == 9
        assert bis.undecided_hi
        assert bis.k_hi_certain == 11
        # ... and with *only* undecided blocks there is no certain ceiling
        bis2, _ = _drive(_monotone_oracle(8, und_above=(9, 10, 11, 16, 22)),
                         5, 11, max_calls=24)
        assert bis2.undecided_hi and bis2.k_hi_certain is None
        # a proven UNSTABLE boundary reports no widening at all
        bis3, _ = _drive(_monotone_oracle(8), 5, 11, max_calls=24)
        assert not bis3.undecided_hi
        assert bis3.k_hi_certain == bis3.k_hi == 9


# ---------------------------------------------------------------------------
# Batched-vs-sequential equivalence: the mini-atlas is bit-identical
# ---------------------------------------------------------------------------

# Heterogeneous topologies (grid / cycle / tree / circulant) in one padded
# batch; eps_b is off-default so the runner memo key — hence the compile
# count below — is private to this test module.
MINI_CELLS = [AtlasJob(s, policy="pi3", eps_b=0.0521)
              for s in ("paper_grid", "ring", "tree", "expander")]
MINI_KW = dict(seeds=(0,), T=2048, chunk=256, rel_tol=0.2, max_calls=8)


@pytest.fixture(scope="module")
def mini_atlas():
    return sweep_lambda_max(MINI_CELLS, **MINI_KW)


@pytest.mark.fleet_smoke
class TestAtlasEquivalence:
    def test_bit_identical_to_sequential_frontier(self, mini_atlas):
        """Every cell of the 4-scenario mini-atlas must reproduce
        per-scenario `find_lambda_max` *bit-identically* — same quantized
        grid, same fold_seed streams, same probe order, same verdicts —
        when the sequential path runs at the atlas-wide PadDims."""
        res = mini_atlas
        assert res.n_cells == 4 and res.n_programs == 1
        for row in res.rows:
            seq = find_lambda_max(
                row.scenario, row.policy, eps_b=row.eps_b,
                topo_seed=row.topo_seed, dims=res.dims, **MINI_KW)
            assert row.lam_max == seq.lam_max, row.scenario
            assert (row.lo, row.hi, row.ratio) == (seq.lo, seq.hi,
                                                   seq.ratio)
            assert row.bound_exact == seq.bound_exact
            assert (row.n_calls, row.n_iters) == (seq.n_calls, seq.n_iters)
            assert row.undecided == seq.undecided
            assert row.hi_certain == seq.hi_certain
            assert row.probes == seq.probes, (
                f"{row.scenario}: probe streams diverged")
            assert (row.total_slots, row.slots_saved) == (
                seq.total_slots, seq.slots_saved)

    def test_single_step_compile_per_policy_group(self, mini_atlas):
        """TestNoRecompilation, atlas edition: hundreds of in-place probe
        rewrites must never re-trace — one compiled chunk-step program per
        policy group, total."""
        res = mini_atlas
        assert res.n_step_compiles == res.n_programs == 1, (
            f"atlas retraced: {res.n_step_compiles} chunk-step programs "
            f"for {res.n_programs} groups")
        assert res.n_launches < res.seq_launches
        assert res.launch_speedup > 1.0
        assert res.n_rewrites >= res.n_cells     # every cell re-probed

    def test_atlas_table_reports_families(self, mini_atlas):
        tbl = atlas_table(mini_atlas)
        assert set(tbl["families"]) == {c.scenario for c in MINI_CELLS}
        for fam in tbl["families"].values():
            assert fam["n_cells"] == 1
            # k * (rel_tol * bound) / bound can round one ulp past 1.0
            assert 0.0 <= fam["ratio_median"] <= 1.0 + 1e-9
            cell = fam["cells"][0]
            assert {"lam_max", "bound_exact", "undecided_hi",
                    "hi_certain"} <= set(cell)
        assert tbl["n_step_compiles"] == tbl["n_programs"] == 1


# ---------------------------------------------------------------------------
# UNDECIDED surfacing on the golden frontier (fix satellite)
# ---------------------------------------------------------------------------

@pytest.mark.fleet_smoke
class TestFrontierUndecidedSurfacing:
    def test_golden_bracket_distinguishes_unstable_from_undecided(self):
        """paper_grid at T=2048 ends its search blocked by an UNDECIDED
        probe one grid step above a genuinely UNSTABLE one: the result
        must keep the conservative bracket *and* surface the distinction
        (probe flags, result.undecided, the widened hi_certain)."""
        r = find_lambda_max("paper_grid", "pi3", eps_b=0.05, seeds=(0,),
                            T=2048, chunk=256, rel_tol=0.1, max_calls=8)
        by_k = {p.rate_index: p for p in r.probes}
        kinds = {n for p in r.probes for n in p.verdicts}
        assert {"STABLE", "UNSTABLE", "UNDECIDED"} <= kinds
        unstable = [p for p in r.probes if "UNSTABLE" in p.verdicts]
        undecided = [p for p in r.probes if p.undecided]
        assert unstable and undecided
        for p in undecided:           # the flag means: blocked, not proven
            assert not p.sustainable and "UNSTABLE" not in p.verdicts
        for p in unstable:
            assert not p.undecided
        # conservative bracket unchanged; honest reading surfaced on top
        k_hi = round(r.hi / (0.1 * r.bound_exact))
        assert by_k[k_hi].undecided == r.undecided
        if r.undecided:
            assert r.hi_certain is not None and r.hi_certain > r.hi
        assert r.lam_max >= 0.8 * r.bound_exact

    def test_horizon_too_short_reports_undecided_not_unstable(self):
        """At T=512/chunk=256 no verdict can latch (first possible latch
        is 6 windows = 1536 slots), so every probe is horizon-blocked:
        the search must say UNDECIDED-everywhere (lam_max collapses to 0
        conservatively, nothing is *proven* infeasible)."""
        r = find_lambda_max("paper_grid", "pi3", eps_b=0.05, seeds=(0,),
                            T=512, chunk=256, rel_tol=0.1, max_calls=6)
        assert r.lam_max == 0.0
        assert all(p.undecided for p in r.probes)
        assert all(set(p.verdicts) == {"UNDECIDED"} for p in r.probes)
        assert r.undecided and r.hi_certain is None


# ---------------------------------------------------------------------------
# Early-stop interaction on a mixed multi-rate batch (regression satellite)
# ---------------------------------------------------------------------------

@pytest.mark.fleet_smoke
class TestMixedRateEarlyStopRegression:
    def test_undecided_sims_bit_equal_despite_mid_chunk_deciders(self):
        """The atlas carry in miniature: one padded batch probing three
        different rates, where the stable and unstable sims decide
        mid-run and freeze while the near-critical one rides to the
        horizon.  The undecided sim's metrics must be bit-equal to an
        early_stop=False run — deciders freezing around it must not
        perturb its lanes."""
        # chunk 512 keeps the verdict burn-in past the gradient-fill
        # transient; T = 8 chunks leaves the deciders room to latch early.
        jobs = [FleetJob(scenario="paper_grid", policy="pi3", lam=lam,
                         eps_b=0.05, seed=0) for lam in (4.0, 8.2, 8.8)]
        a = run_fleet(jobs, T=4096, chunk=512, early_stop=True)
        b = run_fleet(jobs, T=4096, chunk=512, early_stop=False)
        va, vb = a.verdicts(), b.verdicts()
        assert va == vb == ["STABLE", "UNDECIDED", "UNSTABLE"]
        # the mix is real: both deciders latched before the horizon
        assert a.metrics[0]["decided_at_slot"] < 4096
        assert a.metrics[2]["decided_at_slot"] < 4096
        # ... and the undecided sim is bit-untouched by their freezing
        mu_a, mu_b = dict(a.metrics[1]), dict(b.metrics[1])
        mu_a.pop("slots_saved"), mu_b.pop("slots_saved")
        assert mu_a == mu_b, {
            k: (mu_a[k], mu_b[k]) for k in mu_a if mu_a[k] != mu_b[k]}
        assert a.metrics[1]["slots_saved"] == 0.0
        # deciders agree on verdict/decision slot across modes
        for i in (0, 2):
            assert a.metrics[i]["decided_at_slot"] == \
                b.metrics[i]["decided_at_slot"]


# ---------------------------------------------------------------------------
# Size bucketing (DESIGN.md §13): pure partition properties + validation
# ---------------------------------------------------------------------------

class TestBucketing:
    MIXED = [get_scenario(s).build(0)
             for s in ("ring", "tree", "paper_grid", "expander")]

    def test_single_bucket_is_the_global_hull(self):
        dims, assignment = make_buckets(self.MIXED, n_buckets=1)
        assert dims == [PadDims.of(self.MIXED)]
        assert assignment == [0] * len(self.MIXED)

    def test_two_buckets_cover_and_shrink(self):
        dims, assignment = make_buckets(self.MIXED, n_buckets=2)
        assert len(dims) == 2
        hull = PadDims.of(self.MIXED)
        for p, b in zip(self.MIXED, assignment):
            assert dims[b].fits(p)
        # the small bucket must actually be smaller than the hull on the
        # dominant (edge) axis — the whole point of bucketing
        assert min(d.n_edges for d in dims) < hull.n_edges
        # buckets are ordered by size: bucket 0 never exceeds bucket 1
        assert dims[0].n_edges <= dims[1].n_edges

    def test_identical_shapes_share_a_bucket(self):
        probs = [get_scenario("ring").build(ts) for ts in (0, 1, 2)]
        probs += [get_scenario("expander").build(0)]
        _, assignment = make_buckets(probs, n_buckets=3)
        assert len(set(assignment[:3])) == 1      # all rings together

    def test_more_buckets_than_shapes_drops_empties(self):
        probs = [get_scenario("ring").build(0),
                 get_scenario("expander").build(0)]
        dims, assignment = make_buckets(probs, n_buckets=5)
        assert len(dims) == len(set(assignment)) == 2

    def test_empty_problem_list_raises_clearly(self):
        with pytest.raises(ValueError, match="empty problem sequence"):
            PadDims.of([])
        with pytest.raises(ValueError, match="empty problem sequence"):
            make_buckets([])

    def test_pad_problem_overflow_names_shapes(self):
        big = get_scenario("expander").build(0)
        small = PadDims.of([get_scenario("ring").build(0)])
        with pytest.raises(ValueError, match=r"exceeds pad dims"):
            pad_problem(big, small)

    def test_validate_buckets_actionable_errors(self):
        probs = self.MIXED[:2]
        dims = [PadDims.of(probs)]
        with pytest.raises(ValueError, match="bucket assignments"):
            validate_buckets(probs, dims, [0])
        with pytest.raises(ValueError, match="only 1 buckets exist"):
            validate_buckets(probs, dims, [0, 3])
        tiny = PadDims(n_nodes=2, n_edges=1, n_comp=1)
        with pytest.raises(ValueError, match=r"exceeds bucket 0 dims"):
            validate_buckets(probs, [tiny], [0, 0])


# ---------------------------------------------------------------------------
# Bucketed atlas: bit-equality to the single-bucket path at bucket dims
# ---------------------------------------------------------------------------

@pytest.mark.fleet_smoke
class TestBucketedEquivalence:
    @pytest.fixture(scope="class")
    def bucketed(self):
        return sweep_lambda_max(MINI_CELLS, n_buckets=2, **MINI_KW)

    def test_two_buckets_two_programs_one_group(self, bucketed):
        res = bucketed
        assert res.n_buckets == 2
        # one policy group x 2 buckets: one launch unit (and one compiled
        # step trace) per bucket, counted once per group
        assert res.n_programs == 2
        assert res.n_step_compiles == 2
        assert sum(res.bucket_cells.values()) == res.n_cells
        assert sum(res.bucket_launches.values()) == res.n_launches
        assert all(n > 0 for n in res.bucket_launches.values())
        # result.dims is the hull of the bucket dims
        assert res.dims == PadDims(
            n_nodes=max(d.n_nodes for d in res.bucket_dims),
            n_edges=max(d.n_edges for d in res.bucket_dims),
            n_comp=max(d.n_comp for d in res.bucket_dims),
            max_degree=max(d.max_degree for d in res.bucket_dims))

    def test_rows_bit_identical_to_single_bucket_at_bucket_dims(
            self, bucketed):
        """Per-cell searches must not notice bucketing: every row equals
        the row the single-bucket sweep produces when forced (via explicit
        ``dims``) to the cell's bucket dims."""
        res = bucketed
        by_cell = {(r.scenario, r.topo_seed): r for r in res.rows}
        for b, bdims in enumerate(res.bucket_dims):
            cells_b = [c for c in MINI_CELLS
                       if by_cell[(c.scenario, c.topo_seed)].bucket == b]
            assert cells_b, f"bucket {b} has no cells"
            single = sweep_lambda_max(cells_b, dims=bdims, **MINI_KW)
            for row in single.rows:
                got = by_cell[(row.scenario, row.topo_seed)]
                assert dataclasses.replace(got, bucket=0) == row, (
                    f"{row.scenario}: bucketed != single-bucket at "
                    f"bucket {b} dims")

    def test_cells_assigned_to_fitting_buckets(self, bucketed):
        res = bucketed
        for r in res.rows:
            shape = problem_shape(get_scenario(r.scenario).build(r.topo_seed))
            d = res.bucket_dims[r.bucket]
            assert shape <= (d.n_nodes, d.n_edges, d.n_comp) or \
                d.fits(get_scenario(r.scenario).build(r.topo_seed))


# ---------------------------------------------------------------------------
# Adaptive horizons: UNDECIDED-at-top cells re-queue instead of collapsing
# ---------------------------------------------------------------------------

@pytest.mark.fleet_smoke
class TestAdaptiveRequeue:
    # T=512/chunk=256 can never latch a verdict (first possible latch is
    # 1280 slots), so every fixed-horizon probe is UNDECIDED and the
    # bracket collapses to lam_max = 0 — the bug the re-queue fixes.
    CELLS = [AtlasJob("paper_grid", eps_b=0.05)]
    KW = dict(seeds=(0,), T=512, chunk=256, rel_tol=0.1, max_calls=6)

    def test_fixed_horizon_collapses(self):
        res = sweep_lambda_max(self.CELLS, **self.KW)
        row = res.rows[0]
        assert row.lam_max == 0.0 and row.undecided
        assert res.n_requeues == 0 and row.n_requeues == 0

    def test_requeue_recovers_a_real_bracket(self):
        """With max_requeues=2 the cell restarts at 2xT (1024 slots —
        still short of the 1280-slot latch) and then 4xT (2048 slots),
        where verdicts latch and the search localizes a genuine
        bracket: zero silently-collapsed cells."""
        res = sweep_lambda_max(self.CELLS, max_requeues=2, **self.KW)
        row = res.rows[0]
        assert res.n_requeues == 2 and row.n_requeues == 2
        assert row.lam_max > 0.0, "re-queued cell still collapsed"
        assert row.hi > row.lo > 0.0
        # A 2048-slot horizon localizes conservatively (the bench runs
        # far longer); the point here is a real bracket, not precision.
        assert row.lam_max >= 0.6 * row.bound_exact
        # honest reporting either way: decided, or widened with evidence
        if row.undecided:
            assert row.hi_certain is not None
        # probe streams are decoupled per attempt: call_index == attempt
        attempts = {p.call_index for p in row.probes}
        assert attempts == {0, 1, 2}
        # first-attempt probes are the fixed-horizon probes, bit-equal
        fixed = sweep_lambda_max(self.CELLS, **self.KW).rows[0]
        first = tuple(p for p in row.probes if p.call_index == 0)
        assert first == fixed.probes

    def test_budget_cap_reports_honestly(self):
        """One escalation (1024 slots) still cannot latch: the budget-
        capped cell must report the collapse with its attempt count, not
        pretend it converged."""
        res = sweep_lambda_max(self.CELLS, max_requeues=1, **self.KW)
        row = res.rows[0]
        assert res.n_requeues == 1 and row.n_requeues == 1
        assert row.undecided and row.lam_max == 0.0

    def test_certain_collapse_requeues_too(self):
        """A bracket that collapses with *proven*-UNSTABLE evidence (not
        UNDECIDED) must also burn the re-queue ladder.  At rates far
        below capacity the backpressure gradient fills so slowly that
        the whole horizon sits inside the transient and the drift + gap
        tests latch a *false* UNSTABLE — paper_grid topo_seed 8 / seed 0
        at T=4096 reads proven-UNSTABLE at 0.1x its own exact bound and
        collapses with certainty (hi_certain populated, not UNDECIDED).
        One 2xT rung must repair it: the fresh attempt's top-of-bracket
        probe decides STABLE on the longer run and the search ascends
        to the true bound instead of reporting 0."""
        cells = [AtlasJob("paper_grid", topo_seed=8, eps_b=0.05)]
        kw = dict(seeds=(0,), T=4096, chunk=512, rel_tol=0.1, max_calls=8)
        base = sweep_lambda_max(cells, **kw).rows[0]
        # the bug: a false-certain collapse — no UNDECIDED escape hatch
        assert base.lam_max == 0.0 and not base.undecided
        assert base.hi_certain is not None
        res = sweep_lambda_max(cells, max_requeues=1, **kw)
        row = res.rows[0]
        assert res.n_requeues == 1 and row.n_requeues == 1
        # the rung disambiguates transient from instability: full repair
        assert row.lam_max == pytest.approx(row.bound_exact)
        # both attempts ran, with decoupled fold_seed streams
        assert {p.call_index for p in row.probes} == {0, 1}
        # first-attempt probes are the fixed-horizon probes, bit-equal
        first = tuple(p for p in row.probes if p.call_index == 0)
        assert first == base.probes


# ---------------------------------------------------------------------------
# Seed replication: rows and bands invariant to cell dispatch order
# ---------------------------------------------------------------------------

@pytest.mark.fleet_smoke
class TestSeedBandsDeterminism:
    CELLS = [AtlasJob("random_geometric", topo_seed=ts, eps_b=0.05)
             for ts in (0, 1, 2)]
    KW = dict(seeds=(0, 1), T=1536, chunk=256, rel_tol=0.2, max_calls=6,
              n_buckets=2)

    def test_bands_invariant_to_dispatch_order(self):
        a = sweep_lambda_max(self.CELLS, **self.KW)
        b = sweep_lambda_max(list(reversed(self.CELLS)), **self.KW)
        rows_a = {(r.scenario, r.topo_seed): r for r in a.rows}
        rows_b = {(r.scenario, r.topo_seed): r for r in b.rows}
        assert rows_a == rows_b
        ta, tb = atlas_table(a), atlas_table(b)
        assert ta["families"] == tb["families"]
        band = ta["families"]["random_geometric"]["band"]
        assert band["q10"] <= band["q90"]
        assert band["width"] == band["q90"] - band["q10"]
        assert a.bucket_cells == b.bucket_cells

    def test_atlas_table_reports_buckets_and_bands(self):
        res = sweep_lambda_max(self.CELLS, **self.KW)
        tbl = atlas_table(res)
        assert tbl["n_buckets"] == res.n_buckets
        assert len(tbl["bucket_dims"]) == res.n_buckets
        assert tbl["n_requeues"] == res.n_requeues
        fam = tbl["families"]["random_geometric"]
        assert {"band", "n_requeued"} <= set(fam)
        for cell in fam["cells"]:
            assert {"bucket", "n_requeues"} <= set(cell)


# ---------------------------------------------------------------------------
# Atlas-over-policies: the policy-surface table
# ---------------------------------------------------------------------------

@pytest.mark.fleet_smoke
class TestPolicySurface:
    def test_surface_shares_grid_and_pivots(self):
        res = sweep_policy_surface(
            ["paper_grid"], [0], policies=("pi3", "pi3bar"), eps_b=0.05,
            seeds=(0,), T=2048, chunk=256, rel_tol=0.2, max_calls=6)
        assert res.n_cells == 2
        policies = {r.policy for r in res.rows}
        assert policies == {"pi3", "pi3bar"}
        # both policies measured against the same exact bound per cell
        bounds = {r.policy: r.bound_exact for r in res.rows}
        assert bounds["pi3"] > 0 and bounds["pi3bar"] > 0
        tbl = policy_surface_table(res)
        assert set(tbl["policies"]) == policies
        assert tbl["families"] == ["paper_grid"]
        gaps = [tbl["policies"][p]["paper_grid"]["gap_vs_best"]
                for p in policies]
        assert min(gaps) == 0.0 and all(g >= 0.0 for g in gaps)
        for p in policies:
            row = tbl["policies"][p]["paper_grid"]
            assert row["band"]["width"] >= 0.0
