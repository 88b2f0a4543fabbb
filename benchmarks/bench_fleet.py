"""Fleet sweep benchmark: scenarios vs their LP capacity bounds.

Runs a (scenario x policy x rate x seed) grid through the sharded fleet
engine and emits a JSON capacity/efficiency table.  Regulated policies
(pi3_reg etc.) are scored against the *exact* regulated LP bound
`capacity_upper_bound(problem, rho0=1+eps_B)` — rows carry both
`bound_exact` and the closed-form `bound_approx = lam_star/(1+eps_B)`
(DESIGN.md §6).  The smoke preset packs >= 64 simulations into <= 3
compiled programs (one per *semantic* policy group: pi3 and pi3_reg share
a program, eps_B is traced data), includes regulated policies under
Gilbert–Elliott Markov link fading AND Markov comp-node failures
(`ge_comp_grid`), and checks physical sanity: measured useful rate never
exceeds the LP upper bound, pi3 sustains >= 0.8 and pi3_reg >= 0.9 of
their exact bounds on the paper's 4x4 grid.

The emitted table also records engine throughput (`us_per_sim`,
`sims_per_sec`), the XLA memory analysis of the largest chunk-step
program (`memory.peak_bytes` etc.), a `backends` section timing the
same sweep under both slot-decision backends — the XLA oracle and the
fused Pallas slot kernels (`FleetJob(backend="pallas")`, DESIGN.md §7) —
with a bit-exact parity gate, and a `frontier` section measuring the
empirical max sustainable rate per target via `find_lambda_max`
(early-stopped adaptive bisection, DESIGN.md §8): measured
`lam_max / bound_exact` must land in FRONTIER_RATIO_BAND and the early
stop must save >= FRONTIER_MIN_SAVED_FRAC of the simulated slots.
`scripts/check_bench.py` gates committed baselines
(`BENCH_baseline.json`) against regressions.

Usage:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python benchmarks/bench_fleet.py --preset smoke [--out fleet.json] \
          [--stream-out FLEET_stream.jsonl]
"""
from __future__ import annotations

import argparse
import json
import time

PRESETS = {
    "smoke": dict(
        scenario_policies={
            "paper_grid": ("pi3", "pi3bar", "pi3_reg"),
            "random_geometric": ("pi3", "pi3bar"),
            "expander": ("pi3", "pi3bar"),
            "fat_tree": ("pi3", "pi3bar"),
            "ge_grid": ("pi3_reg",),
            "ge_comp_grid": ("pi3_reg",),
        },
        rate_fracs=(0.3, 0.6, 0.8, 0.95),
        seeds=(0, 1),
        T=4000, chunk=500,
        eps_b=0.05,
    ),
    "full": dict(
        scenario_policies={
            "paper_grid": ("pi1", "pi2", "pi3", "pi3bar", "pi2_reg",
                           "pi3_reg"),
            "random_geometric": ("pi3", "pi3bar"),
            "ring": ("pi3", "pi3bar"),
            "tree": ("pi3", "pi3bar"),
            "expander": ("pi3", "pi3bar"),
            "fat_tree": ("pi3", "pi3bar"),
            "wireless_grid": ("pi3",),
            "fading_geometric": ("pi3",),
            "flaky_expander": ("pi3",),
            "failing_grid": ("pi3",),
            "ge_grid": ("pi3_reg", "pi3bar"),
            "ge_geometric": ("pi3_reg",),
            "bursty_grid": ("pi3_reg", "pi3bar"),
            "ge_comp_grid": ("pi3_reg", "pi3bar"),
            "ge_full_grid": ("pi3_reg",),
        },
        rate_fracs=(0.2, 0.4, 0.6, 0.8, 0.9, 0.95),
        seeds=(0, 1, 2),
        T=20000, chunk=1000,
        eps_b=0.05,
    ),
}

# Windowed rates can transiently exceed the long-run bound by backlog drain;
# 2% covers that noise without masking a real capacity violation.
LP_TOL = 1.02

#: (scenario, policy) -> minimum efficiency vs the exact regulated LP bound
#: (DESIGN.md §6).  Single source of truth: asserted here on every bench run
#: and imported by scripts/check_bench.py for the CI baseline gate.  Rows a
#: preset does not sweep are skipped.
EFFICIENCY_GATES = {
    ("paper_grid", "pi3"): 0.8,
    ("paper_grid", "pi3_reg"): 0.9,
    ("ge_grid", "pi3_reg"): 0.9,
    ("ge_comp_grid", "pi3_reg"): 0.9,
}


#: Backend-comparison sweep (DESIGN.md §7): the same jobs through the XLA
#: oracle and the fused Pallas slot kernels (compiled on a TPU), timed
#: side by side and gated on bit-exact metric parity by check_bench.
BACKEND_COMPARE = dict(scenario="paper_grid", policy="pi3_reg", eps_b=0.05,
                       n_jobs=8, lam0=4.0, dlam=0.25, T=512, chunk=128)


#: Frontier smoke (DESIGN.md §8): adaptive lam_max searches, early-stopped.
#: T must comfortably cover the backpressure gradient fill-up (the verdict
#: burn-in is 2 chunks here) or stable rates read as still-growing.
FRONTIER_SMOKE = dict(
    targets=(("paper_grid", "pi3"), ("paper_grid", "pi3_reg")),
    eps_b=0.05, seeds=(0, 1), T=4096, chunk=256, rel_tol=0.025)

#: measured lam_max / bound_exact band for the paper grid (acceptance:
#: the empirical frontier localizes the exact regulated LP bound from
#: below).  Imported by scripts/check_bench.py for the CI baseline gate.
FRONTIER_RATIO_BAND = (0.90, 1.0)

#: minimum fraction of simulated slots the early stop must save across the
#: whole frontier smoke (per-sim freeze savings, summed over all probes).
FRONTIER_MIN_SAVED_FRAC = 0.30


#: Resilience overhead smoke (DESIGN.md §12): the same sweep plain and with
#: chunk-boundary checkpointing on (snapshot-before-donate, background disk
#: writes), timed side by side and gated on bit-exact metric parity here
#: plus the overhead ceiling in scripts/check_bench.py.
RESILIENCE_COMPARE = dict(scenario="paper_grid", policy="pi3_reg",
                          eps_b=0.05, n_jobs=8, lam0=4.0, dlam=0.25,
                          T=2048, chunk=256)

#: checkpoint-on us_per_sim must stay within 1 + this of the plain run.
#: Imported by scripts/check_bench.py for the CI gate.
RESILIENCE_MAX_OVERHEAD = 0.05


def frontier_section(emit) -> dict:
    """Run the FRONTIER_SMOKE searches and gate their ratios/savings.

    Each target runs `find_lambda_max` — exact-LP-seeded bracket, integer
    bisection on the rel_tol grid, per-probe early stop — and must land
    its measured lam_max inside FRONTIER_RATIO_BAND of the exact bound
    while the freeze saves at least FRONTIER_MIN_SAVED_FRAC of the
    simulated slots in aggregate (DESIGN.md §8)."""
    from repro.fleet import find_lambda_max

    c = dict(FRONTIER_SMOKE)
    targets = c.pop("targets")
    out: dict = {"targets": {}, "T": c["T"], "rel_tol": c["rel_tol"],
                 "seeds": list(c["seeds"])}
    saved = full = 0
    for scen, pol in targets:
        t0 = time.time()
        r = find_lambda_max(scen, pol, eps_b=c["eps_b"], seeds=c["seeds"],
                            T=c["T"], chunk=c["chunk"], rel_tol=c["rel_tol"])
        wall = time.time() - t0
        row = {
            "lam_max": r.lam_max, "bound_exact": r.bound_exact,
            "ratio": r.ratio, "n_calls": r.n_calls, "n_iters": r.n_iters,
            "total_slots": r.total_slots, "full_slots": r.full_slots,
            "slots_saved": r.slots_saved,
            "slots_saved_frac": r.slots_saved_frac,
            "launch_slots_saved": r.launch_slots_saved,
            "n_step_compiles": r.n_step_compiles, "wall_s": wall,
        }
        out["targets"][f"{scen}/{pol}"] = row
        saved += r.slots_saved
        full += r.full_slots
        emit(f"fleet/frontier/{scen}/{pol},,lam_max={r.lam_max:.3f} "
             f"bound_exact={r.bound_exact:.3f} ratio={r.ratio:.3f} "
             f"calls={r.n_calls} saved_frac={r.slots_saved_frac:.3f} "
             f"compiles={r.n_step_compiles}")
        lo, hi = FRONTIER_RATIO_BAND
        assert lo <= r.ratio <= hi + 1e-9, (
            f"{scen}/{pol}: lam_max/bound {r.ratio:.3f} outside "
            f"[{lo}, {hi}]")
        assert r.n_step_compiles == 1, (
            f"{scen}/{pol}: bisection compiled {r.n_step_compiles} "
            "chunk-step programs (must reuse one)")
    out["slots_saved"] = saved
    out["full_slots"] = full
    out["slots_saved_frac"] = saved / full if full else 0.0
    emit(f"fleet/frontier/slots_saved,,{saved}/{full} "
         f"frac={out['slots_saved_frac']:.3f} "
         f"gate>={FRONTIER_MIN_SAVED_FRAC}")
    assert out["slots_saved_frac"] >= FRONTIER_MIN_SAVED_FRAC, (
        f"early stopping saved only {out['slots_saved_frac']:.1%} of "
        f"simulated slots (< {FRONTIER_MIN_SAVED_FRAC:.0%})")
    return out


def backend_compare(emit) -> dict:
    """Run the BACKEND_COMPARE sweep under both slot-decision backends.

    Each backend gets a warm-up run (compilation; the engine's memoized
    launches make the second run compile-free) and a timed run.  Returns
    {"xla": {...}, "pallas": {...}, "parity_max_abs_diff": 0.0} for the
    bench table's `backends` section."""
    import numpy as np
    from repro.fleet import FleetJob, run_fleet

    c = BACKEND_COMPARE
    out: dict = {}
    useful = {}
    for backend in ("xla", "pallas"):
        jobs = [FleetJob(scenario=c["scenario"], policy=c["policy"],
                         lam=c["lam0"] + c["dlam"] * s, eps_b=c["eps_b"],
                         seed=s, backend=backend)
                for s in range(c["n_jobs"])]
        run_fleet(jobs, T=c["T"], chunk=c["chunk"])          # warm-up
        t0 = time.time()
        res = run_fleet(jobs, T=c["T"], chunk=c["chunk"])
        wall = time.time() - t0
        useful[backend] = res.column("useful_rate")
        out[backend] = {
            "us_per_sim": wall * 1e6 / len(jobs),
            "wall_s": wall,
            "n_sims": len(jobs),
            "T": res.T,
        }
        emit(f"fleet/backends/{backend},{out[backend]['us_per_sim']:.0f},"
             f"n_sims={len(jobs)} T={res.T}")
    diff = float(np.max(np.abs(useful["xla"] - useful["pallas"])))
    out["parity_max_abs_diff"] = diff
    emit(f"fleet/backends/parity,,max_abs_diff={diff}")
    assert diff == 0.0, (
        f"pallas backend diverged from xla by {diff} (DESIGN.md §7)")
    return out


def resilience_section(emit, ckpt_dir: str = "CKPT_bench") -> dict:
    """Time the RESILIENCE_COMPARE sweep plain vs checkpoint-on.

    Checkpointing rides the chunk boundaries (DESIGN.md §12): the carry
    is read to host synchronously before the next donating launch, disk
    writes go to a background thread.  Metrics must be bit-identical
    (snapshotting is a pure read of the carry); the per-sim overhead is
    reported for check_bench's RESILIENCE_MAX_OVERHEAD gate.  The gate
    is tight (5%), well inside this box's scheduler-noise band, so the
    estimator is paired: each rep times plain and checkpoint-on
    back-to-back (a load burst inflates both), and the overhead is the
    *minimum* per-rep ratio across three reps — one burst-free rep is
    enough for a clean reading."""
    from repro.fleet import FleetJob, run_fleet
    from repro.runtime.resilience import ResilienceConfig

    c = RESILIENCE_COMPARE
    jobs = [FleetJob(scenario=c["scenario"], policy=c["policy"],
                     lam=c["lam0"] + c["dlam"] * s, eps_b=c["eps_b"],
                     seed=s)
            for s in range(c["n_jobs"])]
    kw = dict(T=c["T"], chunk=c["chunk"])
    rc = ResilienceConfig(checkpoint_dir=ckpt_dir, blocking=False,
                          resume=False)
    run_fleet(jobs, **kw)                                    # warm-up
    base = ckpt = None
    walls = {"plain": [], "ckpt": []}
    for _ in range(3):
        t0 = time.time()
        base = run_fleet(jobs, **kw)
        walls["plain"].append(time.time() - t0)
        t0 = time.time()
        ckpt = run_fleet(jobs, **kw, resilience=rc)
        walls["ckpt"].append(time.time() - t0)
    for m0, m1 in zip(base.metrics, ckpt.metrics):
        assert m0 == m1, ("checkpointing perturbed the run "
                          "(observer effect)", m0, m1)
    plain_us = min(walls["plain"]) * 1e6 / len(jobs)
    ckpt_us = min(walls["ckpt"]) * 1e6 / len(jobs)
    overhead = min(c / p for p, c in zip(walls["plain"], walls["ckpt"])) - 1.0
    out = {
        "us_per_sim_plain": plain_us,
        "us_per_sim_ckpt": ckpt_us,
        "overhead_frac": overhead,
        "n_snapshots": c["T"] // c["chunk"],
        "n_sims": len(jobs), "T": c["T"],
        "checkpoint_dir": ckpt_dir,
    }
    emit(f"fleet/resilience/overhead,,plain={plain_us:.0f}us "
         f"ckpt={ckpt_us:.0f}us frac={out['overhead_frac']:+.3f} "
         f"gate<={RESILIENCE_MAX_OVERHEAD}")
    return out


def run(emit, preset: str = "smoke", stream_out: str | None = None,
        ckpt_dir: str = "CKPT_bench") -> dict:
    from repro.fleet import capacity_report

    spec = PRESETS[preset]
    t0 = time.time()
    table = capacity_report(**spec, memory_stats=True,
                            stream_path=stream_out)
    wall = time.time() - t0
    table["preset"] = preset
    table["wall_s"] = wall
    table["us_per_sim"] = wall * 1e6 / max(table["n_sims"], 1)
    table["sims_per_sec"] = table["n_sims"] / max(wall, 1e-9)

    emit(f"fleet/{preset}/sweep,{table['us_per_sim']:.0f},"
         f"n_sims={table['n_sims']} n_programs={table['n_programs']} "
         f"sims_per_sec={table['sims_per_sec']:.2f}")
    if "memory" in table:
        emit(f"fleet/{preset}/chunk_step_memory,,"
             f"peak_bytes={table['memory']['peak_bytes']:.0f} "
             f"temp_bytes={table['memory']['temp_bytes']:.0f}")
    for scen, entry in table["scenarios"].items():
        lam_star = entry["lam_star"]
        for pol, row in entry["policies"].items():
            emit(f"fleet/{preset}/{scen}/{pol},,lam_star={lam_star:.3f} "
                 f"bound_exact={row['bound_exact']:.3f} "
                 f"bound_approx={row['bound_approx']:.3f} "
                 f"rho0={row['rho0']:.3f} "
                 f"best={row['best_useful_rate']:.3f} "
                 f"eff={row['efficiency']:.3f} "
                 f"max_stable_offered={row['max_stable_offered']:.3f}")
            assert row["best_useful_rate"] <= lam_star * LP_TOL, (
                f"{scen}/{pol}: measured {row['best_useful_rate']:.3f} "
                f"exceeds LP bound {lam_star:.3f}")
            # The approximation is a valid lower bound on the exact LP,
            # within rho0 of it (DESIGN.md §6) — a broken cache key or a
            # mismatched rho0 would show up here.
            assert row["bound_approx"] <= row["bound_exact"] * (1 + 1e-9), row
            assert row["bound_exact"] <= \
                row["bound_approx"] * row["rho0"] * (1 + 1e-9), row

    # Acceptance: gated rows reach their efficiency floor vs the *exact*
    # regulated LP bound lam_star(rho0).
    for (scen, pol), floor in EFFICIENCY_GATES.items():
        row = table["scenarios"].get(scen, {}).get("policies", {}).get(pol)
        if row is None:
            continue
        emit(f"fleet/{preset}/{scen}/{pol}_efficiency,,"
             f"eff={row['efficiency']:.3f} gate={floor} "
             f"bound_exact={row['bound_exact']:.3f}")
        assert row["efficiency"] >= floor, (
            f"{scen}/{pol} efficiency {row['efficiency']:.3f} < {floor} vs "
            f"exact regulated bound {row['bound_exact']:.3f}")

    if preset == "smoke":
        assert "pi3_reg" in table["scenarios"]["ge_grid"]["policies"], (
            "smoke must sweep a regulated policy under Gilbert–Elliott "
            "fading")
        assert "ge_comp_grid" in table["scenarios"], (
            "smoke must sweep a Markov comp-node-failure scenario")
        assert table["n_sims"] >= 64
        assert table["n_programs"] <= 3

    # Side-by-side slot-decision backends (xla oracle vs fused Pallas
    # kernels), gated on bit-exact parity (DESIGN.md §7).
    table["backends"] = backend_compare(emit)

    # Adaptive lam_max frontier (DESIGN.md §8): measured frontier must
    # bracket the exact LP bound, early stop must pay for itself.
    table["frontier"] = frontier_section(emit)

    # Preemption-safety overhead (DESIGN.md §12): checkpoint-on must be
    # bit-identical and nearly free (gated by check_bench).
    table["resilience"] = resilience_section(emit, ckpt_dir=ckpt_dir)
    return table


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--preset", choices=sorted(PRESETS), default="smoke")
    ap.add_argument("--out", default=None, help="write the JSON table here")
    ap.add_argument("--stream-out", default=None,
                    help="write per-chunk telemetry records (JSONL, "
                    "repro.obs.schema) here while the sweep runs")
    ap.add_argument("--ckpt-dir", default="CKPT_bench",
                    help="checkpoint dir for the resilience overhead "
                    "section (uploaded in the CI bench artifact)")
    args = ap.parse_args()
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    table = run(print, preset=args.preset, stream_out=args.stream_out,
                ckpt_dir=args.ckpt_dir)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(table, f, indent=2)
        print(f"wrote {args.out}")
    if args.stream_out:
        print(f"wrote {args.stream_out} "
              f"({table.get('stream_records', 0)} records)")


if __name__ == "__main__":
    main()
