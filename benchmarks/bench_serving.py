"""Serving benchmark: trace-driven admission control vs the exact LP bound.

Runs the serving subsystem (DESIGN.md §9) — markov_onoff bursty query
traces through the admission gate into the backpressure network — on the
paper's 4x4 grid under pi3_reg and scores delivered QPS against the
*exact* regulated LP bound `policy_bound_exact` (DESIGN.md §6):

* at 0.95 x bound offered load the gate must stay open (no shedding,
  `delivered_qps / bound >= SERVING_MIN_RATIO`) with bounded p99 sojourn;
* at SERVING_OVERLOAD_FRAC x bound the gate must duty-cycle: shed at
  least SERVING_OVERLOAD_MIN_SHED of the offered mass while the admitted
  rate stays at or below capacity — graceful degradation, not collapse.

A `parity` section replays a small sweep under both slot-decision
backends (XLA oracle vs the fused Pallas slot kernels, DESIGN.md §7) and
requires bit-exact agreement on every serving metric — the admission +
load-balance decision path must not fork per backend.

Per-chunk stream records (windowed QPS / shed / p99 / verdict medians)
are emitted as JSONL via --stream-out.  `scripts/check_bench.py --mode
serving` gates committed baselines (`BENCH_baseline.json`, key
`"serving"`) against regressions.

Usage:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python benchmarks/bench_serving.py --out BENCH_serving.json \
          --stream-out SERVING_stream.jsonl
"""
from __future__ import annotations

import argparse
import json
import time

#: The gated smoke sweep: bursty (markov_onoff) queries on the paper grid
#: under the regulated pi3 policy, one nominal-load row and one overload
#: row, scored against the exact regulated LP bound.  T spans 8 chunks =
#: 8 admission windows (burn-in is the first 2).
SERVING_SMOKE = dict(scenario="paper_grid", policy="pi3_reg",
                     trace="bursty", rate_fracs=(0.95, 1.3),
                     seeds=(0, 1), T=4096, chunk=512, eps_b=0.05)

#: Nominal-load row (rate_frac 0.95) acceptance gates.  Single source of
#: truth: asserted on every bench run and imported by
#: scripts/check_bench.py for the CI baseline gate.
SERVING_MIN_RATIO = 0.9      # delivered_qps / bound_exact floor
SERVING_MAX_SHED = 0.02      # shed fraction ceiling (gate must stay open)
SERVING_P99_MAX = 512.0      # p99 sojourn ceiling, slots (observed ~280)

#: Overload row (rate_frac SERVING_OVERLOAD_FRAC) gates: the gate must
#: actually shed, and the admitted rate must not exceed capacity by more
#: than slack (windowed admission can transiently overshoot the bound).
SERVING_OVERLOAD_FRAC = 1.3
SERVING_OVERLOAD_MIN_SHED = 0.10
SERVING_OVERLOAD_RATE_SLACK = 1.05

#: Backend-parity sweep: the same serving jobs under the XLA oracle and
#: the fused Pallas slot kernels must agree bit-exactly on every finalize
#: leaf.
SERVING_PARITY = dict(scenario="paper_grid", policy="pi3_reg",
                      trace="bursty", rate_frac=0.95, n_jobs=4,
                      T=1024, chunk=256, eps_b=0.05)


def parity_section(emit) -> dict:
    """Replay SERVING_PARITY under both backends; gate bit-exact parity.

    Each backend gets a warm-up run (compilation) and a timed run; the
    parity diff is the max |xla - pallas| over every metric leaf of every
    job — the DESIGN.md §7 contract extended to the serving decision path
    (trace draw + admission gate + bp_slot + latency stamps)."""
    import numpy as np
    from repro.fleet.report import policy_bound_exact
    from repro.serving import ServingJob, run_serving

    c = SERVING_PARITY
    bound = policy_bound_exact(c["scenario"], c["policy"], c["eps_b"], 0)
    out: dict = {}
    metrics = {}
    for backend in ("xla", "pallas"):
        jobs = [ServingJob(scenario=c["scenario"], policy=c["policy"],
                           trace=c["trace"], lam=c["rate_frac"] * bound,
                           seed=s, eps_b=c["eps_b"], backend=backend)
                for s in range(c["n_jobs"])]
        run_serving(jobs, T=c["T"], chunk=c["chunk"])        # warm-up
        t0 = time.time()
        res = run_serving(jobs, T=c["T"], chunk=c["chunk"])
        wall = time.time() - t0
        metrics[backend] = res.metrics
        out[backend] = {"us_per_sim": wall * 1e6 / len(jobs),
                        "wall_s": wall, "n_sims": len(jobs), "T": res.T}
        emit(f"serving/parity/{backend},{out[backend]['us_per_sim']:.0f},"
             f"n_sims={len(jobs)} T={res.T}")
    diff = 0.0
    for mx, mp in zip(metrics["xla"], metrics["pallas"]):
        for k in mx:
            d = float(np.max(np.abs(np.asarray(mx[k]) - np.asarray(mp[k]))))
            diff = max(diff, d)
    out["parity_max_abs_diff"] = diff
    emit(f"serving/parity/diff,,max_abs_diff={diff}")
    assert diff == 0.0, (
        f"pallas serving path diverged from xla by {diff} (DESIGN.md §7/§9)")
    return out


def run(emit) -> dict:
    """Run the gated serving smoke + parity; returns the bench table."""
    from repro.serving import serving_report, write_stream_jsonl

    t0 = time.time()
    rep = serving_report(**SERVING_SMOKE, stream=True)
    wall = time.time() - t0
    result = rep.pop("result")
    table: dict = {"serving": rep}
    rep["stream_records"] = len(result.stream_records)
    table["us_per_sim"] = wall * 1e6 / max(rep["n_sims"], 1)
    table["wall_s"] = wall
    run.stream_records = result.stream_records   # for main()'s JSONL writer
    run.write_stream_jsonl = write_stream_jsonl

    check_gates(rep, emit)
    rep["parity"] = parity_section(emit)
    emit(f"serving/sweep,{table['us_per_sim']:.0f},"
         f"n_sims={rep['n_sims']} wall_s={wall:.1f} "
         f"stream_records={rep['stream_records']}")
    return table


def check_gates(rep: dict, emit) -> None:
    """Assert the nominal-load and overload gates on a `serving_report`
    of SERVING_SMOKE (rows "0.95" and SERVING_OVERLOAD_FRAC)."""
    bound = rep["bound_exact"]
    for frac, row in rep["rows"].items():
        emit(f"serving/smoke/{frac},,offered={row['offered']:.3f} "
             f"qps={row['delivered_qps']:.3f} "
             f"ratio={row['delivered_over_bound']:.3f} "
             f"shed={row['shed_frac']:.3f} p99={row['p99_sojourn']:.0f} "
             f"flips={row['gate_flips']:.0f} "
             f"open={row['gate_open_frac']:.3f}")

    nom = rep["rows"]["0.95"]
    assert nom["delivered_over_bound"] >= SERVING_MIN_RATIO, (
        f"0.95-load delivered/bound {nom['delivered_over_bound']:.3f} < "
        f"{SERVING_MIN_RATIO} (bound_exact={bound:.3f})")
    assert nom["shed_frac_max"] <= SERVING_MAX_SHED, (
        f"0.95-load shed_frac {nom['shed_frac_max']:.3f} > "
        f"{SERVING_MAX_SHED}: the gate shed under nominal load")
    assert nom["p99_sojourn_max"] <= SERVING_P99_MAX, (
        f"0.95-load p99 sojourn {nom['p99_sojourn_max']:.0f} slots > "
        f"{SERVING_P99_MAX}")

    over = rep["rows"][f"{SERVING_OVERLOAD_FRAC:g}"]
    assert over["shed_frac"] >= SERVING_OVERLOAD_MIN_SHED, (
        f"overload shed_frac {over['shed_frac']:.3f} < "
        f"{SERVING_OVERLOAD_MIN_SHED}: the gate failed to shed at "
        f"{SERVING_OVERLOAD_FRAC}x the bound")
    assert over["admitted_rate"] <= bound * SERVING_OVERLOAD_RATE_SLACK, (
        f"overload admitted_rate {over['admitted_rate']:.3f} > bound "
        f"{bound:.3f} x {SERVING_OVERLOAD_RATE_SLACK}")
    emit(f"serving/smoke/gates,,ratio>={SERVING_MIN_RATIO} "
         f"shed<={SERVING_MAX_SHED} p99<={SERVING_P99_MAX:.0f} "
         f"overload_shed>={SERVING_OVERLOAD_MIN_SHED}: pass")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None, help="write the JSON table here")
    ap.add_argument("--stream-out", default=None,
                    help="write per-chunk stream records as JSONL here")
    args = ap.parse_args()
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    table = run(print)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(table, f, indent=2)
        print(f"wrote {args.out}")
    if args.stream_out:
        n = run.write_stream_jsonl(run.stream_records, args.stream_out)
        print(f"wrote {args.stream_out} ({n} records)")


if __name__ == "__main__":
    main()
