#!/usr/bin/env python3
"""Measure a configuration's λ_max once, on the chip, for a traffic mix
whose rates are fractions of it (`"rate_base": {"lambda_max": ...}`).

    python3 perfbench/measure_lambda_max.py --config fig5_grid_wireless

Runs `sweep_lambda_max` for pi3 over `--replicas` replica cells, three
arrival seeds each, at the atlas's verdict discipline (T=4096, chunk=512,
one re-queue) on a finer grid (rel_tol 0.025 of the LP bound), and prints
the rows and their median as one JSON line.
"""
import argparse
import json
import pathlib
import statistics
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent / "src"), str(HERE)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--replicas", type=int, default=32)
    ap.add_argument("--rel-tol", type=float, default=0.025)
    ap.add_argument("--bracket", default="0.1,0.5")
    args = ap.parse_args(argv)

    from harness import device, spec
    from repro.fleet import AtlasJob, sweep_lambda_max

    device.configure(spec.ROOT)
    devs = device.chips(1)
    scenario = spec.load_module(spec.BENCH_DIR / "configs"
                                / f"{args.config}.py").scenario()
    cells = [AtlasJob(scenario=scenario, policy="pi3", topo_seed=r,
                      eps_b=0.05) for r in range(args.replicas)]
    t0 = time.perf_counter()
    res = sweep_lambda_max(cells, seeds=(0, 1, 2), T=4096, chunk=512,
                           rel_tol=args.rel_tol,
                           bracket=tuple(float(x) for x in
                                         args.bracket.split(",")),
                           max_calls=24, max_requeues=1, devices=devs)
    wall = time.perf_counter() - t0
    lam = [r.lam_max for r in res.rows]
    print(json.dumps({
        "config": args.config, "policy": "pi3", "eps_b": 0.05,
        "replicas": args.replicas, "seeds": [0, 1, 2], "T": 4096,
        "chunk": 512, "rel_tol": args.rel_tol, "bracket": args.bracket,
        "lp_bound": res.rows[0].bound_exact,
        "lambda_max_median": statistics.median(lam),
        "lambda_max_min": min(lam), "lambda_max_max": max(lam),
        "rows": [[r.topo_seed, r.lam_max, r.hi, r.n_requeues, r.undecided]
                 for r in res.rows],
        "n_launches": res.n_launches, "wall_s": wall,
        "device": device.describe(devs)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
