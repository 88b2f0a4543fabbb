"""Benchmark harness — one module per paper table/figure plus framework
benchmarks.  Prints ``name,us_per_call,derived`` CSV lines.

  fig5b           paper Fig. 5(b): queue length vs rate, pi3 vs pi3bar
  fig5c           paper Fig. 5(c): running averages at C=2, lam=6
  table_capacity  Theorem 1/4 LP vs simulated saturation + pairing models
  bench_router    backpressure MoE router vs aux-loss vs plain
  bench_serving   backpressure serving scheduler vs RR/JSQ
  bench_kernels   Pallas kernels vs jnp references

Usage: PYTHONPATH=src python -m benchmarks.run [suite ...]
"""
from __future__ import annotations

import sys
import time
import traceback


def main() -> None:
    from . import fig5b, fig5c, table_capacity, bench_router
    suites = {
        "fig5b": fig5b.run,
        "fig5c": fig5c.run,
        "table_capacity": table_capacity.run,
        "bench_router": bench_router.run,
    }
    try:
        from . import bench_serving
        suites["bench_serving"] = bench_serving.run
    except ImportError:
        pass
    try:
        from . import bench_kernels
        suites["bench_kernels"] = bench_kernels.run
    except ImportError:
        pass
    try:
        from . import bench_fleet
        suites["bench_fleet"] = bench_fleet.run
    except ImportError:
        pass

    chosen = sys.argv[1:] or list(suites)
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    failures = []
    print("name,us_per_call,derived")
    for name in chosen:
        t0 = time.time()
        try:
            suites[name](print)
            print(f"# suite {name} ok in {time.time()-t0:.1f}s")
        except Exception:
            failures.append(name)
            traceback.print_exc()
            print(f"# suite {name} FAILED")
    if failures:
        raise SystemExit(f"failed suites: {failures}")


if __name__ == "__main__":
    main()
