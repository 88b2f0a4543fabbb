#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip.

    python3 perfbench/run.py --workload fig5_grid.atlas --seed 7 \
        --seconds 45 --trace 0

Cells, configurations, traffic mixes and metrics are named in
`BENCHMARK.json` and found as files under `perfbench/` (see
`harness/spec.py`).  The run sets up (compile cache, LP bounds, the cell's
programs), measures for `--seconds`, checks the answers against the plain
reference, and prints one JSON line last on standard output: `correct`,
`attempted`, `failed`, `metrics` (end-to-end with `--trace 0`, per-layer
with `--trace 1`), `device`, the trace's `breakdown`, and `checks`, each
compared number beside its limit.  Without a TPU, or with fewer chips than
the cell asks for, it exits 2 and prints no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent / "src"), str(HERE)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import runner
    from harness.device import NoAccelerator

    try:
        result = runner.run(args.workload, args.seed, args.seconds,
                            bool(args.trace), T0)
    except NoAccelerator as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
