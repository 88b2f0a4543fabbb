#!/usr/bin/env python3
"""Readings that a cell's correctness limits are set from, on the chip.

    python3 perfbench/calibrate.py --workload fig5_grid.sweep \
        --seeds 1,2,3 --control-seeds 4,5,6

For each seed of `--seeds`, one job of the cell (a `run_fleet` sweep or a
`sweep_lambda_max` sweep, at the cell's own size) runs through the timed
path and is compared with the plain reference exactly as a run compares
it: the lower readings.  For each of `--control-seeds`, the reference
computed in bfloat16 takes the program's place on the same sample: the
upper readings.  One JSON line per reading; all in one process, so set-up
is paid once.
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
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)

    import jax.numpy as jnp

    from harness import check, device, reference, spec

    cell = spec.load_cell(args.workload)
    device.configure(spec.ROOT)
    devs = device.chips(cell.chips)
    dep = reference.Deployment.from_config(cell.config)
    drv = cell.entry()(cell.traffic, cell.scenario(), devs, 0)
    drv.setup()

    for kind, seeds in (("program", args.seeds),
                        ("control", args.control_seeds)):
        for s in [int(x) for x in seeds.split(",") if x]:
            t1 = time.perf_counter()
            sample = drv.sample(drv.one_job(s), s)
            t2 = time.perf_counter()
            if kind == "program":
                numbers = drv.compare(drv.program(sample), drv.reference(
                    dep, sample, jnp.float32), sample)
            else:
                numbers = check.control(drv, dep, sample)
            print(json.dumps({"workload": args.workload, "kind": kind,
                              "seed": s, "numbers": numbers,
                              "job_s": t2 - t1,
                              "check_s": time.perf_counter() - t2}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
