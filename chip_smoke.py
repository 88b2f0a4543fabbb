#!/usr/bin/env python3
"""Chip smoke test: the network engine's main path on a TPU, end to end.

One process drives every phase through the entry points users call:

  fleet      `run_fleet` on the bench_fleet smoke jobs (88 sims, T=4000)
             with backend="xla" and with the compiled Pallas kernels
             (backend="pallas"): bit-identical metrics, useful rate under
             the exact LP bound, efficiency floors; then once more with
             `stream_path`, bit-equal to the run without it and a stream
             that passes `scripts/check_stream.py`;
  serving    `serving_report` of the bench_serving smoke (bursty trace at
             0.95x and 1.3x the bound) on both backends: bit-identical
             metrics and the bench's admission gates;
  reference  16 key-free pi3bar jobs run on the chip and on the host CPU
             in this same process: identical verdicts, useful rate within
             1% and mean backlog within 5%;
  atlas      `sweep_lambda_max` at the committed atlas width
             (`benchmarks/bench_atlas.py` ATLAS_SWEEP: 504 cells, 1,512
             lanes, T=4096), asserting that bench's gates, and each
             family's λ_max/bound median inside the committed
             BENCH_atlas.json family's q10-q90 band, +- one grid step.

`--chips 4` runs only the capacity atlas at the bench's `ci` preset over
all four chips with streaming on, and the same sweep on one chip: every
row must be identical and the stream must pass `check_stream.py`.

Each phase prints one JSON line with its wall time, the seconds spent in
backend compiles, and its persistent-cache hits.  The last line of
standard output is `{"ok": true, "device": {...}}`.  With no TPU, or if
any phase or gate fails, the script exits non-zero and prints no such
line.  The compile cache is `JAX_COMPILATION_CACHE_DIR` where set, else
`.jax_cache/` in the checkout.  Streams and a summary go to `--out`.

Usage:  python chip_smoke.py [--chips 4] [--out DIR]
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

# The reference phase reruns jobs on the host CPU: keep that platform
# available next to the TPU when the platform list is pinned.
_platforms = os.environ.get("JAX_PLATFORMS", "")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.runtime.compile_cache import use_compile_cache  # noqa: E402

#: Reference jobs: pi3bar draws no per-slot randomness of its own, so the
#: chip and the CPU see identical arrivals and can differ only by float
#: rounding.  T covers 32 verdict windows of 512 slots.
REFERENCE = dict(scenarios=("paper_grid", "expander"), rate_fracs=(0.5, 0.8),
                 seeds=(0, 1, 2, 3), T=16384, chunk=512)
REF_RATE_RTOL = 0.01         # useful rate, chip vs CPU, relative
REF_BACKLOG_RTOL = 0.05      # mean backlog, chip vs CPU, relative

#: Best useful rate / exact bound on the paper grid, every fleet policy.
PAPER_GRID_EFFICIENCY = 0.9

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class Failed(RuntimeError):
    """A phase gate did not hold."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise Failed(msg)


def emit(line: str) -> None:
    print(line, flush=True)


class CompileClock:
    """Seconds spent in backend compiles (persistent-cache reads
    included) and persistent-cache hits, read as deltas per phase."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == BACKEND_COMPILE:
            self.seconds += secs

    def _event(self, event, **_):
        if event == CACHE_HIT:
            self.hits += 1


def metrics_diff(a, b) -> float:
    """Largest |a - b| over every metric of every job (inf on a key or
    shape mismatch)."""
    d = 0.0
    for ma, mb in zip(a, b, strict=True):
        if set(ma) != set(mb):
            return float("inf")
        for k in ma:
            x, y = np.asarray(ma[k]), np.asarray(mb[k])
            if x.shape != y.shape:
                return float("inf")
            d = max(d, float(np.max(np.abs(x - y), initial=0.0)))
    return d


def check_stream(path: pathlib.Path) -> None:
    """`scripts/check_stream.py` on one file, in this process."""
    spec = importlib.util.spec_from_file_location(
        "check_stream", ROOT / "scripts" / "check_stream.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    check(mod.main(["check_stream.py", str(path)]) == 0,
          f"{path.name} fails scripts/check_stream.py")


def atlas_cells(preset: str):
    """Cells and sweep arguments of a bench_atlas preset."""
    from benchmarks.bench_atlas import ATLAS_PRESETS
    from repro.fleet import registry_cells

    c = dict(ATLAS_PRESETS[preset])
    cells = registry_cells(c.pop("families"), c.pop("topo_seeds"),
                           policy=c.pop("policy"), eps_b=c.pop("eps_b"))
    return cells, c


def phase_atlas(out: pathlib.Path) -> dict:
    from benchmarks import bench_atlas

    table = bench_atlas.run(emit, preset="full")["atlas"]   # asserts gates
    committed = json.loads((ROOT / "BENCH_atlas.json").read_text())
    committed = committed["atlas"]["families"]
    step = bench_atlas.ATLAS_SWEEP["rel_tol"] + 1e-9
    diffs = {}
    for fam, row in table["families"].items():
        # The committed table came from another random stream: its median
        # is a sample too.  The chip's median must fall inside the
        # committed family's q10-q90 band, give or take one grid step.
        med, ref = row["ratio_median"], committed[fam]
        diffs[fam] = med - ref["ratio_median"]
        check(ref["band"]["q10"] - step <= med <= ref["band"]["q90"] + step,
              f"atlas {fam}: median ratio {med:.3f} outside the committed "
              f"band [{ref['band']['q10']:.3f}, {ref['band']['q90']:.3f}] "
              f"+- {step:.3f}")
    (out / "atlas.json").write_text(json.dumps(table, indent=1))
    return {"cells": table["n_cells"], "lanes": table["n_lanes"],
            "programs": table["n_programs"],
            "step_compiles": table["n_step_compiles"],
            "launches": table["n_launches"],
            "requeues": table["n_requeues"],
            "sweep_wall_s": table["wall_s"],
            "medians": {f: r["ratio_median"]
                        for f, r in table["families"].items()},
            "median_diff_max": max(abs(d) for d in diffs.values())}


def phase_fleet(out: pathlib.Path) -> dict:
    from benchmarks.bench_fleet import EFFICIENCY_GATES, LP_TOL, PRESETS
    from repro.fleet import policy_bound_exact, run_fleet, sweep_jobs

    spec = PRESETS["smoke"]
    jobs = sweep_jobs(spec["scenario_policies"], spec["rate_fracs"],
                      spec["seeds"], eps_b=spec["eps_b"])
    kw = dict(T=spec["T"], chunk=spec["chunk"])
    walls = {}
    t0 = time.perf_counter()
    xla = run_fleet(jobs, memory_stats=True, **kw)
    walls["xla"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    pallas = run_fleet([dataclasses.replace(j, backend="pallas")
                        for j in jobs], **kw)
    walls["pallas"] = time.perf_counter() - t0
    parity = metrics_diff(xla.metrics, pallas.metrics)
    check(parity == 0.0, f"fleet: pallas metrics differ from xla by {parity}")

    path = out / "fleet_stream.jsonl"
    t0 = time.perf_counter()
    streamed = run_fleet(jobs, stream_path=str(path), **kw)
    walls["xla_streamed"] = time.perf_counter() - t0
    observer = metrics_diff(xla.metrics, streamed.metrics)
    check(observer == 0.0, f"fleet: streaming changed metrics by {observer}")
    check_stream(path)

    best: dict = {}
    for job, m in zip(jobs, xla.metrics):
        bound = policy_bound_exact(job.scenario, job.policy, job.eps_b)
        check(m["useful_rate"] <= bound * LP_TOL,
              f"fleet {job.scenario}/{job.policy} lam={job.lam:.3f}: useful "
              f"rate {m['useful_rate']:.4f} above the LP bound {bound:.4f}")
        key = (job.scenario, job.policy)
        best[key] = max(best.get(key, 0.0), m["useful_rate"] / bound)
    floors = {k: PAPER_GRID_EFFICIENCY for k in best if k[0] == "paper_grid"}
    floors.update({k: v for k, v in EFFICIENCY_GATES.items() if k in best})
    for key, floor in floors.items():
        check(best[key] >= floor,
              f"fleet {key}: efficiency {best[key]:.4f} < {floor}")
    return {"sims": len(jobs), "programs": xla.n_programs, "T": xla.T,
            "wall_s_by_run": walls, "pallas_parity_diff": parity,
            "stream_records": len(streamed.stream_records),
            "memory": xla.memory_stats,
            "efficiency": {f"{s}/{p}": e for (s, p), e in best.items()}}


def phase_serving(out: pathlib.Path) -> dict:
    from benchmarks.bench_serving import SERVING_SMOKE, check_gates
    from repro.serving import serving_report

    reps, walls = {}, {}
    for backend in ("xla", "pallas"):
        t0 = time.perf_counter()
        reps[backend] = serving_report(**SERVING_SMOKE, backend=backend)
        walls[backend] = time.perf_counter() - t0
    parity = metrics_diff(reps["xla"]["result"].metrics,
                          reps["pallas"]["result"].metrics)
    check(parity == 0.0,
          f"serving: pallas metrics differ from xla by {parity}")
    check_gates(reps["xla"], emit)                  # asserts the gates
    return {"sims": reps["xla"]["n_sims"], "T": reps["xla"]["T"],
            "wall_s_by_run": walls, "pallas_parity_diff": parity,
            "rows": {f: {k: r[k] for k in ("delivered_over_bound",
                                           "shed_frac", "p99_sojourn_max")}
                     for f, r in reps["xla"]["rows"].items()}}


def phase_reference(out: pathlib.Path, cfg: dict = REFERENCE) -> dict:
    from repro.fleet import FleetJob, policy_bound_exact, run_fleet

    jobs = [FleetJob(scenario=s, policy="pi3bar",
                     lam=f * policy_bound_exact(s, "pi3bar", 0.0), seed=seed)
            for s in cfg["scenarios"] for f in cfg["rate_fracs"]
            for seed in cfg["seeds"]]
    kw = dict(T=cfg["T"], chunk=cfg["chunk"])
    walls = {}
    t0 = time.perf_counter()
    chip = run_fleet(jobs, **kw)
    walls["chip"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    host = run_fleet(jobs, devices=jax.devices("cpu"), **kw)
    walls["cpu"] = time.perf_counter() - t0

    check(chip.verdicts() == host.verdicts(),
          f"reference: verdicts differ, chip {chip.verdicts()} vs cpu "
          f"{host.verdicts()}")

    def rel(name):
        a, b = chip.column(name), host.column(name)
        return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-12)))

    rate, backlog = rel("useful_rate"), rel("mean_queue")
    check(rate <= REF_RATE_RTOL,
          f"reference: useful rate differs by {rate:.3e} relative")
    check(backlog <= REF_BACKLOG_RTOL,
          f"reference: mean backlog differs by {backlog:.3e} relative")
    return {"jobs": len(jobs), "T": chip.T, "wall_s_by_run": walls,
            "verdicts": sorted(set(chip.verdicts())),
            "useful_rate_rel_diff_max": rate,
            "mean_queue_rel_diff_max": backlog}


def phase_mesh(out: pathlib.Path) -> dict:
    from repro.fleet import sweep_lambda_max

    cells, kw = atlas_cells("ci")
    path = out / "atlas_mesh_stream.jsonl"
    walls = {}
    t0 = time.perf_counter()
    wide = sweep_lambda_max(cells, stream_path=str(path), **kw)
    walls["all_chips"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    one = sweep_lambda_max(cells, devices=jax.devices()[:1], **kw)
    walls["one_chip"] = time.perf_counter() - t0
    for a, b in zip(wide.rows, one.rows, strict=True):
        check(a == b, f"atlas row {a.scenario}/ts{a.topo_seed} differs "
              f"across meshes: lam_max {a.lam_max} vs {b.lam_max}, "
              f"bracket ({a.lo}, {a.hi}) vs ({b.lo}, {b.hi})")
    check_stream(path)
    return {"devices": len(jax.devices()), "cells": wide.n_cells,
            "lanes": wide.n_lanes, "launches": wide.n_launches,
            "wall_s_by_run": walls,
            "stream_records": len(wide.stream_records),
            "rows_identical": True}


# The atlas runs last: the shorter phases fail sooner.  Its one-compile-
# per-program gate is unaffected, since no other phase builds its runner.
ONE_CHIP = (("fleet", phase_fleet), ("serving", phase_serving),
            ("reference", phase_reference), ("atlas", phase_atlas))
FOUR_CHIPS = (("mesh", phase_mesh),)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: every phase on one chip; 4: only the atlas "
                    "over a four-chip mesh against one chip")
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" / "chip_smoke"),
                    help="directory for streams and the summary")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: src/repro is not beside this script; run it "
              "from a checkout of the repository", file=sys.stderr)
        return 1
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (default devices are "
              f"{devices[0].platform}); nothing run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs that many TPU chips, "
              f"JAX finds {len(devices)}", file=sys.stderr)
        return 1
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    cache = use_compile_cache()
    emit(json.dumps({"device": device, "compile_cache": cache}))

    clock = CompileClock()
    report = []
    for name, phase in (ONE_CHIP if args.chips == 1 else FOUR_CHIPS):
        c0, h0, t0 = clock.seconds, clock.hits, time.perf_counter()
        result = phase(out)
        rec = {"phase": name, "ok": True,
               "wall_s": time.perf_counter() - t0,
               "compile_s": clock.seconds - c0,
               "cache_hits": clock.hits - h0, **result}
        report.append(rec)
        emit(json.dumps(rec))
    summary = {"ok": True, "device": device,
               "compile_s": clock.seconds, "cache_hits": clock.hits,
               "phases": report}
    (out / "summary.json").write_text(json.dumps(summary, indent=1))
    emit(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
