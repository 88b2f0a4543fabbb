"""One run of one cell: set-up, the measured window, the check, the line."""
from __future__ import annotations

import dataclasses
import sys
import time

from . import check, device, reference, spec
from .trace import Reduced, Tracer


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader may read."""

    trace: Reduced | None
    setup: dict
    traffic: dict


def run(workload: "str | spec.Cell", seed: int, seconds: float,
        trace: bool, t0: float, require_chip: bool = True, log=sys.stderr,
        overrides: dict | None = None) -> dict:
    """One run of a cell, named or given; `overrides` replaces traffic
    parameters (tests run small cells on the CPU with
    `require_chip=False`)."""
    cell = (workload if isinstance(workload, spec.Cell)
            else spec.load_cell(workload))
    cell.traffic.update(overrides or {})
    device.configure(spec.ROOT)
    if require_chip:
        devs = device.chips(cell.chips)
    else:
        import jax

        devs = jax.devices()[:cell.chips]
    clock = device.CompileClock()

    scenario = cell.scenario()
    drv = cell.entry()(cell.traffic, scenario, devs, seed)
    prep = drv.setup()
    setup_s = time.perf_counter() - t0
    compile_s, n0, h0 = clock.snapshot()

    tracer = Tracer(trace, cell.traffic["trace_after_s"],
                    cell.traffic["trace_s"])
    tracer.start()
    try:
        window = drv.window(seconds)
    finally:
        tracer.stop()
    _, n1, h1 = clock.snapshot()
    print(f"setup: {setup_s:.3f} s; window: {window['window_s']:.3f} s, "
          f"compiles {n1 - n0}, cache hits {h1 - h0}, "
          + ", ".join(f"{k} {v}" for k, v in window.items()
                      if k.startswith(("n_", "launch_s"))),
          file=log, flush=True)
    peak = device.memory_peak(devs) if require_chip else 0
    t1 = time.perf_counter()
    reduced = tracer.reduce(len(devs))
    if reduced is not None:
        print("chunk-step executions (start ms, length ms, whole): "
              + ", ".join(f"({a:.3f}, {b:.3f}, {int(w)})" for a, b, w in
                          reduced.executions("chunk_step")),
              file=log, flush=True)
    t2 = time.perf_counter()
    from repro.fleet import get_scenario

    judged = check.judge(
        check.numbers(drv, reference.Deployment.from_config(cell.config),
                      get_scenario(scenario), window["completed"], seed),
        cell.limits)
    window.pop("completed")
    print(f"trace stop: {tracer.stop_s:.3f} s; "
          f"trace reduction: {t2 - t1:.3f} s; reference check: "
          f"{time.perf_counter() - t2:.3f} s", file=log, flush=True)

    dev = dict(device.describe(devs), memory_peak_bytes=peak)
    if trace:
        ctx = Context(reduced, {"compile_s": compile_s, **prep},
                      cell.traffic)
        metrics = {}
        for m in cell.per_layer:
            v = cell.metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        measured = dict(window["metrics"], setup_s=setup_s)
        metrics = {m["name"]: {"value": float(measured[m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}
    result = {"correct": check.passed(judged),
              "attempted": int(window.get("n_answers",
                                          window.get("n_jobs", 0))),
              "failed": 0, "metrics": metrics, "device": dev}
    if trace and reduced is not None:
        dev["busy_s"] = reduced.busy_s()
        dev["window_s"] = reduced.window_s
        result["breakdown"] = {"device_ops": reduced.top_modules(),
                               "idle_gaps": reduced.idle_gaps()}
    result["checks"] = judged
    for k, j in judged.items():
        print(f"check {k}: {j['value']!r} (limit {j['limit']!r})",
              file=log, flush=True)
    return result
