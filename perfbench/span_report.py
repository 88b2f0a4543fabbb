#!/usr/bin/env python3
"""Where one cell's time goes, read from the program's own spans and
scopes (`repro.obs.spans`).

    python3 perfbench/span_report.py --workload fig5_grid.atlas \
        --seed 7 --seconds 45 --out span_report/atlas.json

After the cell's set-up, three windows run back to back, as `run.py`
runs them:

  plain     recording off, no profiler: the end-to-end metrics;
  spans     `repro.obs.spans` recording, no profiler: the recorder's cost
            (against `plain`) and its spans over every launch of a window;
  traced    recording, under a `jax.profiler` trace placed as the cell's
            `--trace 1` run places it: the chunk step's device time by
            named scope (`SLOT_SCOPES`), and the device-idle time by the
            program span over it.

It prints one JSON line (`--out` also keeps it, with a sample of the
trace's raw op events).  Nothing here checks answers: `run.py` does.
Without a TPU it exits 2.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from typing import Dict, Iterable, List, Tuple  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent / "src"), str(HERE)]

STEP = "chunk_step"
DECIDE = ("balance", "route", "compute")
SCHEDULER = ("atlas.harvest", "atlas.rewrite", "atlas.record")
JOB_SETUP = ("fleet.build", "fleet.group_setup")
PROGRAM_SPANS = ("atlas.", "fleet.")


# --- spans ------------------------------------------------------------------

def span_table(spans) -> Dict[str, Dict[str, float]]:
    """Per span name: count, total and mean ms, and the sum of its `n`."""
    out: Dict[str, Dict[str, float]] = {}
    for s in spans:
        row = out.setdefault(s.name, {"count": 0, "total_ms": 0.0, "n": 0})
        row["count"] += 1
        row["total_ms"] += (s.end_ns - s.start_ns) * 1e-6
        row["n"] += s.n or 0
    for row in out.values():
        row["mean_ms"] = row["total_ms"] / row["count"]
    return out


def _ms(spans, names: Iterable[str]) -> float:
    names = set(names)
    return sum(s.end_ns - s.start_ns for s in spans if s.name in names) * 1e-6


def scheduler_ms(spans) -> float | None:
    """Mean host ms per atlas launch of harvest, rewrite and record."""
    n = sum(s.name == "atlas.launch" for s in spans)
    return _ms(spans, SCHEDULER) / n if n else None


def active_lane_share(spans) -> float | None:
    """Mean over atlas launches of the lanes of active cells over the
    launch unit's padded lanes (the last `atlas.unit_setup` before it in
    the same job)."""
    units: Dict[int, List[Tuple[int, int]]] = {}
    for s in spans:
        if s.name == "atlas.unit_setup":
            units.setdefault(s.job, []).append((s.start_ns, s.n))
    shares = []
    for s in spans:
        if s.name == "atlas.launch":
            before = [u for u in units.get(s.job, []) if u[0] <= s.start_ns]
            if before:
                shares.append(s.n / max(before)[1])
    return sum(shares) / len(shares) if shares else None


def per_job_ms(spans, names: Iterable[str]) -> float | None:
    """Mean over `fleet.job`s of the ms its spans named `names` took."""
    jobs = {s.job for s in spans if s.name == "fleet.job"}
    if not jobs:
        return None
    return sum(_ms([s for s in spans if s.job == j], names)
               for j in jobs) / len(jobs)


def span_metrics(spans) -> Dict[str, float | None]:
    """The quantities the spans give over a whole window."""
    return {"scheduler_ms": scheduler_ms(spans),
            "active_lane_share": active_lane_share(spans),
            "job_setup_ms": per_job_ms(spans, JOB_SETUP),
            "finalize_ms": per_job_ms(spans, ("fleet.finalize",))}


# --- scopes -----------------------------------------------------------------

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def hlo_op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name → op_name path, from a compiled module's text.  A
    fusion carries its root's path; one the compiler left without (a
    multi-output or custom fusion, such as a scatter) takes the path of
    the last instruction with one in the computation it calls."""
    out: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    comps: Dict[str, List[str]] = {}
    body: List[str] = []
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            m = _COMPUTATION.match(line)
            body = comps.setdefault(m.group(1), []) if m else []
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(1)
        body.append(name)
        p, c = _OP_NAME.search(line), _CALLS.search(line)
        if p:
            out.setdefault(name, p.group(1))
        elif c:
            calls[name] = c.group(1)

    def of(comp: str, depth: int = 0) -> str | None:
        for n in reversed(comps.get(comp, [])):
            if n in out:
                return out[n]
            if n in calls and depth < 8:
                p = of(calls[n], depth + 1)
                if p:
                    return p
        return None

    for name, comp in calls.items():
        p = of(comp)
        if p:
            out.setdefault(name, p)
    return out


_EVENT_NAME = re.compile(r"^(?:ROOT\s+)?%?([\w.\-]+)\s*=")


def op_path(name: str, names: Dict[str, str]) -> str | None:
    """An op event's scope path.  A v5e trace names an op event by its
    HLO instruction (``%fusion.3 = f32[..] fusion(..), ...``) and gives
    it no op_name stat, so the instruction's name is looked up in
    `names`, the compiled program's own metadata."""
    m = _EVENT_NAME.match(name)
    return names.get(m.group(1) if m else name)


def scopes_of(path: str | None, scopes: Iterable[str]) -> set:
    """The scopes that are whole segments of `path`."""
    if not path:
        return set()
    segs = set(path.split("/"))
    return {s for s in scopes if s in segs}


def _union(iv: List[Tuple[int, int]]) -> int:
    tot, end = 0, None
    for s, e in sorted(iv):
        if end is None or s > end:
            tot += e - s
            end = e
        elif e > end:
            tot += e - end
            end = e
    return tot


def scope_split(ops: List[Tuple[int, int, set]],
                runs: List[Tuple[int, int]], chunk: int,
                scopes: Iterable[str]) -> Dict[str, float]:
    """Per scope, the union of its ops' device time inside each whole
    chunk-step execution, as the median over executions, in us a slot;
    ``decide`` is balance, route and compute together, ``unscoped`` the
    busy time under no scope, ``step`` the execution itself, and
    ``scoped_share`` the scoped part of the step (median share)."""
    scopes = tuple(scopes)
    per: Dict[str, List[float]] = {}
    for lo, hi in runs:
        inside = [(s, e, sc) for s, e, sc in ops if s >= lo and e <= hi]
        row = {sc: _union([(s, e) for s, e, k in inside if sc in k])
               for sc in scopes}
        row["decide"] = _union([(s, e) for s, e, k in inside
                                if k & set(DECIDE)])
        scoped = _union([(s, e) for s, e, k in inside if k])
        row["unscoped"] = _union([(s, e) for s, e, _ in inside]) - scoped
        row["step"] = hi - lo
        for k, v in row.items():
            per.setdefault(k, []).append(v * 1e-3 / chunk)
        per.setdefault("scoped_share", []).append(scoped / (hi - lo))
    return {k: statistics.median(v) for k, v in per.items()}


_OPCODE = re.compile(r"=\s*\S+\s+([\w\-]+)\(")


def unscoped_kind(name: str, path: str | None) -> str:
    """What an unscoped op is: its opcode where it has no op_name (the
    compiler's own copies), else the first two segments of its path
    after the slot's call (``vmap(jit(_threefry_fold_in))``, ...)."""
    if path is None:
        m = _OPCODE.search(name)
        return "no op_name: " + (m.group(1) if m else name[:40])
    if path.endswith("/while"):
        return "a while loop (spans its body)"
    segs = path.split("/")
    if "closed_call" in segs:
        segs = segs[segs.index("closed_call") + 1:]
    return "/".join(segs[:2]) or "(the slot's call)"


def unscoped_us(ops: List[Tuple[int, int, str, str | None]],
                runs: List[Tuple[int, int]], chunk: int, n: int = 12
                ) -> Dict[str, float]:
    """Summed device time of unscoped ops inside whole executions, by
    `unscoped_kind`, in us a slot (the mean over executions)."""
    out: Dict[str, float] = {}
    for lo, hi in runs:
        for s, e, name, path in ops:
            if s >= lo and e <= hi:
                k = unscoped_kind(name, path)
                out[k] = out.get(k, 0.0) + (e - s) * 1e-3 / chunk / len(runs)
    return dict(sorted(out.items(), key=lambda kv: -kv[1])[:n])


# --- idle gaps --------------------------------------------------------------

def idle_gaps(busy: List[Tuple[int, int]], lo: int, hi: int
              ) -> List[Tuple[int, int]]:
    """Device-idle intervals of [lo, hi] given busy intervals."""
    gaps, t = [], lo
    for s, e in sorted(busy):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


SHORT_GAP_NS = 10_000


def idle_by(gaps: List[Tuple[int, int]], label) -> Dict[str, float]:
    """Idle seconds per `label(middle)` of each gap; gaps under
    `SHORT_GAP_NS` (inside a step, between its ops) are summed apart."""
    out: Dict[str, float] = {}
    for s, e in gaps:
        name = (label((s + e) // 2) if e - s >= SHORT_GAP_NS
                else "(gaps under 10 us)")
        out[name] = out.get(name, 0.0) + (e - s) * 1e-9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def program_span_at(host: List[Tuple[str, int, int]]):
    """`t` → the innermost program span (``atlas.*``/``fleet.*``) around
    it in the trace's host events, or ``-``."""
    ours = [h for h in host if h[0].startswith(PROGRAM_SPANS)]

    def at(t: int) -> str:
        around = [h for h in ours if h[1] <= t <= h[2]]
        return min(around, key=lambda h: h[2] - h[1])[0] if around else "-"
    return at


# --- the chip run -----------------------------------------------------------

def read_ops(path: str, device: str, n_sample: int = 60):
    """(start, end, name) of every op event of `device`'s "XLA Ops" line;
    the plane and line names of the file; and a sample of op events with
    all their stats, to read by hand."""
    from jax.profiler import ProfileData

    from harness.trace import OPS_LINE

    ops, layout, sample = [], {}, []
    for plane in ProfileData.from_file(path).planes:
        layout[plane.name] = [line.name for line in plane.lines]
        if plane.name != device:
            continue
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for i, ev in enumerate(line.events):
                s = int(ev.start_ns)
                ops.append((s, s + int(ev.duration_ns), ev.name))
                if i < n_sample // 2 or i % 20011 == 0:
                    sample.append([ev.name, int(ev.duration_ns),
                                   {k: str(v) for k, v in ev.stats}])
    return ops, layout, sample


def step_hlo(traffic: dict, scenario: str, devices, first_result) -> str:
    """Compiled text of the chunk step the trace sees first: the cell's
    first policy group at its first launch unit's shapes, lowered with
    arguments made as the engines make them (so it is the same program)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from repro.fleet import (FleetJob, VerdictConfig, get_scenario,
                             make_group_launch, make_stream_runner,
                             pad_problem, resolve_verdict)

    policy = traffic.get("policy") or traffic["policies"][0]
    cfg = FleetJob(scenario=scenario, policy=policy,
                   eps_b=float(traffic["eps_b"])).policy_config()
    runner = make_stream_runner(
        cfg, int(traffic["T"]), chunk=int(traffic["chunk"]),
        verdict=resolve_verdict(VerdictConfig(**traffic["verdict"]),
                                bool(traffic["early_stop"])))
    mesh = Mesh(np.array(devices), ("fleet",))
    init_fn, step_fn, _ = make_group_launch(runner, mesh)
    if "policies" in traffic:
        lanes = int(traffic["replicas"]) * len(traffic["seeds"])
        dims = first_result.bucket_dims[0]
    else:
        lanes = len(first_result.jobs)
        dims = first_result.dims
    lanes = -(-lanes // len(devices)) * len(devices)
    prob = pad_problem(get_scenario(scenario).build(0), dims)
    pp = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *[prob] * lanes)
    f32 = jnp.zeros(lanes, jnp.float32)
    i32 = jnp.zeros(lanes, jnp.int32)
    keys = jax.vmap(jax.random.PRNGKey)(i32)
    return step_fn.lower(pp, f32, f32, i32, i32, keys,
                         init_fn(pp)).compile().as_text()


def traced_report(path: str, n_devices: int, traffic: dict, hlo: str
                  ) -> dict:
    from repro.obs.spans import SLOT_SCOPES

    from harness.trace import reduce

    red = reduce(path, n_devices)
    dev = red.devices[0]
    ops, layout, sample = read_ops(path, dev.name)
    names = hlo_op_names(hlo)
    lo, hi = red.window
    tagged, bare = [], []
    found = 0
    for s, e, name in ops:
        p = op_path(name, names)
        found += p is not None
        tagged.append((s, e, scopes_of(p, SLOT_SCOPES)))
        if not tagged[-1][2]:
            bare.append((s, e, name, p))
    runs = [(s, e) for _, s, e in red.module_runs(STEP)[0]]
    gaps = idle_gaps([(s, e) for s, e, *_ in ops], lo, hi)
    return {
        "window_s": red.window_s, "busy_s": red.busy_s(),
        "idle_s": sum(e - s for s, e in gaps) * 1e-9,
        "planes": layout, "op_events": len(ops),
        "ops_with_path": found,
        "hlo_names": len(names), "op_sample": sample,
        "whole_step_runs": len(runs),
        "scope_us_per_slot": (scope_split(tagged, runs, int(traffic["chunk"]),
                                          SLOT_SCOPES) if runs else None),
        "unscoped_us_per_slot": unscoped_us(bare, runs,
                                            int(traffic["chunk"])),
        "idle_by_span_s": idle_by(gaps, program_span_at(red.host)),
        "idle_by_label_s": idle_by(gaps, red.label),
        "longest_gaps": [[red.label((s + e) // 2), (e - s) * 1e-9]
                         for s, e in sorted(gaps, key=lambda g: g[0] - g[1])
                         [:10]],
        "host_spans_in_trace": sum(h[0].startswith(PROGRAM_SPANS)
                                   for h in red.host),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    from harness import device, spec
    from harness.device import NoAccelerator
    from harness.trace import Tracer
    from repro.obs import spans

    cell = spec.load_cell(args.workload)
    device.configure(spec.ROOT)
    try:
        devs = device.chips(cell.chips)
    except NoAccelerator as e:
        print(f"span_report: {e}", file=sys.stderr)
        return 2
    scenario = cell.scenario()
    drv = cell.entry()(cell.traffic, scenario, devs, args.seed)
    drv.setup()
    out = {"workload": cell.name, "seed": args.seed,
           "setup_s": time.perf_counter() - T0,
           "device": device.describe(devs)}

    plain = drv.window(args.seconds)
    out["plain"] = plain["metrics"]

    spans.enable()
    rec = drv.window(args.seconds)
    recorded = spans.collect()
    out["spans"] = {"metrics": rec["metrics"],
                    "cost": {k: rec["metrics"][k] / v - 1.0
                             for k, v in plain["metrics"].items()},
                    "table": span_table(recorded),
                    **span_metrics(recorded)}

    tracer = Tracer(True, cell.traffic["trace_after_s"],
                    cell.traffic["trace_s"])
    spans.enable()
    tracer.start()
    try:
        traced = drv.window(args.seconds)
    finally:
        tracer.stop()
        recorded = spans.collect()
    out["traced"] = {"metrics": traced["metrics"],
                     "stop_s": tracer.stop_s,
                     "spans_recorded": len(recorded),
                     "spans": span_metrics(recorded)}
    try:
        path = glob.glob(os.path.join(tracer.dir, "**", "*.xplane.pb"),
                         recursive=True)[0]
        hlo = step_hlo(cell.traffic, scenario, devs,
                       traced["completed"][0][1])
        out["traced"].update(traced_report(path, len(devs), cell.traffic,
                                           hlo))
    except Exception as e:      # keep the windows' numbers; say what failed
        import traceback

        traceback.print_exc()
        out["traced"]["error"] = repr(e)
    finally:
        shutil.rmtree(tracer.dir, ignore_errors=True)

    line = json.dumps(out, default=str)
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(line + "\n")
    out["traced"].pop("op_sample", None)
    print(json.dumps(out, default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
