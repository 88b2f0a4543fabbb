"""Tests of the chip benchmark under `perfbench/`, on the CPU.

They cover what needs no chip: the trace reduction on a synthetic trace,
finding the benchmark's parts by name (a dropped-in cell, traffic mix,
configuration and metric included), the job lists a seed gives, the
refusal to run without a TPU or without the program, the plain reference
against the program at a small size (and its bfloat16 control against the
limits), and a run with the timed path broken underneath, which has to
come out not correct.
"""
from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "perfbench"
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

from harness import check, reference, spec, trace  # noqa: E402
from harness import traffic as gen  # noqa: E402

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
WORKLOADS = [w["name"] for w in BENCHMARK["workloads"]]
# Deferred cells (PERF.md §7) whose files the benchmark keeps: the wired
# grid under the fixed-rate sweep mix.
DEFERRED = {"fig5_grid.sweep": {"name": "fig5_grid.sweep",
                                "config": "fig5_grid", "traffic": "sweep",
                                "chips": 1}}


def _cell(name):
    if name in DEFERRED:
        return spec.cell_of(BENCHMARK, DEFERRED[name])
    return spec.load_cell(name)


# Small cells for the CPU: the cells' own traffic with fewer lanes and
# shorter horizons (the verdict still needs six windows to latch).
SMALL = {
    "atlas": dict(replicas=1, T=2048, chunk=256, max_calls=3,
                  check={"cells": 2, "longest": 1}),
    "fleet": dict(seeds_per_rate=2, T=1000, chunk=100,
                  check={"lanes_per_rate": 1}),
}


# ---------------------------------------------------------------------------
# Trace reduction
# ---------------------------------------------------------------------------

def _synthetic() -> trace.Reduced:
    """Two devices over a 1000 ns window.  Device 0 runs chunk_step at
    [100, 300) and [500, 700) with a rewrite at [320, 340); device 1 runs
    chunk_step at [100, 400) and [600, 900)."""
    def dev(i, modules, ops):
        return trace.DeviceTrace(f"/device:TPU:{i}",
                                 np.array(ops, np.int64).reshape(-1, 2),
                                 modules)
    d0 = dev(0, [("jit_chunk_step", 100, 300), ("jit_rewrite", 320, 340),
                 ("jit_chunk_step", 500, 700)],
             [(100, 200), (150, 300), (320, 340), (500, 700)])
    d1 = dev(1, [("jit_chunk_step", 100, 400), ("jit_chunk_step", 600, 900)],
             [(100, 400), (600, 900)])
    host = [(trace.WINDOW_SPAN, 0, 1000), ("perfbench.submit_0", 40, 40),
            ("PjitFunction(chunk_step)", 90, 110),
            ("device_get", 340, 500)]
    return trace.Reduced(window=(0, 1000), devices=[d0, d1], host=host)


def _reader(name):
    return _cell("fig5_grid.atlas").metric_reader(name)


def _ctx(reduced, chunk=10):
    return type("Ctx", (), {"trace": reduced, "traffic": {"chunk": chunk},
                            "setup": {"compile_s": 1.5, "lp_s": None}})()


def test_busy_union_and_idle_share():
    r = _synthetic()
    # device 0 busy 200 + 20 + 200 = 420 ns; device 1 busy 600 ns
    assert r.busy_s() == pytest.approx(510e-9)
    assert r.window_s == pytest.approx(1000e-9)
    for kind in ("atlas", "sweep"):
        assert _reader(f"device_idle_share.{kind}")(_ctx(r)) == \
            pytest.approx(49.0)


def test_step_time_and_launch_gaps():
    r = _synthetic()
    # device 0: runs of 200 ns x 10 slots; device 1: runs of 300 ns
    for kind in ("atlas", "sweep"):
        assert _reader(f"step_device_us.{kind}")(_ctx(r)) == \
            pytest.approx((0.02 + 0.03) / 2)
    # idle between runs: device 0 200 - 20 = 180 ns, device 1 200 ns
    assert _reader("launch_gap_ms.atlas")(_ctx(r)) == pytest.approx(190e-6)
    # submitted at 40 ns, first chunk step at 100 ns on both devices
    assert _reader("job_start_ms.sweep")(_ctx(r)) == pytest.approx(60e-6)


def test_step_time_is_the_median_whole_execution():
    r = _synthetic()
    d0 = r.devices[0]
    d0.modules = d0.modules + [("jit_chunk_step", 720, 1000),
                               ("jit_chunk_step", 950, 1200)]
    r.devices = [d0]
    # whole runs 200, 200, 280 ns (the one past the window is left out)
    assert _reader("step_device_us.sweep")(_ctx(r)) == pytest.approx(0.02)
    assert [w for _, _, w in r.executions("chunk_step")] == [
        True, True, True, False]


def test_breakdown_lists_modules_and_labelled_gaps():
    r = _synthetic()
    top = dict(r.top_modules())
    assert top["jit_chunk_step"] == pytest.approx((400 + 600) / 2 * 1e-9)
    gaps = r.idle_gaps()
    assert [g[1] for g in gaps] == pytest.approx([300e-9, 160e-9, 100e-9,
                                                  20e-9])
    assert gaps[1][0] == "perfbench.submit_0/device_get"
    assert gaps[2][0] == "perfbench.submit_0/-"


def test_readers_return_nothing_without_a_trace():
    for name in ("device_idle_share.atlas", "step_device_us.sweep",
                 "launch_gap_ms.atlas", "job_start_ms.sweep", "lp_s"):
        assert _reader(name)(_ctx(None)) is None
    assert _reader("compile_s")(_ctx(None)) == 1.5


# ---------------------------------------------------------------------------
# Parts found by name
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", WORKLOADS)
def test_every_cell_loads_with_its_files(name):
    cell = spec.load_cell(name)
    entry = cell.entry()
    assert all(callable(getattr(entry, f)) for f in (
        "setup", "window", "one_job", "sample", "program", "reference",
        "compare"))
    assert cell.end_to_end[0]["name"] == "setup_s"
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(cell.metric_reader(m["name"]))
    assert set(cell.limits) and all(v is not None
                                    for v in cell.limits.values())
    reference.Deployment.from_config(cell.config)


def test_dropped_in_parts_are_found_without_edits(tmp_path):
    root = tmp_path / "repo"
    shutil.copytree(BENCH, root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    b = root / "perfbench"
    # a new configuration, traffic mix, per-layer metric and cell: files
    # and entries only
    cfg = json.loads((b / "configs" / "fig5_grid.json").read_text())
    cfg["comp_capacity"] = 3.0
    (b / "configs" / "grid_c3.json").write_text(json.dumps(cfg))
    (b / "configs" / "grid_c3.py").write_text(
        "def scenario():\n    return 'grid_c3'\n")
    mix = json.loads((b / "traffic" / "sweep.json").read_text())
    mix["rate_fracs"] = [0.5, 0.9]
    (b / "traffic" / "sweep_two.json").write_text(json.dumps(mix))
    (b / "metrics" / "answers_seen.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    (b / "entries" / "replay.py").write_text(
        "class Entry:\n    kind = 'replay'\n")
    (b / "traffic" / "replay_one.json").write_text(json.dumps(
        {"entry": "replay"}))
    bench["configs"].append({"name": "grid_c3", "source": "x",
                             "file": "perfbench/configs/grid_c3.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "grid_c3.two", "config": "grid_c3",
                               "traffic": "sweep_two", "chips": 1,
                               "why": "x"})
    bench["workloads"].append({"name": "grid_c3.replay", "config": "grid_c3",
                               "traffic": "replay_one", "chips": 1,
                               "why": "x"})
    bench["end_to_end"][1]["workloads"].append("grid_c3.two")
    for name in ("answers_seen", "answers_seen.two"):
        bench["per_layer"].append({"name": name, "unit": "1",
                                   "better": "higher",
                                   "source": "host_clock", "layer": "x",
                                   "moves": "lane_slots_per_s",
                                   "workloads": ["grid_c3.two"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("grid_c3.two", root=root, bench_dir=b)
    assert cell.config["comp_capacity"] == 3.0
    assert cell.traffic["rate_fracs"] == [0.5, 0.9]
    assert cell.scenario() == "grid_c3"
    assert [m["name"] for m in cell.end_to_end] == ["setup_s",
                                                    "lane_slots_per_s"]
    assert cell.metric_reader("answers_seen")(None) == 42.0
    # a split metric without a file of its own is read by its stem's
    assert cell.metric_reader("answers_seen.two")(None) == 42.0
    assert cell.limits == {}
    assert cell.entry().__name__ == "Entry"
    assert spec.load_cell("grid_c3.replay", root=root,
                          bench_dir=b).entry().kind == "replay"
    # the cells that were there are unchanged
    old = spec.load_cell("fig5_grid_wireless.sweep", root=root, bench_dir=b)
    assert "answers_seen" not in [m["name"] for m in old.per_layer]


# ---------------------------------------------------------------------------
# Job lists from the seed
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5, 2 ** 40 + 3])
def test_job_lists_follow_the_seed(seed):
    atlas = spec.load_cell("fig5_grid.atlas").traffic
    sweep = spec.load_cell("fig5_grid_wireless.sweep").traffic
    a = gen.atlas_cells(atlas, seed, 3)
    assert a == gen.atlas_cells(atlas, seed, 3)
    assert len(a) == len(atlas["policies"]) * atlas["replicas"]
    # another seed: the same cells (the same work) in another order
    other = gen.atlas_cells(atlas, seed + 1, 3)
    assert a != other and sorted(a) == sorted(other)
    assert [p for p, _ in a] == [p for p, _ in other]
    assert not set(a) & set(gen.atlas_cells(atlas, seed, 4))
    lanes = gen.fleet_lanes(sweep, 8.0, seed, 0)
    assert lanes == gen.fleet_lanes(sweep, 8.0, seed, 0)
    assert len(lanes) == len(sweep["rate_fracs"]) * sweep["seeds_per_rate"]
    assert all(0 <= s < 2 ** 31 for _, s in lanes)
    assert sorted({lam for lam, _ in lanes}) == sorted(
        8.0 * f for f in sweep["rate_fracs"])
    assert lanes != gen.fleet_lanes(sweep, 8.0, seed + 1, 0)


# ---------------------------------------------------------------------------
# The command refuses what it cannot measure
# ---------------------------------------------------------------------------

def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "fig5_grid_wireless.sweep",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(stdout: str) -> bool:
    return not any(line.startswith("{") for line in stdout.splitlines())


def test_command_fails_without_a_tpu():
    p = _command(ROOT)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_command_fails_without_the_program(tmp_path):
    shutil.copytree(BENCH, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = _command(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert _no_result(p.stdout)


# ---------------------------------------------------------------------------
# Reference, control and faults at a small size
# ---------------------------------------------------------------------------

def test_reference_lp_matches_the_paper():
    dep = reference.Deployment.from_config(
        spec.load_cell("fig5_grid.atlas").config)
    assert reference.lp_capacity(dep) == pytest.approx(8.0, rel=1e-9)


def test_bisection_probes_in_the_documented_order():
    s = reference.cell_search(5, 11, max_calls=8, max_requeues=1)
    truth = lambda k: k <= 9
    probes = []
    k = next(s)
    try:
        while True:
            probes.append(k)
            k = s.send((truth(k[0]), False))
    except StopIteration as stop:
        row = stop.value
    # floor 5 holds, ceiling 11 fails, then bisection 8, 9, 10
    assert [p[0] for p in probes] == [5, 11, 8, 9, 10]
    assert row == {"k_lo": 9, "k_hi": 10, "n_requeues": 0, "n_calls": 5,
                   "undecided": False}


def _fleet_entry(name, policy="pi3"):
    """The cell's fleet entry over four lanes of 1000 slots."""
    cell = _cell(name)
    t = dict(cell.traffic, policy=policy, T=1000, chunk=100)
    return cell, cell.entry()(t, cell.scenario(), None, 0)


def _fleet_sample(name, policy):
    """Program metrics of four lanes, as the entry samples them."""
    from repro.fleet import FleetJob, VerdictConfig, run_fleet

    cell, entry = _fleet_entry(name, policy)
    dep = reference.Deployment.from_config(cell.config)
    lams = [1.0, 2.0, 3.0, 4.0] if dep.wireless else [2.0, 5.0, 7.5, 8.5]
    seeds = [11, 2 ** 31 - 1, 7, 99]
    res = run_fleet([FleetJob(scenario=cell.scenario(), policy=policy, lam=l,
                              seed=s, eps_b=0.05)
                     for l, s in zip(lams, seeds)],
                    T=1000, chunk=100,
                    verdict=VerdictConfig(**cell.traffic["verdict"]))
    return cell, entry, dep, list(zip(lams, seeds, res.metrics))


@pytest.mark.parametrize("config", ["fig5_grid_wireless.sweep",
                                    "fig5_grid.sweep"])
@pytest.mark.parametrize("policy", ["pi3", "pi3bar"])
def test_reference_agrees_with_the_program(config, policy):
    import jax.numpy as jnp

    cell, entry, dep, sample = _fleet_sample(config, policy)
    numbers = entry.compare(entry.program(sample),
                            entry.reference(dep, sample, jnp.float32),
                            sample)
    judged = check.judge(numbers, cell.limits)
    assert check.passed(judged), judged
    assert "verdicts_differ" in judged


def _control_sample(name):
    """The cell's entry at a small size, and cells or lanes for it."""
    cell = _cell(name)
    if cell.traffic["entry"] == "atlas":
        t = dict(cell.traffic, **SMALL["atlas"])
        entry = cell.entry()(t, cell.scenario(), None, 0)
        return cell, entry, [(p, r, None) for p in t["policies"]
                             for r in range(2)]
    _, entry = _fleet_entry(name)
    wireless = reference.Deployment.from_config(cell.config).wireless
    lams = [1.0, 2.0, 3.0, 4.0] if wireless else [2.0, 5.0, 7.5, 8.5]
    return cell, entry, [(l, s, None) for l, s in
                         zip(lams, [11, 2 ** 31 - 1, 7, 99])]


@pytest.mark.parametrize("config", ["fig5_grid_wireless.sweep",
                                    "fig5_grid.sweep", "fig5_grid.atlas"])
def test_bfloat16_control_fails_the_limits(config):
    cell, entry, sample = _control_sample(config)
    dep = reference.Deployment.from_config(cell.config)
    judged = check.judge(check.control(entry, dep, sample), cell.limits)
    assert not check.passed(judged), judged


def _small_run(workload, monkeypatch):
    from harness import device, runner

    monkeypatch.setattr(device, "configure", lambda root: "")
    cell = _cell(workload)
    return runner.run(cell, 2 ** 33 + 17, 1.0, False,
                      time.perf_counter(),
                      require_chip=False, log=open(os.devnull, "w"),
                      overrides=SMALL[cell.traffic["entry"]])


def _break(monkeypatch, fault):
    """Plant `fault` in the program's launch programs or its answers."""
    import jax
    import jax.numpy as jnp
    from repro.fleet import atlas, engine

    real = engine.make_group_launch.__wrapped__

    def launch(runner_, mesh, n_step_args=7):
        init_fn, step_fn, fin_fn = real(runner_, mesh, n_step_args)
        plain = engine._lane_jit(runner_.chunk_step, mesh, n_step_args)
        if fault == "state_unchanged":
            step_fn = jax.jit(lambda *a: a[-1])
        elif fault == "half_batch":
            @jax.jit
            def step_fn(*a):
                new = plain(*a)
                B = a[0].s1.shape[0]
                keep = jnp.arange(B) < (B + 1) // 2
                return jax.tree_util.tree_map(
                    lambda n, o: jnp.where(
                        keep.reshape((B,) + (1,) * (n.ndim - 1)), n, o),
                    new, a[-1])
        elif fault == "answer_altered":
            def fin(lam, eps, carry):
                out = dict(fin_fn(lam, eps, carry))
                out["delivered"] = out["delivered"] * 1.02
                return out
            return init_fn, step_fn, fin
        return init_fn, step_fn, fin_fn

    monkeypatch.setattr(engine, "make_group_launch", launch)
    monkeypatch.setattr(atlas, "make_group_launch", launch)
    if fault == "answer_altered":
        finish = atlas._finish_row

        def altered(cell, bound, step, bis, probes, **kw):
            row = finish(cell, bound, step, bis, probes, **kw)
            import dataclasses
            return dataclasses.replace(row, lam_max=row.lam_max + step)
        monkeypatch.setattr(atlas, "_finish_row", altered)


@pytest.mark.parametrize("workload", ["fig5_grid.atlas", "fig5_grid.sweep",
                                      "fig5_grid_wireless.sweep"])
def test_small_run_is_correct(workload, monkeypatch):
    r = _small_run(workload, monkeypatch)
    assert r["correct"], r["checks"]
    assert list(r)[-1] == "checks"
    assert r["metrics"]["setup_s"]["value"] > 0


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
@pytest.mark.parametrize("workload", ["fig5_grid.atlas", "fig5_grid.sweep",
                                      "fig5_grid_wireless.sweep"])
def test_broken_timed_path_is_not_correct(workload, fault, monkeypatch):
    _break(monkeypatch, fault)
    r = _small_run(workload, monkeypatch)
    assert not r["correct"], r["checks"]
