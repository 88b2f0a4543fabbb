"""The `fattree_k8.sweep` cell on the CPU, at a small size.

The cell's own traffic with a short horizon (T 2,000, chunk 500, one seed
a rate: eight lanes of the k=8 fabric, [83, 3, 32] queues each): the
program agrees with the plain reference within the cell's limits, a whole
run through the harness is `correct`, and the reference computed in
bfloat16 (the control) fails those limits.
"""
from __future__ import annotations

import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "perfbench"), str(ROOT / "src")]

from harness import check, reference, spec  # noqa: E402

CELL = "fattree_k8.sweep"
SMALL = dict(seeds_per_rate=1, T=2000, chunk=500,
             check={"lanes_per_rate": 1})


def _entry():
    cell = spec.load_cell(CELL)
    t = dict(cell.traffic, **SMALL)
    entry = cell.entry()(t, cell.scenario(), None, 2 ** 33 + 5)
    entry.base = reference.lp_capacity(
        reference.Deployment.from_config(cell.config), 1.05)
    return cell, entry


def test_configuration_and_limits_load():
    cell = spec.load_cell(CELL)
    dep = reference.Deployment.from_config(cell.config)
    assert (dep.n_nodes, len(dep.edges), len(dep.comp_nodes)) == (83, 259, 32)
    assert cell.traffic["T"] % cell.traffic["chunk"] == 0
    assert cell.traffic["check"]["lanes_per_rate"] <= \
        cell.traffic["seeds_per_rate"]
    assert set(cell.limits) == {"deployment_differs", "rate_gap",
                                "backlog_gap", "delivered_gap",
                                "verdicts_differ"}


def test_program_agrees_with_the_reference_and_control_fails():
    import jax.numpy as jnp
    from repro.fleet import get_scenario

    cell, entry = _entry()
    dep = reference.Deployment.from_config(cell.config)
    assert check.deployment_differs(get_scenario(cell.scenario()), dep) == 0
    completed = entry.one_job(2 ** 33 + 5)
    sample = entry.sample(completed, 2 ** 33 + 5)
    assert len(sample) == len(cell.traffic["rate_fracs"])
    judged = check.judge(
        entry.compare(entry.program(sample),
                      entry.reference(dep, sample, jnp.float32), sample),
        cell.limits)
    assert check.passed(judged), judged
    control = check.judge(check.control(entry, dep, sample), cell.limits)
    assert not check.passed(control), control


def test_small_run_is_correct(monkeypatch):
    from harness import device, runner

    monkeypatch.setattr(device, "configure", lambda root: "")
    r = runner.run(spec.load_cell(CELL), 2 ** 35 + 3, 1.0, False,
                   time.perf_counter(), require_chip=False,
                   log=open(os.devnull, "w"), overrides=SMALL)
    assert r["correct"], r["checks"]
    assert r["metrics"]["lane_slots_per_s"]["value"] > 0
