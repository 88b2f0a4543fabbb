"""Entry "atlas": back-to-back λ_max sweeps of `sweep_lambda_max`.

A sweep holds `replicas` cells per policy; `correct` compares a sample of
the finished sweeps' rows with the plain reference's own bisection.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from harness import check, reference
from harness import traffic as gen
from harness.trace import mark


class Entry:
    def __init__(self, traffic: dict, scenario: str, devices, seed: int):
        self.t, self.scenario, self.devices, self.seed = (traffic, scenario,
                                                          devices, seed)

    def cells(self, job: int):
        from repro.fleet import AtlasJob

        return [AtlasJob(scenario=self.scenario, policy=p, topo_seed=r,
                         eps_b=float(self.t["eps_b"]))
                for p, r in gen.atlas_cells(self.t, self.seed, job)]

    def sweep(self, cells, **over):
        from repro.fleet import VerdictConfig, sweep_lambda_max

        t = self.t
        kw = dict(seeds=tuple(t["seeds"]), T=int(t["T"]),
                  chunk=int(t["chunk"]), rel_tol=float(t["rel_tol"]),
                  bracket=tuple(t["bracket"]),
                  max_calls=int(t["max_calls"]),
                  max_requeues=int(t["max_requeues"]),
                  n_buckets=int(t["n_buckets"]),
                  early_stop=bool(t["early_stop"]),
                  verdict=VerdictConfig(**t["verdict"]),
                  devices=self.devices)
        kw.update(over)
        return sweep_lambda_max(cells, **kw)

    def setup(self) -> Dict[str, float]:
        from repro.fleet import policy_bound_exact

        warm = self.cells(-1)
        t0 = time.perf_counter()
        for p in self.t["policies"]:
            policy_bound_exact(self.scenario, p, float(self.t["eps_b"]),
                               topo_seed=warm[0].topo_seed)
        lp_s = time.perf_counter() - t0
        # Same lanes, same programs: two probes per cell run every program
        # of the window once, the carry rewrite between probes included.
        self.sweep(warm, max_calls=2, max_requeues=0,
                   stream_log=lambda rec: None)
        return {"lp_s": lp_s}

    def window(self, seconds: float) -> dict:
        """Whole sweeps, back to back: another starts while the last one's
        length still fits before the deadline (the first always runs).  A
        cell's answer time is the host time of the launch record in which
        its row became final (`n_done_cells` of its launch unit grew; a
        re-queued cell stays active until its last attempt ends), from the
        sweep's submission."""
        start = time.perf_counter()
        latencies: List[float] = []
        records: List[float] = []
        completed = []
        end = start
        while True:
            cells = self.cells(len(completed))
            mark(f"submit_{len(completed)}")
            submit = time.perf_counter()
            done: Dict[int, int] = {}

            def log(rec, submit=submit, done=done):
                if rec.get("kind") != "atlas":
                    return
                now = time.perf_counter()
                records.append(now)
                fresh = rec["n_done_cells"] - done.get(rec["group"], 0)
                done[rec["group"]] = rec["n_done_cells"]
                latencies.extend([now - submit] * fresh)

            res = self.sweep(cells, stream_log=log)
            last, end = end, time.perf_counter()
            completed.append((cells, res))
            if end + (end - last) > start + seconds:
                break
        wall = end - start
        # Host time between launch records (a launch, its readback and the
        # scheduler's rewrite): where a host stall shows in the window.
        gaps = np.diff(records)
        return {"metrics": {
                    "cells_per_s": len(latencies) / wall,
                    "answer_s.p80": float(np.percentile(latencies, 80))},
                "n_answers": len(latencies), "window_s": wall,
                "n_sweeps_completed": len(completed),
                "launch_s_median": float(np.median(gaps)),
                "launch_s_slowest": [round(float(g), 4)
                                     for g in np.sort(gaps)[::-1][:5]],
                "completed": completed}

    def one_job(self, seed: int) -> list:
        self.seed = seed
        cells = self.cells(0)
        return [(cells, self.sweep(cells))]

    # --- correct ----------------------------------------------------------

    def sample(self, completed, seed: int) -> List[tuple]:
        """(policy, replica, program row) of `check.cells` cells of the
        finished sweeps: the `check.longest` that simulated the most
        slots, the rest drawn from the seed."""
        rows = [(c.policy, c.topo_seed, r)
                for cells, res in completed for c, r in zip(cells, res.rows)]
        n = int(self.t["check"]["cells"])
        longest = int(self.t["check"]["longest"])
        order = sorted(range(len(rows)), key=lambda i: -rows[i][2].total_slots)
        pick = order[:longest]
        rest = [i for i in range(len(rows)) if i not in set(pick)]
        rng = check.rng(seed, 2)
        pick += [rest[i] for i in rng.choice(
            len(rest), min(n - longest, len(rest)), replace=False)]
        return [rows[i] for i in sorted(pick)]

    @staticmethod
    def program(sample) -> List[Dict]:
        return [{"lam_max": r.lam_max, "hi": r.hi,
                 "n_requeues": r.n_requeues, "n_calls": r.n_calls,
                 "undecided": r.undecided} for _, _, r in sample]

    def reference(self, dep: reference.Deployment, sample, dtype
                  ) -> List[Dict]:
        return reference.atlas_rows(dep, [(p, r) for p, r, _ in sample],
                                    self.t, dtype=dtype)

    @staticmethod
    def compare(got, want, sample) -> Dict[str, float]:
        """Share of cells whose row differs: λ_max, bracket top (both
        against the reference's own LP bound), re-queues, probe count, or
        whether the top was proven unstable."""
        differ = 0
        for g, w in zip(got, want, strict=True):
            tol = 1e-6 * w["bound"]
            differ += (abs(g["lam_max"] - w["lam_max"]) > tol
                       or abs(g["hi"] - w["hi"]) > tol
                       or g["n_requeues"] != w["n_requeues"]
                       or g["n_calls"] != w["n_calls"]
                       or bool(g["undecided"]) != bool(w["undecided"]))
        return {"rows_differ": differ / len(got)}
