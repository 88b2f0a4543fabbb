"""Entry "fleet": back-to-back fixed-rate sweeps of `run_fleet`.

A job is one `run_fleet` call over every lane of the mix (rate fractions
× `seeds_per_rate`); `correct` compares the finalize metrics of a sample
of one finished job's lanes with the plain reference.
"""
from __future__ import annotations

import time
from typing import Dict, List

from harness import check, reference
from harness import traffic as gen
from harness.trace import mark


class Entry:
    def __init__(self, traffic: dict, scenario: str, devices, seed: int):
        self.t, self.scenario, self.devices, self.seed = (traffic, scenario,
                                                          devices, seed)
        self.base = None

    def jobs(self, job: int):
        from repro.fleet import FleetJob

        return [FleetJob(scenario=self.scenario, policy=self.t["policy"],
                         lam=lam, seed=s, eps_b=float(self.t["eps_b"]))
                for lam, s in gen.fleet_lanes(self.t, self.base, self.seed,
                                              job)]

    def run(self, jobs):
        from repro.fleet import VerdictConfig, run_fleet

        return run_fleet(jobs, T=int(self.t["T"]), chunk=int(self.t["chunk"]),
                         early_stop=bool(self.t["early_stop"]),
                         verdict=VerdictConfig(**self.t["verdict"]),
                         devices=self.devices)

    def setup(self) -> Dict[str, float]:
        from repro.fleet import policy_bound_exact

        solved: Dict[str, float] = {}

        def bound():
            t0 = time.perf_counter()
            b = policy_bound_exact(self.scenario, self.t["policy"],
                                   float(self.t["eps_b"]))
            solved["lp_s"] = time.perf_counter() - t0
            return b

        self.base = gen.base_rate(self.t, bound)
        self.run(self.jobs(-1))          # the window's programs, once
        return solved

    def window(self, seconds: float) -> dict:
        """Whole jobs, back to back: another starts while the last one's
        length still fits before the deadline (the first always runs)."""
        start = time.perf_counter()
        completed = []
        end = start
        while True:
            jobs = self.jobs(len(completed))
            mark(f"submit_{len(completed)}")
            res = self.run(jobs)
            last, end = end, time.perf_counter()
            completed.append((jobs, res))
            if end + (end - last) > start + seconds:
                break
        lane_slots = sum(len(j) * r.T for j, r in completed)
        return {"metrics": {"lane_slots_per_s": lane_slots / (end - start)},
                "n_jobs": len(completed), "window_s": end - start,
                "completed": completed}

    def one_job(self, seed: int) -> list:
        self.seed = seed
        jobs = self.jobs(0)
        return [(jobs, self.run(jobs))]

    # --- correct ----------------------------------------------------------

    def sample(self, completed, seed: int) -> List[tuple]:
        """(lam, seed, program metrics) of `lanes_per_rate` lanes at every
        rate of one finished job, both drawn from the seed."""
        rng = check.rng(seed, 1)
        jobs, res = completed[int(rng.integers(len(completed)))]
        per = int(self.t["seeds_per_rate"])
        picks = []
        for i in range(len(self.t["rate_fracs"])):
            for s in rng.choice(per, int(self.t["check"]["lanes_per_rate"]),
                                replace=False):
                j = i * per + int(s)
                picks.append((jobs[j].lam, jobs[j].seed, res.metrics[j]))
        return picks

    @staticmethod
    def program(sample) -> List[Dict]:
        return [m for _, _, m in sample]

    def reference(self, dep: reference.Deployment, sample, dtype
                  ) -> List[Dict]:
        t = self.t
        T, chunk = int(t["T"]), int(t["chunk"])
        lam = [l for l, _, _ in sample]
        out = reference.simulate(dep, t["policy"] in reference.REGULATED,
                                 lam, [t["eps_b"]] * len(sample),
                                 [s for _, s, _ in sample], T, dtype=dtype,
                                 step=chunk)
        verdict = reference.Verdict(window=chunk, burn_in=2 * chunk,
                                    **t["verdict"])
        return [dict(reference.lane_metrics(o),
                     verdict=reference.lane_verdict(o, l, T, verdict))
                for o, l in zip(out, lam)]

    @staticmethod
    def compare(got, want, sample) -> Dict[str, float]:
        """Widest gaps over the lanes: useful rate against the offered
        rate, mean backlog and packets delivered against the reference's;
        and the share of lanes whose streaming verdict differs."""
        rate = backlog = delivered = verdicts = 0.0
        for g, w, (lam, _, _) in zip(got, want, sample, strict=True):
            rate = max(rate, abs(g["useful_rate"] - w["useful_rate"]) / lam)
            backlog = max(backlog, abs(g["mean_queue"] - w["mean_queue"])
                          / max(w["mean_queue"], 1.0))
            delivered = max(delivered, abs(g["delivered"] - w["delivered"])
                            / max(w["delivered"], 1.0))
            verdicts += int(g["verdict"]) != int(w["verdict"])
        return {"rate_gap": rate, "backlog_gap": backlog,
                "delivered_gap": delivered,
                "verdicts_differ": verdicts / len(got)}
