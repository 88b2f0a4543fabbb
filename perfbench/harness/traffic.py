"""The one traffic generator: a mix's parameter file and `--seed` in,
the jobs of a run out.

Two entries are driven:

  "atlas"  back-to-back λ_max sweeps (`sweep_lambda_max`).  Sweep j holds
           `replicas` cells per policy; a cell's replica id feeds its probe
           streams, and the seed orders the cells within each policy.
  "fleet"  back-to-back fixed-rate sweeps (`run_fleet`).  Job j holds
           `seeds_per_rate` lanes at each rate fraction of the mix's base
           rate: the configuration's LP bound (`"rate_base": "lp_bound"`) or
           a measured λ_max (`"rate_base": {"lambda_max": ...}`).

The sizes, rates and arrival law are the same for every seed.  A fleet
job's work is fixed by its lanes and horizon, so there the seed picks the
arrival streams; an atlas sweep's work follows from its probe outcomes, so
there the seed only orders a fixed set of cells.
"""
from __future__ import annotations

from typing import List, Tuple

_M64 = (1 << 64) - 1


def derive(*parts: int) -> int:
    """A non-negative int31 from any integers (the run seed may exceed 32
    bits): a SplitMix64 chain over the parts."""
    h = 0x243F6A8885A308D3
    for p in parts:
        h = (h ^ (int(p) & _M64)) & _M64
        h = (h + 0x9E3779B97F4A7C15) & _M64
        h ^= h >> 30
        h = (h * 0xBF58476D1CE4E5B9) & _M64
        h ^= h >> 27
        h = (h * 0x94D049BB133111EB) & _M64
        h ^= h >> 31
    return int(h & 0x7FFFFFFF)


def atlas_cells(traffic: dict, seed: int, job: int) -> List[Tuple[str, int]]:
    """(policy, replica id) of every cell of sweep `job`: policies in the
    mix's order, each over replicas `job * replicas` onward in an order
    drawn from the seed.  A cell's probe outcomes, and so the sweep's
    work, follow from its replica id; every seed gets the same work."""
    n = int(traffic["replicas"])
    out = []
    for i, p in enumerate(traffic["policies"]):
        order = sorted(range(n), key=lambda r: derive(seed, job, i, r))
        out += [(p, job * n + r) for r in order]
    return out


def fleet_lanes(traffic: dict, base_rate: float, seed: int, job: int
                ) -> List[Tuple[float, int]]:
    """(offered rate, stream seed) of every lane of job `job`, rate
    fractions outermost."""
    return [(float(f) * base_rate, derive(seed, job, i, s))
            for i, f in enumerate(traffic["rate_fracs"])
            for s in range(int(traffic["seeds_per_rate"]))]


def base_rate(traffic: dict, lp_bound) -> float:
    """The rate the mix's fractions apply to; `lp_bound()` is called only
    where the mix asks for it."""
    b = traffic["rate_base"]
    if b == "lp_bound":
        return float(lp_bound())
    return float(b["lambda_max"])
