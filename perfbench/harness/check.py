"""What `correct` compares: the program's answers against the plain
reference (`reference.py`), on a sample drawn from the seed.

Each entry (`entries/<entry>.py`) draws the sample, runs the reference on
it and compares; a run is correct when every number is at or under its
limit (`limits/<workload>.json`).  `control` puts the reference in
bfloat16 in the program's place, which is how the limits' upper readings
were taken.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from . import reference as ref


def rng(seed: int, salt: int) -> np.random.Generator:
    """A sample's draws from the run seed (any size) and a salt."""
    return np.random.default_rng([seed & 0xFFFFFFFF,
                                  (seed >> 32) & 0xFFFFFFFF, salt])


def numbers(entry, dep: ref.Deployment, scenario, completed, seed: int
            ) -> Dict[str, float]:
    """Every number `correct` compares for one run: the deployment, and
    the entry's sample of the window's answers against the reference."""
    import jax.numpy as jnp

    out = {"deployment_differs": deployment_differs(scenario, dep)}
    if not completed:
        return dict(out, finished_jobs_missing=1.0)
    sample = entry.sample(completed, seed)
    want = entry.reference(dep, sample, jnp.float32)
    return dict(out, **entry.compare(entry.program(sample), want, sample))


def control(entry, dep: ref.Deployment, sample) -> Dict[str, float]:
    """The same numbers with the control in the program's place: the
    reference computed in bfloat16, against the reference in float32."""
    import jax.numpy as jnp

    want = entry.reference(dep, sample, jnp.float32)
    return entry.compare(entry.reference(dep, sample, jnp.bfloat16), want,
                         sample)


def deployment_differs(scenario, dep: ref.Deployment) -> float:
    """1.0 unless the program's scenario builds the very network, arrival
    law and interference model the configuration file states."""
    p = scenario.build(0)
    same = (p.graph.n_nodes == dep.n_nodes
            and [tuple(e) for e in p.graph.edges.tolist()] == list(dep.edges)
            and tuple(float(c) for c in p.graph.capacity)
            == dep.link_capacity
            and (p.s1, p.s2, p.dest) == (dep.s1, dep.s2, dep.sink)
            and tuple(p.comp_nodes) == dep.comp_nodes
            and tuple(p.comp_caps) == dep.comp_capacity
            and scenario.wireless == dep.wireless
            and scenario.arrival == "poisson" and scenario.events == "static")
    return 0.0 if same else 1.0


def judge(values: Dict[str, float], limits: Dict[str, float]
          ) -> Dict[str, Dict[str, float]]:
    """Each number beside its limit (None where no limit is set)."""
    return {k: {"value": float(v), "limit": limits.get(k)}
            for k, v in values.items()}


def passed(judged: Dict[str, Dict[str, float]]) -> bool:
    return all(j["limit"] is not None and np.isfinite(j["value"])
               and j["value"] <= j["limit"] for j in judged.values())
