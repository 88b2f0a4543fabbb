"""Serving engine: batched trace-driven runs on the fleet substrate.

`run_serving` is the serving twin of `fleet.run_fleet`: jobs are grouped
by (semantic policy key, trace) — the axes that change Python-level
control flow — padded to the device mesh, and driven by the fleet's own
chunk loop (`fleet.driver.run_groups`): `jit(shard_map(vmap(chunk_step)))`
launches with the carry donated between launches
(`fleet.make_group_launch` with ``n_step_args=6``).  The
scenario's *event* model (capacity perturbations) is per-job traced data
exactly as in the fleet; the scenario's arrival model is superseded by the
job's `TraceSpec` (live query traffic is what serving is about).

Between chunk launches the engine dispatches a small probe of the carry
(cumulative delivered/admitted/shed, gate, verdict, the latency histogram)
through the telemetry plane's io_callback emitter (`repro.obs.emitter`,
DESIGN.md §11), which differences consecutive probes into *windowed*
per-chunk records — delivered QPS, shed fraction, p99 sojourn, verdict
counts, each a median across the group's sims — validated against the
versioned stream schema (`repro.obs.schema`).  With ``stream=True`` these
land in `ServingResult.stream_records`, one dict per chunk boundary,
ready to be written as JSONL (`serving.report.write_stream_jsonl`);
``stream_path`` appends them live for `capacity_report --follow`.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Sequence

import numpy as np
from jax.sharding import Mesh

from repro.core.policies import PolicyConfig
from repro.core.queues import VERDICT_NAMES
from repro.fleet.batching import PadDims, pad_topologies
from repro.fleet.driver import Run, run_groups
from repro.fleet.engine import (VerdictConfig, _lane_jit,
                                _policy_group_key, make_group_launch)
from repro.fleet.scenarios import get_scenario
from repro.obs.spans import span, spanned
from .admission import AdmissionConfig
from .scheduler import make_serving_runner
from .trace import get_trace


@dataclasses.dataclass(frozen=True)
class ServingJob:
    """One serving run: a scenario instance facing a live query trace."""

    scenario: str = "paper_grid"
    policy: str = "pi3_reg"
    trace: str = "bursty"
    lam: float = 1.0              # long-run offered QPS across all classes
    seed: int = 0
    topo_seed: int = 0
    eps_b: float = 0.05
    pairing: str = "fifo"
    threshold: float = 0.0
    fixed_node: int = 0
    backend: str = "xla"          # slot-decision backend: "xla" | "pallas"

    def policy_config(self) -> PolicyConfig:
        return PolicyConfig(
            name=self.policy, eps_b=self.eps_b, pairing=self.pairing,
            threshold=self.threshold, fixed_node=self.fixed_node,
            wireless=get_scenario(self.scenario).wireless,
            backend=self.backend)


@dataclasses.dataclass
class ServingResult:
    jobs: List[ServingJob]
    metrics: List[Dict[str, float]]   # one dict per job, same order;
                                      # per-class leaves are lists of floats
    n_programs: int
    n_sims: int
    dims: PadDims
    T: int
    window: int
    stream_records: List[dict] = dataclasses.field(default_factory=list)
    resumed_from: int | None = None    # checkpoint step this run restored
                                       # (DESIGN.md §12); None = fresh
    degraded: Dict[int, str] = dataclasses.field(default_factory=dict)
                                  # job index -> reason for jobs whose lanes
                                  # sat on a dropped host — serving lanes
                                  # are not parked (the 8-tuple carry has
                                  # no rewriter), only flagged: their
                                  # metrics are untrustworthy, not silent
    recovery_plan: object | None = None   # runtime.fault.RecoveryPlan
    n_fault_retries: int = 0

    def column(self, name: str) -> np.ndarray:
        return np.array([m[name] for m in self.metrics])

    def verdicts(self) -> List[str]:
        return [VERDICT_NAMES[int(m["verdict"])] for m in self.metrics]


def _group_key(job: ServingJob):
    """Program-forking axes: the fleet's semantic policy key + the trace
    (the class mixture is unrolled Python-level structure in the slot)."""
    return (_policy_group_key(job), job.trace)


@functools.lru_cache(maxsize=64)
def _probe_launch(runner, mesh: Mesh):
    """Jit the between-chunk probe readout (no donation — read-only)."""
    return _lane_jit(runner.probe, mesh, 1)


@spanned("serving.job")
def run_serving(jobs: Sequence[ServingJob], T: int, chunk: int = 512,
                window: int | None = None, devices=None,
                dims: PadDims | None = None,
                verdict: VerdictConfig | None = None,
                admission: AdmissionConfig | None = None,
                stream: bool = False,
                stream_log: Callable[[dict], None] | None = None,
                stream_path: str | None = None,
                resilience=None) -> ServingResult:
    """Run every serving job, one compiled program set per (policy, trace)
    group, with per-chunk streaming records when ``stream`` is on.

    ``stream_log``/``stream_path`` (each implies ``stream``) mirror
    `fleet.run_fleet`: records are assembled off the hot path on the
    io_callback thread (DESIGN.md §11) — ``stream_log`` is invoked there,
    and ``stream_path`` appends JSONL live for `capacity_report --follow`.

    ``resilience`` mirrors `fleet.run_fleet` (DESIGN.md §12): snapshots of
    the donated carry (AdmissionState, latency histogram and trace cursor
    included — they all ride the carry) at chunk boundaries, bit-exact
    resume, retry-with-backoff on injected launch failures.  Host dropout
    only *flags* the affected jobs (``ServingResult.degraded``) and plans
    recovery — the serving carry has no park rewriter.

    With `repro.obs.spans` recording, the call is one ``serving.job``
    span holding the spans `run_fleet` records, named ``serving.*``.
    """
    jobs = list(jobs)
    with span("serving.build"):
        padded_of, _, (dims,) = pad_topologies(jobs, dims)
    groups: Dict[tuple, List[int]] = {}
    for i, job in enumerate(jobs):
        groups.setdefault(_group_key(job), []).append(i)

    def make_runner(job):
        return make_serving_runner(job.policy_config(), get_trace(job.trace),
                                   T, chunk=chunk, window=window,
                                   verdict=verdict, admission=admission)

    with Run("serving", devices, resilience, stream, stream_log, stream_path,
             jobs=tuple(jobs), T=T, chunk=chunk, window=window,
             verdict=verdict, admission=admission, dims=dims) as run:
        metrics, eff_T, eff_win, _ = run_groups(
            run, jobs, list(groups.values()), padded_of,
            make_runner=make_runner,
            make_launch=functools.partial(make_group_launch, n_step_args=6),
            probe=_probe_launch, arrivals=False, totals={})
    return ServingResult(jobs=jobs, metrics=metrics, n_programs=len(groups),
                         n_sims=len(jobs), dims=dims, T=eff_T, window=eff_win,
                         stream_records=run.records,
                         resumed_from=run.resumed_from,
                         degraded=run.degraded, recovery_plan=run.recovery,
                         n_fault_retries=run.n_retries)
