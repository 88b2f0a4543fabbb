"""Serving engine: batched trace-driven runs on the fleet substrate.

`run_serving` is the serving twin of `fleet.run_fleet`: jobs are grouped
by (semantic policy key, trace) — the axes that change Python-level
control flow — padded to the device mesh, and driven as a Python loop of
`jit(shard_map(vmap(chunk_step)))` launches with the carry donated between
launches (`fleet.make_group_launch` with ``n_step_args=6``).  The
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

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core.graph import ComputeProblem
from repro.core.policies import PolicyConfig
from repro.core.queues import VERDICT_NAMES
from repro.fleet.batching import PadDims, pad_problem
from repro.fleet.engine import (VerdictConfig, _lane_jit,
                                _policy_group_key, make_group_launch)
from repro.fleet.scenarios import event_code, get_scenario
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
    """
    jobs = list(jobs)
    stream = stream or stream_log is not None or stream_path is not None
    devices = list(devices or jax.devices())
    ndev = len(devices)
    mesh = Mesh(np.array(devices), ("fleet",))

    problem_of: Dict[tuple, ComputeProblem] = {}
    for job in jobs:
        k = (job.scenario, job.topo_seed)
        if k not in problem_of:
            problem_of[k] = get_scenario(job.scenario).build(job.topo_seed)
    dims = dims or PadDims.of(list(problem_of.values()))
    padded_of = {k: pad_problem(p, dims) for k, p in problem_of.items()}

    groups: Dict[tuple, List[int]] = {}
    for i, job in enumerate(jobs):
        groups.setdefault(_group_key(job), []).append(i)

    rt = resumed = None
    if resilience is not None:
        from repro.runtime.resilience import (host_lane_mask as
                                              _host_lane_mask,
                                              maybe_resilient)
        rt = maybe_resilient(resilience, "serving", jobs=tuple(jobs), T=T,
                             chunk=chunk, window=window, verdict=verdict,
                             admission=admission, dims=dims, ndev=ndev)
        resumed = rt.resumed

    metrics: List[Dict[str, float] | None] = [None] * len(jobs)
    eff_T = eff_win = 0
    glaunch = 0
    degraded: Dict[int, str] = {}
    recovery = None
    sink = None
    if stream:
        from repro.obs.emitter import StreamSink
        sink = StreamSink(path=stream_path, log=stream_log,
                          append=resumed is not None)
    if resumed is not None:
        from repro.runtime.resilience import metrics_restore, plan_restore
        for i, m in enumerate(metrics_restore(resumed["metrics"])):
            if m is not None:
                metrics[i] = m
        glaunch = resumed["global_launch"]
        degraded = {int(k): v for k, v in resumed["degraded"].items()}
        recovery = plan_restore(resumed["recovery"])
    try:
        for g, (gkey, idxs) in enumerate(groups.items()):
            job0 = jobs[idxs[0]]
            cfg = job0.policy_config()
            runner = make_serving_runner(cfg, get_trace(job0.trace), T,
                                         chunk=chunk, window=window,
                                         verdict=verdict,
                                         admission=admission)
            eff_T, eff_win = runner.T, runner.window
            if resumed is not None and g < resumed["group"]:
                continue

            B = len(idxs)
            Bp = -(-B // ndev) * ndev
            padded_idxs = idxs + [idxs[-1]] * (Bp - B)
            pp = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs),
                *[padded_of[(jobs[i].scenario, jobs[i].topo_seed)]
                  for i in padded_idxs])
            lam = jnp.array([jobs[i].lam for i in padded_idxs], jnp.float32)
            eps = jnp.array([jobs[i].eps_b for i in padded_idxs],
                            jnp.float32)
            ek = jnp.array([event_code(get_scenario(jobs[i].scenario).events)
                            for i in padded_idxs], jnp.int32)
            keys = jax.vmap(jax.random.PRNGKey)(
                jnp.array([jobs[i].seed for i in padded_idxs], jnp.int32))

            init_fn, step_fn, fin_fn = make_group_launch(runner, mesh,
                                                         n_step_args=6)
            probe_fn = emitter = None
            try:
                if sink is not None:
                    from repro.obs.emitter import ChunkEmitter
                    probe_fn = _probe_launch(runner, mesh)
                    emitter = ChunkEmitter("serving", group=g, n_real=B,
                                           runner=runner, mesh=mesh,
                                           sink=sink)
                launched = 0
                if resumed is not None and g == resumed["group"]:
                    launched = resumed["launched"]
                    if launched > 0:
                        like = jax.eval_shape(init_fn, pp)
                        carry = rt.restore_carry(like, mesh)
                    else:
                        carry = init_fn(pp)
                    if emitter is not None and launched > 0:
                        pf = probe_fn or _probe_launch(runner, mesh)
                        emitter.restore_clock(
                            launched, {k: np.asarray(v) for k, v in
                                       pf(carry).items()})
                    if sink is not None:
                        from repro.obs import schema
                        sink.write(schema.make_record(
                            "resume", group=g, chunk=launched,
                            t=launched * runner.chunk, n_sims=B,
                            engine="serving",
                            ckpt_step=resumed["ckpt_step"],
                            n_preloaded=sink.n_preloaded))
                else:
                    carry = init_fn(pp)
                while launched < runner.n_chunks:
                    if rt is not None:
                        carry = rt.launch(g, glaunch, step_fn, pp, lam, eps,
                                          ek, keys, carry)
                    else:
                        carry = step_fn(pp, lam, eps, ek, keys, carry)
                    launched += 1
                    glaunch += 1
                    if emitter is not None:
                        # The probe launch reduces the carry to small [Bp]
                        # leaves (read-only, no donation); the emitter
                        # dispatches them to the callback thread without
                        # blocking the chunk loop.
                        emitter.emit(probe_fn(carry))
                    if rt is not None:
                        dead = rt.dead_hosts(glaunch)
                        if dead:
                            lane_dead = _host_lane_mask(Bp, ndev, dead)
                            per = Bp // ndev
                            for l in range(B):
                                if lane_dead[l] and idxs[l] not in degraded:
                                    degraded[idxs[l]] = \
                                        f"host_dropout:host{l // per}"
                            from repro.runtime.fault import plan_recovery
                            recovery = plan_recovery(
                                ndev, 1, [f"host{h}" for h in dead], [], 1)
                        if rt.should_snapshot(glaunch):
                            from repro.runtime.resilience import plan_state
                            rt.snapshot(glaunch, carry, {
                                "group": g, "launched": launched,
                                "global_launch": glaunch,
                                "metrics": metrics,
                                "degraded": {str(k): v
                                             for k, v in degraded.items()},
                                "recovery": plan_state(recovery)})
                        rt.maybe_preempt(glaunch)
                out = jax.device_get(fin_fn(lam, eps, carry))
                for j, i in enumerate(idxs):
                    metrics[i] = {
                        k: (float(v[j]) if np.ndim(v[j]) == 0
                            else np.asarray(v[j]).astype(float).tolist())
                        for k, v in out.items()}
            finally:
                if emitter is not None:
                    emitter.close()   # flush in-flight records for this
                                      # group, also on fault/preemption
            if rt is not None:
                from repro.runtime.resilience import plan_state
                rt.snapshot(glaunch, (), {
                    "group": g + 1, "launched": 0, "global_launch": glaunch,
                    "metrics": metrics,
                    "degraded": {str(k): v for k, v in degraded.items()},
                    "recovery": plan_state(recovery)})
    finally:
        if sink is not None:
            sink.close()
    return ServingResult(jobs=jobs, metrics=metrics, n_programs=len(groups),
                         n_sims=len(jobs), dims=dims, T=eff_T, window=eff_win,
                         stream_records=sink.records if sink is not None
                         else [],
                         resumed_from=(resumed["ckpt_step"]
                                       if resumed is not None else None),
                         degraded=degraded, recovery_plan=recovery,
                         n_fault_retries=(rt.n_retries if rt is not None
                                          else 0))
