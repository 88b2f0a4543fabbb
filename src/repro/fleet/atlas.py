"""Capacity atlas: a fleet of λ_max bisections in one launch per group.

`frontier.find_lambda_max` measures the paper's headline quantity — the
maximum sustainable query rate λ_max — for *one* (scenario, topo_seed)
cell at a time: every probe is its own `run_fleet` call, so sweeping the
scenario registry is serially bottlenecked on launch count.  The atlas
inverts it (DESIGN.md §10): the offered rate was *already* per-sim traced
data in the chunk-step signature, so hundreds of (cell × seed) bisection
lanes ride **one padded launch per (policy group × size bucket)**, each
lane probing its own cell's current grid rate.  Buckets (DESIGN.md §13)
cut the padding hull by size quantiles so one big topology no longer
inflates every small lane; adaptive horizons (``max_requeues``) re-queue
UNDECIDED-at-top cells at doubled chunk budgets instead of reporting a
collapsed bracket.

The host loop is the PR-5 machinery turned into a scheduler:

  1. every cell owns a pure `frontier.Bisection` machine (the *identical*
     machine the sequential path drives — same probe order, same budget
     semantics), and its `len(seeds)` lanes run the machine's pending
     grid rate;
  2. after each chunk launch the host reads the [B] drift leaves
     (`runner.drift_of`: latched verdict + decision slot) and harvests
     every cell whose probe finished — all lanes decided (early-stop
     semantics) or the horizon's `n_chunks` elapsed;
  3. harvested cells `record(...)` into their machine, pull the next
     probe, and get their lanes *rewritten in place* at the launch
     boundary (`engine.make_sim_rewriter`): fresh init carry, t = 0, new
     `fold_seed(topo_seed, rate_index, 0, seed)` key, new lam — exactly
     the state a standalone `run_fleet` probe would start from, which is
     why per-lane streams are bit-identical to the sequential path;
  4. cells whose machine finishes are *parked*: their verdict leaf is
     forced UNSTABLE so the freeze mask pins the carry while the rest of
     the atlas keeps bisecting.

Because untouched lanes pass through the rewrite bit-unchanged
(`where(False, fresh, old) == old`) and vmap lanes never interact, the
atlas returns **bit-identical** λ_max to per-cell `find_lambda_max` given
the same `PadDims` — asserted by `tests/test_atlas.py`.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core.graph import ComputeProblem
from repro.core.queues import VERDICT_NAMES, VERDICT_UNDECIDED
from repro.obs.spans import span, spanned
from .batching import PadDims, make_buckets, pad_problem
from .engine import (FleetJob, VerdictConfig, _policy_group_key,
                     make_group_launch, make_sim_rewriter,
                     make_stream_runner, resolve_verdict)
from .frontier import Bisection, RateProbe, fold_seed
from .report import policy_bound_exact
from .scenarios import arrival_code, event_code, get_scenario


@dataclasses.dataclass(frozen=True)
class AtlasJob:
    """One cell of the capacity atlas: a (scenario, topo_seed) instance
    whose λ_max is bisected against its own exact LP bound."""

    scenario: str
    policy: str = "pi3"
    topo_seed: int = 0
    eps_b: float = 0.01


@dataclasses.dataclass(frozen=True)
class AtlasRow:
    """One cell's finished frontier search (the atlas analog of
    `frontier.FrontierResult`, minus the per-search launch accounting
    that only makes sense sequentially)."""

    scenario: str
    policy: str
    eps_b: float
    topo_seed: int
    lam_max: float           # largest grid rate verified sustainable
    bound_exact: float       # the exact regulated LP bound of *this* cell
    ratio: float             # lam_max / bound_exact
    lo: float                # final bracket: sustainable side
    hi: float                # final bracket: unsustainable side
    n_calls: int             # probes evaluated for this cell
    n_iters: int             # bisection halvings
    undecided: bool          # hi never *proven* unstable (DESIGN.md §8):
                             # blocked by UNDECIDED-at-horizon evidence only
    hi_certain: float | None  # smallest rate with genuine UNSTABLE evidence
    total_slots: int         # simulated slots advanced across the probes
    full_slots: int          # slots a freeze-free search would have run
    slots_saved: int         # full_slots - total_slots
    probes: Tuple[RateProbe, ...]
    degraded: bool = False   # the cell's lanes sat on a dropped host: the
                             # search was cut short and (lo, hi) is the
                             # bracket *at the dropout*, not a converged
                             # localization (DESIGN.md §12)
    bucket: int = 0          # PadDims bucket the cell's lanes ran in
                             # (DESIGN.md §13); 0 in single-bucket sweeps
    n_requeues: int = 0      # adaptive-horizon escalations: each re-queue
                             # restarted the search at double the horizon
                             # with a bumped fold_seed call_index


@dataclasses.dataclass
class AtlasResult:
    """The whole atlas: per-cell rows + fleet-level launch accounting."""

    rows: List[AtlasRow]
    n_cells: int
    n_lanes: int             # (cell × seed) bisection lanes advanced
    n_programs: int          # (policy group × bucket) launch units, each
                             # its own padded-shape compiled program
    n_launches: int          # chunk-step launches the atlas dispatched
    seq_launches: int        # launches per-cell find_lambda_max would issue
    n_rewrites: int          # in-place carry rewrites at launch boundaries
    n_step_compiles: int     # step programs the launch units ran: one a
                             # unit that launched, plus each retrace inside
                             # its launch loop (== n_programs unless the
                             # loop retraced; the same in a warm process
                             # and across a resume)
    total_slots: int
    full_slots: int
    slots_saved: int
    launch_slots_saved: int  # sequential-semantics launch savings
    dims: PadDims
    T: int
    chunk: int
    stream_records: List[dict] = dataclasses.field(default_factory=list)
                             # per-launch bisection progress
                             # (sweep_lambda_max(stream=True), DESIGN.md §11)
    resumed_from: int | None = None   # checkpoint step this sweep restored
                                      # (DESIGN.md §12); None = fresh
    degraded: Dict[int, str] = dataclasses.field(default_factory=dict)
                             # cell index -> reason for cells parked by a
                             # host dropout (their rows carry degraded=True)
    recovery_plan: object | None = None   # runtime.fault.RecoveryPlan
    n_fault_retries: int = 0
    bucket_dims: List[PadDims] = dataclasses.field(default_factory=list)
                             # per-bucket padded shapes (DESIGN.md §13);
                             # [dims] for single-bucket sweeps
    bucket_cells: Dict[int, int] = dataclasses.field(default_factory=dict)
                             # bucket -> cells assigned to it
    bucket_launches: Dict[int, int] = dataclasses.field(default_factory=dict)
                             # bucket -> chunk launches dispatched in it
    n_requeues: int = 0      # total adaptive-horizon re-queues across cells

    @property
    def n_buckets(self) -> int:
        return max(len(self.bucket_dims), 1)

    @property
    def launch_speedup(self) -> float:
        """How many sequential launches one atlas launch replaced."""
        return self.seq_launches / self.n_launches if self.n_launches else 0.0


def registry_cells(families: Sequence[str], topo_seeds: Sequence[int],
                   policy: str = "pi3", eps_b: float = 0.01
                   ) -> List[AtlasJob]:
    """The (family × topo_seed) atlas grid as `AtlasJob` cells.

    Random families (random_geometric, expander, ...) vary their topology
    with ``topo_seed``; deterministic ones (paper_grid, ring, ...) reuse
    the graph but still decouple their probe streams, because every probe
    seed is `fold_seed(topo_seed, ...)` — so the grid doubles as a
    seed-replicate study on fixed topologies."""
    return [AtlasJob(scenario=f, policy=policy, topo_seed=int(ts),
                     eps_b=eps_b)
            for f in families for ts in topo_seeds]


@spanned("atlas.sweep")
def sweep_lambda_max(cells: Sequence[AtlasJob], *,
                     seeds: Sequence[int] = (0,), T: int = 4096,
                     chunk: int = 512, window: int | None = None,
                     rel_tol: float = 0.025,
                     bracket: Tuple[float, float] = (0.5, 1.1),
                     max_calls: int = 24, early_stop: bool = True,
                     verdict: VerdictConfig | None = None,
                     devices=None, dims: PadDims | None = None,
                     n_buckets: int = 1, max_requeues: int = 0,
                     stream: bool = False, stream_log=None,
                     stream_path: str | None = None,
                     resilience=None) -> AtlasResult:
    """Bisect λ_max for every atlas cell, batched: one padded chunk-step
    launch per (policy group × size bucket) advances all of its cells'
    current probes at once.

    Parameters mirror `find_lambda_max` — each cell's search is driven by
    the same `Bisection` machine on the same rel_tol-quantized grid of its
    own exact bound, with the same `fold_seed` probe streams, so per-cell
    results are bit-identical to the sequential path run with the cell's
    bucket dims (`AtlasResult.bucket_dims[row.bucket]`).
    ``early_stop=True`` (default) harvests a probe as soon as all its
    lanes latch; ``False`` reproduces full-horizon probing (every probe
    runs all ``n_chunks`` launches).

    ``n_buckets > 1`` groups the distinct topologies into quantile-based
    size buckets (`batching.make_buckets`, DESIGN.md §13): each (policy
    group × bucket) launches its own padded program, so one big expander
    no longer inflates every small ring lane.  An explicit ``dims``
    forces the single-bucket path padded to those shared dims (the
    equivalence-test hook).  Padded shapes change reduction shapes hence
    bits, so per-cell results are compared against the sequential path
    *at the same bucket dims*, never across bucketings.

    ``max_requeues > 0`` turns on adaptive per-cell horizons: a cell
    whose finished machine is still UNDECIDED-at-top (`undecided_hi` —
    the bracket top blocked by horizon-limited evidence only) *or*
    whose bracket fully collapsed (``k_lo == 0``: no rate proved
    sustainable, which at rates far below capacity is usually the
    gradient-fill transient masquerading as a proven UNSTABLE — both
    are the collapsed-bracket failure mode) restarts its whole search
    with double the chunk budget (2×T, then 4×T, ... up to
    ``max_requeues`` escalations).  Re-probes ride the same compiled program — verdict
    latching depends on the window config, not T, so a longer horizon is
    just more chunk launches — through the same `make_sim_rewriter`
    reset path, with the fold_seed ``call_index`` bumped to the attempt
    number so re-probe streams never alias first-attempt streams.

    ``stream``/``stream_log``/``stream_path`` mirror `run_fleet`: one
    "atlas"-kind record per chunk launch (DESIGN.md §11) — active/done
    cell counts, harvested probes, per-family bracket medians — assembled
    host-side from the scheduler state the loop already reads back, so
    streaming cannot perturb the bisections.  Records land in
    `AtlasResult.stream_records`; the stream clock ``t`` counts slots
    *dispatched* per lane (lane carries reset t to 0 on probe rewrites,
    so the raw carry clock is not monotone — the dispatch count is).

    ``resilience`` makes the sweep preemption-safe (DESIGN.md §12): every
    launch boundary snapshots the donated carry *and* the host scheduler —
    each cell's serialized `Bisection` machine, `RateProbe` history,
    pending (rate, seed) lane tables and the launch counters — so a killed
    sweep resumes with bit-identical brackets, rows and stream records.
    Host dropouts park the affected cells' lanes and finish their rows
    from the current bracket with ``degraded=True`` (reported in
    ``AtlasResult.degraded``) while the rest of the atlas keeps bisecting.

    With `repro.obs.spans` recording, the call is one ``atlas.sweep``
    span.  Per launch unit, ``atlas.unit_setup`` (``n``: padded lanes);
    per launch, ``atlas.launch`` (``n``: lanes of active cells),
    ``atlas.readback``, ``atlas.harvest`` (``n``: probes harvested),
    ``atlas.rewrite`` (``n``: lanes reset or parked), ``atlas.record`` and
    ``atlas.snapshot`` where they run.
    """
    cells = list(cells)
    if not cells:
        raise ValueError("empty atlas")
    stream = stream or stream_log is not None or stream_path is not None
    seeds = tuple(seeds)
    vcfg = resolve_verdict(verdict, early_stop)
    devices = list(devices or jax.devices())
    ndev = len(devices)
    mesh = Mesh(np.array(devices), ("fleet",))
    S = len(seeds)

    # --- per-cell bound, grid step, and bisection machine.  The bracket
    # arithmetic repeats find_lambda_max token-for-token so both paths
    # start from the identical integer bracket.
    bounds: List[float] = []
    steps: List[float] = []
    machines: List[Bisection] = []
    k_lo0: List[int] = []
    k_hi0: List[int] = []
    for c in cells:
        bound = policy_bound_exact(c.scenario, c.policy, c.eps_b,
                                   topo_seed=c.topo_seed)
        if bound <= 0.0:
            raise ValueError(f"{c.scenario}: exact LP bound is {bound}; "
                             "nothing to bisect")
        step = rel_tol * bound
        bounds.append(bound)
        steps.append(step)
        k_lo0.append(max(int(np.floor(bracket[0] * bound / step)), 0))
        k_hi0.append(max(int(np.ceil(bracket[1] * bound / step)), 1))
        machines.append(Bisection(k_lo=k_lo0[-1], k_hi=k_hi0[-1],
                                  max_calls=max_calls))

    # --- topologies: build each distinct one once, pad to its bucket's
    # dims.  An explicit `dims` forces one shared bucket (the equivalence
    # hook); otherwise `make_buckets` cuts quantile-based size buckets
    # (DESIGN.md §13) and each problem is padded only to its bucket hull.
    problem_of: Dict[tuple, ComputeProblem] = {}
    for c in cells:
        k = (c.scenario, c.topo_seed)
        if k not in problem_of:
            problem_of[k] = get_scenario(c.scenario).build(c.topo_seed)
    problem_keys = list(problem_of)
    if dims is not None:
        bucket_dims = [dims]
        bucket_of = {k: 0 for k in problem_keys}
    else:
        bucket_dims, assignment = make_buckets(
            [problem_of[k] for k in problem_keys], n_buckets)
        bucket_of = {k: b for k, b in zip(problem_keys, assignment)}
    dims = PadDims(
        n_nodes=max(d.n_nodes for d in bucket_dims),
        n_edges=max(d.n_edges for d in bucket_dims),
        n_comp=max(d.n_comp for d in bucket_dims),
        max_degree=max(d.places for d in bucket_dims))
    padded_of = {k: pad_problem(p, bucket_dims[bucket_of[k]])
                 for k, p in problem_of.items()}
    cell_bucket = [bucket_of[(c.scenario, c.topo_seed)] for c in cells]

    # --- launch units: policy groups (the only axis that forks traced
    # control flow) × buckets (padded shapes fork programs within one
    # group's jit cache).  Outer order is group insertion order, inner is
    # ascending bucket, so the single-bucket path enumerates units exactly
    # like the pre-bucketing group loop.
    groups: Dict[tuple, List[int]] = {}
    for ci, c in enumerate(cells):
        key = _policy_group_key(FleetJob(scenario=c.scenario,
                                         policy=c.policy, eps_b=c.eps_b,
                                         topo_seed=c.topo_seed))
        groups.setdefault(key, []).append(ci)
    units: List[Tuple[tuple, int, List[int]]] = []
    for gkey, cidx_g in groups.items():
        by_bucket: Dict[int, List[int]] = {}
        for ci in cidx_g:
            by_bucket.setdefault(cell_bucket[ci], []).append(ci)
        for b in sorted(by_bucket):
            units.append((gkey, b, by_bucket[b]))

    rt = resumed = None
    if resilience is not None:
        from repro.runtime import resilience as rz
        rt = rz.maybe_resilient(resilience, "atlas", cells=tuple(cells),
                                seeds=seeds, T=T, chunk=chunk, window=window,
                                rel_tol=rel_tol, bracket=tuple(bracket),
                                max_calls=max_calls, early_stop=early_stop,
                                verdict=vcfg, dims=tuple(bucket_dims),
                                n_buckets=n_buckets,
                                max_requeues=max_requeues, ndev=ndev)
        resumed = rt.resumed

    rows: List[AtlasRow | None] = [None] * len(cells)
    attempt: List[int] = [0] * len(cells)
    n_launches = seq_launches = n_rewrites = 0
    launch_slots_saved = 0
    n_step_compiles = 0
    n_requeues = 0
    bucket_launches: Dict[int, int] = {b: 0 for b in range(len(bucket_dims))}
    eff_T = eff_chunk = 0
    degraded: Dict[int, str] = {}
    recovery = None
    sink = None
    if stream:
        from repro.obs.emitter import StreamSink
        sink = StreamSink(path=stream_path, log=stream_log,
                          append=resumed is not None)
    if resumed is not None:
        # Host scheduler restore: every cell's machine (cells in already-
        # finished units carry their final state; unstarted ones their
        # initial state — both re-serialize identically), finished rows,
        # attempt counters, and the launch counters.
        for ci_s, ms in resumed["machines"].items():
            machines[int(ci_s)] = Bisection.from_state(ms)
        for ci_s, rs in resumed["rows"].items():
            rows[int(ci_s)] = rz.row_restore(rs)
        for ci_s, a in resumed["attempt"].items():
            attempt[int(ci_s)] = int(a)
        n_launches = resumed["n_launches"]
        seq_launches = resumed["seq_launches"]
        n_rewrites = resumed["n_rewrites"]
        launch_slots_saved = resumed["launch_slots_saved"]
        n_step_compiles = resumed["n_step_compiles"]
        n_requeues = resumed["n_requeues"]
        bucket_launches.update(
            {int(b): int(n) for b, n in resumed["bucket_launches"].items()})
        degraded = {int(k): v for k, v in resumed["degraded"].items()}
        recovery = rz.plan_restore(resumed["recovery"])

    for g, (gkey, bkt, cidx) in enumerate(units):
        cfg = FleetJob(scenario=cells[cidx[0]].scenario,
                       policy=cells[cidx[0]].policy,
                       eps_b=cells[cidx[0]].eps_b,
                       topo_seed=cells[cidx[0]].topo_seed).policy_config()
        runner = make_stream_runner(cfg, T, chunk=chunk, window=window,
                                    verdict=vcfg)
        eff_T, eff_chunk = runner.T, runner.chunk
        n_chunks = runner.n_chunks
        if resumed is not None and g < resumed["group"]:
            continue              # finished pre-kill: rows restored above

        with span("atlas.unit_setup") as sp:
            # Lane layout: S contiguous lanes per cell, mesh-padded by
            # repeating the last real lane (run_fleet's replica convention
            # — replicas mirror every rewrite of their source cell and are
            # never harvested).
            lane_cells = [ci for ci in cidx for _ in seeds]
            B = len(lane_cells)
            Bp = -(-B // ndev) * ndev
            lane_pad = lane_cells + [lane_cells[-1]] * (Bp - B)
            sp.n = Bp
            lane_of = {ci: slice(j * S, (j + 1) * S)
                       for j, ci in enumerate(cidx)}

            pp = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs),
                *[padded_of[(cells[ci].scenario, cells[ci].topo_seed)]
                  for ci in lane_pad])
            eps = jnp.array([cells[ci].eps_b for ci in lane_pad],
                            jnp.float32)
            ak = jnp.array([arrival_code(
                get_scenario(cells[ci].scenario).arrival)
                for ci in lane_pad], jnp.int32)
            ek = jnp.array([event_code(
                get_scenario(cells[ci].scenario).events)
                for ci in lane_pad], jnp.int32)

            init_fn, step_fn, _ = make_group_launch(runner, mesh)
            rewrite_fn = make_sim_rewriter(runner, mesh)

            # Host-side scheduler state: each active cell's pending grid
            # index and how many chunk launches its current probe has
            # consumed.
            pending: Dict[int, int] = {}
            chunks_used: Dict[int, int] = {}
            probes_of: Dict[int, List[RateProbe]] = {ci: [] for ci in cidx}
            lam_host = np.zeros(Bp, np.float32)
            seed_host = np.zeros(Bp, np.int32)
            active: set = set()

            def _assign(ci: int, k: int) -> None:
                # call_index = attempt: first-attempt probes replay the
                # exact sequential fold_seed stream (call_index 0); adaptive
                # re-probes draw from the documented re-probe stream so
                # doubled-horizon evidence never aliases the evidence that
                # failed to decide.
                pending[ci] = k
                chunks_used[ci] = 0
                sl = lane_of[ci]
                lam_host[sl] = np.float32(k * steps[ci])
                seed_host[sl] = [fold_seed(cells[ci].topo_seed, k,
                                           attempt[ci], s)
                                 for s in seeds]

            resume_here = resumed is not None and g == resumed["group"]
            if resume_here and resumed["g_launches"] > 0:
                # Mid-group restore: the carry at the snapshot boundary
                # plus the lane tables / pending probes exactly as the
                # killed sweep left them; machines/rows/counters were
                # restored above.
                pending = {int(k): v for k, v in resumed["pending"].items()}
                chunks_used = {int(k): v
                               for k, v in resumed["chunks_used"].items()}
                for ci_s, ps in resumed["probes"].items():
                    probes_of[int(ci_s)] = [rz.probe_restore(p) for p in ps]
                lam_host = np.array(resumed["lam_host"], np.float32)
                seed_host = np.array(resumed["seed_host"], np.int32)
                active = set(resumed["active"])
                like = jax.eval_shape(init_fn, pp)
                carry = rt.restore_carry(like, mesh)
                g_launches = resumed["g_launches"]
            else:
                carry = init_fn(pp)
                park0 = np.zeros(Bp, bool)
                for ci in cidx:
                    k = machines[ci].next_rate_index()
                    if k is None:   # degenerate budget: decided probe-free
                        rows[ci] = _finish_row(cells[ci], bounds[ci],
                                               steps[ci], machines[ci], [],
                                               bucket=bkt)
                        park0[lane_of[ci]] = True
                    else:
                        active.add(ci)
                        _assign(ci, k)
                lam_host[B:] = lam_host[B - 1]
                seed_host[B:] = seed_host[B - 1]
                park0[B:] = park0[B - 1]
                if park0.any():
                    carry = rewrite_fn(pp, jnp.zeros(Bp, bool),
                                       jnp.asarray(park0), carry)
                    n_rewrites += 1
                g_launches = 0
            if sink is not None and resume_here:
                from repro.obs import schema
                sink.write(schema.make_record(
                    "resume", group=g, chunk=g_launches,
                    t=g_launches * runner.chunk, n_sims=B, engine="atlas",
                    ckpt_step=resumed["ckpt_step"],
                    n_preloaded=sink.n_preloaded))

        first_cache = None        # the step's jit-cache size after launch 1
        while active:
            with span("atlas.launch", n=len(active) * S):
                lam = jnp.asarray(lam_host)
                keys = jax.vmap(jax.random.PRNGKey)(jnp.asarray(seed_host))
                if rt is not None:
                    try:
                        carry = rt.launch(g, n_launches, step_fn, pp, lam,
                                          eps, ak, ek, keys, carry)
                    except Exception:
                        if sink is not None:
                            sink.close()
                        raise
                else:
                    carry = step_fn(pp, lam, eps, ak, ek, keys, carry)
            if first_cache is None:
                first_cache = int(step_fn._cache_size())
            n_launches += 1
            g_launches += 1
            bucket_launches[bkt] += 1
            for ci in active:
                chunks_used[ci] += 1

            # Between-launch readout: the two [Bp] drift leaves only.
            with span("atlas.readback"):
                v_leaf, d_leaf = runner.drift_of(carry)
                verdicts = np.asarray(jax.device_get(v_leaf))
                decided_at = np.asarray(jax.device_get(d_leaf))

            with span("atlas.harvest") as sp:
                harvested = 0
                reset = np.zeros(Bp, bool)
                park = np.zeros(Bp, bool)
                changed = False
                if rt is not None:
                    dead = rt.dead_hosts(n_launches)
                    if dead:
                        # Graceful degradation: park every active cell with
                        # a lane on a dead host, finish its row from the
                        # bracket *at the dropout* (degraded=True, never
                        # silent), and re-plan the mesh.  The rest of the
                        # atlas keeps bisecting.
                        lane_dead = rz.host_lane_mask(Bp, ndev, dead)
                        per = Bp // ndev
                        for ci in sorted(active):
                            sl = lane_of[ci]
                            if lane_dead[sl].any():
                                active.discard(ci)
                                park[sl] = True
                                rows[ci] = _finish_row(
                                    cells[ci], bounds[ci], steps[ci],
                                    machines[ci], probes_of[ci],
                                    degraded=True, bucket=bkt,
                                    n_requeues=attempt[ci])
                                hosts = sorted({
                                    l // per
                                    for l in range(sl.start, sl.stop)
                                    if lane_dead[l]})
                                degraded[ci] = "host_dropout:" + ",".join(
                                    f"host{h}" for h in hosts)
                                changed = True
                        if (recovery is None
                                or set(dead) != set(recovery.evict)):
                            from repro.runtime.fault import plan_recovery
                            recovery = plan_recovery(
                                ndev, 1, [f"host{h}" for h in dead], [], 1)
                for ci in sorted(active):
                    sl = lane_of[ci]
                    v = verdicts[sl]
                    # Adaptive horizon: attempt a probes up to
                    # n_chunks << a launches — verdict latching lives in the
                    # window config, not T, so a doubled horizon is just
                    # more chunk launches of the same program.
                    horizon = n_chunks << attempt[ci]
                    finished = chunks_used[ci] >= horizon or (
                        early_stop and bool(np.all(v != VERDICT_UNDECIDED)))
                    if not finished:
                        continue
                    # --- harvest: the exact RateProbe the sequential path
                    # would have built from run_fleet's finalize metrics.
                    k = pending[ci]
                    cell_T = runner.T << attempt[ci]
                    names = tuple(VERDICT_NAMES[int(x)] for x in v)
                    sustainable = all(n == "STABLE" for n in names)
                    d_eff = np.where(v != VERDICT_UNDECIDED,
                                     decided_at[sl], cell_T)
                    saved = (int(np.sum(cell_T - d_eff)) if vcfg.freeze
                             else 0)
                    probes_of[ci].append(RateProbe(
                        rate_index=k, call_index=attempt[ci],
                        lam=k * steps[ci],
                        sustainable=sustainable, verdicts=names,
                        decided_at=tuple(int(x) for x in d_eff),
                        slots_run=S * cell_T - saved, slots_saved=saved,
                        undecided=(not sustainable
                                   and "UNSTABLE" not in names)))
                    harvested += 1
                    seq_launches += chunks_used[ci]
                    launch_slots_saved += \
                        S * (horizon - chunks_used[ci]) * runner.chunk
                    machines[ci].record(k, sustainable,
                                        probes_of[ci][-1].undecided)
                    k2 = machines[ci].next_rate_index()
                    if k2 is None and (machines[ci].undecided_hi
                                       or machines[ci].k_lo == 0) \
                            and attempt[ci] < max_requeues:
                        # Re-queue (DESIGN.md §13): either the bracket top
                        # is blocked by UNDECIDED-at-horizon evidence only,
                        # or the bracket fully collapsed (k_lo == 0: no rate
                        # proved sustainable).  The collapse case covers the
                        # low-rate false-UNSTABLE artifact — at rates far
                        # below capacity the backpressure gradient fills so
                        # slowly that the whole horizon sits inside the
                        # transient and the drift + gap tests both latch
                        # UNSTABLE (paper_grid topo_seeds 5/8/15 at T=4096
                        # read proven-UNSTABLE at 0.1x their own exact
                        # bound); genuinely-capacity-0 cells (wireless_grid)
                        # burn the re-queue ladder and still report 0, which
                        # the bench asserts.  Restart the whole search from
                        # the original integer bracket with a doubled chunk
                        # budget instead of reporting the collapsed bracket.
                        # The fresh machine replays the deterministic probe
                        # order; _assign stamps the bumped call_index into
                        # every fold_seed.
                        attempt[ci] += 1
                        n_requeues += 1
                        machines[ci] = Bisection(k_lo=k_lo0[ci],
                                                 k_hi=k_hi0[ci],
                                                 max_calls=max_calls)
                        k2 = machines[ci].next_rate_index()
                    if k2 is None:
                        active.discard(ci)
                        park[sl] = True
                        rows[ci] = _finish_row(cells[ci], bounds[ci],
                                               steps[ci], machines[ci],
                                               probes_of[ci], bucket=bkt,
                                               n_requeues=attempt[ci])
                    else:
                        reset[sl] = True
                        _assign(ci, k2)
                    changed = True
                sp.n = harvested
            if changed and active:
                # Replicas mirror the last real lane's fate so they stay
                # bit-synchronized with (or parked alongside) their source.
                # No rewrite once the group drains: nothing launches again.
                with span("atlas.rewrite", n=int((reset | park)[:B].sum())):
                    reset[B:] = reset[B - 1]
                    park[B:] = park[B - 1]
                    lam_host[B:] = lam_host[B - 1]
                    seed_host[B:] = seed_host[B - 1]
                    carry = rewrite_fn(pp, jnp.asarray(reset),
                                       jnp.asarray(park), carry)
                n_rewrites += 1
            if sink is not None:
                with span("atlas.record"):
                    sink.write(_atlas_record(
                        g, bkt, n_requeues, g_launches, runner.chunk, B,
                        cells, cidx, active, machines, steps, bounds,
                        probes_of, verdicts[:B]))

            if rt is not None and rt.should_snapshot(n_launches):
                with span("atlas.snapshot"):
                    rt.snapshot(n_launches, carry, _atlas_extra(
                        g, g_launches, n_launches, seq_launches, n_rewrites,
                        launch_slots_saved, n_step_compiles, machines, rows,
                        pending, chunks_used, probes_of, cidx, lam_host,
                        seed_host, active, degraded, recovery, attempt,
                        n_requeues, bucket_launches))
            if rt is not None:
                try:
                    rt.maybe_preempt(n_launches)
                except Exception:
                    if sink is not None:
                        sink.close()
                    raise

        if first_cache is not None:
            # The unit's program, plus every trace its launches added after
            # the first: a retrace inside the loop counts, a program that an
            # earlier sweep in this process compiled counts once, as here.
            n_step_compiles += 1 + int(step_fn._cache_size()) - first_cache

        if rt is not None and rt.should_snapshot(n_launches):
            # Unit-end marker: empty carry, cursor at the next unit's
            # start — a resume here re-enters the fresh path with the
            # restored machines re-pulling the same deterministic grid.
            with span("atlas.snapshot"):
                rt.snapshot(n_launches, (), _atlas_extra(
                    g + 1, 0, n_launches, seq_launches, n_rewrites,
                    launch_slots_saved, n_step_compiles, machines, rows,
                    {}, {}, {ci: [] for ci in cidx}, cidx, lam_host,
                    seed_host, set(), degraded, recovery, attempt,
                    n_requeues, bucket_launches))

    if sink is not None:
        sink.close()
    done_rows = [r for r in rows if r is not None]
    assert len(done_rows) == len(cells)
    n_bucket_cells: Dict[int, int] = {}
    for b in cell_bucket:
        n_bucket_cells[b] = n_bucket_cells.get(b, 0) + 1
    return AtlasResult(
        rows=done_rows, n_cells=len(cells), n_lanes=len(cells) * S,
        n_programs=len(units), n_launches=n_launches,
        seq_launches=seq_launches, n_rewrites=n_rewrites,
        n_step_compiles=n_step_compiles,
        total_slots=sum(r.total_slots for r in done_rows),
        full_slots=sum(r.full_slots for r in done_rows),
        slots_saved=sum(r.slots_saved for r in done_rows),
        launch_slots_saved=launch_slots_saved,
        dims=dims, T=eff_T, chunk=eff_chunk,
        stream_records=sink.records if sink is not None else [],
        resumed_from=(resumed["n_launches"] if resumed is not None
                      else None),
        degraded=degraded, recovery_plan=recovery,
        n_fault_retries=rt.n_retries if rt is not None else 0,
        bucket_dims=list(bucket_dims),
        bucket_cells=n_bucket_cells,
        bucket_launches=dict(bucket_launches),
        n_requeues=n_requeues)


def sweep_policy_surface(families: Sequence[str],
                         topo_seeds: Sequence[int], *,
                         policies: Sequence[str] = ("pi3", "pi3_reg",
                                                    "pi3bar"),
                         eps_b: float = 0.01, **kw) -> AtlasResult:
    """Atlas-over-policies: one sweep of (policy × family × topo_seed).

    Every policy runs the *same* grid of topologies against the same
    per-cell exact bounds, so ratio gaps between policies are pure policy
    effects — the λ_max surface the in-network placement literature
    compares on.  Policies that fork traced control flow
    (`_policy_group_key`) land in separate launch units automatically;
    policies that trace identically (pi3 vs pi3_reg) share one program
    and differ only in data.  Pivot the rows with
    `report.policy_surface_table`.  Keyword args pass through to
    `sweep_lambda_max` (seeds, T, chunk, n_buckets, max_requeues, ...).
    """
    cells = [AtlasJob(scenario=f, policy=p, topo_seed=int(ts), eps_b=eps_b)
             for p in policies for f in families for ts in topo_seeds]
    return sweep_lambda_max(cells, **kw)


def _atlas_extra(group, g_launches, n_launches, seq_launches, n_rewrites,
                 launch_slots_saved, n_step_compiles, machines, rows,
                 pending, chunks_used, probes_of, cidx, lam_host,
                 seed_host, active, degraded, recovery, attempt,
                 n_requeues, bucket_launches) -> dict:
    """JSON-serializable sweep cursor for one checkpoint (DESIGN.md §12).

    Machines, finished rows and attempt counters are global (every cell,
    so already-finished units restore without replay); the lane tables
    and pending probes are the current (group × bucket) unit's only.
    ``group`` is the unit cursor — the bucket identity is implied by the
    deterministic unit enumeration."""
    from repro.runtime import resilience as rz

    return {
        "group": group, "g_launches": g_launches,
        "n_launches": n_launches, "seq_launches": seq_launches,
        "n_rewrites": n_rewrites,
        "launch_slots_saved": launch_slots_saved,
        "n_step_compiles": n_step_compiles,
        "n_requeues": n_requeues,
        "bucket_launches": {str(b): int(n)
                            for b, n in bucket_launches.items()},
        "machines": {str(ci): m.to_state()
                     for ci, m in enumerate(machines)},
        "rows": {str(ci): rz.row_state(r)
                 for ci, r in enumerate(rows) if r is not None},
        "attempt": {str(ci): int(a) for ci, a in enumerate(attempt)},
        "pending": {str(ci): int(k) for ci, k in pending.items()},
        "chunks_used": {str(ci): int(n) for ci, n in chunks_used.items()},
        "probes": {str(ci): [rz.probe_state(p) for p in probes_of[ci]]
                   for ci in cidx},
        "lam_host": [float(x) for x in lam_host],
        "seed_host": [int(x) for x in seed_host],
        "active": sorted(int(ci) for ci in active),
        "degraded": {str(ci): v for ci, v in degraded.items()},
        "recovery": rz.plan_state(recovery),
    }


def _atlas_record(group: int, bucket: int, n_requeues: int,
                  g_launches: int, chunk: int, n_real: int,
                  cells, cidx, active, machines, steps, bounds, probes_of,
                  lane_verdicts: np.ndarray) -> dict:
    """One launch's bisection-progress record, assembled from the host
    scheduler state (DESIGN.md §11).  ``t`` is the per-lane dispatch count
    (launches × chunk): lane carries reset their slot clock on probe
    rewrites, so the carry's own t is not a usable stream clock.
    ``group`` is the (policy group × bucket) unit cursor; ``bucket`` names
    the PadDims bucket the unit runs in (DESIGN.md §13)."""
    from repro.obs import schema

    def rel(ci, k):
        return k * steps[ci] / bounds[ci]

    widths = [rel(ci, machines[ci].k_hi - machines[ci].k_lo)
              for ci in cidx]
    fams: Dict[str, dict] = {}
    for ci in cidx:
        fam = fams.setdefault(cells[ci].scenario, {"cells": 0, "done": 0,
                                                   "_lo": [], "_hi": []})
        fam["cells"] += 1
        fam["done"] += ci not in active
        fam["_lo"].append(rel(ci, machines[ci].k_lo))
        fam["_hi"].append(rel(ci, machines[ci].k_hi))
    for fam in fams.values():
        fam["lo_med"] = round(float(np.median(fam.pop("_lo"))), 4)
        fam["hi_med"] = round(float(np.median(fam.pop("_hi"))), 4)
    v = lane_verdicts.astype(int)
    return schema.make_record(
        "atlas",
        group=group, bucket=bucket, n_requeues=n_requeues,
        chunk=g_launches - 1, t=g_launches * chunk,
        n_sims=n_real,
        n_active_cells=len(active),
        n_done_cells=len(cidx) - len(active),
        n_probes=sum(len(probes_of[ci]) for ci in cidx),
        bracket_rel_width_med=round(float(np.median(widths)), 4),
        verdicts={VERDICT_NAMES[k]: int((v == k).sum())
                  for k in sorted(set(v.tolist()))},
        families=fams)


def _finish_row(cell: AtlasJob, bound: float, step: float, bis: Bisection,
                probes: Sequence[RateProbe],
                degraded: bool = False, bucket: int = 0,
                n_requeues: int = 0) -> AtlasRow:
    full = sum(p.slots_run + p.slots_saved for p in probes)
    run_slots = sum(p.slots_run for p in probes)
    return AtlasRow(
        scenario=cell.scenario, policy=cell.policy, eps_b=cell.eps_b,
        topo_seed=cell.topo_seed,
        lam_max=bis.k_lo * step, bound_exact=bound,
        ratio=bis.k_lo * step / bound,
        lo=bis.k_lo * step, hi=bis.k_hi * step,
        n_calls=len(probes), n_iters=bis.n_iters,
        undecided=bis.undecided_hi,
        hi_certain=(None if bis.k_hi_certain is None
                    else bis.k_hi_certain * step),
        total_slots=run_slots, full_slots=full,
        slots_saved=full - run_slots,
        probes=tuple(probes), degraded=degraded,
        bucket=bucket, n_requeues=n_requeues)
