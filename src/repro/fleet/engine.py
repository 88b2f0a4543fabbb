"""Sharded fleet engine: thousands of simulations as a few device launches.

Jobs = (scenario x policy x rate x seed) tuples.  The engine

  1. builds each job's topology once, pads all of them to fleet-wide maxima
     (`batching.pad_topologies`, laid out by `batching.lane_batch`), and
  2. groups jobs by `PolicyConfig` — the only axis that changes Python-level
     control flow in `slot_step`, hence the only axis that forces a separate
     compiled program.  Everything else (topology, arrival model, event
     model, rate, seed) is traced data: heterogeneous scenarios ride one
     program via padded constants and `lax.switch` over model codes.
  3. runs each group as a short Python loop (`driver.run_groups`) of
     `jit(shard_map(vmap(chunk_step)))` launches over the device mesh — each
     launch advances one chunk of the time scan with the carry *donated*
     back into the next launch (`make_group_launch`), and per-slot *online*
     metric accumulators ride the carry — no [T]-shaped trace is ever
     allocated and the fleet state exists exactly once, so horizons of
     10^6+ slots are memory-O(1).

Per-job streaming metrics: trailing-window useful rate, running mean/max
backlog, a head/tail backlog ratio and the derived stability heuristic,
plus the streaming stability *verdict* (DESIGN.md §8): anchored
Lyapunov-drift statistics in the carry latch each sim
STABLE/UNSTABLE/UNDECIDED at a chunk boundary, `early_stop=True`
bit-freezes decided sims and stops launching chunks for fully-decided
groups.  Backlog sums are Kahan-compensated, and `NetState`'s cumulative
delivery counters are compensated at the source
(`NetState.credit_delivery`), so horizons past ~10^7 delivered packets
keep exact counts in plain float32.

Regulated policies (pi2/pi3 and the explicit `pi2_reg`/`pi3_reg` aliases)
carry the regulator parameter eps_B as *per-job traced data*, and the
Markov-modulated event/arrival models (Gilbert–Elliott fading, ON-OFF
bursty arrivals) carry their chain state through the scan — neither axis
forks a compiled program.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.obs.spans import span, spanned
from repro.runtime.fault import RecoveryPlan
from repro.core.graph import ComputeProblem
from repro.core.policies import PolicyConfig, slot_step
from repro.core.queues import (DriftStats, VERDICT_NAMES, VERDICT_STABLE,
                               VERDICT_UNDECIDED, VERDICT_UNSTABLE,
                               drift_verdict_update, init_state, kahan_add)
from .batching import PadDims, PaddedProblem, pad_problem, pad_topologies
from .driver import Run, run_groups
from .scenarios import (ARRIVAL_MODELS, ARRIVAL_MODEL_ORDER, EVENT_MODELS,
                        EVENT_MODEL_ORDER, ModState, arrival_code, event_code,
                        get_scenario)


@dataclasses.dataclass(frozen=True)
class FleetJob:
    """One simulation of the sweep grid."""

    scenario: str
    policy: str = "pi3"
    lam: float = 1.0
    seed: int = 0                 # simulation randomness
    topo_seed: int = 0            # topology-generator randomness
    eps_b: float = 0.01           # regulator parameter — traced per-job data,
                                  # sweeping it does not fork compiled programs
    pairing: str = "fifo"
    threshold: float = 0.0
    fixed_node: int = 0
    backend: str = "xla"          # slot-decision backend: "xla" | "pallas"
                                  # (fused tiled kernels, DESIGN.md §7)

    def policy_config(self) -> PolicyConfig:
        return PolicyConfig(
            name=self.policy, eps_b=self.eps_b, pairing=self.pairing,
            threshold=self.threshold, fixed_node=self.fixed_node,
            wireless=get_scenario(self.scenario).wireless,
            backend=self.backend)


@dataclasses.dataclass(frozen=True)
class VerdictConfig:
    """Streaming stability-verdict parameters (DESIGN.md §8).

    The verdict is *always* computed — `DriftStats` rides the donated scan
    carry and costs a handful of scalar ops per slot — but only
    ``freeze=True`` (what `run_fleet(early_stop=True)` resolves to) makes
    it consequential: a decided sim is bit-frozen in place inside its
    still-running padded batch, and the engine stops launching chunks for
    a group once every sim in it has decided.

    A frozen dataclass so it can key the `make_stream_runner` memo cache:
    two sweeps with the same verdict parameters share compiled programs.
    """

    window: int = 0        # verdict window in slots; <= 0 -> the chunk size
    burn_in: int = 0       # slots before evidence counts; <= 0 -> 2 windows
    k_stable: int = 3      # consecutive stable windows that latch STABLE
    k_unstable: int = 3    # consecutive unstable windows that latch UNSTABLE
    drift_tol: float = 0.02   # per-slot drift threshold, x max(lam, 1)
    gap_tol: float = 0.05     # delivered-vs-offered gap threshold, x max(lam, 1)
    freeze: bool = False      # bit-freeze decided sims (early-stop semantics)


DEFAULT_VERDICT = VerdictConfig()


def resolve_verdict(verdict: VerdictConfig | None,
                    early_stop: bool) -> VerdictConfig:
    """The verdict config `run_fleet` actually runs: the default when none
    is given, with ``freeze`` forced on when early stopping is requested.
    Shared with `fleet.frontier` so cache probes key the same runner."""
    v = verdict or DEFAULT_VERDICT
    if early_stop and not v.freeze:
        v = dataclasses.replace(v, freeze=True)
    return v


class StreamStats(NamedTuple):
    """Online accumulators carried through the scan (O(1) memory).

    The backlog sums are Kahan-compensated (`c_*` carry the compensation
    term) so float32 running sums stay accurate far beyond the naive
    ~2^24-increment saturation point.  The *cumulative* delivery counters
    live in `NetState` and are compensated the same way
    (`NetState.credit_delivery`, DESIGN.md §4).
    """

    sum_queue: jax.Array          # [] running sum of total backlog
    c_queue: jax.Array            # [] Kahan compensation for sum_queue
    sum_queue_q3: jax.Array       # [] backlog sum over slots [T/2, 3T/4)
    c_q3: jax.Array
    sum_queue_q4: jax.Array       # [] backlog sum over slots [3T/4, T)
    c_q4: jax.Array
    max_queue: jax.Array          # []
    useful_at_mark: jax.Array     # [] cumulative useful count at window start

    @staticmethod
    def zero() -> "StreamStats":
        z = jnp.zeros((), jnp.float32)
        return StreamStats(z, z, z, z, z, z, z, z)


def make_stream_runner(cfg: PolicyConfig, T: int, chunk: int = 1024,
                       window: int | None = None,
                       verdict: VerdictConfig | None = None):
    """Build `run(pp, lam, eps_b, akind, ekind, key, arrivals=None) -> dict`.

    Memoized on `(cfg, T, chunk, window, verdict)` (PolicyConfig and
    VerdictConfig are frozen, hashable dataclasses): repeated calls — every
    `stream_simulate`, every `run_fleet` group with the same shape, every
    frontier bisection step — get the *same* runner object, so the
    `jax.jit` caches hanging off it (`make_group_launch`, the
    `stream_simulate` closed program) are reused instead of re-traced.

    `eps_b` is the regulator parameter as *traced per-job data* (ignored by
    unregulated policies); a `ModState` (Gilbert–Elliott link/comp chains,
    the bursty-arrival phase) rides the scan carry next to `NetState`, so
    Markov-modulated scenarios stay O(1) in memory too.

    The horizon is rounded up to a whole number of chunks; `run.T` exposes
    the effective slot count.  With `arrivals=None` the arrival process is
    generated per-slot from (key, t) — passing an explicit [T] trace is the
    reference path used by equivalence tests (the arrival modulation chain
    is bypassed; event chains still run).

    Besides `run` (a single closed program, used by `stream_simulate` and
    the explicit-arrivals path), the returned object exposes the pieces the
    fleet engine drives chunk-by-chunk from Python with a *donated* carry
    (`run_fleet`): `run.init_carry(pp)`, `run.chunk_step(pp, lam, eps_b,
    akind, ekind, key, carry)` (advances `chunk` slots; the slot index in
    the carry keeps the RNG stream and window marks global), and
    `run.finalize(lam, eps_b, carry)` (the metrics dict).  `run.n_chunks`
    is the number of chunk_step applications that make up one run.
    """
    # Normalize before the memo key: `verdict=None` and an explicit
    # DEFAULT_VERDICT must hit the same cache entry, or stream_simulate
    # (passes None) and run_fleet (passes the resolved config) would each
    # compile their own copy of an identical program.
    return _make_stream_runner(cfg, T, chunk, window,
                               verdict or DEFAULT_VERDICT)


@functools.lru_cache(maxsize=64)
def _make_stream_runner(cfg: PolicyConfig, T: int, chunk: int,
                        window: int | None, verdict: VerdictConfig):
    chunk = max(1, min(chunk, T))
    n_chunks = -(-T // chunk)
    T_eff = n_chunks * chunk
    win = T_eff // 2 if window is None else min(window, T_eff)
    win = max(win, 1)             # T==1 / window==0 would divide by zero
    mark = T_eff - win            # windowed rate baseline: end of slot mark-1
    q3_lo, q4_lo = T_eff // 2, (3 * T_eff) // 4
    vcfg = verdict
    # Verdict windows default to the chunk length so decisions land exactly
    # on the boundaries the engine's Python chunk loop can observe; the
    # burn-in skips the fill-up transient (DESIGN.md §8).
    vwin = chunk if vcfg.window <= 0 else max(1, min(vcfg.window, T_eff))
    vburn = 2 * vwin if vcfg.burn_in <= 0 else vcfg.burn_in

    arrival_branches = tuple(ARRIVAL_MODELS[k] for k in ARRIVAL_MODEL_ORDER)
    event_branches = tuple(EVENT_MODELS[k] for k in EVENT_MODEL_ORDER)

    def slot(pp, lam, eps_b, akind, ekind, key, carry, slot_arr):
        state, stats, drift, mod, t = carry
        kt = jax.random.fold_in(key, t)
        k_arr, k_ev, k_step = jax.random.split(kt, 3)
        # Named scopes (`repro.obs.spans.SLOT_SCOPES`) label the slot's
        # parts in a device trace; `slot_step` names its own sub-steps.
        with jax.named_scope("arrive"):
            if slot_arr is None:
                arr, mod2 = jax.lax.switch(akind, arrival_branches, k_arr,
                                           lam, mod)
            else:
                arr, mod2 = slot_arr, mod
        with jax.named_scope("events"):
            esc, csc, mod2 = jax.lax.switch(ekind, event_branches, pp, t,
                                            k_ev, mod2)
        new_state, m = slot_step(pp.with_capacity_scales(esc, csc), cfg,
                                 state, arr, k_step, eps_b=eps_b)
        tq = m["total_queue"]
        with jax.named_scope("stats"):
            sq, cq = kahan_add(stats.sum_queue, stats.c_queue, tq)
            s3, c3 = kahan_add(stats.sum_queue_q3, stats.c_q3,
                               tq * ((t >= q3_lo) & (t < q4_lo)))
            s4, c4 = kahan_add(stats.sum_queue_q4, stats.c_q4,
                               tq * (t >= q4_lo))
            new_stats = StreamStats(
                sum_queue=sq, c_queue=cq,
                sum_queue_q3=s3, c_q3=c3,
                sum_queue_q4=s4, c_q4=c4,
                max_queue=jnp.maximum(stats.max_queue, tq),
                useful_at_mark=jnp.where(t == mark - 1,
                                         m["delivered_useful"],
                                         stats.useful_at_mark),
            )
        with jax.named_scope("verdict"):
            new_drift = drift_verdict_update(
                drift, t, tq, m["delivered_useful"], lam,
                window=vwin, burn_in=vburn, k_stable=vcfg.k_stable,
                k_unstable=vcfg.k_unstable, drift_tol=vcfg.drift_tol,
                gap_tol=vcfg.gap_tol)
        new_carry = (new_state, new_stats, new_drift, mod2, t + 1)
        if vcfg.freeze:
            # Per-sim freeze mask: a sim whose verdict latched *before*
            # this slot passes its whole carry through bit-unchanged (t
            # included, so the RNG stream and window marks stay pinned at
            # decided_at) while the rest of the padded batch keeps
            # running.  where(False, old, new) is exactly `new`, so
            # undecided sims are bit-identical to a freeze-free run.
            with jax.named_scope("freeze"):
                frozen = drift.verdict != VERDICT_UNDECIDED
                new_carry = jax.tree_util.tree_map(
                    lambda o, n: jnp.where(frozen, o, n), carry, new_carry)
        return new_carry, None

    def init_carry(pp: PaddedProblem):
        return (init_state(pp), StreamStats.zero(), DriftStats.zero(),
                ModState.init(pp), jnp.int32(0))

    def chunk_step(pp: PaddedProblem, lam, eps_b, akind, ekind, key, carry):
        """Advance one chunk of slots.  Pure; the engine jits this with
        `donate_argnums` on `carry` so the scan carry is updated in place
        across the Python-level chunk loop (no 2x peak on the [B, N, 3, NC]
        queue state at fleet batch sizes)."""
        body = functools.partial(slot, pp, lam, eps_b, akind, ekind, key)
        carry, _ = jax.lax.scan(lambda c, x: body(c, None), carry,
                                xs=None, length=chunk)
        return carry

    def finalize(lam, eps_b, carry) -> Dict[str, jax.Array]:
        state, stats, drift, _, t = carry
        mean_q3 = stats.sum_queue_q3 / max(q4_lo - q3_lo, 1)
        mean_q4 = stats.sum_queue_q4 / max(T_eff - q4_lo, 1)
        decided = drift.verdict != VERDICT_UNDECIDED
        decided_at = jnp.where(decided, drift.decided_at,
                               T_eff).astype(jnp.float32)
        # Heuristic verdict comparing the 3rd vs 4th quarter of the run
        # (both past the fill-up transient): a stable network's backlog
        # plateaus, so the ratio stays near 1; linearly growing backlog
        # (instability) gives mean_q4/mean_q3 -> 7/5.
        stable_heur = mean_q4 <= 1.25 * mean_q3 + 5.0
        useful_rate = (state.delivered_useful - stats.useful_at_mark) / win
        # `t` is the per-sim slots-advanced counter (frozen sims pin it at
        # decided_at); dividing by it — a *runtime* value in every program
        # — keeps frozen and full-horizon runs emitting the identical
        # division op, so their mean_queue agrees bit-for-bit (a constant
        # T_eff denominator would constant-fold to a reciprocal multiply).
        mean_queue = stats.sum_queue / jnp.maximum(t.astype(jnp.float32),
                                                   1.0)
        slots_saved = jnp.zeros((), jnp.float32)
        if vcfg.freeze:
            # A frozen sim's accumulators stop at decided_at: the trailing
            # useful-rate window and the q3/q4 heuristic never complete, so
            # report the last full verdict window's (anchored) rate and let
            # the latched verdict *be* the stability flag.
            useful_rate = jnp.where(decided, drift.last_rate, useful_rate)
            stable_heur = jnp.where(decided, drift.verdict == VERDICT_STABLE,
                                    stable_heur)
            slots_saved = jnp.where(decided, T_eff - decided_at, 0.0)
        return {
            "offered": jnp.asarray(lam, jnp.float32),
            "eps_b": jnp.asarray(eps_b, jnp.float32),
            "useful_rate": useful_rate,
            "delivered": state.delivered,
            "delivered_useful": state.delivered_useful,
            "delivered_dummy": state.delivered - state.delivered_useful,
            "mean_queue": mean_queue,
            "mean_queue_mid": mean_q3,
            "mean_queue_tail": mean_q4,
            "max_queue": stats.max_queue,
            "stable": stable_heur.astype(jnp.float32),
            # Streaming verdict (DESIGN.md §8): latched drift-test outcome,
            # the slot it latched at (= T for undecided sims), and the
            # simulated slots the freeze saved (0 unless freezing is on).
            "verdict": drift.verdict.astype(jnp.float32),
            "decided_at_slot": decided_at,
            "slots_saved": slots_saved,
        }

    def run(pp: PaddedProblem, lam, eps_b, akind, ekind, key,
            arrivals: jax.Array | None = None) -> Dict[str, jax.Array]:
        carry = init_carry(pp)
        if arrivals is None:
            def chunk_body(c, _):
                return chunk_step(pp, lam, eps_b, akind, ekind, key, c), None
            carry, _ = jax.lax.scan(chunk_body, carry, xs=None,
                                    length=n_chunks)
        else:
            if arrivals.shape[0] != T_eff:
                raise ValueError(
                    f"explicit arrivals must have length {T_eff} "
                    f"(= n_chunks*chunk), got {arrivals.shape[0]}")
            # Reshape the arrival trace to [n_chunks, chunk] once, ahead of
            # the chunk scan — not per chunk.
            arr_chunks = arrivals.astype(jnp.float32).reshape(n_chunks, chunk)
            body = functools.partial(slot, pp, lam, eps_b, akind, ekind, key)
            def chunk_body(c, a):
                c, _ = jax.lax.scan(body, c, a)
                return c, None
            carry, _ = jax.lax.scan(chunk_body, carry, arr_chunks)
        return finalize(lam, eps_b, carry)

    run.T = T_eff
    run.window = win
    run.chunk = chunk
    run.n_chunks = n_chunks
    run.verdict_window = vwin
    run.verdict_burn_in = vburn
    run.init_carry = init_carry
    run.chunk_step = chunk_step
    run.finalize = finalize
    # Cheap between-chunk readout: the [B] int32 verdict leaf of the carry
    # (the only thing `run_fleet` transfers per chunk when early-stopping).
    run.verdict_of = lambda carry: carry[2].verdict
    # Atlas readout (DESIGN.md §10): the two drift leaves a bisection host
    # loop needs per launch boundary — latched verdict + the slot it
    # latched at — without running `finalize` mid-flight.
    run.drift_of = lambda carry: (carry[2].verdict, carry[2].decided_at)

    def probe(carry) -> Dict[str, jax.Array]:
        """Telemetry tap (DESIGN.md §11): the windowed-rate / backlog /
        drift leaves of the carry, as plain pytree indexing — no program,
        so tapping cannot fork the compiled chunk step.  The emitter
        differences consecutive probes into per-chunk stream records."""
        state, stats, drift, _, t = carry
        return {
            "t": t,
            "delivered_useful": state.delivered_useful,
            "sum_queue": stats.sum_queue,
            "max_queue": stats.max_queue,
            "last_rate": drift.last_rate,
            "last_drift": drift.last_drift,
            "verdict": drift.verdict,
            "decided_at": drift.decided_at,
        }

    run.probe = probe
    return run


def stream_simulate(problem: ComputeProblem, cfg: PolicyConfig, lam: float,
                    T: int, chunk: int = 1024, window: int | None = None,
                    seed: int = 0, arrivals: jax.Array | None = None,
                    arrival: str = "poisson", events: str = "static",
                    dims: PadDims | None = None) -> Dict[str, jax.Array]:
    """Single-problem streaming simulation (the fleet path without sharding).

    Memory is O(N + E) regardless of T — the reference `simulate` keeps
    O(T) traces.  Matches `simulate(...).delivered_useful[-1]` exactly for
    key-free policies (pi1/pi1'/pi3bar) given the same arrival trace.
    """
    dims = dims or PadDims.of([problem])
    pp = pad_problem(problem, dims)
    run = make_stream_runner(cfg, T, chunk=chunk, window=window)
    # `run` is memoized and `arrivals` is passed as a traced operand (None
    # is static pytree structure), so repeated calls with the same
    # (cfg, T, chunk, window) share one compiled program instead of
    # re-jitting a fresh partial per invocation.
    out = _jit_run(run)(
        pp, jnp.float32(lam), jnp.float32(cfg.eps_b), arrival_code(arrival),
        event_code(events), jax.random.PRNGKey(seed), arrivals)
    return out


@functools.lru_cache(maxsize=64)
def _jit_run(run):
    return jax.jit(run)


@dataclasses.dataclass
class FleetResult:
    jobs: List[FleetJob]
    metrics: List[Dict[str, float]]     # one dict per job, same order
    n_programs: int
    n_sims: int
    dims: PadDims
    T: int
    window: int
    memory_stats: Dict[str, float] | None = None  # XLA memory analysis of the
                                                  # largest chunk-step program
                                                  # (run_fleet(memory_stats=True))
    slots_saved: int = 0          # sum of per-sim frozen slots (early stop):
                                  # simulated slots never advanced past each
                                  # sim's decided_at_slot
    launch_slots_saved: int = 0   # device-level savings: slots in chunk
                                  # launches skipped once a whole group
                                  # decided (<= slots_saved)
    stream_records: List[dict] = dataclasses.field(default_factory=list)
                                  # per-chunk telemetry (run_fleet(stream=True),
                                  # DESIGN.md §11), schema'd by repro.obs
    resumed_from: int | None = None    # checkpoint step this run restored
                                       # (None = started fresh), DESIGN.md §12
    degraded: Dict[int, str] = dataclasses.field(default_factory=dict)
                                  # job index -> reason for jobs whose lanes
                                  # were parked by a host dropout: their
                                  # metrics reflect a truncated sim and must
                                  # not be read as converged (degraded, not
                                  # silent)
    recovery_plan: RecoveryPlan | None = None  # re-plan for the dropout
    n_fault_retries: int = 0      # transient launch failures absorbed

    def column(self, name: str) -> np.ndarray:
        return np.array([m[name] for m in self.metrics])

    def verdicts(self) -> List[str]:
        """Per-job streaming verdicts as names (DESIGN.md §8)."""
        return [VERDICT_NAMES[int(m["verdict"])] for m in self.metrics]


@functools.lru_cache(maxsize=64)
def make_group_launch(runner, mesh: Mesh, n_step_args: int = 7):
    """Jit the three per-group programs of the chunked fleet launch.

    Returns `(init_fn, step_fn, fin_fn)`, each a
    `jax.jit(shard_map(vmap(...)))` over the `"fleet"` mesh axis.  `step_fn`
    donates its carry argument — the *last* of the `n_step_args` chunk-step
    arguments (7 for the fleet runner, 6 for the serving runner, which has
    no arrival-model switch code): across the Python-level chunk loop the
    [B, N, 3, NC] queue state is updated in place instead of being
    double-buffered — the memory audit that matters once B·N·NC grows past
    cache sizes.  Donation is asserted by `tests/test_fleet.py::TestDonation`.

    Memoized on `(runner, mesh, n_step_args)` (runners are themselves
    memoized, Mesh is hashable): two sweeps over the same policy group
    reuse the compiled programs instead of re-tracing, and within one sweep
    the chunk loop is guaranteed a single compilation
    (`tests/test_fleet.py::TestNoRecompilation`)."""
    init_fn = _lane_jit(runner.init_carry, mesh, 1)
    step_fn = _lane_jit(runner.chunk_step, mesh, n_step_args,
                        donate_argnums=(n_step_args - 1,))
    fin_fn = _lane_jit(runner.finalize, mesh, 3)
    return init_fn, step_fn, fin_fn


def _lane_jit(fn, mesh: Mesh, n_in: int, **jit_kw):
    """`jax.jit(shard_map(vmap(fn)))` over the `"fleet"` lane axis.

    Outputs are declared lane-sharded.  Left to itself, a jit over a
    one-device mesh reports its outputs as replicated (`P()`), which no
    longer matches a carry restored as `P("fleet")` from a checkpoint —
    and a different input sharding is a second compiled program."""
    return jax.jit(
        jax.shard_map(jax.vmap(fn), mesh=mesh,
                      in_specs=(P("fleet"),) * n_in, out_specs=P("fleet"),
                      check_vma=False),        # scan carries: no vma rule
        out_shardings=NamedSharding(mesh, P("fleet")), **jit_kw)


@functools.lru_cache(maxsize=64)
def make_sim_rewriter(runner, mesh: Mesh):
    """Jit the per-sim carry *rewrite* of the capacity atlas (DESIGN.md §10).

    Returns ``rewrite_fn(pp, reset, park, carry) -> carry``, a
    `jax.jit(shard_map(vmap(...)))` over the same `"fleet"` mesh axis as
    `make_group_launch`, with the carry donated like the chunk step.  Two
    [B] bool masks drive it at a launch boundary:

      * ``reset`` — the lane starts its cell's *next* bisection probe:
        its whole carry is replaced by a fresh `init_carry(pp)` (t = 0
        included, so the RNG stream restarts under the new fold_seed key
        the host passes to the next launch).  `where(False, fresh, old)`
        is exactly ``old``, so untouched lanes are bit-identical to a
        rewrite-free run — the atlas-vs-sequential equivalence hinge.
      * ``park`` — the lane's cell finished its whole search: the verdict
        leaf is forced to UNSTABLE so the freeze mask pins the carry
        bit-exactly for every remaining launch (a no-op unless the runner
        freezes, i.e. `early_stop=True` semantics).

    Memoized on `(runner, mesh)` like the launch programs: one compiled
    rewrite per policy group, asserted by the atlas single-compile test."""
    def rewrite(pp, reset, park, carry):
        fresh = runner.init_carry(pp)
        state, stats, drift, mod, t = jax.tree_util.tree_map(
            lambda f, o: jnp.where(reset, f, o), fresh, carry)
        drift = drift._replace(verdict=jnp.where(
            park, jnp.int32(VERDICT_UNSTABLE), drift.verdict))
        return (state, stats, drift, mod, t)

    return _lane_jit(rewrite, mesh, 4, donate_argnums=(3,))


def _memory_analysis(step_fn, args) -> Dict[str, float] | None:
    """XLA memory analysis of a compiled chunk-step (peak/live byte sizes).

    A host backend that reports no analysis gives None; on a TPU a missing
    analysis raises, and so does any failure to lower or compile."""
    ma = step_fn.lower(*args).compile().memory_analysis()
    if ma is None:
        if jax.default_backend() == "tpu":
            raise RuntimeError("the TPU backend gave no memory analysis of "
                               "the chunk-step program")
        return None
    # The output carry is donated onto the input carry (aliased), so
    # counting argument + output + temp would double-count the fleet
    # state; peak live memory of a launch is arguments + temporaries.
    return {
        "argument_bytes": float(ma.argument_size_in_bytes),
        "output_bytes": float(ma.output_size_in_bytes),
        "temp_bytes": float(ma.temp_size_in_bytes),
        "peak_bytes": float(ma.argument_size_in_bytes
                            + ma.temp_size_in_bytes),
    }


def _policy_group_key(job: FleetJob):
    """Axes that change Python-level control flow => separate XLA program.

    Deliberately *semantic*, not the policy name: pi3 and pi3_reg trace to
    identical programs (both regulated, load-balancing), and eps_b is traced
    per-job data — so a sweep over regulator parameters, or over plain and
    ``_reg``-aliased variants, still compiles once per behavior."""
    cfg = job.policy_config()
    return (cfg.use_regulator, cfg.load_balance, cfg.thresholded,
            cfg.pairing, cfg.threshold, cfg.fixed_node, cfg.wireless,
            cfg.backend)


@spanned("fleet.job")
def run_fleet(jobs: Sequence[FleetJob], T: int, chunk: int = 1024,
              window: int | None = None, devices=None,
              dims: PadDims | None = None,
              memory_stats: bool = False,
              early_stop: bool = False,
              verdict: VerdictConfig | None = None,
              stream: bool = False,
              stream_log=None,
              stream_path: str | None = None,
              resilience=None) -> FleetResult:
    """Run the whole sweep, one compiled program set per policy group.

    Each group runs as a Python-level loop (`driver.run_groups`) of
    `n_chunks` launches of one `jit(shard_map(vmap(chunk_step)))` with
    the scan carry *donated*
    between launches (`make_group_launch`), so arbitrarily long horizons
    keep a single in-place copy of the fleet state.  `memory_stats=True`
    additionally attaches the XLA memory analysis of the largest group's
    chunk-step program to the result (one extra lowering, so opt-in).

    ``early_stop=True`` turns the streaming stability verdict
    (DESIGN.md §8) into an actual early exit: decided sims are bit-frozen
    inside their still-running padded batch (``VerdictConfig.freeze``),
    the [B] verdict leaf is read back between chunk launches, and a group
    stops launching chunks as soon as *every* sim in it has decided.
    Per-sim savings land in each row's ``slots_saved`` (simulated slots
    never advanced past ``decided_at_slot``); launch-level savings — the
    chunks that were never dispatched — in ``FleetResult.launch_slots_saved``.

    ``stream=True`` (implied by ``stream_log``/``stream_path``) turns on
    the telemetry plane (DESIGN.md §11): after every chunk launch the
    engine dispatches the carry's probe leaves through the io_callback
    emitter — a separate tiny program, so the chunk step is byte-identical
    to a telemetry-off run and all metrics stay bit-equal.  Records land
    in ``FleetResult.stream_records``; ``stream_path`` additionally
    appends them live as JSONL (tail with ``capacity_report --follow``)
    and ``stream_log`` is called per record *on the callback thread*.

    ``resilience`` (a `runtime.resilience.ResilienceConfig`) makes the run
    preemption-safe (DESIGN.md §12): the donated carry + host cursor are
    snapshotted at chunk boundaries (before the next launch donates the
    buffers), a killed run resumes bit-exact from the newest intact
    checkpoint, injected launch failures retry with bounded backoff, and
    host dropouts park the dead lanes via `make_sim_rewriter` — surfaced
    in ``FleetResult.degraded``/``recovery_plan`` rather than aborting.

    With `repro.obs.spans` recording, the call is one ``fleet.job`` span
    holding ``fleet.build``, and per group ``fleet.group_setup``, one
    ``fleet.launch`` per chunk launch, ``fleet.readout`` per early-stop
    verdict readback and ``fleet.finalize``; ``fleet.snapshot`` per
    checkpoint written.
    """
    jobs = list(jobs)
    vcfg = resolve_verdict(verdict, early_stop)
    with span("fleet.build"):
        padded_of, _, (dims,) = pad_topologies(jobs, dims)
    groups: Dict[tuple, List[int]] = {}
    for i, job in enumerate(jobs):
        groups.setdefault(_policy_group_key(job), []).append(i)

    with Run("fleet", devices, resilience, stream, stream_log, stream_path,
             jobs=tuple(jobs), T=T, chunk=chunk, window=window,
             verdict=vcfg, early_stop=early_stop, dims=dims) as run:
        totals = {"launch_saved": (run.resumed["launch_saved"]
                                   if run.resumed is not None else 0)}
        metrics, eff_T, eff_win, mem = run_groups(
            run, jobs, list(groups.values()), padded_of,
            make_runner=lambda job: make_stream_runner(
                job.policy_config(), T, chunk=chunk, window=window,
                verdict=vcfg),
            make_launch=make_group_launch,
            probe=lambda runner, mesh: runner.probe,
            park=make_sim_rewriter,
            early_stop=early_stop,
            memory=_memory_analysis if memory_stats else None,
            totals=totals)
    return FleetResult(jobs=jobs, metrics=metrics, n_programs=len(groups),
                       n_sims=len(jobs), dims=dims, T=eff_T, window=eff_win,
                       memory_stats=mem,
                       slots_saved=int(sum(m["slots_saved"]
                                           for m in metrics)),
                       launch_slots_saved=totals["launch_saved"],
                       stream_records=run.records,
                       resumed_from=run.resumed_from,
                       degraded=run.degraded, recovery_plan=run.recovery,
                       n_fault_retries=run.n_retries)
