"""Adaptive λ_max frontier search: bisection over early-stopped fleet runs.

The paper's headline quantity is the *maximum sustainable query rate*
λ_max.  `find_lambda_max` measures it empirically: it brackets the PR-3
exact regulated LP bound (`fleet.report.policy_bound_exact`), then bisects
the offered rate over successive `run_fleet` calls, each early-stopped by
the streaming stability verdict (DESIGN.md §8) — a rate is *sustainable*
iff every seed's sim latches STABLE.  The search contract:

  * **Grid quantization.**  Probed rates live on the fixed grid
    ``lam = k * rel_tol * bound`` (integer ``k``), so bisection from *any*
    valid initial bracket converges to the same boundary index — the
    golden-frontier invariance property.  The final bracket width is one
    grid step, i.e. λ_max is localized to ``rel_tol`` relative to the bound.
  * **Seed decoupling.**  Each probe's per-seed PRNG seeds are SplitMix64
    folds of ``(topo_seed, rate_index, call_index, seed)`` (`fold_seed`) —
    NOT the raw job seed — so two bisection steps at different rates never
    share arrival streams.  Within one search every grid index is
    evaluated at most once (memoized), always with ``call_index = 0``, so
    probes are deterministic per rate and the bracket-invariance above
    holds exactly; a driver that *re*-probes a rate for confirmation
    passes ``call_index > 0`` to draw fresh noise.
  * **Launch-only steps.**  Every probe reuses the same memoized
    `make_stream_runner`/`make_group_launch` programs (identical policy
    config, shapes, and verdict config), so after the first call each
    bisection step is launch-only — asserted via
    `FrontierResult.n_step_compiles == 1`.

Verdict aggregation is conservative: UNDECIDED (like UNSTABLE) counts as
unsustainable, so λ_max is biased *down*, never above the true frontier.
The two outcomes are *recorded* separately, though: every probe carries an
``undecided`` flag (no seed latched UNSTABLE — the probe was blocked by
horizon-limited evidence, not by a diverging queue), and the result's
``undecided`` flag marks a final bracket whose upper end was never
*proven* unstable — the honest reading is "λ_max is at least ``lo``,
localization above it is horizon-limited", not "``hi`` is infeasible".

The bisection *control flow* lives in the pure `Bisection` state machine
so the sequential driver here and the batched capacity atlas
(`fleet.atlas`, DESIGN.md §10) advance bit-identical searches: same probe
order, same budget semantics, same final bracket, given the same verdict
oracle.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

from .engine import (FleetJob, VerdictConfig, make_group_launch,
                     make_stream_runner, resolve_verdict, run_fleet)
from .report import policy_bound_exact

_M64 = (1 << 64) - 1


def _mix64(x: int) -> int:
    """SplitMix64 finalizer: a bijective avalanche on 64-bit ints."""
    x &= _M64
    x ^= x >> 30
    x = (x * 0xBF58476D1CE4E5B9) & _M64
    x ^= x >> 27
    x = (x * 0x94D049BB133111EB) & _M64
    x ^= x >> 31
    return x


def fold_seed(topo_seed: int, rate_index: int, call_index: int,
              seed: int = 0) -> int:
    """Derive one probe's PRNG seed from the bisection coordinates.

    Successive `run_fleet` calls in the bisection loop must NOT reuse the
    raw job seed: two probes at different rates would then draw the same
    uniforms, coupling their arrival streams (identical noise at every
    probed rate biases the measured frontier).  Folding
    ``(topo_seed, rate_index, call_index, seed)`` through SplitMix64
    decouples every axis while staying deterministic — `find_lambda_max`
    always probes with ``call_index = 0`` (each grid index is evaluated
    once per search), which is what makes the search invariant to the
    initial bracket; confirmation re-probes pass ``call_index > 0`` for
    fresh noise.  Returns a non-negative int31, safe for
    `jax.random.PRNGKey` via the engine's int32 path."""
    h = (0x9E3779B97F4A7C15 * (topo_seed & _M64)
         + 0xBF58476D1CE4E5B9 * (rate_index & _M64)
         + 0x94D049BB133111EB * (call_index & _M64)
         + 0xD6E8FEB86659FD93 * (seed & _M64) + 0x2545F4914F6CDD1D) & _M64
    return int(_mix64(h) & 0x7FFFFFFF)


class Bisection:
    """Pure pull-based bisection state machine for one frontier cell.

    The exact control flow `find_lambda_max` has always run — shrink the
    floor (``k_lo //= 2`` until sustainable), push the ceiling (``k_hi *= 2``
    while sustainable), then integer bisection — inverted into a state
    machine the *driver* pulls probes from: `next_rate_index()` returns the
    grid index to evaluate next (or None when the search is finished), and
    `record(k, sustainable, undecided)` feeds the verdict back.  Cached
    indices and the ``max_calls`` budget are consumed internally, so a
    driver never sees a repeat probe and the budget-exhausted pseudo-result
    (conservative: unsustainable, nothing cached) matches the sequential
    path's semantics exactly.

    This is what makes the batched capacity atlas (`fleet.atlas`,
    DESIGN.md §10) bit-equivalent to per-scenario `find_lambda_max`: both
    drive the *same* machine, only the probe evaluation is batched.

    Outcome bookkeeping is conservative-but-honest (DESIGN.md §8):
    UNDECIDED counts as unsustainable for the bracket update, but
    `undecided_hi` flags a final upper end that was never *proven*
    unstable, and `k_hi_certain` is the smallest index with genuinely
    UNSTABLE evidence (None if the search never saw one) — the widened,
    certain bracket is ``(k_lo, k_hi_certain)``.
    """

    def __init__(self, k_lo: int, k_hi: int, max_calls: int = 24):
        self.k_lo = max(int(k_lo), 0)
        self.k_hi = max(int(k_hi), self.k_lo + 1)
        self.max_calls = int(max_calls)
        self.n_evals = 0             # probes actually evaluated (the budget)
        self.n_iters = 0             # bisection halvings (excl. validation)
        # k -> (sustainable, undecided); undecided = blocked by UNDECIDED
        # seeds only, no UNSTABLE evidence.
        self.outcomes: Dict[int, Tuple[bool, bool]] = {}
        self._phase = "lo"           # lo -> hi -> mid -> done
        self._pending: Optional[int] = None
        self._mid_pending: Optional[int] = None
        self.done = False

    def _resolve(self, k: int) -> Tuple[bool, bool]:
        """evaluate(k) without launching: (resolved, sustainable)."""
        if k <= 0:
            return True, True        # lam = 0 is trivially sustainable
        if k in self.outcomes:
            return True, self.outcomes[k][0]
        if self.n_evals >= self.max_calls:
            return True, False       # budget exhausted: stay conservative
        return False, False

    def next_rate_index(self) -> Optional[int]:
        """The next grid index to probe, or None when the search is done.

        Idempotent while a probe is outstanding: repeated calls return the
        same pending index until `record` resolves it."""
        if self._pending is not None:
            return self._pending
        while not self.done:
            if self._phase == "lo":
                # shrink toward a sustainable floor
                if self.k_lo <= 0:
                    self._phase = "hi"
                    continue
                resolved, ok = self._resolve(self.k_lo)
                if not resolved:
                    self._pending = self.k_lo
                    return self.k_lo
                if ok:
                    self._phase = "hi"
                else:
                    self.k_lo //= 2
            elif self._phase == "hi":
                # a sustainable ceiling means the bracket missed: push it
                resolved, ok = self._resolve(self.k_hi)
                if not resolved:
                    self._pending = self.k_hi
                    return self.k_hi
                if ok and self.n_evals < self.max_calls:
                    self.k_lo = max(self.k_lo, self.k_hi)
                    self.k_hi *= 2
                else:
                    self._phase = "mid"
            else:
                # integer bisection: invariant of the starting bracket
                if self._mid_pending is not None:
                    # A bisection iteration that issued a probe finishes
                    # *after* the budget check it already passed — the
                    # sequential loop applies the outcome of its last
                    # in-budget probe before re-testing the loop guard.
                    mid, self._mid_pending = self._mid_pending, None
                    if self.outcomes[mid][0]:
                        self.k_lo = mid
                    else:
                        self.k_hi = mid
                    self.n_iters += 1
                    continue
                if self.k_hi - self.k_lo <= 1 or \
                        self.n_evals >= self.max_calls:
                    self.done = True
                    break
                mid = (self.k_lo + self.k_hi) // 2
                resolved, ok = self._resolve(mid)
                if not resolved:
                    self._pending = mid
                    self._mid_pending = mid
                    return mid
                if ok:
                    self.k_lo = mid
                else:
                    self.k_hi = mid
                self.n_iters += 1
        return None

    def record(self, k: int, sustainable: bool,
               undecided: bool = False) -> None:
        """Resolve the pending probe.  ``undecided`` marks a probe blocked
        only by UNDECIDED-at-horizon seeds (no UNSTABLE evidence)."""
        if k != self._pending:
            raise ValueError(f"recorded k={k} but pending probe is "
                             f"{self._pending}")
        # A decided (done) machine never has a pending probe, so a stray
        # record after convergence raises above rather than mutating state.
        self.outcomes[k] = (bool(sustainable), bool(undecided))
        self.n_evals += 1
        self._pending = None

    # -- checkpoint serialization (DESIGN.md §12) --------------------------
    # The machine is pure host state, so a JSON round-trip of these fields
    # is a *bit-exact* resume of the search: same pending probe, same memo,
    # same budget — the atlas checkpoints every cell's machine this way.

    def to_state(self) -> dict:
        return {"k_lo": self.k_lo, "k_hi": self.k_hi,
                "max_calls": self.max_calls, "n_evals": self.n_evals,
                "n_iters": self.n_iters,
                "outcomes": [[k, ok, und]
                             for k, (ok, und) in self.outcomes.items()],
                "phase": self._phase, "pending": self._pending,
                "mid_pending": self._mid_pending, "done": self.done}

    @classmethod
    def from_state(cls, state: dict) -> "Bisection":
        b = cls(1, 2)                       # placeholders, overwritten below
        b.k_lo = int(state["k_lo"])
        b.k_hi = int(state["k_hi"])
        b.max_calls = int(state["max_calls"])
        b.n_evals = int(state["n_evals"])
        b.n_iters = int(state["n_iters"])
        b.outcomes = {int(k): (bool(ok), bool(und))
                      for k, ok, und in state["outcomes"]}
        b._phase = state["phase"]
        b._pending = (None if state["pending"] is None
                      else int(state["pending"]))
        b._mid_pending = (None if state["mid_pending"] is None
                          else int(state["mid_pending"]))
        b.done = bool(state["done"])
        return b

    @property
    def undecided_hi(self) -> bool:
        """Final upper end blocked by horizon-limited (UNDECIDED) evidence
        rather than a proven UNSTABLE verdict."""
        o = self.outcomes.get(self.k_hi)
        return bool(o is not None and not o[0] and o[1])

    @property
    def k_hi_certain(self) -> Optional[int]:
        """Smallest probed index with genuinely UNSTABLE evidence — the
        honest (widened) upper bracket end when `undecided_hi`."""
        certain = [k for k, (ok, und) in self.outcomes.items()
                   if not ok and not und]
        return min(certain) if certain else None


@dataclasses.dataclass(frozen=True)
class RateProbe:
    """One evaluated rate of the frontier search."""

    rate_index: int          # grid index k (lam = k * rel_tol * bound)
    call_index: int          # how many times this rate had been probed before
    lam: float
    sustainable: bool        # all seeds latched STABLE
    verdicts: Tuple[str, ...]
    decided_at: Tuple[int, ...]
    slots_run: int           # simulated slots actually advanced
    slots_saved: int         # simulated slots the freeze skipped
    undecided: bool = False  # unsustainable only for lack of evidence: no
                             # seed latched UNSTABLE (horizon-limited)


@dataclasses.dataclass(frozen=True)
class FrontierResult:
    """Outcome of `find_lambda_max` (DESIGN.md §8)."""

    scenario: str
    policy: str
    eps_b: float
    topo_seed: int
    lam_max: float           # largest grid rate verified sustainable
    bound_exact: float       # the exact regulated LP bound it is measured against
    ratio: float             # lam_max / bound_exact
    lo: float                # final bracket: sustainable side
    hi: float                # final bracket: unsustainable side (lo + grid step)
    n_calls: int             # run_fleet launches issued
    n_iters: int             # bisection halvings (excl. bracket validation)
    total_slots: int         # simulated slots advanced across all probes
    full_slots: int          # slots a no-early-stop search would have run
    slots_saved: int         # full_slots - total_slots (per-sim freeze savings)
    launch_slots_saved: int  # chunks never dispatched once groups decided
    n_step_compiles: int     # compiled chunk-step programs used (must be 1)
    probes: Tuple[RateProbe, ...]
    undecided: bool = False  # final bracket's upper end was never *proven*
                             # unstable — blocked by UNDECIDED-at-horizon
                             # evidence only (satellite: DESIGN.md §8)
    hi_certain: float | None = None  # smallest rate with genuine UNSTABLE
                                     # evidence; None if the search saw none.
                                     # When `undecided`, the honest (widened)
                                     # bracket is (lo, hi_certain].

    @property
    def slots_saved_frac(self) -> float:
        return self.slots_saved / self.full_slots if self.full_slots else 0.0


def find_lambda_max(scenario: str, policy: str = "pi3", *,
                    eps_b: float = 0.01, topo_seed: int = 0,
                    seeds: Sequence[int] = (0, 1), T: int = 4096,
                    chunk: int = 512, window: int | None = None,
                    rel_tol: float = 0.025,
                    bracket: Tuple[float, float] = (0.5, 1.1),
                    max_calls: int = 24, early_stop: bool = True,
                    verdict: VerdictConfig | None = None,
                    devices=None, dims=None,
                    stream_log=None) -> FrontierResult:
    """Locate the empirical max sustainable rate λ_max of one (scenario,
    policy) pair by bisecting offered rate over early-stopped fleet runs.

    ``bracket`` is the initial (lo, hi) as *fractions of the exact bound*;
    it is validated first (lo must be sustainable, hi unsustainable) and
    expanded/shrunk on the quantized grid if not.  Every probe runs
    ``len(seeds)`` sims through `run_fleet(early_stop=...)`; the probe is
    sustainable iff all of them latch STABLE.  ``dims`` optionally pins the
    padded topology dims (`batching.PadDims`) — the atlas equivalence tests
    pass the atlas-wide dims here so both paths run the identical padded
    program.  ``stream_log`` taps every probe's per-chunk telemetry
    (DESIGN.md §11): it is handed to each `run_fleet` call, so records
    restart their (group, chunk, t) clocks per probe — a live progress
    feed, not one monotone stream (the atlas emits that).  See the module
    docstring for the quantization / seed-fold / launch-only contract."""
    bound = policy_bound_exact(scenario, policy, eps_b, topo_seed=topo_seed)
    if bound <= 0.0:
        raise ValueError(f"{scenario}: exact LP bound is {bound}; "
                         "nothing to bisect")
    step = rel_tol * bound
    vcfg = resolve_verdict(verdict, early_stop)
    seeds = tuple(seeds)

    probes: List[RateProbe] = []
    launch_saved = 0

    # The control flow lives in the pure Bisection machine — the identical
    # machine `fleet.atlas` advances for hundreds of cells at once — so the
    # sequential and batched searches probe the same grid indices in the
    # same order with the same budget semantics.
    bis = Bisection(
        k_lo=max(int(np.floor(bracket[0] * bound / step)), 0),
        k_hi=max(int(np.ceil(bracket[1] * bound / step)), 1),
        max_calls=max_calls)

    while (k := bis.next_rate_index()) is not None:
        # Each grid index is evaluated once per search (the machine's memo),
        # always at call_index 0 — deterministic per rate, which is what
        # makes the result invariant to the initial bracket.
        jobs = [FleetJob(scenario=scenario, policy=policy, lam=k * step,
                         eps_b=eps_b, topo_seed=topo_seed,
                         seed=fold_seed(topo_seed, k, 0, s))
                for s in seeds]
        res = run_fleet(jobs, T=T, chunk=chunk, window=window,
                        early_stop=early_stop, verdict=verdict,
                        devices=devices, dims=dims, stream_log=stream_log)
        launch_saved += res.launch_slots_saved
        names = res.verdicts()
        sustainable = all(v == "STABLE" for v in names)
        probe = RateProbe(
            rate_index=k, call_index=0, lam=k * step,
            sustainable=sustainable,
            verdicts=tuple(names),
            decided_at=tuple(int(d)
                             for d in res.column("decided_at_slot")),
            slots_run=res.n_sims * res.T - res.slots_saved,
            slots_saved=res.slots_saved,
            undecided=not sustainable and "UNSTABLE" not in names)
        probes.append(probe)
        bis.record(k, probe.sustainable, probe.undecided)

    # Each probe's engine accounting already splits n_sims * T_eff into
    # (slots_run, slots_saved); summing both sides recovers the full-run
    # denominator without re-deriving the engine's chunk rounding.
    full = sum(p.slots_run + p.slots_saved for p in probes)
    run_slots = sum(p.slots_run for p in probes)
    return FrontierResult(
        scenario=scenario, policy=policy, eps_b=eps_b, topo_seed=topo_seed,
        lam_max=bis.k_lo * step, bound_exact=bound,
        ratio=bis.k_lo * step / bound,
        lo=bis.k_lo * step, hi=bis.k_hi * step,
        n_calls=len(probes), n_iters=bis.n_iters,
        total_slots=run_slots, full_slots=full,
        slots_saved=full - run_slots,
        launch_slots_saved=launch_saved,
        n_step_compiles=_probe_step_compiles(
            scenario, policy, eps_b, topo_seed, T, chunk, window, vcfg,
            devices),
        probes=tuple(probes),
        undecided=bis.undecided_hi,
        hi_certain=(None if bis.k_hi_certain is None
                    else bis.k_hi_certain * step))


def _probe_step_compiles(scenario, policy, eps_b, topo_seed, T, chunk,
                         window, vcfg: VerdictConfig, devices) -> int:
    """How many chunk-step programs the search's launches compiled.

    `make_stream_runner`/`make_group_launch` are memoized on exactly the
    values every probe passed, so this lookup returns the *same* jitted
    step_fn the bisection used; its jit cache size is the compile count
    (`TestNoRecompilation` convention)."""
    cfg = FleetJob(scenario=scenario, policy=policy, eps_b=eps_b,
                   topo_seed=topo_seed).policy_config()
    runner = make_stream_runner(cfg, T, chunk=chunk, window=window,
                                verdict=vcfg)
    mesh = Mesh(np.array(list(devices or jax.devices())), ("fleet",))
    _, step_fn, _ = make_group_launch(runner, mesh)
    return int(step_fn._cache_size())
