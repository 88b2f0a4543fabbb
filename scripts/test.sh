#!/usr/bin/env bash
# Tier-1 test entry point with a deterministic host configuration:
#   - 8 fake host-platform devices so the fleet engine's shard_map path and
#     the fleet_smoke-marked tests exercise a real (emulated) mesh in CI;
#   - x64 opt-in via JAX_ENABLE_X64=1 (useful for LP/capacity comparisons;
#     NOT the default because the simulator's float32 scan carries — and the
#     kernels' dtype assertions — are written for the f32 world and ~40 seed
#     tests fail under forced f64);
#   - src on PYTHONPATH (the repo is also pip-installable: pip install -e .[dev]);
#   - a lint gate (ruff check + ruff format --check, config in
#     pyproject.toml) — skipped with a notice when ruff is not installed
#     (the CI workflow installs it via the dev extras);
#   - a docs gate (scripts/check_docs.py): dangling DESIGN.md/README.md
#     section references fail CI, and the README cookbook snippets run
#     under doctest;
#   (lint + docs together are the "fast gates"; CI runs them in a
#   dedicated ~1 min lint job and sets CI_FAST_GATES_DONE=1 so the long
#   test job doesn't repeat them — locally they always run);
#   - a one-job regulated fleet smoke: pi3_reg under Gilbert–Elliott fading
#     must run end-to-end and deliver useful packets;
#   - a frontier smoke: find_lambda_max (early-stopped adaptive bisection,
#     DESIGN.md §8) must bracket the paper grid's exact LP bound from
#     below, launch-only after the first probe, and save slots;
#   - the Pallas parity stanza: the fused slot-kernel suite (marker
#     `pallas`) re-run under JAX_PLATFORMS=cpu interpret mode, plus the
#     kernel micro-bench gate (BENCH_kernels.json vs the committed
#     BENCH_kernels_baseline.json, DESIGN.md §7);
#   - a serving smoke: bursty queries through the admission gate
#     (DESIGN.md §9) must deliver >= 0.9x the exact LP bound with the
#     gate open and nothing shed;
#   - a resume smoke: a fault-injected SIGTERM mid-atlas, then a resume
#     from CKPT_resume_smoke/ that must be bit-exact (identical rows and
#     launch accounting, zero extra step compiles) with a validating
#     spliced stream (scripts/check_stream.py --resumed, DESIGN.md §12);
#   - the bench gate: benchmarks/bench_fleet.py --preset smoke emits
#     BENCH_fleet.json (incl. the xla-vs-pallas backend section and the
#     frontier lam_max section) and scripts/check_bench.py fails on >25%
#     us/sim regression vs the committed BENCH_baseline.json, any
#     efficiency gate breach (DESIGN.md §6), any xla/pallas parity diff,
#     a frontier ratio outside [0.90, 1.0], <30% early-stop savings
#     (DESIGN.md §8), or >5% checkpoint-on us/sim overhead in the
#     resilience section (DESIGN.md §12);
#   - the serving bench gate: benchmarks/bench_serving.py emits
#     BENCH_serving.json + SERVING_stream.jsonl and scripts/check_bench.py
#     --mode serving gates delivered-QPS/bound, shedding, p99 sojourn,
#     overload behavior, and serving-path xla/pallas parity against the
#     committed baseline's "serving" section (DESIGN.md §9);
#   - an atlas smoke + bench gate: the batched fleet-of-bisections
#     (DESIGN.md §10) must advance the registry grid in <= 2 compiled
#     programs (and, re-run with 2 shape buckets, in <= 2 programs per
#     (policy x bucket) with a consistent per-bucket launch ledger,
#     DESIGN.md §13) and surface UNDECIDED at a too-short horizon, and
#     benchmarks/bench_atlas.py --preset ci emits BENCH_atlas_new.json —
#     lambda_max bisections vs their exact LP bounds, in shape buckets
#     with adaptive re-queues, subsampled from the committed full
#     preset's >= 500 (scenario x topo_seed) cells x 3 seeds — gated by
#     scripts/check_bench.py --mode atlas against the committed
#     BENCH_atlas.json (ratio medians + seed-band widths, launch budgets
#     total and per bucket, single-compile per program, preset-scaled
#     floors);
#   - the stream schema gate (scripts/check_stream.py): every
#     *_stream.jsonl the benches emitted (DESIGN.md §11) must validate
#     against the versioned repro.obs.schema — blessed digest, exact
#     key/type tables, monotone per-(kind, group) clocks.
#
# Every bench gate honors the same soft-skip convention as the lint
# gate: when its committed baseline JSON is missing (a pruned checkout
# or a fresh fork that hasn't blessed baselines yet), the stanza prints
# a notice and moves on instead of hard-failing.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_ENABLE_X64="${JAX_ENABLE_X64:-0}"
export XLA_FLAGS="--xla_force_host_platform_device_count=8 ${XLA_FLAGS:-}"
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# Fast gates (lint + docs).  CI's split lint job runs exactly these and
# sets CI_FAST_GATES_DONE=1 in the long test job so they aren't repeated;
# locally the variable is unset and they always run.
if [[ "${CI_FAST_GATES_DONE:-0}" != "1" ]]; then
    # Lint gate: hard-fail on violations when ruff is available, soft-skip
    # otherwise (hermetic containers without the dev extras).
    if python -m ruff --version >/dev/null 2>&1; then
        python -m ruff check .
        python -m ruff format --check .
    elif command -v ruff >/dev/null 2>&1; then
        ruff check .
        ruff format --check .
    else
        echo "test.sh: ruff not installed; skipping lint gate (pip install -e .[dev])"
    fi

    python scripts/check_docs.py
else
    echo "test.sh: CI_FAST_GATES_DONE=1; lint + docs gates ran in the lint job"
fi

# The pallas parity suite is excluded here and run once in its dedicated
# JAX_PLATFORMS=cpu stanza below (same tests, explicit platform pin).
python -m pytest -x -q -m "not pallas" "$@"

# fleet_smoke: one regulated job under Markov (Gilbert–Elliott) link fading
# through the full sharded engine path.
python - <<'PY'
from repro.fleet import FleetJob, run_fleet

res = run_fleet([FleetJob(scenario="ge_grid", policy="pi3_reg", lam=4.0,
                          eps_b=0.05, seed=0)], T=512, chunk=128)
m = res.metrics[0]
assert res.n_programs == 1
assert m["delivered_useful"] > 0.0, m
assert m["useful_rate"] >= 0.0 and abs(m["eps_b"] - 0.05) < 1e-6, m
print(f"fleet_smoke: pi3_reg/ge_grid useful_rate={m['useful_rate']:.3f} "
      f"dummy={m['delivered_dummy']:.1f} ok")
PY

# frontier_smoke: adaptive lam_max search (early-stopped bisection,
# DESIGN.md §8) end-to-end on the paper grid — must stay below the exact
# LP bound, reuse one compiled chunk-step program across probes, and
# actually save slots.  (The strict [0.90, 1.0] ratio band is gated on
# the longer-horizon bench section below.)
python - <<'PY2'
from repro.fleet import find_lambda_max

r = find_lambda_max("paper_grid", "pi3", eps_b=0.05, seeds=(0,),
                    T=2048, chunk=256, rel_tol=0.05)
assert 0.0 < r.lam_max <= r.bound_exact * (1 + 1e-9), (r.lam_max,
                                                       r.bound_exact)
assert r.n_step_compiles == 1, r.n_step_compiles
assert r.slots_saved > 0 and r.launch_slots_saved > 0, r
print(f"frontier_smoke: lam_max={r.lam_max:.2f} / bound={r.bound_exact:.2f}"
      f" (ratio {r.ratio:.3f}, {r.n_calls} probes, "
      f"{100 * r.slots_saved_frac:.0f}% slots saved) ok")
PY2

# atlas_smoke: the batched fleet-of-bisections scheduler (DESIGN.md §10)
# end-to-end across the registry grid at a tiny horizon: 18 (scenario x
# topo_seed) cells advance in <= 2 compiled programs (wireless_grid forks
# the second), one step compile each, far fewer launches than per-cell
# searches.  T=512 cannot latch any verdict (burn-in + 2 windows > T),
# so every cell must surface UNDECIDED — collapsed bracket, no certain
# instability — rather than a false UNSTABLE (DESIGN.md §8/§10).
python - <<'PY4'
from repro.fleet import registry_cells, sweep_lambda_max

cells = registry_cells(
    ("paper_grid", "random_geometric", "ring", "tree", "expander",
     "fat_tree", "wireless_grid", "ge_grid", "ge_comp_grid"),
    topo_seeds=(0, 1), eps_b=0.05)
res = sweep_lambda_max(cells, seeds=(0,), T=512, chunk=256,
                       rel_tol=0.1, max_calls=6)
assert len(res.rows) == res.n_cells == len(cells) == 18
assert res.n_programs <= 2 and res.n_step_compiles == res.n_programs, res
assert res.launch_speedup > 1.0, res.launch_speedup
assert all(r.undecided and r.hi_certain is None and r.lam_max == 0.0
           for r in res.rows), "short horizon must read UNDECIDED"
print(f"atlas_smoke: {res.n_cells} cells in {res.n_launches} launches "
      f"(seq {res.seq_launches}, x{res.launch_speedup:.1f}) "
      f"programs={res.n_programs} all-UNDECIDED ok")

# Bucketed re-run (DESIGN.md §13): 2 shape buckets must stay <= 2
# compiled programs per (policy group x bucket), with a per-bucket launch
# ledger that sums to the total.  n_step_compiles counts one program a
# launch unit plus every retrace inside a unit's launch loop, so the
# no-retrace invariant holds whatever the single-bucket run above left in
# the jit caches.
res2 = sweep_lambda_max(cells, seeds=(0,), T=512, chunk=256,
                        rel_tol=0.1, max_calls=6, n_buckets=2)
assert res2.n_buckets == 2, res2.n_buckets
assert res2.n_programs <= 2 * res2.n_buckets, res2.n_programs
assert res2.n_step_compiles - res.n_step_compiles <= res2.n_programs, \
    (res.n_step_compiles, res2.n_step_compiles, res2.n_programs)
assert sum(res2.bucket_launches.values()) == res2.n_launches, res2
assert all(r.undecided and r.lam_max == 0.0 for r in res2.rows)
print(f"atlas_smoke: bucketed {res2.n_buckets} buckets "
      f"launches={dict(sorted(res2.bucket_launches.items()))} "
      f"programs={res2.n_programs} (<= {2 * res2.n_buckets}) ok")
PY4

# serving_smoke: bursty query traffic through the admission gate into the
# backpressure network (DESIGN.md §9) — at 0.95x the exact LP bound the
# gate must stay open (no shedding, no flips) and deliver >= 0.9x bound.
python - <<'PY3'
from repro.fleet import policy_bound_exact
from repro.serving import ServingJob, run_serving

bound = policy_bound_exact("paper_grid", "pi3_reg", 0.05)
jobs = [ServingJob(trace="bursty", lam=0.95 * bound, seed=s)
        for s in (0, 1)]
res = run_serving(jobs, T=2048, chunk=256)
for m in res.metrics:
    assert m["shed_frac"] == 0.0 and m["gate_flips"] == 0.0, m
    assert m["delivered_qps"] >= 0.9 * bound, m
    assert 0.0 < m["p99_sojourn"] <= 512.0, m
qps = [m["delivered_qps"] for m in res.metrics]
print(f"serving_smoke: pi3_reg/bursty qps={min(qps):.2f}..{max(qps):.2f} "
      f"vs bound={bound:.1f} (gate open, 0 shed) ok")
PY3

# resume_smoke: the preemption-safety contract (DESIGN.md §12) end-to-end
# in one process — a mid-atlas SIGTERM (FaultPlane.preempt_after) lands a
# durable snapshot, and the resumed sweep must reproduce the uninterrupted
# run bit-for-bit: identical rows (brackets, verdicts, λ_max), identical
# launch accounting, and ZERO extra step compiles (the memoized launch
# builders hand the resume its already-compiled programs).  The spliced
# stream must carry the resume seam and still validate with it stripped.
rm -rf CKPT_resume_smoke RESUME_stream.jsonl
python - <<'PY5'
from repro.fleet import registry_cells, sweep_lambda_max
from repro.runtime.fault import FaultPlane, Preempted
from repro.runtime.resilience import ResilienceConfig

cells = registry_cells(("paper_grid", "ring"), topo_seeds=(0,), eps_b=0.05)
kw = dict(seeds=(0, 1), T=512, chunk=256, rel_tol=0.1, max_calls=4)
base = sweep_lambda_max(cells, **kw)

kill = ResilienceConfig(checkpoint_dir="CKPT_resume_smoke",
                        fault_plane=FaultPlane.preempt_after(3))
try:
    sweep_lambda_max(cells, **kw, resilience=kill,
                     stream_path="RESUME_stream.jsonl")
    raise SystemExit("resume_smoke: expected Preempted")
except Preempted:
    pass

res = sweep_lambda_max(cells, **kw, stream_path="RESUME_stream.jsonl",
                       resilience=ResilienceConfig(
                           checkpoint_dir="CKPT_resume_smoke"))
assert res.resumed_from == 3, res.resumed_from
assert res.rows == base.rows, "resume is not bit-exact"
assert res.n_launches == base.n_launches, (res.n_launches, base.n_launches)
assert res.n_step_compiles == base.n_step_compiles, \
    (res.n_step_compiles, base.n_step_compiles)
print(f"resume_smoke: killed at launch 3/{base.n_launches}, resumed "
      f"bit-exact ({res.n_cells} cells, {res.n_step_compiles} step "
      f"compiles, 0 extra) ok")
PY5
python scripts/check_stream.py --resumed RESUME_stream.jsonl

# Pallas parity suite, re-run under an explicit CPU platform pin: the
# fused slot kernels (DESIGN.md §7) must be bit-identical to the XLA
# oracle in interpret mode — the exact configuration CI runs them in.
JAX_PLATFORMS=cpu python -m pytest -q -m pallas tests/

# Kernel micro-bench gate: fused bp_slot decide vs reference at fleet pad
# dims -> BENCH_kernels.json, regression-checked against the committed
# baseline.  Micro-kernel timings vary more across hosts than the fleet
# sweep, so the kernel gate gets a 2x allowance (exact-match assertions
# inside the bench are unconditional).
if [[ -f BENCH_kernels_baseline.json ]]; then
    python benchmarks/bench_kernels.py --out BENCH_kernels.json
    CHECK_BENCH_MAX_REGRESSION="${CHECK_BENCH_MAX_REGRESSION:-2.0}" \
        python scripts/check_bench.py BENCH_kernels.json BENCH_kernels_baseline.json
else
    echo "test.sh: BENCH_kernels_baseline.json missing; skipping kernel bench gate"
fi

# Bench gate: smoke sweep -> BENCH_fleet.json (incl. the xla-vs-pallas
# backend comparison section) + FLEET_stream.jsonl chunk-boundary
# telemetry, regression-checked against the committed baseline.
if [[ -f BENCH_baseline.json ]]; then
    python benchmarks/bench_fleet.py --preset smoke --out BENCH_fleet.json \
        --stream-out FLEET_stream.jsonl
    python scripts/check_bench.py --mode fleet BENCH_fleet.json BENCH_baseline.json
else
    echo "test.sh: BENCH_baseline.json missing; skipping fleet bench gate"
fi

# Serving bench gate: trace-driven admission-control smoke (DESIGN.md §9)
# -> BENCH_serving.json + per-chunk stream records, gated against the
# committed baseline's "serving" section.
if [[ -f BENCH_baseline.json ]]; then
    python benchmarks/bench_serving.py --out BENCH_serving.json \
        --stream-out SERVING_stream.jsonl
    python scripts/check_bench.py --mode serving BENCH_serving.json BENCH_baseline.json
else
    echo "test.sh: BENCH_baseline.json missing; skipping serving bench gate"
fi

# Atlas bench gate: the registry-wide capacity surface (DESIGN.md §10/§13)
# at the ci preset — the same families, horizon, shape buckets, adaptive
# re-queue rung and seed-band math as the committed 504-cell full preset,
# subsampled to 12 topo_seeds x 2 seeds so the live re-run fits the CI
# budget -> BENCH_atlas_new.json + ATLAS_stream.jsonl launch-clock
# telemetry, gated by check_bench --mode atlas (unfaded-family ratio
# medians in [0.90, 1.0], band widths <= 0.2, one step compile per
# (policy group x bucket) program, per-bucket launch ledger + budgets,
# batching speedup; preset-scaled floors from bench_atlas.ATLAS_GATES).
# The committed BENCH_atlas.json stays full-preset — regenerate it with
# `python benchmarks/bench_atlas.py --preset full --out BENCH_atlas.json`
# (~35 CPU-min).
if [[ -f BENCH_atlas.json ]]; then
    python benchmarks/bench_atlas.py --preset ci --out BENCH_atlas_new.json \
        --stream-out ATLAS_stream.jsonl
    python scripts/check_bench.py --mode atlas BENCH_atlas_new.json BENCH_atlas.json
else
    echo "test.sh: BENCH_atlas.json missing; skipping atlas bench gate"
fi

# Stream schema gate (DESIGN.md §11): whatever *_stream.jsonl files the
# bench stanzas above emitted must validate against the versioned
# repro.obs.schema — no args means glob-and-soft-pass, so skipped
# benches don't turn into missing-file failures here.
python scripts/check_stream.py
