"""Resilience-plane tests (DESIGN.md §12): hardened checkpointing (atomic
publish, per-leaf sha256, corrupt-step fallback), the injectable fault
plane (launch failures -> bounded retry, preemption -> durable snapshot,
host dropout -> graceful lane degradation), and the headline contract —
kill-and-resume at any chunk boundary reproduces the uninterrupted run
bit-exactly (metrics, lambda_max brackets, slot accounting, stream
records) for all three engines."""
import json
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from repro.checkpoint import Checkpointer, CheckpointCorruption
from repro.fleet import (FleetJob, registry_cells, run_fleet,
                         sweep_lambda_max)
from repro.obs import schema
from repro.obs.emitter import StreamSink
from repro.runtime.fault import (FaultExhausted, FaultPlane, InjectedFault,
                                 Preempted)
from repro.runtime.resilience import (ResilienceConfig, host_lane_mask,
                                      maybe_resilient, run_signature)
from repro.serving import ServingJob, run_serving

REPO = pathlib.Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# Checkpointer hardening: atomic publish, checksums, corruption fallback
# ---------------------------------------------------------------------------

def _state(seed):
    rng = np.random.default_rng(seed)
    return {"a": rng.standard_normal((4, 3)).astype(np.float32),
            "t": np.int32(seed)}


class TestCheckpointer:
    def test_save_restore_with_extra_payload(self, tmp_path):
        ck = Checkpointer(tmp_path, keep=2)
        st = _state(1)
        ck.save(1, st, extra={"group": 0, "launched": 3, "pi": 0.25})
        out = ck.restore(st)
        np.testing.assert_array_equal(out["a"], st["a"])
        assert out["t"] == st["t"]
        assert ck.extra(1) == {"group": 0, "launched": 3, "pi": 0.25}
        # atomic publish: no tmp dirs survive a completed save
        assert not list(tmp_path.glob(".tmp_*"))

    def test_background_save_then_wait(self, tmp_path):
        ck = Checkpointer(tmp_path)
        ck.save(1, _state(1), blocking=False)
        ck.wait()
        assert ck.all_steps() == [1]
        np.testing.assert_array_equal(ck.restore(_state(0))["a"],
                                      _state(1)["a"])

    def test_corruption_detected_and_fallback(self, tmp_path):
        ck = Checkpointer(tmp_path, keep=3)
        ck.save(1, _state(1), extra={"step": 1})
        ck.save(2, _state(2), extra={"step": 2})
        # torn write / bit rot in the newest step's array payload
        arr = tmp_path / "step_00000002" / "arr_0.npy"
        raw = bytearray(arr.read_bytes())
        raw[-1] ^= 0xFF
        arr.write_bytes(bytes(raw))
        with pytest.raises(CheckpointCorruption, match="sha256"):
            ck.restore(_state(0))
        # fallback walks back to the newest *intact* step: one snapshot
        # interval lost, never the run
        assert ck.restored_step(fallback=True) == 1
        out = ck.restore(_state(0), fallback=True)
        np.testing.assert_array_equal(out["a"], _state(1)["a"])

    def test_unreadable_manifest_falls_back(self, tmp_path):
        ck = Checkpointer(tmp_path)
        ck.save(1, _state(1))
        ck.save(2, _state(2))
        (tmp_path / "step_00000002" / "manifest.json").write_text("{tor")
        assert ck.restored_step(fallback=True) == 1

    def test_keep_last_k_gc(self, tmp_path):
        ck = Checkpointer(tmp_path, keep=2)
        for s in (1, 2, 3, 4):
            ck.save(s, _state(s))
        assert ck.all_steps() == [3, 4]


# ---------------------------------------------------------------------------
# Fault plane: deterministic schedules, bounded retry, dropout sets
# ---------------------------------------------------------------------------

class TestFaultPlane:
    def test_preempt_fires_exactly_once_at_boundary(self):
        fp = FaultPlane.preempt_after(3)
        fp.maybe_preempt(2)
        with pytest.raises(Preempted):
            fp.maybe_preempt(3)
        fp.maybe_preempt(4)

    def test_launch_fail_budget_is_shared_across_attempts(self):
        fp = FaultPlane.launch_fail(at_launch=5, fails=2)
        for _ in range(2):
            with pytest.raises(InjectedFault):
                fp.on_launch(0, 5)
        fp.on_launch(0, 5)          # budget spent: the retry succeeds
        assert fp.n_injected == 2

    def test_dead_hosts_monotone(self):
        fp = FaultPlane([*FaultPlane.host_dropout(2, at_launch=1).specs,
                         *FaultPlane.host_dropout(0, at_launch=3).specs])
        assert fp.dead_hosts(0) == ()
        assert fp.dead_hosts(1) == (2,)
        assert fp.dead_hosts(3) == (0, 2)
        assert fp.dead_hosts(99) == (0, 2)

    def test_host_lane_mask_contiguous_blocks(self):
        mask = host_lane_mask(8, 4, (1, 3))
        np.testing.assert_array_equal(
            mask, [False, False, True, True, False, False, True, True])

    def test_retry_recovers_within_budget(self):
        rt = maybe_resilient(
            ResilienceConfig(fault_plane=FaultPlane.launch_fail(0, fails=2),
                             max_retries=3),
            "unit")
        calls = []
        out = rt.launch(0, 0, lambda x: calls.append(x) or x, 7)
        assert out == 7 and calls == [7]
        assert rt.n_retries == 2

    def test_retry_exhaustion_raises(self):
        rt = maybe_resilient(
            ResilienceConfig(fault_plane=FaultPlane.launch_fail(0, fails=9),
                             max_retries=2),
            "unit")
        with pytest.raises(FaultExhausted):
            rt.launch(0, 0, lambda: 0)
        assert rt.n_retries == 3    # initial + 2 retries, all failed

    def test_signature_guards_against_run_blending(self, tmp_path):
        assert run_signature("fleet", T=512) == run_signature("fleet", T=512)
        assert run_signature("fleet", T=512) != run_signature("fleet", T=256)
        ck = Checkpointer(tmp_path)
        ck.save(1, (), extra={"engine": "fleet",
                              "signature": run_signature("fleet", T=512)})
        with pytest.raises(ValueError, match="signature mismatch"):
            maybe_resilient(ResilienceConfig(checkpoint_dir=str(tmp_path)),
                            "fleet", T=256)
        with pytest.raises(ValueError, match="belongs to"):
            maybe_resilient(ResilienceConfig(checkpoint_dir=str(tmp_path)),
                            "serving", T=512)


# ---------------------------------------------------------------------------
# Kill-and-resume bit-equality, all three engines
# ---------------------------------------------------------------------------

FLEET_JOBS = [FleetJob(scenario=scen, policy="pi3_reg", lam=lam,
                       eps_b=0.05, seed=s)
              for scen, lam in (("paper_grid", 4.0), ("ge_grid", 3.0))
              for s in (0, 1)]
SERVING_JOBS = [ServingJob(trace="bursty", lam=3.0, seed=s) for s in (0, 1)]
ATLAS_CELLS = registry_cells(("paper_grid", "ring"), topo_seeds=(0, 1),
                             eps_b=0.05)
ATLAS_KW = dict(seeds=(0,), T=512, chunk=256, rel_tol=0.1, max_calls=4)


def _metrics_equal(off, on):
    assert len(off) == len(on)
    for m0, m1 in zip(off, on):
        assert set(m0) == set(m1)
        for k in m0:
            assert m0[k] == m1[k], (k, m0[k], m1[k])


def _stream_equal(base_path, resumed_path):
    """The resumed file, resume seam markers stripped, must be the base
    stream byte-for-byte (records are canonical sorted-key JSON)."""
    with open(base_path) as f:
        base = [json.loads(x) for x in f]
    with open(resumed_path) as f:
        merged = [json.loads(x) for x in f]
    seams = [r for r in merged if r["kind"] == "resume"]
    assert seams, "resumed run emitted no resume record"
    assert [r for r in merged if r["kind"] != "resume"] == base
    assert schema.validate_stream(merged) == []
    return seams


def _kill_and_resume(run, kill_at, ckpt_dir, stream_path):
    """Run `run` with a preempt at boundary `kill_at`, then resume it."""
    with pytest.raises(Preempted):
        run(resilience=ResilienceConfig(
            checkpoint_dir=str(ckpt_dir),
            fault_plane=FaultPlane.preempt_after(kill_at)),
            stream_path=str(stream_path))
    return run(resilience=ResilienceConfig(checkpoint_dir=str(ckpt_dir)),
               stream_path=str(stream_path))


@pytest.mark.fleet_smoke
class TestFleetResume:
    @pytest.fixture(scope="class")
    def base(self, tmp_path_factory):
        path = tmp_path_factory.mktemp("fleet") / "base_stream.jsonl"
        res = run_fleet(FLEET_JOBS, T=512, chunk=128, stream_path=str(path))
        return res, path

    # one program group, 4 chunk launches: every boundary incl. the last
    # (post-launch, pre-finalize — resume recomputes the finalize)
    @pytest.mark.parametrize("kill_at", range(1, 5))
    def test_kill_at_every_boundary_bit_exact(self, base, tmp_path,
                                              kill_at):
        base_res, base_path = base
        res = _kill_and_resume(
            lambda **kw: run_fleet(FLEET_JOBS, T=512, chunk=128, **kw),
            kill_at, tmp_path / "ckpt", tmp_path / "stream.jsonl")
        _metrics_equal(base_res.metrics, res.metrics)
        assert res.slots_saved == base_res.slots_saved
        assert res.launch_slots_saved == base_res.launch_slots_saved
        assert res.resumed_from == kill_at
        assert res.degraded == {} and res.n_fault_retries == 0
        seams = _stream_equal(base_path, tmp_path / "stream.jsonl")
        assert seams[0]["engine"] == "fleet"
        assert seams[0]["ckpt_step"] == kill_at

    def test_early_stop_resume_bit_exact(self, tmp_path):
        kw = dict(T=2048, chunk=256, early_stop=True)
        base = run_fleet(FLEET_JOBS, **kw)
        res = _kill_and_resume(
            lambda **over: run_fleet(FLEET_JOBS, **kw, **over),
            2, tmp_path / "ckpt", tmp_path / "stream.jsonl")
        _metrics_equal(base.metrics, res.metrics)
        assert res.slots_saved == base.slots_saved
        assert res.launch_slots_saved == base.launch_slots_saved

    def test_resume_false_starts_fresh(self, base, tmp_path):
        base_res, _ = base
        with pytest.raises(Preempted):
            run_fleet(FLEET_JOBS, T=512, chunk=128,
                      resilience=ResilienceConfig(
                          checkpoint_dir=str(tmp_path),
                          fault_plane=FaultPlane.preempt_after(2)))
        res = run_fleet(FLEET_JOBS, T=512, chunk=128,
                        resilience=ResilienceConfig(
                            checkpoint_dir=str(tmp_path), resume=False))
        assert res.resumed_from is None
        _metrics_equal(base_res.metrics, res.metrics)


@pytest.mark.fleet_smoke
class TestServingResume:
    @pytest.fixture(scope="class")
    def base(self, tmp_path_factory):
        path = tmp_path_factory.mktemp("serving") / "base_stream.jsonl"
        res = run_serving(SERVING_JOBS, T=512, chunk=128,
                          stream_path=str(path))
        return res, path

    @pytest.mark.parametrize("kill_at", range(1, 5))
    def test_kill_at_every_boundary_bit_exact(self, base, tmp_path,
                                              kill_at):
        base_res, base_path = base
        res = _kill_and_resume(
            lambda **kw: run_serving(SERVING_JOBS, T=512, chunk=128, **kw),
            kill_at, tmp_path / "ckpt", tmp_path / "stream.jsonl")
        _metrics_equal(base_res.metrics, res.metrics)
        assert res.resumed_from == kill_at
        seams = _stream_equal(base_path, tmp_path / "stream.jsonl")
        assert seams[0]["engine"] == "serving"


@pytest.mark.fleet_smoke
class TestAtlasResume:
    @pytest.fixture(scope="class")
    def base(self, tmp_path_factory):
        path = tmp_path_factory.mktemp("atlas") / "base_stream.jsonl"
        res = sweep_lambda_max(ATLAS_CELLS, **ATLAS_KW,
                               stream_path=str(path))
        return res, path

    @pytest.mark.parametrize("kill_at", range(1, 8))
    def test_kill_at_every_boundary_bit_exact(self, base, tmp_path,
                                              kill_at):
        base_res, base_path = base
        res = _kill_and_resume(
            lambda **kw: sweep_lambda_max(ATLAS_CELLS, **ATLAS_KW, **kw),
            kill_at, tmp_path / "ckpt", tmp_path / "stream.jsonl")
        # rows are frozen dataclasses (brackets, probes, slot accounting):
        # == is full bit-equality of the lambda_max search
        assert res.rows == base_res.rows
        assert res.n_launches == base_res.n_launches
        assert res.seq_launches == base_res.seq_launches
        assert res.launch_slots_saved == base_res.launch_slots_saved
        assert res.resumed_from == kill_at
        # memoized launch builders: a same-process resume recompiles nothing
        assert res.n_step_compiles == base_res.n_step_compiles
        seams = _stream_equal(base_path, tmp_path / "stream.jsonl")
        assert seams[0]["engine"] == "atlas"


# ---------------------------------------------------------------------------
# Graceful degradation: host dropout parks lanes, reports, never aborts
# ---------------------------------------------------------------------------

# One small run of each engine, and its per-job results (rows for the atlas).
ENGINES = {
    "fleet": lambda **kw: run_fleet(FLEET_JOBS, T=512, chunk=128, **kw),
    "serving": lambda **kw: run_serving(SERVING_JOBS, T=512, chunk=128,
                                        **kw),
    "atlas": lambda **kw: sweep_lambda_max(ATLAS_CELLS, **ATLAS_KW, **kw),
}


def _per_job(res):
    return res.rows if hasattr(res, "rows") else res.metrics


def _job_equal(a, b):
    if isinstance(a, dict):
        _metrics_equal([a], [b])
    else:
        assert a == b              # AtlasRow: the whole lambda_max search


@pytest.mark.fleet_smoke
class TestDegradation:
    @pytest.mark.parametrize("engine", list(ENGINES))
    def test_host_dropout_degrades_not_aborts(self, engine):
        """A dead host flags its jobs and re-plans the mesh, and the run
        finishes.  The fleet and the atlas park the dead lanes; serving
        only flags them, so its lanes run on to the same metrics."""
        run = ENGINES[engine]
        base = run()
        res = run(resilience=ResilienceConfig(
            fault_plane=FaultPlane.host_dropout(host=0, at_launch=2)))
        assert res.degraded, "dropout was silent"
        for why in res.degraded.values():
            assert why.startswith("host_dropout:")
        assert len(_per_job(res)) == len(_per_job(base))
        if engine == "atlas":
            flagged = {i for i, r in enumerate(res.rows) if r.degraded}
            assert flagged == set(res.degraded)
        for i, (a, b) in enumerate(zip(_per_job(base), _per_job(res))):
            if engine == "serving" or i not in res.degraded:
                _job_equal(a, b)
        assert res.recovery_plan is not None
        assert res.recovery_plan.action == "remesh"
        assert res.recovery_plan.evict == ("host0",)

    @pytest.mark.parametrize("engine", list(ENGINES))
    def test_transient_launch_failure_retries(self, engine):
        run = ENGINES[engine]
        base = run()
        res = run(resilience=ResilienceConfig(
            fault_plane=FaultPlane.launch_fail(at_launch=1, fails=2)))
        for a, b in zip(_per_job(base), _per_job(res), strict=True):
            _job_equal(a, b)
        assert res.n_fault_retries == 2
        assert res.degraded == {}


def test_atlas_stream_closed_on_fault_between_launches(tmp_path,
                                                       monkeypatch):
    """A fault raised between launches (the record consumer failing on the
    third launch record) leaves every record written before it in the
    JSONL, and the sink closed, as a launch failure or a preemption
    would."""
    closed = []
    real_close = StreamSink.close

    def close(sink):
        closed.append(sink)
        real_close(sink)

    monkeypatch.setattr(StreamSink, "close", close)
    seen = []

    def log(rec):
        seen.append(rec)
        if len(seen) == 3:
            raise RuntimeError("record consumer failed")

    path = tmp_path / "atlas_stream.jsonl"
    with pytest.raises(RuntimeError, match="record consumer failed"):
        sweep_lambda_max(ATLAS_CELLS, **ATLAS_KW, stream_path=str(path),
                         stream_log=log)
    assert len(closed) == 1
    assert schema.read_stream_jsonl(str(path)) == seen


# ---------------------------------------------------------------------------
# Resume-aware stream append: dedupe clock, seam records, --resumed gate
# ---------------------------------------------------------------------------

def _fleet_rec(chunk, t, **over):
    fields = dict(group=0, chunk=chunk, t=t, n_sims=4,
                  useful_rate_med=0.5, backlog_med=0.1, max_queue_med=3.0,
                  drift_med=-0.01, n_decided=1, verdicts={"UNDECIDED": 4})
    fields.update(over)
    return schema.make_record("fleet", **fields)


def _resume_rec(chunk, t):
    return schema.make_record("resume", group=0, chunk=chunk, t=t,
                              n_sims=4, engine="fleet", ckpt_step=chunk,
                              n_preloaded=chunk)


class TestStreamResume:
    def test_append_dedupes_by_chunk_clock(self, tmp_path):
        path = tmp_path / "s_stream.jsonl"
        first = StreamSink(path=str(path))
        for c in (0, 1):
            first.write(_fleet_rec(c, 64 * (c + 1)))
        first.close()
        sink = StreamSink(path=str(path), append=True)
        assert sink.n_preloaded == 2
        sink.write(_resume_rec(1, 128))          # seam marker: never deduped
        sink.write(_fleet_rec(1, 128))           # replayed: suppressed
        sink.write(_fleet_rec(2, 192))           # fresh: appended
        sink.close()
        recs = schema.read_stream_jsonl(str(path))
        assert [r["kind"] for r in recs] == ["fleet", "fleet", "resume",
                                             "fleet"]
        assert [r["chunk"] for r in recs if r["kind"] == "fleet"] == \
            [0, 1, 2]
        assert schema.validate_stream(recs) == []

    def test_append_drops_torn_trailing_line(self, tmp_path):
        path = tmp_path / "s_stream.jsonl"
        with open(path, "w") as f:
            f.write(schema.jsonl_line(_fleet_rec(0, 64)) + "\n")
            f.write('{"kind": "fl')               # killed mid-append
        sink = StreamSink(path=str(path), append=True)
        assert sink.n_preloaded == 1
        sink.write(_fleet_rec(1, 128))
        sink.close()
        assert len(schema.read_stream_jsonl(str(path))) == 2

    def test_validate_stream_allows_repeated_resume_seams(self):
        recs = [_fleet_rec(0, 64), _resume_rec(0, 64), _resume_rec(0, 64),
                _fleet_rec(1, 128)]
        assert schema.validate_stream(recs) == []
        dup = [_fleet_rec(0, 64), _fleet_rec(0, 64)]
        assert any("chunk" in e for e in schema.validate_stream(dup))

    def test_check_stream_resumed_gate(self, tmp_path):
        good = tmp_path / "ok_stream.jsonl"
        schema.write_stream_jsonl(
            [_fleet_rec(0, 64), _resume_rec(0, 64), _fleet_rec(1, 128)],
            str(good))
        r = subprocess.run(
            [sys.executable, "scripts/check_stream.py", "--resumed",
             str(good)], cwd=REPO, capture_output=True, text=True)
        assert r.returncode == 0, r.stderr
        bare = tmp_path / "bare_stream.jsonl"
        schema.write_stream_jsonl([_fleet_rec(0, 64)], str(bare))
        r = subprocess.run(
            [sys.executable, "scripts/check_stream.py", "--resumed",
             str(bare)], cwd=REPO, capture_output=True, text=True)
        assert r.returncode == 1
        assert "no resume record" in r.stderr


# ---------------------------------------------------------------------------
# Kill/resume through buckets and adaptive re-queues (DESIGN.md §13)
# ---------------------------------------------------------------------------

@pytest.mark.fleet_smoke
class TestBucketedRequeueResume:
    """The PR-9 snapshot must carry the bucketed-atlas cursor: launch-unit
    index (the bucket cursor), per-bucket launch counters, and per-cell
    attempt counters — a kill mid-re-queue or mid-bucket resumes
    bit-exactly."""

    # paper_grid + ring land in different size buckets; T=512/chunk=256
    # cannot latch, so every cell escalates through both re-queues —
    # every boundary is either mid-bucket or mid-attempt.
    CELLS = registry_cells(("paper_grid", "ring"), topo_seeds=(0,),
                           eps_b=0.05)
    KW = dict(seeds=(0,), T=512, chunk=256, rel_tol=0.1, max_calls=4,
              n_buckets=2, max_requeues=2)

    @pytest.fixture(scope="class")
    def base(self, tmp_path_factory):
        path = tmp_path_factory.mktemp("req") / "base_stream.jsonl"
        res = sweep_lambda_max(self.CELLS, **self.KW,
                               stream_path=str(path))
        return res, path

    def _kill_points(self, base_res):
        n = base_res.n_launches
        return sorted({1, 2, n // 2, n - 1, n})

    def test_kill_mid_requeue_and_mid_bucket_bit_exact(self, base,
                                                       tmp_path):
        base_res, base_path = base
        assert base_res.n_buckets == 2
        assert base_res.n_requeues == 2 * len(self.CELLS)
        for kill_at in self._kill_points(base_res):
            ckpt = tmp_path / f"ckpt_{kill_at}"
            stream = tmp_path / f"stream_{kill_at}.jsonl"
            res = _kill_and_resume(
                lambda **kw: sweep_lambda_max(self.CELLS, **self.KW, **kw),
                kill_at, ckpt, stream)
            assert res.rows == base_res.rows, f"kill_at={kill_at}"
            assert res.n_requeues == base_res.n_requeues
            assert res.bucket_launches == base_res.bucket_launches
            assert res.bucket_cells == base_res.bucket_cells
            assert res.n_launches == base_res.n_launches
            assert res.resumed_from == kill_at
            # attempt counters survived: per-row re-queue counts intact
            assert [r.n_requeues for r in res.rows] == \
                [r.n_requeues for r in base_res.rows]
            seams = _stream_equal(base_path, stream)
            assert seams[0]["engine"] == "atlas"
