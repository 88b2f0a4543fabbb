"""Tests of `perfbench/span_report.py`'s readers of the program's spans
and named scopes, on synthetic spans and ops and on the CPU-compiled
chunk step."""
from __future__ import annotations

import pathlib
import re
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "perfbench"
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import span_report as sr  # noqa: E402
from repro.obs.spans import SLOT_SCOPES, Span  # noqa: E402

MS = 1_000_000


def _atlas_spans():
    """One sweep (job 0): a 10-lane unit, two launches with 8 and 4
    active lanes; harvest, rewrite and record after each but the last
    rewrite."""
    s = [Span("atlas.unit_setup", 0, 2 * MS, "atlas.sweep", 0, 10)]
    t = 2 * MS
    for lanes, rewrite in ((8, True), (4, False)):
        s.append(Span("atlas.launch", t, t + MS, "atlas.sweep", 0, lanes))
        s.append(Span("atlas.readback", t + MS, t + 3 * MS, "atlas.sweep",
                      0, None))
        s.append(Span("atlas.harvest", t + 3 * MS, t + 5 * MS,
                      "atlas.sweep", 0, 2))
        t += 5 * MS
        if rewrite:
            s.append(Span("atlas.rewrite", t, t + MS, "atlas.sweep", 0, 4))
            t += MS
        s.append(Span("atlas.record", t, t + MS, "atlas.sweep", 0, None))
        t += MS
    s.append(Span("atlas.sweep", 0, t, None, 0, None))
    return s


def test_atlas_span_metrics():
    spans = _atlas_spans()
    # (2 + 1 + 1) + (2 + 1) ms of scheduler work over 2 launches
    assert sr.scheduler_ms(spans) == pytest.approx(3.5)
    assert sr.active_lane_share(spans) == pytest.approx((0.8 + 0.4) / 2)
    table = sr.span_table(spans)
    assert table["atlas.launch"]["count"] == 2
    assert table["atlas.launch"]["n"] == 12
    assert table["atlas.readback"]["mean_ms"] == pytest.approx(2.0)
    m = sr.span_metrics(spans)
    assert m["job_setup_ms"] is None and m["finalize_ms"] is None


def test_fleet_span_metrics_are_per_job():
    spans = []
    for job, setup in ((1, 3), (2, 5)):
        spans += [Span("fleet.build", 0, MS, "fleet.job", job, None),
                  Span("fleet.group_setup", MS, setup * MS, "fleet.job",
                       job, None),
                  Span("fleet.launch", setup * MS, (setup + 1) * MS,
                       "fleet.job", job, None),
                  Span("fleet.finalize", 10 * MS, 10 * MS + job * MS,
                       "fleet.job", job, None),
                  Span("fleet.job", 0, 20 * MS, None, job, None)]
    assert sr.per_job_ms(spans, sr.JOB_SETUP) == pytest.approx(4.0)
    assert sr.per_job_ms(spans, ("fleet.finalize",)) == pytest.approx(1.5)
    assert sr.scheduler_ms(spans) is None
    assert sr.active_lane_share(spans) is None


def test_scope_split_unions_scoped_time_per_execution():
    # one execution [0, 1000) ns of 10 slots: balance [0, 100), route
    # [100, 400) holding matching [200, 300), compute [400, 500), an
    # unscoped op [500, 600), and an op past the execution
    ops = [(0, 100, {"balance"}), (100, 400, {"route"}),
           (200, 300, {"route", "matching"}), (400, 500, {"compute"}),
           (500, 600, set()), (900, 1100, {"stats"})]
    got = sr.scope_split(ops, [(0, 1000)], 10, SLOT_SCOPES)
    assert got["balance"] == pytest.approx(0.01)
    assert got["route"] == pytest.approx(0.03)
    assert got["matching"] == pytest.approx(0.01)
    assert got["decide"] == pytest.approx(0.05)
    assert got["unscoped"] == pytest.approx(0.01)
    assert got["stats"] == 0
    assert got["step"] == pytest.approx(0.1)
    assert got["scoped_share"] == pytest.approx(0.5)


def test_unscoped_ops_by_kind():
    ops = [(0, 100, "%copy-done.3 = f32[96] copy-done(%copy-start.3)", None),
           (100, 300, "%fusion.9 = u32[96,2] fusion(%k)",
            "jit(chunk_step)/while/body/closed_call/"
            "vmap(jit(_threefry_fold_in))/slot/xor"),
           (300, 400, "%reduce.1 = f32[96] reduce(%q)",
            "jit(chunk_step)/while/body/closed_call/reduce_sum"),
           (900, 1100, "%copy.1 = s32[2] copy(%x)", None)]
    got = sr.unscoped_us(ops, [(0, 1000)], 10)
    assert got == pytest.approx({
        "vmap(jit(_threefry_fold_in))/slot": 0.02,
        "no op_name: copy-done": 0.01, "reduce_sum": 0.01})
    assert sr.unscoped_kind("x", "jit(f)/while/body/add") == "jit(f)/while"


def test_fusions_without_op_name_take_their_computations_path():
    hlo = """HloModule jit_chunk_step

%fused_computation.2 (p0: f32[8], p1: s32[4]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %scatter.1 = f32[8]{0} scatter(%p0), metadata={op_name="jit(f)/route/scatter-add"}
  ROOT %tuple.3 = (f32[8]{0}) tuple(%scatter.1)
}

%fused_computation.1 (p0: f32[8]) -> f32[8] {
  %p0.1 = f32[8]{0} parameter(0)
  ROOT %fusion.9 = f32[8]{0} fusion(%p0.1), kind=kCustom, calls=%fused_computation.2
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %add.1 = f32[8]{0} add(%a, %a), metadata={op_name="jit(f)/balance/add"}
  ROOT %fusion.8 = f32[8]{0} fusion(%add.1), kind=kLoop, calls=%fused_computation.1
}
"""
    names = sr.hlo_op_names(hlo)
    assert names["add.1"] == "jit(f)/balance/add"
    assert names["fusion.9"] == names["fusion.8"] == "jit(f)/route/scatter-add"
    assert "a" not in names
    assert sr.unscoped_kind("x", "jit(f)/vmap()/while") == \
        "a while loop (spans its body)"


def test_scopes_are_whole_path_segments():
    path = "jit(chunk_step)/while/body/route/matching/while/body/add"
    assert sr.scopes_of(path, SLOT_SCOPES) == {"route", "matching"}
    assert sr.scopes_of("jit(f)/rerouted/computer", SLOT_SCOPES) == set()
    assert sr.scopes_of(None, SLOT_SCOPES) == set()
    names = {"add_fusion.3": "jit(f)/balance/add"}
    event = ("%add_fusion.3 = f32[96,1]{0,1:T(1,128)} fusion(f32[96] "
             "%lam.1), kind=kLoop, calls=%fused_computation.804")
    assert sr.op_path(event, names) == "jit(f)/balance/add"
    assert sr.op_path("add_fusion.3", names) == "jit(f)/balance/add"
    assert sr.op_path("%copy.1 = s32[1] copy(s32[1] %x)", names) is None


def test_idle_gaps_by_program_span():
    busy = [(100, 200), (150, 300), (300, 30_000), (60_000, 61_000),
            (61_005, 70_000)]
    gaps = sr.idle_gaps(busy, 0, 100_000)
    assert gaps == [(0, 100), (30_000, 60_000), (61_000, 61_005),
                    (70_000, 100_000)]
    host = [("atlas.sweep", 0, 100_000), ("atlas.readback", 25_000, 50_000),
            ("atlas.harvest", 50_000, 90_000), ("np.asarray", 40_000, 50_000)]
    got = sr.idle_by(gaps, sr.program_span_at(host))
    # gap middles: 45,000 in readback, 85,000 in harvest; the two short
    # ones apart
    assert got == pytest.approx({"atlas.readback": 30e-6,
                                 "atlas.harvest": 30e-6,
                                 "(gaps under 10 us)": 105e-9})
    assert sr.program_span_at([])(5) == "-"


def test_hlo_op_names_carry_the_slot_scopes():
    """The compiled chunk step's own text maps its instructions to paths
    that hold the slot's scopes."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.core import PolicyConfig
    from repro.fleet import (PadDims, VerdictConfig, get_scenario,
                             pad_problem)
    from repro.fleet.engine import _make_stream_runner, make_group_launch

    prob = get_scenario("paper_grid").build(0)
    pp = jax.tree_util.tree_map(lambda *xs: np.stack(xs),
                                *[pad_problem(prob, PadDims.of([prob]))] * 2)
    runner = _make_stream_runner.__wrapped__(
        PolicyConfig(name="pi3", wireless=True), 256, 128, None,
        VerdictConfig(freeze=True))
    mesh = Mesh(np.array(jax.devices()[:1]), ("fleet",))
    init_fn, step_fn, _ = make_group_launch.__wrapped__(runner, mesh)
    lane = np.zeros(2, np.float32)
    code = np.zeros(2, np.int32)
    keys = jax.vmap(jax.random.PRNGKey)(code)
    hlo = step_fn.lower(pp, lane, lane, code, code, keys,
                        init_fn(pp)).compile().as_text()
    names = sr.hlo_op_names(hlo)
    found = set()
    for path in names.values():
        found |= sr.scopes_of(path, SLOT_SCOPES)
    assert found == set(SLOT_SCOPES)
    # a chip trace names each op event by its instruction's text, less
    # the metadata: every fusion maps back to its own path
    fusions = [ln.strip() for ln in hlo.splitlines()
               if " fusion(" in ln and "op_name=" in ln]
    assert fusions
    for ln in fusions:
        event = ln.removeprefix("ROOT ").split(", metadata=")[0]
        want = re.search(r'op_name="([^"]*)"', ln).group(1)
        assert sr.op_path(event, names) == want
