"""Host spans and the slot's named scopes (`repro.obs.spans`).

The recorder is off unless enabled, nests spans per thread under the
outermost span's job id, counts the engines' loop spans exactly, and
changes no result; every name in `SLOT_SCOPES` reaches the op_name
metadata of the compiled chunk step.
"""
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core import PolicyConfig
from repro.fleet import (AtlasJob, FleetJob, PadDims, VerdictConfig,
                         get_scenario, pad_problem, run_fleet,
                         sweep_lambda_max)
from repro.fleet.engine import _make_stream_runner, make_group_launch
from repro.obs import spans
from repro.serving import ServingJob, run_serving


@pytest.fixture
def recording():
    """Recording on for one test; whatever it left is dropped after."""
    spans.enable()
    yield
    spans.collect()


def test_off_returns_the_shared_noop():
    a, b = spans.span("x"), spans.span("y", n=3)
    assert a is b
    with a as sp:
        sp.n = 7                       # accepted and dropped
    assert spans.collect() == []


def test_spans_nest_with_parent_and_job(recording):
    with spans.span("job.a"):
        with spans.span("a.child", n=3):
            pass
        with spans.span("a.late") as sp:
            sp.n = 5
    with spans.span("job.b"):
        pass
    recs = {r.name: r for r in spans.collect()}
    assert set(recs) == {"job.a", "a.child", "a.late", "job.b"}
    a, b = recs["job.a"], recs["job.b"]
    assert a.parent is None and b.parent is None and a.job != b.job
    for name, n in (("a.child", 3), ("a.late", 5)):
        r = recs[name]
        assert (r.parent, r.job, r.n) == ("job.a", a.job, n)
        assert a.start_ns <= r.start_ns <= r.end_ns <= a.end_ns
    assert spans.span("z") is spans.span("w")     # collect switched it off


def test_parent_stack_is_per_thread(recording):
    seen = threading.Event()

    def other():
        with spans.span("thread.job"):
            pass
        seen.set()

    with spans.span("main.job"):
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=30)
    assert seen.is_set() and not t.is_alive()
    recs = {r.name: r for r in spans.collect()}
    assert recs["thread.job"].parent is None
    assert recs["thread.job"].job != recs["main.job"].job


ATLAS_CELLS = [AtlasJob("paper_grid", policy="pi3", eps_b=0.0432),
               AtlasJob("ring", policy="pi3", eps_b=0.0432)]
ATLAS_KW = dict(seeds=(0, 1), T=1024, chunk=256, rel_tol=0.25, max_calls=3)


@pytest.mark.fleet_smoke
def test_atlas_spans_count_every_launch():
    off = sweep_lambda_max(ATLAS_CELLS, **ATLAS_KW)
    spans.enable()
    try:
        on = sweep_lambda_max(ATLAS_CELLS, **ATLAS_KW,
                              stream_log=lambda rec: None)
    finally:
        recs = spans.collect()
    assert on.rows == off.rows                    # spans change no result
    by = {}
    for r in recs:
        by.setdefault(r.name, []).append(r)
    (sweep,) = by["atlas.sweep"]
    assert all(r.job == sweep.job for r in recs)
    assert len(by["atlas.launch"]) == on.n_launches
    assert len(by["atlas.readback"]) == on.n_launches
    assert len(by["atlas.harvest"]) == on.n_launches
    assert len(by["atlas.record"]) == on.n_launches
    assert len(by["atlas.unit_setup"]) == on.n_programs
    assert len(by.get("atlas.rewrite", [])) <= on.n_rewrites
    assert sum(r.n for r in by["atlas.harvest"]) == sum(
        row.n_calls for row in on.rows)
    lanes = len(ATLAS_CELLS) * len(ATLAS_KW["seeds"])
    assert [r.n for r in by["atlas.unit_setup"]] == [lanes]
    assert all(0 < r.n <= lanes for r in by["atlas.launch"])
    assert all(r.parent == "atlas.sweep" for r in recs if r is not sweep)


FLEET_JOBS = [FleetJob(scenario="paper_grid", policy=p, lam=2.0, seed=s,
                       eps_b=0.0432)
              for p in ("pi3", "pi1") for s in (0, 1)]


@pytest.mark.fleet_smoke
def test_fleet_spans_one_finalize_per_group():
    off = run_fleet(FLEET_JOBS, T=512, chunk=128)
    spans.enable()
    try:
        on = run_fleet(FLEET_JOBS, T=512, chunk=128)
    finally:
        recs = spans.collect()
    assert on.metrics == off.metrics              # bit-identical
    names = [r.name for r in recs]
    assert on.n_programs == 2
    assert names.count("fleet.job") == names.count("fleet.build") == 1
    assert names.count("fleet.group_setup") == on.n_programs
    assert names.count("fleet.finalize") == on.n_programs
    assert names.count("fleet.launch") == on.n_programs * 4
    assert "fleet.readout" not in names           # no early stop
    (job,) = [r for r in recs if r.name == "fleet.job"]
    assert all(r.job == job.job for r in recs)


@pytest.mark.fleet_smoke
def test_serving_spans_come_from_the_shared_loop():
    jobs = [ServingJob(trace="bursty", lam=3.0, seed=s) for s in (0, 1)]
    off = run_serving(jobs, T=512, chunk=128)
    spans.enable()
    try:
        on = run_serving(jobs, T=512, chunk=128)
    finally:
        recs = spans.collect()
    assert on.metrics == off.metrics              # bit-identical
    names = [r.name for r in recs]
    assert names.count("serving.job") == names.count("serving.build") == 1
    assert names.count("serving.group_setup") == on.n_programs
    assert names.count("serving.finalize") == on.n_programs
    assert names.count("serving.launch") == on.n_programs * 4
    (job,) = [r for r in recs if r.name == "serving.job"]
    assert all(r.job == job.job for r in recs)


def _step_hlo(wireless: bool, freeze: bool) -> str:
    """Compiled (CPU) text of the fleet's chunk step for a fresh,
    un-memoized runner at two lanes of the paper grid."""
    prob = get_scenario("paper_grid").build(0)
    pp = jax.tree_util.tree_map(
        lambda *xs: np.stack(xs),
        *[pad_problem(prob, PadDims.of([prob]))] * 2)
    cfg = PolicyConfig(name="pi3", wireless=wireless)
    runner = _make_stream_runner.__wrapped__(
        cfg, 512, 256, None, VerdictConfig(freeze=freeze))
    mesh = Mesh(np.array(jax.devices()[:1]), ("fleet",))
    _, step_fn, _ = make_group_launch.__wrapped__(runner, mesh)
    lane = jnp.zeros((2,), jnp.float32)
    code = jnp.zeros((2,), jnp.int32)
    keys = jax.vmap(jax.random.PRNGKey)(code)
    carry = jax.vmap(runner.init_carry)(pp)
    return step_fn.lower(pp, lane, lane, code, code, keys,
                         carry).compile().as_text()


def scopes_in(hlo: str) -> set:
    """Every `SLOT_SCOPES` name that is a whole segment of some op_name."""
    paths = re.findall(r'op_name="([^"]*)"', hlo)
    return {s for s in spans.SLOT_SCOPES
            if any(s in p.split("/") for p in paths)}


@pytest.mark.parametrize("wireless,freeze", [(False, True), (True, False)],
                         ids=["wired_freeze", "wireless"])
def test_slot_scopes_reach_compiled_step(wireless, freeze):
    want = set(spans.SLOT_SCOPES)
    if not wireless:
        want.discard("matching")
    if not freeze:
        want.discard("freeze")
    assert scopes_in(_step_hlo(wireless, freeze)) == want
