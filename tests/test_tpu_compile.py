"""Compile the chip's main path for a described TPU v5e, without a chip.

The TPU compiler is installed next to JAX, and it compiles for a topology
that is described rather than attached: what it refuses here (tile
alignment, unsupported kernel ops, memory) would be refused on the chip.
This file compiles, with the kernels' platform rule steered to the
chip's choice (compiled, not interpreted):

  * both `bp_slot` kernels at the atlas bucket hull (N=16, E=51, NC=4),
    alone and vmapped over the engine's lanes, and at a deployment-size
    shape with E >= 1,024;
  * the whole fleet chunk step for backend="xla" and backend="pallas" on
    one chip (the pallas step must carry the kernels as
    `tpu_custom_call`s);
  * the xla chunk step on a four-chip lane mesh, and the atlas cell's
    pi3 chunk step at its unreduced 192 lanes a chip over that mesh;
  * the chunk step of the k=8 fat-tree fabric (`fattree_k8`: 83 nodes,
    259 links, 32 computation nodes) at 64 lanes, eight seeds a rate of
    the sweep cell's mix (the cell runs one);
  * the chunk step of each benchmark cell's program (wired with the
    freeze mask, wireless without), which must keep every named scope of
    the slot (`repro.obs.spans.SLOT_SCOPES`) in its op metadata;
  * the atlas (96 lanes) and fat-tree (8 lanes) chunk steps, whose
    `route` scope must hold no scatter, and no contraction below HIGHEST
    precision.

Nothing runs, so nothing here is a result or a time.  The topology is
described inside a module fixture (never at import: only one process
may hold the TPU library), and the persistent compile cache is off
around these compiles, since entries written for a described chip cannot
be read back without one.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import PolicyConfig
from repro.fleet import PadDims, get_scenario, pad_problem
from repro.fleet.engine import (DEFAULT_VERDICT, VerdictConfig,
                                _make_stream_runner, make_group_launch)
from repro.kernels import platform
from repro.kernels.bp_slot.kernel import comp_balance_decide, slot_route_decide
from repro.obs.spans import SLOT_SCOPES

#: (N, E, NC): the atlas bucket hull, and a fat-tree-scale shape.
KERNEL_SHAPES = [(16, 51, 4), (208, 1536, 16)]
LANES = 504                       # the atlas's largest bucket, in lanes


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        from jax.experimental import topologies
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()


@pytest.fixture
def chip_kernels(monkeypatch):
    """The kernels resolve their mode from the default backend, which is
    the CPU here; steer it to the chip's choice for these traces."""
    monkeypatch.setattr(platform, "default_backend", lambda: "tpu")


@pytest.fixture(scope="module")
def one_chip(topo):
    return Mesh(np.array(topo.devices[:1]), ("fleet",))


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.array(topo.devices[:4]), ("fleet",))


def _sds(shape, dtype, mesh, spec=P()):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def _kernel_args(mesh, N, E, NC, lanes):
    lead = (lanes,) if lanes else ()
    spec = P("fleet") if lanes else P()
    f32 = lambda *s: _sds(lead + s, jnp.float32, mesh, spec)
    i32 = lambda *s: _sds(lead + s, jnp.int32, mesh, spec)
    route = (f32(N, 3 * NC), i32(E), i32(E))
    comp = (f32(),) + tuple(f32(NC) for _ in range(12))
    return route, comp


@pytest.mark.parametrize("lanes", [0, LANES], ids=["one", "vmapped"])
@pytest.mark.parametrize("N,E,NC", KERNEL_SHAPES,
                         ids=lambda v: str(v))
def test_bp_slot_kernels_compile(one_chip, chip_kernels, N, E, NC, lanes):
    route_args, comp_args = _kernel_args(one_chip, N, E, NC, lanes)
    route, comp = slot_route_decide, comp_balance_decide
    if lanes:
        route, comp = jax.vmap(route), jax.vmap(comp)
    for fn, args in ((route, route_args), (comp, comp_args)):
        compiled = jax.jit(fn).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()


def _compile_chunk_step(mesh, backend, wireless=False,
                        verdict=DEFAULT_VERDICT,
                        scenarios=("paper_grid", "expander", "fat_tree"),
                        lanes=LANES, T=4096, chunk=512):
    """Lower and compile the fleet's donated chunk step for `mesh`: by
    default the atlas's pi3 group at the pad dims of
    paper_grid/expander/fat_tree, T=4096, chunk=512.  Fresh (un-memoized)
    runner and launch objects, so no trace made on the CPU is reused."""
    problems = [get_scenario(s).build(0) for s in scenarios]
    dims = PadDims.of(problems)
    pads = [pad_problem(problems[i % len(problems)], dims)
            for i in range(lanes)]
    pp = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *pads)
    cfg = PolicyConfig(name="pi3", backend=backend, wireless=wireless)
    runner = _make_stream_runner.__wrapped__(cfg, T, chunk, None, verdict)
    _, step_fn, _ = make_group_launch.__wrapped__(runner, mesh)
    lane = jax.ShapeDtypeStruct((lanes,), jnp.float32)
    code = jax.ShapeDtypeStruct((lanes,), jnp.int32)
    keys = jax.eval_shape(jax.vmap(jax.random.PRNGKey), code)
    carry = jax.eval_shape(jax.vmap(runner.init_carry), pp)
    shard = NamedSharding(mesh, P("fleet"))
    args = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=shard),
        (pp, lane, lane, code, code, keys, carry))
    return step_fn.lower(*args).compile()


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_fleet_chunk_step_compiles_one_chip(one_chip, chip_kernels, backend):
    compiled = _compile_chunk_step(one_chip, backend)
    hlo = compiled.as_text()
    assert ("tpu_custom_call" in hlo) == (backend == "pallas")
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 16e9


def test_fleet_chunk_step_compiles_four_chip_mesh(four_chips):
    compiled = _compile_chunk_step(four_chips, "xla")
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes > 0


def test_atlas_chunk_step_compiles_at_192_lanes_a_chip(four_chips):
    """The `fig5_grid.atlas_4chip` cell's shape: the paper grid's pi3
    chunk step at 128 replicas x 3 seeds x 2 policies = 768 lanes, 192 a
    chip, over the 2x2 mesh."""
    compiled = _compile_chunk_step(four_chips, "xla",
                                   verdict=VerdictConfig(freeze=True),
                                   scenarios=("paper_grid",), lanes=768)
    ma = compiled.memory_analysis()
    assert 0 < ma.argument_size_in_bytes + ma.temp_size_in_bytes < 16e9


def test_fat_tree_chunk_step_compiles_one_chip(one_chip):
    """The `fattree_k8.sweep` cell's program at up to eight seeds a rate:
    the k=8 fabric's pi3 chunk step, 64 lanes of [83, 3, 32] queues,
    chunk 20,000 of T 120,000."""
    compiled = _compile_chunk_step(one_chip, "xla", scenarios=("fattree_k8",),
                                   lanes=64, T=120_000, chunk=20_000)
    ma = compiled.memory_analysis()
    assert 0 < ma.argument_size_in_bytes + ma.temp_size_in_bytes < 16e9


@pytest.mark.parametrize("wireless,freeze", [(False, True), (True, False)],
                         ids=["wired", "wireless"])
def test_chunk_step_keeps_slot_scopes(one_chip, wireless, freeze):
    """The chip's compiler keeps the slot's named scopes in the op_name
    metadata a device trace names its ops by."""
    hlo = _compile_chunk_step(one_chip, "xla", wireless=wireless,
                              verdict=VerdictConfig(freeze=freeze)).as_text()
    paths = [p.split("/") for p in re.findall(r'op_name="([^"]*)"', hlo)]
    want = {s for s in SLOT_SCOPES
            if (wireless or s != "matching") and (freeze or s != "freeze")}
    assert {s for s in SLOT_SCOPES if any(s in p for p in paths)} == want


@pytest.mark.parametrize("scenario,lanes,T,chunk,freeze", [
    ("paper_grid", 96, 4096, 512, True),
    ("fattree_k8", 8, 120_000, 20_000, False),
], ids=["atlas", "fattree_k8"])
def test_route_apply_has_no_scatter(one_chip, scenario, lanes, T, chunk,
                                    freeze):
    """The route's apply half reaches the chip without a scatter, at the
    atlas's shape and at the largest a cell runs: no scatter under the
    `route` scope, and no contraction there below HIGHEST precision (f32,
    not bfloat16 operands).  The compiler drops the op_name of scatters
    it rewrites, so an unnamed scatter counts as one that could be the
    route's."""
    hlo = _compile_chunk_step(one_chip, "xla", scenarios=(scenario,),
                              lanes=lanes, T=T, chunk=chunk,
                              verdict=VerdictConfig(freeze=freeze)).as_text()
    ops = [line for line in hlo.splitlines() if " = " in line]
    scatters = [line for line in ops if re.search(r" scatter\(", line)]
    assert scatters, "the balance and compute scopes still scatter"
    for line in scatters:
        name = re.search(r'op_name="([^"]*)"', line)
        assert name and "/route/" not in name.group(1) + "/", line[:200]
    contractions = [line for line in ops if "/route/" in line
                    and re.search(r" (dot|convolution)\(", line)]
    for line in contractions:
        assert "operand_precision={highest,highest}" in line, line[:200]
