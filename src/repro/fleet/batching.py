"""Padded topology batching: heterogeneous graphs as one vmappable pytree.

The seed simulator compiles one XLA program per `ComputeProblem` because the
problem constants (edges, capacities, sinks) are baked into the trace.  The
fleet engine instead pads every instance to fleet-wide maxima and carries the
problem as *traced* arrays, so a thousand different topologies share one
compiled program under `vmap`/`shard_map`.

Mask convention (the single source of truth — narrated in DESIGN.md §3 and
referenced by README and the core policies):

  * Every instance is padded to shared maxima ``(n_nodes, n_edges, n_comp)``.
  * Padded edges are self-loops ``(0, 0)`` with ``edge_cap == 0`` and
    ``edge_mask == 0``.  A self-loop has zero differential backlog, so it can
    never route traffic even before masking; the mask additionally keeps it
    out of wireless matchings and any capacity statistics.
  * Padded computation nodes point at node 0 with ``comp_caps == 0`` and
    ``comp_mask == 0``.  Masked nodes are excluded from the load-balance
    argmin (score forced to +inf) and combine zero pairs per slot; the
    regulator consequently sees ``assigned == 0`` there and pushes nothing
    (``F = 0``), so padded slots accumulate no ``Y``/``H``/``Ddum`` state —
    padding is the network-side mirror of the paper's dummy-packet
    regulator (DESIGN.md §2).
  * ``edge_places`` of padded edges are 0: they follow the real edges, so
    they never take a place before a real one, and the route's apply half
    moves nothing on them (``max_degree``, static, bounds the real places).
  * ``sink`` rows of padded classes are all ``False``; padded *nodes* simply
    host queues that never receive traffic (no active edge touches them).

`PaddedProblem` is duck-type compatible with `repro.core.queues.StaticProblem`
— `slot_step`, `init_state`, and `make_step` accept either.  The padded node
and class counts stay static (pytree aux data) so shapes remain concrete.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import ComputeProblem
from repro.core.queues import StaticProblem, max_node_degree


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class PaddedProblem:
    """A (possibly batched) padded problem with traced constants."""

    n_nodes: int               # static: padded node count
    n_comp: int                # static: padded comp-node count
    edges: jax.Array           # [..., E, 2] int32
    edge_cap: jax.Array        # [..., E] float32
    s1: jax.Array              # [...] int32
    s2: jax.Array              # [...] int32
    dest: jax.Array            # [...] int32
    comp_nodes: jax.Array      # [..., NC] int32
    comp_caps: jax.Array       # [..., NC] float32
    sink: jax.Array            # [..., N, 3, NC] bool
    edge_mask: jax.Array       # [..., E] float32
    comp_mask: jax.Array       # [..., NC] float32
    edge_places: jax.Array     # [..., E, 2] int32 (`link_places`)
    max_degree: int            # static: places a node gives its links

    def tree_flatten(self):
        leaves = (self.edges, self.edge_cap, self.s1, self.s2, self.dest,
                  self.comp_nodes, self.comp_caps, self.sink,
                  self.edge_mask, self.comp_mask, self.edge_places)
        return leaves, (self.n_nodes, self.n_comp, self.max_degree)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(aux[0], aux[1], *leaves, aux[2])

    @property
    def n_edges(self) -> int:
        return int(self.edges.shape[-2])

    def replace(self, **kw) -> "PaddedProblem":
        return dataclasses.replace(self, **kw)

    def with_capacity_scales(self, edge_scale: jax.Array,
                             comp_scale: jax.Array) -> "PaddedProblem":
        """Per-slot time-varying capacities (fleet event models).

        A comp node whose scale is zero this slot — an event-model outage,
        e.g. the Markov `ge_comp` chain — is also gated out of `comp_mask`,
        so it is excluded from the load-balance argmin exactly like a
        padded slot: a Down node keeps its queues but neither combines
        pairs nor attracts new query assignments (DESIGN.md §3).  Edge
        masks need no gating: `bp_route_slot` already weights matching and
        allocation by the scaled capacity."""
        return self.replace(
            edge_cap=self.edge_cap * edge_scale,
            comp_caps=self.comp_caps * comp_scale,
            comp_mask=self.comp_mask * (comp_scale > 0.0).astype(jnp.float32))


def problem_shape(problem: ComputeProblem) -> Tuple[int, int, int]:
    """The (n_nodes, n_edges, n_comp) shape of one instance — the axes
    padding has to cover."""
    return (int(problem.graph.n_nodes), int(problem.graph.n_edges),
            int(problem.n_comp))


def problem_degree(problem: ComputeProblem) -> int:
    """The most links that meet at one node of the instance."""
    return max_node_degree(np.asarray(problem.graph.edges))


@dataclasses.dataclass(frozen=True)
class PadDims:
    """The padded shapes, and `max_degree`: the places each node gives
    the links that meet it in the route's apply half (None: `n_edges`,
    which bounds any instance that fits)."""

    n_nodes: int
    n_edges: int
    n_comp: int
    max_degree: int | None = None

    @staticmethod
    def of(problems: Sequence[ComputeProblem]) -> "PadDims":
        problems = list(problems)
        if not problems:
            raise ValueError(
                "PadDims.of: empty problem sequence — there is nothing to "
                "take shape maxima over (did a scenario/topo_seed grid "
                "expand to zero cells?)")
        return PadDims(
            n_nodes=max(p.graph.n_nodes for p in problems),
            n_edges=max(p.graph.n_edges for p in problems),
            n_comp=max(p.n_comp for p in problems),
            max_degree=max(problem_degree(p) for p in problems),
        )

    @property
    def places(self) -> int:
        return self.n_edges if self.max_degree is None else self.max_degree

    def fits(self, problem: ComputeProblem) -> bool:
        n, e, nc = problem_shape(problem)
        return (n <= self.n_nodes and e <= self.n_edges and nc <= self.n_comp
                and problem_degree(problem) <= self.places)


def make_buckets(problems: Sequence[ComputeProblem],
                 n_buckets: int = 1
                 ) -> Tuple[List[PadDims], List[int]]:
    """Partition problems into size buckets with per-bucket `PadDims`.

    Returns ``(bucket_dims, assignment)`` where ``assignment[i]`` is the
    bucket index of ``problems[i]`` and ``bucket_dims[b]`` covers every
    problem assigned to bucket ``b``.  Breakpoints are quantiles of a
    lexicographic (n_edges, n_nodes, n_comp) size key — edges first
    because the [E, 2] routing arrays dominate the padded slot cost — so
    a 500-node expander stops inflating every 16-node ring (DESIGN.md
    §13).  Problems with identical shapes always share a bucket, empty
    quantile bins are dropped, and each bucket's dims are the maxima over
    its own members, so every problem fits its bucket by construction
    (re-checked by `validate_buckets`).  ``n_buckets=1`` reproduces the
    single global `PadDims.of` hull exactly.
    """
    problems = list(problems)
    if not problems:
        raise ValueError("make_buckets: empty problem sequence")
    n_buckets = max(1, int(n_buckets))
    shapes = np.array([problem_shape(p) for p in problems], np.int64)
    # Lexicographic (E, N, NC) packed into one int64 so quantiles of the
    # scalar respect the full ordering (shifts leave 2^23 headroom per axis).
    key = (shapes[:, 1] << 40) | (shapes[:, 0] << 20) | shapes[:, 2]
    cuts = [int(np.quantile(key, (b + 1) / n_buckets, method="lower"))
            for b in range(n_buckets - 1)]
    raw = np.zeros(len(problems), np.int64)
    for c in cuts:
        raw += key > c
    dense: Dict[int, int] = {}
    for r in sorted(set(raw.tolist())):
        dense[r] = len(dense)
    assignment = [dense[int(r)] for r in raw]
    bucket_dims = []
    for b in range(len(dense)):
        members = [p for p, a in zip(problems, assignment) if a == b]
        bucket_dims.append(PadDims.of(members))
    validate_buckets(problems, bucket_dims, assignment)
    return bucket_dims, assignment


def validate_buckets(problems: Sequence[ComputeProblem],
                     bucket_dims: Sequence[PadDims],
                     assignment: Sequence[int]) -> None:
    """Check every problem fits its assigned bucket's dims.

    Raises an actionable `ValueError` naming the offending instance shape
    and the bucket dims it overflows — the bucketed-atlas contract
    (DESIGN.md §13) is that a cell is *never* silently truncated."""
    if len(problems) != len(assignment):
        raise ValueError(
            f"validate_buckets: {len(problems)} problems but "
            f"{len(assignment)} bucket assignments")
    for i, (p, b) in enumerate(zip(problems, assignment)):
        if not 0 <= b < len(bucket_dims):
            raise ValueError(
                f"validate_buckets: problem {i} assigned to bucket {b}, "
                f"but only {len(bucket_dims)} buckets exist")
        d = bucket_dims[b]
        if not d.fits(p):
            n, e, nc = problem_shape(p)
            raise ValueError(
                f"validate_buckets: problem {i} with shape (n_nodes={n}, "
                f"n_edges={e}, n_comp={nc}, max_degree={problem_degree(p)}) "
                f"exceeds bucket {b} dims (n_nodes={d.n_nodes}, "
                f"n_edges={d.n_edges}, n_comp={d.n_comp}, "
                f"max_degree={d.places})")


def pad_problem(problem: ComputeProblem, dims: PadDims) -> PaddedProblem:
    """Embed one ComputeProblem into the fleet-wide padded shapes."""
    sp = StaticProblem.build(problem)
    N, E, NC = dims.n_nodes, dims.n_edges, dims.n_comp
    e, nc, deg = sp.edges.shape[0], sp.n_comp, sp.max_degree
    if sp.n_nodes > N or e > E or nc > NC or deg > dims.places:
        raise ValueError(
            f"pad_problem: instance shape (n_nodes={sp.n_nodes}, "
            f"n_edges={e}, n_comp={nc}, max_degree={deg}) exceeds pad dims "
            f"(n_nodes={N}, n_edges={E}, n_comp={NC}, "
            f"max_degree={dims.places}) — pass PadDims.of over every "
            f"problem in the batch (or its bucket)")

    edges = np.zeros((E, 2), np.int32)               # padding: self-loop (0,0)
    edges[:e] = sp.edges
    edge_cap = np.zeros((E,), np.float32)
    edge_cap[:e] = sp.edge_cap
    edge_mask = np.zeros((E,), np.float32)
    edge_mask[:e] = 1.0
    edge_places = np.zeros((E, 2), np.int32)
    edge_places[:e] = sp.edge_places

    comp_nodes = np.zeros((NC,), np.int32)           # padding: node 0, cap 0
    comp_nodes[:nc] = sp.comp_nodes
    comp_caps = np.zeros((NC,), np.float32)
    comp_caps[:nc] = sp.comp_caps
    comp_mask = np.zeros((NC,), np.float32)
    comp_mask[:nc] = 1.0

    sink = np.zeros((N, 3, NC), bool)
    sink[:sp.n_nodes, :, :nc] = sp.sink

    return PaddedProblem(
        n_nodes=N, n_comp=NC,
        edges=jnp.asarray(edges), edge_cap=jnp.asarray(edge_cap),
        s1=jnp.int32(sp.s1), s2=jnp.int32(sp.s2), dest=jnp.int32(sp.dest),
        comp_nodes=jnp.asarray(comp_nodes), comp_caps=jnp.asarray(comp_caps),
        sink=jnp.asarray(sink),
        edge_mask=jnp.asarray(edge_mask), comp_mask=jnp.asarray(comp_mask),
        edge_places=jnp.asarray(edge_places), max_degree=dims.places,
    )


def stack_problems(problems: Sequence[ComputeProblem],
                   dims: PadDims | None = None) -> PaddedProblem:
    """Pad + stack a fleet of problems into one batched PaddedProblem.

    Every leaf gains a leading batch axis; `vmap`/`shard_map` over the pytree
    then runs all instances inside a single compiled program.
    """
    dims = dims or PadDims.of(problems)
    padded = [pad_problem(p, dims) for p in problems]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *padded)
