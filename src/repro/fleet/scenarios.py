"""Scenario registry: parameterized topology generators + event/arrival models.

A `Scenario` bundles everything the fleet engine needs to spawn simulation
jobs: a topology factory (seed -> ComputeProblem), an arrival-process model,
a capacity event model (time-varying links / comp-node failure), and the
interference model (wired vs wireless).  Scenarios are registered by name so
sweeps are declared as data (`["paper_grid", "random_geometric", ...]`).

Event and arrival models are *online*: functions of (slot index, key) plus a
fixed-shape modulation state `ModState`, evaluated inside the scan body, so
a 10^6-slot horizon never materializes a [T]-shaped trace.  Memoryless
models ignore and pass through the state; Markov-modulated models
(Gilbert–Elliott link fading, ON-OFF bursty arrivals) update it — the
engine threads one `ModState` through the scan carry (DESIGN.md §4).  The
registry order is frozen into tuples (`ARRIVAL_MODEL_ORDER`,
`EVENT_MODEL_ORDER`) so per-job integer codes can drive a `lax.switch` —
heterogeneous scenarios share one compiled program.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import ComputeProblem, Graph, grid_graph, paper_grid_problem
from repro.sim import workload


class ModState(NamedTuple):
    """Markov-modulation state carried through the scan (O(E + NC) memory).

    Every event/arrival model receives and returns the full state so all
    `lax.switch` branches share one pytree signature; memoryless models pass
    it through untouched.

      link[e] : 1.0 = Good / 0.0 = Bad   (Gilbert–Elliott channel state)
      comp[n] : 1.0 = Up   / 0.0 = Down  (Gilbert–Elliott comp-node state)
      burst   : 1.0 = ON  / 0.0 = OFF    (Markov-modulated arrival phase)
    """

    link: jax.Array    # [E] float32
    comp: jax.Array    # [NC] float32
    burst: jax.Array   # [] float32

    @staticmethod
    def init(sp) -> "ModState":
        """All links Good, all comp nodes Up, arrivals ON — the chains mix
        within O(1/p) slots."""
        E = sp.edges.shape[-2]
        return ModState(jnp.ones((E,), jnp.float32),
                        jnp.ones((sp.n_comp,), jnp.float32),
                        jnp.ones((), jnp.float32))


# ---------------------------------------------------------------------------
# Arrival models: (key, lam, mod) -> (scalar arrivals, mod').  Memoryless
# models wrap the canonical [T]-trace law in repro.sim.workload with T=1 so
# the two stay in lockstep (same clipping rules, same batch defaults).
# ---------------------------------------------------------------------------

def _arrival_poisson(key: jax.Array, lam: jax.Array, mod: ModState):
    return workload.poisson_arrivals(key, lam, 1)[0], mod


def _arrival_bernoulli_batch(key: jax.Array, lam: jax.Array, mod: ModState):
    return workload.bernoulli_batch_arrivals(key, lam, 1)[0], mod


def _arrival_constant(key: jax.Array, lam: jax.Array, mod: ModState):
    return workload.constant_arrivals(lam, 1)[0], mod


# Markov ON-OFF (interrupted-Poisson) defaults: stationary P(ON) = 0.75,
# mean ON run 1/P_OFF = 20 slots, mean OFF run 1/P_ON ≈ 6.7 slots.
MMPP_P_ON_OFF = 0.05     # P(ON -> OFF) per slot
MMPP_P_OFF_ON = 0.15     # P(OFF -> ON) per slot


def _arrival_markov_onoff(key: jax.Array, lam: jax.Array, mod: ModState):
    """Markov-modulated ON-OFF Poisson arrivals (bursty, *correlated* load).

    A 2-state chain gates the query stream: while ON, arrivals are
    Poisson(lam / P(ON)); while OFF, none.  The long-run mean is exactly
    `lam`, so capacity sweeps are comparable with the memoryless models —
    only the correlation structure changes (mean burst length 1/P_OFF
    slots instead of 1)."""
    k_flip, k_arr = jax.random.split(key)
    pi_on = MMPP_P_OFF_ON / (MMPP_P_ON_OFF + MMPP_P_OFF_ON)
    u = jax.random.uniform(k_flip)
    on = jnp.where(mod.burst > 0.5,
                   (u >= MMPP_P_ON_OFF).astype(jnp.float32),
                   (u < MMPP_P_OFF_ON).astype(jnp.float32))
    arr = workload.poisson_arrivals(k_arr, lam / pi_on, 1)[0] * on
    return arr, mod._replace(burst=on)


ARRIVAL_MODELS: Dict[str, Callable] = {
    "poisson": _arrival_poisson,
    "bernoulli_batch": _arrival_bernoulli_batch,
    "constant": _arrival_constant,
    "markov_onoff": _arrival_markov_onoff,
}
ARRIVAL_MODEL_ORDER: Tuple[str, ...] = tuple(ARRIVAL_MODELS)


def arrival_code(name: str) -> int:
    return ARRIVAL_MODEL_ORDER.index(name)


# ---------------------------------------------------------------------------
# Event models: (problem, t, key, mod) -> (edge_scale [E], comp_scale [NC],
# mod').  `problem` is any StaticProblem/PaddedProblem duck type; scales
# multiply the static capacities for this slot only.
# ---------------------------------------------------------------------------

def _ones(sp):
    E = sp.edges.shape[-2]
    return jnp.ones((E,), jnp.float32), jnp.ones((sp.n_comp,), jnp.float32)


def _ev_static(sp, t: jax.Array, key: jax.Array, mod: ModState):
    es, cs = _ones(sp)
    return es, cs, mod


def _ev_fading(sp, t: jax.Array, key: jax.Array, mod: ModState,
               period: float = 200.0, depth: float = 0.35):
    """Deterministic per-link slow fading: capacity oscillates in
    [1 - 2*depth, 1] with an edge-dependent phase."""
    E = sp.edges.shape[-2]
    phase = jnp.arange(E, dtype=jnp.float32) / jnp.float32(max(E, 1))
    s = 1.0 - depth + depth * jnp.cos(
        2.0 * jnp.pi * (t.astype(jnp.float32) / period + phase))
    return s.astype(jnp.float32), _ones(sp)[1], mod


def _ev_link_flaps(sp, t: jax.Array, key: jax.Array, mod: ModState,
                   p_up: float = 0.9):
    """i.i.d. per-slot link outages: each edge is up w.p. `p_up`."""
    E = sp.edges.shape[-2]
    up = jax.random.bernoulli(key, p_up, (E,)).astype(jnp.float32)
    return up, _ones(sp)[1], mod


def _ev_comp_failures(sp, t: jax.Array, key: jax.Array, mod: ModState,
                      p_up: float = 0.9):
    """i.i.d. per-slot comp-node failure/recovery: node computes w.p. `p_up`.
    Failed nodes keep their queues (state is untouched) but combine nothing."""
    up = jax.random.bernoulli(key, p_up, (sp.n_comp,)).astype(jnp.float32)
    return _ones(sp)[0], up, mod


# Gilbert–Elliott defaults: stationary P(Bad) = P_GB/(P_GB+P_BG) ≈ 0.091,
# mean Bad run 1/P_BG = 5 slots, long-run mean capacity scale ≈ 0.93.
GE_P_GB = 0.02           # P(Good -> Bad) per slot, per link
GE_P_BG = 0.20           # P(Bad -> Good) per slot, per link
GE_BAD_SCALE = 0.25      # capacity multiplier while Bad

# Comp-node Gilbert–Elliott defaults: stationary P(Down) =
# P_UD/(P_UD+P_DU) = 0.0625, mean outage 1/P_DU ≈ 6.7 slots.  A Down node
# keeps its queues but combines nothing and is excluded from the
# load-balance argmin for the slot (mask gating, DESIGN.md §3).
GE_COMP_P_UD = 0.01      # P(Up -> Down) per slot, per comp node
GE_COMP_P_DU = 0.15      # P(Down -> Up) per slot, per comp node


def _ge_step(u: jax.Array, good: jax.Array, p_enter_bad: float,
             p_exit_bad: float) -> jax.Array:
    """One transition of independent 2-state Good/Bad chains.

    `good` is the current state as float (1.0 = Good/Up); `u` is uniform
    randomness of the same shape.  Returns the next state as float32."""
    return jnp.where(good > 0.5,
                     (u >= p_enter_bad).astype(jnp.float32),
                     (u < p_exit_bad).astype(jnp.float32))


def _ev_gilbert_elliott(sp, t: jax.Array, key: jax.Array, mod: ModState):
    """2-state Markov (Gilbert–Elliott) per-link fading.

    Each link runs an independent Good/Bad chain; Bad links keep only
    `GE_BAD_SCALE` of their capacity.  Unlike `link_flaps` the outages are
    *correlated in time* (mean Bad run 1/P_BG slots), the regime where
    backpressure's implicit re-routing matters — the chain state lives in
    `mod.link` and is updated here, inside the scan."""
    E = sp.edges.shape[-2]
    good = _ge_step(jax.random.uniform(key, (E,)), mod.link, GE_P_GB, GE_P_BG)
    scale = GE_BAD_SCALE + (1.0 - GE_BAD_SCALE) * good
    return scale, _ones(sp)[1], mod._replace(link=good)


def _ev_ge_comp(sp, t: jax.Array, key: jax.Array, mod: ModState):
    """Markov (Gilbert–Elliott) comp-node failures: each computation node
    runs an independent Up/Down chain in `mod.comp`.

    Unlike the i.i.d. `comp_failures` model, outages persist (mean Down run
    1/P_DU slots) — the regime of Benoit et al., *Resource Allocation
    Strategies for In-Network Stream Processing*, where the operative
    question is whether load balancing reroutes queries around a node that
    will stay dark for many slots.  The returned comp scale is 0/1; the
    engine's `with_capacity_scales` gates `comp_mask` with it, so a Down
    node combines nothing *and* never wins the load-balance argmin."""
    up = _ge_step(jax.random.uniform(key, (sp.n_comp,)), mod.comp,
                  GE_COMP_P_UD, GE_COMP_P_DU)
    return _ones(sp)[0], up, mod._replace(comp=up)


def _ev_ge_full(sp, t: jax.Array, key: jax.Array, mod: ModState):
    """Combined Markov dynamics: Gilbert–Elliott link fading *and* comp-node
    failures, both chains advancing every slot (independent randomness)."""
    k_link, k_comp = jax.random.split(key)
    link_scale, _, mod = _ev_gilbert_elliott(sp, t, k_link, mod)
    _, comp_up, mod = _ev_ge_comp(sp, t, k_comp, mod)
    return link_scale, comp_up, mod


# Scripted comp-node outage: one deterministic Gilbert–Elliott Down run
# with its endpoints pinned, so tests can assert shed/recover *timing*
# (the serving fault-injection test, tests/test_serving.py).  Node
# `OUTAGE_NODE` is Down for slots [OUTAGE_LO, OUTAGE_HI).
OUTAGE_NODE = 0
OUTAGE_LO = 1024
OUTAGE_HI = 1536


def _ev_outage_window(sp, t: jax.Array, key: jax.Array, mod: ModState):
    down = (t >= OUTAGE_LO) & (t < OUTAGE_HI)
    up = jnp.ones((sp.n_comp,), jnp.float32).at[OUTAGE_NODE].set(
        jnp.where(down, 0.0, 1.0))
    return _ones(sp)[0], up, mod


EVENT_MODELS: Dict[str, Callable] = {
    "static": _ev_static,
    "fading": _ev_fading,
    "link_flaps": _ev_link_flaps,
    "comp_failures": _ev_comp_failures,
    "gilbert_elliott": _ev_gilbert_elliott,
    "ge_comp": _ev_ge_comp,
    "ge_full": _ev_ge_full,
    "outage_window": _ev_outage_window,   # appended: switch codes are frozen
}
EVENT_MODEL_ORDER: Tuple[str, ...] = tuple(EVENT_MODELS)


def event_code(name: str) -> int:
    return EVENT_MODEL_ORDER.index(name)


# ---------------------------------------------------------------------------
# Topology generators.  All are (seed, **params) -> ComputeProblem with
# sources/dest/comp-node placement chosen by simple degree/eccentricity
# heuristics so every instance is feasible (connected, lam* > 0).
# ---------------------------------------------------------------------------

def _place(graph: Graph, n_comp: int, C: float,
           rng: np.random.Generator) -> ComputeProblem:
    """Pick s1/s2 far apart, dest far from both, comp nodes by degree."""
    n = graph.n_nodes
    deg = np.zeros(n, np.int64)
    for m, l in graph.edges:
        deg[m] += 1
        deg[l] += 1
    # BFS eccentricity from a random start to find a far pair.
    adj = [[] for _ in range(n)]
    for m, l in graph.edges:
        adj[m].append(int(l))
        adj[l].append(int(m))

    def bfs(src):
        dist = np.full(n, -1)
        dist[src] = 0
        q = [src]
        while q:
            u = q.pop(0)
            for v in adj[u]:
                if dist[v] < 0:
                    dist[v] = dist[u] + 1
                    q.append(v)
        return dist

    s1 = int(rng.integers(n))
    d1 = bfs(s1)
    s2 = int(np.argmax(d1))
    d2 = bfs(s2)
    dest = int(np.argmax(d1 + d2))
    if dest in (s1, s2):
        dest = int(np.argsort(-(d1 + d2))[1])
    # highest-degree nodes (excluding endpoints) host computation
    order = np.argsort(-deg)
    comp = [int(u) for u in order if u not in (s1, s2, dest)][:n_comp]
    if len(comp) < n_comp:                       # tiny graphs: allow overlap
        comp += [int(u) for u in order if int(u) not in comp][:n_comp - len(comp)]
    return ComputeProblem(graph, s1, s2, dest,
                          tuple(comp), (C,) * len(comp))


def random_geometric(seed: int, n: int = 14, radius: float = 0.42,
                     cap: float = 4.0, n_comp: int = 3,
                     C: float = 2.0) -> ComputeProblem:
    """Random geometric graph in the unit square; a chain over x-sorted nodes
    is added so the graph is always connected."""
    rng = np.random.default_rng(seed)
    pts = rng.uniform(size=(n, 2))
    order = np.argsort(pts[:, 0])
    edges = set()
    for i in range(n):
        for j in range(i + 1, n):
            if np.linalg.norm(pts[i] - pts[j]) <= radius:
                edges.add((min(i, j), max(i, j)))
    for a, b in zip(order[:-1], order[1:]):      # connectivity backbone
        edges.add((min(int(a), int(b)), max(int(a), int(b))))
    e = np.array(sorted(edges), np.int32)
    g = Graph(n, e, np.full(len(e), cap))
    return _place(g, n_comp, C, rng)


def ring(seed: int, n: int = 12, cap: float = 4.0, n_comp: int = 3,
         C: float = 2.0) -> ComputeProblem:
    e = np.array([(i, (i + 1) % n) for i in range(n)], np.int32)
    g = Graph(n, e, np.full(n, cap))
    return _place(g, n_comp, C, np.random.default_rng(seed))


def balanced_tree(seed: int, branch: int = 2, depth: int = 3, cap: float = 4.0,
                  n_comp: int = 3, C: float = 2.0) -> ComputeProblem:
    """Complete `branch`-ary tree of the given depth."""
    edges, nodes = [], 1
    frontier = [0]
    for _ in range(depth):
        nxt = []
        for u in frontier:
            for _ in range(branch):
                edges.append((u, nodes))
                nxt.append(nodes)
                nodes += 1
        frontier = nxt
    e = np.array(edges, np.int32)
    g = Graph(nodes, e, np.full(len(e), cap))
    return _place(g, n_comp, C, np.random.default_rng(seed))


def expander(seed: int, n: int = 14, cap: float = 4.0, n_comp: int = 3,
             C: float = 2.0) -> ComputeProblem:
    """Circulant expander: ring + chord offsets (2, n//2 - 1) + random chords."""
    rng = np.random.default_rng(seed)
    edges = set()
    for off in (1, 2, max(n // 2 - 1, 3)):
        for i in range(n):
            j = (i + off) % n
            if i != j:
                edges.add((min(i, j), max(i, j)))
    for _ in range(n // 3):                      # extra random chords
        i, j = rng.integers(n), rng.integers(n)
        if i != j:
            edges.add((min(int(i), int(j)), max(int(i), int(j))))
    e = np.array(sorted(edges), np.int32)
    g = Graph(n, e, np.full(len(e), cap))
    return _place(g, n_comp, C, rng)


def fat_tree(seed: int, pods: int = 2, hosts_per_edge: int = 2,
             core_cap: float = 8.0, agg_cap: float = 4.0,
             host_cap: float = 4.0, C: float = 2.0) -> ComputeProblem:
    """Miniature datacenter fat-tree: core -> per-pod agg -> edge -> hosts.
    Computation lives in the aggregation layer (in-network processing)."""
    edges, caps = [], []
    core, n = 0, 1                # node 0 is the single core of the mini tree
    aggs, hosts = [], []
    for _ in range(pods):
        agg, n = n, n + 1
        aggs.append(agg)
        edges.append((core, agg))
        caps.append(core_cap)
        for _ in range(2):
            sw, n = n, n + 1
            edges.append((agg, sw))
            caps.append(agg_cap)
            for _ in range(hosts_per_edge):
                h, n = n, n + 1
                hosts.append(h)
                edges.append((sw, h))
                caps.append(host_cap)
    g = Graph(n, np.array(edges, np.int32), np.array(caps))
    s1, s2 = int(hosts[0]), int(hosts[-1])       # opposite pods
    dest = int(hosts[len(hosts) // 2])
    if dest in (s1, s2):
        dest = int(hosts[1])
    return ComputeProblem(g, s1, s2, dest, tuple(aggs), (C,) * len(aggs))


def kary_fat_tree(k: int, R: float = 5.0) -> ComputeProblem:
    """The k-ary fat-tree switch fabric of Al-Fares et al. (SIGCOMM 2008,
    §3), computing at its aggregation layer, with the query's three hosts.

    Nodes, in order: the (k/2)² core switches; then pod by pod its k/2
    aggregation switches and its k/2 edge switches; then the hosts s1
    (first host of pod 0), s2 (first host of pod k/2) and the sink (first
    host of pod k-1), each on the first edge switch of its pod.  Links, in
    order: aggregation switch i of each pod to cores i·k/2 … i·k/2+k/2-1
    (pod by pod, switch by switch), then every edge switch of a pod to
    every aggregation switch of it (pod, aggregation, edge), then the three
    host links; every link carries R.  Every aggregation switch computes
    at C_n = R/(k²/2), so the layer's total compute equals one access
    link.  The other hosts neither send nor receive, carry no flow in any
    LP optimum, and are left out (a server does not forward).
    """
    if k < 4 or k % 2:
        raise ValueError(f"k must be even and at least 4, got {k}")
    h = k // 2
    n_core = h * h

    def agg(p, i):
        return n_core + p * k + i

    def edge(p, j):
        return n_core + p * k + h + j

    edges = [(i * h + c, agg(p, i))
             for p in range(k) for i in range(h) for c in range(h)]
    edges += [(agg(p, i), edge(p, j))
              for p in range(k) for i in range(h) for j in range(h)]
    s1, s2, sink = n_core + k * k + np.arange(3)
    edges += [(edge(0, 0), s1), (edge(h, 0), s2), (edge(k - 1, 0), sink)]
    g = Graph(int(sink) + 1, np.array(edges, np.int32),
              np.full(len(edges), R))
    comp = tuple(agg(p, i) for p in range(k) for i in range(h))
    return ComputeProblem(g, int(s1), int(s2), int(sink), comp,
                          (R / (k * k / 2),) * len(comp))


def wireless_grid(seed: int, rows: int = 4, cols: int = 4, cap: float = 5.0,
                  C: float = 2.0) -> ComputeProblem:
    """The paper-§IV-C setting: grid graph under node-exclusive interference
    (pair with `wireless=True` in the scenario)."""
    g = grid_graph(rows, cols, cap)
    rng = np.random.default_rng(seed)
    return _place(g, n_comp=4, C=C, rng=rng)


# ---------------------------------------------------------------------------
# Scenario registry.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Scenario:
    name: str
    factory: Callable[[int], ComputeProblem]     # topo seed -> problem
    arrival: str = "poisson"                     # ARRIVAL_MODELS key
    events: str = "static"                       # EVENT_MODELS key
    wireless: bool = False
    description: str = ""

    def build(self, topo_seed: int = 0) -> ComputeProblem:
        return self.factory(topo_seed)


SCENARIOS: Dict[str, Scenario] = {}


def register_scenario(s: Scenario) -> Scenario:
    if s.name in SCENARIOS:
        raise ValueError(f"scenario {s.name!r} already registered")
    if s.arrival not in ARRIVAL_MODELS:
        raise ValueError(f"unknown arrival model {s.arrival!r}")
    if s.events not in EVENT_MODELS:
        raise ValueError(f"unknown event model {s.events!r}")
    SCENARIOS[s.name] = s
    return s


def get_scenario(name: str) -> Scenario:
    try:
        return SCENARIOS[name]
    except KeyError:
        raise KeyError(
            f"unknown scenario {name!r}; known: {sorted(SCENARIOS)}") from None


def list_scenarios() -> list[str]:
    return sorted(SCENARIOS)


register_scenario(Scenario(
    "paper_grid", lambda seed: paper_grid_problem(),
    description="The paper's 4x4 grid (Fig. 5a), C=2, R=5."))
register_scenario(Scenario(
    "random_geometric", random_geometric,
    description="Random geometric graph, degree-placed comp nodes."))
register_scenario(Scenario(
    "ring", ring, description="Cycle topology; worst-case path diversity."))
register_scenario(Scenario(
    "tree", balanced_tree,
    description="Complete binary tree; single-path routing stress."))
register_scenario(Scenario(
    "expander", expander,
    description="Circulant expander + random chords; high conductance."))
register_scenario(Scenario(
    "fat_tree", fat_tree, arrival="bernoulli_batch",
    description="Mini datacenter fat-tree; bursty arrivals, agg-layer compute."))
register_scenario(Scenario(
    "fattree_k8", lambda seed: kary_fat_tree(8),
    description="Al-Fares k=8 fat-tree fabric (80 switches, 256 links) "
                "computing at its 32 aggregation switches; three query "
                "hosts."))
register_scenario(Scenario(
    "wireless_grid", wireless_grid, wireless=True,
    description="Grid under node-exclusive interference (greedy matching)."))
register_scenario(Scenario(
    "fading_geometric", random_geometric, events="fading",
    description="Random geometric graph with sinusoidal link fading."))
register_scenario(Scenario(
    "flaky_expander", expander, events="link_flaps",
    description="Expander with i.i.d. per-slot link outages."))
register_scenario(Scenario(
    "failing_grid", lambda seed: paper_grid_problem(), events="comp_failures",
    description="Paper grid with comp-node failure/recovery."))
register_scenario(Scenario(
    "ge_grid", lambda seed: paper_grid_problem(), events="gilbert_elliott",
    description="Paper grid under Gilbert–Elliott (Markov) link fading."))
register_scenario(Scenario(
    "ge_geometric", random_geometric, events="gilbert_elliott",
    description="Random geometric graph under Gilbert–Elliott link fading."))
register_scenario(Scenario(
    "bursty_grid", lambda seed: paper_grid_problem(), arrival="markov_onoff",
    description="Paper grid with Markov ON-OFF (correlated bursty) arrivals."))
register_scenario(Scenario(
    "ge_comp_grid", lambda seed: paper_grid_problem(), events="ge_comp",
    description="Paper grid with Markov (Gilbert–Elliott) comp-node "
                "failures: outages persist for ~1/P_DU slots."))
register_scenario(Scenario(
    "ge_full_grid", lambda seed: paper_grid_problem(), events="ge_full",
    description="Paper grid under combined Markov link fading AND "
                "comp-node failures."))
register_scenario(Scenario(
    "outage_grid", lambda seed: paper_grid_problem(), events="outage_window",
    description="Paper grid with a scripted comp-node outage in slots "
                "[OUTAGE_LO, OUTAGE_HI) — deterministic fault-injection "
                "for the serving shed/recover test."))
