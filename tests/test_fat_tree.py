"""The k-ary fat-tree fabric (`kary_fat_tree`, scenario `fattree_k8`).

Al-Fares et al. (SIGCOMM 2008, §3): (k/2)² core switches and k pods of
k/2 aggregation and k/2 edge switches; aggregation switch i of a pod
reaches cores i·k/2 … i·k/2+k/2-1, and every edge switch reaches every
aggregation switch of its pod.  The query's three hosts hang off the
first edge switch of pods 0, k/2 and k-1, and computation sits at every
aggregation switch.  The benchmark's configuration file must list the
very nodes and links the program builds.
"""
import json
import pathlib

import numpy as np
import pytest

from repro.fleet import get_scenario
from repro.fleet.scenarios import kary_fat_tree

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _layers(k):
    h = k // 2
    core = list(range(h * h))
    agg = [h * h + p * k + i for p in range(k) for i in range(h)]
    edge = [h * h + p * k + h + j for p in range(k) for j in range(h)]
    return core, agg, edge


def _pod(k, switch):
    return (switch - (k // 2) ** 2) // k


@pytest.mark.parametrize("k", [4, 8])
def test_switch_counts_and_degrees(k):
    p = kary_fat_tree(k)
    core, agg, edge = _layers(k)
    h = k // 2
    assert (len(core), len(agg), len(edge)) == (h * h, k * h, k * h)
    assert p.graph.n_nodes == len(core) + len(agg) + len(edge) + 3
    assert p.graph.n_edges == 2 * k * h * h + 3
    deg = np.bincount(p.graph.edges.ravel(), minlength=p.graph.n_nodes)
    assert (deg[core] == k).all()            # one link into every pod
    assert (deg[agg] == k).all()             # k/2 up, k/2 down
    hosts = {p.s1, p.s2, p.dest}
    assert len(hosts) == 3 and (deg[sorted(hosts)] == 1).all()
    carries = np.bincount([int(a) for a, b in p.graph.edges if b in hosts],
                          minlength=p.graph.n_nodes)
    assert (deg[edge] == h + carries[edge]).all()   # k/2 up, its hosts
    assert carries[edge].sum() == 3
    # wiring: aggregation switch i of a pod to cores i*h .. i*h+h-1 only,
    # edge switches to the aggregation switches of their own pod only
    links = {tuple(sorted(map(int, e))) for e in p.graph.edges}
    for a in agg:
        i = (a - h * h) % k
        assert {c for c in core if (c, a) in links} == \
            set(range(i * h, i * h + h))
        assert {e for e in edge if (a, e) in links} == \
            {e for e in edge if _pod(k, e) == _pod(k, a)}
    assert np.all(p.graph.capacity == 5.0)


@pytest.mark.parametrize("k", [4, 8])
def test_query_hosts_pods_and_comp_layer(k):
    p = kary_fat_tree(k)
    core, agg, edge = _layers(k)
    attach = {int(b): int(a) for a, b in p.graph.edges
              if b in (p.s1, p.s2, p.dest)}
    # the first edge switch of pods 0, k/2 and k-1
    assert [attach[n] for n in (p.s1, p.s2, p.dest)] == \
        [edge[0], edge[(k // 2) * (k // 2)], edge[(k - 1) * (k // 2)]]
    assert [_pod(k, attach[n]) for n in (p.s1, p.s2, p.dest)] == \
        [0, k // 2, k - 1]
    assert list(p.comp_nodes) == agg
    assert p.comp_caps == (5.0 / (k * k / 2),) * len(agg)
    assert sum(p.comp_caps) == pytest.approx(5.0)


def test_odd_or_small_k_is_refused():
    for k in (3, 2, 0):
        with pytest.raises(ValueError):
            kary_fat_tree(k)


def test_registered_scenario_is_k8_static_poisson_wired():
    s = get_scenario("fattree_k8")
    assert (s.arrival, s.events, s.wireless) == ("poisson", "static", False)
    a, b = s.build(0), kary_fat_tree(8)
    assert np.array_equal(a.graph.edges, b.graph.edges)
    assert s.build(3).graph.n_edges == 259


def test_configuration_file_lists_what_the_program_builds():
    cfg = json.loads((ROOT / "perfbench" / "configs"
                      / "fattree_k8.json").read_text())
    p = kary_fat_tree(8)
    assert cfg["n_nodes"] == p.graph.n_nodes == 83
    assert cfg["edges"] == p.graph.edges.tolist()
    assert len(cfg["edges"]) == 259
    assert cfg["link_capacity"] == 5.0
    assert cfg["sources"] == [p.s1, p.s2] and cfg["sink"] == p.dest
    assert cfg["comp_nodes"] == list(p.comp_nodes)
    assert cfg["comp_capacity"] == p.comp_caps[0] == 0.15625
    assert cfg["lambda_star"] == 5.0
    assert cfg["interference"] == "none" and cfg["reduced"] == []
