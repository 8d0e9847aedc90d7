"""Graph engine validation vs networkx on a one-device graph mesh (the
multi-device variants live in test_multidevice.py's subprocesses)."""
import networkx as nx
import numpy as np
import pytest

from repro.core import baselines, ordering
from repro.core.graph import Graph, rmat_graph
from repro.graphs import engine as E
from repro.launch import mesh as MM


@pytest.fixture(scope="module")
def small():
    g = rmat_graph(6, 4, seed=5)
    nxg = nx.Graph()
    nxg.add_nodes_from(range(g.num_vertices))
    nxg.add_edges_from(zip(g.src.tolist(), g.dst.tolist()))
    return g, nxg


@pytest.fixture(scope="module")
def mesh1():
    return MM.make_graph_mesh(1)


def _data(g, k=4):
    order = ordering.geo_order(g, seed=0)
    return E.cep_engine_data(g, order, k)


def test_pagerank_matches_networkx(small, mesh1):
    g, nxg = small
    data = E.shard_engine_data(_data(g), mesh1)
    pr = np.asarray(E.pagerank(data, iterations=50))
    want = nx.pagerank(nxg, alpha=0.85, max_iter=100, tol=1e-10)
    want_v = np.array([want[i] for i in range(g.num_vertices)])
    np.testing.assert_allclose(pr, want_v, rtol=5e-3, atol=1e-5)


def test_sssp_matches_networkx(small, mesh1):
    g, nxg = small
    data = E.shard_engine_data(_data(g), mesh1)
    dist, iters = E.sssp(data, source=0)
    lengths = nx.single_source_shortest_path_length(nxg, 0)
    got = np.asarray(dist)
    for v in range(g.num_vertices):
        if v in lengths:
            assert got[v] == pytest.approx(lengths[v]), v
        else:
            assert got[v] > 1e8
    assert iters > 0


def test_wcc_matches_networkx(small, mesh1):
    g, nxg = small
    data = E.shard_engine_data(_data(g), mesh1)
    lab, _ = E.wcc(data)
    lab = np.asarray(lab).astype(np.int64)
    comps = list(nx.connected_components(nxg))
    for comp in comps:
        ls = {lab[v] for v in comp}
        assert len(ls) == 1, "component must share one label"
    # Distinct components get distinct labels.
    reps = [lab[next(iter(c))] for c in comps]
    assert len(set(reps)) == len(comps)


def test_geo_partition_has_fewer_mirrors_than_hash(small, mesh1):
    g, _ = small
    k = 8
    geo = _data(g, k)
    hsh = E.build_engine_data(g, baselines.hash_1d(g, k), k)
    assert geo.mirrors < hsh.mirrors
    assert E.comm_volume_per_iteration(geo) < E.comm_volume_per_iteration(hsh)


def test_pagerank_invariant_to_partitioning(small, mesh1):
    """Results must not depend on how edges are partitioned (engine soundness)."""
    g, _ = small
    d1 = E.shard_engine_data(_data(g, 2), mesh1)
    d2 = E.shard_engine_data(E.build_engine_data(g, baselines.hash_1d(g, 7), 7), mesh1)
    p1 = np.asarray(E.pagerank(d1, iterations=30))
    p2 = np.asarray(E.pagerank(d2, iterations=30))
    np.testing.assert_allclose(p1, p2, rtol=1e-5, atol=1e-8)


def test_sssp_new_root_compiles_nothing(small, mesh1):
    """A second search on the same pack shape, from another root, reuses the
    compiled program: the root is an operand, not a constant of the trace."""
    from repro.obs import trace as OT

    g, nxg = small
    data = E.shard_engine_data(_data(g), mesh1)
    tr = OT.Tracer()
    # max_iters=23 is a program key no other test builds, so the first call
    # must compile and the count below is known to see compiles.
    with tr.span("first"):
        E.sssp(data, source=0, max_iters=23)
    with tr.span("second"):
        dist, _ = E.sssp(data, source=5, max_iters=23)
    counts = {s.name: s.counts or {} for s in tr.spans()}
    assert counts["first"].get("compiles", 0) + counts["first"].get("cache_loads", 0) >= 1
    assert counts["second"].get("compiles", 0) == 0
    assert counts["second"].get("cache_loads", 0) == 0
    lengths = nx.single_source_shortest_path_length(nxg, 5)
    assert all(np.asarray(dist)[v] == d for v, d in lengths.items())
