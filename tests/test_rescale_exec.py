"""ElasticRescaler: executed k_old → k_new migration ≡ from-scratch packing,
with exactly ScalePlan.migrated_bytes of cross-partition traffic."""
import numpy as np
import pytest

from repro.core import baselines, cep, ordering
from repro.core.graph import rmat_graph
from repro.elastic import controller as ec
from repro.elastic.rescale_exec import ElasticRescaler
from repro.graphs import engine as E
from repro.graphs.engine import EDGE_BYTES
from repro.launch import mesh as MM


@pytest.fixture(scope="module")
def ordered():
    g = rmat_graph(8, 6, seed=0)
    order = ordering.geo_order(g, seed=0)
    return g, g.src[order], g.dst[order]


@pytest.fixture(scope="module")
def rescaler():
    return ElasticRescaler()


@pytest.fixture(scope="module")
def graph_mesh():
    return MM.make_graph_mesh(1)


# Scale-out and scale-in, including non-adjacent k and co-prime pairs.
PAIRS = [(8, 12), (12, 8), (4, 5), (5, 4), (16, 20), (20, 16), (3, 7), (2, 3)]


@pytest.mark.parametrize("k_old,k_new", PAIRS)
def test_executed_equals_from_scratch(ordered, rescaler, graph_mesh, k_old, k_new):
    g, src, dst = ordered
    data = E.pack_ordered_sharded(src, dst, g.num_vertices, k_old, graph_mesh)
    plan = cep.scale_plan(g.num_edges, k_old, k_new)
    new, stats = rescaler.execute(data, plan, verify=True)
    want = E.pack_ordered(src, dst, g.num_vertices, k_new)
    got = E.unshard_engine_data(new)
    np.testing.assert_array_equal(got.edges, want.edges)
    np.testing.assert_array_equal(got.mask, want.mask)
    np.testing.assert_array_equal(got.degrees, want.degrees)
    assert new.k == k_new and new.num_edges == g.num_edges
    # Metrics re-check must agree with the from-scratch pack's quality numbers.
    assert new.mirrors == want.mirrors
    assert new.replication_factor == pytest.approx(want.replication_factor, abs=0)
    assert stats.oracle_checked


@pytest.mark.parametrize("k_old,k_new", PAIRS)
def test_bytes_copied_equal_plan_migrated_bytes(ordered, rescaler, graph_mesh, k_old, k_new):
    g, src, dst = ordered
    data = E.pack_ordered_sharded(src, dst, g.num_vertices, k_old, graph_mesh)
    plan = cep.scale_plan(g.num_edges, k_old, k_new)
    _, stats = rescaler.execute(data, plan)
    assert stats.migrated_edges == plan.migrated_edges
    assert stats.migrated_bytes == plan.migrated_bytes(EDGE_BYTES)
    # Moved + stayed rows account for every edge exactly once.
    assert stats.migrated_edges + stats.stay_edges == g.num_edges
    # The program is O(overlay ranges), never O(|E|).
    assert stats.copy_ops <= k_old + k_new


def test_roundtrip_bit_identical(ordered, rescaler, graph_mesh):
    g, src, dst = ordered
    d8 = E.pack_ordered_sharded(src, dst, g.num_vertices, 8, graph_mesh)
    d12, _ = rescaler.rescale(d8, 12, verify=True)
    back, _ = rescaler.rescale(d12, 8, verify=True)
    orig = E.pack_ordered(src, dst, g.num_vertices, 8)
    got = E.unshard_engine_data(back)
    np.testing.assert_array_equal(got.edges, orig.edges)
    np.testing.assert_array_equal(got.mask, orig.mask)
    assert back.mirrors == orig.mirrors


def test_degenerate_more_partitions_than_edges(rescaler, graph_mesh):
    g = rmat_graph(4, 1, seed=2)  # tiny: |E| can be < k_new
    order = np.arange(g.num_edges)
    src, dst = g.src[order], g.dst[order]
    k_new = g.num_edges + 5
    data = E.pack_ordered_sharded(src, dst, g.num_vertices, 2, graph_mesh)
    new, _ = rescaler.rescale(data, k_new, verify=True)
    want = E.pack_ordered(src, dst, g.num_vertices, k_new)
    np.testing.assert_array_equal(E.unshard_engine_data(new).edges, want.edges)


def test_rejects_non_cep_layout(ordered, rescaler, graph_mesh):
    g, _, _ = ordered
    hashed = E.shard_engine_data(E.build_engine_data(g, baselines.hash_1d(g, 4), 4), graph_mesh)
    with pytest.raises(ValueError, match="not CEP-chunked"):
        rescaler.rescale(hashed, 5)


def test_rejects_mismatched_plan(ordered, rescaler, graph_mesh):
    g, src, dst = ordered
    data = E.pack_ordered_sharded(src, dst, g.num_vertices, 4, graph_mesh)
    with pytest.raises(ValueError, match="k_old"):
        rescaler.execute(data, cep.scale_plan(g.num_edges, 5, 6))
    with pytest.raises(ValueError, match=r"\|E\|"):
        rescaler.execute(data, cep.scale_plan(g.num_edges + 1, 4, 5))


def test_unpack_ordered_roundtrip(ordered):
    g, src, dst = ordered
    data = E.pack_ordered(src, dst, g.num_vertices, 7)
    s2, d2 = E.unpack_ordered(data)
    np.testing.assert_array_equal(s2, src)
    np.testing.assert_array_equal(d2, dst)


def test_controller_executes_attached_engine(ordered, graph_mesh):
    g, src, dst = ordered
    t = [0.0]
    ctl = ec.ElasticController(4, dead_after_s=5.0, clock=lambda: t[0])
    ctl.attach_engine(E.pack_ordered_sharded(src, dst, g.num_vertices, 4, graph_mesh))
    t[0] = 1.0
    for h in (0, 1, 2):
        ctl.heartbeat(h, 1)
    t[0] = 5.6  # host 3 missed its beat; 0-2 are fresh
    ev = ctl.poll()
    assert ev is not None and ev.kind == "scale_in" and ev.executed
    assert ctl.engine_data.k == 3
    want = E.pack_ordered(src, dst, g.num_vertices, 3)
    np.testing.assert_array_equal(E.unshard_engine_data(ctl.engine_data).edges, want.edges)
    assert ctl.rescale_stats[0].migrated_edges == cep.migrated_edges_exact(g.num_edges, 4, 3)
    # Executed events report the fraction actually migrated, not the
    # synthetic state_elements model.
    assert ev.plan_edges_moved_frac == pytest.approx(
        ctl.rescale_stats[0].migrated_edges / g.num_edges
    )


def test_controller_without_engine_still_plans_only():
    t = [0.0]
    ctl = ec.ElasticController(3, dead_after_s=5.0, clock=lambda: t[0])
    t[0] = 1.0
    ctl.heartbeat(0, 1)
    ctl.heartbeat(1, 1)
    t[0] = 5.6
    ev = ctl.poll()
    assert ev is not None and not ev.executed and ctl.engine_data is None


def test_rescaled_engine_runs_pagerank(ordered, graph_mesh):
    """The migrated pack is live engine state, not just buffers."""
    g, src, dst = ordered
    d4 = E.pack_ordered_sharded(src, dst, g.num_vertices, 4, graph_mesh)
    p4 = np.asarray(E.pagerank(d4, iterations=20))  # before: d4 is donated
    d6, _ = ElasticRescaler().rescale(d4, 6)
    p6 = np.asarray(E.pagerank(d6, iterations=20))
    np.testing.assert_allclose(p4, p6, rtol=1e-5, atol=1e-8)


def test_recheck_false_skips_host_metrics(ordered, rescaler, graph_mesh):
    g, src, dst = ordered
    data = E.pack_ordered_sharded(src, dst, g.num_vertices, 4, graph_mesh)
    new, stats = rescaler.rescale(data, 6, recheck=False)
    assert new.mirrors == -1 and np.isnan(new.replication_factor)
    assert stats.recheck_s == 0.0 or stats.recheck_s < 1e-3
    # Buffers are still the real migration result.
    want = E.pack_ordered(src, dst, g.num_vertices, 6)
    np.testing.assert_array_equal(E.unshard_engine_data(new).edges, want.edges)


def test_noop_rescale_returns_same_buffers(graph_mesh):
    g = rmat_graph(6, 4, seed=1)
    order = np.arange(g.num_edges)
    data = E.pack_ordered_sharded(g.src[order], g.dst[order], g.num_vertices, 3, graph_mesh)
    new, stats = ElasticRescaler().rescale(data, 3)
    assert new is data and stats.migrated_edges == 0 and stats.copy_ops == 0
    np.asarray(new.edges)  # must NOT have been donated away


def test_program_cache_is_lru_bounded(ordered, graph_mesh):
    g, src, dst = ordered
    r = ElasticRescaler(program_cache_size=2)
    for k_old, k_new in [(4, 5), (5, 6), (6, 7)]:  # 3 distinct program keys
        data = E.pack_ordered_sharded(src, dst, g.num_vertices, k_old, graph_mesh)
        r.rescale(data, k_new, verify=True)
    assert len(r._programs) == 2
    # (4, 5) was evicted (LRU); re-executing it retraces and still verifies.
    # Keys are kind-prefixed: ("migrate", n, k_old, k_new, mesh).
    keys = list(r._programs)
    assert all(key[0] == "migrate" and key[2:4] != (4, 5) for key in keys)
    data = E.pack_ordered_sharded(src, dst, g.num_vertices, 4, graph_mesh)
    _, stats = r.rescale(data, 5, verify=True)
    assert stats.oracle_checked and len(r._programs) == 2
    # A cache hit refreshes recency instead of evicting.
    data = E.pack_ordered_sharded(src, dst, g.num_vertices, 4, graph_mesh)
    r.rescale(data, 5)
    assert len(r._programs) == 2 and list(r._programs)[-1][2:4] == (4, 5)


def test_program_cache_size_validation():
    with pytest.raises(ValueError, match="program_cache_size"):
        ElasticRescaler(program_cache_size=0)


# ----------------------- sharded path, degenerate mesh of 1 (tier-1 safe) ----
def test_single_device_stats_have_no_cross_device_traffic(ordered, rescaler, graph_mesh):
    g, src, dst = ordered
    data = E.pack_ordered_sharded(src, dst, g.num_vertices, 8, graph_mesh)
    _, stats = rescaler.rescale(data, 12)
    assert stats.devices == 1 and stats.cross_device_edges == 0
    assert stats.on_device_edges == stats.migrated_edges


@pytest.mark.parametrize("k_old,k_new", [(8, 12), (12, 8), (3, 7)])
def test_sharded_mesh1_bit_identical(ordered, rescaler, graph_mesh, k_old, k_new):
    """Mesh of 1 is the degenerate case of the sharded path, not a fork: the
    executed migration must still match the single-device oracle bit-for-bit."""
    g, src, dst = ordered
    sdata = E.pack_ordered_sharded(src, dst, g.num_vertices, k_old, graph_mesh)
    new, stats = rescaler.rescale(sdata, k_new, verify=True)
    assert isinstance(new, E.ShardedEngineData) and new.k == k_new
    assert stats.devices == 1 and stats.cross_device_edges == 0
    want = E.pack_ordered(src, dst, g.num_vertices, k_new)
    got = E.unshard_engine_data(new)
    np.testing.assert_array_equal(got.edges, want.edges)
    np.testing.assert_array_equal(got.mask, want.mask)


def test_sharded_roundtrip_bit_identical(ordered, rescaler, graph_mesh):
    g, src, dst = ordered
    d8 = E.pack_ordered_sharded(src, dst, g.num_vertices, 8, graph_mesh)
    d12, _ = rescaler.rescale(d8, 12, verify=True)
    back, _ = rescaler.rescale(d12, 8, verify=True)
    orig = E.pack_ordered(src, dst, g.num_vertices, 8)
    got = E.unshard_engine_data(back)
    np.testing.assert_array_equal(got.edges, orig.edges)
    np.testing.assert_array_equal(got.mask, orig.mask)


def test_sharded_noop_returns_same_object(ordered, graph_mesh):
    g, src, dst = ordered
    sdata = E.pack_ordered_sharded(src, dst, g.num_vertices, 4, graph_mesh)
    new, stats = ElasticRescaler().rescale(sdata, 4)
    assert new is sdata and stats.copy_ops == 0 and stats.devices == 1
    np.asarray(new.edges)  # must NOT have been donated away


def test_sharded_more_partitions_than_edges(graph_mesh):
    g = rmat_graph(4, 1, seed=2)  # tiny: |E| < k_new ⇒ zero-size chunks
    order = np.arange(g.num_edges)
    src, dst = g.src[order], g.dst[order]
    k_new = g.num_edges + 5
    sdata = E.pack_ordered_sharded(src, dst, g.num_vertices, 2, graph_mesh)
    new, stats = ElasticRescaler().rescale(sdata, k_new, verify=True)
    assert stats.oracle_checked and new.k == k_new
    want = E.pack_ordered(src, dst, g.num_vertices, k_new)
    got = E.unshard_engine_data(new)
    np.testing.assert_array_equal(got.edges, want.edges)


def test_sharded_rejects_non_cep_layout(ordered, graph_mesh):
    g, _, _ = ordered
    hashed = E.build_engine_data(g, baselines.hash_1d(g, 4), 4)
    sdata = E.shard_engine_data(hashed, graph_mesh)
    with pytest.raises(ValueError, match="not CEP-chunked"):
        ElasticRescaler().rescale(sdata, 5)


def test_sharded_rescaled_engine_runs_pagerank(ordered, graph_mesh):
    g, src, dst = ordered
    d4 = E.pack_ordered_sharded(src, dst, g.num_vertices, 4, graph_mesh)
    d6, _ = ElasticRescaler().rescale(d4, 6)
    p_sharded = np.asarray(E.pagerank(d6, iterations=20))  # mesh from the data
    # The host oracle at k=6, committed afresh: same answer as the migrated pack.
    ref = E.pack_ordered_sharded(src, dst, g.num_vertices, 6, graph_mesh)
    p_ref = np.asarray(E.pagerank(ref, iterations=20))
    np.testing.assert_allclose(p_sharded, p_ref, rtol=1e-6, atol=1e-9)


def test_controller_attach_engine_with_mesh(ordered, graph_mesh):
    g, src, dst = ordered
    t = [0.0]
    ctl = ec.ElasticController(4, dead_after_s=5.0, clock=lambda: t[0])
    ctl.attach_engine(E.shard_engine_data(E.pack_ordered(src, dst, g.num_vertices, 4), graph_mesh))
    assert isinstance(ctl.engine_data, E.ShardedEngineData)
    t[0] = 1.0
    for h in (0, 1, 2):
        ctl.heartbeat(h, 1)
    t[0] = 5.6
    ev = ctl.poll()
    assert ev is not None and ev.executed and ctl.engine_data.k == 3
    # Mesh of 1: everything migrated on-device, so no cross-device traffic.
    assert ev.cross_device_bytes == 0
    assert ctl.rescale_stats[0].on_device_edges == ctl.rescale_stats[0].migrated_edges
    want = E.pack_ordered(src, dst, g.num_vertices, 3)
    got = E.unshard_engine_data(ctl.engine_data)
    np.testing.assert_array_equal(got.edges, want.edges)
