"""Mesh-sharded elastic runtime on 8 real (host) devices.

These tests run in-process and need >= 8 devices, so they are skipped in the
tier-1 suite (1 CPU device) and run by the CI ``multidevice`` job under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``. The same acceptance
properties are also proven inside tier-1 by the subprocess-based test in
tests/test_multidevice.py.
"""
import jax
import numpy as np
import pytest

from repro.core import cep, ordering
from repro.core.graph import rmat_graph
from repro.elastic import controller as ec
from repro.elastic.rescale_exec import ElasticRescaler
from repro.graphs import engine as E
from repro.graphs.engine import EDGE_BYTES
from repro.launch import mesh as MM
from repro.launch import sharding as SH

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs 8 devices (XLA_FLAGS=--xla_force_host_platform_device_count=8)",
)


@pytest.fixture(scope="module")
def ordered():
    g = rmat_graph(8, 6, seed=0)
    order = ordering.geo_order(g, seed=0)
    return g, g.src[order], g.dst[order]


@pytest.fixture(scope="module")
def mesh():
    return MM.make_graph_mesh(8)


@pytest.fixture(scope="module")
def rescaler():
    return ElasticRescaler()


def test_round_robin_device_placement(ordered, mesh):
    """Partition p's buffer rows physically live on graph-axis device p % 8,
    for k below / equal to / above (and not dividing) the device count."""
    g, src, dst = ordered
    dev_order = list(mesh.devices.ravel())
    for k in (5, 8, 12):
        sdata = E.pack_ordered_sharded(src, dst, g.num_vertices, k, mesh)
        assert sdata.k_pad % 8 == 0 and sdata.devices == 8
        m = sdata.rows_per_device
        for shard in sdata.edges.addressable_shards:
            d = dev_order.index(shard.device)
            lo = shard.index[0].start or 0
            assert lo == d * m  # device d holds rows [d·m, (d+1)·m)
            for r in range(lo, lo + m):
                p = SH.row_partition(r, k, 8)
                if p < k:
                    assert SH.partition_device(p, 8) == d


def test_acceptance_8_12_8_bit_identical_and_thm2_cross_device(ordered, mesh, rescaler):
    """The ISSUE's acceptance: executing 8→12→8 on the sharded buffers is
    byte-identical to the single-device pack_ordered oracle, and the reported
    cross-device migrated bytes equal ScalePlan.migrated_bytes (Thm. 2)."""
    g, src, dst = ordered
    d8 = E.pack_ordered_sharded(src, dst, g.num_vertices, 8, mesh)
    plan_out = cep.scale_plan(g.num_edges, 8, 12)
    d12, s_out = rescaler.execute(d8, plan_out, verify=True)
    assert s_out.oracle_checked and s_out.devices == 8
    assert s_out.cross_device_bytes == plan_out.migrated_bytes(EDGE_BYTES)
    assert s_out.cross_device_edges + s_out.on_device_edges == s_out.migrated_edges

    want12 = E.pack_ordered(src, dst, g.num_vertices, 12)
    got12 = E.unshard_engine_data(d12)
    np.testing.assert_array_equal(got12.edges, want12.edges)
    np.testing.assert_array_equal(got12.mask, want12.mask)

    plan_in = cep.scale_plan(g.num_edges, 12, 8)
    back, s_in = rescaler.execute(d12, plan_in, verify=True)
    assert s_in.cross_device_bytes == plan_in.migrated_bytes(EDGE_BYTES)
    orig = E.pack_ordered(src, dst, g.num_vertices, 8)
    got8 = E.unshard_engine_data(back)
    np.testing.assert_array_equal(got8.edges, orig.edges)
    np.testing.assert_array_equal(got8.mask, orig.mask)


@pytest.mark.parametrize("k_old,k_new", [(5, 9), (12, 20), (3, 7), (20, 16), (7, 8)])
def test_sharded_rescale_matches_oracle_awkward_k(ordered, mesh, rescaler, k_old, k_new):
    """k need not equal or divide the device count: padded rows stay masked
    and the executed result still matches the from-scratch pack."""
    g, src, dst = ordered
    sdata = E.pack_ordered_sharded(src, dst, g.num_vertices, k_old, mesh)
    new, stats = rescaler.rescale(sdata, k_new, verify=True)
    assert stats.oracle_checked
    assert stats.cross_device_edges + stats.on_device_edges == stats.migrated_edges
    # Cross-device accounting agrees with the plan + round-robin layout.
    plan = cep.scale_plan(g.num_edges, k_old, k_new)
    want_cross = sum(
        hi - lo for lo, hi, s, d in plan.moves if s % 8 != d % 8
    )
    assert stats.cross_device_edges == want_cross


def test_sharded_roundtrip_bit_identical_on_mesh(ordered, mesh, rescaler):
    g, src, dst = ordered
    d5 = E.pack_ordered_sharded(src, dst, g.num_vertices, 5, mesh)
    d11, _ = rescaler.rescale(d5, 11, verify=True)
    back, _ = rescaler.rescale(d11, 5, verify=True)
    orig = E.pack_ordered(src, dst, g.num_vertices, 5)
    got = E.unshard_engine_data(back)
    np.testing.assert_array_equal(got.edges, orig.edges)
    np.testing.assert_array_equal(got.mask, orig.mask)


def test_gas_apps_on_sharded_buffers_match_replicated(ordered, mesh):
    """PageRank / SSSP / WCC shard_map directly over the distributed rows and
    must agree with the same programs on the host oracle committed to a
    one-device graph mesh."""
    g, src, dst = ordered
    ref = E.pack_ordered_sharded(src, dst, g.num_vertices, 12, MM.make_graph_mesh(1))
    sdata = E.pack_ordered_sharded(src, dst, g.num_vertices, 12, mesh)

    np.testing.assert_allclose(
        np.asarray(E.pagerank(sdata, iterations=15)),
        np.asarray(E.pagerank(ref, iterations=15)),
        rtol=1e-6, atol=1e-9,
    )
    ds, its = E.sssp(sdata, source=0)
    dr, itr = E.sssp(ref, source=0)
    assert its == itr
    np.testing.assert_array_equal(np.asarray(ds), np.asarray(dr))
    ls, _ = E.wcc(sdata)
    lr, _ = E.wcc(ref)
    np.testing.assert_array_equal(np.asarray(ls), np.asarray(lr))


def test_gas_after_on_mesh_migration(ordered, mesh, rescaler):
    """The migrated ShardedEngineData is live engine state on the mesh."""
    g, src, dst = ordered
    d8 = E.pack_ordered_sharded(src, dst, g.num_vertices, 8, mesh)
    p8 = np.asarray(E.pagerank(d8, iterations=15))  # before donation consumes d8
    d12, _ = rescaler.rescale(d8, 12)
    p12 = np.asarray(E.pagerank(d12, iterations=15))
    np.testing.assert_allclose(p8, p12, rtol=1e-5, atol=1e-8)


def test_controller_reports_executed_cross_device_traffic(ordered, mesh):
    g, src, dst = ordered
    t = [0.0]
    ctl = ec.ElasticController(8, dead_after_s=5.0, clock=lambda: t[0])
    ctl.attach_engine(E.pack_ordered_sharded(src, dst, g.num_vertices, 8, mesh))
    t[0] = 1.0
    for h in range(7):
        ctl.heartbeat(h, 1)
    t[0] = 5.6  # host 7 missed its beat
    ev = ctl.poll()
    assert ev is not None and ev.kind == "scale_in" and ev.executed
    stats = ctl.rescale_stats[0]
    assert ev.cross_device_bytes == stats.cross_device_bytes > 0
    # 8→7 on 8 devices: every old partition sits alone on its device, so all
    # migrated rows cross a device boundary — the Thm.-2 bytes ARE the traffic.
    assert stats.cross_device_bytes == cep.scale_plan(
        g.num_edges, 8, 7
    ).migrated_bytes(EDGE_BYTES)
    want = E.pack_ordered(src, dst, g.num_vertices, 7)
    got = E.unshard_engine_data(ctl.engine_data)
    np.testing.assert_array_equal(got.edges, want.edges)


def test_sharded_noop_and_degenerate_chunks_on_mesh(mesh):
    g = rmat_graph(4, 1, seed=2)  # tiny: |E| < 8 devices ⇒ zero-size chunks
    order = np.arange(g.num_edges)
    src, dst = g.src[order], g.dst[order]
    sdata = E.pack_ordered_sharded(src, dst, g.num_vertices, 3, mesh)
    same, stats = ElasticRescaler().rescale(sdata, 3)
    assert same is sdata and stats.copy_ops == 0
    np.asarray(same.edges)  # not donated away
    k_new = g.num_edges + 5  # some partitions own zero edges
    new, stats = ElasticRescaler().rescale(sdata, k_new, verify=True)
    assert stats.oracle_checked
    want = E.pack_ordered(src, dst, g.num_vertices, k_new)
    got = E.unshard_engine_data(new)
    np.testing.assert_array_equal(got.edges, want.edges)
