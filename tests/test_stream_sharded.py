"""Streaming subsystem on 8 real (host) devices: sharded ingest scatter,
compact rescale, and the bit-identity oracle across a live stream.

Skipped in the tier-1 suite (1 CPU device); run by the CI ``multidevice`` job
under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``. A subprocess
smoke of the same acceptance properties lives in tests/test_multidevice.py so
tier-1 still exercises the sharded path.
"""
import jax
import numpy as np
import pytest

from repro.core import ordering
from repro.core.graph import rmat_graph
from repro.elastic import controller as ec
from repro.graphs import engine as E
from repro.launch import mesh as MM
from repro.launch import sharding as SH
from repro.stream import IncrementalOrderer, StreamingEngine, SyntheticStream

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs 8 devices (XLA_FLAGS=--xla_force_host_platform_device_count=8)",
)


@pytest.fixture(scope="module")
def ordered():
    g = rmat_graph(8, 6, seed=0)
    order = ordering.geo_order(g, seed=0)
    return g, g.src[order].astype(np.int64), g.dst[order].astype(np.int64)


@pytest.fixture(scope="module")
def mesh():
    return MM.make_graph_mesh(8)


def test_streaming_pack_rows_live_on_round_robin_devices(ordered, mesh):
    g, src, dst = ordered
    o = IncrementalOrderer(src, dst, g.num_vertices, regions=12)  # 12 ∤ 8
    eng = StreamingEngine(o, mesh)
    sdata = eng.data
    assert sdata.k_pad % 8 == 0 and sdata.devices == 8
    dev_order = list(mesh.devices.ravel())
    m = sdata.rows_per_device
    for shard in sdata.edges.addressable_shards:
        d = dev_order.index(shard.device)
        lo = shard.index[0].start or 0
        assert lo == d * m
        for r in range(lo, lo + m):
            p = SH.row_partition(r, 12, 8)
            if p < 12:
                assert SH.partition_device(p, 8) == d


def test_sharded_ingest_bit_identical_over_stream(ordered, mesh):
    g, src, dst = ordered
    o = IncrementalOrderer(src, dst, g.num_vertices, regions=8)
    eng = StreamingEngine(o, mesh)
    stream = SyntheticStream(g, batch_size=64, seed=1)
    for _ in range(5):
        stats = eng.ingest(stream.batch(), verify=True)  # raises on divergence
        assert stats.num_edges == o.num_edges
        eng.monitor()
    eng.verify_bit_identity()


def test_sharded_rescale_under_ingest_with_cross_device_accounting(ordered, mesh):
    g, src, dst = ordered
    o = IncrementalOrderer(src, dst, g.num_vertices, regions=8)
    eng = StreamingEngine(o, mesh)
    stream = SyntheticStream(g, batch_size=64, seed=2)
    eng.ingest(stream.batch(), verify=True)
    rs_out = eng.rescale(12, verify=True)  # k → k+x under ingest
    # Every region sits alone on its device at k=8 → every region-ownership
    # change is device traffic, and the accounting must agree.
    assert rs_out.cross_device_edges <= rs_out.moved_edges
    assert rs_out.cross_device_bytes == rs_out.cross_device_edges * 8
    eng.ingest(stream.batch(), verify=True)
    rs_in = eng.rescale(5, verify=True)  # k → k−y, 5 ∤ 8 devices
    assert rs_in.k_new == 5 and eng.data.k == 5
    eng.ingest(stream.batch(), verify=True)
    # GAS still runs on the migrated streaming pack.
    s, d = o.snapshot()
    ref = E.pack_ordered_sharded(s, d, g.num_vertices, 5, MM.make_graph_mesh(1))
    np.testing.assert_allclose(
        np.asarray(E.pagerank(eng.data, iterations=10)),
        np.asarray(E.pagerank(ref, iterations=10)),
        rtol=1e-6, atol=1e-9,
    )


def test_sharded_device_span_repair_bit_identical_over_stream(ordered, mesh):
    """ISSUE-5 satellite (sharded variant): the on-mesh span-repair program —
    jnp objective path, since Pallas is gated off on multi-device meshes —
    stays byte-identical to the host mirror across forced partial escalations
    and a rescale that re-keys the program."""
    from repro.stream.incremental import StreamConfig

    g, src, dst = ordered
    o = IncrementalOrderer(
        src, dst, g.num_vertices, regions=8,
        config=StreamConfig(partial_drift=1.0, full_drift=99.0, span_regions=2),
    )
    o._baseline_kappa = o._kappa() / 1.5  # every monitor fires 'partial'
    eng = StreamingEngine(o, mesh, span_repair="device")
    stream = SyntheticStream(g, batch_size=64, seed=5)
    for b in range(4):
        if b == 2:
            eng.rescale(12, verify=True)
        eng.ingest(stream.batch(), verify=True)
        assert eng.monitor() == "partial" and eng.last_repair == "device"
        eng.verify_bit_identity()
    assert eng.rung_counts["partial"] == 4
    keys = [k for k in eng._programs if k[0] == "span_repair"]
    assert keys and all(k[7] is False for k in keys)  # use_pallas gated off


def test_sharded_differential_span_repair_never_worse_than_geo(ordered, mesh):
    """Sharded differential mode: geo candidate scored on device, result
    byte-identical to the host mirror's selection."""
    from repro.stream.incremental import StreamConfig

    g, src, dst = ordered
    o = IncrementalOrderer(
        src, dst, g.num_vertices, regions=8,
        config=StreamConfig(partial_drift=1.0, full_drift=99.0, span_regions=2),
    )
    o._baseline_kappa = o._kappa() / 1.5
    eng = StreamingEngine(o, mesh, span_repair="differential")
    stream = SyntheticStream(g, batch_size=64, seed=6)
    for _ in range(2):
        eng.ingest(stream.batch(), verify=True)
        assert eng.monitor() == "partial"
        eng.verify_bit_identity()


def test_sharded_escalation_resync_stays_bit_identical(ordered, mesh):
    g, src, dst = ordered
    o = IncrementalOrderer(src, dst, g.num_vertices, regions=8)
    eng = StreamingEngine(o, mesh)
    # Middle rung: the span rewrite reaches the mesh as one scatter.
    n = o.partial_reorder()
    assert n > 0 and not o.needs_resync
    ops, deg = o.drain_ops()
    eng._scatter(ops, deg)
    eng.verify_bit_identity()
    # Top rung: full rebuild forces a resync upload.
    o.full_rebuild()
    assert o.needs_resync
    eng._resync()
    eng.verify_bit_identity()


def test_sharded_async_full_rebuild_commits_bit_identical(ordered, mesh):
    """ISSUE-6 (sharded variant): the async full rebuild — dispatch against
    shadow buffers, fly for one batch, commit with a delta splice — stays
    byte-identical to the host slot oracle on an 8-device mesh, and both the
    whole-graph re-order and splice programs land in the one program LRU."""
    from repro.stream.incremental import StreamConfig

    g, src, dst = ordered
    o = IncrementalOrderer(
        src, dst, g.num_vertices, regions=8,
        config=StreamConfig(partial_drift=40.0, full_drift=50.0),
    )
    eng = StreamingEngine(o, mesh, full_rebuild="geo", rebuild_flight=1)
    ctl = ec.ElasticController(8)
    ctl.attach_stream(eng)
    stream = SyntheticStream(g, batch_size=64, seed=7)
    states = []
    for b in range(4):
        if b == 1:
            o.drift = lambda: 99.0  # force the dispatch on this batch
        ctl.ingest(stream.batch())
        if b == 1:
            del o.drift
        states.append(eng.rebuild_state)
        eng.verify_bit_identity()  # raises on any host/device divergence
    assert states == ["", "dispatch", "commit", ""]
    rebuilds = [e for e in ctl.events if e.kind == "full_rebuild"]
    assert len(rebuilds) == 1
    rb = rebuilds[0]
    assert rb.committed and rb.flight_batches == 1 and rb.replayed_batches == 1
    assert [e.seq for e in ctl.events] == list(range(len(ctl.events)))
    kinds = {k[0] for k in eng._programs}
    assert "full_reorder" in kinds and "splice" in kinds


def test_controller_interleaves_sharded_ingest_and_scale(ordered, mesh):
    g, src, dst = ordered
    o = IncrementalOrderer(src, dst, g.num_vertices, regions=8)
    eng = StreamingEngine(o, mesh)
    t = [0.0]
    ctl = ec.ElasticController(8, dead_after_s=5.0, clock=lambda: t[0])
    ctl.attach_stream(eng)
    stream = SyntheticStream(g, batch_size=64, seed=3)
    ctl.ingest(stream.batch())
    t[0] = 1.0
    for h in range(6):
        ctl.heartbeat(h, 1)
    t[0] = 6.0
    ev = ctl.poll()  # hosts 6, 7 preempted → rescale on the mesh
    assert ev is not None and ev.executed and eng.k == 6
    ctl.ingest(stream.batch())
    eng.verify_bit_identity()
    seqs = [e.seq for e in ctl.events]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
