"""Serving front end: cached query programs match the one-shot entry points,
queries survive rescale-under-ingest and async full rebuilds, and the serve
loop's accounting is internally consistent (ISSUE-9)."""
import numpy as np
import pytest

from repro.core import ordering
from repro.core.graph import rmat_graph
from repro.elastic import autoscale as EA
from repro.elastic import controller as ec
from repro.graphs import engine as ge
from repro.launch import mesh as MM
from repro.launch import serve as LS
from repro.obs import metrics as OM
from repro.stream import IncrementalOrderer, StreamingEngine, SyntheticStream
from repro.stream.incremental import StreamConfig
from repro.stream.workload import OpenLoopWorkload


def _engine(scale=7, regions=2, seed=0, **kw):
    g = rmat_graph(scale, 8, seed=seed)
    order = ordering.geo_order(g, seed=0)
    src, dst = g.src[order].astype(np.int64), g.dst[order].astype(np.int64)
    orderer = IncrementalOrderer(src, dst, g.num_vertices, regions=regions)
    return g, StreamingEngine(orderer, MM.make_graph_mesh(None), **kw)


# ----------------------------------------------------------- query programs
def test_query_programs_match_one_shot_entry_points():
    _, engine = _engine()
    data = engine.data
    # The one-shot entry points on the host oracle of the same graph: the
    # orderer's snapshot in the gap-free pack_ordered layout, committed to a
    # one-device graph mesh — a check across layouts.
    s, d = engine.orderer.snapshot()
    ref = ge.pack_ordered_sharded(s, d, data.num_vertices, engine.k, MM.make_graph_mesh(1))
    ranks = ge.query_program(
        "pagerank", num_vertices=data.num_vertices, mesh=data.mesh, iterations=20
    )(data.edges, data.mask, data.degrees)
    np.testing.assert_allclose(
        np.asarray(ranks), np.asarray(ge.pagerank(ref)), rtol=1e-6, atol=1e-9
    )
    dist, iters = ge.query_program(
        "sssp", num_vertices=data.num_vertices, mesh=data.mesh
    )(data.edges, data.mask, 3)
    ref_dist, ref_iters = ge.sssp(ref, source=3)
    np.testing.assert_array_equal(np.asarray(dist), np.asarray(ref_dist))
    assert iters == ref_iters
    lab, _ = ge.query_program("wcc", num_vertices=data.num_vertices, mesh=data.mesh)(
        data.edges, data.mask
    )
    np.testing.assert_array_equal(np.asarray(lab), np.asarray(ge.wcc(ref)[0]))


def test_query_program_is_cached_and_source_is_an_operand():
    _, engine = _engine()
    data = engine.data
    kw = dict(num_vertices=data.num_vertices, mesh=data.mesh)
    # Same (kind, layout, params) → the SAME program object (compile paid once).
    assert ge.query_program("sssp", **kw) is ge.query_program("sssp", **kw)
    prog = ge.query_program("sssp", **kw)
    # Different sources reuse the program — source is a traced operand.
    d0, _ = prog(data.edges, data.mask, 0)
    d5, _ = prog(data.edges, data.mask, 5)
    assert np.asarray(d0)[0] == 0.0 and np.asarray(d5)[5] == 0.0
    with pytest.raises(ValueError):
        ge.query_program("nope", **kw)


def test_queries_survive_rescale_and_full_rebuild():
    g, engine = _engine(regions=4)
    qe = LS.QueryEngine(engine)
    base, _ = qe.query("pagerank")
    # Rescale under the query engine's feet: same graph, new layout.
    engine.rescale(3)
    engine.verify_bit_identity()
    after, _ = qe.query("pagerank")
    np.testing.assert_allclose(np.asarray(base), np.asarray(after), rtol=1e-5, atol=1e-8)

    # Async full rebuild: ingest with thresholds that force the full rung,
    # then query the committed pack — answers must reflect the NEW graph.
    g2 = rmat_graph(7, 8, seed=3)
    order = ordering.geo_order(g2, seed=0)
    src, dst = g2.src[order].astype(np.int64), g2.dst[order].astype(np.int64)
    cfg = StreamConfig(partial_drift=1.01, full_drift=1.02, span_regions=2)
    orderer = IncrementalOrderer(src, dst, g2.num_vertices, regions=4, config=cfg)
    eng2 = StreamingEngine(
        orderer, MM.make_graph_mesh(None), span_repair="device",
        full_rebuild="geo", rebuild_flight=1,
    )
    qe2 = LS.QueryEngine(eng2)
    stream = SyntheticStream(g2, batch_size=64, seed=2, burst_every=3, burst_factor=4)
    committed = 0
    for _ in range(20):
        eng2.ingest(stream.batch())
        eng2.monitor()
        _, elapsed = qe2.query("wcc")  # a query between every batch
        assert elapsed > 0.0
        committed = sum(1 for r in eng2.drain_rebuild_events() if r["committed"])
        if committed:
            break
    assert committed >= 1, "stream never committed a full rebuild"
    eng2.verify_bit_identity()
    # The post-rebuild pack answers queries consistently with a from-scratch
    # engine over the same live edge set.
    (lab, _), _ = qe2.query("wcc")
    live = orderer.snapshot()
    fresh = IncrementalOrderer(live[0], live[1], g2.num_vertices, regions=4)
    ref_engine = StreamingEngine(fresh, MM.make_graph_mesh(None))
    ref, _ = ge.wcc(ref_engine.data)
    np.testing.assert_array_equal(np.asarray(lab), np.asarray(ref))


def test_query_engine_single_timing_read_feeds_registry():
    reg = OM.MetricsRegistry()
    _, engine = _engine()
    qe = LS.QueryEngine(engine, registry=reg)
    _, e1 = qe.query("sssp", source=1)
    _, e2 = qe.query("wcc")
    h = reg.histogram("serve.query_measured_s")
    assert h.total == 2 and reg.counter("serve.queries").value == 2.0
    # The recorded samples ARE the returned elapsed values (one read each).
    assert sorted(np.asarray(h._samples).tolist()) == sorted([e1, e2])


# ---------------------------------------------------------------- serve loop
def _loop(ticks=24, base_rate=6.0, k0=2, autoscaler=True):
    g, engine = _engine(regions=k0)
    reg = OM.MetricsRegistry()
    ref = []
    ctl = ec.ElasticController(
        k0, clock=lambda: ref[0].now if ref else 0.0, metrics_registry=reg
    )
    ctl.attach_stream(engine)
    if autoscaler:
        ctl.attach_autoscaler(
            EA.AutoscalePolicy(
                EA.AutoscaleConfig(
                    k_min=1, k_max=8, queue_high_per_host=2.0, queue_low=0.5,
                    ema=0.6, out_cooldown_s=4.0, in_cooldown_s=8.0,
                )
            )
        )
    workload = OpenLoopWorkload(
        num_vertices=g.num_vertices, base_rate=base_rate, day_ticks=ticks,
        diurnal_amp=0.7, burst_every=0, seed=0,
    )
    updates = SyntheticStream(g, batch_size=8, seed=0)
    loop = LS.ServeLoop(
        ctl, workload, updates=updates, registry=reg,
        config=LS.ServeConfig(probe_every=4),
    )
    ref.append(loop)
    return loop, ctl, engine, reg


def test_serve_loop_accounting_is_consistent():
    loop, ctl, engine, reg = _loop()
    loop.run(24)
    loop.drain()
    s = loop.summary()
    assert s["served"] == len(loop.records) > 0
    assert s["backlog"] == 0  # drain retired everything
    assert s["slo_violations"] == sum(1 for r in loop.records if r.violated)
    assert reg.histogram("serve.latency_s").total == s["served"]
    # FIFO on the virtual timeline: retirement ticks are non-decreasing and
    # nothing retires before it arrives.
    ticks = [r.tick for r in loop.records]
    assert ticks == sorted(ticks)
    assert all(r.tick >= r.arrival_tick for r in loop.records)
    # Modeled latency = wait + service, exactly.
    c = loop.config
    for r in loop.records:
        assert r.latency_s == pytest.approx(
            (r.tick - r.arrival_tick) * c.tick_s + c.tick_s / c.per_host_rate
        )
    # Probes ran and measured real device time.
    assert any(r.measured_s > 0 for r in loop.records)
    # One ingest per tick rode along, all on the shared seq log.
    ingests = [e for e in ctl.events if e.kind == "ingest"]
    assert len(ingests) == 24
    seqs = [e.seq for e in ctl.events]
    assert seqs == sorted(seqs)


def test_serve_loop_autoscales_and_stays_bit_identical():
    loop, ctl, engine, _ = _loop(ticks=32, base_rate=10.0)
    loop.run(32)
    assert loop.scale_events, "load never moved k"
    assert all(e.executed for e in loop.scale_events)
    assert engine.k == ctl.k
    assert engine.verify_bit_identity()
    s = loop.summary()
    assert len(s["migrated_bytes_per_decision"]) == len(loop.scale_events)
    assert len(s["moved_edges_per_decision"]) == len(loop.scale_events)
    assert all(m > 0 for m in s["moved_edges_per_decision"])
    assert s["k_path"][0] == 2 and len(s["k_path"]) == len(loop.scale_events) + 1


def test_serve_loop_requires_stream_and_sheds_at_capacity():
    g, _ = _engine()
    ctl = ec.ElasticController(2)
    workload = OpenLoopWorkload(num_vertices=g.num_vertices, base_rate=4.0)
    with pytest.raises(ValueError):
        LS.ServeLoop(ctl, workload)
    # Admission bound: a tiny queue cap sheds the overflow and counts it.
    loop, *_ = _loop(autoscaler=False)
    loop.config = LS.ServeConfig(queue_cap=2, probe_every=0, per_host_rate=0.5)
    loop.run(12)
    assert loop.shed > 0
    assert len(loop.queue) <= 2