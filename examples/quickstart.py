"""Quickstart: the paper in 60 lines — order once, rescale forever.

  PYTHONPATH=src python examples/quickstart.py
"""
import time

import numpy as np

from repro.core import cep, metrics, ordering
from repro.core.graph import rmat_graph


def main() -> None:
    # 1. A skewed social-network-like graph (RMAT, ~100k edges).
    g = rmat_graph(scale=12, edge_factor=12, seed=0)
    print(f"graph: |V|={g.num_vertices:,} |E|={g.num_edges:,}")

    # 2. Preprocess ONCE: GEO orders edges so nearby edges share vertices.
    t0 = time.time()
    order = ordering.geo_order(g, k_min=4, k_max=128)
    print(f"GEO ordering: {time.time()-t0:.2f}s (one-time)")
    src, dst = g.src[order], g.dst[order]

    # 3. Partition to ANY k in O(1) — just chunk arithmetic.
    for k in (4, 16, 64, 128):
        t0 = time.time()
        bounds = cep.chunk_bounds(g.num_edges, k)
        dt_us = (time.time() - t0) * 1e6
        rf = metrics.replication_factor_ordered(src, dst, k, g.num_vertices)
        print(f"  k={k:4d}: partition computed in {dt_us:7.1f}us, RF={rf:.3f}")

    # 4. Elastic rescale 16 → 17 workers: move only the overlay ranges.
    plan = cep.scale_plan(g.num_edges, 16, 17)
    frac = plan.migrated_edges / g.num_edges
    print(f"rescale 16→17: move {plan.migrated_edges:,} edges "
          f"({frac:.1%}; hash-based would move {16/17:.1%})")
    # Corollary 1: ≈ |E|/2 for x=1.
    print(f"Cor.1 check: moved≈|E|/2 → {plan.migrated_edges / (g.num_edges/2):.3f}")

    # 5. EXECUTE the rescale on device: the plan's ranges become batched
    #    slice copies over the packed (k, E_max, 2) engine buffers.
    from repro.elastic.rescale_exec import ElasticRescaler
    from repro.graphs import engine as E
    from repro.launch import mesh as MM

    mesh1 = MM.make_graph_mesh(1)
    rescaler = ElasticRescaler()
    rescaler.execute(E.pack_ordered_sharded(src, dst, g.num_vertices, 16, mesh1), plan)  # warm
    data = E.pack_ordered_sharded(src, dst, g.num_vertices, 16, mesh1)
    new_data, stats = rescaler.execute(data, plan, verify=True)
    print(f"executed 16→17 in {stats.elapsed_s*1e3:.2f}ms: "
          f"{stats.migrated_bytes:,}B over {stats.copy_ops} slice copies, "
          f"bit-identical to a from-scratch k=17 pack (RF={new_data.replication_factor:.3f})")

    # 6. STREAM updates while staying rescalable: incremental ordering on the
    #    host, scatter-based ingest on device, full-GEO quality oracle. The
    #    quality monitor's PARTIAL re-order rung also runs on-mesh: a cached
    #    span-repair program recomputes the degraded span's order from the
    #    sharded buffers and scatters it back, while the host advances its
    #    bookkeeping through the byte-exact numpy mirror — engine.monitor()
    #    below never ships a span re-upload (span_repair="host" restores the
    #    old behavior). (Full scenario + committed numbers:
    #    python -m benchmarks.run stream → BENCH_stream.json.)
    from repro.stream import IncrementalOrderer, StreamingEngine, SyntheticStream

    orderer = IncrementalOrderer(src, dst, g.num_vertices, regions=8)
    engine = StreamingEngine(orderer, mesh1)
    stream = SyntheticStream(g, batch_size=256, seed=1)
    for _ in range(4):
        st = engine.ingest(stream.batch(), verify=True)
        engine.monitor()
    rs = engine.rescale(12, verify=True)
    rf_inc, rf_oracle = engine.rf_vs_oracle()
    print(f"streamed 4x256 updates (last batch {st.elapsed_s*1e3:.1f}ms, "
          f"bit-identical to host oracle), rescaled 8→12 live in "
          f"{rs.elapsed_s*1e3:.1f}ms; RF {rf_inc:.3f} vs full-GEO {rf_oracle:.3f} "
          f"({rf_inc/rf_oracle:.2f}x)")

    # 7. MULTI-HOST: the same rescale across a real jax.distributed process
    #    group — a 2-process localhost cluster; the reported cross_process
    #    bytes are what a real cluster pays on the network (DESIGN.md §10;
    #    full acceptance: tests/test_multihost.py, BENCH_multihost.json).
    from repro.launch.multihost import spawn_local_cluster

    worker = """
from repro.launch.multihost import initialize_from_env
spec = initialize_from_env()
import jax
from repro.core import cep, ordering
from repro.core.graph import rmat_graph
from repro.elastic.rescale_exec import ElasticRescaler
from repro.graphs import engine as E
from repro.launch import mesh as MM
g = rmat_graph(scale=8, edge_factor=6, seed=0)   # every process: same seed
order = ordering.geo_order(g, seed=0)
mesh = MM.make_graph_mesh()                      # spans both processes
data = E.pack_ordered_sharded(g.src[order], g.dst[order], g.num_vertices, 4, mesh)
_, stats = ElasticRescaler().rescale(data, 6, recheck=False)
print(f"proc {jax.process_index()}/{jax.process_count()}: 4->6 moved "
      f"{stats.migrated_bytes}B, {stats.cross_process_bytes}B across the "
      f"process boundary ({stats.devices} devices)")
"""
    res = spawn_local_cluster(2, 2, ["-c", worker], timeout=300.0)
    if not res.ok:
        raise RuntimeError(f"multi-host demo failed:\n{res.format_logs()}")
    for p in res.procs:
        print(f"  {p.stdout.strip()}")

    # 8. ASYNC FULL REBUILD: when drift escalates past the partial rung, the
    #    whole-graph re-order runs as a device program against SHADOW buffers
    #    while ingest keeps landing on the live ones — dispatch, fly for
    #    rebuild_flight batches, then one commit batch splices the flight's
    #    delta onto the new order and swaps it live (DESIGN.md §11). Ingest
    #    never blocks for longer than that one commit. full_rebuild="host"
    #    restores the synchronous stop-the-world rung.
    orderer2 = IncrementalOrderer(src, dst, g.num_vertices, regions=8)
    engine2 = StreamingEngine(
        orderer2, MM.make_graph_mesh(1), full_rebuild="geo", rebuild_flight=2
    )
    stream2 = SyntheticStream(g, batch_size=256, seed=2)
    engine2.ingest(stream2.batch(), verify=True)
    orderer2.drift = lambda: 99.0  # force the top rung for the demo
    engine2.monitor()  # dispatch: returns immediately, rebuild in flight
    del orderer2.drift
    states = [engine2.rebuild_state]
    while engine2.rebuilds_in_flight:  # ingest continues UNDER the rebuild
        engine2.ingest(stream2.batch(), verify=True)
        engine2.monitor()
        states.append(engine2.rebuild_state)
    (rb,) = engine2.drain_rebuild_events()
    engine2.verify_bit_identity()
    print(f"async full rebuild: {' -> '.join(s or 'ingest' for s in states)}; "
          f"re-ordered {rb['snapshot_edges']:,} edges while {rb['flight_batches']} "
          f"batches kept ingesting, replayed {rb['replayed_batches']} onto the new "
          f"order as {rb['splice_ops']} splice ops "
          f"(dispatch {rb['dispatch_s']*1e3:.0f}ms async, "
          f"commit {rb['commit_s']*1e3:.0f}ms blocked)")

    # 9. OUT-OF-CORE PREPROCESS: past 2^23 edges the graph never exists as
    #    one array. The input is a stateless shard PLAN (any process
    #    regenerates any shard, or a strided sample, from the seed alone),
    #    the GEO order is hierarchical — rank from the sample, equal-LOAD
    #    chunk cuts (the load histogram is additive across shards), per-chunk
    #    GEO — and a worker holds ONE ordered chunk at a time. Small scale
    #    here so the in-core oracle is cheap to compare against; the 2^23+
    #    2-process acceptance lives in tests/test_outofcore.py +
    #    benchmarks/bench_outofcore.py (DESIGN.md §12).
    from repro.core import hier_order as HO
    from repro.data import shards as DS

    plan = DS.RmatShardPlan(scale=10, edge_factor=8, seed=0, num_shards=4)
    cfg = HO.HierConfig(num_chunks=4, seam_window=0, seed=0)
    sample = DS.sample_edges(plan, stride=2)
    rank = HO.locality_rank(sample, plan.num_vertices, cfg.seed)
    load = sum(HO.chunk_load(rank, DS.shard_edges(plan, s))
               for s in range(plan.num_shards))      # additive: psum on a cluster
    splits = HO.chunk_splits(load, cfg)

    def ordered_chunk(c):  # pure in (plan, rank, splits) — any worker, any chunk
        shards = [DS.shard_edges(plan, s) for s in range(plan.num_shards)]
        block = np.concatenate(
            [es[HO.chunk_of_edges(splits, rank, es) == c] for es in shards])
        return block[HO.order_edge_block(block, cfg, seed=cfg.seed + c)]

    ordered = np.concatenate([ordered_chunk(c) for c in range(cfg.num_chunks)])
    from repro.core.graph import Graph

    key = ordered[:, 0] * np.int64(plan.num_vertices) + ordered[:, 1]
    gg = Graph.from_edges(ordered[np.sort(np.unique(key, return_index=True)[1])],
                          plan.num_vertices)
    oo = ordering.geo_order(gg, seed=0)
    rf_h = metrics.replication_factor_ordered(ordered[:, 0], ordered[:, 1],
                                              16, plan.num_vertices)
    rf_o = metrics.replication_factor_ordered(gg.src[oo], gg.dst[oo],
                                              16, plan.num_vertices)
    print(f"out-of-core hierarchical order: {ordered.shape[0]:,} edges in "
          f"{cfg.num_chunks} chunks (workers hold 1 ordered chunk at a time), "
          f"RF@16 {rf_h:.3f} vs in-core GEO {rf_o:.3f} ({rf_h/rf_o:.3f}x)")

    # 10. OBSERVE the runtime: hand the engine a span tracer + metrics
    #     registry (both default OFF — a disabled tracer costs one branch per
    #     would-be span), run a stream, and dump a Chrome trace you can open
    #     in chrome://tracing or ui.perfetto.dev — one swimlane per phase
    #     (ingest / rung / rebuild / rescale / transfer), plus exact latency
    #     percentiles from the registry's histograms (DESIGN.md §13;
    #     benchmarks/bench_stream.py --trace does this for the full scenario,
    #     and on a multi-process mesh registry.snapshot_global(mesh) sums the
    #     metrics across every process with one collective).
    from repro.obs import MetricsRegistry, Tracer, chrome_trace, write_chrome_trace

    tracer = Tracer()
    registry = MetricsRegistry()
    orderer3 = IncrementalOrderer(src, dst, g.num_vertices, regions=8)
    engine3 = StreamingEngine(orderer3, MM.make_graph_mesh(1),
                              tracer=tracer, metrics_registry=registry)
    stream3 = SyntheticStream(g, batch_size=256, seed=3)
    for _ in range(4):
        engine3.ingest(stream3.batch())
        engine3.monitor()
    engine3.rescale(12)
    write_chrome_trace("/tmp/quickstart_trace.json", chrome_trace(tracer))
    pct = registry.percentiles("stream.ingest.batch_s")
    print(f"observability: {len(tracer)} spans -> /tmp/quickstart_trace.json "
          f"(open in ui.perfetto.dev); ingest p50 {pct['p50']*1e3:.1f}ms "
          f"p99 {pct['p99']*1e3:.1f}ms, "
          f"{int(registry.counter('stream.scatter_ops').value)} scatter ops")

    # 11. SERVE + AUTOSCALE: close the loop — a traffic-driven policy reads
    #     the registry (queue depth, event rate, windowed p99) and moves k
    #     through the controller while PageRank/SSSP/WCC queries run against
    #     the live pack between ingest batches. One virtual clock drives the
    #     workload, the controller, and the policy's cooldowns, so the whole
    #     trajectory is deterministic in (seed, config); queries survive every
    #     policy rescale bit-identically (DESIGN.md §14; the two-day diurnal
    #     scenario lives in benchmarks/bench_serve.py → BENCH_serve.json).
    from repro.elastic import autoscale as AS
    from repro.elastic import controller as EC
    from repro.launch import serve as SV
    from repro.stream.workload import OpenLoopWorkload

    reg4 = MetricsRegistry()
    orderer4 = IncrementalOrderer(src, dst, g.num_vertices, regions=2)
    engine4 = StreamingEngine(orderer4, MM.make_graph_mesh(1),
                              metrics_registry=reg4)
    ref = []
    ctl = EC.ElasticController(2, clock=lambda: ref[0].now if ref else 0.0,
                               metrics_registry=reg4)
    ctl.attach_stream(engine4)
    ctl.attach_autoscaler(AS.AutoscalePolicy(AS.AutoscaleConfig(
        k_min=2, k_max=8, queue_high_per_host=2.0, queue_low=0.5,
        ema=0.6, out_cooldown_s=4.0, in_cooldown_s=8.0)))
    workload = OpenLoopWorkload(num_vertices=g.num_vertices, base_rate=8.0,
                                day_ticks=32, diurnal_amp=0.8, seed=0)
    loop = SV.ServeLoop(ctl, workload,
                        updates=SyntheticStream(g, batch_size=64, seed=4),
                        registry=reg4, config=SV.ServeConfig(probe_every=8))
    ref.append(loop)
    loop.run(32)
    loop.drain()
    assert engine4.verify_bit_identity()
    s = loop.summary()
    print(f"serve+autoscale: {s['served']} queries over one virtual day, "
          f"k path {'->'.join(map(str, s['k_path']))} "
          f"({s['scale_outs']} out / {s['scale_ins']} in), "
          f"p50 {s['latency_p50_s']:.1f}s p99 {s['latency_p99_s']:.1f}s, "
          f"{s['slo_violations']} SLO misses; pack bit-identical through "
          f"every policy rescale")

    # 12. SURVIVE A PREEMPTION: a 2-process cluster streams updates with
    #     every process renewing a file lease per batch and process 0
    #     checkpointing every batch (chunked snapshot + WAL). We SIGKILL
    #     process 1 mid-stream — no goodbye — detect it from the parent by
    #     lease expiry (no collective in the detection path: the victim died
    #     HOLDING the collective plane), abandon the stranded group, restore
    #     from the checkpoint, and shrink k over the survivors through the
    #     controller (FailureEvent + scale_in on one seq log). The restored
    #     order is the pre-failure order byte-for-byte: recovery replays raw
    #     slot ops, it does not re-run placement (DESIGN.md §15; full drill:
    #     tests/test_faults.py, numbers: BENCH_recovery.json).
    import tempfile

    from repro.checkpoint import SlotCheckpoint
    from repro.launch.multihost import LeaseBoard, launch_local_cluster

    drill_dir = tempfile.mkdtemp(prefix="quickstart_drill_")
    victim_worker = f"""
from repro.launch.multihost import LeaseBoard, initialize_from_env
spec = initialize_from_env()
import time
import jax
import numpy as np
from repro.checkpoint import SlotCheckpoint
from repro.core import ordering
from repro.core.graph import rmat_graph
from repro.elastic import controller as EC
from repro.launch import mesh as MM
from repro.stream import IncrementalOrderer, StreamingEngine, SyntheticStream
g = rmat_graph(scale=8, edge_factor=6, seed=0)
order = ordering.geo_order(g, seed=0)
o = IncrementalOrderer(g.src[order].astype(np.int64), g.dst[order].astype(np.int64),
                       g.num_vertices, regions=4)
eng = StreamingEngine(o, MM.make_graph_mesh())
ctl = EC.ElasticController(4)
ctl.attach_stream(eng)
board = LeaseBoard({drill_dir!r} + "/leases", lease_s=1.0)
pid = jax.process_index()
if pid == 0:  # one durability writer: its orderer is a full replica
    ctl.attach_checkpoint(SlotCheckpoint({drill_dir!r} + "/ckpt", interval=2))
stream = SyntheticStream(g, batch_size=128, seed=5)
for step in range(40):
    ctl.ingest(stream.batch())
    board.stamp(pid, step)
    time.sleep(0.1)
"""
    cluster = launch_local_cluster(2, 2, ["-c", victim_worker])
    board = LeaseBoard(drill_dir + "/leases", lease_s=1.0)
    try:
        board.wait_for_step(1, 3, timeout=120.0)  # let the stream get going
        t_kill = time.time()
        cluster.kill(1, reason="simulated preemption")
        while 1 not in board.dead(2):
            time.sleep(0.05)
        detect_s = time.time() - t_kill
        cluster.kill(0, reason="stranded survivor abandoned with the group")
    except TimeoutError as e:
        res = cluster.wait(10.0)
        raise RuntimeError(f"fault drill: the stream never started:\n{res.format_logs()}") from e
    cluster.wait(30.0)
    o5, info = SlotCheckpoint(drill_dir + "/ckpt", interval=2).restore()
    eng5 = StreamingEngine.from_restored(o5, MM.make_graph_mesh(1))
    ctl5 = EC.ElasticController(4)
    ctl5.attach_stream(eng5)
    fev, sev = ctl5.report_failure([2, 3], detect_s=detect_s,
                                   reason="process lease expired",
                                   restored_bytes=info["bytes_read"])
    eng5.verify_bit_identity()
    print(f"fault drill: killed p1 mid-stream, lease expired after "
          f"{detect_s:.2f}s; restored batch {info['step']} from snapshot "
          f"chunks + {info['replayed']} WAL records ({info['bytes_read']:,}B), "
          f"k {fev.k_old} -> {fev.k_new} over the survivors "
          f"(events: {' -> '.join(e.kind for e in ctl5.events)}); recovered "
          f"pack bit-identical to the host slot state")


if __name__ == "__main__":
    main()
