"""Per-process worker for the multi-host acceptance (tests/test_multihost.py).

``launch.multihost.spawn_local_cluster`` runs this file once per process; each
worker joins the ``jax.distributed`` group via ``initialize_from_env``, builds
the SAME graph deterministically from the seed (no host is special — this is
the "replicated deterministic load" path of DESIGN.md §10), and executes the
two prior acceptances on the now-global ``graph`` mesh:

* **rescale** — the PR-2 acceptance: pack at k=8 over all processes' devices,
  execute ScalePlans 8 → 12 → 8 (``ElasticRescaler``, ``recheck=False`` so no
  collective readback hides in the timed path);
* **stream** — the PR-3 acceptance: ingest batches through the controller
  with a scale-out to 12 and a preemption down to 7 interleaved
  (``StreamingEngine`` + ``ElasticController``).

One tracer + metrics registry (repro.obs) spans all phases: the record
additionally carries this process's Chrome-trace fragment, its local metric
snapshot, the psum_host-aggregated global snapshot, a process-indexed peak
RSS gauge, and drop-timings JSONL event logs — the observability acceptance
surface the parent test checks (merge, sum-of-locals, byte-identical logs).

Each process writes ONLY its local shard rows (`local_shard_rows`) plus a
stats/event JSON to ``--out``; the parent test reassembles the global buffers
from all processes' files and compares them byte-for-byte against the
single-process oracle it computes itself — so the proof never trusts a
cross-process collective to check cross-process execution. Logs go to stdout
(one line per step, prefixed with the process id) so spawn_local_cluster can
print per-process traces when something fails in CI.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src"))

from repro.launch import multihost as MH  # noqa: E402  (before jax device init)

SPEC = MH.initialize_from_env()  # must run before the first jax computation

import jax  # noqa: E402

from repro.core import cep, ordering  # noqa: E402
from repro.core.graph import rmat_graph  # noqa: E402
from repro.elastic import controller as ec  # noqa: E402
from repro.elastic.rescale_exec import ElasticRescaler  # noqa: E402
from repro.graphs import engine as E  # noqa: E402
from repro.graphs.engine import EDGE_BYTES  # noqa: E402
from repro.launch import mesh as MM  # noqa: E402
from repro.launch import sharding as SH  # noqa: E402
from repro.obs import metrics as OM  # noqa: E402
from repro.obs import trace as OT  # noqa: E402
from repro.obs import trace_export as OX  # noqa: E402
from repro.stream import IncrementalOrderer, StreamingEngine, SyntheticStream  # noqa: E402
from repro.stream.incremental import StreamConfig  # noqa: E402

GRAPH_SCALE = 8
GRAPH_EDGE_FACTOR = 6
GRAPH_SEED = 0
STREAM_SEED = 1
STREAM_BATCH = 64
REBUILD_SEED = 2
REBUILD_FLIGHT = 1


def stream_config() -> StreamConfig:
    """Stream phase config: a 2-region span so partial re-orders can move the
    monitored objective, full rebuilds parked out of the way — the ISSUE-5
    acceptance wants the DEVICE span-repair rung exercised across the process
    boundary, not drowned by resync uploads."""
    return StreamConfig(full_drift=99.0, span_regions=2)


def rebuild_config() -> StreamConfig:
    """Rebuild phase config: both thresholds parked high so the natural drift
    never escalates — the ISSUE-6 acceptance forces exactly ONE async full
    rebuild at a scripted batch, keeping the event log byte-reproducible for
    the parent's host replay."""
    return StreamConfig(partial_drift=40.0, full_drift=50.0)


def force_partial_baseline(orderer: IncrementalOrderer) -> None:
    """Pin drift ≈ 1.5 (> partial_drift, < full_drift) so every monitor step
    deterministically fires the partial rung — the parent's host replay
    applies the identical pin, keeping decisions byte-reproducible."""
    orderer._baseline_kappa = orderer._kappa() / 1.5


def log(pid: int, msg: str) -> None:
    print(f"[proc {pid}] {msg}", flush=True)


def build_ordered():
    """The acceptance graph + GEO order — bit-identical in every process."""
    g = rmat_graph(GRAPH_SCALE, GRAPH_EDGE_FACTOR, seed=GRAPH_SEED)
    order = ordering.geo_order(g, seed=0)
    return g, g.src[order], g.dst[order]


def save_blocks(store: dict, name: str, arr) -> None:
    """Record this process's local shard rows of a global array."""
    for lo, hi, data in MH.local_shard_rows(arr):
        store[f"{name}__{lo}__{hi}"] = data


def run_rescale_phase(src, dst, num_vertices, mesh, store: dict,
                      tracer=None, registry=None) -> dict:
    pid = jax.process_index()
    n = int(src.shape[0])
    rescaler = ElasticRescaler(tracer=tracer, metrics_registry=registry)
    d8 = E.pack_ordered_sharded(src, dst, num_vertices, 8, mesh)
    log(pid, f"packed k=8 over {len(jax.devices())} global devices")

    import time

    t0 = time.perf_counter()
    plan_out = cep.scale_plan(n, 8, 12)
    plan_s = time.perf_counter() - t0
    d12, s_out = rescaler.execute(d8, plan_out, recheck=False)
    log(pid, f"8->12 executed: cross_process_bytes={s_out.cross_process_bytes}")
    save_blocks(store, "rescale_k12_edges", d12.edges)
    save_blocks(store, "rescale_k12_mask", d12.mask)

    plan_in = cep.scale_plan(n, 12, 8)
    d8b, s_in = rescaler.execute(d12, plan_in, recheck=False)
    log(pid, f"12->8 executed: cross_process_bytes={s_in.cross_process_bytes}")
    save_blocks(store, "rescale_k8_edges", d8b.edges)
    save_blocks(store, "rescale_k8_mask", d8b.mask)

    def stats_dict(s):
        return {
            "k_old": s.k_old, "k_new": s.k_new,
            "migrated_edges": s.migrated_edges, "migrated_bytes": s.migrated_bytes,
            "cross_device_edges": s.cross_device_edges,
            "cross_device_bytes": s.cross_device_bytes,
            "cross_process_edges": s.cross_process_edges,
            "cross_process_bytes": s.cross_process_bytes,
            "devices": s.devices, "processes": s.processes,
            "exec_s": s.elapsed_s,
        }

    return {
        "plan_s": plan_s,
        "out": stats_dict(s_out),
        "in": stats_dict(s_in),
        "edge_bytes": EDGE_BYTES,
    }


def stream_script(ctl, stream, clock):
    """The PR-3 rescale-under-ingest acceptance script — now with the drift
    baseline pinned so every ingest's monitor fires the PARTIAL rung (the
    ISSUE-5 device span repair) — expressed once so the parent test can
    replay the identical controller decisions host-side."""
    ctl.ingest(stream.batch())
    ctl.ingest(stream.batch())  # partial re-orders around the scale-out …
    ctl.add_hosts(4)  # 8 -> 12 under ingest
    ctl.ingest(stream.batch())
    clock[0] = 1.0
    for h in range(7):
        ctl.heartbeat(h, 1)
    clock[0] = 6.0
    ctl.poll()  # 5 silent hosts preempted: 12 -> 7
    ctl.ingest(stream.batch())  # … and after the preemption
    ctl.ingest(stream.batch())


def run_stream_phase(g, src, dst, mesh, store: dict,
                     tracer=None, registry=None) -> dict:
    pid = jax.process_index()
    o = IncrementalOrderer(
        src.astype(np.int64), dst.astype(np.int64), g.num_vertices,
        regions=8, config=stream_config(),
    )
    force_partial_baseline(o)
    # span_repair="device": the rung under test
    eng = StreamingEngine(o, mesh, tracer=tracer, metrics_registry=registry)
    clock = [0.0]
    ctl = ec.ElasticController(
        8, dead_after_s=5.0, clock=lambda: clock[0],
        tracer=tracer, metrics_registry=registry,
    )
    ctl.attach_stream(eng)
    stream = SyntheticStream(g, batch_size=STREAM_BATCH, seed=STREAM_SEED)
    stream_script(ctl, stream, clock)
    log(pid, f"stream script done: k={eng.k}, events={len(ctl.events)}")
    eng.verify_bit_identity()  # in-child check (collective unshard)
    log(pid, "in-child bit identity OK")

    save_blocks(store, "stream_edges", eng.data.edges)
    save_blocks(store, "stream_mask", eng.data.mask)
    save_blocks(store, "stream_degrees", eng.data.degrees)
    events = [
        {
            "kind": ev.kind,
            "seq": ev.seq,
            "executed": getattr(ev, "executed", None),
            "cross_process_bytes": getattr(ev, "cross_process_bytes", None),
            "escalation": getattr(ev, "escalation", None),
            "repair": getattr(ev, "repair", None),
        }
        for ev in ctl.events
    ]
    return {
        "k_final": eng.k,
        "num_edges": o.num_edges,
        "events": events,
        "rung_counts": eng.rung_counts,
        # Structured log with wall-clock fields zeroed: the only
        # nondeterministic event content on a deterministic replica, so the
        # parent asserts the two processes' logs are BYTE-identical.
        "events_jsonl": ctl.events_jsonl(drop_timings=True),
    }


def run_rebuild_phase(g, src, dst, mesh, store: dict,
                      tracer=None, registry=None) -> dict:
    """ISSUE-6 acceptance: one async full rebuild (geo mode, flight 1) flies
    across the 2-process mesh — dispatch on batch 2, flight through batch 3,
    commit with a delta splice, two quiet batches around it. The parent
    replays the identical protocol host-side and byte-compares the pack."""
    pid = jax.process_index()
    o = IncrementalOrderer(
        src.astype(np.int64), dst.astype(np.int64), g.num_vertices,
        regions=8, config=rebuild_config(),
    )
    eng = StreamingEngine(
        o, mesh, full_rebuild="geo", rebuild_flight=REBUILD_FLIGHT,
        tracer=tracer, metrics_registry=registry,
    )
    ctl = ec.ElasticController(8, tracer=tracer, metrics_registry=registry)
    ctl.attach_stream(eng)
    stream = SyntheticStream(g, batch_size=STREAM_BATCH, seed=REBUILD_SEED)
    states = []
    for b in range(5):
        if b == 2:
            o.drift = lambda: 99.0  # force the dispatch on this batch only
        ctl.ingest(stream.batch())
        if b == 2:
            del o.drift
        states.append(eng.rebuild_state)
    log(pid, f"rebuild script done: states={states}")
    eng.verify_bit_identity()  # in-child check (collective unshard)
    log(pid, "rebuild in-child bit identity OK")

    save_blocks(store, "rebuild_edges", eng.data.edges)
    save_blocks(store, "rebuild_mask", eng.data.mask)
    rebuilds = [e for e in ctl.events if e.kind == "full_rebuild"]
    return {
        "num_edges": o.num_edges,
        "states": states,
        "events": [{"kind": e.kind, "seq": e.seq} for e in ctl.events],
        "rebuilds": [
            {
                "mode": e.mode, "committed": e.committed, "aborted": e.aborted,
                "snapshot_edges": e.snapshot_edges,
                "replayed_batches": e.replayed_batches,
                "splice_ops": e.splice_ops, "flight_batches": e.flight_batches,
                "seq": e.seq,
            }
            for e in rebuilds
        ],
        "program_cache": eng.program_cache_counters(),
        "events_jsonl": ctl.events_jsonl(drop_timings=True),
    }


def snapshot_to_json(snap: dict) -> dict:
    """Registry snapshots carry numpy bucket vectors — JSON-ify them."""
    return {
        k: (np.asarray(v).tolist() if isinstance(v, np.ndarray) else float(v))
        for k, v in snap.items()
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True, help="directory for per-process results")
    args = ap.parse_args()
    pid = jax.process_index()
    log(pid, f"{jax.process_count()} processes, {len(jax.local_devices())} local / "
             f"{len(jax.devices())} global devices")

    g, src, dst = build_ordered()
    mesh = MM.make_graph_mesh()  # spans every process's devices
    # ONE tracer + registry across all three phases (DESIGN.md §13): the
    # trace fragment and metric snapshots below are the observability
    # acceptance — per-process ingest/rung/rebuild/rescale span tracks that
    # merge into a single Chrome trace, and a registry whose psum_host-
    # aggregated snapshot must equal the sum of the per-process ones.
    # set_tracer also routes launch/multihost's transfer.* spans here.
    tracer = OT.set_tracer(OT.Tracer(capacity=1 << 16))
    registry = OM.MetricsRegistry()
    store: dict = {}
    record = {
        "process_id": pid,
        "num_processes": jax.process_count(),
        "devices": len(jax.devices()),
        "device_process_map": SH.device_process_map(mesh).tolist(),
        "graph": {"num_vertices": g.num_vertices, "num_edges": g.num_edges},
        "rescale": run_rescale_phase(src, dst, g.num_vertices, mesh, store,
                                     tracer, registry),
    }
    record["stream"] = run_stream_phase(g, src, dst, mesh, store, tracer, registry)
    record["rebuild"] = run_rebuild_phase(g, src, dst, mesh, store, tracer, registry)

    peak_mb = OM.record_peak_rss(registry)
    local_snap = registry.snapshot()
    global_snap = registry.snapshot_global(mesh)  # collective: same point everywhere
    log(pid, f"obs: {tracer.recorded} spans, {len(local_snap)} snapshot entries, "
             f"peak_rss={peak_mb:.1f}MB")
    record["obs"] = {
        "peak_rss_mb": peak_mb,
        "spans_recorded": tracer.recorded,
        "spans_dropped": tracer.dropped,
        "trace": OX.chrome_trace(tracer, process=pid, process_name=f"proc{pid}"),
        "local_snapshot": snapshot_to_json(local_snap),
        "global_snapshot": snapshot_to_json(global_snap),
    }

    os.makedirs(args.out, exist_ok=True)
    np.savez(os.path.join(args.out, f"proc{pid}.npz"), **store)
    with open(os.path.join(args.out, f"proc{pid}.json"), "w") as fh:
        json.dump(record, fh, indent=2)
    log(pid, "DONE")


if __name__ == "__main__":
    main()
