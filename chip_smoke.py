#!/usr/bin/env python3
"""Chip smoke of the elastic graph runtime's main path, on one TPU chip.

Run from the repository root::

    python3 chip_smoke.py             # one chip: the phases below
    python3 chip_smoke.py --chips 4   # the sharded path on a four-chip host

Phases (one chip):

1. device     — names the device; exits nonzero unless JAX found a TPU.
2. preprocess — a Graph500-style RMAT graph (``RmatShardPlan(scale=19,
   edge_factor=17, seed=0)``, the graph of BENCH_outofcore.json) with its
   duplicate draws dropped, ordered by hierarchical GEO (core/hier_order.py,
   the out-of-core workers' calls in one process) and committed to the chip:
   ``IncrementalOrderer`` → ``StreamingEngine`` on ``make_graph_mesh(1)`` →
   ``ElasticController.attach_stream``.
3. stream     — insert+delete batches through ``ElasticController.ingest``,
   each checked byte-for-byte against the host slot oracle; one forced span
   repair on the device rung; one 8→12 scale event under ingest.
4. queries    — the PageRank, SSSP and WCC query programs against the live
   pack, each compared with a plain numpy reference; then the live order's
   replication factor through the segment_rf kernel against the host count.

``--chips 4`` runs only what exists across chips, on ``make_graph_mesh(4)``:
a pack of the same graph, an ``ElasticRescaler`` 8→12→8 checked against
``pack_ordered``, a stream with a rescale under ingest (no span repair),
PageRank on the sharded rows, and the bytes each device holds.

Each phase prints its wall time (host clock, around ``block_until_ready``)
on its own line. The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``; any failed check
raises and exits nonzero before it is printed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402

from repro import compat  # noqa: E402
from repro.core import hier_order as HO  # noqa: E402
from repro.core.graph import Graph  # noqa: E402
from repro.core import cep, metrics  # noqa: E402
from repro.data import shards as DS  # noqa: E402
from repro.elastic import controller as EC  # noqa: E402
from repro.elastic.rescale_exec import ElasticRescaler  # noqa: E402
from repro.graphs import engine as GE  # noqa: E402
from repro.graphs.engine import EDGE_BYTES  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch import mesh as MM  # noqa: E402
from repro.stream import IncrementalOrderer, StreamingEngine, SyntheticStream  # noqa: E402

SCALE, EDGE_FACTOR, SEED = 19, 17, 0
K_OLD, K_NEW = 8, 12
STREAM_BATCH = 1024  # updates per batch, a quarter of them deletes
PAGERANK_ITERS = 20
MAX_ITERS = 64  # query_program's SSSP/WCC bound; reaching it is a failure
# PageRank is summed in f32 on the device and in f64 here; a hub's rank adds
# up to its degree's worth of terms, each rounding at 2^-24 relative.
PAGERANK_RTOL = 1e-3


class SmokeFailure(RuntimeError):
    """A check of the smoke failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def report(name: str, t0: float, **facts) -> None:
    extra = " ".join(f"{k}={v}" for k, v in facts.items())
    print(f"phase {name}: {time.perf_counter() - t0:.3f} s {extra}".rstrip(), flush=True)


# ------------------------------------------------------------- references
def pagerank_ref(src, dst, v: int, iterations: int, damping: float = 0.85) -> np.ndarray:
    """Undirected PageRank by bincount, in f64 (the query program's semantics:
    each edge pushes both ways, dangling mass spreads uniformly)."""
    deg = np.bincount(src, minlength=v) + np.bincount(dst, minlength=v)
    inv = 1.0 / np.maximum(deg, 1)
    dangling = deg == 0
    x = np.full(v, 1.0 / v)
    for _ in range(iterations):
        c = x * inv
        y = np.bincount(dst, weights=c[src], minlength=v) + np.bincount(
            src, weights=c[dst], minlength=v
        )
        x = (1 - damping) / v + damping * (y + x[dangling].sum() / v)
    return x


def bfs_ref(src, dst, v: int, source: int) -> tuple[np.ndarray, int]:
    """Hop distances by frontier BFS (inf where unreachable) and the
    source's eccentricity."""
    a = np.concatenate([src, dst])
    b = np.concatenate([dst, src])
    dist = np.full(v, np.inf)
    dist[source] = 0
    frontier = np.zeros(v, dtype=bool)
    frontier[source] = True
    level = 0
    while True:
        nxt = np.unique(b[frontier[a]])
        nxt = nxt[np.isinf(dist[nxt])]
        if nxt.size == 0:
            return dist, level
        level += 1
        dist[nxt] = level
        frontier[:] = False
        frontier[nxt] = True


def wcc_ref(src, dst, v: int) -> tuple[np.ndarray, int]:
    """Min-label propagation to its fixed point, one synchronous sweep per
    step; returns the labels and the sweeps that changed something."""
    lab = np.arange(v)
    steps = 0
    while True:
        new = lab.copy()
        np.minimum.at(new, dst, lab[src])
        np.minimum.at(new, src, lab[dst])
        if np.array_equal(new, lab):
            return lab, steps
        lab = new
        steps += 1


# ----------------------------------------------------------------- phases
def order_graph(scale: int, edge_factor: int = EDGE_FACTOR, seed: int = SEED):
    """Generate the RMAT graph, drop duplicate draws (the orderer keeps one
    slot per distinct edge) and order it by hierarchical GEO with the
    out-of-core bench's settings. Returns (num_vertices, src, dst, drawn)."""
    plan = DS.RmatShardPlan(scale=scale, edge_factor=edge_factor, seed=seed, num_shards=16)
    edges = np.concatenate([DS.shard_edges(plan, s) for s in range(plan.num_shards)])
    drawn = int(edges.shape[0])
    key = edges[:, 0] * np.int64(plan.num_vertices) + edges[:, 1]
    _, first = np.unique(key, return_index=True)
    edges = edges[np.sort(first)]
    cfg = HO.HierConfig(num_chunks=4, max_chunk_edges=1 << 20, seam_window=0, seed=0)
    ordered, _ = HO.hier_order_edges(
        edges, plan.num_vertices, cfg, sample=DS.sample_edges(plan, 16)
    )
    return plan.num_vertices, ordered[:, 0], ordered[:, 1], drawn


def commit(v: int, src, dst, mesh, span_repair: str = "device"):
    """The first committed pack: orderer → streaming engine → controller."""
    o = IncrementalOrderer(src, dst, v, regions=K_OLD)
    eng = StreamingEngine(o, mesh, span_repair=span_repair)
    ctl = EC.ElasticController(K_OLD)
    ctl.attach_stream(eng)
    eng.verify_bit_identity()
    return ctl, eng


def device_bytes(eng) -> int:
    return int(eng.data.edges.nbytes + eng.data.mask.nbytes + eng.data.degrees.nbytes)


def stream(ctl, eng, base: Graph, batch_size: int = STREAM_BATCH, seed: int = 1,
           repair: bool = True) -> dict:
    """Ingest with a byte check after every batch: one batch, one batch with
    a forced span repair (``repair``), an 8→12 scale event, then two batches
    at k=12."""
    o = eng.orderer
    gen = SyntheticStream(base, batch_size=batch_size, seed=seed)

    def ingest(forced: bool):
        if forced:  # the way tests/test_stream.py forces the partial rung
            o.drift = lambda: o.config.partial_drift + 0.01
        try:
            ev = ctl.ingest(gen.batch())
        finally:
            if forced:
                del o.drift
        eng.verify_bit_identity()
        check(ev.inserted > 0 and ev.deleted > 0, f"batch {ev.seq} did not both insert and delete")
        return ev

    ingest(False)
    ev = ingest(repair)
    if repair:
        check(
            ev.escalation == "partial" and ev.repair == "device",
            f"forced repair ran {ev.escalation}/{ev.repair}, not partial/device",
        )
    sev = ctl.add_hosts(K_NEW - K_OLD)
    check(sev.executed and eng.k == K_NEW, f"scale event {sev.k_old}->{sev.k_new} not executed")
    eng.verify_bit_identity()
    for _ in range(2):
        ingest(False)
    if repair:
        check(
            eng.rung_counts["partial"] == 1 and eng.rung_counts["full"] == 0,
            f"expected exactly one span repair and no rebuild, got {eng.rung_counts}",
        )
    stats = ctl.rescale_stats[-1]
    return {
        "batches": sum(1 for e in ctl.events if e.kind == "ingest"),
        "live_edges": o.num_edges,
        "k": eng.k,
        "rescale_moved_edges": stats.moved_edges,
        "rescale_s": f"{stats.elapsed_s:.3f}",
        "span_repairs": eng.rung_counts["partial"],
    }


def _timed(fn):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def query_pagerank(data, mesh, src, dst, v: int) -> dict:
    prog = GE.query_program("pagerank", num_vertices=v, mesh=mesh, iterations=PAGERANK_ITERS)
    run = lambda: prog(data.edges, data.mask, data.degrees)  # noqa: E731
    _, cold = _timed(run)
    got, warm = _timed(run)
    got = np.asarray(got, dtype=np.float64)
    want = pagerank_ref(src, dst, v, PAGERANK_ITERS)
    err = float(np.max(np.abs(got - want) / want))
    check(err <= PAGERANK_RTOL, f"pagerank off the numpy reference by {err:.3g} (relative)")
    return {"cold_s": f"{cold:.3f}", "warm_s": f"{warm:.3f}", "max_rel_err": f"{err:.3g}"}


def query_sssp(data, mesh, src, dst, v: int) -> dict:
    source = int(np.argmax(np.bincount(np.concatenate([src, dst]), minlength=v)))
    prog = GE.query_program("sssp", num_vertices=v, mesh=mesh, max_iters=MAX_ITERS)
    run = lambda: prog(data.edges, data.mask, source)  # noqa: E731
    _, cold = _timed(run)
    (dist, iters), warm = _timed(run)
    want, ecc = bfs_ref(src, dst, v, source)
    check(iters < MAX_ITERS, f"sssp stopped at max_iters={MAX_ITERS}")
    check(iters == ecc + 1, f"sssp took {iters} iterations for eccentricity {ecc}")
    got = np.asarray(dist, dtype=np.float64)
    got[got >= 1e9] = np.inf
    check(np.array_equal(got, want), "sssp distances differ from the numpy BFS")
    return {"cold_s": f"{cold:.3f}", "warm_s": f"{warm:.3f}", "source": source,
            "iters": iters, "reached": int(np.isfinite(want).sum())}


def query_wcc(data, mesh, src, dst, v: int) -> dict:
    prog = GE.query_program("wcc", num_vertices=v, mesh=mesh, max_iters=MAX_ITERS)
    run = lambda: prog(data.edges, data.mask)  # noqa: E731
    _, cold = _timed(run)
    (lab, iters), warm = _timed(run)
    want, steps = wcc_ref(src, dst, v)
    check(iters < MAX_ITERS, f"wcc stopped at max_iters={MAX_ITERS}")
    check(iters == steps + 1, f"wcc took {iters} iterations, the reference {steps} + 1")
    check(np.array_equal(np.asarray(lab).astype(np.int64), want), "wcc labels differ from numpy")
    return {"cold_s": f"{cold:.3f}", "warm_s": f"{warm:.3f}", "iters": iters,
            "components": int(np.unique(want).size)}


def kernel_rf(src, dst, k: int, v: int) -> dict:
    """Replication factor of the live order at k through the segment_rf
    kernel (lowered to Mosaic on a TPU) against the host count."""
    got = ops.replication_factor_kernel(src, dst, k, v)
    want = metrics.replication_factor_ordered(src, dst, k, v)
    check(abs(got - want) <= 1e-12 * want, f"segment_rf RF {got} != host RF {want}")
    return {"k": k, "rf": f"{got:.6f}", "interpret": ops.interpret_mode()}


def rescale_roundtrip(data, host_pack, plan_out, plan_in) -> dict:
    """8→12→8 on the sharded pack, each leg verified against a from-scratch
    pack; the round trip must give back ``host_pack`` byte for byte."""
    rescaler = ElasticRescaler()
    g = data.devices
    facts = {}
    for name, plan in (("out", plan_out), ("in", plan_in)):
        data, st = rescaler.execute(data, plan, verify=True)
        check(st.oracle_checked and st.devices == g, f"{name}: rescale not verified on {g} devices")
        check(st.migrated_bytes == plan.migrated_bytes(EDGE_BYTES), f"{name}: moved bytes off plan")
        # Partition p lives on device p % g: a move crosses devices unless
        # its old and new partitions share one.
        cross = sum(hi - lo for lo, hi, s, d in plan.moves if s % g != d % g) * EDGE_BYTES
        check(st.cross_device_bytes == cross, f"{name}: cross-device bytes off the plan")
        facts[f"{name}_cross_device_bytes"] = st.cross_device_bytes
        facts[f"{name}_migrated_bytes"] = st.migrated_bytes
    back = GE.unshard_engine_data(data)
    check(
        np.array_equal(back.edges, host_pack.edges)
        and np.array_equal(back.mask, host_pack.mask),
        "8->12->8 round trip is not byte-identical to pack_ordered",
    )
    return facts


def resident_bytes(arrays) -> dict:
    """Bytes each device holds of the given arrays, from their shards."""
    out: dict = {}
    for a in arrays:
        for sh in a.addressable_shards:
            out[sh.device.id] = out.get(sh.device.id, 0) + int(sh.data.nbytes)
    return dict(sorted(out.items()))


# ------------------------------------------------------------------- runs
def run_one_chip(scale: int = SCALE, batch_size: int = STREAM_BATCH) -> None:
    mesh = MM.make_graph_mesh(1)
    t0 = time.perf_counter()
    v, src, dst, drawn = order_graph(scale)
    report("preprocess.order", t0, vertices=v, drawn_edges=drawn, distinct_edges=src.size)
    t0 = time.perf_counter()
    ctl, eng = commit(v, src, dst, mesh)
    report("preprocess.commit", t0, k=eng.k, slots=eng.orderer.capacity,
           device_bytes=device_bytes(eng))
    base = Graph.from_edges(np.stack([src, dst], axis=1), v)
    t0 = time.perf_counter()
    facts = stream(ctl, eng, base, batch_size=batch_size)
    report("stream", t0, **facts)
    s, d = eng.orderer.snapshot()
    for name, fn in (("pagerank", query_pagerank), ("sssp", query_sssp), ("wcc", query_wcc)):
        t0 = time.perf_counter()
        facts = fn(eng.data, mesh, s, d, v)
        report(f"query.{name}", t0, live_edges=s.size, **facts)
    t0 = time.perf_counter()
    report("kernel.segment_rf", t0, **kernel_rf(s, d, eng.k, v))


def run_four_chip(scale: int = SCALE, batch_size: int = STREAM_BATCH, devices: int = 4) -> None:
    mesh = MM.make_graph_mesh(devices)
    t0 = time.perf_counter()
    v, src, dst, drawn = order_graph(scale)
    report("preprocess.order", t0, vertices=v, drawn_edges=drawn, distinct_edges=src.size)
    t0 = time.perf_counter()
    host_pack = GE.pack_ordered(src, dst, v, K_OLD)
    data = GE.shard_engine_data(host_pack, mesh)
    jax.block_until_ready(data.edges)
    held = resident_bytes([data.edges, data.mask])
    check(len(held) == devices and min(held.values()) > 0, f"pack not on all devices: {held}")
    report("pack.sharded", t0, k=K_OLD, devices=devices,
           resident_bytes_per_device=",".join(f"{i}:{b}" for i, b in held.items()))
    t0 = time.perf_counter()
    facts = query_pagerank(data, mesh, src, dst, v)
    report("query.pagerank.sharded", t0, **facts)
    t0 = time.perf_counter()
    n = int(src.size)
    facts = rescale_roundtrip(data, host_pack, cep.scale_plan(n, K_OLD, K_NEW),
                              cep.scale_plan(n, K_NEW, K_OLD))
    report("rescale.8-12-8", t0, **facts)
    t0 = time.perf_counter()
    # No device span rung: the one-chip run covers it, and the engine warms
    # its sharded program at every layout (about 2–3 minutes of compile each
    # at this size for a v5e:2x2 mesh), which nothing here would use.
    ctl, eng = commit(v, src, dst, mesh, span_repair="host")
    report("stream.commit", t0, k=eng.k, slots=eng.orderer.capacity)
    base = Graph.from_edges(np.stack([src, dst], axis=1), v)
    t0 = time.perf_counter()
    facts = stream(ctl, eng, base, batch_size=batch_size, repair=False)
    held = resident_bytes([eng.data.edges, eng.data.mask])
    check(len(held) == devices and min(held.values()) > 0, f"stream not on all devices: {held}")
    report("stream", t0, resident_bytes_per_device=",".join(
        f"{i}:{b}" for i, b in held.items()), **facts)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path on a four-chip mesh")
    args = ap.parse_args(argv)
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    print(f"device platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}", flush=True)
    if device["platform"] != "tpu":
        print("no TPU found: this smoke runs on the chip only", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices", file=sys.stderr)
        return 1
    print(f"compile cache: {compat.use_compile_cache(ROOT)}", flush=True)
    if args.chips == 4:
        run_four_chip()
    else:
        run_one_chip()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
