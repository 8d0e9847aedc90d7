"""Multi-host rescale cost on a real 2-process localhost cluster.

Spawns a 2-process × 4-device ``jax.distributed`` group
(``launch.multihost.spawn_local_cluster``) and executes ScalePlans on the
global ``graph`` mesh, so cross-device migrations cross an actual process
boundary (gloo collectives on CPU — the same code path a multi-NIC cluster
takes, minus the physical wire). Records, per (k_old → k_new):

* plan latency (the O(k) overlay) and executed program latency;
* migrated bytes vs the Thm.-2 closed form — the paper's headline bound;
* ``cross_process_bytes`` — the subset of Thm.-2 bytes that is genuinely the
  *network bill*, vs same-host device copies (for one-partition-per-device
  rescales every migrated byte crosses devices, and the process split is
  decided purely by the partition→process map);
* a streaming section: per-batch ingest latency on the 2-process mesh and one
  rescale-under-ingest with its cross-process traffic.

Writes BENCH_multihost.json (committed) and emits the usual CSV lines.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

from repro.core import cep, ordering
from repro.elastic.rescale_exec import ElasticRescaler
from repro.graphs.engine import EDGE_BYTES

from .common import bench_graph, emit, emit_peak_rss, parse_peak_rss, peak_rss_mb

_JSON_MARK = "MULTIHOST-JSON:"
N_PROCS = 2
DEVS_PER_PROC = 4
SCALE, EDGE_FACTOR = 12, 12


def run_child() -> dict:
    """Executes the sweep inside one process of the spawned cluster."""
    from repro.launch import multihost as MH

    spec = MH.initialize_from_env()
    import jax

    from repro.graphs import engine as E
    from repro.launch import mesh as MM
    from repro.launch import sharding as SH
    from repro.stream import IncrementalOrderer, StreamingEngine, SyntheticStream

    assert spec is not None, "run via the parent (python -m benchmarks.bench_multihost)"
    g = bench_graph(SCALE, EDGE_FACTOR)
    order = ordering.geo_order(g, seed=0)
    src, dst = g.src[order], g.dst[order]
    n = g.num_edges
    mesh = MM.make_graph_mesh()
    ndev = len(jax.devices())
    rescaler = ElasticRescaler()
    out = {
        "processes": jax.process_count(),
        "devices": ndev,
        "devs_per_proc": ndev // jax.process_count(),
        "device_process_map": SH.device_process_map(mesh).tolist(),
        "graph": {"rmat_scale": SCALE, "edge_factor": EDGE_FACTOR, "seed": 0,
                  "num_vertices": g.num_vertices, "num_edges": n},
        "edge_bytes": EDGE_BYTES,
        "sweep": [],
    }

    for k_old, k_new in [(8, 12), (12, 8), (12, 20), (5, 9)]:
        t0 = time.perf_counter()
        plan = cep.scale_plan(n, k_old, k_new)
        plan_s = time.perf_counter() - t0
        best = None
        for _ in range(3):
            sdata = E.pack_ordered_sharded(src, dst, g.num_vertices, k_old, mesh)
            _, stats = rescaler.execute(sdata, plan, recheck=False)
            best = stats if best is None or stats.elapsed_s < best.elapsed_s else best
        x = k_new - k_old
        thm2 = cep.migration_cost_theorem2(n, k_old, x) if x > 0 else None
        out["sweep"].append({
            "k_old": k_old, "k_new": k_new,
            "plan_us": plan_s * 1e6,
            "exec_us": best.elapsed_s * 1e6,
            "migrated_edges": best.migrated_edges,
            "migrated_bytes": best.migrated_bytes,
            "thm2_predicted_edges": thm2,
            "within_thm2_prediction": (
                None if thm2 is None
                else bool(best.migrated_edges <= thm2 + (k_old + k_new))
            ),
            "cross_device_edges": best.cross_device_edges,
            "cross_device_bytes": best.cross_device_bytes,
            "cross_process_edges": best.cross_process_edges,
            "cross_process_bytes": best.cross_process_bytes,
            "cross_process_frac_of_migrated": (
                best.cross_process_edges / max(best.migrated_edges, 1)
            ),
            "one_partition_per_device": k_old == ndev,
        })

    # Streaming on the 2-process mesh: ingest cadence + rescale-under-ingest.
    o = IncrementalOrderer(
        src.astype(np.int64), dst.astype(np.int64), g.num_vertices, regions=8
    )
    eng = StreamingEngine(o, mesh)
    stream = SyntheticStream(g, batch_size=256, seed=1)
    ingest_s = []
    for _ in range(4):
        st = eng.ingest(stream.batch())
        ingest_s.append(st.elapsed_s)
    rs = eng.rescale(12)
    out["stream"] = {
        "batch_size": 256,
        "ingest_us_per_batch": [s * 1e6 for s in ingest_s],
        "rescale": {
            "k_old": rs.k_old, "k_new": rs.k_new,
            "moved_edges": rs.moved_edges,
            "cep_plan_edges": rs.cep_plan_edges,
            "cross_device_bytes": rs.cross_device_bytes,
            "cross_process_bytes": rs.cross_process_bytes,
            "exec_us": rs.elapsed_s * 1e6,
        },
    }
    eng.verify_bit_identity()
    out["stream"]["bit_identical_to_host_oracle"] = True
    return out


def run(out_path: str = "BENCH_multihost.json") -> dict | None:
    from repro.launch import multihost as MH

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env_extra = {
        "PYTHONPATH": os.path.join(root, "src")
        + os.pathsep
        + os.environ.get("PYTHONPATH", "")
    }
    res = MH.spawn_local_cluster(
        N_PROCS,
        DEVS_PER_PROC,
        ["-m", "benchmarks.bench_multihost", "--child"],
        timeout=900.0,
        env_extra=env_extra,
        cwd=root,
    )
    if not res.ok:
        emit("multihost/FAILED", 0.0, res.format_logs()[-200:].replace("\n", " "))
        print(res.format_logs(), file=sys.stderr)
        return None
    record = None
    for line in res.procs[0].stdout.splitlines():
        if line.startswith(_JSON_MARK):
            record = json.loads(line[len(_JSON_MARK):])
    assert record is not None, "child emitted no JSON record"
    for row in record["sweep"]:
        emit(
            f"multihost/rescale/k{row['k_old']}->{row['k_new']}",
            row["exec_us"],
            f"plan_us={row['plan_us']:.0f};"
            f"xproc_bytes={row['cross_process_bytes']};"
            f"xdev_bytes={row['cross_device_bytes']};"
            f"migrated={row['migrated_edges']}",
        )
    emit(
        "multihost/stream/ingest",
        float(np.mean(record["stream"]["ingest_us_per_batch"])),
        f"rescale_xproc_bytes={record['stream']['rescale']['cross_process_bytes']}",
    )
    record["peak_rss_mb"] = {
        "parent": round(peak_rss_mb(), 1),
        "per_process": [parse_peak_rss(p.stdout) for p in res.procs],
    }
    with open(out_path, "w") as fh:
        json.dump(record, fh, indent=2)
        fh.write("\n")
    return record


if __name__ == "__main__":
    if "--child" in sys.argv:
        print(_JSON_MARK + json.dumps(run_child()), flush=True)
        emit_peak_rss()
    else:
        run()
