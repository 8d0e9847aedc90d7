"""Executed-migration cost: ElasticRescaler (CEP overlay range copies) vs a
full hash repartition, across k ∈ {4…128} on the quickstart graph; plus the
acceptance round-trip k=8 → 12 → 8 with bit-identity and Thm.-2 checks.

Also runs a forced-8-device mode (subprocess with
``--xla_force_host_platform_device_count=8``): the same plans executed as
on-mesh migrations over the ``graph`` axis, reporting per-device program
size (copy ops / bytes written per device) and the cross-device traffic,
which for one-partition-per-device rescales equals the Thm.-2 bytes exactly.

Emits the usual ``name,us_per_call,derived`` CSV and writes the full record
to BENCH_rescale.json (committed — the repo's evidence that rescaling moves
only the theorem-predicted ranges, not ≈ k/(k+x)·|E| like hashing).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

from repro.core import baselines, cep, ordering
from repro.elastic.rescale_exec import ElasticRescaler, plan_segments
from repro.graphs import engine as E
from repro.graphs.engine import EDGE_BYTES
from repro.launch import mesh as MM

from .common import bench_graph, emit, peak_rss_mb

_CHILD_FLAG = "--multidevice-child"
_JSON_MARK = "MULTIDEVICE-JSON:"


def _hash_baseline(g, k_old, k_new, seed=0):
    """Hash repartition k_old → k_new: count relabeled edges and time a full
    repack (there is no incremental path — every moved edge is re-placed)."""
    p0 = baselines.hash_1d(g, k_old, seed)
    p1 = baselines.hash_1d(g, k_new, seed)
    moved = int(np.sum(p0 != p1))
    t0 = time.perf_counter()
    E.build_engine_data(g, p1, k_new)
    return moved, time.perf_counter() - t0


def _best_exec(rescaler, pack, plan, repeats=3):
    """Min-of-N executed migration; repack each round so donation semantics
    stay honest on backends that actually invalidate the donated buffer."""
    best = None
    for _ in range(repeats):
        _, stats = rescaler.execute(pack(), plan, verify=True)
        best = stats if best is None or stats.elapsed_s < best.elapsed_s else best
    return best


def run(scale: int = 12, edge_factor: int = 12, out_path: str = "BENCH_rescale.json") -> dict:
    g = bench_graph(scale, edge_factor)  # == examples/quickstart.py's graph
    order = ordering.geo_order(g, seed=0)
    src, dst = g.src[order], g.dst[order]
    n = g.num_edges
    rescaler = ElasticRescaler()
    mesh1 = MM.make_graph_mesh(1)
    record = {
        "graph": {"rmat_scale": scale, "edge_factor": edge_factor, "seed": 0,
                  "num_vertices": g.num_vertices, "num_edges": n},
        "edge_bytes": EDGE_BYTES,
        "sweep": [],
    }

    for k in (4, 8, 16, 32, 64, 128):
        k_new = k + 1  # the paper's elasticity step (Cor. 1: ≈ |E|/2 moves)
        plan = cep.scale_plan(n, k, k_new)
        pack = lambda: E.pack_ordered_sharded(src, dst, g.num_vertices, k, mesh1)
        stats = _best_exec(rescaler, pack, plan)
        hash_moved, hash_s = _hash_baseline(g, k, k_new)
        row = {
            "k_old": k, "k_new": k_new,
            "cep_moved_edges": stats.migrated_edges,
            "cep_moved_bytes": stats.migrated_bytes,
            "cep_moved_frac": stats.migrated_edges / n,
            "cep_exec_us": stats.elapsed_s * 1e6,
            "cep_recheck_us": stats.recheck_s * 1e6,  # host metrics re-check + oracle
            "cep_total_us": (stats.elapsed_s + stats.recheck_s) * 1e6,
            "cep_copy_ops": stats.copy_ops,
            "bit_identical_to_scratch": stats.oracle_checked,
            "hash_moved_edges": hash_moved,
            "hash_moved_bytes": hash_moved * EDGE_BYTES,
            "hash_moved_frac": hash_moved / n,
            "hash_repack_us": hash_s * 1e6,
        }
        record["sweep"].append(row)
        emit(f"rescale/cep/k{k}->{k_new}", row["cep_exec_us"],
             f"moved={stats.migrated_edges};frac={row['cep_moved_frac']:.3f};"
             f"ops={stats.copy_ops};total_us={row['cep_total_us']:.0f}")
        emit(f"rescale/hash/k{k}->{k_new}", row["hash_repack_us"],
             f"moved={hash_moved};frac={row['hash_moved_frac']:.3f}")

    # ---- acceptance round-trip: 8 → 12 → 8, bit-identical both ways -------
    d8 = E.pack_ordered_sharded(src, dst, g.num_vertices, 8, mesh1)
    plan_out = cep.scale_plan(n, 8, 12)
    d12, s_out = rescaler.execute(d8, plan_out, verify=True)
    back, s_in = rescaler.rescale(d12, 8, verify=True)
    orig = E.pack_ordered(src, dst, g.num_vertices, 8)
    back = E.unshard_engine_data(back)
    identical = bool(
        np.array_equal(back.edges, orig.edges) and np.array_equal(back.mask, orig.mask)
    )
    thm2 = cep.migration_cost_theorem2(n, 8, 4)
    # Thm. 2 is a closed-form approximation with O(k) rounding slack; the
    # executed copies must sit within that slack of the prediction.
    within_thm2 = s_out.migrated_edges <= thm2 + (plan_out.k_old + plan_out.k_new)
    record["roundtrip_8_12_8"] = {
        "bit_identical": identical,
        "out_moved_edges": s_out.migrated_edges,
        "in_moved_edges": s_in.migrated_edges,
        "thm2_predicted_edges": thm2,
        "within_thm2_prediction": bool(within_thm2),
        "hash_frac_k8_x4": cep.migration_cost_random(n, 8, 4) / n,
        "out_exec_us": s_out.elapsed_s * 1e6,
        "in_exec_us": s_in.elapsed_s * 1e6,
    }
    assert identical, "round trip must be bit-identical to the original pack"
    assert within_thm2, (s_out.migrated_edges, thm2)
    emit("rescale/roundtrip/8-12-8", s_out.elapsed_s * 1e6,
         f"bit_identical={identical};moved={s_out.migrated_edges};thm2={thm2:.0f}")

    # ---- forced-8-device mode: the same plans as on-mesh migrations --------
    md = _spawn_multidevice(scale, edge_factor)
    record["multidevice"] = md
    for row in md["sweep"]:
        emit(
            f"rescale/mesh8/k{row['k_old']}->{row['k_new']}",
            row["exec_us"],
            f"cross_dev_bytes={row['cross_device_bytes']};"
            f"on_dev_edges={row['on_device_edges']};"
            f"max_dev_ops={max(d['copy_ops'] for d in row['per_device'])}",
        )

    record["peak_rss_mb"] = round(peak_rss_mb(), 1)
    with open(out_path, "w") as fh:
        json.dump(record, fh, indent=2)
        fh.write("\n")
    return record


def run_multidevice(scale: int = 12, edge_factor: int = 12) -> dict:
    """Sharded-path sweep; must run in a process that already sees >= 8
    devices (the parent spawns one via _spawn_multidevice)."""
    import jax

    from repro.launch import sharding as SH

    g = bench_graph(scale, edge_factor)
    order = ordering.geo_order(g, seed=0)
    src, dst = g.src[order], g.dst[order]
    n = g.num_edges
    ndev = 8
    assert len(jax.devices()) >= ndev, "run via the parent (forces 8 host devices)"
    mesh = MM.make_graph_mesh(ndev)
    rescaler = ElasticRescaler()
    out = {"devices": ndev, "sweep": []}

    # 8→12→8 is the acceptance pair; 12→20 exercises k ∤ devices with a
    # genuine on-device/cross-device split; 5→9 starts below the device count.
    for k_old, k_new in [(8, 12), (12, 8), (12, 20), (5, 9)]:
        plan = cep.scale_plan(n, k_old, k_new)
        best = None
        for _ in range(3):
            sdata = E.pack_ordered_sharded(src, dst, g.num_vertices, k_old, mesh)
            _, stats = rescaler.execute(sdata, plan, verify=True)
            best = stats if best is None or stats.elapsed_s < best.elapsed_s else best
        # Per-device program size: copy ops landing on each device and the
        # bytes they write (stays + local shifts are shard-local; moves whose
        # endpoints share a device never touch the interconnect).
        per_dev = [
            {"device": d, "copy_ops": 0, "bytes_written": 0, "recv_bytes": 0}
            for d in range(ndev)
        ]
        for lo, hi, s, d in plan_segments(plan):
            dev = SH.partition_device(d, ndev)
            per_dev[dev]["copy_ops"] += 1
            per_dev[dev]["bytes_written"] += (hi - lo) * EDGE_BYTES
            if SH.partition_device(s, ndev) != dev:
                per_dev[dev]["recv_bytes"] += (hi - lo) * EDGE_BYTES
        k_pad_new = SH.padded_partition_count(k_new, ndev)
        e_max_new = int(np.diff(cep.chunk_bounds(n, k_new)).max())
        out["sweep"].append({
            "k_old": k_old, "k_new": k_new,
            "migrated_edges": best.migrated_edges,
            "migrated_bytes": best.migrated_bytes,
            "cross_device_edges": best.cross_device_edges,
            "cross_device_bytes": best.cross_device_bytes,
            "on_device_edges": best.on_device_edges,
            "cross_device_equals_thm2": bool(
                best.cross_device_bytes == plan.migrated_bytes(EDGE_BYTES)
            ),
            "bit_identical_to_scratch": best.oracle_checked,
            "exec_us": best.elapsed_s * 1e6,
            "copy_ops": best.copy_ops,
            "per_device_shard_bytes": (k_pad_new // ndev) * e_max_new * EDGE_BYTES,
            "per_device": per_dev,
        })
    return out


def _spawn_multidevice(scale: int, edge_factor: int):
    """Run run_multidevice in a child with 8 forced host devices (XLA device
    count is fixed at import, so the parent can't widen its own platform)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # forced host devices; the parent may hold the chip
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_rescale_exec", _CHILD_FLAG,
         str(scale), str(edge_factor)],
        capture_output=True, text=True, timeout=600, env=env, cwd=root,
    )
    if r.returncode != 0:
        raise RuntimeError(f"8-device rescale child failed:\n{(r.stderr or r.stdout)[-2000:]}")
    for line in r.stdout.splitlines():
        if line.startswith(_JSON_MARK):
            return json.loads(line[len(_JSON_MARK):])
    raise RuntimeError("8-device rescale child printed no result")


if __name__ == "__main__":
    if _CHILD_FLAG in sys.argv:
        i = sys.argv.index(_CHILD_FLAG)
        md_record = run_multidevice(int(sys.argv[i + 1]), int(sys.argv[i + 2]))
        print(_JSON_MARK + json.dumps(md_record))
    else:
        run()
