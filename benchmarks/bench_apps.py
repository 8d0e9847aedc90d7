"""Table 6 — graph applications (PageRank / SSSP / WCC) on the JAX engine:
elapsed time + communication volume under different partitioners."""
from __future__ import annotations

import time

import numpy as np

from repro.core import baselines, ordering
from repro.graphs import engine as E
from repro.launch import mesh as MM

from .common import bench_graph, emit


def run(scale: int = 11, edge_factor: int = 10, k: int = 8) -> None:
    g = bench_graph(scale, edge_factor)
    mesh = MM.make_graph_mesh(1)
    geo = ordering.geo_order(g, seed=0)
    partitions = {
        "geo+cep": None,  # via cep_engine_data
        "1d": baselines.hash_1d(g, k),
        "2d": baselines.hash_2d(g, k),
        "dbh": baselines.dbh(g, k),
    }
    for name, part in partitions.items():
        data = E.cep_engine_data(g, geo, k) if part is None else E.build_engine_data(g, part, k)
        com = E.comm_volume_per_iteration(data)
        sdata = E.shard_engine_data(data, mesh)
        t0 = time.perf_counter()
        pr = E.pagerank(sdata, iterations=10)
        t_pr = (time.perf_counter() - t0) * 1e6
        t0 = time.perf_counter()
        _, it_s = E.sssp(sdata, source=0)
        t_ss = (time.perf_counter() - t0) * 1e6
        t0 = time.perf_counter()
        _, it_w = E.wcc(sdata)
        t_wc = (time.perf_counter() - t0) * 1e6
        emit(
            f"table6/{name}/k{k}",
            t_pr,
            f"rf={data.replication_factor:.3f};mirrors={data.mirrors};"
            f"com_per_iter_bytes={com};sssp_us={t_ss:.0f};wcc_us={t_wc:.0f}",
        )


if __name__ == "__main__":
    run()
