"""Distributed PageRank on GEO+CEP partitions vs hash partitions (paper §6.4).

  PYTHONPATH=src python examples/graph_pagerank.py
"""
import time

import numpy as np

from repro.core import baselines, ordering
from repro.core.graph import rmat_graph
from repro.graphs import engine as E
from repro.launch import mesh as MM


def main() -> None:
    g = rmat_graph(scale=12, edge_factor=10, seed=1)
    mesh = MM.make_graph_mesh()  # run with XLA_FLAGS=...device_count=8 for real shards
    k = 8
    print(f"graph: |V|={g.num_vertices:,} |E|={g.num_edges:,}, k={k}")

    order = ordering.geo_order(g)
    variants = {
        "GEO+CEP": E.cep_engine_data(g, order, k),
        "1D hash": E.build_engine_data(g, baselines.hash_1d(g, k), k),
        "2D grid": E.build_engine_data(g, baselines.hash_2d(g, k), k),
    }
    results = {}
    for name, data in variants.items():
        sdata = E.shard_engine_data(data, mesh)
        t0 = time.time()
        pr = E.pagerank(sdata, iterations=20)
        dt = time.time() - t0
        com = E.comm_volume_per_iteration(data)
        results[name] = np.asarray(pr)
        print(f"  {name:8s}: RF={data.replication_factor:5.2f} mirrors={data.mirrors:7,} "
              f"comm/iter={com/1e6:6.2f}MB time={dt:.2f}s")
    # Same answer regardless of partitioning:
    a, b = results["GEO+CEP"], results["1D hash"]
    print(f"max |Δpagerank| across partitionings: {np.abs(a-b).max():.2e}")


if __name__ == "__main__":
    main()
