"""Out-of-core chunked GEO pipeline (single-process half).

Covers the stateless generation/draw contracts (one mix_hash for every
deterministic stream in the repo — the property tests the helper's docstring
promises), the hierarchical ordering pipeline (core/hier_order.py) with its
small-scale RF differential against the in-core ``geo_order`` oracle, the
shard-streamed ``pack_slots`` commit, and the cold-region spill layer.  The
2-process end-to-end acceptance rides on tests/outofcore_harness.py via the
``cluster`` fixture below.
"""
import numpy as np
import pytest
from conftest import hypothesis_or_stub

from repro.core import hier_order as HO
from repro.core.baselines import mix_hash, splitmix64
from repro.core.graph import Graph, grid_graph, powerlaw_graph, rmat_graph
from repro.core.metrics import replication_factor_ordered
from repro.core.ordering import geo_order
from repro.data import shards as DS
from repro.elastic import controller as ec
from repro.graphs import engine as GE
from repro.launch import mesh as MM
from repro.stream import (
    EdgeUpdateBatch,
    OutOfCoreIngestor,
    SpillConfig,
    SpillStore,
    SyntheticStream,
)

given, settings, st = hypothesis_or_stub()


# ---------------------------------------------------- one stateless draw (S6)
@given(
    seed=st.integers(0, 2**31 - 1),
    major=st.integers(0, 2**40),
    minor=st.integers(0, 2**20),
    salt=st.integers(0, 255),
)
@settings(max_examples=60, deadline=None)
def test_mix_hash_scalar_vector_agree(seed, major, minor, salt):
    """The same (seed, major, minor, salt) yields the same u64 draw whether
    hashed as a scalar or as an element of a broadcast array — the property
    that lets call sites vectorize freely without forking the contract."""
    scalar = int(mix_hash(seed, major, minor, salt))
    vec = mix_hash(seed, np.asarray([major, major + 1]), minor, salt)
    assert int(vec[0]) == scalar
    vec2 = mix_hash(seed, major, np.arange(minor, minor + 3), salt)
    assert int(vec2[0]) == scalar
    assert 0 <= scalar < 2**64


def test_mix_hash_scalar_vector_agree_deterministic():
    """Deterministic pin of the hypothesis property above (the stub skips it
    when hypothesis is absent): scalar and vectorized draws agree on a grid
    of keys."""
    for seed in (0, 1, 2**31 - 1):
        for major in (0, 17, 2**40):
            vec = mix_hash(seed, np.asarray([major, major + 1]), 5, 3)
            assert int(vec[0]) == int(mix_hash(seed, major, 5, 3))
            vec2 = mix_hash(seed, major, np.arange(5, 8), 3)
            assert int(vec2[0]) == int(mix_hash(seed, major, 5, 3))


def test_region_of_symmetric_deterministic():
    ing = OutOfCoreIngestor(2**20, regions=7, slots_per_region=4)
    rng = np.random.default_rng(0)
    for u, v in rng.integers(0, 2**20, size=(50, 2)).tolist():
        assert ing.region_of(u, v) == ing.region_of(v, u)
        lo, hi = min(u, v), max(u, v)
        key = np.uint64(lo) * np.uint64(2**20) + np.uint64(hi)
        assert ing.region_of(u, v) == int(splitmix64(key) % np.uint64(7))


def test_mix_hash_shared_across_call_sites():
    """SyntheticStream and data/shards hash through the SAME helper with the
    same key layout: the stream's private draw equals a direct mix_hash call,
    and stream_edges' pairs are recomputable from raw mix_hash draws."""
    g = rmat_graph(6, 4, seed=3)
    stream = SyntheticStream(g, batch_size=16, seed=42)
    for batch, pos, salt in [(0, 0, 1), (3, 7, 2), (11, 5, 3)]:
        assert stream._h(batch, pos, salt) == int(mix_hash(42, batch, pos, salt))
    plan = DS.RmatShardPlan(scale=8, edge_factor=4, seed=9)
    got = DS.stream_edges(plan, batch=5, size=64, salt=2)
    pos = np.arange(64, dtype=np.uint64)
    nv = np.uint64(plan.num_vertices)
    u = mix_hash(9, 5, pos, DS._SALT_STREAM + 4) % nv
    v = mix_hash(9, 5, pos, DS._SALT_STREAM + 5) % nv
    lo, hi = np.minimum(u, v).astype(np.int64), np.maximum(u, v).astype(np.int64)
    keep = lo != hi
    np.testing.assert_array_equal(got, np.stack([lo[keep], hi[keep]], axis=1))


@given(seed=st.integers(0, 2**16), start=st.integers(0, 2**12), n=st.integers(1, 64))
@settings(max_examples=25, deadline=None)
def test_candidate_edges_stateless_in_index(seed, start, n):
    """candidate_edges over any index subset equals the same rows of a full
    scan — the regenerate-is-the-shuffle property: an edge's value depends
    on (seed, index) only, never on which process asks or in what company."""
    plan = DS.RmatShardPlan(scale=7, edge_factor=8, seed=seed)
    idx = np.arange(start % plan.num_candidates, plan.num_candidates, 7)[:n]
    subset = DS.candidate_edges(plan, idx)
    singles = [DS.candidate_edges(plan, np.asarray([i])) for i in idx]
    np.testing.assert_array_equal(
        subset,
        np.concatenate(singles) if singles else np.empty((0, 2), np.int64),
    )


def test_shards_partition_candidates_and_reshard_invariant():
    """Shard edges concatenated in shard order ARE the full candidate scan
    (nothing lost/duplicated at shard boundaries), for ANY shard count —
    regenerating under a different num_shards is a free reshard."""
    full = DS.candidate_edges(DS.RmatShardPlan(scale=8, edge_factor=8, seed=1),
                              np.arange(DS.RmatShardPlan(scale=8, edge_factor=8).num_candidates))
    for num_shards in (1, 3, 5):
        plan = DS.RmatShardPlan(scale=8, edge_factor=8, seed=1, num_shards=num_shards)
        got = np.concatenate([DS.shard_edges(plan, s) for s in range(num_shards)])
        np.testing.assert_array_equal(got, full)


def test_sample_edges_is_direct_strided_scan():
    plan = DS.RmatShardPlan(scale=8, edge_factor=8, seed=4)
    np.testing.assert_array_equal(
        DS.sample_edges(plan, stride=4, dedup=False),
        DS.candidate_edges(plan, np.arange(0, plan.num_candidates, 4)),
    )


@given(u=st.integers(0, 2**20 - 1), v=st.integers(0, 2**20 - 1))
@settings(max_examples=40, deadline=None)
def test_region_of_symmetric_and_stateless(u, v):
    """Content addressing: region_of is orientation-free and a pure function
    of the canonical edge — any process (or a later delete) resolves the
    same region with zero shared state."""
    ing = OutOfCoreIngestor(2**20, regions=7, slots_per_region=4)
    assert ing.region_of(u, v) == ing.region_of(v, u)
    lo, hi = min(u, v), max(u, v)
    key = np.uint64(lo) * np.uint64(2**20) + np.uint64(hi)
    assert ing.region_of(u, v) == int(splitmix64(key) % np.uint64(7))


# ----------------------------------------------- hierarchical pipeline units
def test_chunk_load_additive_across_shards():
    """The load histogram of the whole edge list equals the sum of per-shard
    histograms — the property that lets every process bincount only its own
    shards and merge by collective sum."""
    plan = DS.RmatShardPlan(scale=9, edge_factor=8, seed=0, num_shards=4)
    edges = np.concatenate([DS.shard_edges(plan, s) for s in range(4)])
    rank = HO.locality_rank(edges, plan.num_vertices, seed=0)
    whole = HO.chunk_load(rank, edges)
    summed = sum(HO.chunk_load(rank, DS.shard_edges(plan, s)) for s in range(4))
    np.testing.assert_array_equal(whole, summed)


def test_chunk_splits_balance_and_membership():
    """Equal-load cuts: every chunk's edge count stays within one rank's
    keyed degree of E/C, membership is consistent with the splits, and the
    split array is strictly ascending 0 … V."""
    g = rmat_graph(12, 16, seed=0)
    edges = np.stack([g.src, g.dst], axis=1).astype(np.int64)
    cfg = HO.HierConfig(num_chunks=6)
    rank = HO.locality_rank(edges, g.num_vertices, seed=0)
    load = HO.chunk_load(rank, edges)
    splits = HO.chunk_splits(load, cfg)
    assert splits[0] == 0 and splits[-1] == g.num_vertices
    assert (np.diff(splits) > 0).all()
    cid = HO.chunk_of_edges(splits, rank, edges)
    assert cid.min() >= 0 and cid.max() < splits.shape[0] - 1
    counts = np.bincount(cid, minlength=splits.shape[0] - 1)
    target = edges.shape[0] / (splits.shape[0] - 1)
    max_keyed_degree = int(load.max())
    assert (np.abs(counts - target) <= max_keyed_degree + 1).all()
    # Pure in (load, cfg): identical inputs, identical splits.
    np.testing.assert_array_equal(splits, HO.chunk_splits(load.copy(), cfg))


def test_max_chunk_edges_is_a_real_bound():
    """Asking for chunks under a byte budget yields MORE chunks, each within
    max_chunk_edges + one keyed degree — the out-of-core memory contract."""
    g = rmat_graph(11, 16, seed=1)
    edges = np.stack([g.src, g.dst], axis=1).astype(np.int64)
    cfg = HO.HierConfig(num_chunks=2, max_chunk_edges=4096)
    rank = HO.locality_rank(edges, g.num_vertices, seed=0)
    load = HO.chunk_load(rank, edges)
    splits = HO.chunk_splits(load, cfg)
    assert splits.shape[0] - 1 >= g.num_edges // 4096
    counts = np.bincount(
        HO.chunk_of_edges(splits, rank, edges), minlength=splits.shape[0] - 1
    )
    assert counts.max() <= 4096 + int(load.max())


def test_order_edge_block_duplicates_ride_adjacent():
    """Duplicate rows follow their key's first occurrence: the ordered block
    restricted to unique keys is a permutation of the unique edge set, and
    copies are contiguous runs."""
    g = rmat_graph(7, 6, seed=2)
    edges = np.stack([g.src, g.dst], axis=1).astype(np.int64)
    dup = np.concatenate([edges, edges[:40], edges[:10]])
    rng = np.random.default_rng(0)
    dup = dup[rng.permutation(dup.shape[0])]
    perm = HO.order_edge_block(dup, HO.HierConfig(), seed=0)
    assert sorted(perm.tolist()) == list(range(dup.shape[0]))
    out = dup[perm]
    key = out[:, 0] * np.int64(g.num_vertices) + out[:, 1]
    # Copies contiguous: each key occupies exactly one run.
    change = np.flatnonzero(np.diff(key) != 0).shape[0] + 1
    assert change == np.unique(key).shape[0]


def test_chunk_mode_mirror_matches_device():
    """chunk_mode="device" (on-mesh greedy) and "mirror" (its numpy twin)
    produce the identical permutation — the byte-exact host mirror the
    differential mode leans on."""
    g = rmat_graph(7, 6, seed=0)
    edges = np.stack([g.src, g.dst], axis=1).astype(np.int64)
    p_dev = HO.order_edge_block(edges, HO.HierConfig(chunk_mode="device"), seed=3)
    p_mir = HO.order_edge_block(edges, HO.HierConfig(chunk_mode="mirror"), seed=3)
    np.testing.assert_array_equal(p_dev, p_mir)


def test_seam_spans_never_overlap():
    spans = HO.seam_spans([100, 30, 8, 200], seam_window=2048)
    for (lo, hi), (lo2, hi2) in zip(spans, spans[1:]):
        assert hi <= lo2
    assert all(lo < hi for lo, hi in spans)
    assert HO.seam_spans([5, 0, 7], seam_window=16) == []  # degenerate boundary


def test_hier_order_permutation_and_deterministic():
    g = rmat_graph(9, 8, seed=0)
    cfg = HO.HierConfig(num_chunks=4)
    perm, info = HO.hier_order(g, cfg)
    assert sorted(perm.tolist()) == list(range(g.num_edges))
    perm2, info2 = HO.hier_order(g, cfg)
    np.testing.assert_array_equal(perm, perm2)
    np.testing.assert_array_equal(info["splits"], info2["splits"])
    assert sum(info["chunk_sizes"]) == g.num_edges


# ------------------------------------------------ RF differential (the gate)
def _worst_ratio(g: Graph, cfg: HO.HierConfig) -> float:
    edges = np.stack([g.src, g.dst], axis=1).astype(np.int64)
    ordered, _ = HO.hier_order_edges(edges, g.num_vertices, cfg)
    o = geo_order(g, seed=0)
    so, do = g.src[o], g.dst[o]
    worst = 0.0
    for k in (4, 8, 16, 32, 64, 128):
        rf_h = replication_factor_ordered(ordered[:, 0], ordered[:, 1], k, g.num_vertices)
        rf_o = replication_factor_ordered(so, do, k, g.num_vertices)
        worst = max(worst, rf_h / rf_o)
    return worst


@pytest.mark.parametrize(
    "name,make,cfg",
    [
        # Low-degree lattice: needs the full-stream bfs rank (a sparse sample
        # fragments below percolation); 8 chunks.
        ("grid128", lambda: grid_graph(128), HO.HierConfig(num_chunks=8, rank_mode="bfs")),
        # Heavy-tailed sparse: geo first-touch rank, 8 chunks.
        ("powerlaw60k", lambda: powerlaw_graph(60_000, seed=0), HO.HierConfig(num_chunks=8)),
        # Dense skewed RMAT: 4 chunks (num_chunks is a memory knob, not
        # parallel slack — see the hier_order module docstring).
        ("rmat14", lambda: rmat_graph(14, 16, seed=0), HO.HierConfig(num_chunks=4)),
    ],
)
def test_hier_rf_within_margin_of_incore_oracle(name, make, cfg):
    """THE acceptance differential: hierarchical (bounded-memory) ordering
    stays within 1.10× of the sequential in-core geo_order oracle's RF at
    every k in {4..128}, on every tested graph family."""
    worst = _worst_ratio(make(), cfg)
    assert worst <= 1.10, f"{name}: worst RF ratio {worst:.4f} > 1.10"


# ------------------------------------------- shard-streamed pack_slots commit
def test_pack_slots_sharded_stream_matches_oracle():
    """Unsharded (1-device mesh), the shard-streamed commit is byte-identical
    to the in-core pack_slots oracle — edges, mask, degrees, and edge count."""
    g = rmat_graph(8, 6, seed=0)
    k, spr = 4, -(-g.num_edges // 4)
    cap = k * spr
    slot_src = np.zeros(cap, dtype=np.int64)
    slot_dst = np.zeros(cap, dtype=np.int64)
    slot_valid = np.zeros(cap, dtype=bool)
    order = geo_order(g, seed=0)
    slot_src[: g.num_edges] = g.src[order]
    slot_dst[: g.num_edges] = g.dst[order]
    slot_valid[: g.num_edges] = True
    mesh = MM.make_graph_mesh(1)
    oracle = GE.pack_slots(slot_src, slot_dst, slot_valid, k, g.num_vertices)

    def part_fn(p):
        sl = slice(p * spr, (p + 1) * spr)
        return slot_src[sl], slot_dst[sl], slot_valid[sl]

    sharded = GE.pack_slots_sharded_stream(part_fn, k, g.num_vertices, mesh, spr)
    np.testing.assert_array_equal(np.asarray(sharded.edges), oracle.edges)
    np.testing.assert_array_equal(np.asarray(sharded.mask), oracle.mask)
    np.testing.assert_array_equal(
        np.asarray(sharded.degrees), np.asarray(oracle.degrees)
    )
    assert sharded.num_edges == g.num_edges and sharded.k == k


def test_local_slot_partitions_cover_k_once():
    mesh = MM.make_graph_mesh(1)
    parts = GE.local_slot_partitions(5, mesh)
    assert sorted(parts) == list(range(5))  # single process owns everything


# ----------------------------------------------------------- spill layer
def test_spill_store_bounds_residency_and_faults_exact():
    store = SpillStore(regions=10, slots_per_region=8, config=SpillConfig(max_resident=3))
    written = {}
    for p in range(10):
        src, dst, valid = store.get(p)
        src[0], dst[0], valid[0] = 100 + p, 200 + p, True
        written[p] = (100 + p, 200 + p)
        store.evict_to_budget()
        assert store.resident <= 3
    assert store.counters["spills"] >= 7
    assert store.counters["bytes_spilled"] > 0
    # Faulting every region back returns the exact bytes written.
    for p in range(10):
        src, dst, valid = store.get(p)
        assert (int(src[0]), int(dst[0])) == written[p] and bool(valid[0])
        store.evict_to_budget()
    assert store.counters["faults"] >= 7
    assert store.counters["bytes_faulted"] > 0


def test_spill_store_lru_is_least_recently_touched():
    store = SpillStore(regions=4, slots_per_region=4, config=SpillConfig(max_resident=2))
    for p in range(3):
        src, dst, valid = store.get(p)
        valid[0] = True
    store.touch(0)  # 0 is now most recent; 1 is the LRU victim
    store.evict_to_budget()
    assert set(store._hot) == {0, 2}


def test_spill_store_disk_mode_roundtrip(tmp_path):
    cfg = SpillConfig(max_resident=1, directory=str(tmp_path / "spill"))
    store = SpillStore(regions=3, slots_per_region=4, config=cfg)
    for p in range(3):
        src, dst, valid = store.get(p)
        src[1], dst[1], valid[1] = 7 * p + 1, 7 * p + 2, True
        store.evict_to_budget()
    files = sorted((tmp_path / "spill").iterdir())
    assert len(files) == 2  # two spilled region files on disk
    for p in range(3):
        src, dst, valid = store.get(p)
        assert (int(src[1]), int(dst[1])) == (7 * p + 1, 7 * p + 2)
        store.evict_to_budget()
    # Faulted files are consumed (read + removed), not left to go stale.
    assert len(list((tmp_path / "spill").iterdir())) <= 2


def test_spill_store_drops_empty_blocks_without_serializing():
    store = SpillStore(regions=6, slots_per_region=4, config=SpillConfig(max_resident=1))
    for p in range(6):
        store.get(p)  # created zeroed, never written
    store.evict_to_budget()
    assert store.counters["spills"] == 0 and store.counters["bytes_spilled"] == 0
    assert store.resident == 1


def test_outofcore_ingestor_live_set_exact_under_spill():
    """Differential vs a python-set oracle through a random insert/delete
    stream: the spilled+faulted live set is EXACTLY the oracle's — spilling
    must never lose or duplicate an edge. spr is sized generously so no
    region-full skip muddies the accounting (skips are themselves asserted
    zero)."""
    V, regions = 500, 16
    ing = OutOfCoreIngestor(V, regions, slots_per_region=256,
                            config=SpillConfig(max_resident=4))
    oracle: set = set()
    rng = np.random.default_rng(7)
    skipped = 0
    for step in range(12):
        ins = rng.integers(0, V, size=(60, 2))
        ins = ins[ins[:, 0] != ins[:, 1]]
        dele = (
            np.asarray(sorted(oracle), dtype=np.int64)[
                rng.permutation(len(oracle))[: len(oracle) // 4]
            ]
            if oracle
            else np.empty((0, 2), np.int64)
        )
        stats = ing.ingest(EdgeUpdateBatch(insert=ins, delete=dele))
        skipped += stats.skipped
        for u, v in dele.tolist():
            oracle.discard((min(u, v), max(u, v)))
        for u, v in ins.tolist():
            oracle.add((min(u, v), max(u, v)))
        assert ing.store.resident <= 4
    # Dedup-in-batch means skips only from duplicates, never capacity.
    src, dst = ing.snapshot()
    got = set(zip(src.tolist(), dst.tolist()))
    assert got == oracle
    assert ing.num_edges == len(oracle)
    assert ing.store.counters["spills"] > 0 and ing.store.counters["faults"] > 0


def test_outofcore_ingestor_duplicate_and_absent_are_skips():
    ing = OutOfCoreIngestor(100, regions=4, slots_per_region=8)
    s0 = ing.ingest(EdgeUpdateBatch(insert=np.asarray([[2, 1]]),
                                    delete=np.empty((0, 2), np.int64)))
    assert s0.inserted == 1
    s1 = ing.ingest(EdgeUpdateBatch(insert=np.asarray([[1, 2]]),
                                    delete=np.empty((0, 2), np.int64)))
    assert s1.inserted == 0 and s1.skipped == 1  # same canonical edge again
    s2 = ing.ingest(EdgeUpdateBatch(insert=np.empty((0, 2), np.int64),
                                    delete=np.asarray([[5, 6]])))
    assert s2.deleted == 0 and s2.skipped == 1  # absent delete is idempotent
    assert ing.num_edges == 1


def test_controller_ingest_event_carries_spill_counters():
    """The attached-stream protocol: an OutOfCoreIngestor behind the elastic
    controller produces IngestEvents whose ``spill`` dict exposes the store
    counters + resident size — spill traffic lands in the shared event log."""
    ing = OutOfCoreIngestor(1000, regions=12, slots_per_region=16,
                            config=SpillConfig(max_resident=2))
    ctl = ec.ElasticController(4)
    ctl.attach_stream(ing)
    rng = np.random.default_rng(3)
    ev = None
    for b in range(4):
        ins = rng.integers(0, 1000, size=(40, 2))
        ev = ctl.ingest(EdgeUpdateBatch(insert=ins[ins[:, 0] != ins[:, 1]],
                                        delete=np.empty((0, 2), np.int64)))
    assert ev.kind == "ingest" and ev.escalation == "none"
    assert set(ev.spill) == {"spills", "faults", "bytes_spilled", "bytes_faulted", "resident"}
    assert ev.spill["resident"] <= 2 and ev.spill["spills"] > 0
    assert [e.seq for e in ctl.events] == list(range(len(ctl.events)))


# =================================================== 2-process acceptance
# The end-to-end out-of-core run: tests/outofcore_harness.py executes
# generate → rank/count → chunk-order → shard-streamed commit → rescale →
# spill-bounded stream on a real 2-process jax.distributed cluster; this
# parent reassembles the written row blocks and byte-compares against the
# in-core oracle composition it computes itself.
import os
import sys

import outofcore_harness as OH
from benchmarks.common import parse_peak_rss
from repro.core import cep
from repro.launch import multihost as MH

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_PROCS = 2
DEVS_PER_PROC = 4

_UNSUPPORTED_MARKERS = (
    "gloo",
    "cpu_collectives",
    "collectives_implementation",
    "Unable to initialize backend",
    "UNIMPLEMENTED",
    "DEADLINE_EXCEEDED",
)
_BOOTSTRAP_BANNER = "global devices"


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    out = tmp_path_factory.mktemp("outofcore")
    res = MH.spawn_local_cluster(
        N_PROCS,
        DEVS_PER_PROC,
        [os.path.join(ROOT, "tests", "outofcore_harness.py"), "--out", str(out)],
        timeout=540.0,
        cwd=ROOT,
    )
    if not res.ok:
        logs = res.format_logs()
        print(logs, file=sys.stderr)
        bootstrapped = any(_BOOTSTRAP_BANNER in p.stdout for p in res.procs)
        if not bootstrapped and any(m in logs for m in _UNSUPPORTED_MARKERS):
            pytest.skip(f"localhost jax.distributed unsupported here:\n{logs[-2000:]}")
        pytest.fail(f"out-of-core harness failed:\n{logs}")
    records, shards = [], []
    import json

    for pid in range(N_PROCS):
        with open(out / f"proc{pid}.json") as fh:
            records.append(json.load(fh))
        shards.append(dict(np.load(out / f"proc{pid}.npz")))
    return res, records, shards


@pytest.fixture(scope="module")
def oracle():
    """The in-core oracle composition — same plan, same config, one process,
    full edge list in memory (fine at test scale)."""
    edges = np.concatenate(
        [DS.shard_edges(OH.PLAN, s) for s in range(OH.PLAN.num_shards)]
    )
    sample = DS.sample_edges(OH.PLAN, OH.SAMPLE_STRIDE)
    ordered, info = HO.hier_order_edges(edges, OH.PLAN.num_vertices, OH.CFG, sample=sample)
    total = int(ordered.shape[0])
    bounds = cep.chunk_bounds(total, OH.K_PACK)
    spr = int(np.diff(bounds).max())
    cap = OH.K_PACK * spr
    slot_src = np.zeros(cap, dtype=np.int64)
    slot_dst = np.zeros(cap, dtype=np.int64)
    slot_valid = np.zeros(cap, dtype=bool)
    for p in range(OH.K_PACK):
        lo, hi = int(bounds[p]), int(bounds[p + 1])
        n = hi - lo
        slot_src[p * spr : p * spr + n] = ordered[lo:hi, 0]
        slot_dst[p * spr : p * spr + n] = ordered[lo:hi, 1]
        slot_valid[p * spr : p * spr + n] = True
    pack = GE.pack_slots(slot_src, slot_dst, slot_valid, OH.K_PACK, OH.PLAN.num_vertices)
    return edges, ordered, info, pack


def reassemble(shards, name: str, global_rows: int) -> np.ndarray:
    """Merge per-process (lo, hi) row blocks; overlaps must byte-agree."""
    rows = {}
    for store in shards:
        for key, data in store.items():
            if not key.startswith(name + "__"):
                continue
            _, lo, hi = key.rsplit("__", 2)
            lo, hi = int(lo), int(hi)
            for r in range(lo, hi):
                row = data[r - lo]
                if r in rows:
                    assert np.array_equal(rows[r], row), f"{name}: divergent row {r}"
                else:
                    rows[r] = row
    assert sorted(rows) == list(range(global_rows)), f"{name}: incomplete row coverage"
    return np.stack([rows[r] for r in range(global_rows)])


def test_processes_agree_on_plan(cluster):
    """Phase A is coordination-free: both processes derived identical splits,
    chunk sizes, and total edge count from their disjoint shard histograms."""
    _, records, _ = cluster
    assert records[0]["splits"] == records[1]["splits"]
    assert records[0]["chunk_sizes"] == records[1]["chunk_sizes"]
    assert records[0]["num_edges"] == records[1]["num_edges"]


def test_commit_is_byte_identical_to_incore_oracle(cluster, oracle):
    """The shard-streamed commit — no process ever held the full edge list —
    equals the in-core pack_slots oracle byte for byte."""
    _, records, shards = cluster
    edges, ordered, info, pack = oracle
    assert records[0]["num_edges"] == int(ordered.shape[0])
    got_edges = reassemble(shards, "commit_edges", OH.K_PACK)
    got_mask = reassemble(shards, "commit_mask", OH.K_PACK)
    got_deg = reassemble(shards, "commit_degrees", OH.PLAN.num_vertices)
    np.testing.assert_array_equal(got_edges, pack.edges)
    np.testing.assert_array_equal(got_mask, pack.mask)
    np.testing.assert_array_equal(got_deg.reshape(-1), pack.degrees)


def test_rescale_roundtrip_returns_to_commit(cluster, oracle):
    """8 → 12 → 8 across the process boundary lands back on the committed
    pack — identical live-edge prefix per partition (the rescaler sizes its
    own slot width, so raw shapes may differ by the scratch column)."""
    _, records, shards = cluster
    pack = oracle[3]
    edges = pack.edges
    mask = pack.mask
    back_edges = reassemble(shards, "rescale_back_edges", OH.K_PACK)
    back_mask = reassemble(shards, "rescale_back_mask", OH.K_PACK)
    for p in range(OH.K_PACK):
        want_live = mask[p] > 0
        got_live = back_mask[p] > 0
        n = int(want_live.sum())
        assert int(got_live.sum()) == n
        assert got_live[:n].all(), f"partition {p}: not prefix-valid after round trip"
        np.testing.assert_array_equal(back_edges[p][:n], edges[p][want_live])


def test_rescale_up_preserves_ordered_sequence(cluster, oracle):
    """At k=12 the flat ordered edge list is invariant: concatenating the
    partition prefixes (partition-major) reproduces the oracle's ordered
    sequence, and per-partition counts are the CEP chunk sizes at k=12."""
    from repro.launch import sharding as SH

    _, records, shards = cluster
    ordered = oracle[1]
    total = int(ordered.shape[0])
    g = N_PROCS * DEVS_PER_PROC
    k_pad = SH.padded_partition_count(OH.K_UP, g)
    up_edges = reassemble(shards, "rescale_up_edges", k_pad)
    up_mask = reassemble(shards, "rescale_up_mask", k_pad)
    sizes = np.diff(cep.chunk_bounds(total, OH.K_UP))
    flat = []
    for p in range(OH.K_UP):
        row = SH.partition_row(p, OH.K_UP, g)
        count = int((up_mask[row] > 0).sum())
        assert count == sizes[p], f"partition {p}: {count} != {sizes[p]}"
        live = up_mask[row] > 0
        flat.append(up_edges[row][live])
    np.testing.assert_array_equal(np.concatenate(flat), ordered.astype(np.int32))


def test_quality_within_margin_of_geo_oracle(oracle):
    """The acceptance RF gate on the distributed composition's order (proven
    byte-identical to this oracle): within 1.10× of sequential geo_order at
    every k — duplicates ride along in the hierarchical sequence, the oracle
    orders the deduped graph."""
    edges, ordered, _, _ = oracle
    V = OH.PLAN.num_vertices
    key = edges[:, 0] * np.int64(V) + edges[:, 1]
    _, first = np.unique(key, return_index=True)
    g = Graph.from_edges(edges[np.sort(first)], V)
    o = geo_order(g, seed=0)
    so, do = g.src[o], g.dst[o]
    worst = 0.0
    for k in (4, 8, 16, 32, 64, 128):
        rf_h = replication_factor_ordered(ordered[:, 0], ordered[:, 1], k, V)
        rf_o = replication_factor_ordered(so, do, k, V)
        worst = max(worst, rf_h / rf_o)
    assert worst <= 1.10, f"worst RF ratio {worst:.4f} > 1.10"


def test_stream_phase_deterministic_and_spill_bounded(cluster):
    """The spill-bounded ingest tail: both processes' stateless replays land
    the identical live-edge count, residency stayed within budget, and spill
    traffic actually happened (the counters prove the bound bit)."""
    _, records, _ = cluster
    s0, s1 = records[0]["stream"], records[1]["stream"]
    assert s0["num_edges"] == s1["num_edges"] > 0
    assert s0["inserted"] == s1["inserted"]
    assert s0["skipped"] == s1["skipped"]
    for s in (s0, s1):
        assert s["resident"] <= OH.SPILL_RESIDENT
        assert s["spill"]["spills"] > 0
        assert s["seqs"] == list(range(len(s["seqs"])))


def test_peak_rss_markers_emitted(cluster):
    res, _, _ = cluster
    for p in res.procs:
        rss = parse_peak_rss(p.stdout)
        assert rss is not None and rss > 0
