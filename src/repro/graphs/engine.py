"""Distributed vertex-cut graph engine (JAX shard_map) — the paper's §6.4
workloads (PageRank / SSSP / WCC) running on CEP edge partitions.

TPU adaptation (DESIGN.md §4): each device owns one edge chunk as a dense
padded (E_max, 2) int32 array; the GAS gather/apply/scatter is a dense
scatter-add into a (V,) accumulator (VPU-friendly), combined across devices
with psum/pmin. Per-iteration *communication volume* is reported with the
paper's own mirror metric (Σ_p |V(E_p)| − |V|), which is what the partition
quality controls on a real sparse-exchange system.

Two layouts (DESIGN.md §6):

* ``EngineData`` — the replicated pack: one (k, E_max, 2) buffer, partition p
  at row p. Fine on one device; the ``data`` mesh axis splits rows.
* ``ShardedEngineData`` — the distributed pack: a (k_pad, E_max, 2) buffer
  carrying a NamedSharding over the ``graph`` mesh axis, rows in device-major
  round-robin order (partition p on device p % g, at row
  launch.sharding.partition_row(p, k, g)). GAS iteration shard_maps directly
  over the sharded rows, and elastic/rescale_exec.py executes ScalePlans on it
  as on-mesh migrations.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..compat import shard_map
from ..core import cep, metrics
from ..core.graph import Graph
from ..launch import sharding as SH

AXIS = "data"


@dataclasses.dataclass(frozen=True)
class EngineData:
    edges: jnp.ndarray  # (k, E_max, 2) int32 — undirected, both endpoints
    mask: jnp.ndarray  # (k, E_max) f32 1/0 padding mask
    degrees: jnp.ndarray  # (V,) f32
    num_vertices: int
    k: int
    mirrors: int  # Σ_p |V(E_p)| − |V(E)| — the paper's comm-volume metric
    replication_factor: float
    num_edges: int = 0  # total valid (unpadded) edges across partitions


def build_engine_data(g: Graph, part: np.ndarray, k: int) -> EngineData:
    """Pack per-partition edge chunks (padded to a common max) + quality metrics."""
    order = np.argsort(part, kind="stable")
    counts = np.bincount(part, minlength=k)
    e_max = int(counts.max())
    edges = np.zeros((k, e_max, 2), dtype=np.int32)
    mask = np.zeros((k, e_max), dtype=np.float32)
    src, dst = g.src[order], g.dst[order]
    off = 0
    for p in range(k):
        c = int(counts[p])
        edges[p, :c, 0] = src[off : off + c]
        edges[p, :c, 1] = dst[off : off + c]
        mask[p, :c] = 1.0
        off += c
    deg = np.zeros(g.num_vertices, dtype=np.float32)
    np.add.at(deg, g.src, 1.0)
    np.add.at(deg, g.dst, 1.0)
    mir = metrics.mirror_count(g.src, g.dst, part, k, g.num_vertices)
    rf = metrics.replication_factor(g.src, g.dst, part, k, g.num_vertices)
    return EngineData(
        edges=jnp.asarray(edges),
        mask=jnp.asarray(mask),
        degrees=jnp.asarray(deg),
        num_vertices=g.num_vertices,
        k=k,
        mirrors=mir,
        replication_factor=rf,
        num_edges=g.num_edges,
    )


def pack_ordered(
    src_ordered: np.ndarray,
    dst_ordered: np.ndarray,
    num_vertices: int,
    k: int,
    *,
    e_max: int | None = None,
) -> EngineData:
    """Pack CEP chunks of an already-ordered edge list: partition p owns
    ordered edge ids [bounds[p], bounds[p+1]), stored *in list order*.

    This partition-major layout is exactly what elastic/rescale_exec.py's
    range copies preserve, so an executed k_old → k_new migration is
    bit-comparable against a from-scratch pack at k_new.

    ``e_max`` overrides the per-partition row width: passing a value larger
    than the biggest chunk leaves masked slack rows at each partition's tail —
    the headroom the streaming subsystem's on-device ingest scatters new edges
    into (DESIGN.md §9), same masked-rows convention as the k-padding of §6.
    """
    e = int(src_ordered.shape[0])
    b = cep.chunk_bounds(e, k)
    sizes = np.diff(b)
    if e_max is None:
        e_max = int(sizes.max())
    elif e_max < int(sizes.max()):
        raise ValueError(f"e_max={e_max} is below the largest chunk ({int(sizes.max())})")
    edges = np.zeros((k, e_max, 2), dtype=np.int32)
    mask = np.zeros((k, e_max), dtype=np.float32)
    for p in range(k):
        lo, hi = int(b[p]), int(b[p + 1])
        c = hi - lo
        edges[p, :c, 0] = src_ordered[lo:hi]
        edges[p, :c, 1] = dst_ordered[lo:hi]
        mask[p, :c] = 1.0
    deg = np.zeros(num_vertices, dtype=np.float32)
    np.add.at(deg, src_ordered, 1.0)
    np.add.at(deg, dst_ordered, 1.0)
    mir = metrics.mirror_count_ordered(src_ordered, dst_ordered, k, num_vertices)
    rf = metrics.replication_factor_ordered(src_ordered, dst_ordered, k, num_vertices)
    return EngineData(
        edges=jnp.asarray(edges),
        mask=jnp.asarray(mask),
        degrees=jnp.asarray(deg),
        num_vertices=num_vertices,
        k=k,
        mirrors=mir,
        replication_factor=rf,
        num_edges=e,
    )


def unpack_ordered(data: EngineData) -> tuple[np.ndarray, np.ndarray]:
    """Host-side inverse of pack_ordered: the flat ordered (src, dst) lists."""
    edges = np.asarray(data.edges)
    counts = np.asarray(data.mask).astype(bool).sum(axis=1)
    src = np.concatenate([edges[p, : counts[p], 0] for p in range(data.k)])
    dst = np.concatenate([edges[p, : counts[p], 1] for p in range(data.k)])
    return src, dst


def cep_engine_data(g: Graph, order: np.ndarray, k: int) -> EngineData:
    return pack_ordered(g.src[order], g.dst[order], g.num_vertices, k)


# ------------------------------------------------------------ sharded layout
@dataclasses.dataclass(frozen=True)
class ShardedEngineData:
    """EngineData distributed over the ``graph`` axis of a mesh.

    ``edges``/``mask`` are (k_pad, E_max, 2) / (k_pad, E_max) arrays committed
    with a NamedSharding that splits the leading axis over ``graph``; rows are
    in device-major round-robin order (partition p at row
    ``launch.sharding.partition_row(p, k, g)``, hence on device p % g). Rows
    whose partition id ≥ k are padding: all-zero, fully masked. ``degrees`` is
    replicated. A mesh of 1 makes this layout bit-identical to ``EngineData``.
    """

    edges: jnp.ndarray  # (k_pad, E_max, 2) int32, sharded P("graph", ∅, ∅)
    mask: jnp.ndarray  # (k_pad, E_max) f32, sharded P("graph", ∅)
    degrees: jnp.ndarray  # (V,) f32, replicated
    num_vertices: int
    k: int  # logical partition count (rows may exceed it: k_pad = ⌈k/g⌉·g)
    mesh: object  # jax.sharding.Mesh with a "graph" axis
    mirrors: int
    replication_factor: float
    num_edges: int = 0

    @property
    def devices(self) -> int:
        return SH.graph_axis_size(self.mesh)

    @property
    def k_pad(self) -> int:
        return int(self.edges.shape[0])

    @property
    def rows_per_device(self) -> int:
        return self.k_pad // self.devices

    def partition_device(self, p: int) -> int:
        return SH.partition_device(p, self.devices)


def shard_engine_data(data: EngineData, mesh) -> ShardedEngineData:
    """Distribute a packed EngineData over ``mesh``'s ``graph`` axis.

    Works on multi-process meshes too: the host pack must then be replicated
    on every process (graphs are built deterministically from the seed, or
    broadcast by process 0 outside this function) and each process commits
    only the rows its devices own (``launch.multihost.put_global``)."""
    from ..launch import multihost as MH

    g = SH.graph_axis_size(mesh)
    k = data.k
    k_pad = SH.padded_partition_count(k, g)
    e_max = int(data.edges.shape[1])
    edges = np.zeros((k_pad, e_max, 2), dtype=np.int32)
    mask = np.zeros((k_pad, e_max), dtype=np.float32)
    rows = [SH.partition_row(p, k, g) for p in range(k)]
    edges[rows] = np.asarray(data.edges)
    mask[rows] = np.asarray(data.mask)
    s_edges, s_mask, s_vert = SH.engine_shardings(mesh)
    return ShardedEngineData(
        edges=MH.put_global(edges, s_edges),
        mask=MH.put_global(mask, s_mask),
        degrees=MH.put_global(np.asarray(data.degrees), s_vert),
        num_vertices=data.num_vertices,
        k=k,
        mesh=mesh,
        mirrors=data.mirrors,
        replication_factor=data.replication_factor,
        num_edges=data.num_edges,
    )


def unshard_engine_data(sdata: ShardedEngineData) -> EngineData:
    """Host-side inverse of shard_engine_data: gather + un-permute rows back to
    the partition-major replicated pack (the bit-identity oracle layout). On a
    multi-process mesh the gather is a collective (every process must call)."""
    from ..launch import multihost as MH

    rows = [SH.partition_row(p, sdata.k, sdata.devices) for p in range(sdata.k)]
    return EngineData(
        edges=jnp.asarray(MH.host_read(sdata.edges)[rows]),
        mask=jnp.asarray(MH.host_read(sdata.mask)[rows]),
        degrees=jnp.asarray(MH.host_read(sdata.degrees)),
        num_vertices=sdata.num_vertices,
        k=sdata.k,
        mirrors=sdata.mirrors,
        replication_factor=sdata.replication_factor,
        num_edges=sdata.num_edges,
    )


def pack_ordered_sharded(
    src_ordered: np.ndarray,
    dst_ordered: np.ndarray,
    num_vertices: int,
    k: int,
    mesh,
    *,
    e_max: int | None = None,
) -> ShardedEngineData:
    """pack_ordered, distributed: CEP chunks land round-robin on mesh devices."""
    return shard_engine_data(
        pack_ordered(src_ordered, dst_ordered, num_vertices, k, e_max=e_max), mesh
    )


# ------------------------------------------------------------- slot layout
def pack_slots(
    slot_src: np.ndarray,
    slot_dst: np.ndarray,
    slot_valid: np.ndarray,
    k: int,
    num_vertices: int,
) -> EngineData:
    """Pack a streaming slot array (stream/incremental.py) into engine buffers.

    Region p's ``slots_per_region`` slots become partition p's first columns —
    occupied slots keep their column (gaps are masked rows interleaved IN
    PLACE, not compacted, so a host slot maps 1:1 to a device (row, col) and
    an EdgeUpdateBatch applies as a scatter) — plus one trailing always-masked
    scratch column that padded scatter ops target (stream/ingest.py). GAS
    algorithms are mask-driven and run unchanged on this layout; this function
    is also the streaming bit-identity oracle: on-device ingest, unsharded,
    must equal it byte-for-byte.
    """
    slot_valid = np.asarray(slot_valid, dtype=bool)
    c = int(slot_valid.shape[0])
    if c % k:
        raise ValueError(f"slot capacity {c} is not a multiple of k={k}")
    spr = c // k
    e_cap = spr + 1  # + scratch column
    edges = np.zeros((k, e_cap, 2), dtype=np.int32)
    mask = np.zeros((k, e_cap), dtype=np.float32)
    edges[:, :spr, 0] = (np.asarray(slot_src) * slot_valid).reshape(k, spr)
    edges[:, :spr, 1] = (np.asarray(slot_dst) * slot_valid).reshape(k, spr)
    mask[:, :spr] = slot_valid.reshape(k, spr).astype(np.float32)
    deg = np.zeros(num_vertices, dtype=np.float32)
    np.add.at(deg, np.asarray(slot_src)[slot_valid], 1.0)
    np.add.at(deg, np.asarray(slot_dst)[slot_valid], 1.0)
    # Quality metrics are monitored incrementally by the orderer, not carried
    # on the pack (same convention as ElasticRescaler's recheck=False).
    return EngineData(
        edges=jnp.asarray(edges),
        mask=jnp.asarray(mask),
        degrees=jnp.asarray(deg),
        num_vertices=num_vertices,
        k=k,
        mirrors=-1,
        replication_factor=float("nan"),
        num_edges=int(slot_valid.sum()),
    )


def local_slot_partitions(k: int, mesh) -> list[int]:
    """Partition ids whose buffer rows this process's devices own, in row
    order (ids ≥ k are the all-masked padding rows and are omitted). The
    out-of-core commit materializes exactly these partitions' slots and no
    others — the full partition list never exists on one host."""
    from ..launch import multihost as MH

    g = SH.graph_axis_size(mesh)
    k_pad = SH.padded_partition_count(k, g)
    s_edges, _, _ = SH.engine_shardings(mesh)
    lo, hi = MH.addressable_row_block((k_pad, 1, 2), s_edges)
    parts = [SH.row_partition(r, k, g) for r in range(lo, hi)]
    return [p for p in parts if p < k]


def pack_slots_sharded_stream(
    part_fn,
    k: int,
    num_vertices: int,
    mesh,
    slots_per_region: int,
) -> ShardedEngineData:
    """``pack_slots`` committed shard by shard: no full-graph host array.

    ``part_fn(p) -> (slot_src, slot_dst, slot_valid)`` produces ONE
    partition's ``slots_per_region`` slots; it is called only for the
    partitions this process's devices own (``local_slot_partitions``), one
    at a time, into a staging buffer bounded by the local row block — which
    is per-process device memory, the floor for any commit. Degrees and the
    edge count are V-sized accumulators merged by ``psum_host``. Unsharded,
    the result is byte-identical to ``pack_slots`` over the concatenated
    slot arrays — the in-core oracle the out-of-core tests compare against.
    """
    from ..launch import multihost as MH

    g = SH.graph_axis_size(mesh)
    k_pad = SH.padded_partition_count(k, g)
    spr = int(slots_per_region)
    e_cap = spr + 1  # + scratch column, as pack_slots
    s_edges, s_mask, s_vert = SH.engine_shardings(mesh)
    lo, hi = MH.addressable_row_block((k_pad, e_cap, 2), s_edges)
    edges_local = np.zeros((hi - lo, e_cap, 2), dtype=np.int32)
    mask_local = np.zeros((hi - lo, e_cap), dtype=np.float32)
    deg_local = np.zeros(num_vertices, dtype=np.float32)
    count_local = 0
    for r in range(lo, hi):
        p = SH.row_partition(r, k, g)
        if p >= k:
            continue
        slot_src, slot_dst, slot_valid = part_fn(p)
        slot_valid = np.asarray(slot_valid, dtype=bool)
        if slot_valid.shape[0] != spr:
            raise ValueError(
                f"partition {p}: got {slot_valid.shape[0]} slots, expected {spr}"
            )
        edges_local[r - lo, :spr, 0] = np.asarray(slot_src) * slot_valid
        edges_local[r - lo, :spr, 1] = np.asarray(slot_dst) * slot_valid
        mask_local[r - lo, :spr] = slot_valid.astype(np.float32)
        np.add.at(deg_local, np.asarray(slot_src)[slot_valid], 1.0)
        np.add.at(deg_local, np.asarray(slot_dst)[slot_valid], 1.0)
        count_local += int(slot_valid.sum())
    deg = MH.psum_host(deg_local, mesh)
    total = int(MH.psum_host(np.asarray([count_local], dtype=np.int64), mesh)[0])
    return ShardedEngineData(
        edges=MH.put_global_local(edges_local, (k_pad, e_cap, 2), s_edges),
        mask=MH.put_global_local(mask_local, (k_pad, e_cap), s_mask),
        degrees=MH.put_global(deg, s_vert),
        num_vertices=num_vertices,
        k=k,
        mesh=mesh,
        mirrors=-1,
        replication_factor=float("nan"),
        num_edges=total,
    )


def _axis_and_mesh(data, mesh):
    """GAS dispatch: ShardedEngineData iterates over its own ``graph`` mesh;
    the replicated pack keeps the historical ``data``-axis path."""
    if isinstance(data, ShardedEngineData):
        return SH.GRAPH_AXIS, (mesh if mesh is not None else data.mesh)
    if mesh is None:
        raise ValueError("EngineData (replicated pack) requires an explicit mesh")
    return AXIS, mesh


def _sharded(fn, mesh, axis, extra_in=(), extra_out=P()):
    in_specs = (P(axis, None, None), P(axis, None)) + tuple(extra_in)
    return shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=extra_out, check_vma=False)


def pagerank(data, mesh=None, *, iterations: int = 20, damping: float = 0.85):
    axis, mesh = _axis_and_mesh(data, mesh)
    v = data.num_vertices
    deg = jnp.maximum(data.degrees, 1.0)

    def local(edges, mask, x):
        e = edges.reshape(-1, 2)  # all chunks owned by this device
        m = mask.reshape(-1)
        contrib = x / deg
        y = jnp.zeros((v,), jnp.float32)
        # Undirected: each edge pushes both ways (vertex-cut GAS scatter).
        y = y.at[e[:, 1]].add(contrib[e[:, 0]] * m)
        y = y.at[e[:, 0]].add(contrib[e[:, 1]] * m)
        return lax.psum(y, axis)

    step = _sharded(local, mesh, axis, extra_in=(P(),), extra_out=P())
    dangling = data.degrees == 0

    def body(x, _):
        y = step(data.edges, data.mask, x)
        # Dangling vertices spread their mass uniformly (networkx convention).
        dm = jnp.sum(jnp.where(dangling, x, 0.0))
        return (1 - damping) / v + damping * (y + dm / v), None

    x0 = jnp.full((v,), 1.0 / v, jnp.float32)
    with mesh:
        x, _ = jax.jit(lambda x0: lax.scan(body, x0, None, length=iterations))(x0)
    return x


def sssp(data, mesh=None, *, source: int = 0, max_iters: int = 64):
    axis, mesh = _axis_and_mesh(data, mesh)
    v = data.num_vertices
    inf = jnp.float32(1e9)

    def local(edges, mask, dist):
        e = edges.reshape(-1, 2)
        m = mask.reshape(-1) > 0
        cand = jnp.full((v,), inf)
        du = jnp.where(m, dist[e[:, 0]] + 1.0, inf)
        dv = jnp.where(m, dist[e[:, 1]] + 1.0, inf)
        cand = cand.at[e[:, 1]].min(du)
        cand = cand.at[e[:, 0]].min(dv)
        return lax.pmin(cand, axis)

    step = _sharded(local, mesh, axis, extra_in=(P(),), extra_out=P())

    def cond(state):
        _, changed, it = state
        return jnp.logical_and(changed, it < max_iters)

    def body(state):
        dist, _, it = state
        nd = jnp.minimum(dist, step(data.edges, data.mask, dist))
        return nd, jnp.any(nd < dist), it + 1

    d0 = jnp.full((v,), inf).at[source].set(0.0)
    with mesh:
        dist, _, iters = jax.jit(lambda d: lax.while_loop(cond, body, (d, jnp.bool_(True), 0)))(d0)
    return dist, int(iters)


def wcc(data, mesh=None, *, max_iters: int = 64):
    axis, mesh = _axis_and_mesh(data, mesh)
    v = data.num_vertices

    def local(edges, mask, lab):
        e = edges.reshape(-1, 2)
        m = mask.reshape(-1) > 0
        big = jnp.float32(1e9)
        cand = jnp.full((v,), big)
        lu = jnp.where(m, lab[e[:, 0]], big)
        lv = jnp.where(m, lab[e[:, 1]], big)
        cand = cand.at[e[:, 1]].min(lu)
        cand = cand.at[e[:, 0]].min(lv)
        return lax.pmin(cand, axis)

    step = _sharded(local, mesh, axis, extra_in=(P(),), extra_out=P())

    def cond(state):
        _, changed, it = state
        return jnp.logical_and(changed, it < max_iters)

    def body(state):
        lab, _, it = state
        nl = jnp.minimum(lab, step(data.edges, data.mask, lab))
        return nl, jnp.any(nl < lab), it + 1

    l0 = jnp.arange(v, dtype=jnp.float32)
    with mesh:
        lab, _, iters = jax.jit(lambda l: lax.while_loop(cond, body, (l, jnp.bool_(True), 0)))(l0)
    return lab, int(iters)


def comm_volume_per_iteration(data: EngineData, bytes_per_value: int = 8) -> int:
    """Paper §6.4 COM metric: each mirror sends + receives one value/iteration."""
    return 2 * data.mirrors * bytes_per_value


# --------------------------------------------------------------------------
# Cached pure-operand query programs (the serving path, launch/serve.py).
#
# The module-level entry points above close over the pack and build a fresh
# ``jax.jit(lambda ...)`` per call — every call is a new callable, so every
# call retraces. Fine for a benchmark that runs PageRank once; fatal for a
# front end answering thousands of queries. ``query_program`` returns a
# callable that takes the pack OPERANDS (edges, mask, degrees[, source])
# explicitly: the jit compiles once per operand shape, so one program serves
# every query against any pack of that layout — including the packs that
# rescale / async full rebuild swap underneath a live StreamingEngine, which
# only retrace when (k_pad, e_cap) actually changes. SSSP's source is a
# traced int32 operand, so querying a new source is a cache hit, not a
# retrace. Programs iterate over the ``graph`` mesh axis (the sharded-pack
# layout both ShardedEngineData and StreamingEngine.data use). Their jitted
# functions are named ``query_<kind>``, so a profiler trace reads them as
# ``jit_query_<kind>``.


def _pagerank_program(v: int, mesh, axis: str, iterations: int, damping: float):
    def local(edges, mask, contrib):
        e = edges.reshape(-1, 2)
        m = mask.reshape(-1)
        y = jnp.zeros((v,), jnp.float32)
        y = y.at[e[:, 1]].add(contrib[e[:, 0]] * m)
        y = y.at[e[:, 0]].add(contrib[e[:, 1]] * m)
        return lax.psum(y, axis)

    step = _sharded(local, mesh, axis, extra_in=(P(),), extra_out=P())

    def query_pagerank(edges, mask, degrees):
        deg = jnp.maximum(degrees, 1.0)
        dangling = degrees == 0

        def body(x, _):
            y = step(edges, mask, x / deg)
            dm = jnp.sum(jnp.where(dangling, x, 0.0))
            return (1 - damping) / v + damping * (y + dm / v), None

        x0 = jnp.full((v,), 1.0 / v, jnp.float32)
        x, _ = lax.scan(body, x0, None, length=iterations)
        return x

    jitted = jax.jit(query_pagerank)

    def call(edges, mask, degrees):
        with mesh:
            return jitted(edges, mask, degrees)

    return call


def _sssp_program(v: int, mesh, axis: str, max_iters: int):
    inf = jnp.float32(1e9)

    def local(edges, mask, dist):
        e = edges.reshape(-1, 2)
        m = mask.reshape(-1) > 0
        cand = jnp.full((v,), inf)
        du = jnp.where(m, dist[e[:, 0]] + 1.0, inf)
        dv = jnp.where(m, dist[e[:, 1]] + 1.0, inf)
        cand = cand.at[e[:, 1]].min(du)
        cand = cand.at[e[:, 0]].min(dv)
        return lax.pmin(cand, axis)

    step = _sharded(local, mesh, axis, extra_in=(P(),), extra_out=P())

    def cond(state):
        _, changed, it = state
        return jnp.logical_and(changed, it < max_iters)

    def body_fn(edges, mask):
        def body(state):
            dist, _, it = state
            nd = jnp.minimum(dist, step(edges, mask, dist))
            return nd, jnp.any(nd < dist), it + 1

        return body

    def query_sssp(edges, mask, source):
        d0 = jnp.full((v,), inf).at[source].set(0.0)
        return lax.while_loop(cond, body_fn(edges, mask), (d0, jnp.bool_(True), 0))

    jitted = jax.jit(query_sssp)

    def call(edges, mask, source=0):
        with mesh:
            dist, _, iters = jitted(edges, mask, jnp.int32(source))
        return dist, int(iters)

    return call


def _wcc_program(v: int, mesh, axis: str, max_iters: int):
    def local(edges, mask, lab):
        e = edges.reshape(-1, 2)
        m = mask.reshape(-1) > 0
        big = jnp.float32(1e9)
        cand = jnp.full((v,), big)
        lu = jnp.where(m, lab[e[:, 0]], big)
        lv = jnp.where(m, lab[e[:, 1]], big)
        cand = cand.at[e[:, 1]].min(lu)
        cand = cand.at[e[:, 0]].min(lv)
        return lax.pmin(cand, axis)

    step = _sharded(local, mesh, axis, extra_in=(P(),), extra_out=P())

    def cond(state):
        _, changed, it = state
        return jnp.logical_and(changed, it < max_iters)

    def body_fn(edges, mask):
        def body(state):
            lab, _, it = state
            nl = jnp.minimum(lab, step(edges, mask, lab))
            return nl, jnp.any(nl < lab), it + 1

        return body

    def query_wcc(edges, mask):
        l0 = jnp.arange(v, dtype=jnp.float32)
        return lax.while_loop(cond, body_fn(edges, mask), (l0, jnp.bool_(True), 0))

    jitted = jax.jit(query_wcc)

    def call(edges, mask):
        with mesh:
            lab, _, iters = jitted(edges, mask)
        return lab, int(iters)

    return call


QUERY_KINDS = ("pagerank", "sssp", "wcc")
_QUERY_PROGRAMS: dict = {}


def query_program(
    kind: str,
    *,
    num_vertices: int,
    mesh,
    iterations: int = 20,
    damping: float = 0.85,
    max_iters: int = 64,
):
    """Get-or-build the cached pure-operand program for ``kind``.

    Keyed on (kind, V, mesh, params); the returned callable's jit adds the
    per-shape level, so the full cache hierarchy is program → XLA executable
    per pack layout. Call signatures: pagerank ``(edges, mask, degrees) →
    ranks``; sssp ``(edges, mask, source=0) → (dist, iters)``; wcc
    ``(edges, mask) → (lab, iters)``.
    """
    key = (kind, int(num_vertices), mesh, int(iterations), float(damping), int(max_iters))
    prog = _QUERY_PROGRAMS.get(key)
    if prog is not None:
        return prog
    axis = SH.GRAPH_AXIS
    if kind == "pagerank":
        prog = _pagerank_program(int(num_vertices), mesh, axis, int(iterations), float(damping))
    elif kind == "sssp":
        prog = _sssp_program(int(num_vertices), mesh, axis, int(max_iters))
    elif kind == "wcc":
        prog = _wcc_program(int(num_vertices), mesh, axis, int(max_iters))
    else:
        raise ValueError(f"unknown query kind {kind!r} (expected one of {QUERY_KINDS})")
    _QUERY_PROGRAMS[key] = prog
    return prog
