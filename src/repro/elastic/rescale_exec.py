"""On-device elastic rescale executor — the paper's Thm.-1/2 promise, executed.

``cep.scale_plan(k_old → k_new)`` names the ≤ k_old + k_new − 1 ordered-edge
ranges whose owner changes; everything else stays where it is. This module
applies such a plan directly to the packed ``(k, E_max, 2)`` buffers of
graphs/engine.py as ONE jitted program of static slice copies, with the old
buffer donated — so executing a rescale costs O(overlay ranges) program size
and moves exactly the Thm.-2-minimal edge ranges across partitions, instead of
re-running any partitioner or re-packing from the host.

The program executes on the device pack, ``ShardedEngineData`` (DESIGN.md
§6): rows are permuted device-major (partition p on device p % g), the output
carries the k_new NamedSharding, and XLA's SPMD partitioner turns exactly the
plan's cross-device boundary ranges into device-to-device transfers while
stays and local shifts compile to shard-local slice copies. A mesh of 1 is
the degenerate case: every copy is device-local.

Cost accounting distinguishes what a real multi-host deployment would see:

* ``migrated_*`` — rows whose owner *partition* changes (equals
  ``ScalePlan.migrated_bytes`` by construction, asserted in tests);
* ``cross_device_*`` — the subset of migrated rows whose source and
  destination partitions live on different mesh devices (actual network /
  interconnect traffic; on a mesh of 1 this is 0);
* ``cross_process_*`` — the subset of cross-device rows whose devices belong
  to different ``jax.distributed`` processes (launch/multihost.py): what a
  real multi-host cluster pays on the NIC, reported separately from
  same-host device-to-device copies;
* ``on_device_edges`` — migrated rows whose partitions share a device
  (cross_device_edges + on_device_edges == migrated_edges);
* ``local_shift_edges`` — rows that keep their owner but land at a different
  slot in the padded buffer because the chunk start moved (device-local
  memmove, never network);
* pure stays are untouched semantically and alias through buffer donation on
  backends that implement it.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..compat import donate_jit
from ..core import cep, metrics
from ..graphs import engine as graph_engine
from ..graphs.engine import EDGE_BYTES
from ..launch import sharding as SH
from ..obs import metrics as OM
from ..obs import trace as OT
from ..program_cache import ProgramCache

__all__ = [
    "RescaleStats",
    "ElasticRescaler",
    "plan_segments",
    "cross_process_plan_edges",
]


def _mesh_processes(mesh) -> int:
    """Distinct processes behind a mesh (1 on a single-process mesh)."""
    return len(set(SH.device_process_map(mesh).tolist()))


def cross_process_plan_edges(plan: cep.ScalePlan, mesh) -> int:
    """Edges of the plan's move ranges whose source and destination partitions
    live on different *processes* of ``mesh`` — the Thm.-2 subset that a
    multi-host deployment pays on the network. Pure host arithmetic over the
    overlay (no device readback), so the network bill is known before the
    migration runs."""
    g = SH.graph_axis_size(mesh)
    procs = SH.device_process_map(mesh)
    return int(
        sum(
            hi - lo
            for lo, hi, s, d in plan.moves
            if procs[s % g] != procs[d % g]
        )
    )


def plan_segments(plan: cep.ScalePlan) -> list:
    """The plan's overlay as ordered (lo, hi, src_part, dst_part) copy
    segments — stays spelled src == dst. This is the exact instruction list of
    the migration program; benchmarks reuse it for per-device accounting."""
    return sorted(
        [(lo, hi, p, p) for lo, hi, p in plan.stay]
        + [(lo, hi, s, d) for lo, hi, s, d in plan.moves]
    )


@dataclasses.dataclass(frozen=True)
class RescaleStats:
    k_old: int
    k_new: int
    num_edges: int
    migrated_edges: int  # cross-partition rows (owner changed)
    migrated_bytes: int  # migrated_edges · EDGE_BYTES
    stay_edges: int  # rows whose owner is unchanged
    local_shift_edges: int  # stays that changed slot inside their partition
    copy_ops: int  # slice-copy instructions in the jitted program
    oracle_checked: bool  # compared bit-exactly vs a from-scratch pack
    elapsed_s: float  # wall time of the device program (blocked)
    recheck_s: float  # host-side metrics re-check (+ oracle compare) time
    devices: int = 1  # graph-axis size the program ran over
    cross_device_edges: int = 0  # migrated rows crossing a device boundary
    cross_device_bytes: int = 0  # cross_device_edges · EDGE_BYTES
    on_device_edges: int = 0  # migrated rows staying on their device
    processes: int = 1  # jax.distributed process count behind the mesh
    cross_process_edges: int = 0  # migrated rows crossing a PROCESS boundary
    cross_process_bytes: int = 0  # cross_process_edges · EDGE_BYTES — the
    # network bill of a real multi-host deployment (subset of cross_device_*;
    # same-host device-to-device copies never touch the NIC)


class ElasticRescaler:
    """Executes ``cep.ScalePlan``s against a ``ShardedEngineData`` (partitions
    distributed round-robin over a ``graph`` mesh axis; a mesh of 1 is the
    degenerate case).

    Jitted migration programs are cached per (num_edges, k_old, k_new, mesh)
    in a bounded LRU (``program_cache_size``) so a controller oscillating
    between cluster sizes pays tracing once without the cache growing without
    limit across a long-lived serving process. ``verify=True`` re-packs from
    scratch on the host and asserts bit-equality (the tests' oracle); the
    metrics re-check (mirrors, replication factor) keeps the returned data
    self-consistent.
    """

    def __init__(
        self,
        *,
        donate: bool = True,
        program_cache_size: int = 8,
        tracer=None,
        metrics_registry=None,
    ):
        self.donate = donate
        self._programs = ProgramCache(program_cache_size)
        # Observability (obs/): tracer=None falls back to the process-global
        # tracer (disabled by default); metrics default to the inert registry.
        self._tracer = tracer
        self.metrics = OM.NULL if metrics_registry is None else metrics_registry

    @property
    def tracer(self):
        return self._tracer if self._tracer is not None else OT.get_tracer()

    @property
    def program_cache_size(self) -> int:
        return self._programs.size

    # ------------------------------------------------------------- planning
    def plan(self, data: graph_engine.ShardedEngineData, k_new: int) -> cep.ScalePlan:
        return cep.scale_plan(data.num_edges, data.k, k_new)

    # ------------------------------------------------------------ execution
    def execute(
        self,
        data: graph_engine.ShardedEngineData,
        plan: cep.ScalePlan,
        *,
        verify: bool = False,
        recheck: bool = True,
    ):
        """Apply ``plan`` to ``data``; returns ``(new_data, RescaleStats)``.

        ``data`` must be CEP-chunked (partition p = ordered range p, as built
        by ``pack_ordered_sharded``). The old edge buffer
        is donated to the migration program: treat ``data`` as CONSUMED — on
        backends where XLA can alias it, reading ``data.edges`` afterwards
        raises "Array has been deleted".

        ``recheck=True`` recomputes mirrors / replication factor for k_new —
        an O(|E|) host pass (readback + per-chunk uniques). Latency-critical
        callers can pass ``recheck=False`` to keep the pure O(overlay-ranges)
        migration cost; the returned data then carries ``mirrors=-1``,
        ``replication_factor=nan`` (engine algorithms never read them).
        ``verify=True`` implies the readback regardless.
        """
        n, k_old, k_new = plan.num_edges, plan.k_old, plan.k_new
        mesh = data.mesh
        g = data.devices
        if data.k != k_old:
            raise ValueError(f"plan is for k_old={k_old} but engine data has k={data.k}")
        if data.num_edges != n:
            raise ValueError(f"plan is for |E|={n} but engine data has |E|={data.num_edges}")
        # Layout check without gathering the full mask: reduce per-row counts
        # on device (O(k_pad) ints to host) so recheck=False keeps the
        # O(overlay-ranges) migration cost on a real mesh. On a
        # multi-process mesh the row sums must land replicated before the host
        # can read them (the sharded result spans non-addressable devices);
        # the tiny counts program is cached like the migration programs.
        if not data.mask.is_fully_addressable:
            counts = np.asarray(self._counts_program(data.mask.shape, mesh)(data.mask))
        else:
            counts = np.asarray(jnp.sum(data.mask > 0, axis=1))
        sizes_old = np.diff(cep.chunk_bounds(n, k_old))
        want = np.zeros(counts.shape[0], dtype=sizes_old.dtype)
        for p in range(k_old):  # padding rows must stay empty
            want[SH.partition_row(p, k_old, g)] = sizes_old[p]
        if not np.array_equal(counts, want):
            raise ValueError(
                "engine data is not CEP-chunked (per-row edge counts "
                f"{counts.tolist()} != chunk sizes {want.tolist()}); "
                "range-copy rescaling only applies to pack_ordered layouts"
            )
        if k_new == k_old:
            # No-op plan: hand the buffers back untouched instead of pushing
            # them through a donating identity program (which would alias and
            # delete them out from under the caller).
            stats = RescaleStats(
                k_old=k_old, k_new=k_new, num_edges=n, migrated_edges=0,
                migrated_bytes=0, stay_edges=n, local_shift_edges=0,
                copy_ops=0, oracle_checked=False, elapsed_s=0.0, recheck_s=0.0,
                devices=g, processes=_mesh_processes(mesh),
            )
            return data, stats

        # One host readback of the *pre-migration* buffers: the flat ordered
        # edge list is invariant under rescaling, so it serves both the k_new
        # metrics re-check and — crucially independent of the program's output
        # — the verify=True from-scratch oracle.
        readback = recheck or verify
        if readback:
            src_o, dst_o = graph_engine.unpack_ordered(graph_engine.unshard_engine_data(data))
        else:
            src_o, dst_o = None, None

        program, stats_base = self._program(n, k_old, k_new, plan, mesh)
        t0 = time.perf_counter()
        with self.tracer.span("rescale.migrate"):
            new_edges, new_mask = program(data.edges)
            jax.block_until_ready(new_edges)
        elapsed = time.perf_counter() - t0
        m = self.metrics
        m.counter("rescale.cross_device_bytes").inc(stats_base.cross_device_bytes)
        m.counter("rescale.cross_process_bytes").inc(stats_base.cross_process_bytes)

        # Metrics re-check: recompute quality numbers for the new k (never
        # carried over from the old pack).
        t1 = time.perf_counter()
        if readback:
            counts_v = metrics.chunk_vertex_counts_ordered(src_o, dst_o, k_new)
            present = np.unique(np.concatenate([src_o, dst_o])).shape[0]
            mirrors = int(counts_v.sum() - present)
            rf = float(counts_v.sum()) / float(data.num_vertices)
        else:
            mirrors, rf = -1, float("nan")
        new_data = dataclasses.replace(
            data,
            edges=new_edges,
            mask=new_mask,
            k=k_new,
            mirrors=mirrors,
            replication_factor=rf,
        )

        oracle_checked = False
        if verify:
            # From-scratch pack of the ORIGINAL ordered list at k_new — a
            # mis-routed move segment cannot fool this.
            oracle = graph_engine.pack_ordered(src_o, dst_o, data.num_vertices, k_new)
            got = graph_engine.unshard_engine_data(new_data)
            if not (
                np.array_equal(oracle.edges, got.edges) and np.array_equal(oracle.mask, got.mask)
            ):
                raise AssertionError("executed rescale does not match from-scratch pack")
            oracle_checked = True
        recheck = time.perf_counter() - t1

        stats = dataclasses.replace(
            stats_base, oracle_checked=oracle_checked, elapsed_s=elapsed, recheck_s=recheck
        )
        return new_data, stats

    def rescale(
        self,
        data: graph_engine.ShardedEngineData,
        k_new: int,
        *,
        verify: bool = False,
        recheck: bool = True,
    ):
        """Plan + execute in one call (what the elastic controller uses)."""
        return self.execute(data, self.plan(data, k_new), verify=verify, recheck=recheck)

    # -------------------------------------------------------------- interns
    def _counts_program(self, mask_shape, mesh):
        """Per-row mask counts, replicated so every process can host-read them
        (multi-process meshes only — fully-addressable arrays reduce eagerly).
        Lives in the one kind-prefixed ProgramCache with the migration
        programs, so program_cache_size bounds ALL cached programs."""
        key = ("counts", tuple(mask_shape), mesh)
        cached = self._programs.get(key)
        if cached is not None:
            return cached
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        program = jax.jit(
            lambda m: jnp.sum(m > 0, axis=1), out_shardings=NamedSharding(mesh, P())
        )
        return self._programs.put(key, program)

    def _program(self, n: int, k_old: int, k_new: int, plan: cep.ScalePlan, mesh):
        g = SH.graph_axis_size(mesh)
        key = ("migrate", n, k_old, k_new, mesh)
        cached = self._programs.get(key)
        if cached is not None:
            return cached

        bo = cep.chunk_bounds(n, k_old)
        bn = cep.chunk_bounds(n, k_new)
        sizes_new = np.diff(bn)
        e_max_new = int(sizes_new.max())
        k_pad_new = SH.padded_partition_count(k_new, g)
        # Device-major row of each partition in the old / new layouts (the
        # identity on a mesh of 1).
        row_old = [SH.partition_row(p, k_old, g) for p in range(k_old)]
        row_new = [SH.partition_row(p, k_new, g) for p in range(k_new)]
        segments = plan_segments(plan)
        local_shift = sum(
            hi - lo for lo, hi, s, d in segments if s == d and int(bo[s]) != int(bn[s])
        )
        cross = sum(
            hi - lo
            for lo, hi, s, d in plan.moves
            if SH.partition_device(s, g) != SH.partition_device(d, g)
        )
        xproc = cross_process_plan_edges(plan, mesh)
        stats = RescaleStats(
            k_old=k_old,
            k_new=k_new,
            num_edges=n,
            migrated_edges=plan.migrated_edges,
            migrated_bytes=plan.migrated_bytes(EDGE_BYTES),
            stay_edges=sum(hi - lo for lo, hi, _ in plan.stay),
            local_shift_edges=int(local_shift),
            copy_ops=len(segments),
            oracle_checked=False,
            elapsed_s=0.0,
            recheck_s=0.0,
            devices=g,
            cross_device_edges=int(cross),
            cross_device_bytes=int(cross) * EDGE_BYTES,
            on_device_edges=plan.migrated_edges - int(cross),
            processes=_mesh_processes(mesh),
            cross_process_edges=xproc,
            cross_process_bytes=xproc * EDGE_BYTES,
        )
        mask_rows = np.zeros(k_pad_new, dtype=np.int64)
        for p in range(k_new):
            mask_rows[row_new[p]] = sizes_new[p]
        mask_new = jnp.asarray(
            (np.arange(e_max_new)[None, :] < mask_rows[:, None]).astype(np.float32)
        )

        def migrate(edges_old):
            new = jnp.zeros((k_pad_new, e_max_new, 2), edges_old.dtype)
            for lo, hi, s, d in segments:
                seg = edges_old[row_old[s], lo - int(bo[s]) : hi - int(bo[s]), :]
                new = new.at[row_new[d], lo - int(bn[d]) : hi - int(bn[d]), :].set(seg)
            return new, mask_new

        s_edges, s_mask, _ = SH.engine_shardings(mesh)
        out_shardings = (s_edges, s_mask)
        if self.donate:
            program = donate_jit(migrate, donate_argnums=(0,), out_shardings=out_shardings)
        else:
            program = jax.jit(migrate, out_shardings=out_shardings)
        return self._programs.put(key, (program, stats))
