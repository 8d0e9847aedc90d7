"""On-device ingest: apply EdgeUpdateBatches to the (sharded) engine pack.

The host ``IncrementalOrderer`` owns the ordered slot array; the
``StreamingEngine`` mirrors it on the mesh as a ``ShardedEngineData`` whose
partition p holds region p's slots (``graphs/engine.py pack_slots`` layout:
occupied slots keep their column, gaps are masked, one trailing scratch
column). Three jitted device program families — all in one bounded
kind-prefixed ``ProgramCache`` LRU, the same container the migration programs
of elastic/rescale_exec.py use — keep the mirror current without ever
re-packing from the host:

* **scatter** (ingest): each drained ``SlotOp`` becomes one (row, col) write
  of the edge values + mask bit, plus a scatter-add of the per-vertex degree
  deltas into the replicated degree vector. Ops are padded to a power-of-two
  batch capacity; padding targets the scratch column, which the program
  re-zeroes, so one traced program serves every batch of similar size.
* **compact** (rescale-under-ingest): the orderer's re-layout gather map
  (new slot ← old slot) becomes one gather over the old buffers with the
  k_new output sharding — XLA's SPMD partitioner routes exactly the rows
  whose region changed devices as device-to-device transfers, so rescaling
  keeps its O(k)-plan character while the stream is live.
* **span_repair** (partial re-order, the escalation ladder's middle rung):
  one program reads the degraded span's live slots straight from the sharded
  buffers, recomputes the span-local order (neighbor-expansion scoring with
  exact-objective candidate selection — kernels/span_reorder.py), and writes
  the repaired layout back as a single scatter over the span rows. The host
  runs the byte-exact numpy mirror of the same algorithm to keep its slot
  array and drift counters current, so the rung needs NO device round-trip
  and no slot-op upload (``scatter_limit`` only governs the host-mode
  fallback). Host ``geo_order`` on the extracted span is retained as the
  oracle: ``span_repair="oracle"`` applies it verbatim on device
  (bit-identical to the PR-3 host path), ``"differential"`` feeds it to the
  candidate selection so the repair is never worse than GEO by construction.
* **full_reorder** + **splice** (the full-rebuild rung, async — DESIGN.md
  §11): when ``full_rebuild`` is an async mode, the top rung only DISPATCHES
  — the whole-graph re-order program (kernels/full_reorder.py, the span
  program generalized to s = k) runs against the current buffers WITHOUT
  donating them, producing shadow output buffers while ingest keeps
  scattering into the live ones. ``rebuild_flight`` batches later the commit
  re-layouts the host slot array to the candidate order, replays the batches
  queued during the flight (``IncrementalOrderer.commit_full_rebuild``), and
  the **splice** program scatters the replay's coalesced slot ops onto the
  shadow buffers in fixed-capacity chunks — the swap that makes them the
  live pack. Ingest is never blocked longer than that one commit batch.

Their jitted functions are named ``stream_scatter``, ``rescale_compact``,
``span_repair``, ``full_reorder`` and ``rebuild_splice``, so a profiler trace
reads each as ``jit_<name>`` whatever the code around it is called.

All five program families live in ONE bounded ``ProgramCache`` LRU under
kind-prefixed keys, so ``program_cache_size`` bounds every cached program of
a long-lived engine — and the cache's per-kind hit/miss/eviction counters
(``program_cache_counters``) let the bench prove escalations never pay a
compile (every signature is warmed at layout changes; misses == compiles).

Bit-identity contract (DESIGN.md §9): after any sequence of ingests,
rescales, and span repairs, ``unshard_engine_data(engine.data)`` equals the
host-side ``pack_slots`` oracle byte-for-byte (``verify_bit_identity``;
asserted per step with ``verify=True``).
"""
from __future__ import annotations

import dataclasses
import logging
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from .. import compat
from ..compat import donate_jit
from ..core import cep
from ..elastic.rescale_exec import EDGE_BYTES, ProgramCache
from ..graphs import engine as graph_engine
from ..kernels import full_reorder as FRK
from ..kernels import span_reorder as SRK
from ..launch import sharding as SH
from ..obs import metrics as OM
from ..obs import trace as OT
from .incremental import IncrementalOrderer
from .updates import EdgeUpdateBatch

__all__ = ["IngestStats", "StreamRescaleStats", "StreamingEngine"]

_LOG = logging.getLogger(__name__)

_MIN_OP_CAPACITY = 32
# Fixed op capacity of the commit splice: one warmed program signature serves
# every commit; larger replay deltas run as chained chunks of this size.
_SPLICE_CAP = 1024
# full_rebuild engine mode → full-reorder program mode (kernels/full_reorder):
#   "geo"          — host geo_order candidate applied verbatim (the oracle
#                    path: commits are byte-identical to a host full_rebuild
#                    of the snapshot, modulo the async delta replay)
#   "device"       — on-mesh step-parallel greedy; the host mirror's
#                    never-worse-than-incumbent selection ships as an operand
#   "differential" — geo candidate, greedy-vs-candidate selection ON device
_FULL_PROGRAM_MODE = {"geo": "apply", "device": "greedy", "differential": "select"}


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def _rows_of_regions(regions: np.ndarray, k: int, g: int) -> np.ndarray:
    """Vectorized launch.sharding.partition_row."""
    m = SH.padded_partition_count(k, g) // g
    return (regions % g) * m + regions // g


@dataclasses.dataclass(frozen=True)
class IngestStats:
    inserted: int  # edges added to the graph
    deleted: int  # edges removed
    skipped: int  # duplicate inserts / deletes of absent edges (idempotent)
    scatter_ops: int  # slot writes in the device scatter (0 when resynced)
    resynced: bool  # True when the slot array re-laid out (grow/escalation)
    elapsed_s: float  # host apply + device program, blocked
    num_edges: int  # live edges after the batch


@dataclasses.dataclass(frozen=True)
class StreamRescaleStats:
    k_old: int
    k_new: int
    num_edges: int
    moved_edges: int  # edges whose owning region changed (actual)
    cep_plan_edges: int  # what CEP-chunk layouts would move for this |E|, k_old → k_new
    cross_device_edges: int  # moved edges whose regions live on different devices
    cross_device_bytes: int
    elapsed_s: float
    cross_process_edges: int = 0  # moved edges whose devices live on different
    cross_process_bytes: int = 0  # jax.distributed processes (the NIC bill)


class StreamingEngine:
    """Keeps a mesh-resident engine pack in lock-step with an
    ``IncrementalOrderer`` under streaming updates and rescales.

    ``engine.data`` is always a live ``ShardedEngineData``: GAS algorithms
    (pagerank / sssp / wcc) run on it unchanged between — and across —
    ingests, because the slot layout is mask-driven. A mesh of 1
    (``launch.mesh.make_graph_mesh(1)``) is the degenerate case of the same
    code path, per the repo's graph-axis convention.
    """

    def __init__(
        self,
        orderer: IncrementalOrderer,
        mesh=None,
        *,
        donate: bool = True,
        program_cache_size: int = 24,
        scatter_limit: int = 1024,
        span_repair: str = "device",
        full_rebuild: str = "host",
        rebuild_flight: int = 2,
        warm_scatter_caps: tuple = (),
        tracer=None,
        metrics_registry=None,
        commit: str = "pack",
    ):
        if mesh is None:
            from ..launch import mesh as MM

            mesh = MM.make_graph_mesh(1)
        if span_repair not in ("device", "host", "oracle", "differential"):
            raise ValueError(f"unknown span_repair mode {span_repair!r}")
        if full_rebuild not in ("host", "geo", "device", "differential"):
            raise ValueError(f"unknown full_rebuild mode {full_rebuild!r}")
        if rebuild_flight < 0:
            raise ValueError("rebuild_flight must be >= 0")
        if commit not in ("pack", "stream"):
            raise ValueError(f"unknown commit mode {commit!r}")
        self.orderer = orderer
        self.mesh = mesh
        self.donate = donate
        # Above this many slot ops, a full pack re-upload beats a giant
        # scatter — on CPU meshes markedly so. Only the HOST-mode partial rung
        # still produces span-sized op batches; the device rung rewrites the
        # span on-mesh and uploads nothing. Real accelerator meshes, where
        # host→device uploads cross PCIe while the scatter stays device-local,
        # should raise it.
        self.scatter_limit = int(scatter_limit)
        # Partial-rung implementation (DESIGN.md §9):
        #   "device"       — on-mesh span repair + byte-exact host mirror
        #   "host"         — PR-3 path: host geo_order + slot-op scatter
        #   "oracle"       — host geo_order applied verbatim by the device
        #                    program (bit-identical to "host"; the tests'
        #                    apply-mode oracle)
        #   "differential" — device repair with the geo_order oracle as the
        #                    scored candidate (never worse than GEO)
        self.span_repair = span_repair
        # Full-rebuild rung implementation (DESIGN.md §11):
        #   "host"         — PR-3 path: synchronous host geo_order + re-upload
        #   "geo"          — async; host geo_order candidate applied on-mesh
        #                    (the production mode on hosts where the device
        #                    greedy is not profitable, and the oracle mode)
        #   "device"       — async; on-mesh step-parallel greedy, never worse
        #                    than the incumbent layout by exact selection
        #   "differential" — async; geo candidate with on-device selection,
        #                    bit-identity verified at every commit
        self.full_rebuild = full_rebuild
        # Batches a dispatched rebuild stays in flight before its commit. 0 =
        # commit inside the dispatching monitor call (synchronous semantics —
        # the oracle-equivalence mode the tests pin against "host").
        self.rebuild_flight = int(rebuild_flight)
        self._flight: Optional[dict] = None  # in-flight rebuild state
        self._last_drift = 1.0  # drift tracker for dispatch anticipation
        self._drift_rate = 0.0  # EMA of per-batch drift growth
        self.rebuild_log: list = []  # committed/aborted rebuild records
        self.rebuild_state = ""  # ""/"dispatch"/"flight"/"commit"/"abort"
        self.last_rebuild_s = 0.0  # rebuild work inside the last monitor call
        self._greedy_overflow_logged = False  # int32-fallback warning fires once
        # ONE kind-prefixed LRU for every program family (scatter / compact /
        # span_repair / full_reorder / splice), like ElasticRescaler's
        # migrate+counts cache. The default is sized for the families SHARING
        # it: several scatter op-capacity buckets per layout, one compact
        # program per (k_old, k_new) pair of an oscillating controller, one
        # span + one full-reorder + one splice program per layout — an
        # eviction of a warmed program would put its recompile back inside
        # the monitored escalation path.
        self._programs = ProgramCache(program_cache_size)
        # Per-rung escalation accounting, surfaced on IngestEvents.
        self.rung_counts = {"none": 0, "partial": 0, "full": 0}
        self.rung_s = {"none": 0.0, "partial": 0.0, "full": 0.0}
        self.last_repair = ""  # what the last partial/full rung executed
        # Scatter op-capacity buckets to keep warm. Buckets are added as the
        # stream uses them and re-warmed at every layout change; callers that
        # know their batch sizes seed the expected buckets here so not even
        # the FIRST batch pays a compile inside the ingest path.
        self._seen_scatter_caps = {
            int(_next_pow2(int(c))) for c in warm_scatter_caps
        }
        # Observability (obs/, DESIGN.md §13). tracer=None falls back to the
        # process-global tracer (disabled by default: spans cost one branch);
        # the orderer's spans go to the same tracer. Metric objects are bound
        # once here so the per-batch hot path does no registry lookups —
        # against the default NULL registry every bound object is the shared
        # inert metric.
        self._tracer = tracer
        orderer.tracer = tracer
        self.metrics = OM.NULL if metrics_registry is None else metrics_registry
        m = self.metrics
        self._m_ingest_s = m.histogram("stream.ingest.batch_s")
        self._m_updates = {k: m.counter(f"stream.updates.{k}") for k in ("inserted", "deleted", "skipped")}
        self._m_scatter_ops = m.counter("stream.scatter_ops")
        # commit="stream" builds the INITIAL pack shard-by-shard
        # (pack_slots_sharded_stream): each process stages only the slot
        # rows its devices own, never a full host pack — the recovery path's
        # commit mode (a restored orderer re-homing onto a smaller surviving
        # mesh must not require the dead cluster's per-host memory headroom).
        # Steady-state resyncs after a re-layout still use the in-core
        # upload; "stream" only changes how the FIRST pack is committed.
        self.data = self._upload() if commit == "pack" else self._stream_upload()
        orderer.needs_resync = False
        self._warm_programs("ingest.warm")

    @classmethod
    def from_restored(cls, orderer, mesh=None, **kwargs) -> "StreamingEngine":
        """Build an engine around a checkpoint-restored orderer
        (``checkpoint.SlotCheckpoint.restore``), committing the initial pack
        via ``pack_slots_sharded_stream`` on the SURVIVING mesh — the
        recovery half of DESIGN.md §15. The orderer's slot array is already
        the recovered order (snapshot chunks + replayed WAL tail), so this is
        purely a commit: partition p's slot range feeds the shard streamer
        one region at a time, and only the rows this process's devices own
        are ever staged. Ingest then continues exactly as on the original
        cluster — the engine is indistinguishable from one that never died
        (the fault drill asserts that bit-for-bit)."""
        return cls(orderer, mesh, commit="stream", **kwargs)

    # ------------------------------------------------------------- plumbing
    @property
    def tracer(self):
        return self._tracer if self._tracer is not None else OT.get_tracer()

    @property
    def k(self) -> int:
        return self.orderer.regions

    @property
    def num_vertices(self) -> int:
        return self.orderer.num_vertices

    def oracle_pack(self) -> graph_engine.EngineData:
        """Host-side bit-identity oracle: pack_slots of the current host
        slot array (what the device buffers must equal after unshard)."""
        o = self.orderer
        return graph_engine.pack_slots(
            o.slot_src, o.slot_dst, o.slot_valid, o.regions, o.num_vertices
        )

    def _upload(self) -> graph_engine.ShardedEngineData:
        return graph_engine.shard_engine_data(self.oracle_pack(), self.mesh)

    def _stream_upload(self) -> graph_engine.ShardedEngineData:
        """Shard-streamed initial commit (see ``from_restored``): region r's
        slot range IS its CEP chunk, so the part_fn is a pure slice."""
        o = self.orderer
        spr = o.slots_per_region

        def part_fn(p: int):
            lo, hi = p * spr, (p + 1) * spr
            return o.slot_src[lo:hi], o.slot_dst[lo:hi], o.slot_valid[lo:hi]

        with self.tracer.span("ingest.stream_commit"):
            return graph_engine.pack_slots_sharded_stream(
                part_fn, o.regions, o.num_vertices, self.mesh, spr
            )

    def _host_operand(self, arr):
        """Host-built program operand (scatter indices, gather maps). On a
        multi-process mesh these must be committed replicated global arrays —
        every process builds the identical value from its replica of the host
        orderer state — because uncommitted single-device arrays cannot feed a
        program whose out_shardings span other processes. The single-process
        path stays the plain device transfer."""
        if compat.process_count() == 1:
            return jnp.asarray(arr)
        from ..launch import multihost as MH

        return MH.put_global(np.asarray(arr), NamedSharding(self.mesh, P()))

    def program_cache_counters(self) -> dict:
        """Per-kind {hits, misses, evictions} snapshot of the shared program
        cache — surfaced on IngestEvents/ScaleEvents so a stream log proves
        escalations never pay a compile (misses == compiles: the warm helpers
        probe with ``touch``, which counts nothing on absence)."""
        return self._programs.counters_snapshot()

    @property
    def rebuilds_in_flight(self) -> int:
        return 1 if self._flight is not None else 0

    def _resync(self) -> None:
        """Full host re-upload after a slot re-layout (grow / full rebuild).
        Rare by design — the escalation ladder's upper rungs. Aborts any
        in-flight rebuild: its snapshot geometry no longer exists."""
        if self._flight is not None:
            self._abort_rebuild("resync")
        with self.tracer.span("ingest.resync"):
            self.orderer.drain_ops()  # ops predate the re-layout; drop them
            self.data = self._upload()
        self.orderer.needs_resync = False
        self._warm_programs("ingest.warm")  # layout signature may have changed

    def _warm_programs(self, name: str) -> None:
        """Warm the span-repair, full-rebuild and scatter programs of the
        current layout, as the span ``name`` with children ``<name>.span``,
        ``.full`` and ``.scatter``; each child counts the program-cache
        misses (compiles) it paid as ``cache_misses``."""
        tr = self.tracer
        with tr.span(name):
            for child, warm in (("span", self._warm_span_program),
                                ("full", self._warm_full_program),
                                ("scatter", self._warm_scatter_programs)):
                with tr.span(f"{name}.{child}") as sp:
                    misses = self._program_misses()
                    warm()
                    sp.count(cache_misses=self._program_misses() - misses)

    def _program_misses(self) -> int:
        return sum(c["misses"] for c in self._programs.counters.values())

    def _warm_span_program(self) -> None:
        """Trace + compile the span-repair program for the CURRENT layout
        signature on throwaway buffers. Called at every layout change (init,
        rescale, resync) so a partial escalation never pays the compile
        inside the monitored stream path; a no-op when the signature is
        already cached."""
        if self.span_repair == "host":
            return
        o = self.orderer
        s = min(o.config.span_regions, o.regions)
        mode = {"oracle": "apply", "differential": "select"}.get(self.span_repair, "greedy")
        e_cap = int(self.data.edges.shape[1])
        key = self._span_key(mode, o.regions, self.data.k_pad, e_cap, s, self.mesh)
        # touch(), not `in`: a cache hit must refresh LRU recency, or a warmed
        # span program idling between escalations becomes the eviction victim
        # — and unlike get(), a touch of an ABSENT key counts no miss, which
        # keeps the counters' `misses == compiles` invariant exact.
        if self._programs.touch(key):
            return
        program = self._span_program(mode, o.regions, self.data.k_pad, e_cap, s, self.mesh)
        from ..launch import multihost as MH

        s_edges, s_mask, _ = SH.engine_shardings(self.mesh)
        dummy_e = MH.put_global(np.zeros(self.data.edges.shape, np.int32), s_edges)
        dummy_m = MH.put_global(np.zeros(self.data.mask.shape, np.float32), s_mask)
        out = program(
            dummy_e,
            dummy_m,
            self._host_operand(np.arange(s, dtype=np.int32)),
            self._host_operand(np.arange(s * (e_cap - 1), dtype=np.int32)),
            self._host_operand(np.zeros(1, dtype=np.int32)),
        )
        jax.block_until_ready(out[0])

    def _warm_full_program(self) -> None:
        """Trace + compile the async full-rebuild programs (whole-graph
        re-order + commit splice) for the CURRENT layout signature on
        throwaway buffers — same contract as ``_warm_span_program``: a full
        escalation must never pay a compile inside the monitored stream.
        No-op in the synchronous host mode."""
        if self.full_rebuild == "host":
            return
        from ..launch import multihost as MH

        o = self.orderer
        e_cap = int(self.data.edges.shape[1])
        mode = _FULL_PROGRAM_MODE[self.full_rebuild]
        s_edges, s_mask, _ = SH.engine_shardings(self.mesh)
        key = self._full_key(mode, o.regions, self.data.k_pad, e_cap, self.mesh)
        if not self._programs.touch(key):
            program = self._full_program(mode, o.regions, self.data.k_pad, e_cap, self.mesh)
            cap = o.regions * (e_cap - 1)
            operands = [
                MH.put_global(np.zeros(self.data.edges.shape, np.int32), s_edges),
                MH.put_global(np.zeros(self.data.mask.shape, np.float32), s_mask),
                self._host_operand(np.arange(o.regions, dtype=np.int32)),
                self._host_operand(np.arange(cap, dtype=np.int32)),
            ]
            if mode == "greedy":
                operands.append(self._host_operand(np.zeros(1, np.int32)))
            if mode in ("greedy", "select"):
                operands += [
                    self._host_operand(np.ones(1, np.int32)),  # alpha
                    self._host_operand(np.ones(1, np.int32)),  # beta
                    self._host_operand(np.ones(1, np.int32)),  # delta
                    self._host_operand(np.zeros(self.num_vertices, np.int32)),
                ]
            jax.block_until_ready(program(*operands)[0])
        skey = self._splice_key(self.data.k_pad, e_cap, self.mesh)
        if not self._programs.touch(skey):
            program = self._splice_program(self.data.k_pad, e_cap, self.mesh)
            out = program(
                MH.put_global(np.zeros(self.data.edges.shape, np.int32), s_edges),
                MH.put_global(np.zeros(self.data.mask.shape, np.float32), s_mask),
                self._host_operand(np.zeros(_SPLICE_CAP, np.int32)),
                self._host_operand(np.full(_SPLICE_CAP, e_cap - 1, np.int32)),
                self._host_operand(np.zeros((_SPLICE_CAP, 2), np.int32)),
                self._host_operand(np.zeros(_SPLICE_CAP, np.float32)),
            )
            jax.block_until_ready(out[0])

    def _warm_scatter_programs(self) -> None:
        """Trace + compile the ingest scatter program for every op-capacity
        bucket the stream has used (plus any caller-seeded buckets) under the
        CURRENT layout signature, on throwaway buffers. Re-run at every
        layout change, so steady-state ingest never pays a compile — not even
        on the first batch after a rescale swaps the program signature."""
        if not self._seen_scatter_caps:
            return
        from ..launch import multihost as MH

        e_cap = int(self.data.edges.shape[1])
        k_pad = self.data.k_pad
        s_edges, s_mask, s_vert = SH.engine_shardings(self.mesh)
        for cap in sorted(self._seen_scatter_caps):
            if self._programs.touch(("scatter", k_pad, e_cap, cap, self.mesh)):
                continue
            program = self._scatter_program(k_pad, e_cap, cap, self.mesh)
            out = program(
                MH.put_global(np.zeros(self.data.edges.shape, np.int32), s_edges),
                MH.put_global(np.zeros(self.data.mask.shape, np.float32), s_mask),
                MH.put_global(np.zeros(self.data.degrees.shape, np.float32), s_vert),
                self._host_operand(np.zeros(cap, np.int32)),
                self._host_operand(np.full(cap, e_cap - 1, np.int32)),
                self._host_operand(np.zeros((cap, 2), np.int32)),
                self._host_operand(np.zeros(cap, np.float32)),
                self._host_operand(np.zeros(2 * cap, np.int32)),
                self._host_operand(np.zeros(2 * cap, np.float32)),
            )
            jax.block_until_ready(out[0])

    def _sync_pending(self) -> None:
        """Bring the device mirror up to date with whatever the host orderer
        has applied since the last sync: resync after a re-layout, otherwise
        scatter the drained ops (re-upload beyond ``scatter_limit``)."""
        if self.orderer.needs_resync:
            self._resync()
            return
        ops, deg = self.orderer.drain_ops()
        if len(ops) > self.scatter_limit:
            self.data = self._upload()
        elif ops or deg:
            self._scatter(ops, deg)

    def verify_bit_identity(self) -> bool:
        got = graph_engine.unshard_engine_data(self.data)
        want = self.oracle_pack()
        if not (
            np.array_equal(np.asarray(got.edges), np.asarray(want.edges))
            and np.array_equal(np.asarray(got.mask), np.asarray(want.mask))
            and np.array_equal(np.asarray(got.degrees), np.asarray(want.degrees))
        ):
            raise AssertionError("sharded streaming pack diverged from the host slot oracle")
        return True

    # --------------------------------------------------------------- ingest
    def ingest(self, batch: EdgeUpdateBatch, *, verify: bool = False) -> IngestStats:
        """Apply one update batch: host slot placement, then the device
        scatter (or a resync when the batch forced a re-layout)."""
        t0 = time.perf_counter()
        tr = self.tracer
        with tr.span("ingest.batch"):
            with tr.span("ingest.apply") as sp:
                counts = self.orderer.apply(batch)
                sp.count(inserts=counts["inserted"], deletes=counts["deleted"],
                         skipped=counts["skipped"],
                         incident_entries=counts["incident_entries"],
                         free_entries=counts["free_entries"], grows=counts["grows"],
                         append_fallbacks=counts["append_fallbacks"])
            resynced = False
            n_ops = 0
            if self.orderer.needs_resync:
                self._resync()
                resynced = True
            else:
                ops, deg = self.orderer.drain_ops()
                n_ops = len(ops)
                if n_ops or deg:
                    self._scatter(ops, deg)
            with tr.span("ingest.ready"):
                jax.block_until_ready(self.data.edges)
        elapsed = time.perf_counter() - t0
        self._m_ingest_s.observe(elapsed)
        self._m_updates["inserted"].inc(counts["inserted"])
        self._m_updates["deleted"].inc(counts["deleted"])
        self._m_updates["skipped"].inc(counts["skipped"])
        if verify:
            self.verify_bit_identity()
        return IngestStats(
            inserted=counts["inserted"],
            deleted=counts["deleted"],
            skipped=counts["skipped"],
            scatter_ops=n_ops,
            resynced=resynced,
            elapsed_s=elapsed,
            num_edges=self.orderer.num_edges,
        )

    def _scatter(self, ops, deg: dict) -> None:
        cap = _next_pow2(max(len(ops), (len(deg) + 1) // 2, _MIN_OP_CAPACITY))
        with self.tracer.span("ingest.scatter") as sp:
            self._scatter_inner(ops, deg, cap)
            sp.count(ops=len(ops), cap=cap)
        self._m_scatter_ops.inc(len(ops))

    def _scatter_inner(self, ops, deg: dict, cap: int) -> None:
        o = self.orderer
        g = SH.graph_axis_size(self.mesh)
        k_pad = self.data.k_pad
        e_cap = int(self.data.edges.shape[1])  # slots_per_region + scratch
        self._seen_scatter_caps.add(cap)
        # Padding ops target the scratch column (always re-zeroed by the
        # program), so no real slot is ever clobbered by a no-op.
        rows = np.zeros(cap, dtype=np.int32)
        cols = np.full(cap, e_cap - 1, dtype=np.int32)
        vals = np.zeros((cap, 2), dtype=np.int32)
        mvals = np.zeros(cap, dtype=np.float32)
        for i, op in enumerate(ops):
            rows[i] = SH.partition_row(op.slot // o.slots_per_region, o.regions, g)
            cols[i] = op.slot % o.slots_per_region
            if op.valid:
                vals[i] = (op.u, op.v)
                mvals[i] = 1.0
        verts = np.zeros(2 * cap, dtype=np.int32)
        dvals = np.zeros(2 * cap, dtype=np.float32)
        for i, (v, d) in enumerate(sorted(deg.items())):
            verts[i] = v
            dvals[i] = float(d)
        program = self._scatter_program(k_pad, e_cap, cap, self.mesh)
        edges, mask, degrees = program(
            self.data.edges,
            self.data.mask,
            self.data.degrees,
            self._host_operand(rows),
            self._host_operand(cols),
            self._host_operand(vals),
            self._host_operand(mvals),
            self._host_operand(verts),
            self._host_operand(dvals),
        )
        self.data = dataclasses.replace(
            self.data,
            edges=edges,
            mask=mask,
            degrees=degrees,
            num_edges=o.num_edges,
        )

    def _scatter_program(self, k_pad: int, e_cap: int, cap: int, mesh):
        key = ("scatter", k_pad, e_cap, cap, mesh)
        cached = self._programs.get(key)
        if cached is not None:
            return cached

        def stream_scatter(edges, mask, degrees, rows, cols, vals, mvals, verts, dvals):
            edges = edges.at[rows, cols].set(vals)
            mask = mask.at[rows, cols].set(mvals)
            degrees = degrees.at[verts].add(dvals)
            # The scratch column absorbs padded no-op writes; keep it zero so
            # the pack stays bit-identical to the host oracle.
            edges = edges.at[:, -1, :].set(0)
            mask = mask.at[:, -1].set(0.0)
            return edges, mask, degrees

        s_edges, s_mask, s_vert = SH.engine_shardings(mesh)
        jit_kwargs = {"out_shardings": (s_edges, s_mask, s_vert)}
        if self.donate:
            program = donate_jit(stream_scatter, donate_argnums=(0, 1, 2), **jit_kwargs)
        else:
            program = jax.jit(stream_scatter, **jit_kwargs)
        return self._programs.put(key, program)

    # -------------------------------------------------------------- rescale
    def rescale(self, k_new: int, *, verify: bool = False) -> StreamRescaleStats:
        """Re-slice the live stream to ``k_new`` partitions without leaving
        the mesh: the orderer re-chunks the current incremental order (CEP at
        k_new) and the gather map executes as one compact program. Its steps
        are spans: ``rescale.sync``, ``rescale.relayout`` (the orderer's),
        ``rescale.gather_plan``, ``rescale.compact``, ``rescale.warm`` and
        ``rescale.ready``."""
        t0 = time.perf_counter()
        o = self.orderer
        tr = self.tracer
        with tr.span("rescale.sync"):
            # The host may have applied updates since the last device sync
            # (e.g. orderer.apply called directly): flush them first — the
            # gather map below describes the post-flush layout, and relayout
            # drops pending ops.
            self._sync_pending()
            # A rescale re-layouts every slot: an in-flight rebuild's snapshot
            # geometry (and its shadow buffers' shape) is void — abort it.
            if self._flight is not None:
                self._abort_rebuild("rescale")
        g = SH.graph_axis_size(self.mesh)
        k_old, spr_old = o.regions, o.slots_per_region
        old_edges = self.data.edges
        o.relayout(int(k_new))
        with tr.span("rescale.gather_plan"):
            gm = o.drain_gather_map()
            spr_new = o.slots_per_region
            e_cap_old = int(old_edges.shape[1])
            e_cap_new = spr_new + 1
            k_pad_new = SH.padded_partition_count(int(k_new), g)

            new_slots = np.flatnonzero(gm >= 0)
            old_slots = gm[new_slots]
            new_regions = new_slots // spr_new
            old_regions = old_slots // spr_old
            src_row = np.zeros((k_pad_new, e_cap_new), dtype=np.int32)
            src_col = np.zeros((k_pad_new, e_cap_new), dtype=np.int32)
            validf = np.zeros((k_pad_new, e_cap_new), dtype=np.float32)
            dst_rows = _rows_of_regions(new_regions, int(k_new), g)
            dst_cols = new_slots % spr_new
            src_row[dst_rows, dst_cols] = _rows_of_regions(old_regions, k_old, g)
            src_col[dst_rows, dst_cols] = old_slots % spr_old
            validf[dst_rows, dst_cols] = 1.0

            moved = int(np.count_nonzero(new_regions != old_regions))
            cross = int(
                np.count_nonzero(
                    (new_regions != old_regions) & (new_regions % g != old_regions % g)
                )
            )
            procs = SH.device_process_map(self.mesh)
            xproc = int(
                np.count_nonzero(
                    (new_regions != old_regions)
                    & (procs[new_regions % g] != procs[old_regions % g])
                )
            )
        program = self._compact_program(
            (int(old_edges.shape[0]), e_cap_old, k_pad_new, e_cap_new, self.mesh)
        )
        with tr.span("rescale.compact"):
            edges, mask = program(
                old_edges,
                self._host_operand(src_row),
                self._host_operand(src_col),
                self._host_operand(validf),
            )
        self.data = graph_engine.ShardedEngineData(
            edges=edges,
            mask=mask,
            degrees=self.data.degrees,  # same graph, degrees unchanged
            num_vertices=self.num_vertices,
            k=int(k_new),
            mesh=self.mesh,
            mirrors=-1,
            replication_factor=float("nan"),
            num_edges=o.num_edges,
        )
        o.needs_resync = False
        # The k_new layout is a new span/full/scatter-program signature:
        # compile them here, inside the rescale's reported latency, not
        # inside the first escalation or ingest of the new layout.
        self._warm_programs("rescale.warm")
        with tr.span("rescale.ready"):
            jax.block_until_ready(self.data.edges)
        elapsed = time.perf_counter() - t0
        m = self.metrics
        m.counter("stream.rescale.cross_device_bytes").inc(cross * EDGE_BYTES)
        m.counter("stream.rescale.cross_process_bytes").inc(xproc * EDGE_BYTES)
        if verify:
            self.verify_bit_identity()
        return StreamRescaleStats(
            k_old=k_old,
            k_new=int(k_new),
            num_edges=o.num_edges,
            moved_edges=moved,
            cep_plan_edges=cep.migrated_edges_exact(o.num_edges, k_old, int(k_new)),
            cross_device_edges=cross,
            cross_device_bytes=cross * EDGE_BYTES,
            elapsed_s=elapsed,
            cross_process_edges=xproc,
            cross_process_bytes=xproc * EDGE_BYTES,
        )

    def _compact_program(self, key):
        cached = self._programs.get(("compact",) + key)
        if cached is not None:
            return cached
        mesh = key[-1]

        def rescale_compact(edges_old, src_row, src_col, validf):
            gathered = edges_old[src_row, src_col]  # (k_pad_new, e_cap_new, 2)
            new_edges = gathered * validf[..., None].astype(gathered.dtype)
            return new_edges, validf

        s_edges, s_mask, _ = SH.engine_shardings(mesh)
        jit_kwargs = {"out_shardings": (s_edges, s_mask)}
        if self.donate:
            program = donate_jit(rescale_compact, donate_argnums=(0,), **jit_kwargs)
        else:
            program = jax.jit(rescale_compact, **jit_kwargs)
        return self._programs.put(("compact",) + key, program)

    # ------------------------------------------------------------ escalation
    def monitor(self) -> str:
        """Quality-monitor step of the escalation ladder. The ladder decision
        stays in the orderer (``escalation()``); execution is delegated here
        per rung: a partial span re-order runs as the cached on-mesh
        span-repair program (mode ``span_repair``; host mode falls back to
        slot-op scatter / re-upload under ``scatter_limit``), a full rebuild
        as a synchronous resync (``full_rebuild="host"``) or an ASYNC
        dispatch (DESIGN.md §11): the whole-graph re-order program runs
        against shadow buffers for ``rebuild_flight`` batches, then commits,
        so ingest never blocks for longer than the one commit batch.
        Escalation is suppressed while a rebuild is in flight — the drift
        being measured is already being repaired, and the dispatch
        ANTICIPATION below fires the rung early enough that the commit lands
        before the live order leaves its quality margin. Per-rung counters
        and timings accumulate in ``rung_counts`` / ``rung_s`` (dispatch and
        commit both land in 'full'). Returns 'none' | 'partial' | 'full'."""
        t0 = time.perf_counter()
        with self.tracer.span("rung.monitor"):
            rung = self._monitor_inner()
        self.rung_counts[rung] += 1
        self.rung_s[rung] += time.perf_counter() - t0
        return rung

    def _monitor_inner(self) -> str:
        self.rebuild_state = ""
        self.last_rebuild_s = 0.0
        # Flush anything the host applied since the last sync FIRST: the span
        # program reads the device buffers, which must mirror the host slots.
        self._sync_pending()
        # Dispatch anticipation: project the drift forward by the flight
        # window (per-batch growth rate × rebuild_flight) so an async full
        # rung fires early enough that its COMMIT lands at roughly the drift
        # a synchronous rebuild would have repaired at. The rate is an EMA of
        # the per-batch growth — anticipation projects the TREND; a single
        # noisy drift jump must not halve the rebuild cycle by inflating the
        # lookahead for one batch. Commits/rescales drop drift below the
        # tracker, clamping that batch's sample to 0 and decaying the EMA —
        # anticipation re-arms as growth resumes.
        d = self.orderer.drift()
        lookahead = 0.0
        if self.full_rebuild != "host" and self.rebuild_flight > 0:
            sample = max(0.0, d - self._last_drift)
            self._drift_rate = 0.7 * self._drift_rate + 0.3 * sample
            lookahead = self.rebuild_flight * self._drift_rate
        self._last_drift = d
        if self._flight is not None:
            self._flight["countdown"] -= 1
            if self._flight["countdown"] <= 0:
                self._commit_rebuild()
                rung = "full"
            else:
                self.rebuild_state = "flight"
                self.last_repair = ""
                rung = "none"
        else:
            # Partial shadow: with a full projected within two flight windows,
            # a span repair buys nothing the imminent whole-graph commit will
            # not erase (repeated partials on the same drifted layout plateau
            # after the first pass) — suppress it and save the rung's cost.
            rung = self.orderer.maybe_escalate(
                partial_fn=self._partial_rung, full_fn=self._full_rung,
                full_lookahead=lookahead, partial_shadow=2.0 * lookahead,
            )
            if rung == "none":
                self.last_repair = ""
            if self._flight is not None and self._flight["countdown"] <= 0:
                # rebuild_flight == 0: dispatch and commit inside one monitor
                # call — synchronous semantics, the oracle-equivalence mode.
                self._commit_rebuild()
        return rung

    def _full_rung(self) -> None:
        """Execute the full rung: host mode keeps the synchronous PR-3 path
        (host ``geo_order`` + full re-upload); the async modes dispatch the
        on-mesh rebuild and return without blocking."""
        if self.full_rebuild == "host":
            with self.tracer.span("rebuild.sync"):
                self.orderer.full_rebuild()
                self._resync()
            self.last_repair = "resync"
        else:
            self._dispatch_rebuild()
            self.rebuild_state = "dispatch"
            self.last_repair = "dispatch"

    # ------------------------------------------------------ async full rebuild
    def _dispatch_rebuild(self) -> None:
        """Dispatch the full rung asynchronously: snapshot the host slot
        arrays (``begin_full_rebuild`` starts queuing batches for the commit's
        replay), compute the candidate decision host-side via the byte-exact
        mirror, and launch the cached whole-graph re-order program against the
        CURRENT device buffers WITHOUT donating them — the program's fresh
        output arrays are the shadow pack the commit splices the flight's
        delta onto, while ingest keeps scattering into the live ones. Nothing
        here blocks on the device."""
        with self.tracer.span("rebuild.dispatch"):
            self._dispatch_rebuild_inner()
        self.last_rebuild_s = self._flight["dispatch_s"]

    def _dispatch_rebuild_inner(self) -> None:
        t0 = time.perf_counter()
        o = self.orderer
        u = o.slot_src.copy()
        v = o.slot_dst.copy()
        valid = o.slot_valid.copy()
        o.begin_full_rebuild()
        mode = _FULL_PROGRAM_MODE[self.full_rebuild]
        nv = self.num_vertices
        n_live = int(valid.sum())
        ks = FRK.eval_ks_full(o.config.k_min, o.config.k_max, o.regions)
        use_cand = True
        params = None
        mode_label = self.full_rebuild
        rung_mode = self.full_rebuild
        if rung_mode != "geo":
            deg = np.bincount(np.concatenate([u[valid], v[valid]]), minlength=1)
            if not FRK.greedy_fits_int32(
                n_live, o.config.k_min, o.config.k_max, int(deg.max())
            ):
                # The on-mesh greedy's int32 priorities would overflow on
                # this graph (out-of-core scales cross the bound routinely).
                # Degrade to the host-order "apply" path instead of raising —
                # a full rebuild must never abort the ingest loop.
                if not self._greedy_overflow_logged:
                    self._greedy_overflow_logged = True
                    _LOG.warning(
                        "full-rebuild greedy overflows int32 at |E|=%d, "
                        "max_degree=%d: falling back to host geo_order "
                        "(logged once per engine)",
                        n_live,
                        int(deg.max()),
                    )
                rung_mode = "geo"
                mode = _FULL_PROGRAM_MODE["geo"]
                mode_label = f"{self.full_rebuild}+host-fallback"
        if rung_mode == "geo":
            # Oracle path: host geo_order IS the committed order; the device
            # program applies it verbatim (mode "apply").
            chosen = FRK.geo_full_candidate(u, v, valid, nv, o.config.k_min, o.config.k_max)
            cand = chosen
        else:
            if rung_mode == "device":
                cand = FRK.identity_candidate(valid)  # incumbent = never-worse floor
            else:  # differential: geo oracle as the scored candidate
                cand = FRK.geo_full_candidate(u, v, valid, nv, o.config.k_min, o.config.k_max)
            alpha, beta, delta = FRK.greedy_params(
                n_live, o.config.k_min, o.config.k_max, int(deg.max())
            )
            permpos = FRK.fallback_positions(nv)
            chosen, use_cand = FRK.select_full_order_host(
                u, v, valid, nv, cand, ks, alpha, beta, delta, permpos
            )
            params = (alpha, beta, delta, permpos)
        live_order = np.asarray(chosen[:n_live], dtype=np.int64)
        cand_src = u[live_order]
        cand_dst = v[live_order]
        e_cap = int(self.data.edges.shape[1])
        g = SH.graph_axis_size(self.mesh)
        rows = np.asarray(
            [SH.partition_row(p, o.regions, g) for p in range(o.regions)], dtype=np.int32
        )
        program = self._full_program(mode, o.regions, self.data.k_pad, e_cap, self.mesh)
        operands = [
            self.data.edges,
            self.data.mask,
            self._host_operand(rows),
            self._host_operand(np.asarray(cand, dtype=np.int32)),
        ]
        if mode == "greedy":
            operands.append(
                self._host_operand(np.asarray([1 if use_cand else 0], np.int32))
            )
        if params is not None:
            alpha, beta, delta, permpos = params
            operands += [
                self._host_operand(np.asarray([alpha], np.int32)),
                self._host_operand(np.asarray([beta], np.int32)),
                self._host_operand(np.asarray([delta], np.int32)),
                self._host_operand(np.asarray(permpos, np.int32)),
            ]
        cand_edges, cand_mask = program(*operands)  # async — never blocked here
        self._flight = {
            "mode": mode_label,
            "countdown": self.rebuild_flight,
            "cand_dev": (cand_edges, cand_mask),
            "cand_src": cand_src,
            "cand_dst": cand_dst,
            "snapshot_edges": n_live,
            "dispatch_s": time.perf_counter() - t0,
        }

    def _commit_rebuild(self) -> None:
        """Commit the in-flight rebuild: re-layout the host slot array to the
        candidate order and replay the flight's queued batches
        (``commit_full_rebuild``), then splice the replay's coalesced slot ops
        onto the shadow buffers — the swap that makes them the live pack.
        Blocks, so the full rung's reported cost is honest. Falls back to a
        resync when the commit could not keep the buffer shape."""
        with self.tracer.span("rebuild.commit"):
            self._commit_rebuild_inner()

    def _commit_rebuild_inner(self) -> None:
        t0 = time.perf_counter()
        fl, self._flight = self._flight, None
        o = self.orderer
        replayed = o.rebuild_delta_batches
        ok = o.commit_full_rebuild(fl["cand_src"], fl["cand_dst"])
        splice_ops = 0
        if not ok:
            self._resync()
            self.last_repair = "resync"
        else:
            ops, _ = o.drain_ops()  # the replay's delta vs the candidate layout
            splice_ops = len(ops)
            edges, mask = fl["cand_dev"]
            if ops:
                edges, mask = self._splice(edges, mask, ops)
            self.data = dataclasses.replace(
                self.data, edges=edges, mask=mask, num_edges=o.num_edges
            )
            self.last_repair = fl["mode"]
        jax.block_until_ready(self.data.edges)
        self.rebuild_state = "commit"
        commit_s = time.perf_counter() - t0
        self.last_rebuild_s = commit_s
        if self.full_rebuild == "differential":
            self.verify_bit_identity()
        self.rebuild_log.append(
            {
                "kind": "full_rebuild",
                "mode": fl["mode"],
                "committed": bool(ok),
                "aborted": False,
                "snapshot_edges": fl["snapshot_edges"],
                "replayed_batches": replayed,
                "splice_ops": splice_ops,
                "flight_batches": self.rebuild_flight - fl["countdown"],
                "dispatch_s": fl["dispatch_s"],
                "commit_s": commit_s,
            }
        )

    def _abort_rebuild(self, reason: str) -> None:
        """Drop an in-flight rebuild: a re-layout (grow / rescale) voided its
        snapshot geometry. The shadow buffers are simply released; drift is
        untouched, so the ladder re-fires once the dust settles."""
        fl, self._flight = self._flight, None
        self.orderer.abort_full_rebuild()
        self.rebuild_state = "abort"
        self.rebuild_log.append(
            {
                "kind": "full_rebuild",
                "mode": fl["mode"],
                "committed": False,
                "aborted": True,
                "abort_reason": reason,
                "snapshot_edges": fl["snapshot_edges"],
                "replayed_batches": 0,
                "splice_ops": 0,
                "flight_batches": self.rebuild_flight - fl["countdown"],
                "dispatch_s": fl["dispatch_s"],
                "commit_s": 0.0,
            }
        )

    def drain_rebuild_events(self) -> list:
        """Completed (committed or aborted) rebuild records since the last
        drain. The controller wraps them into ``RebuildEvent``s, assigning
        the shared monotonic seq at drain — i.e. completion-commit — time."""
        log, self.rebuild_log = self.rebuild_log, []
        return log

    def _splice(self, edges, mask, ops):
        """Scatter the commit's replay ops onto the shadow buffers in
        fixed-capacity chunks (one warmed splice signature serves every
        commit; padding targets the re-zeroed scratch column, exactly like
        the ingest scatter)."""
        o = self.orderer
        g = SH.graph_axis_size(self.mesh)
        e_cap = int(edges.shape[1])
        program = self._splice_program(self.data.k_pad, e_cap, self.mesh)
        for base in range(0, len(ops), _SPLICE_CAP):
            chunk = ops[base : base + _SPLICE_CAP]
            rows = np.zeros(_SPLICE_CAP, dtype=np.int32)
            cols = np.full(_SPLICE_CAP, e_cap - 1, dtype=np.int32)
            vals = np.zeros((_SPLICE_CAP, 2), dtype=np.int32)
            mvals = np.zeros(_SPLICE_CAP, dtype=np.float32)
            for i, op in enumerate(chunk):
                rows[i] = SH.partition_row(op.slot // o.slots_per_region, o.regions, g)
                cols[i] = op.slot % o.slots_per_region
                if op.valid:
                    vals[i] = (op.u, op.v)
                    mvals[i] = 1.0
            edges, mask = program(
                edges,
                mask,
                self._host_operand(rows),
                self._host_operand(cols),
                self._host_operand(vals),
                self._host_operand(mvals),
            )
        return edges, mask

    def _splice_key(self, k_pad: int, e_cap: int, mesh):
        return ("splice", k_pad, e_cap, _SPLICE_CAP, mesh)

    def _splice_program(self, k_pad: int, e_cap: int, mesh):
        key = self._splice_key(k_pad, e_cap, mesh)
        cached = self._programs.get(key)
        if cached is not None:
            return cached

        def rebuild_splice(edges, mask, rows, cols, vals, mvals):
            edges = edges.at[rows, cols].set(vals)
            mask = mask.at[rows, cols].set(mvals)
            # Scratch column absorbs the padded no-op writes (same contract
            # as the ingest scatter).
            edges = edges.at[:, -1, :].set(0)
            mask = mask.at[:, -1].set(0.0)
            return edges, mask

        s_edges, s_mask, _ = SH.engine_shardings(mesh)
        jit_kwargs = {"out_shardings": (s_edges, s_mask)}
        if self.donate:
            # Donating is safe HERE: the inputs are the shadow buffers (or a
            # previous chunk's output), which nothing else references.
            program = donate_jit(rebuild_splice, donate_argnums=(0, 1), **jit_kwargs)
        else:
            program = jax.jit(rebuild_splice, **jit_kwargs)
        return self._programs.put(key, program)

    def _full_key(self, mode: str, k: int, k_pad: int, e_cap: int, mesh):
        o = self.orderer
        ks = FRK.eval_ks_full(o.config.k_min, o.config.k_max, k)
        use_pallas = SH.graph_axis_size(mesh) == 1 and compat.process_count() == 1
        return ("full_reorder", mode, k, k_pad, e_cap, ks, use_pallas, mesh)

    def _full_program(self, mode: str, k: int, k_pad: int, e_cap: int, mesh):
        """Whole-graph re-order program — the span program generalized to
        s = k (kernels/full_reorder.py), with one structural difference: the
        input buffers are NOT donated. The outputs are fresh arrays — the
        shadow half of the double buffer — so ingest keeps scattering into
        the live pack while this runs.

        Modes: ``apply`` applies the host geo_order candidate verbatim (the
        oracle path); ``greedy`` recomputes the step-parallel greedy on
        device with the mirror's never-worse selection as a scalar operand;
        ``select`` scores greedy vs candidate on device (differential)."""
        spr = e_cap - 1
        cap = k * spr
        key = self._full_key(mode, k, k_pad, e_cap, mesh)
        ks, use_pallas = key[5], key[6]
        cached = self._programs.get(key)
        if cached is not None:
            return cached
        num_vertices = self.num_vertices

        def full_reorder(edges, mask, rows, cand, *rest):
            blk_e = edges[rows]  # (k, e_cap, 2) — every region's row
            blk_m = mask[rows]
            u = blk_e[:, :spr, 0].reshape(cap)
            v = blk_e[:, :spr, 1].reshape(cap)
            valid = blk_m[:, :spr].reshape(cap) > 0
            n = jnp.sum(valid.astype(jnp.int32))
            if mode == "apply":
                order = cand
            elif mode == "select":
                alpha, beta, delta, permpos = rest
                order = FRK.select_full_order_device(
                    u, v, valid, num_vertices, cand, ks,
                    alpha[0], beta[0], delta[0], permpos, use_pallas=use_pallas,
                )
            else:  # greedy: the mirror's exact decision arrives as an operand
                use_cand, alpha, beta, delta, permpos = rest
                order = jax.lax.cond(
                    use_cand[0] > 0,
                    lambda: cand,
                    lambda: FRK.full_order_device(
                        u, v, valid, num_vertices, alpha[0], beta[0], delta[0], permpos
                    ),
                )
            tgt = SRK.splice_targets_device(n, k, spr, cap)
            j = jnp.arange(cap, dtype=jnp.int32)
            live = j < n
            new_u = jnp.zeros(cap + 1, jnp.int32).at[tgt].set(
                jnp.where(live, u[order], 0)
            )[:cap]
            new_v = jnp.zeros(cap + 1, jnp.int32).at[tgt].set(
                jnp.where(live, v[order], 0)
            )[:cap]
            new_m = jnp.zeros(cap + 1, jnp.float32).at[tgt].set(
                live.astype(jnp.float32)
            )[:cap]
            blk = jnp.stack([new_u.reshape(k, spr), new_v.reshape(k, spr)], axis=-1)
            blk = jnp.concatenate([blk, jnp.zeros((k, 1, 2), jnp.int32)], axis=1)
            mblk = jnp.concatenate(
                [new_m.reshape(k, spr), jnp.zeros((k, 1), jnp.float32)], axis=1
            )
            return edges.at[rows].set(blk), mask.at[rows].set(mblk)

        s_edges, s_mask, _ = SH.engine_shardings(mesh)
        # No donation by design — see the docstring.
        program = jax.jit(full_reorder, out_shardings=(s_edges, s_mask))
        return self._programs.put(key, program)

    def _partial_rung(self) -> None:
        """Execute the partial rung in the configured mode. Host bookkeeping
        (slot array, drift counters) always advances through the orderer —
        via the byte-exact numpy mirror for the device modes — so the monitor
        needs no device readback."""
        with self.tracer.span("rung.partial"):
            self._partial_rung_inner()

    def _partial_rung_inner(self) -> None:
        o = self.orderer
        if self.span_repair == "host":
            o.partial_reorder()  # slot ops picked up by _sync_pending below
            self._sync_pending()
            self.last_repair = "host"
            return
        r0, r1 = o.span_bounds()
        u, v, valid = o.span_arrays(r0, r1)
        if int(valid.sum()) < 2:
            self.last_repair = "skipped"
            return
        if self.span_repair == "device":
            cand = SRK.identity_candidate(valid)
        else:  # "oracle" | "differential": host geo_order on the span
            cand = o.geo_span_candidate(u, v, valid)
        use_cand = False
        if self.span_repair == "oracle":
            o.apply_span_order(r0, r1, cand, emit_ops=False)
        else:
            _, use_cand = o.partial_reorder_mirror(
                region=r0, candidate=cand, emit_ops=False
            )
        self._span_repair_device(r0, r1, cand, use_cand)
        self.last_repair = self.span_repair

    def _span_repair_device(
        self, r0: int, r1: int, cand: np.ndarray, use_cand: bool
    ) -> None:
        """Run the cached span-repair program over regions [r0, r1): extract
        the span's live slots from the sharded buffers, re-order, splice back
        — one program, nothing read back (the host mirror already advanced
        the slot array, so the call is left ASYNC and overlaps the next
        batch's host placement). In the production mode the mirror's exact
        candidate decision ships as a scalar operand; differential mode keeps
        the whole selection — objectives included — on device."""
        o = self.orderer
        g = SH.graph_axis_size(self.mesh)
        rows = np.asarray(
            [SH.partition_row(p, o.regions, g) for p in range(r0, r1)], dtype=np.int32
        )
        mode = {"oracle": "apply", "differential": "select"}.get(self.span_repair, "greedy")
        program = self._span_program(
            mode, o.regions, self.data.k_pad, int(self.data.edges.shape[1]),
            r1 - r0, self.mesh,
        )
        edges, mask = program(
            self.data.edges,
            self.data.mask,
            self._host_operand(rows),
            self._host_operand(np.asarray(cand, dtype=np.int32)),
            # shape (1,), not 0-d: put_global's row-block math needs an axis
            self._host_operand(np.asarray([1 if use_cand else 0], dtype=np.int32)),
        )
        # Block here so the rung's reported cost INCLUDES the device program
        # (honest accounting: without this, async dispatch would push the
        # repair's runtime into whatever next touches the buffers).
        jax.block_until_ready(edges)
        # Degrees untouched: a re-order never changes the graph.
        self.data = dataclasses.replace(self.data, edges=edges, mask=mask)

    def _span_key(self, mode: str, k: int, k_pad: int, e_cap: int, s: int, mesh):
        ks = SRK.eval_ks(self.orderer.config.k_min, self.orderer.config.k_max)
        # Pallas custom calls don't SPMD-partition: only single-device,
        # single-process programs route the objective's distinct counting
        # through the segment_rf boundary kernel (same integers either way).
        use_pallas = SH.graph_axis_size(mesh) == 1 and compat.process_count() == 1
        return ("span_repair", mode, k, k_pad, e_cap, s, ks, use_pallas, mesh)

    def _span_program(self, mode: str, k: int, k_pad: int, e_cap: int, s: int, mesh):
        """Span-repair program, cached per static signature: kind-prefixed in
        the shared LRU; span length, k, and e_max changes all re-key.

        Modes: ``greedy`` recomputes the expansion order on device and takes
        the mirror's candidate decision as a scalar operand (production —
        nothing travels device→host); ``select`` scores both orders on device
        too (differential); ``apply`` applies the candidate verbatim (the
        geo_order oracle)."""
        spr = e_cap - 1
        cap = s * spr
        key = self._span_key(mode, k, k_pad, e_cap, s, mesh)
        ks, use_pallas = key[6], key[7]
        cached = self._programs.get(key)
        if cached is not None:
            return cached
        num_vertices = self.num_vertices

        def span_repair(edges, mask, rows, cand, use_cand):
            blk_e = edges[rows]  # (s, e_cap, 2) — span rows only
            blk_m = mask[rows]
            u = blk_e[:, :spr, 0].reshape(cap)
            v = blk_e[:, :spr, 1].reshape(cap)
            valid = blk_m[:, :spr].reshape(cap) > 0
            n = jnp.sum(valid.astype(jnp.int32))
            if mode == "apply":
                order = cand
            elif mode == "select":
                order = SRK.select_span_order_device(
                    u, v, valid, num_vertices, cand, ks, use_pallas=use_pallas
                )
            else:
                # greedy: the mirror's exact candidate decision arrives as an
                # operand; lax.cond executes ONLY the taken branch, so when
                # the current layout already scored best the program skips
                # the expansion-order compute and is a pure gap re-spread.
                order = jax.lax.cond(
                    use_cand[0] > 0,
                    lambda: cand,
                    lambda: SRK.span_order_device(u, v, valid, num_vertices),
                )
            tgt = SRK.splice_targets_device(n, s, spr, cap)
            j = jnp.arange(cap, dtype=jnp.int32)
            live = j < n
            new_u = jnp.zeros(cap + 1, jnp.int32).at[tgt].set(
                jnp.where(live, u[order], 0)
            )[:cap]
            new_v = jnp.zeros(cap + 1, jnp.int32).at[tgt].set(
                jnp.where(live, v[order], 0)
            )[:cap]
            new_m = jnp.zeros(cap + 1, jnp.float32).at[tgt].set(
                live.astype(jnp.float32)
            )[:cap]
            blk = jnp.stack([new_u.reshape(s, spr), new_v.reshape(s, spr)], axis=-1)
            blk = jnp.concatenate([blk, jnp.zeros((s, 1, 2), jnp.int32)], axis=1)
            mblk = jnp.concatenate(
                [new_m.reshape(s, spr), jnp.zeros((s, 1), jnp.float32)], axis=1
            )
            return edges.at[rows].set(blk), mask.at[rows].set(mblk)

        s_edges, s_mask, _ = SH.engine_shardings(mesh)
        jit_kwargs = {"out_shardings": (s_edges, s_mask)}
        if self.donate:
            program = donate_jit(span_repair, donate_argnums=(0, 1), **jit_kwargs)
        else:
            program = jax.jit(span_repair, **jit_kwargs)
        return self._programs.put(key, program)

    def rf_vs_oracle(self, k: Optional[int] = None) -> tuple[float, float]:
        """(incremental RF, full geo_order re-run RF) at k (default: current
        partition count) — the acceptance margin check."""
        return self.orderer.rf_vs_oracle(self.k if k is None else int(k))
