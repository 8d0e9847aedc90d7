"""Multi-host ``graph`` mesh: jax.distributed process groups + local test clusters.

The single-process runtime (DESIGN.md §6) already routes every ingest scatter
and rescale migration through NamedShardings over the ``graph`` mesh axis, so
going multi-host "just" changes the mesh: ``make_graph_mesh`` spans
``jax.devices()``, which after ``initialize_distributed`` is the *global*
device list of every process in the group. This module owns everything that
becomes process-aware at that point (DESIGN.md §10):

* **Process bootstrap.** ``initialize_distributed`` / ``initialize_from_env``
  wrap ``jax.distributed.initialize`` through ``repro.compat`` (the CPU
  collectives knob and the initialize surface are the version-sensitive
  parts). Environment variables (``REPRO_MH_*``) carry the cluster spec so a
  worker script needs zero argument plumbing.
* **Global-array construction.** ``put_global`` builds a mesh-committed array
  from host data that every process holds replicas of (graphs are loaded /
  generated deterministically from the seed in each process), handing each
  process exactly its addressable block via
  ``jax.make_array_from_process_local_data``. A 1-process mesh is the
  degenerate case of the same call — never a separate code path.
* **Host readback.** Arrays sharded over a multi-process mesh are not fully
  addressable; ``host_read`` replicates through a jitted identity (one
  all-gather) so oracle checks can still compare bytes, and
  ``local_shard_rows`` fetches only this process's rows — what the
  multi-process acceptance harness writes out for the parent to reassemble.
* **Localhost clusters for tests/benchmarks.** ``spawn_local_cluster`` starts
  N processes on this machine, each with ``devs_per_proc`` forced host
  devices and a free-port coordinator, and returns per-process logs (printed
  on failure so CI flakes are diagnosable).

What crosses the NIC: partition p lives on graph-axis position p % g
(launch/sharding.py), and positions map to processes via the mesh's device
order — so exactly the ScalePlan move ranges whose source and destination
positions belong to different processes are network traffic. ``RescaleStats``
reports them as ``cross_process_edges/bytes``, computed from the plan overlay
and ``sharding.device_process_map`` (no device readback needed).
"""
from __future__ import annotations

import dataclasses
import functools
import os
import socket
import subprocess
import sys
import threading
import time

import numpy as np

from .. import compat
from ..obs import trace as OT
from . import sharding as SH

__all__ = [
    "ClusterSpec",
    "LeaseBoard",
    "LocalCluster",
    "LocalClusterResult",
    "ProcResult",
    "initialize_distributed",
    "initialize_from_env",
    "force_host_device_flags",
    "free_port",
    "put_global",
    "put_global_local",
    "addressable_row_block",
    "psum_host",
    "host_read",
    "local_shard_rows",
    "launch_local_cluster",
    "spawn_local_cluster",
]

# Environment contract between spawn_local_cluster and worker processes.
ENV_COORD = "REPRO_MH_COORDINATOR"
ENV_NPROCS = "REPRO_MH_NUM_PROCESSES"
ENV_PID = "REPRO_MH_PROCESS_ID"
ENV_DEVS = "REPRO_MH_DEVS_PER_PROC"

_FORCE_FLAG = "--xla_force_host_platform_device_count"


@dataclasses.dataclass(frozen=True)
class ClusterSpec:
    coordinator: str  # "host:port" of process 0's coordinator service
    num_processes: int
    process_id: int
    devs_per_proc: int = 1


def force_host_device_flags(n: int, base: str = "") -> str:
    """XLA_FLAGS value forcing ``n`` host devices, built explicitly: any
    existing force-count flag in ``base`` is removed (never patched with
    string substitution — see tests/test_multidevice.py history) and every
    other flag is preserved."""
    kept = [f for f in str(base).split() if not f.startswith(_FORCE_FLAG)]
    return " ".join(kept + [f"{_FORCE_FLAG}={int(n)}"])


def free_port(host: str = "127.0.0.1") -> int:
    """An OS-assigned free TCP port (the usual bind(0) race caveat applies —
    fine for spawning one local coordinator right after)."""
    s = socket.socket()
    try:
        s.bind((host, 0))
        return int(s.getsockname()[1])
    finally:
        s.close()


def initialize_distributed(coordinator: str, num_processes: int, process_id: int) -> None:
    """Join this process to the ``jax.distributed`` group. After this,
    ``jax.devices()`` is the global device list (process-major order) and
    ``make_graph_mesh`` spans it — all version-sensitive surface lives in
    ``repro.compat``. Call before the first jax computation."""
    compat.distributed_initialize(coordinator, num_processes, process_id)


def initialize_from_env(environ=None) -> ClusterSpec | None:
    """Initialize from the ``REPRO_MH_*`` variables ``spawn_local_cluster``
    sets; returns the spec, or None (no-op) outside a spawned cluster — so a
    worker script runs unchanged as a plain single process."""
    env = os.environ if environ is None else environ
    if ENV_COORD not in env:
        return None
    spec = ClusterSpec(
        coordinator=env[ENV_COORD],
        num_processes=int(env[ENV_NPROCS]),
        process_id=int(env[ENV_PID]),
        devs_per_proc=int(env.get(ENV_DEVS, 1)),
    )
    initialize_distributed(spec.coordinator, spec.num_processes, spec.process_id)
    return spec


# ---------------------------------------------------------------- liveness
class LeaseBoard:
    """File-based liveness leases for a process group (DESIGN.md §15).

    Worker process ``i`` stamps ``lease_p{i}.json`` with its batch step and
    the lease clock after every unit of progress; anyone holding the shared
    directory (the drill parent, a sibling process) classifies the group
    without any collective — which is the point: a process that died inside
    a gloo collective strands its peers, so detection must not itself ride
    on the collective plane. Stamps are written via tmp+rename, so a reader
    never sees a torn lease; a SIGKILL mid-stamp leaves the previous stamp.

    The clock follows the runtime's injected-clock convention
    (``ElasticController(clock=...)``): default ``time.monotonic``, which is
    CLOCK_MONOTONIC on Linux — one system-wide timeline every local process
    shares, so cross-process lease ages are directly comparable. Tests
    inject a fake clock and drive expiry deterministically.

    A process that never stamped is aged from the board's construction time
    (a worker that died before its first stamp must still expire).
    """

    def __init__(self, directory, *, lease_s: float = 2.0, clock=time.monotonic):
        self.dir = os.fspath(directory)
        os.makedirs(self.dir, exist_ok=True)
        self.lease_s = float(lease_s)
        self.clock = clock
        self._t0 = clock()

    def _path(self, process_id: int) -> str:
        return os.path.join(self.dir, f"lease_p{int(process_id)}.json")

    def stamp(self, process_id: int, step: int) -> None:
        """Renew process ``process_id``'s lease at progress ``step``."""
        import json

        path = self._path(process_id)
        tmp = f"{path}.tmp{int(process_id)}"
        with open(tmp, "w") as f:
            f.write(json.dumps({"step": int(step), "t": float(self.clock())}))
        os.replace(tmp, path)  # atomic: readers see whole stamps or nothing

    def read(self, process_id: int) -> dict | None:
        """The last stamp of ``process_id`` — {"step", "t"} — or None."""
        import json

        try:
            with open(self._path(process_id)) as f:
                return json.loads(f.read())
        except (OSError, ValueError):
            return None

    def age(self, process_id: int, *, now: float | None = None) -> float:
        """Seconds since the last stamp (since board construction when the
        process never stamped)."""
        now = self.clock() if now is None else now
        stamp = self.read(process_id)
        return now - (self._t0 if stamp is None else stamp["t"])

    def step(self, process_id: int) -> int:
        """Last stamped progress step (-1 before the first stamp)."""
        stamp = self.read(process_id)
        return -1 if stamp is None else int(stamp["step"])

    def dead(self, num_processes: int, *, now: float | None = None) -> list[int]:
        """Process ids whose lease age exceeds ``lease_s`` — the failure
        detector's verdict at ``now``. A frozen stamp (the victim's last
        write before SIGKILL) ages past the lease like silence does."""
        now = self.clock() if now is None else now
        return [
            pid for pid in range(int(num_processes))
            if self.age(pid, now=now) > self.lease_s
        ]

    def survivors(self, num_processes: int, *, now: float | None = None) -> list[int]:
        gone = set(self.dead(num_processes, now=now))
        return [pid for pid in range(int(num_processes)) if pid not in gone]

    def surviving_devices(
        self, num_processes: int, devs_per_proc: int, *, now: float | None = None
    ) -> list[int]:
        """Global device indices still backed by a live process. Global
        devices are process-major after ``initialize_distributed`` (process
        i owns [i·d, (i+1)·d)), so the surviving list is exactly what a
        recovery mesh re-plans k over."""
        d = int(devs_per_proc)
        return [
            dev
            for pid in self.survivors(num_processes, now=now)
            for dev in range(pid * d, (pid + 1) * d)
        ]

    def wait_for_step(
        self, process_id: int, step: int, *, timeout: float = 60.0, poll_s: float = 0.01
    ) -> int:
        """Block (real time) until ``process_id``'s lease reaches ``step``.
        The drill parent uses this to align its SIGKILL with a chosen batch
        index. Returns the observed step; raises TimeoutError."""
        deadline = time.monotonic() + timeout
        while True:
            s = self.step(process_id)
            if s >= int(step):
                return s
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"process {process_id} never reached step {step} "
                    f"(last stamped {s}) within {timeout}s"
                )
            time.sleep(poll_s)


# ------------------------------------------------------------- global arrays
def put_global(host_arr, sharding):
    """Commit a host array (replicated on every process) to ``sharding``.

    Each process contributes exactly the rows its devices own
    (``jax.make_array_from_process_local_data``); with one process the local
    block is the whole array — the degenerate case of the same path. Falls
    back to ``device_put`` when the sharding has no multi-process structure
    helper available (plain single-process jax)."""
    import jax

    with OT.span("transfer.put_global"):
        host_arr = np.asarray(host_arr)
        if compat.process_count() == 1:
            return jax.device_put(host_arr, sharding)
        lo, hi = addressable_row_block(host_arr.shape, sharding)
        return compat.array_from_process_local_data(
            sharding, host_arr[lo:hi], host_arr.shape
        )


def put_global_local(local_block, global_shape, sharding):
    """Commit to ``sharding`` from ONLY this process's row block.

    The out-of-core counterpart of ``put_global``: the caller materializes
    just the rows this process's devices own (``addressable_row_block``
    says which) instead of replicating the full host array — the whole
    point of shard-streamed packing is that no process ever stages a
    global-shape buffer. Single-process shardings take the direct
    device_put path (the local block IS the array)."""
    import jax

    with OT.span("transfer.put_global"):
        local_block = np.asarray(local_block)
        lo, hi = addressable_row_block(global_shape, sharding)
        if local_block.shape[0] != hi - lo or local_block.shape[1:] != tuple(global_shape[1:]):
            raise ValueError(
                f"local block shape {local_block.shape} does not cover rows "
                f"[{lo}, {hi}) of global shape {tuple(global_shape)}"
            )
        if compat.process_count() == 1:
            return jax.device_put(local_block, sharding)
        return compat.array_from_process_local_data(sharding, local_block, tuple(global_shape))


def psum_host(local, mesh) -> np.ndarray:
    """Sum a host array over all processes of ``mesh`` (collective).

    How the out-of-core pipeline merges V-sized accumulators — the chunk
    load histogram, degree vectors, edge counts — that each process builds
    from its own shards: the local value is staged as this process's row of
    a (num_processes, …) device array sharded over ``graph`` and summed
    after one all-gather. Single-process meshes return the input unchanged."""
    with OT.span("transfer.psum_host"):
        local = np.asarray(local)
        n_procs = compat.process_count()
        if n_procs == 1:
            return local.copy()
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        g = SH.graph_axis_size(mesh)
        devs_per_proc = g // n_procs
        # One row per DEVICE (the graph axis shards by device): this process
        # contributes its value on its first device's row, zeros elsewhere.
        block = np.zeros((devs_per_proc,) + local.shape, dtype=local.dtype)
        block[0] = local
        sharding = NamedSharding(mesh, P("graph"))
        arr = compat.array_from_process_local_data(sharding, block, (g,) + local.shape)
        return host_read(arr).sum(axis=0)


def addressable_row_block(global_shape, sharding) -> tuple[int, int]:
    """[lo, hi) leading-axis rows this process's devices own under
    ``sharding``. The graph layouts shard only the leading axis (or nothing),
    so the addressable region is one contiguous row block; asserted here
    rather than assumed — O(devices) interval merging, never O(rows)."""
    spans = []
    for _, idx in sharding.addressable_devices_indices_map(tuple(global_shape)).items():
        sl = idx[0] if idx else slice(None)
        lo = 0 if sl.start is None else int(sl.start)
        hi = global_shape[0] if sl.stop is None else int(sl.stop)
        spans.append((lo, hi))
    spans.sort()
    lo, hi = spans[0]
    for s_lo, s_hi in spans[1:]:
        if s_lo > hi:  # gap between this device's rows and the block so far
            raise ValueError("addressable rows are not contiguous; not a graph-axis layout")
        hi = max(hi, s_hi)
    return lo, hi


@functools.lru_cache(maxsize=8)
def _replicate_fn(mesh):
    """One jitted identity-to-replicated program per mesh (jit caches per
    input shape internally) — host_read must not retrace on every readback."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.jit(lambda x: x, out_shardings=NamedSharding(mesh, P()))


def host_read(arr) -> np.ndarray:
    """Fetch a (possibly multi-process) committed array to host memory.

    Fully-addressable arrays read directly. Arrays spanning other processes
    are first replicated by a jitted identity with a replicated out_sharding —
    one all-gather over the interconnect; every process gets the full value
    (collective: all processes in the group must call this together)."""
    import jax

    if not isinstance(arr, jax.Array) or arr.is_fully_addressable:
        return np.asarray(arr)
    with OT.span("transfer.host_read"):
        out = _replicate_fn(arr.sharding.mesh)(arr)
        jax.block_until_ready(out)
        return np.asarray(out)


def local_shard_rows(arr) -> list[tuple[int, int, np.ndarray]]:
    """This process's addressable shards of a leading-axis-sharded array, as
    (row_lo, row_hi, data) blocks — what the acceptance harness persists so
    the parent can reassemble the global buffer without any collective."""
    blocks = []
    for s in arr.addressable_shards:
        sl = s.index[0] if s.index else slice(None)
        lo = 0 if sl.start is None else int(sl.start)
        hi = arr.shape[0] if sl.stop is None else int(sl.stop)
        blocks.append((lo, hi, np.asarray(s.data)))
    # Replicated arrays: every device holds full rows; dedup identical blocks.
    uniq: dict[tuple[int, int], np.ndarray] = {}
    for lo, hi, data in blocks:
        if (lo, hi) in uniq:
            if not np.array_equal(uniq[(lo, hi)], data):
                raise AssertionError(f"divergent replicas for rows [{lo}, {hi})")
        else:
            uniq[(lo, hi)] = data
    return sorted((lo, hi, d) for (lo, hi), d in uniq.items())


# --------------------------------------------------------- localhost clusters
@dataclasses.dataclass(frozen=True)
class ProcResult:
    process_id: int
    returncode: int
    stdout: str
    stderr: str


@dataclasses.dataclass(frozen=True)
class LocalClusterResult:
    spec_coordinator: str
    procs: tuple[ProcResult, ...]

    @property
    def ok(self) -> bool:
        return all(p.returncode == 0 for p in self.procs)

    def format_logs(self, tail: int = 4000) -> str:
        """Per-process logs, for test/CI failure diagnosis."""
        out = []
        for p in self.procs:
            out.append(f"--- process {p.process_id} (rc={p.returncode}) ---")
            if p.stdout:
                out.append(f"[stdout]\n{p.stdout[-tail:]}")
            if p.stderr:
                out.append(f"[stderr]\n{p.stderr[-tail:]}")
        return "\n".join(out)


class LocalCluster:
    """A RUNNING localhost cluster: the handle ``launch_local_cluster``
    returns. ``spawn_local_cluster`` is the blocking wrapper (launch +
    ``wait``); the fault drill holds the handle instead, so it can SIGKILL a
    chosen process mid-stream (``kill``) and still collect every process's
    partial log afterwards. Whatever happens — clean exits, a timeout, an
    injected kill, an exception in the caller — ``wait`` reaps every child
    (kill + OS ``wait()``): no zombies holding the coordinator port."""

    def __init__(self, coord: str, procs: list, captured: dict, threads: list):
        self.coordinator = coord
        self._procs = procs
        self._captured = captured
        self._threads = threads
        self._notes: dict[int, list] = {pid: [] for pid in range(len(procs))}

    @property
    def n_procs(self) -> int:
        return len(self._procs)

    def poll(self, pid: int):
        """Exit code of process ``pid``, or None while it runs."""
        return self._procs[pid].poll()

    def kill(self, pid: int, *, reason: str = "fault injection") -> None:
        """SIGKILL process ``pid`` and reap it immediately. The hard-kill is
        deliberate — a preempted instance gets no chance to flush, close, or
        say goodbye, and the drill must model exactly that. The victim's
        partial log stays captured (drained line-wise with the ``[p{pid}]``
        prefix as it was emitted) and gets an attributable kill note."""
        p = self._procs[pid]
        if p.poll() is None:
            p.kill()
            p.wait()
        self._notes[pid].append(
            f"[p{pid}] [local_cluster] SIGKILL injected mid-run ({reason})\n"
        )

    def wait(self, timeout: float = 600.0) -> LocalClusterResult:
        """Block until every process exits (killing the whole group at the
        deadline), reap everything, and return all logs."""
        deadline = time.monotonic() + timeout
        timed_out = []
        try:
            for pid, p in enumerate(self._procs):
                try:
                    p.wait(timeout=max(0.0, deadline - time.monotonic()))
                except subprocess.TimeoutExpired:
                    p.kill()
                    timed_out.append(pid)
        finally:
            for q in self._procs:
                if q.poll() is None:
                    q.kill()
            for q in self._procs:
                try:
                    q.wait(timeout=30.0)  # REAP: a killed child must not linger
                except subprocess.TimeoutExpired:  # pragma: no cover — SIGKILL
                    pass  # cannot be refused; defensive only
        for t in self._threads:  # readers end at EOF once every child exited
            t.join(30.0)
        results = []
        for pid, p in enumerate(self._procs):
            err = "".join(self._captured[(pid, 1)]) + "".join(self._notes[pid])
            if pid in timed_out:
                err += f"\n[p{pid}] [spawn_local_cluster] killed after {timeout}s timeout"
            rc = p.returncode if p.returncode is not None else -1
            results.append(ProcResult(pid, rc, "".join(self._captured[(pid, 0)]), err))
        return LocalClusterResult(self.coordinator, tuple(results))


def launch_local_cluster(
    n_procs: int,
    devs_per_proc: int,
    argv: list[str],
    *,
    env_extra: dict | None = None,
    cwd: str | None = None,
) -> LocalCluster:
    """Start ``python <argv>`` as an ``n_procs``-process localhost cluster
    and return the RUNNING handle (see ``LocalCluster``); the caller must
    ``wait()`` it. ``spawn_local_cluster`` wraps this for the common
    launch-and-block case."""
    if n_procs < 1:
        raise ValueError("n_procs must be >= 1")
    coord = f"127.0.0.1:{free_port()}"
    procs = []
    for pid in range(n_procs):
        env = dict(os.environ)
        # A CPU cluster: on a TPU host the parent may hold the chip, and a
        # child that inherited its platform would fail to claim it.
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = force_host_device_flags(devs_per_proc, env.get("XLA_FLAGS", ""))
        env[ENV_COORD] = coord
        env[ENV_NPROCS] = str(n_procs)
        env[ENV_PID] = str(pid)
        env[ENV_DEVS] = str(devs_per_proc)
        if env_extra:
            env.update({k: str(v) for k, v in env_extra.items()})
        procs.append(
            subprocess.Popen(
                [sys.executable] + list(argv),
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
                env=env,
                cwd=cwd,
            )
        )
    # Drain every child's pipes CONCURRENTLY and LINE-WISE: the processes
    # form one collective group, so a single child blocked writing to a full
    # pipe (verbose backend logging, a long traceback) would stall every
    # other child at its next collective. Reading line-by-line (one thread
    # per pipe) lets each line be tagged with its process index AT EMIT TIME
    # — so interleaved multi-process logs stay attributable even when a test
    # prints them mid-run, instead of only in the per-process failure dump.
    # This also means a SIGKILLed process's PARTIAL log is already captured
    # the moment it dies — the drill's post-mortem needs no cooperation.
    captured: dict[tuple, list] = {(pid, s): [] for pid in range(n_procs) for s in (0, 1)}

    def drain(pid: int, stream, which: int) -> None:
        prefix = f"[p{pid}] "
        sink = captured[(pid, which)]
        for line in iter(stream.readline, ""):
            sink.append(prefix + line)
        stream.close()

    threads = [
        threading.Thread(target=drain, args=(pid, s, which), daemon=True)
        for pid, p in enumerate(procs)
        for which, s in ((0, p.stdout), (1, p.stderr))
    ]
    for t in threads:
        t.start()
    return LocalCluster(coord, procs, captured, threads)


def spawn_local_cluster(
    n_procs: int,
    devs_per_proc: int,
    argv: list[str],
    *,
    timeout: float = 600.0,
    env_extra: dict | None = None,
    cwd: str | None = None,
) -> LocalClusterResult:
    """Run ``python <argv>`` as an ``n_procs``-process localhost cluster.

    Each process gets ``devs_per_proc`` forced host devices (XLA_FLAGS built
    explicitly, preserving unrelated flags) and the ``REPRO_MH_*`` variables
    pointing at a free-port coordinator on process 0 — the worker calls
    ``initialize_from_env()`` and sees an ``n_procs · devs_per_proc``-device
    global platform. Blocks until every process exits (or kills the whole
    group on timeout), REAPS every child, and returns all logs; the caller
    decides what a failure means (tests print ``format_logs()``). Every
    captured log line is prefixed ``[p{pid}] `` at emit time, so interleaved
    cluster output stays attributable; marker scanners must search within
    lines, not at line starts (benchmarks.common.parse_peak_rss does).
    Fault drills that must kill a member mid-run hold the
    ``launch_local_cluster`` handle instead."""
    return launch_local_cluster(
        n_procs, devs_per_proc, argv, env_extra=env_extra, cwd=cwd
    ).wait(timeout)
