"""Reduction of a JAX profiler trace (``.xplane.pb``) to device busy time.

The trace is read with ``jax.profiler.ProfileData`` alone. Device planes are
named ``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per
operation run on the device, and their ``XLA Modules`` line one event per
program. Host annotations (``jax.profiler.TraceAnnotation``, which the
benchmark writes around its window and its operations, and the program's obs
spans when its tracer annotates) are events of the ``/host:CPU`` plane.

Device and host events share one nanosecond timeline. On a TPU v5e the device
events were seen to sit about 1.4 ms early against the host annotations that
dispatched them; against intervals of a second or more that is below 0.2%,
and the reduction does not correct it.
"""
from __future__ import annotations

import dataclasses
import glob
import os

import numpy as np


def union(intervals) -> np.ndarray:
    """Merge ``[(start, end), ...]`` into sorted disjoint intervals, (n, 2)."""
    a = np.asarray(sorted(intervals), dtype=np.float64).reshape(-1, 2)
    if a.shape[0] == 0:
        return a
    out = [list(a[0])]
    for s, e in a[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, dtype=np.float64)


def covered(merged: np.ndarray, lo: float, hi: float) -> float:
    """Length of ``[lo, hi)`` that the disjoint intervals ``merged`` cover."""
    if merged.shape[0] == 0 or hi <= lo:
        return 0.0
    s = np.clip(merged[:, 0], lo, hi)
    e = np.clip(merged[:, 1], lo, hi)
    return float(np.sum(e - s))


@dataclasses.dataclass
class Trace:
    """What the reduction keeps of one trace: per device, its operations as
    (start_ns, end_ns, program, op) and their merged busy intervals; and the
    host annotations as (name, start_ns, end_ns)."""

    ops: dict  # device name -> list of (start, end, program, op)
    busy: dict  # device name -> merged (n, 2) busy intervals
    host: list  # (name, start, end)

    def annotations(self, name: str) -> list:
        return [(s, e) for n, s, e in self.host if n == name]

    def busy_s(self, intervals) -> float:
        """Device-busy seconds inside ``intervals`` (ns), averaged over the
        devices of the trace."""
        if not self.busy:
            return 0.0
        per = [sum(covered(m, s, e) for s, e in intervals) for m in self.busy.values()]
        return float(np.mean(per)) * 1e-9

    def idle_share(self, intervals):
        """1 - busy / length over ``intervals``; None for no intervals."""
        total = sum(e - s for s, e in intervals) * 1e-9
        if total <= 0:
            return None
        return 1.0 - self.busy_s(intervals) / total

    def top_ops(self, lo: float, hi: float, n: int = 10) -> list:
        """The ``n`` operations with most device time inside ``[lo, hi)``,
        as ``[program:op, seconds]``, summed over devices."""
        acc: dict = {}
        for ops in self.ops.values():
            for s, e, prog, op in ops:
                t = min(e, hi) - max(s, lo)
                if t > 0:
                    key = f"{prog}:{op}"
                    acc[key] = acc.get(key, 0.0) + t * 1e-9
        return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, lo: float, hi: float, n: int = 10) -> list:
        """The ``n`` longest gaps with no operation on the first device inside
        ``[lo, hi)``, named by the innermost host annotation that covers the
        gap's midpoint, as ``[name, seconds]``."""
        if not self.busy:
            return []
        merged = next(iter(self.busy.values()))
        edges = [lo] + [x for s, e in merged if e > lo and s < hi for x in (s, e)] + [hi]
        gaps = [(max(a, lo), min(b, hi)) for a, b in zip(edges[::2], edges[1::2])]
        gaps = sorted((g for g in gaps if g[1] > g[0]), key=lambda g: g[0] - g[1])[:n]
        out = []
        for a, b in gaps:
            mid = 0.5 * (a + b)
            inner = [(e - s, nm) for nm, s, e in self.host if s <= mid < e]
            out.append([min(inner)[1] if inner else "host", float(b - a) * 1e-9])
        return out


def _short(name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``; a program's
    ``jit_apply(123)`` -> ``jit_apply``."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return name.split("(", 1)[0]


def load(path: str, host_prefixes=("bench.", "ingest.", "rung.", "rescale.", "rebuild.",
                                   "query.")) -> Trace:
    """Read one ``.xplane.pb``; host annotations are kept when their name
    starts with one of ``host_prefixes``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, busy, host = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU"):
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns, _short(e.name))
                          for e in lines.get("XLA Modules", []))
            starts = [m[0] for m in mods]
            dev = []
            for e in lines.get("XLA Ops", []):
                s, t = e.start_ns, e.start_ns + e.duration_ns
                i = int(np.searchsorted(starts, s, side="right")) - 1
                prog = mods[i][2] if i >= 0 and mods[i][1] >= s else "?"
                dev.append((s, t, prog, _short(e.name)))
            ops[plane.name] = dev
            busy[plane.name] = union([(s, t) for s, t, _, _ in dev])
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(host_prefixes):
                        host.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    return Trace(ops=ops, busy=busy, host=host)


def find(log_dir: str) -> str:
    """The one ``.xplane.pb`` under a profiler log directory."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, found {len(paths)}")
    return paths[0]
