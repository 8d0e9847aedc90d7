"""Near-zero-overhead span tracing for the streaming runtime (DESIGN.md §13).

A ``Tracer`` records nested host spans — ``with tracer.span("ingest.scatter")``
— into a bounded per-process ring buffer using the monotonic
``time.perf_counter`` clock. The design constraints, in order:

* **Disabled = one branch.** ``span()`` on a disabled tracer returns a shared
  no-op context manager without allocating anything; instrumented hot paths
  (per-batch ingest, per-op scatter) pay a single attribute check.
* **Enabled = bounded.** Records are 4-tuples in a ``deque(maxlen=capacity)``
  — a long-lived serving process can trace forever without growing; the
  ``dropped`` property says how many spans the ring evicted.
* **Cross-process alignable.** Each tracer captures a paired
  (``perf_counter``, wall-clock) epoch at construction, so
  ``obs/trace_export.py`` can place every process's spans on one absolute
  microsecond timeline and merge the per-process fragments into a single
  Chrome-trace/Perfetto JSON with one track per process × phase.
* **Device-correlatable.** ``Tracer(annotate=True)`` additionally enters a
  ``jax.profiler`` TraceAnnotation for every span (via
  ``compat.profiler_annotation``), so host spans line up with device
  programs inside a jax profiler capture.
* **A tree with counts.** Every span records its ``id`` and the ``parent``
  id of the span open around it (-1 at a root), so ``self_times`` can take a
  layer's self time; and ``counts``, a small dict that ``span.count(...)`` or
  ``Tracer.count(...)`` fills on the innermost open span, so ratios are taken
  where the work happens. Spans of one tracer nest on one thread.
* **Compiles where they happen.** While an enabled tracer exists, one
  ``jax.monitoring`` listener (registered once per process, on the first
  enabled tracer) credits each XLA backend compile to the innermost open
  span of every enabled tracer, as ``compiles`` and ``compile_s``, and each
  persistent-cache load as ``cache_loads``. Importing this module imports
  no jax.

The phase of a span defaults to the dotted prefix of its name
(``"ingest.scatter"`` → phase ``"ingest"``); phases become the per-process
tracks of the exported trace.

Components take ``tracer=None`` and fall back to the module-level default
(``get_tracer()`` / ``set_tracer()``), which starts DISABLED — an
uninstrumented run records nothing and pays (almost) nothing.
"""
from __future__ import annotations

import collections
import dataclasses
import time
import weakref
from typing import Optional

__all__ = [
    "SpanRecord",
    "Tracer",
    "get_tracer",
    "self_times",
    "set_tracer",
    "span",
]


@dataclasses.dataclass(frozen=True)
class SpanRecord:
    """One completed span, on the tracer's ``perf_counter`` timeline."""

    name: str
    phase: str
    t0: float  # perf_counter at entry
    t1: float  # perf_counter at exit
    id: int  # unique within its tracer
    parent: int  # id of the span open around it; -1 at a root
    counts: Optional[dict] = None  # what span.count / Tracer.count attached

    @property
    def duration_s(self) -> float:
        return self.t1 - self.t0


def self_times(spans) -> dict:
    """``{span id: self time in s}``: each span's duration minus the part
    its child spans cover (children of one span run one after another)."""
    out = {s.id: s.duration_s for s in spans}
    for s in spans:
        if s.parent in out:
            out[s.parent] -= s.duration_s
    return out


class _NullSpan:
    """Shared no-op context manager — what a disabled tracer's ``span()``
    returns. One instance for the whole process; no allocation per call."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def count(self, **kw) -> None:
        pass


_NULL_SPAN = _NullSpan()


def _add(counts: Optional[dict], kw: dict) -> dict:
    if counts is None:
        return dict(kw)
    for k, v in kw.items():
        counts[k] = counts.get(k, 0) + v
    return counts


class _Span:
    __slots__ = ("_tracer", "_name", "_phase", "_t0", "_annot", "id", "parent", "counts")

    def __init__(self, tracer: "Tracer", name: str, phase):
        self._tracer = tracer
        self._name = name
        self._phase = phase
        self._annot = None
        self.counts = None

    def count(self, **kw) -> None:
        """Add ``kw``'s numbers to this span's counts."""
        self.counts = _add(self.counts, kw)

    def __enter__(self):
        tr = self._tracer
        self.id = tr._next_id
        tr._next_id += 1
        self.parent = tr._open[-1].id if tr._open else -1
        tr._open.append(self)
        if tr.annotate:
            from .. import compat

            self._annot = compat.profiler_annotation(self._name)
            self._annot.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._annot is not None:
            self._annot.__exit__(*exc)
        tr = self._tracer
        tr._open.pop()  # spans nest: the innermost open span is this one
        tr._record(self._name, self._phase, self._t0, t1, self.id, self.parent, self.counts)
        return False


# Tracers constructed enabled, for the compile listener; weak, so a dropped
# tracer leaves. The listener itself is process-wide, as jax.monitoring is.
_LIVE: "weakref.WeakSet[Tracer]" = weakref.WeakSet()
_listening = False
_hit_pending = False  # a cache hit was reported; its compile duration follows


def _credit(**kw) -> None:
    for tr in list(_LIVE):
        if tr.enabled and tr._open:
            sp = tr._open[-1]
            sp.counts = _add(sp.counts, kw)


def _on_event(name: str, **kw) -> None:
    global _hit_pending
    if name == "/jax/compilation_cache/cache_hits":
        _hit_pending = True
        _credit(cache_loads=1)


def _on_duration(name: str, secs: float, **kw) -> None:
    # JAX times a persistent-cache load as a backend compile too, right
    # after reporting the hit: that duration is the load, not a compile.
    global _hit_pending
    if name != "/jax/core/compile/backend_compile_duration":
        return
    if _hit_pending:
        _hit_pending = False
    else:
        _credit(compiles=1, compile_s=float(secs))


def _listen_for_compiles() -> None:
    """Register the compile listener, once per process."""
    global _listening
    if _listening:
        return
    import jax.monitoring as jm

    jm.register_event_listener(_on_event)
    jm.register_event_duration_secs_listener(_on_duration)
    _listening = True


class Tracer:
    """Bounded span recorder. See the module docstring for the contract."""

    __slots__ = ("enabled", "annotate", "_ring", "recorded", "pc0", "wall0", "_open",
                 "_next_id", "__weakref__")

    def __init__(self, capacity: int = 65536, *, enabled: bool = True, annotate: bool = False):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.enabled = bool(enabled)
        self.annotate = bool(annotate)
        self._ring: collections.deque = collections.deque(maxlen=int(capacity))
        self.recorded = 0  # total spans ever recorded (ring may have dropped)
        self._open: list = []  # spans entered and not yet exited, outermost first
        self._next_id = 0
        # Paired epoch: perf_counter timestamps map to absolute wall time as
        # wall0 + (t - pc0). Captured back-to-back so the pairing error is the
        # two clock reads themselves, far under trace resolution.
        self.pc0 = time.perf_counter()
        self.wall0 = time.time()
        if self.enabled:
            _LIVE.add(self)
            _listen_for_compiles()

    # ------------------------------------------------------------- recording
    def span(self, name: str, phase: str | None = None):
        """Context manager timing one span. THE hot call: a disabled tracer
        answers with the shared null span after one branch."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, phase)

    def count(self, **kw) -> None:
        """Add ``kw``'s numbers to the innermost open span's counts (dropped
        when no span is open). A disabled tracer returns after one branch."""
        if not self.enabled:
            return
        if self._open:
            self._open[-1].count(**kw)

    def _record(self, name: str, phase, t0: float, t1: float, sid: int, parent: int,
                counts) -> None:
        self.recorded += 1
        self._ring.append((name, phase, t0, t1, sid, parent, counts))

    # -------------------------------------------------------------- readout
    @property
    def capacity(self) -> int:
        return self._ring.maxlen

    @property
    def dropped(self) -> int:
        """Spans evicted by the ring (recorded minus retained)."""
        return self.recorded - len(self._ring)

    def __len__(self) -> int:
        return len(self._ring)

    def spans(self) -> list[SpanRecord]:
        """Retained spans, oldest first, with phases resolved (a span's phase
        defaults to the dotted prefix of its name)."""
        return [
            SpanRecord(name, phase if phase is not None else name.split(".", 1)[0], t0, t1,
                       sid, parent, counts)
            for name, phase, t0, t1, sid, parent, counts in self._ring
        ]

    def clear(self) -> None:
        self._ring.clear()
        self.recorded = 0


# A permanently-disabled default so uninstrumented runs record nothing; its
# tiny capacity is irrelevant (a disabled tracer never touches its ring).
_DEFAULT = Tracer(capacity=1, enabled=False)
_tracer: Tracer = _DEFAULT


def get_tracer() -> Tracer:
    """The process-global tracer components fall back to when constructed
    without an explicit one."""
    return _tracer


def set_tracer(tracer: Tracer | None) -> Tracer:
    """Install (or, with None, reset) the process-global tracer; returns the
    now-active tracer."""
    global _tracer
    _tracer = tracer if tracer is not None else _DEFAULT
    return _tracer


def span(name: str, phase: str | None = None):
    """``get_tracer().span(...)`` — for module-level instrumentation points
    (e.g. launch/multihost.py transfer helpers) that have no component to
    hang a tracer off."""
    return _tracer.span(name, phase)
