"""Bounded LRU of jitted device programs, shared by every runtime component
that caches programs (elastic/rescale_exec.py's rescaler, stream/ingest.py's
streaming engine). It imports nothing from this package, so any layer may
use it without an import pointing up."""
from __future__ import annotations

import collections

__all__ = ["ProgramCache"]


class ProgramCache:
    """Bounded LRU of jitted device programs keyed by their static shape/mesh
    signature. Keys are KIND-prefixed tuples (("migrate", ...), ("counts",
    ...), ("scatter", ...), ("compact", ...), ("span_repair", ...)) so every
    program family of one runtime component shares a single cache — a
    long-lived controller oscillating between configurations pays tracing
    once per signature without any cache growing without limit, and
    ``program_cache_size`` bounds ALL of a component's cached programs at
    once (ElasticRescaler: migrate + counts; StreamingEngine: scatter +
    compact + span_repair + full_reorder + splice).

    Per-kind hit/miss/eviction counters (``counters`` / ``counters_snapshot``)
    make the cache's behavior auditable from event logs: a ``get`` returning a
    program is a hit, a ``get`` returning None a miss (the caller compiles and
    ``put``s), and ``put`` evicting an LRU victim an eviction — so the stream
    bench can PROVE an escalation never paid a compile (its kind's miss count
    is flat across the monitored stream) instead of asserting it by eye."""

    def __init__(self, size: int):
        if size < 1:
            raise ValueError("program_cache_size must be >= 1")
        self.size = int(size)
        self._programs: collections.OrderedDict = collections.OrderedDict()
        # kind (key[0] for tuple keys, "?" otherwise) → {hits, misses, evictions}
        self.counters: dict = {}
        self._counters_shared = False  # a snapshot aliases self.counters

    def __len__(self) -> int:
        return len(self._programs)

    def __contains__(self, key) -> bool:
        return key in self._programs

    def __iter__(self):
        return iter(self._programs)  # keys, least- to most-recently used

    @staticmethod
    def _kind(key) -> str:
        return str(key[0]) if isinstance(key, tuple) and key else "?"

    def _count(self, key, event: str) -> None:
        if self._counters_shared:
            # Copy-on-WRITE: a snapshot handed out earlier aliases the live
            # dicts — clone before mutating so every outstanding snapshot
            # stays frozen at its emit-time values.
            self.counters = {kind: dict(c) for kind, c in self.counters.items()}
            self._counters_shared = False
        c = self.counters.setdefault(
            self._kind(key), {"hits": 0, "misses": 0, "evictions": 0}
        )
        c[event] += 1

    def counters_snapshot(self) -> dict:
        """Per-kind counters, isolated from later cache activity — safe to
        attach to events. Lazily: the LIVE mapping is returned and the cache
        clones it before its next mutation (copy-on-write), so the per-event
        hot path (every IngestEvent snapshots) costs a flag set, not a deep
        copy per batch. Callers must treat the result as immutable."""
        self._counters_shared = True
        return self.counters

    def get(self, key):
        cached = self._programs.get(key)
        if cached is not None:
            self._programs.move_to_end(key)
            self._count(key, "hits")
        else:
            self._count(key, "misses")
        return cached

    def touch(self, key) -> bool:
        """Refresh recency if present (counted as a hit). Unlike ``get``, an
        absent key counts NOTHING — warm-up helpers probe with this before
        delegating to the builder (whose own ``get`` miss then counts the
        compile exactly once, keeping misses == compiles for the bench)."""
        cached = self._programs.get(key)
        if cached is not None:
            self._programs.move_to_end(key)
            self._count(key, "hits")
            return True
        return False

    def put(self, key, value):
        self._programs[key] = value
        while len(self._programs) > self.size:
            victim, _ = self._programs.popitem(last=False)
            self._count(victim, "evictions")
        return value
