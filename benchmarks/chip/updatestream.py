"""The benchmark's own update generator: batches of edge inserts and deletes.

A copy of the semantics of the program's ``stream/updates.SyntheticStream``,
kept here so that the traffic cannot change with the program. Batch ``b`` is
a pure function of ``(seed, b)`` and the live edge set before it:

* a batch is ``batch`` updates, or ``batch * burst_factor`` at
  ``burst_delete_frac`` where it is a burst: the last of every
  ``burst_every`` batches (``burst_every`` 0: no bursts);
* deletes come first, ``int(size * delete_frac)`` of them, each a hash-picked
  live edge, removed by swap-remove (so the next pick sees the new set);
* inserts fill the rest: with probability ``triadic_frac`` an endpoint of a
  hash-picked live edge joined to a uniform vertex (triadic closure, which
  gives the stream community structure), otherwise a uniform pair; self-loops
  and edges already live are skipped.

The hashes of a batch are drawn in one vectorised call; only the pick loop
runs per update. Edges are canonical ``(lo, hi)`` int64 pairs.
"""
from __future__ import annotations

import numpy as np

from graphgen import mix_hash

_SALT_INSERT, _SALT_TRIADIC, _SALT_UNIFORM, _SALT_DELETE = 1, 2, 3, 7


class UpdateStream:
    """Deterministic insert/delete batches over an evolving live edge set."""

    def __init__(self, base_edges: np.ndarray, num_vertices: int, *, batch: int,
                 delete_frac: float, triadic_frac: float, seed: int, burst_every: int = 0,
                 burst_factor: int = 1, burst_delete_frac: float = None):
        burst_delete_frac = delete_frac if burst_delete_frac is None else burst_delete_frac
        if (batch < 1 or burst_every < 0 or burst_factor < 1 or not 0.0 <= triadic_frac <= 1.0
                or not 0.0 <= delete_frac < 1.0 or not 0.0 <= burst_delete_frac < 1.0):
            raise ValueError("batch >= 1, burst_every >= 0, burst_factor >= 1, "
                             "delete fractions in [0, 1), triadic_frac in [0, 1]")
        self.v = int(num_vertices)
        self.batch_size = int(batch)
        self.delete_frac = float(delete_frac)
        self.burst_every, self.burst_factor = int(burst_every), int(burst_factor)
        self.burst_delete_frac = float(burst_delete_frac)
        self.triadic = int(triadic_frac * 1000)
        self.seed = int(seed)
        base = np.asarray(base_edges, dtype=np.int64)
        self._keys = base[:, 0] * self.v + base[:, 1]  # live edges, swap-remove order
        self._n = int(self._keys.size)
        self._live = set(self._keys.tolist())
        self._next = 0

    @property
    def num_edges(self) -> int:
        return self._n

    def _grow(self) -> None:
        if self._n == self._keys.size:
            self._keys = np.concatenate([self._keys, np.zeros(max(1024, self._n // 8), np.int64)])

    def shape(self, b: int) -> tuple[int, int]:
        """(deletes, inserts) that batch ``b`` asks for."""
        burst = self.burst_every > 0 and b % self.burst_every == self.burst_every - 1
        size = self.batch_size * (self.burst_factor if burst else 1)
        n_del = int(size * (self.burst_delete_frac if burst else self.delete_frac))
        return n_del, size - n_del

    def next_batch(self) -> tuple[np.ndarray, np.ndarray]:
        """(inserts, deletes): (n, 2) int64 canonical edges of the next batch."""
        b = self._next
        self._next += 1
        n_del, n_ins = self.shape(b)
        v, keys, live = self.v, self._keys, self._live
        hd = mix_hash(self.seed, b, np.arange(n_del), _SALT_DELETE).tolist()
        deletes = []
        for h in hd:
            if self._n == 0:
                break
            j = h % self._n
            key = int(keys[j])
            self._n -= 1
            keys[j] = keys[self._n]
            live.discard(key)
            deletes.append(key)
        scan = 16 * (n_del + n_ins)
        pos = np.arange(scan)
        h1 = mix_hash(self.seed, b, pos, _SALT_INSERT)
        h2 = (mix_hash(self.seed, b, pos, _SALT_TRIADIC) % np.uint64(v)).tolist()
        h3 = (mix_hash(self.seed, b, pos, _SALT_UNIFORM) % np.uint64(v)).tolist()
        tri = (((h1 >> np.uint64(8)) % np.uint64(1000)) < np.uint64(self.triadic)).tolist()
        pick = (h1 >> np.uint64(16)).tolist()
        side = ((h1 >> np.uint64(4)) & np.uint64(1)).tolist()
        uni = (h1 % np.uint64(v)).tolist()
        inserts = []
        for i in range(scan):
            if len(inserts) == n_ins:
                break
            if tri[i] and self._n:
                e = int(keys[pick[i] % self._n])
                a, c = divmod(e, v)
                x, y = (a if side[i] else c), h2[i]
            else:
                x, y = uni[i], h3[i]
            if x == y:
                continue
            key = min(x, y) * v + max(x, y)
            if key in live:
                continue
            live.add(key)
            self._grow()
            keys = self._keys
            keys[self._n] = key
            self._n += 1
            inserts.append(key)
        ins = np.asarray(inserts, dtype=np.int64)
        dels = np.asarray(deletes, dtype=np.int64)
        return (np.stack([ins // v, ins % v], axis=1).reshape(-1, 2),
                np.stack([dels // v, dels % v], axis=1).reshape(-1, 2))
