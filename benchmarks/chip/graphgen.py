"""The benchmark's own graph generator: Graph500 / GAP Kronecker (RMAT) draws.

A copy of the candidate arithmetic of the program's ``data/shards.py``
(``RmatShardPlan``), kept here so that a change to the program cannot change
the graph a cell runs on. Every candidate edge ``i`` is a pure function of
``(seed, i)``: per recursion level one ``mix_hash`` draw picks a quadrant of
the initiator ``(a, b, c, d)``; vertex ids are then relabelled by one
invertible mix, the same for both endpoints, so that ids carry no quadrant
locality, as Graph500's generator permutes every vertex label once. (The
program's plan scrambles sources and destinations with two different mixes;
that splits each Kronecker hub into two vertices, and is not copied.)

As GAP builds its graphs (Beamer, Asanovic, Patterson, arXiv:1508.03619), the
result is undirected: self-loops are dropped and each unordered pair is kept
once. ``kron`` is the Graph500 initiator 0.57/0.19/0.19; ``urand`` is the
uniform initiator 0.25/0.25/0.25, under which every quadrant draw is uniform
and so is every edge.
"""
from __future__ import annotations

import numpy as np

_U64 = np.uint64(0xFFFFFFFFFFFFFFFF)
_MIX_GOLD = np.uint64(0x9E3779B97F4A7C15)
_MIX_FNV = np.uint64(0x100000001B3)
_MIX_POS = np.uint64(1_000_003)
_SALT_QUAD = 101  # + recursion level: the quadrant draw of that level


def splitmix64(x) -> np.ndarray:
    """The splitmix64 finaliser over uint64 wraparound arithmetic."""
    x = np.asarray(x, dtype=np.uint64)
    with np.errstate(over="ignore"):
        z = (x + _MIX_GOLD) & _U64
        z = ((z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)) & _U64
        z = ((z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)) & _U64
        return z ^ (z >> np.uint64(31))


def mix_hash(seed, major, minor, salt) -> np.ndarray:
    """``splitmix64(seed*phi + major*FNV + minor*1000003 + salt)``; arguments
    broadcast. Seeds up to 2**64 - 1 are accepted."""
    with np.errstate(over="ignore"):
        key = (
            np.uint64(int(seed) & 0xFFFFFFFFFFFFFFFF) * _MIX_GOLD
            + np.asarray(major, dtype=np.uint64) * _MIX_FNV
            + np.asarray(minor, dtype=np.uint64) * _MIX_POS
            + np.asarray(salt, dtype=np.uint64)
        )
        return splitmix64(key)


def _scramble(v: np.ndarray, scale: int, seed: int) -> np.ndarray:
    """Invertible permutation of [0, 2**scale): odd multiply + xor-shift."""
    mask = np.uint64((1 << scale) - 1)
    c1 = (splitmix64(np.uint64(seed) + np.uint64(0xA5)) | np.uint64(1)) & mask
    c2 = (splitmix64(np.uint64(seed) + np.uint64(0xC3)) | np.uint64(1)) & mask
    s1 = max(1, scale // 2)
    s2 = max(1, (2 * scale) // 3)
    x = v.astype(np.uint64)
    with np.errstate(over="ignore"):
        x = (x * c1) & mask
        x ^= x >> np.uint64(s1)
        x = (x * c2) & mask
        x ^= x >> np.uint64(s2)
    return x


def kron_pairs(scale: int, initiator, seed: int, idx: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(src, dst) uint64 of candidate indices ``idx`` before relabelling: one
    quadrant draw of the initiator per recursion level."""
    a, b, c = (float(x) for x in initiator)
    idx = np.asarray(idx, dtype=np.uint64).reshape(-1)
    src = np.zeros(idx.shape[0], dtype=np.uint64)
    dst = np.zeros(idx.shape[0], dtype=np.uint64)
    cum = np.cumsum([a, b, c])
    # Thresholds on the u64 scale; the last quadrant takes everything above.
    t = np.asarray([min(int(x * 2**64), 2**64 - 1) for x in cum] + [2**64 - 1], dtype=np.uint64)
    for bit in range(scale):
        h = mix_hash(seed, idx, bit, _SALT_QUAD)
        q = np.searchsorted(t, h, side="left").astype(np.uint64)
        src |= ((q >> np.uint64(1)) & np.uint64(1)) << np.uint64(bit)
        dst |= (q & np.uint64(1)) << np.uint64(bit)
    return src, dst


def candidate_edges(scale: int, initiator, seed: int, idx: np.ndarray) -> np.ndarray:
    """(n, 2) int64 canonical (lo < hi) edges of candidate indices ``idx``,
    both endpoints relabelled by the one permutation; self-loops dropped,
    duplicates kept."""
    src, dst = kron_pairs(scale, initiator, seed, idx)
    src = _scramble(src, scale, seed)
    dst = _scramble(dst, scale, seed)
    lo = np.minimum(src, dst).astype(np.int64)
    hi = np.maximum(src, dst).astype(np.int64)
    keep = lo != hi
    return np.stack([lo[keep], hi[keep]], axis=1)


def graph_edges(scale: int, edge_factor: int, initiator, seed: int,
                block: int = 1 << 21) -> np.ndarray:
    """The configuration's undirected simple graph: ``edge_factor * 2**scale``
    draws, self-loops and repeated pairs dropped (first draw kept, draw
    order preserved). (E, 2) int64."""
    n = (1 << scale) * int(edge_factor)
    parts = [
        candidate_edges(scale, initiator, seed, np.arange(lo, min(lo + block, n)))
        for lo in range(0, n, block)
    ]
    edges = np.concatenate(parts)
    key = edges[:, 0] * np.int64(1 << scale) + edges[:, 1]
    _, first = np.unique(key, return_index=True)
    return edges[np.sort(first)]
