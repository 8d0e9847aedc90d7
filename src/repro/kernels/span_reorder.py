"""Span-local GEO repair — the device side of the partial re-order rung.

The streaming escalation ladder's middle rung (DESIGN.md §9) repairs only the
worst span of regions. PR-3 ran host ``geo_order`` on the extracted span and
re-uploaded the rewritten slots; that host pass dominated the stream's
amortized cost (BENCH_stream.json). This module provides the on-device
replacement and its byte-exact host mirror — the *differential oracle*
discipline: the jitted program and the numpy mirror implement the identical
integer algorithm, so the engine can update host bookkeeping from the mirror
(no device round-trip) while ``verify_bit_identity`` proves the two never
diverge.

Algorithm (``span_order_*``): neighbor-expansion scoring over the span's live
edges, fully vectorized so it runs in O(rounds · span) VPU-friendly ops
instead of GEO's sequential greedy —

1. ``rounds`` iterations of min-label propagation over the span edges
   (scatter-min): every vertex adopts the smallest vertex id reachable within
   ``rounds`` hops inside the span. Connected neighborhoods collapse onto one
   label — the vectorized stand-in for GEO's frontier expansion.
2. Each vertex records the round its label last improved (``depth``) — its
   expansion distance from the neighborhood root, the analogue of GEO's
   recency M[v].
3. Edges sort by (label, depth, lo endpoint, hi endpoint, slot): one
   neighborhood at a time, inner edges before fringe edges. The slot key makes
   the composite unique, so ANY correct sort yields the same permutation —
   host np.lexsort and the device's ``lexsort_slots`` agree bit-for-bit.

Candidate selection (``select_span_order_*``): the repair never commits blind.
The program scores its expansion order AND a caller-supplied candidate
permutation by the exact multi-k span objective (Eq.-(7)-style distinct-vertex
counts over CEP chunks at ``eval_ks``) and keeps the better, ties to the
expansion order. Production passes the *current* layout as the candidate, so a
repair can never worsen the span objective; the differential tests pass host
``geo_order`` as the candidate, making never-worse-than-GEO hold by
construction (ISSUE-5 satellite).

Objective evaluation is tombstone-aware (dead slots key to PAD and count
nothing) and, where profitable, runs the distinct counting through the Pallas
boundary-count kernel of ``kernels/segment_rf.py`` — the per-(chunk, k) key
rows are exactly that kernel's sorted-row layout. The Pallas path is gated to
single-device/single-process meshes; the jnp count computes the identical
integers on every other mesh.

Everything here sticks to int32-range arithmetic (jax x64 is off by default),
mirrored in int64 by numpy without divergence.
"""
from __future__ import annotations

from typing import Sequence

import jax.lax
import jax.numpy as jnp
import numpy as np

from ..core import cep
from .ops import interpret_mode
from .segment_rf import PAD_ID, segment_distinct_counts

__all__ = [
    "SPAN_ROUNDS",
    "eval_ks",
    "identity_candidate",
    "span_order_host",
    "span_objective_host",
    "select_span_order_host",
    "span_order_device",
    "lexsort_slots",
    "span_objective_device",
    "select_span_order_device",
    "splice_targets_device",
]

# Label-propagation rounds: how far a neighborhood expands. Spans are one to
# three regions wide; 4 hops collapses any community that fits in one
# (measured identical span objective to 16 rounds on degraded RMAT spans),
# and each round costs two scatter-mins — the program's dominant op on CPU
# meshes, so rounds are the partial rung's main cost knob.
SPAN_ROUNDS = 4

_PAD = int(PAD_ID)  # int32 max — tombstone/padding key for ids and chunk keys


def eval_ks(k_min: int, k_max: int) -> tuple:
    """The static k grid the span objective sums over: geometric steps of the
    GEO objective's [k_min, k_max] range (evaluating all ~125 k's per repair
    would cost more than the repair; three decades rank candidates the same
    way the full grid does on every span tested)."""
    ks = tuple(k for k in (4, 16, 64) if k_min <= k <= k_max)
    return ks if ks else (max(2, int(k_min)),)


def identity_candidate(valid: np.ndarray) -> np.ndarray:
    """The current span layout as a live-first permutation: occupied slots in
    slot order, tombstones appended — the production candidate (a repair must
    never score worse than what's already there)."""
    valid = np.asarray(valid, dtype=bool)
    return np.concatenate([np.flatnonzero(valid), np.flatnonzero(~valid)])


# ----------------------------------------------------------------- host mirror
def span_order_host(
    u: np.ndarray,
    v: np.ndarray,
    valid: np.ndarray,
    num_vertices: int,
    rounds: int = SPAN_ROUNDS,
) -> np.ndarray:
    """Numpy mirror of ``span_order_device`` — identical permutation, proven
    byte-for-byte by the differential tests and ``verify_bit_identity``."""
    u = np.asarray(u, dtype=np.int64)
    v = np.asarray(v, dtype=np.int64)
    valid = np.asarray(valid, dtype=bool)
    cap = u.shape[0]
    uu, vv = u[valid], v[valid]
    lbl = np.arange(num_vertices, dtype=np.int64)
    depth = np.zeros(num_vertices, dtype=np.int64)
    for t in range(1, rounds + 1):
        le = np.minimum(lbl[uu], lbl[vv])
        new = lbl.copy()
        np.minimum.at(new, uu, le)
        np.minimum.at(new, vv, le)
        depth = np.where(new < lbl, t, depth)
        if np.array_equal(new, lbl):
            break  # converged — the device runs all rounds as no-ops
        lbl = new
    comp = np.where(valid, np.minimum(lbl[u], lbl[v]), _PAD)
    dep = np.where(valid, np.minimum(depth[u], depth[v]), 0)
    lo = np.where(valid, np.minimum(u, v), 0)
    hi = np.where(valid, np.maximum(u, v), 0)
    slot = np.arange(cap, dtype=np.int64)
    # Unique composite (slot breaks every tie) → sort-implementation agnostic.
    return np.lexsort((slot, hi, lo, dep, comp))


def span_objective_host(
    u: np.ndarray,
    v: np.ndarray,
    valid: np.ndarray,
    order: np.ndarray,
    ks: Sequence[int],
) -> int:
    """Exact span objective of a live-first permutation: Σ_{k∈ks} Σ_chunks
    |V(chunk)| over CEP chunks of the span's live edges. Integer, so the host
    and device comparisons agree exactly."""
    u = np.asarray(u, dtype=np.int64)
    v = np.asarray(v, dtype=np.int64)
    valid = np.asarray(valid, dtype=bool)
    order = np.asarray(order, dtype=np.int64)
    n = int(valid.sum())
    if n == 0:
        return 0
    uo, vo = u[order[:n]], v[order[:n]]
    total = 0
    j = np.arange(n, dtype=np.int64)
    for k in ks:
        p = np.asarray(cep.id2p(n, int(k), j), dtype=np.int64)
        key = np.concatenate([p, p])
        ids = np.concatenate([uo, vo])
        total += np.unique(key * (np.int64(2) ** 32) + ids).shape[0]
    return int(total)


def select_span_order_host(
    u: np.ndarray,
    v: np.ndarray,
    valid: np.ndarray,
    num_vertices: int,
    candidate: np.ndarray,
    ks: Sequence[int],
    rounds: int = SPAN_ROUNDS,
) -> tuple[np.ndarray, bool]:
    """(chosen order, chose_candidate): expansion order vs candidate by exact
    objective, candidate only on a strict win — mirror of the device select."""
    vec = span_order_host(u, v, valid, num_vertices, rounds)
    obj_vec = span_objective_host(u, v, valid, vec, ks)
    obj_cand = span_objective_host(u, v, valid, candidate, ks)
    if obj_cand < obj_vec:
        return np.asarray(candidate, dtype=np.int64), True
    return vec, False


# -------------------------------------------------------------- device (jnp)
def span_order_device(u, v, valid, num_vertices: int, rounds: int = SPAN_ROUNDS):
    """Traced twin of ``span_order_host``. ``u``/``v`` int32 (cap,), ``valid``
    bool (cap,); returns the (cap,) permutation, live slots first."""
    ui = jnp.where(valid, u, 0)
    vi = jnp.where(valid, v, 0)

    def body(i, carry):
        lbl, depth = carry
        le = jnp.where(valid, jnp.minimum(lbl[ui], lbl[vi]), jnp.int32(_PAD))
        new = lbl.at[ui].min(le).at[vi].min(le)
        depth = jnp.where(new < lbl, (i + 1).astype(jnp.int32), depth)
        return new, depth

    # fori_loop, not an unrolled python loop: the body compiles once, keeping
    # the span program's trace small (compile time is a real cost — one per
    # (k, e_cap, span) signature over a stream's life).
    lbl, depth = jax.lax.fori_loop(
        0,
        rounds,
        body,
        (jnp.arange(num_vertices, dtype=jnp.int32), jnp.zeros(num_vertices, jnp.int32)),
    )
    comp = jnp.where(valid, jnp.minimum(lbl[ui], lbl[vi]), jnp.int32(_PAD))
    dep = jnp.where(valid, jnp.minimum(depth[ui], depth[vi]), 0)
    lo = jnp.where(valid, jnp.minimum(u, v), 0)
    hi = jnp.where(valid, jnp.maximum(u, v), 0)
    # The slot breaks every tie, so the composite is a total order and any
    # correct sort — np.lexsort on the host — gives the identical permutation.
    return lexsort_slots((comp, dep, lo, hi))


def lexsort_slots(keys):
    """The permutation that sorts slots by ``keys`` (most significant first)
    and then by slot: ``np.lexsort((slot,) + keys[::-1])``.

    Stable one-key sorts from the least significant key up, not one
    ``lax.sort`` over every key: the TPU compiler's cost grows steeply with
    a sort's key count (for v5e, a 5-key sort of 65,536 slots took 252 s to
    compile, four stable one-key sorts 29 s)."""
    perm = jnp.arange(keys[0].shape[0], dtype=jnp.int32)
    for key in reversed(keys):
        perm = jax.lax.sort((key[perm], perm), num_keys=1, is_stable=True)[1]
    return perm


def _chunk_keys_device(u, v, valid, order, n, ks):
    """(len(ks), 2·cap) int32 rows of (chunk, vertex-rank) keys, PAD where
    dead — each row sorted is exactly the layout segment_rf counts over."""
    cap = u.shape[0]
    ids_sorted = jnp.sort(
        jnp.concatenate(
            [jnp.where(valid, u, jnp.int32(_PAD)), jnp.where(valid, v, jnp.int32(_PAD))]
        )
    )
    stride = jnp.int32(2 * cap + 2)
    uo = u[order]
    vo = v[order]
    ru = jnp.searchsorted(ids_sorted, uo).astype(jnp.int32)
    rv = jnp.searchsorted(ids_sorted, vo).astype(jnp.int32)
    j = jnp.arange(cap, dtype=jnp.int32)
    live = j < n
    rows = []
    for k in ks:
        p = cep.id2p(n, int(k), j).astype(jnp.int32)
        ku = jnp.where(live, p * stride + ru, jnp.int32(_PAD))
        kv = jnp.where(live, p * stride + rv, jnp.int32(_PAD))
        rows.append(jnp.concatenate([ku, kv]))
    return jnp.stack(rows)


def span_objective_device(u, v, valid, order, n, ks, *, use_pallas: bool):
    """Traced twin of ``span_objective_host`` (identical integer result).

    ``use_pallas=True`` routes the distinct counting through the segment_rf
    boundary-count kernel (Mosaic on a TPU, interpreted elsewhere); the jnp
    path is the same boundary comparison inline, for meshes where a Pallas
    custom call cannot be SPMD-partitioned."""
    keys = jnp.sort(_chunk_keys_device(u, v, valid, order, n, ks), axis=-1)
    if use_pallas:
        return jnp.sum(segment_distinct_counts(keys, interpret=interpret_mode()))
    prev = jnp.concatenate(
        [jnp.full((keys.shape[0], 1), -1, keys.dtype), keys[:, :-1]], axis=1
    )
    return jnp.sum(((keys != prev) & (keys != _PAD)).astype(jnp.int32))


def select_span_order_device(
    u, v, valid, num_vertices: int, candidate, ks, *, use_pallas: bool,
    rounds: int = SPAN_ROUNDS,
):
    """Traced twin of ``select_span_order_host``: returns the chosen (cap,)
    permutation (never returns the objective — the host mirror recomputes the
    identical choice, so nothing needs to travel back)."""
    n = jnp.sum(valid.astype(jnp.int32))
    vec = span_order_device(u, v, valid, num_vertices, rounds)
    obj_vec = span_objective_device(u, v, valid, vec, n, ks, use_pallas=use_pallas)
    obj_cand = span_objective_device(u, v, valid, candidate, n, ks, use_pallas=use_pallas)
    return jnp.where(obj_cand < obj_vec, candidate.astype(jnp.int32), vec)


def _mul_div(a, b, c):
    """⌊a·b / c⌋ in int32 without forming a·b, for 0 ≤ a ≤ c < 2^30 and
    0 ≤ b < 2^31. Binary long multiplication over a's bits keeps a quotient
    (at most b) and a remainder below c, so nothing leaves int32 — the
    host's int64 product overflows int32 once a region holds ~46k edges."""
    qb, rb = b // c, b % c
    q = jnp.zeros_like(a)
    r = jnp.zeros_like(a)
    for bit in range(30, -1, -1):
        q, r = 2 * q, 2 * r
        q, r = jnp.where(r >= c, q + 1, q), jnp.where(r >= c, r - c, r)
        on = (a >> bit) & 1
        q, r = q + on * qb, r + on * rb
        q, r = jnp.where(r >= c, q + 1, q), jnp.where(r >= c, r - c, r)
    return q


def splice_targets_device(n, span_regions: int, spr: int, cap: int):
    """Span-local slot target of each order position — the traced twin of the
    host ``_rewrite_span`` splice: CEP chunks of the n live edges over the
    span's regions, each chunk spread evenly over its region's ``spr`` slots.
    Dead positions (j ≥ n) target the overflow slot ``cap``."""
    j = jnp.arange(cap, dtype=jnp.int32)
    p = cep.id2p(n, span_regions, j).astype(jnp.int32)
    start = cep.chunk_start(n, span_regions, p).astype(jnp.int32)
    nxt = cep.chunk_start(n, span_regions, p + 1).astype(jnp.int32)
    n_p = jnp.maximum(nxt - start, 1)
    live = j < n
    col = _mul_div(jnp.where(live, j - start, 0), jnp.int32(spr), n_p)
    return jnp.where(live, p * jnp.int32(spr) + col, jnp.int32(cap))
