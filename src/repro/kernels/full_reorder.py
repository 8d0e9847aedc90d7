"""Whole-graph GEO re-ordering as a device program — the full-rebuild rung.

The escalation ladder's top rung (DESIGN.md §9/§11) re-orders EVERY live slot,
not just a degraded span. This module generalizes the span-repair kernel of
``kernels/span_reorder.py`` from span scope to whole-graph scope, keeping the
same program shape — an order kernel finished by a multi-key sort
(``lexsort_slots``) whose slot tie-break makes the composite a total order
(any correct sort, host np.lexsort included, yields the identical
permutation) — and the same differential-oracle discipline: ``full_order_host`` is the
byte-exact numpy mirror of ``full_order_device``, proven by the differential
tests, so the engine advances host bookkeeping without a device round-trip.

The order kernel itself is NOT the span rung's label propagation. At span
scope label propagation works because a span holds one or two communities;
at whole-graph scope it was measured to never beat the incumbent layout under
mild drift (the whole point of a full rebuild is restoring fine-k locality,
which community labels alone cannot express). Instead the kernel is a
step-parallel form of GEO's greedy itself (core/ordering.py Algorithm 4):

1. Per step, pick v_min by the exact GEO priority α·D[v] − β·M[v] over
   touched unselected vertices (random-permutation fallback otherwise).
2. Order ALL of v_min's remaining edges at once (GEO orders them ascending
   by neighbor; here they share a step and sort by the neighbor key), then
   eagerly order the two-hop edges e_{u,w} whose w was touched within δ —
   the same Line-11 recency test, with M updated at step granularity.
3. Every ordered edge records (step, phase, key_a, key_b); the final sort
   by (step, phase, key_a, key_b, slot) IS the order. Dead slots key to
   int32-max and sort last, so the permutation is live-first like the span
   kernel's.

The step-granular M makes this a coarser recency than the sequential greedy's
per-edge M — measured within 1.05× of host ``geo_order``'s RF across the
k grid on drifted RMAT streams — while turning GEO's pointer chase into
O(|V_selected|) vectorized steps of scatter/gather, the form an accelerator
can run over the snapshot buffer while ingest keeps landing on the live one.

Candidate selection (``select_full_order_*``) reuses the span kernel's exact
integer objective at whole-graph scope: the greedy order and a caller-supplied
candidate permutation (production: the incumbent layout; oracle/differential
modes: host ``geo_order``) are scored over the CEP chunk grid and the better
one wins, ties to the greedy — a committed device rebuild can never regress
the objective below what is already there.

int32-range discipline: the device runs int32 (jax x64 off); the mirror runs
int64 and ``greedy_params`` rejects graphs whose priorities could overflow
int32, so the two never diverge by wraparound.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core import ordering
from .segment_rf import PAD_ID
from .span_reorder import (
    eval_ks,
    identity_candidate,
    lexsort_slots,
    span_objective_device,
    span_objective_host,
)

__all__ = [
    "greedy_fits_int32",
    "greedy_params",
    "fallback_positions",
    "eval_ks_full",
    "full_order_host",
    "full_order_device",
    "full_objective_host",
    "full_objective_device",
    "select_full_order_host",
    "select_full_order_device",
    "geo_full_candidate",
]

_PAD = int(PAD_ID)  # int32 max — dead-slot sort key


def greedy_fits_int32(num_edges: int, k_min: int, k_max: int, max_degree: int) -> bool:
    """Whether the step-parallel greedy's priorities α·D − β·M stay inside
    int32 for this graph — the precondition of ``greedy_params``. Callers on
    the rebuild path (stream/ingest, core/hier_order) test this and fall back
    to a host ordering instead of aborting: out-of-core chunks routinely
    cross the bound and a rebuild must degrade, not die."""
    ks = np.arange(k_min, k_max + 1, dtype=np.int64)
    alpha = int(np.sum(num_edges // ks))
    beta = int(k_max - k_min)
    return alpha * (int(max_degree) + 1) + beta * (num_edges + 1) < 2**31


def greedy_params(
    num_edges: int,
    k_min: int,
    k_max: int,
    max_degree: int,
) -> tuple[int, int, int]:
    """(alpha, beta, delta) of the step-parallel greedy — the SAME constants
    core/ordering.geo_order derives (Eq. 8 priorities, §4.1 δ), so the two
    rungs optimize one objective. Raises when a priority α·D − β·M could
    leave int32 range: the device computes int32, the mirror int64, and a
    silent wrap on only one side would break the byte-identity contract."""
    ks = np.arange(k_min, k_max + 1, dtype=np.int64)
    alpha = int(np.sum(num_edges // ks))
    beta = int(k_max - k_min)
    delta = max(1, num_edges // k_max)
    bound = alpha * (int(max_degree) + 1) + beta * (num_edges + 1)
    if bound >= 2**31:
        raise ValueError(
            f"greedy priorities may overflow int32 (bound {bound}): "
            "graph too large for the device full-reorder kernel"
        )
    return alpha, beta, delta


def fallback_positions(num_vertices: int, seed: int = 0) -> np.ndarray:
    """Random-vertex fallback ranks (paper: RandomVertex()): position of each
    vertex in a seeded permutation — the untouched-component tie-break, fixed
    per rebuild so host and device pick the identical fallback vertex."""
    rng = np.random.default_rng(seed)
    pos = np.empty(num_vertices, dtype=np.int64)
    pos[rng.permutation(num_vertices)] = np.arange(num_vertices)
    return pos


def eval_ks_full(k_min: int, k_max: int, regions: int) -> tuple:
    """Objective k grid for full-rebuild candidate selection: the span grid
    plus the CURRENT region count — a full rebuild must never regress the RF
    at the k the mesh is actually partitioned into."""
    ks = set(eval_ks(k_min, k_max))
    if k_min <= regions <= k_max:
        ks.add(int(regions))
    return tuple(sorted(ks))


# ----------------------------------------------------------------- host mirror
def full_order_host(
    u: np.ndarray,
    v: np.ndarray,
    valid: np.ndarray,
    num_vertices: int,
    alpha: int,
    beta: int,
    delta: int,
    permpos: np.ndarray,
) -> np.ndarray:
    """Numpy mirror of ``full_order_device`` — identical permutation byte for
    byte (int64 arithmetic over int32-range values; see ``greedy_params``)."""
    cap = u.shape[0]
    ui = np.asarray(u, dtype=np.int64)
    vi = np.asarray(v, dtype=np.int64)
    valid = np.asarray(valid, dtype=bool)
    permpos = np.asarray(permpos, dtype=np.int64)
    done = ~valid.copy()
    d = np.zeros(num_vertices, np.int64)
    np.add.at(d, ui[valid], 1)
    np.add.at(d, vi[valid], 1)
    m = np.zeros(num_vertices, np.int64)
    touched = np.zeros(num_vertices, bool)
    selected = np.zeros(num_vertices, bool)
    e_live = int(valid.sum())
    MAX = np.int64(_PAD)
    step = np.full(cap, MAX, np.int64)
    phase = np.full(cap, MAX, np.int64)
    ka = np.full(cap, MAX, np.int64)
    kb = np.full(cap, MAX, np.int64)
    i = 0
    for t in range(num_vertices):
        if i >= e_live:
            break
        cand = touched & ~selected & (d > 0)
        if cand.any():
            vmin = int(np.argmin(np.where(cand, alpha * d - beta * m, MAX)))
        else:
            vmin = int(np.argmin(np.where(~selected & (d > 0), permpos, MAX)))
        # --- one-hop: every remaining edge of v_min, keyed by the neighbor
        oh = (~done) & ((ui == vmin) | (vi == vmin))
        other = np.where(ui == vmin, vi, ui)
        n1 = int(oh.sum())
        i1 = i + n1
        step[oh] = t
        phase[oh] = 0
        ka[oh] = other[oh]
        kb[oh] = 0
        m[other[oh]] = i1
        np.subtract.at(d, other[oh], 1)
        touched[other[oh]] = True
        touched[vmin] = True
        done |= oh
        d[vmin] = 0
        selected[vmin] = True
        i = i1
        # --- two-hop: e_{u,w} with u in the fresh frontier, w recent (≤ δ)
        if n1:
            fr = np.zeros(num_vertices, bool)
            fr[other[oh]] = True
            u_in = fr[ui]
            v_in = fr[vi]
            wother = np.where(u_in, vi, ui)
            rec = (
                touched[wother]
                & ~selected[wother]
                & (m[wother] > 0)
                & ((i1 - m[wother]) <= delta)
            )
            th = (~done) & (u_in | v_in) & rec & (wother != vmin)
            n2 = int(th.sum())
            if n2:
                tu = np.where(u_in[th], ui[th], vi[th])
                tw = wother[th]
                step[th] = t
                phase[th] = 1
                ka[th] = tu
                kb[th] = tw
                i2 = i1 + n2
                np.subtract.at(d, tu, 1)
                np.subtract.at(d, tw, 1)
                m[tu] = i2
                m[tw] = i2
                done |= th
                i = i2
    slot = np.arange(cap, dtype=np.int64)
    # Unique composite (slot breaks all ties) → sort-implementation agnostic.
    return np.lexsort((slot, kb, ka, phase, step))


# -------------------------------------------------------------- device (jnp)
def full_order_device(u, v, valid, num_vertices: int, alpha, beta, delta, permpos):
    """Traced twin of ``full_order_host``. ``u``/``v`` int32 (cap,), ``valid``
    bool (cap,), ``alpha``/``beta``/``delta`` int32 scalars, ``permpos`` int32
    (|V|,) — all operands, so ONE compiled program serves every rebuild of a
    layout signature. Returns the (cap,) permutation, live slots first."""
    cap = u.shape[0]
    nv = int(num_vertices)
    MAX = jnp.int32(_PAD)
    ui = u.astype(jnp.int32)
    vi = v.astype(jnp.int32)
    e_live = jnp.sum(valid.astype(jnp.int32))
    # Degrees via a dump-row scatter: invalid slots target index nv, sliced off.
    iu = jnp.where(valid, ui, nv)
    iv = jnp.where(valid, vi, nv)
    d0 = jnp.zeros(nv + 1, jnp.int32).at[iu].add(1).at[iv].add(1)[:nv]
    state0 = (
        jnp.int32(0),  # t — step counter
        jnp.int32(0),  # i — edges ordered so far (|X^phi|)
        d0,  # D[v]
        jnp.zeros(nv, jnp.int32),  # M[v]
        jnp.zeros(nv, jnp.bool_),  # touched
        jnp.zeros(nv, jnp.bool_),  # selected
        ~valid,  # done (per slot)
        jnp.full(cap, MAX, jnp.int32),  # step key
        jnp.full(cap, MAX, jnp.int32),  # phase key
        jnp.full(cap, MAX, jnp.int32),  # neighbor key a
        jnp.full(cap, MAX, jnp.int32),  # neighbor key b
    )

    def cond(s):
        return (s[0] < nv) & (s[1] < e_live)

    def body(s):
        t, i, d, m, touched, selected, done, step, phase, ka, kb = s
        cand = touched & (~selected) & (d > 0)
        pri = jnp.where(cand, alpha * d - beta * m, MAX)
        vmin_c = jnp.argmin(pri).astype(jnp.int32)
        elig = (~selected) & (d > 0)
        vmin_f = jnp.argmin(jnp.where(elig, permpos, MAX)).astype(jnp.int32)
        vmin = jnp.where(cand.any(), vmin_c, vmin_f)
        # one-hop
        oh = (~done) & ((ui == vmin) | (vi == vmin))
        other = jnp.where(ui == vmin, vi, ui)
        n1 = oh.sum().astype(jnp.int32)
        i1 = i + n1
        step = jnp.where(oh, t, step)
        phase = jnp.where(oh, 0, phase)
        ka = jnp.where(oh, other, ka)
        kb = jnp.where(oh, 0, kb)
        oidx = jnp.where(oh, other, nv)  # dump row nv for unordered slots
        m = jnp.pad(m, (0, 1)).at[oidx].set(i1)[:nv]
        d = jnp.pad(d, (0, 1)).at[oidx].add(-1)[:nv]
        touched = jnp.pad(touched, (0, 1)).at[oidx].set(True)[:nv]
        touched = touched.at[vmin].set(True)
        done = done | oh
        d = d.at[vmin].set(0)
        selected = selected.at[vmin].set(True)
        # two-hop
        fr = jnp.zeros(nv + 1, jnp.bool_).at[oidx].set(True)[:nv]
        u_in = fr[ui]
        v_in = fr[vi]
        wother = jnp.where(u_in, vi, ui)
        rec = (
            touched[wother]
            & (~selected[wother])
            & (m[wother] > 0)
            & ((i1 - m[wother]) <= delta)
        )
        th = (~done) & (u_in | v_in) & rec & (wother != vmin) & (n1 > 0)
        n2 = th.sum().astype(jnp.int32)
        tu = jnp.where(u_in, ui, vi)
        step = jnp.where(th, t, step)
        phase = jnp.where(th, 1, phase)
        ka = jnp.where(th, tu, ka)
        kb = jnp.where(th, wother, kb)
        i2 = i1 + n2
        tui = jnp.where(th, tu, nv)
        twi = jnp.where(th, wother, nv)
        d = jnp.pad(d, (0, 1)).at[tui].add(-1).at[twi].add(-1)[:nv]
        m = jnp.pad(m, (0, 1)).at[tui].set(i2).at[twi].set(i2)[:nv]
        done = done | th
        return (t + 1, i2, d, m, touched, selected, done, step, phase, ka, kb)

    s = lax.while_loop(cond, body, state0)
    step, phase, ka, kb = s[7], s[8], s[9], s[10]
    # The whole-graph twin of the span kernel's finish.
    return lexsort_slots((step, phase, ka, kb))


# ------------------------------------------------------- objective + selection
def full_objective_host(
    u: np.ndarray, v: np.ndarray, valid: np.ndarray, order: np.ndarray, ks: Sequence[int]
) -> int:
    """Exact whole-graph objective of a live-first permutation — the span
    objective evaluated at graph scope (the machinery is scope-free)."""
    return span_objective_host(u, v, valid, order, ks)


def full_objective_device(u, v, valid, order, n, ks, *, use_pallas: bool):
    """Traced twin of ``full_objective_host`` (identical integers)."""
    return span_objective_device(u, v, valid, order, n, ks, use_pallas=use_pallas)


def select_full_order_host(
    u: np.ndarray,
    v: np.ndarray,
    valid: np.ndarray,
    num_vertices: int,
    candidate: np.ndarray,
    ks: Sequence[int],
    alpha: int,
    beta: int,
    delta: int,
    permpos: np.ndarray,
) -> tuple[np.ndarray, bool]:
    """(chosen order, chose_candidate): the step-parallel greedy order vs the
    candidate permutation by the exact whole-graph objective; the candidate
    wins only on a STRICT improvement. With the incumbent layout as the
    candidate this is the never-worse guarantee; with host ``geo_order`` it is
    never-worse-than-GEO by construction."""
    greedy = full_order_host(u, v, valid, num_vertices, alpha, beta, delta, permpos)
    obj_g = full_objective_host(u, v, valid, greedy, ks)
    obj_c = full_objective_host(u, v, valid, candidate, ks)
    if obj_c < obj_g:
        return np.asarray(candidate, dtype=np.int64), True
    return greedy, False


def select_full_order_device(
    u, v, valid, num_vertices: int, candidate, ks, alpha, beta, delta, permpos,
    *, use_pallas: bool,
):
    """Traced twin of ``select_full_order_host`` (returns only the chosen
    permutation — the mirror recomputes the identical decision host-side)."""
    n = jnp.sum(valid.astype(jnp.int32))
    greedy = full_order_device(u, v, valid, num_vertices, alpha, beta, delta, permpos)
    obj_g = full_objective_device(u, v, valid, greedy, n, ks, use_pallas=use_pallas)
    obj_c = full_objective_device(u, v, valid, candidate, n, ks, use_pallas=use_pallas)
    return jnp.where(obj_c < obj_g, candidate.astype(jnp.int32), greedy)


def geo_full_candidate(
    slot_src: np.ndarray,
    slot_dst: np.ndarray,
    slot_valid: np.ndarray,
    num_vertices: int,
    k_min: int = ordering.K_MIN_DEFAULT,
    k_max: int = ordering.K_MAX_DEFAULT,
    seed: int = 0,
) -> np.ndarray:
    """Host ``geo_order`` of the WHOLE live slot array as a live-first slot
    permutation — the full-rebuild quality oracle, and the production
    candidate of the async rung on hosts where the greedy device program is
    not profitable. The graph is rebuilt from the slots, ordered, and mapped
    back to slot ids (slots hold unique canonical u < v pairs, so the mapping
    is a bijection — the order is expressed over the slots, never over the
    Graph's re-sorted edge arrays)."""
    from ..core.graph import Graph

    valid = np.asarray(slot_valid, dtype=bool)
    live = np.flatnonzero(valid)
    if live.size < 2:
        return identity_candidate(valid)
    u = np.asarray(slot_src, dtype=np.int64)
    v = np.asarray(slot_dst, dtype=np.int64)
    g = Graph.from_edges(np.stack([u[live], v[live]], axis=1), num_vertices)
    order = ordering.geo_order(g, k_min, k_max, seed=seed)
    # Slot lookup via scalar keys + searchsorted (the (u, v) pairs are unique
    # canonical edges, so u·V + v is a bijection — and V² fits int64 for any
    # graph this subsystem can hold).
    nv = np.int64(num_vertices)
    slot_keys = u[live] * nv + v[live]
    sorter = np.argsort(slot_keys, kind="stable")
    ordered_keys = g.src[order].astype(np.int64) * nv + g.dst[order].astype(np.int64)
    cand_live = live[sorter[np.searchsorted(slot_keys[sorter], ordered_keys)]]
    return np.concatenate([cand_live, np.flatnonzero(~valid)])
