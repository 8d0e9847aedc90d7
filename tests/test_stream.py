"""Streaming-graph subsystem: update model, incremental orderer, on-device
ingest (tier-1 scale — the mesh-of-1 degenerate case; the 8-device suite is
tests/test_stream_sharded.py)."""
import numpy as np
import pytest
from conftest import hypothesis_or_stub

from repro.core import metrics, ordering
from repro.core.graph import rmat_graph
from repro.elastic import controller as ec
from repro.graphs import engine as E
from repro.launch import mesh as MM
from repro.stream import (
    EdgeUpdateBatch,
    IncrementalOrderer,
    StreamConfig,
    StreamingEngine,
    SyntheticStream,
    best_insert_position,
)

given, settings, st = hypothesis_or_stub()


@pytest.fixture(scope="module")
def ordered():
    g = rmat_graph(7, 6, seed=0)
    order = ordering.geo_order(g, seed=0)
    return g, g.src[order].astype(np.int64), g.dst[order].astype(np.int64)


def make_orderer(ordered, regions=4, **cfg):
    g, src, dst = ordered
    config = StreamConfig(**cfg) if cfg else StreamConfig()
    return g, IncrementalOrderer(src, dst, g.num_vertices, regions=regions, config=config)


# ------------------------------------------------------------------- updates
def test_update_batch_canonicalizes():
    b = EdgeUpdateBatch(
        insert=np.array([[3, 1], [1, 3], [2, 2], [4, 5]]),
        delete=np.array([[9, 7]]),
    )
    # Dedup (1,3)/(3,1), drop the self loop, canonicalize src < dst.
    assert b.insert.tolist() == [[1, 3], [4, 5]]
    assert b.delete.tolist() == [[7, 9]]
    assert b.num_updates == 3


def test_synthetic_stream_is_deterministic_and_consistent():
    g = rmat_graph(6, 4, seed=1)
    s1 = SyntheticStream(g, batch_size=32, seed=7)
    s2 = SyntheticStream(g, batch_size=32, seed=7)
    live = {(int(u), int(v)) for u, v in zip(g.src, g.dst)}
    for _ in range(5):
        b1, b2 = s1.batch(), s2.batch()
        np.testing.assert_array_equal(b1.insert, b2.insert)
        np.testing.assert_array_equal(b1.delete, b2.delete)
        # Batches apply delete-then-insert (IncrementalOrderer.apply order).
        for u, v in b1.delete.tolist():
            assert (u, v) in live  # deletes always name live edges
            live.discard((u, v))
        for u, v in b1.insert.tolist():
            assert (u, v) not in live  # inserts are always novel
            live.add((u, v))
    assert {tuple(e) for e in s1.edges().tolist()} == live
    with pytest.raises(ValueError, match="in order"):
        s1.batch(99)


def test_stream_and_orderer_live_sets_stay_in_sync():
    """Regression: a delete that hash-picks a same-batch insert used to leave
    the orderer and generator with different live sets."""
    g = rmat_graph(6, 4, seed=1)
    order = ordering.geo_order(g, seed=0)
    o = IncrementalOrderer(
        g.src[order].astype(np.int64), g.dst[order].astype(np.int64),
        g.num_vertices, regions=3,
    )
    s = SyntheticStream(g, batch_size=64, delete_frac=0.4, seed=3)
    for _ in range(20):
        o.apply(s.batch())
    got = {(int(a), int(b)) for a, b in zip(*o.snapshot())}
    assert got == {tuple(e) for e in s.edges().tolist()}
    assert o.num_edges == s.num_edges


def test_synthetic_stream_different_seeds_differ():
    g = rmat_graph(6, 4, seed=1)
    a = SyntheticStream(g, batch_size=32, seed=0).batch()
    b = SyntheticStream(g, batch_size=32, seed=1).batch()
    assert a.insert.tolist() != b.insert.tolist()


# ----------------------------------------------------- bursty stream (ISSUE 6)
def test_synthetic_stream_burst_schedule_and_shapes():
    """Bursts land on the LAST batch of each window (a pure function of the
    index), are burst_factor× the base size, and draw deletes at
    burst_delete_frac; off-burst batches keep the base plan."""
    g = rmat_graph(7, 8, seed=1)
    s = SyntheticStream(
        g, batch_size=16, delete_frac=0.25, seed=5,
        burst_every=4, burst_factor=3, burst_delete_frac=0.5,
    )
    for b in range(8):
        assert s.is_burst(b) == (b % 4 == 3)
        n_del, n_ins = s.batch_shape(b)
        if s.is_burst(b):
            assert n_del + n_ins == 16 * 3 and n_del == 24  # 48 × 0.5
        else:
            assert n_del + n_ins == 16 and n_del == 4  # 16 × 0.25
        batch = s.batch()
        # The graph is large enough that the plan is never clamped.
        assert batch.num_deletes == n_del and batch.num_inserts == n_ins


def test_synthetic_stream_burst_replay_is_stateless(ordered):
    """The stateless-replay contract survives bursty mode: two generators
    with the same (seed, burst plan) emit identical batches, and the orderer's
    live set tracks the generator's through the churn spikes."""
    g, src, dst = ordered
    kw = dict(batch_size=24, delete_frac=0.3, seed=9, burst_every=3, burst_factor=4)
    s1 = SyntheticStream(g, **kw)
    s2 = SyntheticStream(g, **kw)
    o = IncrementalOrderer(src, dst, g.num_vertices, regions=4)
    for b in range(7):
        b1, b2 = s1.batch(), s2.batch()
        np.testing.assert_array_equal(b1.insert, b2.insert)
        np.testing.assert_array_equal(b1.delete, b2.delete)
        o.apply(b1)
    got = {(int(a), int(c)) for a, c in zip(*o.snapshot())}
    assert got == {tuple(e) for e in s1.edges().tolist()}


def test_synthetic_stream_burst_default_delete_frac_and_off_mode():
    g = rmat_graph(6, 4, seed=1)
    s = SyntheticStream(g, batch_size=16, delete_frac=0.25, burst_every=2)
    assert s.burst_delete_frac == 0.25  # defaults to the base delete_frac
    off = SyntheticStream(g, batch_size=16)
    assert not any(off.is_burst(b) for b in range(20))  # burst_every=0 = never
    assert off.batch_shape(3) == (4, 12)


def test_synthetic_stream_burst_validation():
    g = rmat_graph(5, 4, seed=1)
    with pytest.raises(ValueError, match="burst_every"):
        SyntheticStream(g, burst_every=-1)
    with pytest.raises(ValueError, match="burst_factor"):
        SyntheticStream(g, burst_every=2, burst_factor=0)
    with pytest.raises(ValueError, match="burst_delete_frac"):
        SyntheticStream(g, burst_every=2, burst_delete_frac=1.0)


# ------------------------------------------------------------------- orderer
def test_orderer_snapshot_roundtrips_initial_order(ordered):
    g, src, dst = ordered
    o = IncrementalOrderer(src, dst, g.num_vertices, regions=4)
    s, d = o.snapshot()
    np.testing.assert_array_equal(s, src)
    np.testing.assert_array_equal(d, dst)
    assert o.num_edges == g.num_edges
    assert o.capacity == 4 * o.slots_per_region


def test_orderer_insert_delete_idempotent(ordered):
    g, o = make_orderer(ordered)
    e0 = o.num_edges
    batch = EdgeUpdateBatch(
        insert=np.array([[int(g.src[0]), int(g.dst[0])]]),  # duplicate insert
        delete=np.array([[g.num_vertices - 1, g.num_vertices - 2]]),  # absent
    )
    counts = o.apply(batch)
    # Skipped updates place nothing: no placement work is counted either.
    assert counts == {"inserted": 0, "deleted": 0, "skipped": 2, "incident_entries": 0,
                      "free_entries": 0, "grows": 0, "append_fallbacks": 0}
    assert o.num_edges == e0
    # Real delete then re-insert lands the edge back.
    edge = [int(g.src[5]), int(g.dst[5])]
    o.apply(EdgeUpdateBatch(insert=np.zeros((0, 2)), delete=np.array([edge])))
    assert o.num_edges == e0 - 1
    o.apply(EdgeUpdateBatch(insert=np.array([edge]), delete=np.zeros((0, 2))))
    assert o.num_edges == e0
    s, d = o.snapshot()
    assert {(int(a), int(b)) for a, b in zip(s, d)} == {
        (int(a), int(b)) for a, b in zip(g.src, g.dst)
    }


def test_orderer_locality_placement_beats_append(ordered):
    """Streaming a locality-heavy update mix, the locality placement must not
    lose to naive append-at-end on the monitored region objective."""
    g, src, dst = ordered
    o_loc = IncrementalOrderer(src, dst, g.num_vertices, regions=4)
    stream = SyntheticStream(g, batch_size=64, seed=3)
    batches = [stream.batch() for _ in range(4)]
    for b in batches:
        o_loc.apply(b)
    # Append-only variant: same updates, placement forced to the append path
    # by emptying the incident index lookups.
    o_app = IncrementalOrderer(src, dst, g.num_vertices, regions=4)
    real_incident = o_app._incident
    o_app._incident = {}
    for b in batches:
        o_app.apply(b)
    o_app._incident = real_incident
    assert o_loc.region_vertex_sum() <= o_app.region_vertex_sum()


def test_orderer_grow_on_overflow(ordered):
    """Inserting past the slot array's free capacity (bucketed slack included
    — slots_per_region is 256-aligned with growth headroom) must grow it in
    place without losing edges."""
    g, src, dst = ordered
    o = IncrementalOrderer(
        src, dst, g.num_vertices, regions=2, config=StreamConfig(slack=0.05)
    )
    spr0 = o.slots_per_region
    free0 = int(o.capacity - o.num_edges)
    rng = np.random.default_rng(0)
    new = []
    existing = {(int(a), int(b)) for a, b in zip(src, dst)}
    while len(new) <= free0:  # one past capacity forces the grow
        u, v = int(rng.integers(0, g.num_vertices)), int(rng.integers(0, g.num_vertices))
        e = (min(u, v), max(u, v))
        if u != v and e not in existing:
            existing.add(e)
            new.append(e)
    o.apply(EdgeUpdateBatch(insert=np.array(new), delete=np.zeros((0, 2))))
    assert o.slots_per_region > spr0 and o.needs_resync
    s, d = o.snapshot()
    assert s.shape[0] == o.num_edges  # nothing lost in the grow


def test_partial_reorder_improves_objective_and_keeps_graph(ordered):
    g, src, dst = ordered
    o = IncrementalOrderer(src, dst, g.num_vertices, regions=4)
    rng = np.random.default_rng(1)
    # Degrade: random cross-community inserts.
    new = set()
    while len(new) < 60:
        u, v = sorted(rng.integers(0, g.num_vertices, 2).tolist())
        if u != v and (u, v) not in new:
            new.add((u, v))
    o.apply(EdgeUpdateBatch(insert=np.array(sorted(new)), delete=np.zeros((0, 2))))
    before_edges = {(int(a), int(b)) for a, b in zip(*o.snapshot())}
    before_obj = o.region_vertex_sum()
    o.drain_ops()  # isolate the re-order's own ops
    n = o.partial_reorder()
    assert n > 0 and not o.needs_resync  # span rewrite travels as slot ops
    after_edges = {(int(a), int(b)) for a, b in zip(*o.snapshot())}
    assert after_edges == before_edges  # re-order never changes the graph
    assert o.region_vertex_sum() <= before_obj
    # The emitted ops cover exactly the span's slots and carry no degree
    # deltas (a re-order moves edges, it never adds or removes them).
    ops, deg = o.drain_ops()
    assert deg == {}
    spr = o.slots_per_region
    span_regions = {op.slot // spr for op in ops}
    assert len(span_regions) == o.config.span_regions
    assert len(ops) == len(span_regions) * spr


def test_full_rebuild_matches_fresh_geo(ordered):
    g, o = make_orderer(ordered)
    stream = SyntheticStream(g, batch_size=64, seed=5)
    for _ in range(3):
        o.apply(stream.batch())
    o.full_rebuild(seed=0)
    assert o.needs_resync and abs(o.drift() - 1.0) < 1e-9
    s, d = o.snapshot()
    gg = o.graph()
    fresh = ordering.geo_order(gg, seed=0)
    np.testing.assert_array_equal(s, gg.src[fresh])
    np.testing.assert_array_equal(d, gg.dst[fresh])


def test_rf_vs_oracle_margin_under_monitored_stream(ordered):
    g, src, dst = ordered
    o = IncrementalOrderer(src, dst, g.num_vertices, regions=4)
    stream = SyntheticStream(g, batch_size=32, seed=2)
    for _ in range(10):
        o.apply(stream.batch())
        o.maybe_escalate()
        o.needs_resync = False
    inc, oracle = o.rf_vs_oracle(4)
    assert inc <= oracle * o.config.rf_margin + 1e-9


# ---------------------------------------------------- objective property tests
def _check_objective_invariant_under_within_chunk_permutation(seed, k):
    """Eq. (7) at a single k sums per-chunk vertex counts: permuting edges
    WITHIN a chunk must not change it (satellite: ordering_objective
    invariance)."""
    g = rmat_graph(5, 3, seed=seed)
    order = ordering.random_edge_order(g, seed=seed)
    s, d = g.src[order].astype(np.int64), g.dst[order].astype(np.int64)
    base = ordering.ordering_objective(s, d, g.num_edges, g.num_vertices, k, k)
    rng = np.random.default_rng(seed)
    from repro.core import cep

    bounds = cep.chunk_bounds(g.num_edges, k)
    s2, d2 = s.copy(), d.copy()
    for p in range(k):
        lo, hi = int(bounds[p]), int(bounds[p + 1])
        perm = lo + rng.permutation(hi - lo)
        s2[lo:hi], d2[lo:hi] = s2[perm], d2[perm]
    permuted = ordering.ordering_objective(s2, d2, g.num_edges, g.num_vertices, k, k)
    assert permuted == pytest.approx(base, rel=1e-12)


def _check_incremental_placement_never_worse_than_append(seed, k):
    """best_insert_position (the exact oracle of the streaming placement)
    must never pick a position with a worse objective than append-at-end."""
    g = rmat_graph(4, 3, seed=seed)
    order = ordering.geo_order(g, seed=seed)
    s, d = g.src[order].astype(np.int64), g.dst[order].astype(np.int64)
    rng = np.random.default_rng(seed)
    u, v = 0, 0
    while u == v:
        u, v = rng.integers(0, g.num_vertices, 2).tolist()
    pos = best_insert_position(s, d, int(u), int(v), g.num_vertices, k)
    assert 0 <= pos <= s.shape[0]

    def obj_at(p):
        return ordering.ordering_objective(
            np.insert(s, p, min(u, v)), np.insert(d, p, max(u, v)),
            g.num_edges + 1, g.num_vertices, k, k,
        )

    assert obj_at(pos) <= obj_at(s.shape[0]) + 1e-12


@given(seed=st.integers(0, 8), k=st.integers(2, 6))
@settings(max_examples=12, deadline=None)
def test_objective_invariant_under_within_chunk_permutation(seed, k):
    _check_objective_invariant_under_within_chunk_permutation(seed, k)


@given(seed=st.integers(0, 10), k=st.integers(2, 5))
@settings(max_examples=12, deadline=None)
def test_incremental_placement_never_worse_than_append(seed, k):
    _check_incremental_placement_never_worse_than_append(seed, k)


@pytest.mark.parametrize("seed,k", [(0, 2), (1, 3), (2, 4), (5, 6)])
def test_objective_properties_deterministic(seed, k):
    """Deterministic fallback (conftest hypothesis shim skips @given without
    hypothesis): same properties on fixed examples."""
    _check_objective_invariant_under_within_chunk_permutation(seed, k)
    _check_incremental_placement_never_worse_than_append(seed, min(k, 5))


# --------------------------------------- device span repair (ISSUE-5 tentpole)
def _degraded_orderer(seed, regions=4, span_regions=1, delta=None, scale=5):
    """Randomized graph + randomized degradation: the span-repair property
    fixtures. Returns the orderer after cross-community noise inserts."""
    g = rmat_graph(scale, 4, seed=seed)
    order = ordering.geo_order(g, seed=seed)
    cfg = StreamConfig(span_regions=span_regions, delta=delta)
    o = IncrementalOrderer(
        g.src[order].astype(np.int64), g.dst[order].astype(np.int64),
        g.num_vertices, regions=regions, config=cfg,
    )
    rng = np.random.default_rng(seed + 1)
    new = set()
    while len(new) < 25:
        u, v = sorted(rng.integers(0, g.num_vertices, 2).tolist())
        if u != v and (u, v) not in new:
            new.add((u, v))
    o.apply(EdgeUpdateBatch(insert=np.array(sorted(new)), delete=np.zeros((0, 2))))
    o.drain_ops()
    return g, o


def _check_span_repair_never_worse_than_geo(seed, span_regions, delta):
    """Satellite 1: for randomized graphs, spans, and δ windows, the span
    repair's resulting objective is never worse than the host geo_order span
    oracle (geo fed to the candidate selection), never worse than the current
    layout (production identity candidate), and the device program computes
    the byte-identical permutation to the host mirror."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import span_reorder as SRK

    g, o = _degraded_orderer(seed, span_regions=span_regions, delta=delta)
    r0, r1 = o.span_bounds()
    u, v, valid = o.span_arrays(r0, r1)
    assert valid.sum() >= 2
    ks = SRK.eval_ks(o.config.k_min, o.config.k_max)
    ident = SRK.identity_candidate(valid)
    geo = o.geo_span_candidate(u, v, valid)

    def obj(order):
        return SRK.span_objective_host(u, v, valid, order, ks)

    sel_geo, _ = SRK.select_span_order_host(u, v, valid, g.num_vertices, geo, ks)
    assert obj(sel_geo) <= obj(geo)  # never worse than the geo span oracle
    sel_id, _ = SRK.select_span_order_host(u, v, valid, g.num_vertices, ident, ks)
    assert obj(sel_id) <= obj(ident)  # production: never worse than current
    # Differential oracle: the traced program picks the identical permutation.
    dev = np.asarray(
        jax.jit(
            lambda a, b, c, d: SRK.select_span_order_device(
                a, b, c, g.num_vertices, d, ks, use_pallas=True
            )
        )(
            jnp.asarray(u, jnp.int32), jnp.asarray(v, jnp.int32),
            jnp.asarray(valid), jnp.asarray(geo, jnp.int32),
        )
    )
    np.testing.assert_array_equal(dev, sel_geo)


@given(seed=st.integers(0, 12), span=st.integers(1, 3), delta=st.sampled_from([None, 16, 64]))
@settings(max_examples=10, deadline=None)
def test_span_repair_never_worse_than_geo_oracle(seed, span, delta):
    _check_span_repair_never_worse_than_geo(seed, span, delta)


@pytest.mark.parametrize("seed,span,delta", [(0, 1, None), (1, 2, 16), (2, 3, 64), (5, 2, None)])
def test_span_repair_never_worse_deterministic(seed, span, delta):
    """Deterministic fallback (conftest hypothesis shim skips @given without
    hypothesis)."""
    _check_span_repair_never_worse_than_geo(seed, span, delta)


@pytest.mark.parametrize(
    "n,span,spr",
    [
        (5, 2, 8),
        (62_366, 1, 115_968),  # (j - start)·spr passes 2^31 from ~46k edges
        (1_060_001, 1, 1_979_648),
        (3_000_001, 3, 1_979_648),
    ],
)
def test_splice_targets_device_match_host_layout_past_int32_products(n, span, spr):
    """The device splice must land every live edge on the slot the host
    ``_rewrite_span`` computes in int64, at region sizes whose int32
    product would overflow."""
    import jax

    from repro.core import cep
    from repro.kernels import span_reorder as SRK

    cap = span * spr
    got = np.asarray(jax.jit(SRK.splice_targets_device, static_argnums=(1, 2, 3))(
        np.int32(n), span, spr, cap))
    j = np.arange(n, dtype=np.int64)
    p = np.asarray(cep.id2p(n, span, j), dtype=np.int64)
    bounds = np.asarray(cep.chunk_bounds(n, span), dtype=np.int64)
    want = p * spr + ((j - bounds[p]) * spr) // (bounds[p + 1] - bounds[p])
    np.testing.assert_array_equal(got[:n], want)
    assert np.all(got[n:] == cap)


def _force_partial_engine(mode, seed=7, span_regions=2):
    g, o = _degraded_orderer(seed, span_regions=span_regions, scale=6)
    # Thresholds pinned so the monitor fires the partial rung every batch and
    # never escalates to full — the rung under test.
    o.config = StreamConfig(partial_drift=1.0, full_drift=99.0, span_regions=span_regions)
    o._baseline_kappa = o._kappa() / 1.5  # drift == 1.5 > partial, < full
    return g, o, StreamingEngine(o, MM.make_graph_mesh(1), span_repair=mode)


def test_span_repair_oracle_mode_bit_identical_to_host_path():
    """Satellite 1, second clause: in oracle mode the device program applies
    the host geo span order verbatim — buffers byte-identical to the PR-3
    host path on the same stream."""
    packs = {}
    for mode in ("oracle", "host"):
        g, o, eng = _force_partial_engine(mode)
        stream = SyntheticStream(g, batch_size=32, seed=11)
        for _ in range(3):
            eng.ingest(stream.batch(), verify=True)
            assert eng.monitor() == "partial"
            eng.verify_bit_identity()
        packs[mode] = E.unshard_engine_data(eng.data)
    for field in ("edges", "mask", "degrees"):
        np.testing.assert_array_equal(
            np.asarray(getattr(packs["oracle"], field)),
            np.asarray(getattr(packs["host"], field)),
        )


def test_span_repair_device_mode_matches_mirror_over_stream():
    """Production device rung: repairs land on the mesh while the host mirror
    advances the slot array — byte-identical after every event, including
    around a rescale that re-keys the span program."""
    g, o, eng = _force_partial_engine("device")
    stream = SyntheticStream(g, batch_size=32, seed=13)
    for b in range(5):
        if b == 3:
            eng.rescale(6, verify=True)
        eng.ingest(stream.batch(), verify=True)
        assert eng.monitor() == "partial"
        eng.verify_bit_identity()
    assert eng.last_repair == "device"
    assert eng.rung_counts["partial"] == 5 and eng.rung_s["partial"] > 0


def test_span_repair_differential_mode_never_worse_than_geo_end_to_end():
    g, o, eng = _force_partial_engine("differential")
    stream = SyntheticStream(g, batch_size=32, seed=17)
    for _ in range(3):
        eng.ingest(stream.batch(), verify=True)
        assert eng.monitor() == "partial"
        eng.verify_bit_identity()
    assert eng.last_repair == "differential"


def test_span_repair_skips_tiny_spans():
    """A span with <2 live edges must not launch the device program."""
    src = np.array([0, 2], dtype=np.int64)
    dst = np.array([1, 3], dtype=np.int64)
    o = IncrementalOrderer(src, dst, 8, regions=2)
    eng = StreamingEngine(o, MM.make_graph_mesh(1))
    o.apply(EdgeUpdateBatch(insert=np.zeros((0, 2)), delete=np.array([[0, 1]])))
    eng._sync_pending()
    o.drift = lambda: 1.05  # force the partial rung
    assert eng.monitor() == "partial"
    assert eng.last_repair == "skipped"
    eng.verify_bit_identity()


# ------------------------------------------- escalation ladder (satellite 2)
def test_escalation_rung_selection_at_exact_thresholds(ordered):
    """Thresholds are strict: drift exactly at a threshold does not fire."""
    g, o = make_orderer(ordered)
    cfg = o.config
    for drift, want in [
        (1.0, "none"),
        (cfg.partial_drift, "none"),  # exactly at the partial threshold
        (np.nextafter(cfg.partial_drift, 2.0), "partial"),
        (cfg.full_drift, "partial"),  # exactly at the full threshold
        (np.nextafter(cfg.full_drift, 2.0), "full"),
        (cfg.full_drift * 2, "full"),
    ]:
        o.drift = lambda d=drift: d  # instance attr shadows the method
        assert o.escalation() == want, f"drift={drift}"
    del o.drift


def test_maybe_escalate_delegates_partial_rung(ordered):
    g, o = make_orderer(ordered)
    o.drift = lambda: o.config.partial_drift + 0.01
    ran = []
    before = o.slot_src.copy()
    assert o.maybe_escalate(partial_fn=lambda: ran.append(1)) == "partial"
    assert ran == [1]
    np.testing.assert_array_equal(o.slot_src, before)  # delegate owned the work
    del o.drift


def test_partial_cooldown_hysteresis(ordered):
    """A fired partial opens a partial_cooldown window reporting 'none'; the
    full rung ignores the window and resets it."""
    g, o = make_orderer(ordered, partial_cooldown=2)
    o.drift = lambda: o.config.partial_drift + 0.01
    ran = []
    fn = lambda: ran.append(1)
    assert o.maybe_escalate(partial_fn=fn) == "partial"  # fires, opens window
    assert o.maybe_escalate(partial_fn=fn) == "none"  # cooling (2 left)
    assert o.maybe_escalate(partial_fn=fn) == "none"  # cooling (1 left)
    assert o.maybe_escalate(partial_fn=fn) == "partial"  # window closed
    assert len(ran) == 2
    o.drift = lambda: o.config.full_drift + 0.01
    assert o.maybe_escalate(partial_fn=fn) == "full"  # ignores + resets window
    o.drift = lambda: o.config.partial_drift + 0.01
    assert o.maybe_escalate(partial_fn=fn) == "partial"  # no leftover cooldown
    assert len(ran) == 3
    del o.drift


def test_drift_carried_across_relayouts_reset_only_by_full_rebuild(ordered):
    g, o = make_orderer(ordered)
    stream = SyntheticStream(g, batch_size=64, seed=21)
    for _ in range(4):
        o.apply(stream.batch())
    d0 = o.drift()
    assert d0 != 1.0
    o.relayout(6)  # rescale under ingest: drift VALUE carried across k change
    assert o.drift() == pytest.approx(d0, rel=1e-9)
    o.grow()  # slot-array growth: carried too
    assert o.drift() == pytest.approx(d0, rel=1e-9)
    o.full_rebuild()  # only a full rebuild moves the yardstick
    assert o.drift() == pytest.approx(1.0, abs=1e-9)


def _prepared_orderer(ordered, prep):
    """An orderer at k=4 with tombstones and inserts from a stream, then
    ``prep``: ``grow`` re-spreads it wider, ``span_rewrite`` re-orders one
    span in place, ``from_slots`` rebuilds it from its raw slot arrays."""
    g, o = make_orderer(ordered)
    stream = SyntheticStream(g, batch_size=64, seed=17)
    for _ in range(5):
        o.apply(stream.batch())
    if prep == "grow":
        o.grow()
    elif prep == "span_rewrite":
        assert o.partial_reorder() > 0
    elif prep == "from_slots":
        o = IncrementalOrderer.from_slots(
            o.slot_src, o.slot_dst, o.slot_valid, g.num_vertices, regions=o.regions
        )
    assert not o.slot_valid.all()  # holes to skip
    return o


@pytest.mark.parametrize("k_new", [6, 3, 1])
@pytest.mark.parametrize("prep", ["holes", "grow", "span_rewrite", "from_slots"])
def test_relayout_gather_map_matches_edge_dict_reference(ordered, prep, k_new):
    """The gather map ``relayout`` builds from the two slot arrays equals, slot
    for slot, the per-edge construction it replaced: the old slot of each edge
    looked up by its (u, v) key."""
    from repro.obs import trace as OT

    o = _prepared_orderer(ordered, prep)
    old_src, old_dst = o.slot_src.copy(), o.slot_dst.copy()
    old_slot = {e: int(s) for e, s in o._edge2slot.items()}
    o.tracer = OT.Tracer(capacity=256)
    o.relayout(k_new)
    gm = o.drain_gather_map()

    ref = np.full(o.capacity, -1, dtype=np.int64)
    for s in np.flatnonzero(o.slot_valid).tolist():
        ref[s] = old_slot[(int(o.slot_src[s]), int(o.slot_dst[s]))]
    np.testing.assert_array_equal(gm, ref)
    occ = gm >= 0
    np.testing.assert_array_equal(occ, o.slot_valid)
    np.testing.assert_array_equal(old_src[gm[occ]], o.slot_src[occ])
    np.testing.assert_array_equal(old_dst[gm[occ]], o.slot_dst[occ])

    by = {s.name: s for s in o.tracer.spans()}
    assert by["rescale.relayout.gather_map"].counts == {"pairs": o.num_edges}
    assert by["rescale.relayout"].counts["edges"] == o.num_edges


def test_per_rung_counters_and_timings_recorded_on_ingest_events(ordered):
    g, src, dst = ordered
    o = IncrementalOrderer(
        src, dst, g.num_vertices, regions=4,
        config=StreamConfig(partial_drift=1.0, full_drift=99.0, span_regions=2),
    )
    o._baseline_kappa = o._kappa() / 1.5  # every monitor fires 'partial'
    eng = StreamingEngine(o, MM.make_graph_mesh(1))
    ctl = ec.ElasticController(4)
    ctl.attach_stream(eng)
    stream = SyntheticStream(g, batch_size=32, seed=23)
    events = [ctl.ingest(stream.batch()) for _ in range(3)]
    for i, ev in enumerate(events):
        assert ev.escalation == "partial" and ev.repair == "device"
        assert ev.rung_count == i + 1  # cumulative firings of this rung
        assert ev.monitor_s > 0 and ev.rung_total_s > 0
    assert events[-1].rung_total_s >= events[0].rung_total_s
    assert eng.rung_counts == {"none": 0, "partial": 3, "full": 0}
    assert sum(eng.rung_counts.values()) == len(events)


def test_rung_total_s_cumulative_and_consistent_with_engine(ordered):
    """Rung accounting contract (DESIGN.md §13): each IngestEvent's
    rung_total_s is the engine's CUMULATIVE rung_s for that event's rung at
    emit time — monotone per rung, never reset mid-stream — and every
    monitored second lands in exactly one rung (the controller's monitor_s
    envelops the engine's own accounting from just outside the call)."""
    g, src, dst = ordered
    o = IncrementalOrderer(
        src, dst, g.num_vertices, regions=4,
        config=StreamConfig(partial_drift=1.0, full_drift=99.0, span_regions=2),
    )
    o._baseline_kappa = o._kappa() / 1.5  # every monitor fires 'partial'
    eng = StreamingEngine(o, MM.make_graph_mesh(1))
    ctl = ec.ElasticController(4)
    ctl.attach_stream(eng)
    stream = SyntheticStream(g, batch_size=32, seed=29)
    last_total: dict = {}
    for _ in range(5):
        ev = ctl.ingest(stream.batch())
        assert ev.rung_total_s >= last_total.get(ev.escalation, 0.0)
        last_total[ev.escalation] = ev.rung_total_s
        # The emit-time snapshot IS the engine accumulator's current value.
        assert ev.rung_total_s == pytest.approx(eng.rung_s[ev.escalation])
        assert ev.rung_count == eng.rung_counts[ev.escalation]
    events = [e for e in ctl.events if e.kind == "ingest"]
    engine_total = sum(eng.rung_s.values())
    monitor_total = sum(e.monitor_s for e in events)
    assert engine_total <= monitor_total  # enveloped from outside
    assert monitor_total - engine_total < 5e-3 * len(events)  # …by call overhead only


def test_rebuild_s_matches_tracer_rebuild_spans(ordered):
    """IngestEvent.rebuild_s (dispatch_s on the dispatch batch, commit_s on
    the commit batch) must agree with the tracer's rebuild.dispatch /
    rebuild.commit span for that same batch: the span envelops the timed
    inner region, so duration >= rebuild_s and close. Flight batches report
    rebuild_s == 0.0 — the per-monitor reset semantics."""
    from repro.obs import trace as OT

    g, src, dst = ordered
    o = IncrementalOrderer(
        src, dst, g.num_vertices, regions=4,
        config=StreamConfig(partial_drift=1.0, full_drift=1.0),
    )
    o._baseline_kappa = o._kappa() / 1.5  # every unsuppressed monitor: 'full'
    tracer = OT.Tracer(capacity=4096)
    eng = StreamingEngine(
        o, MM.make_graph_mesh(1), full_rebuild="geo", rebuild_flight=1,
        tracer=tracer,
    )
    ctl = ec.ElasticController(4)
    ctl.attach_stream(eng)
    stream = SyntheticStream(g, batch_size=32, seed=31)
    seen = set()
    for _ in range(8):
        n0 = len(tracer)
        ev = ctl.ingest(stream.batch())
        new = tracer.spans()[n0:]
        if ev.rebuild_state in ("dispatch", "commit"):
            spans = [s for s in new if s.name == f"rebuild.{ev.rebuild_state}"]
            assert len(spans) == 1
            assert ev.rebuild_s > 0.0
            assert spans[0].duration_s >= ev.rebuild_s
            assert spans[0].duration_s == pytest.approx(
                ev.rebuild_s, rel=0.5, abs=5e-3
            )
            seen.add(ev.rebuild_state)
        elif ev.rebuild_state == "flight":
            assert ev.rebuild_s == 0.0
            assert not [s for s in new if s.phase == "rebuild"]
    assert seen == {"dispatch", "commit"}
def test_streaming_engine_bit_identity_through_stream_and_rescales(ordered):
    """Small-scale version of the acceptance: ingest batches with two
    interleaved rescales; the sharded pack stays bit-identical to the host
    slot oracle at every step (verify=True raises otherwise)."""
    g, src, dst = ordered
    o = IncrementalOrderer(src, dst, g.num_vertices, regions=4)
    eng = StreamingEngine(o, MM.make_graph_mesh(1))
    stream = SyntheticStream(g, batch_size=32, seed=4)
    for b in range(6):
        if b == 2:
            rs = eng.rescale(6, verify=True)
            assert rs.k_old == 4 and rs.k_new == 6 and rs.moved_edges > 0
        if b == 4:
            rs = eng.rescale(3, verify=True)
            assert rs.k_new == 3
        stats = eng.ingest(stream.batch(), verify=True)
        assert stats.num_edges == o.num_edges
        eng.monitor()
    assert eng.data.k == 3 and eng.data.num_edges == o.num_edges


def test_rescale_flushes_pending_host_ops(ordered):
    """Regression: orderer.apply called directly (outside engine.ingest)
    followed by engine.rescale used to drop the pending slot ops — the gather
    read a stale device buffer against the post-apply host layout."""
    g, src, dst = ordered
    o = IncrementalOrderer(src, dst, g.num_vertices, regions=4)
    eng = StreamingEngine(o, MM.make_graph_mesh(1))
    stream = SyntheticStream(g, batch_size=32, seed=9)
    o.apply(stream.batch())  # host-only: device mirror not yet synced
    eng.rescale(6, verify=True)  # raises on divergence without the flush


def test_orderer_rejects_out_of_range_vertices(ordered):
    g, o = make_orderer(ordered)
    with pytest.raises(ValueError, match="out of range"):
        o.apply(EdgeUpdateBatch(insert=np.array([[-3, 5]]), delete=np.zeros((0, 2))))
    with pytest.raises(ValueError, match="out of range"):
        o.apply(
            EdgeUpdateBatch(insert=np.array([[1, g.num_vertices]]), delete=np.zeros((0, 2)))
        )


def test_streaming_pack_runs_gas_between_ingests(ordered):
    g, src, dst = ordered
    o = IncrementalOrderer(src, dst, g.num_vertices, regions=3)
    eng = StreamingEngine(o, MM.make_graph_mesh(1))
    stream = SyntheticStream(g, batch_size=32, seed=6)
    eng.ingest(stream.batch(), verify=True)
    # Reference: re-pack the orderer's snapshot from scratch.
    s, d = o.snapshot()
    ref = E.pack_ordered(s, d, g.num_vertices, 3)
    np.testing.assert_allclose(
        np.asarray(E.pagerank(eng.data, iterations=10)),
        np.asarray(E.pagerank(ref, MM.make_test_mesh(1, 1), iterations=10)),
        rtol=1e-6, atol=1e-9,
    )
    ds, its = E.sssp(eng.data, source=0)
    dr, itr = E.sssp(ref, MM.make_test_mesh(1, 1), source=0)
    assert its == itr
    np.testing.assert_array_equal(np.asarray(ds), np.asarray(dr))


def test_pack_slots_layout_and_scratch_column(ordered):
    g, src, dst = ordered
    o = IncrementalOrderer(src, dst, g.num_vertices, regions=4)
    data = E.pack_slots(o.slot_src, o.slot_dst, o.slot_valid, 4, g.num_vertices)
    assert data.edges.shape == (4, o.slots_per_region + 1, 2)
    assert np.all(np.asarray(data.mask)[:, -1] == 0)  # scratch col always masked
    assert data.num_edges == o.num_edges and data.mirrors == -1
    # Occupied slots keep their (region, column) coordinates.
    mask = np.asarray(data.mask)[:, :-1].reshape(-1)
    np.testing.assert_array_equal(mask.astype(bool), o.slot_valid)


def test_pack_ordered_slack_rows(ordered):
    g, src, dst = ordered
    tight = E.pack_ordered(src, dst, g.num_vertices, 4)
    slack = E.pack_ordered(src, dst, g.num_vertices, 4, e_max=int(tight.edges.shape[1]) + 7)
    assert slack.edges.shape[1] == tight.edges.shape[1] + 7
    np.testing.assert_array_equal(
        np.asarray(slack.edges)[:, : tight.edges.shape[1]], np.asarray(tight.edges)
    )
    assert np.all(np.asarray(slack.mask)[:, tight.edges.shape[1] :] == 0)
    with pytest.raises(ValueError, match="e_max"):
        E.pack_ordered(src, dst, g.num_vertices, 4, e_max=1)


# --------------------------------------------- vectorized placement (perf)
class _ReferencePlacementOrderer(IncrementalOrderer):
    """The pre-vectorization placement: per-insert occupancy rescans and
    Python-sorted medians. Kept as the decision oracle for the batched
    free-slot cache / np.partition path (ROADMAP follow-up: placement
    decisions must be bit-identical, only faster)."""

    def _median_slot(self, u, v):
        inc = sorted(self._incident.get(u, set()) | self._incident.get(v, set()))
        return inc[len(inc) // 2] if inc else None

    def _free_in(self, region, near=None):
        lo = region * self._spr
        free = np.flatnonzero(~self.slot_valid[lo : lo + self._spr])
        if free.size == 0:
            return None
        if near is None:
            return int(lo + free[0])
        return int(lo + free[np.argmin(np.abs(free + lo - near))])

    def _any_free_slot(self, near):
        free = np.flatnonzero(~self.slot_valid)
        if free.size == 0:
            return None
        if near is None:
            return int(free[0])
        return int(free[np.argmin(np.abs(free - near))])


@pytest.mark.parametrize("seed,delete_frac", [(2, 0.25), (5, 0.4), (9, 0.0)])
def test_vectorized_placement_decisions_unchanged(seed, delete_frac):
    """Stream identical batches (incl. grows and partial re-orders) through
    the vectorized orderer and the reference implementation: every slot
    assignment must be identical — the vectorization may only change speed."""
    g = rmat_graph(7, 6, seed=0)
    order = ordering.geo_order(g, seed=0)
    src, dst = g.src[order].astype(np.int64), g.dst[order].astype(np.int64)
    fast = IncrementalOrderer(src, dst, g.num_vertices, regions=4)
    ref = _ReferencePlacementOrderer(src, dst, g.num_vertices, regions=4)
    s1 = SyntheticStream(g, batch_size=64, delete_frac=delete_frac, seed=seed)
    s2 = SyntheticStream(g, batch_size=64, delete_frac=delete_frac, seed=seed)
    decisions = ("inserted", "deleted", "skipped", "grows", "append_fallbacks")
    for i in range(10):
        c1 = fast.apply(s1.batch())
        c2 = ref.apply(s2.batch())
        # The work counts (incident_entries, free_entries) are the two
        # implementations' own; every decision count must agree.
        assert {k: c1[k] for k in decisions} == {k: c2[k] for k in decisions}
        assert c1["incident_entries"] > 0 and c1["free_entries"] > 0
        if i == 5:  # escalation path rewrites spans in both
            assert fast.partial_reorder(0) == ref.partial_reorder(0)
        np.testing.assert_array_equal(fast.slot_src, ref.slot_src)
        np.testing.assert_array_equal(fast.slot_dst, ref.slot_dst)
        np.testing.assert_array_equal(fast.slot_valid, ref.slot_valid)
    assert fast.slots_per_region == ref.slots_per_region


def test_free_slot_cache_stays_exact(ordered):
    """The incremental free-slot cache must mirror slot_valid exactly after
    any mix of inserts, deletes, span rewrites, and grows."""
    g, src, dst = ordered
    o = IncrementalOrderer(src, dst, g.num_vertices, regions=4)
    stream = SyntheticStream(g, batch_size=48, delete_frac=0.35, seed=12)
    for _ in range(8):
        o.apply(stream.batch())
        o.maybe_escalate()
        o.needs_resync = False
        for r in range(o.regions):
            lo = r * o.slots_per_region
            want = lo + np.flatnonzero(~o.slot_valid[lo : lo + o.slots_per_region])
            np.testing.assert_array_equal(o._free_slots(r), want)
            assert o._free[r] == want.size  # counters agree with the cache


# ------------------------------------------------- interleaving property test
def _check_random_interleaving(seed: int, steps: int = 8):
    """Drive a random interleaving of ingest() and scale events through the
    controller; after EVERY event the sharded pack must equal the host slot
    oracle byte-for-byte and the shared seq must stay strictly monotonic
    across mixed event kinds."""
    g = rmat_graph(6, 4, seed=1)
    order = ordering.geo_order(g, seed=0)
    o = IncrementalOrderer(
        g.src[order].astype(np.int64), g.dst[order].astype(np.int64),
        g.num_vertices, regions=4,
    )
    eng = StreamingEngine(o, MM.make_graph_mesh(1))
    clock = [0.0]
    ctl = ec.ElasticController(4, dead_after_s=5.0, clock=lambda: clock[0])
    ctl.attach_stream(eng)
    stream = SyntheticStream(g, batch_size=24, seed=seed)
    rng = np.random.default_rng(seed)
    events = []
    for _ in range(steps):
        alive = ctl.k
        choices = ["ingest", "ingest", "scale_out"] + (["scale_in"] if alive > 2 else [])
        action = choices[int(rng.integers(0, len(choices)))]
        if action == "ingest":
            events.append(ctl.ingest(stream.batch()))
        elif action == "scale_out":
            events.append(ctl.add_hosts(int(rng.integers(1, 3))))
        else:  # scale_in: one live host goes silent, the rest stay fresh
            victim = max(h for h, st in ctl.hosts.items() if st.alive)
            clock[0] += ctl.dead_after_s + 1.0  # victim's beat is now stale …
            for h, st in ctl.hosts.items():
                if st.alive and h != victim:
                    ctl.heartbeat(h, 1)  # … every other host just beat
            ev = ctl.poll()
            assert ev is not None and ev.kind == "scale_in"
            events.append(ev)
        # Invariant 1: device mirror == host slot oracle after every event.
        eng.verify_bit_identity()
        assert eng.k == ctl.k == o.regions
    # Invariant 2: one strictly monotonic seq across mixed event kinds.
    seqs = [e.seq for e in events]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    assert [e.seq for e in ctl.events] == list(range(len(ctl.events)))
    assert {e.kind for e in events} >= {"ingest"}  # mixed logs really mixed
    return [e.kind for e in events]


@given(seed=st.integers(0, 24))
@settings(max_examples=8, deadline=None)
def test_random_interleaving_matches_oracle_and_seq_monotonic(seed):
    _check_random_interleaving(seed)


@pytest.mark.parametrize("seed", [0, 3, 11, 17])
def test_random_interleaving_deterministic(seed):
    """Deterministic fallback (conftest hypothesis shim skips @given without
    hypothesis): fixed seeds chosen to cover scale_out, scale_in, and ingest
    interleavings."""
    kinds = _check_random_interleaving(seed)
    assert len(kinds) == 8


def test_interleaving_seeds_cover_both_scale_kinds():
    """The fallback seeds must actually exercise both scale directions
    between ingests (otherwise the deterministic variant silently degrades)."""
    kinds = sum((_check_random_interleaving(s) for s in (0, 3, 11, 17)), [])
    assert "scale_out" in kinds and "scale_in" in kinds and "ingest" in kinds


# -------------------------------------------------------------- controller
def test_controller_ingest_and_scale_events_share_seq(ordered):
    g, src, dst = ordered
    o = IncrementalOrderer(src, dst, g.num_vertices, regions=4)
    eng = StreamingEngine(o, MM.make_graph_mesh(1))
    t = [0.0]
    ctl = ec.ElasticController(4, dead_after_s=5.0, clock=lambda: t[0])
    ctl.attach_stream(eng)
    stream = SyntheticStream(g, batch_size=32, seed=8)
    ev0 = ctl.ingest(stream.batch())
    assert ev0.kind == "ingest" and ev0.inserted > 0
    # A preemption mid-stream: scale event executes on the streaming pack.
    t[0] = 1.0
    for h in range(3):
        ctl.heartbeat(h, 1)
    t[0] = 6.0
    ev1 = ctl.poll()
    assert ev1 is not None and ev1.kind == "scale_in" and ev1.executed
    assert eng.k == 3 and eng.data.k == 3
    ev2 = ctl.ingest(stream.batch())
    eng.verify_bit_identity()
    # One shared monotonic seq across kinds → interleaved logs are orderable.
    assert (ev0.seq, ev1.seq, ev2.seq) == (0, 1, 2)
    assert [e.seq for e in ctl.events] == [0, 1, 2]


def test_attached_stream_takes_precedence_over_engine_data(ordered):
    """Regression: with both attach_engine and attach_stream, a scale event
    whose k_new equals the stream's current k must NOT fall through to the
    stale non-streaming pack."""
    g, src, dst = ordered
    o = IncrementalOrderer(src, dst, g.num_vertices, regions=5)
    eng = StreamingEngine(o, MM.make_graph_mesh(1))
    ctl = ec.ElasticController(4)
    ctl.attach_engine(E.pack_ordered(src, dst, g.num_vertices, 4))
    ctl.attach_stream(eng)
    ev = ctl.add_hosts(1)  # k_new = 5 == stream.k: nothing to execute
    assert ev.k_new == 5 and not ev.executed and ctl.rescale_stats == []
    assert ctl.engine_data.k == 4  # stale pack untouched
    np.asarray(ctl.engine_data.edges)  # and not donated away
    ev2 = ctl.add_hosts(1)  # k_new = 6: executes on the STREAM
    assert ev2.executed and eng.k == 6 and ctl.engine_data.k == 4
    assert ctl.rescale_stats[-1].k_new == 6
    eng.verify_bit_identity()


def test_controller_ingest_requires_stream():
    ctl = ec.ElasticController(2)
    with pytest.raises(ValueError, match="attach_stream"):
        ctl.ingest(EdgeUpdateBatch(insert=np.zeros((0, 2)), delete=np.zeros((0, 2))))
