"""Elastic runtime tests: resharder, expert placement, controller, data
pipeline, checkpoint+restore-with-rescale, optimizer."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import cep
from repro.data import pipeline as dp
from repro.elastic import controller as ec
from repro.elastic import expert_place as ep
from repro.elastic import resharder as rs
from repro.elastic.rescale_exec import ElasticRescaler
from repro.program_cache import ProgramCache
from repro.train import optimizer as O


# ------------------------------------------------------------------ resharder
def test_apply_reshard_preserves_data_and_moves_minimum():
    n = 10_000
    rng = np.random.default_rng(0)
    flat = rng.standard_normal(n).astype(np.float32)
    k_old, k_new = 8, 9
    old = [rs.gather_host_shard(flat, k_old, h) for h in range(k_old)]
    new, moved = rs.apply_reshard(old, n, k_old, k_new)
    rebuilt = np.concatenate(new)
    np.testing.assert_array_equal(rebuilt, flat)
    assert moved == cep.migrated_edges_exact(n, k_old, k_new)
    assert moved < n * k_old / (k_old + 1)  # beats hash-based reshuffle


def test_reshard_plan_summary():
    plan = rs.plan_reshard({"w": ((1024, 1024), 4), "b": ((1024,), 4)}, 16, 17)
    s = plan.summary()
    assert 0 < s["moved_frac"] < 0.6
    assert s["moved_frac"] < s["random_frac"]


# ------------------------------------------------------- expert placement
def test_expert_placement_reduces_cross_group_traffic():
    rng = np.random.default_rng(1)
    e = 32
    # Two co-activation communities of 16 experts each.
    stats = rng.random((e, e)) * 0.1
    stats[:16, :16] += 5.0
    stats[16:, 16:] += 5.0
    stats = (stats + stats.T) / 2
    np.fill_diagonal(stats, 0)
    order = ep.order_experts(stats)
    assert sorted(order.tolist()) == list(range(e))
    placed = ep.ExpertPlacement(order, k_groups=2)
    naive = ep.ExpertPlacement(np.arange(e), k_groups=2)
    rng2 = np.random.default_rng(2)
    shuffled = ep.ExpertPlacement(rng2.permutation(e), k_groups=2)
    t_placed = ep.cross_group_traffic(stats, placed)
    t_shuffled = ep.cross_group_traffic(stats, shuffled)
    assert t_placed < 0.7 * t_shuffled
    # Elastic EP resize: O(1) plan, bounded movement.
    placed2, moved = placed.rescale(3)
    assert placed2.k_groups == 3 and 0 < moved <= e


def test_coactivation_graph_from_routing_trace():
    rng = np.random.default_rng(3)
    ids = rng.integers(0, 16, size=(500, 2))
    g = ep.coactivation_graph(ids, 16)
    assert g.num_vertices == 16 and g.num_edges > 0


# ----------------------------------------------------------------- controller
def test_controller_detects_preemption_and_straggler():
    t = [0.0]
    ctl = ec.ElasticController(4, dead_after_s=5.0, straggler_lag_steps=10, clock=lambda: t[0])
    for h in range(4):
        ctl.heartbeat(h, step=100)
    t[0] = 4.0
    for h in range(3):  # host 3 goes silent (spot preemption)
        ctl.heartbeat(h, step=110)
    t[0] = 7.0
    ev = ctl.poll()
    assert ev and ev.kind == "scale_in" and ev.lost_hosts == (3,) and ctl.k == 3
    assert 0 < ev.plan_edges_moved_frac < 1
    # Straggler: host 2 stops progressing.
    for step in (150, 200):
        for h in (0, 1):
            ctl.heartbeat(h, step)
        ctl.heartbeat(2, 111)
        t[0] += 1.0
    ev2 = ctl.poll()
    assert ev2 and ev2.kind == "straggler" and 2 in ev2.lost_hosts
    ev3 = ctl.add_hosts(2)
    assert ev3.kind == "scale_out" and ctl.k == 4
    # Interleaved event logs are ordered by one monotonic seq (frozen events
    # can't rely on wall-clock: the test clock above never moves during polls).
    assert (ev.seq, ev2.seq, ev3.seq) == (0, 1, 2)
    assert [e.seq for e in ctl.events] == [0, 1, 2]


def test_scale_event_seq_is_monotonic_across_controllers_and_kinds():
    t = [0.0]
    ctl = ec.ElasticController(3, dead_after_s=5.0, clock=lambda: t[0])
    events = [ctl.add_hosts(1), ctl.add_hosts(2)]
    t[0] = 1.0
    for h in range(4):
        ctl.heartbeat(h, 1)  # hosts 4, 5 never beat
    t[0] = 6.0
    events.append(ctl.poll())
    assert all(e is not None for e in events)
    seqs = [e.seq for e in events]
    assert seqs == [0, 1, 2] and [e.seq for e in ctl.events] == seqs
    # A fresh controller restarts its own counter (per-log ordering).
    assert ec.ElasticController(2).add_hosts(1).seq == 0


def test_mark_event_rate_gauge_runs_on_injected_clock():
    # Regression: _mark_event used to read time.perf_counter() directly, so
    # the events/s gauge — the autoscaler's rate signal — could not be driven
    # by a fake clock and disagreed with heartbeat/poll liveness timing.
    from repro.obs import metrics as OM

    t = [0.0]
    reg = OM.MetricsRegistry()
    ctl = ec.ElasticController(2, clock=lambda: t[0], metrics_registry=reg)
    gauge = reg.gauge("controller.events_per_s")
    ctl.add_hosts(1)
    assert gauge.value == 0.0  # one event: no inter-event interval yet
    t[0] = 2.0  # exactly 0.5 events/s on the FAKE timeline
    ctl.add_hosts(1)
    assert gauge.value == pytest.approx(0.5)
    t[0] = 2.5  # 2 events/s raw → EMA 0.8*0.5 + 0.2*2.0
    ctl.add_hosts(1)
    assert gauge.value == pytest.approx(0.8 * 0.5 + 0.2 * 2.0)
    # A frozen clock between events leaves the gauge untouched (dt == 0).
    before = gauge.value
    ctl.add_hosts(1)
    assert gauge.value == before


def test_poll_eviction_clamps_at_k_min_floor():
    # Regression: evicting every laggard in one poll could drive k to 0 and
    # emit a scale plan to zero partitions. The floor keeps the most recently
    # beating hosts alive and surfaces the clamp in the event reason.
    t = [0.0]
    ctl = ec.ElasticController(4, dead_after_s=5.0, clock=lambda: t[0], k_min=2)
    ctl.heartbeat(2, step=1)
    t[0] = 1.0
    ctl.heartbeat(3, step=1)  # host 3 beat most recently, then 2, then 0/1
    t[0] = 10.0  # ALL hosts are now past dead_after_s
    ev = ctl.poll()
    assert ev is not None and ev.kind == "scale_in"
    assert ctl.k == 2  # floor held: k never reached 0
    assert set(ev.lost_hosts) == {0, 1}  # stalest evicted, freshest retained
    assert ctl.hosts[2].alive and ctl.hosts[3].alive
    assert "clamped at k_min=2" in ev.reason and "[2, 3]" in ev.reason
    # When the floor retains EVERY candidate there is no event at all.
    ctl2 = ec.ElasticController(1, dead_after_s=5.0, clock=lambda: t[0], k_min=1)
    t[0] += 10.0  # the lone host goes dark — but it IS the floor
    assert ctl2.poll() is None and ctl2.k == 1
    with pytest.raises(ValueError):
        ec.ElasticController(2, k_min=0)


# ---------------------------------------------------------------- ProgramCache
# The LRU is load-bearing for three program families (rescale migration,
# ingest scatter, streaming compact) — unit-test the container itself, not
# just the end-to-end eviction behavior of test_rescale_exec.py.
def test_program_cache_lru_eviction_order():
    c = ProgramCache(2)
    c.put("a", 1)
    c.put("b", 2)
    assert list(c) == ["a", "b"]  # least- to most-recently used
    assert c.get("a") == 1  # hit refreshes recency …
    assert list(c) == ["b", "a"]
    c.put("c", 3)  # … so "b", not "a", is the victim
    assert list(c) == ["a", "c"] and "b" not in c
    assert c.get("b") is None and len(c) == 2


def test_program_cache_capacity_one_thrash():
    c = ProgramCache(1)
    for i in range(5):
        c.put(("k", i), i)
        assert len(c) == 1 and c.get(("k", i)) == i
        if i:
            assert ("k", i - 1) not in c  # every put evicts the previous entry
    # Re-putting the resident key must not evict it.
    c.put(("k", 4), 40)
    assert len(c) == 1 and c.get(("k", 4)) == 40


def test_program_cache_kind_prefixed_keys_do_not_collide():
    """StreamingEngine keys scatter/compact programs by a kind prefix over
    otherwise-identical shape signatures; one cache must hold all kinds and a
    hit on one kind must not serve (or evict) another."""
    c = ProgramCache(3)
    sig = (8, 128, 4)  # same static shape signature for every family
    c.put(("migrate",) + sig, "m")
    c.put(("scatter",) + sig, "s")
    c.put(("compact",) + sig, "c")
    assert len(c) == 3
    assert c.get(("scatter",) + sig) == "s"
    assert c.get(("migrate",) + sig) == "m"
    assert c.get(("compact",) + sig) == "c"
    # Capacity pressure evicts by recency across kinds, not by kind.
    c.put(("migrate",) + (9, 128, 4), "m2")
    assert ("scatter",) + sig not in c  # LRU victim was the scatter entry
    assert c.get(("migrate",) + sig) == "m" and c.get(("compact",) + sig) == "c"


def test_program_cache_resize_has_no_stale_reuse():
    """Changing program_cache_size means a NEW rescaler/cache: programs traced
    under the old capacity must not leak into the new instance, and the new
    capacity is enforced from the first put."""
    src = np.arange(64, dtype=np.int64)
    dst = (src + 1) % 64
    from repro.graphs import engine as E
    from repro.launch import mesh as MM

    mesh = MM.make_graph_mesh(1)
    r1 = ElasticRescaler(program_cache_size=4)
    for k_new in (5, 6, 7):
        r1.rescale(E.pack_ordered_sharded(src, dst, 64, 4, mesh), k_new)
    assert len(r1._programs) == 3 and r1.program_cache_size == 4

    r2 = ElasticRescaler(program_cache_size=1)
    assert len(r2._programs) == 0  # nothing carried over from r1
    d2, _ = r2.rescale(E.pack_ordered_sharded(src, dst, 64, 4, mesh), 5)
    r2.rescale(d2, 6)
    assert len(r2._programs) == 1  # new capacity enforced immediately
    assert list(r2._programs)[0][2:4] == (5, 6)  # only the latest program kept
    assert len(r1._programs) == 3  # and the old instance is untouched


def test_program_cache_span_repair_kind_coexists_and_rekeys():
    """ISSUE-5 satellite: the span-repair programs live in the SAME bounded
    LRU as the streaming engine's scatter/compact programs under a kind
    prefix, and changes to span length, k, or e_max each produce a fresh key
    (no stale program reuse)."""
    from repro.core import ordering
    from repro.core.graph import rmat_graph
    from repro.launch import mesh as MM
    from repro.stream import IncrementalOrderer, StreamingEngine, SyntheticStream
    from repro.stream.incremental import StreamConfig

    g = rmat_graph(6, 4, seed=2)
    order = ordering.geo_order(g, seed=0)
    o = IncrementalOrderer(
        g.src[order].astype(np.int64), g.dst[order].astype(np.int64),
        g.num_vertices, regions=4,
        config=StreamConfig(partial_drift=1.0, full_drift=99.0, span_regions=2),
    )
    o._baseline_kappa = o._kappa() / 1.5  # monitor always fires 'partial'
    eng = StreamingEngine(o, MM.make_graph_mesh(1), program_cache_size=16)
    stream = SyntheticStream(g, batch_size=24, seed=3)

    def span_keys():
        return [k for k in eng._programs if k[0] == "span_repair"]

    eng.ingest(stream.batch(), verify=True)  # scatter program
    eng.monitor()  # span program #1 (k=4, e_cap_0, s=2)
    assert {k[0] for k in eng._programs} == {"scatter", "span_repair"}
    k1 = span_keys()[-1]
    eng.monitor()
    assert len(span_keys()) == 1  # same signature → cache hit, no retrace
    eng.rescale(6, verify=True)  # compact program; k and e_cap both change
    eng.monitor()
    assert {k[0] for k in eng._programs} == {"scatter", "span_repair", "compact"}
    k2 = span_keys()[-1]
    assert k2 != k1 and k2[2] == 6 and k1[2] == 4  # k re-keys
    o.grow()  # e_max changes at the same k
    eng._resync()
    eng.monitor()
    k3 = span_keys()[-1]
    assert k3 != k2 and k3[4] > k2[4]  # e_cap re-keys
    # Span length re-keys: a 1-region span at the same k / e_cap.
    o.config = StreamConfig(partial_drift=1.0, full_drift=99.0, span_regions=1)
    eng.monitor()
    k4 = span_keys()[-1]
    assert k4 != k3 and k4[5] == 1 and k3[5] == 2
    eng.verify_bit_identity()  # none of the re-keyed programs went stale
    assert len(span_keys()) == 4  # all four coexist in the one LRU


def test_program_cache_hits_shared_across_rescale_kinds():
    """One ElasticRescaler instance serves repeated oscillation between
    configurations from cache: the second pass over the same (k_old, k_new)
    pairs must trace nothing new."""
    src = np.arange(60, dtype=np.int64)
    dst = (src + 7) % 60
    from repro.graphs import engine as E
    from repro.launch import mesh as MM

    mesh = MM.make_graph_mesh(1)
    r = ElasticRescaler(program_cache_size=8)
    for _ in range(2):  # second lap = pure cache hits
        d = E.pack_ordered_sharded(src, dst, 60, 4, mesh)
        d, _ = r.rescale(d, 6)
        d, _ = r.rescale(d, 4)
    assert len(r._programs) == 2  # (4→6) and (6→4), each traced exactly once


def test_program_cache_counters_per_kind():
    """ISSUE-6 satellite: the cache counts hits/misses/evictions PER KIND so
    event logs can prove an escalation never paid a compile. get-miss then
    put then get-hit is the compile-once discipline; misses == compiles."""
    c = ProgramCache(2)
    key_a, key_b = ("scatter", 8, 64), ("span_repair", 8, 64)
    assert c.get(key_a) is None  # miss counted
    c.put(key_a, "a")
    assert c.get(key_a) == "a"  # hit counted
    assert c.get(key_b) is None
    c.put(key_b, "b")
    assert c.counters_snapshot() == {
        "scatter": {"hits": 1, "misses": 1, "evictions": 0},
        "span_repair": {"hits": 0, "misses": 1, "evictions": 0},
    }
    # Eviction is billed to the VICTIM's kind, at put time.
    c.put(("splice", 8, 64), "s")  # evicts key_a (LRU: b was put after a's hit)
    snap = c.counters_snapshot()
    assert snap["scatter"]["evictions"] == 1
    assert snap["span_repair"]["evictions"] == 0
    assert "splice" not in snap  # put counts nothing for its own kind


def test_program_cache_touch_counts_hit_only_when_present():
    """touch refreshes recency and counts a hit IF present; an absent key
    counts NOTHING — the warm-up probe must not inflate the miss count the
    builder's own get-miss is about to record (misses == compiles)."""
    c = ProgramCache(2)
    key = ("full_reorder", 4, 128)
    assert c.touch(key) is False
    assert c.counters_snapshot() == {}  # absent touch left no trace
    c.put(key, "p")
    assert c.touch(key) is True
    assert c.counters_snapshot() == {"full_reorder": {"hits": 1, "misses": 0, "evictions": 0}}
    # touch refreshes recency like get: the untouched entry is the victim.
    c.put(("other", 1), "q")
    c.touch(key)
    c.put(("third", 2), "r")
    assert key in c and ("other", 1) not in c


def test_program_cache_counters_snapshot_is_isolated():
    """Snapshots attached to events must not alias the live counters."""
    c = ProgramCache(2)
    c.get(("scatter", 1))  # miss
    snap = c.counters_snapshot()
    c.put(("scatter", 1), "x")
    c.get(("scatter", 1))  # hit after snapshot
    assert snap == {"scatter": {"hits": 0, "misses": 1, "evictions": 0}}
    snap["scatter"]["misses"] = 99  # mutating the snapshot …
    assert c.counters["scatter"]["misses"] == 1  # … never reaches the cache


def test_program_cache_counters_snapshot_is_lazy_copy_on_write():
    """Observability satellite: snapshotting costs a flag, not a deep copy —
    the live mapping is handed out as-is and only CLONED by the cache's next
    counter mutation, so every IngestEvent's snapshot stays frozen at its
    emit-time values while back-to-back snapshots (no cache activity between
    events) share one dict."""
    c = ProgramCache(4)
    c.get(("scatter", 1))  # miss
    s1 = c.counters_snapshot()
    s2 = c.counters_snapshot()
    assert s1 is s2  # idle cache: zero copies between events
    c.put(("scatter", 1), "x")
    c.get(("scatter", 1))  # hit → clone-before-mutate detaches s1/s2
    s3 = c.counters_snapshot()
    assert s3 is not s1
    assert s1 == {"scatter": {"hits": 0, "misses": 1, "evictions": 0}}
    assert s3["scatter"] == {"hits": 1, "misses": 1, "evictions": 0}
    # A new kind appearing later never leaks into earlier snapshots.
    c.get(("splice", 2))  # miss on a fresh kind
    s4 = c.counters_snapshot()
    assert s4 is not s3 and "splice" in s4 and "splice" not in s3


# ------------------------------------------------------------------- data
def test_data_pipeline_deterministic_and_elastic():
    dc = dp.DataConfig(vocab_size=1000, seq_len=16, global_batch=64)
    gb = dp.global_batch(dc, step=7)
    assert gb["tokens"].shape == (64, 16)
    # Union of host shards == global batch, for any k.
    for k in (4, 5):
        rows = [dp.host_batch(dc, 7, k, h) for h in range(k)]
        got = np.concatenate([r["tokens"] for r in rows])
        np.testing.assert_array_equal(got, gb["tokens"])
    # Rescale plan touches < half the samples for +1 host.
    plan = dp.rescale_moves(dc, 4, 5)
    assert plan.migrated_edges <= 64 * 0.6


# ------------------------------------------------------------------ optimizer
def test_adamw_reduces_quadratic_loss():
    params = {"w": jnp.asarray([2.0, -3.0, 1.5])}
    opt = O.OptConfig(peak_lr=0.1, warmup_steps=5, total_steps=200, weight_decay=0.0)
    state = O.init_opt_state(params)

    def loss_fn(p):
        return jnp.sum(p["w"] ** 2)

    p = params
    for _ in range(150):
        g = jax.grad(loss_fn)(p)
        p, state, _ = O.adamw_update(p, g, state, opt)
    assert float(loss_fn(p)) < 1e-2


def test_lr_schedule_shape():
    opt = O.OptConfig(peak_lr=1.0, warmup_steps=10, total_steps=100, min_lr_frac=0.1)
    assert float(O.lr_schedule(opt, 0)) < 0.2
    assert float(O.lr_schedule(opt, 10)) == pytest.approx(1.0, rel=0.05)
    assert float(O.lr_schedule(opt, 99)) == pytest.approx(0.1, rel=0.15)


# ------------------------------------------------------------------ checkpoint
def test_checkpoint_save_restore_roundtrip(tmp_path):
    from repro.checkpoint import store

    tree = {
        "a": jnp.arange(37, dtype=jnp.float32).reshape(37),
        "nested": {"b": jnp.ones((5, 7), jnp.float32) * 3},
    }
    store.save(tree, tmp_path, step=3, k_shards=4)
    restored, bytes_touched = store.restore(tmp_path, 3, k_new=5, template=tree)
    np.testing.assert_array_equal(np.asarray(restored["a"]), np.asarray(tree["a"]))
    np.testing.assert_array_equal(np.asarray(restored["nested"]["b"]), np.asarray(tree["nested"]["b"]))
    assert bytes_touched > 0  # rescale 4→5 must account moved bytes
