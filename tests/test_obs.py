"""Observability layer unit tests (DESIGN.md §13): span tracer + ring
semantics, Chrome-trace export/merge/validation, metrics registry
(histogram exactness, bucket fallback, snapshot flattening), structured
event-log JSONL round-trips, and the peak-RSS gauge convention."""
import json

import numpy as np
import pytest

from repro.obs import log as OL
from repro.obs import metrics as OM
from repro.obs import trace as OT
from repro.obs import trace_export as OX


# ------------------------------------------------------------------ tracer
class TestTracer:
    def test_disabled_records_nothing_and_shares_null_span(self):
        t = OT.Tracer(capacity=16, enabled=False)
        s1 = t.span("ingest.batch")
        s2 = t.span("rung.monitor")
        assert s1 is s2  # the shared no-op CM — no per-call allocation
        with s1:
            pass
        assert t.recorded == 0 and len(t) == 0

    def test_span_records_name_phase_duration(self):
        t = OT.Tracer(capacity=16)
        with t.span("ingest.scatter"):
            pass
        with t.span("custom", phase="special"):
            pass
        spans = t.spans()
        assert [s.name for s in spans] == ["ingest.scatter", "custom"]
        # Phase defaults to the dotted prefix; explicit phase wins.
        assert [s.phase for s in spans] == ["ingest", "special"]
        assert all(s.t1 >= s.t0 and s.duration_s >= 0.0 for s in spans)

    def test_nesting_orders_by_exit(self):
        t = OT.Tracer(capacity=16)
        with t.span("outer.a"):
            with t.span("outer.b"):
                pass
        names = [s.name for s in t.spans()]
        assert names == ["outer.b", "outer.a"]  # inner exits (records) first
        inner, outer = t.spans()
        assert outer.t0 <= inner.t0 and inner.t1 <= outer.t1

    def test_ring_bounds_and_dropped_counter(self):
        t = OT.Tracer(capacity=4)
        for i in range(10):
            with t.span(f"x.{i}"):
                pass
        assert t.recorded == 10 and len(t) == 4 and t.dropped == 6
        assert [s.name for s in t.spans()] == [f"x.{i}" for i in range(6, 10)]
        t.clear()
        assert t.recorded == 0 and t.dropped == 0 and not t.spans()

    def test_span_survives_exceptions(self):
        t = OT.Tracer(capacity=4)
        with pytest.raises(RuntimeError):
            with t.span("ingest.batch"):
                raise RuntimeError("boom")
        assert [s.name for s in t.spans()] == ["ingest.batch"]

    def test_global_default_disabled_and_settable(self):
        assert OT.get_tracer().enabled is False
        t = OT.Tracer(capacity=8)
        try:
            assert OT.set_tracer(t) is t and OT.get_tracer() is t
            with OT.span("transfer.put_global"):
                pass
            assert [s.name for s in t.spans()] == ["transfer.put_global"]
        finally:
            OT.set_tracer(None)
        assert OT.get_tracer().enabled is False
        with OT.span("transfer.put_global"):
            pass  # no-op again
        assert OT.get_tracer().recorded == 0

    def test_annotate_enters_profiler_annotation(self):
        # The span records whether or not a profiler capture is running.
        t = OT.Tracer(capacity=4, annotate=True)
        with t.span("rebuild.dispatch"):
            pass
        assert t.recorded == 1

    def test_parent_ids_follow_the_nesting(self):
        t = OT.Tracer(capacity=16)
        with t.span("rescale.event"):
            with t.span("rescale.relayout"):
                with t.span("rescale.relayout.layout"):
                    pass
            with t.span("rescale.compact"):
                pass
        with t.span("ingest.batch"):
            pass
        by = {s.name: s for s in t.spans()}
        assert len({s.id for s in by.values()}) == 5
        assert by["rescale.event"].parent == -1 and by["ingest.batch"].parent == -1
        assert by["rescale.relayout"].parent == by["rescale.event"].id
        assert by["rescale.compact"].parent == by["rescale.event"].id
        assert by["rescale.relayout.layout"].parent == by["rescale.relayout"].id

    def test_counts_land_on_the_innermost_open_span(self):
        t = OT.Tracer(capacity=16)
        with t.span("ingest.batch") as outer:
            t.count(batches=1)
            with t.span("ingest.apply") as inner:
                t.count(inserts=3)
                inner.count(inserts=2, deletes=1)
            outer.count(batches=1)
        t.count(dropped=1)  # no span open: nowhere to land
        by = {s.name: s for s in t.spans()}
        assert by["ingest.apply"].counts == {"inserts": 5, "deletes": 1}
        assert by["ingest.batch"].counts == {"batches": 2}
        with t.span("rung.monitor"):
            pass
        assert t.spans()[-1].counts is None  # a span with no counts keeps none

    def test_disabled_counts_record_and_allocate_nothing(self):
        import tracemalloc

        t = OT.Tracer(capacity=16, enabled=False)
        sp = t.span("ingest.apply")
        assert sp is OT._NULL_SPAN and not hasattr(sp, "__dict__")
        for _ in range(10):  # let the interpreter settle its own caches
            with t.span("ingest.apply") as sp:
                sp.count(inserts=1)
            t.count(inserts=1)
        tracemalloc.start()
        try:
            before = tracemalloc.get_traced_memory()[0]
            for _ in range(10_000):
                with t.span("ingest.apply") as sp:
                    sp.count(inserts=1, deletes=2)
                t.count(inserts=1)
            held = tracemalloc.get_traced_memory()[0] - before
        finally:
            tracemalloc.stop()
        assert held < 1024  # nothing retained per call
        assert t.recorded == 0 and len(t) == 0 and not t._open

    def test_self_time_is_duration_minus_children(self):
        rec = [
            OT.SpanRecord("rescale.relayout.layout", "rescale", 1.0, 3.0, id=2, parent=1),
            OT.SpanRecord("rescale.relayout", "rescale", 0.5, 4.0, id=1, parent=0),
            OT.SpanRecord("rescale.compact", "rescale", 4.0, 4.5, id=3, parent=0),
            OT.SpanRecord("rescale.event", "rescale", 0.0, 5.0, id=0, parent=-1),
        ]
        st = OT.self_times(rec)
        assert st == pytest.approx({0: 5.0 - 3.5 - 0.5, 1: 3.5 - 2.0, 2: 2.0, 3: 0.5})
        # On a live tracer: the parent's self time plus its child's is its span.
        t = OT.Tracer(capacity=8)
        with t.span("a.outer"):
            with t.span("a.inner"):
                pass
        inner, outer = t.spans()
        st = OT.self_times(t.spans())
        assert st[inner.id] == pytest.approx(inner.duration_s)
        assert st[outer.id] + inner.duration_s == pytest.approx(outer.duration_s)

    def test_a_compile_is_credited_to_the_span_it_happened_in(self):
        import jax
        import jax.numpy as jnp

        t = OT.Tracer(capacity=8)
        x = jnp.arange(7.0)

        def credited_compile_probe(v):
            return jnp.cumsum(v * 3.0) - 1.0

        f = jax.jit(credited_compile_probe)
        with t.span("rescale.warm"):
            with t.span("rescale.warm.span"):
                f(x).block_until_ready()
            with t.span("rescale.warm.full"):
                f(x).block_until_ready()  # cached: no compile
        by = {s.name: s for s in t.spans()}
        c = by["rescale.warm.span"].counts
        assert c["compiles"] + c.get("cache_loads", 0) == 1
        assert c.get("compile_s", 0.0) >= 0.0
        assert by["rescale.warm.full"].counts is None
        assert by["rescale.warm"].counts is None  # not credited to the parent
        # A disabled tracer, alone, is credited nothing.
        off = OT.Tracer(capacity=8, enabled=False)
        with off.span("rescale.warm"):
            jax.jit(lambda v: v * 5.0 + 2.0)(x).block_until_ready()
        assert off.recorded == 0


# ------------------------------------------------------------ trace export
def _traced(n=3, process=0):
    t = OT.Tracer(capacity=64)
    for i in range(n):
        with t.span(f"ingest.batch{i}"):
            pass
        with t.span("rung.monitor"):
            pass
    return OX.chrome_trace(t, process=process, process_name=f"proc{process}")


class TestChromeTrace:
    def test_export_structure(self):
        tr = _traced(n=2)
        assert OX.validate_chrome_trace(tr) == []
        events = tr["traceEvents"]
        meta = [e for e in events if e["ph"] == "M"]
        xs = [e for e in events if e["ph"] == "X"]
        assert len(xs) == 4
        # One process_name + one thread_name per phase track.
        names = {e["name"] for e in meta}
        assert names == {"process_name", "thread_name"}
        tracks = {e["args"]["name"] for e in meta if e["name"] == "thread_name"}
        assert tracks == {"ingest", "rung"}
        # Phase == cat == its track's thread_name; tids are per-phase.
        tids = {e["cat"]: e["tid"] for e in xs}
        assert len(tids) == 2
        assert all(isinstance(e["ts"], float) and e["dur"] >= 0.0 for e in xs)

    def test_merge_rebases_and_keeps_pids(self):
        merged = OX.merge_traces([_traced(process=0), _traced(process=1)])
        assert OX.validate_chrome_trace(merged) == []
        xs = [e for e in merged["traceEvents"] if e["ph"] == "X"]
        assert {e["pid"] for e in xs} == {0, 1}
        assert min(e["ts"] for e in xs) == 0.0
        assert merged["otherData"]["p0.spans_recorded"] == 6
        assert merged["otherData"]["p1.spans_recorded"] == 6

    def test_export_carries_counts_as_args(self):
        t = OT.Tracer(capacity=8)
        with t.span("ingest.apply") as sp:
            sp.count(inserts=3, free_entries=40)
        with t.span("ingest.ready"):
            pass
        tr = OX.chrome_trace(t)
        assert OX.validate_chrome_trace(tr) == []
        xs = {e["name"]: e for e in tr["traceEvents"] if e["ph"] == "X"}
        assert xs["ingest.apply"]["args"] == {"inserts": 3, "free_entries": 40}
        assert "args" not in xs["ingest.ready"]
        assert json.loads(json.dumps(tr)) == tr

    def test_write_is_plain_json(self, tmp_path):
        p = tmp_path / "trace.json"
        OX.write_chrome_trace(str(p), _traced())
        assert OX.validate_chrome_trace(json.loads(p.read_text())) == []

    def test_validate_rejects_malformed(self):
        assert OX.validate_chrome_trace([]) == ["trace is not a JSON object"]
        assert OX.validate_chrome_trace({"traceEvents": []}) == [
            "traceEvents missing or empty"
        ]
        bad = {"traceEvents": [{"ph": "X", "name": "a", "pid": 0, "tid": 0,
                                "ts": 0.0, "dur": -1.0}]}
        assert any("negative dur" in p for p in OX.validate_chrome_trace(bad))
        meta_only = {"traceEvents": [{"ph": "M", "name": "process_name",
                                      "pid": 0, "tid": 0}]}
        assert OX.validate_chrome_trace(meta_only) == ["no complete ('X') span events"]


# ----------------------------------------------------------------- metrics
class TestMetrics:
    def test_counter_gauge(self):
        r = OM.MetricsRegistry()
        c = r.counter("stream.updates")
        c.inc()
        c.inc(4)
        g = r.gauge("queue.depth")
        g.set(3)
        g.set(7)
        snap = r.snapshot()
        assert snap["stream.updates"] == 5.0 and snap["queue.depth"] == 7.0
        # get-or-create returns the SAME object; kind mismatch raises.
        assert r.counter("stream.updates") is c
        with pytest.raises(TypeError):
            r.gauge("stream.updates")

    def test_histogram_exact_percentiles(self):
        h = OM.Histogram()
        vals = [0.001 * (i + 1) for i in range(100)]
        for v in vals:
            h.observe(v)
        assert h.exact
        assert h.percentile(50) == pytest.approx(np.percentile(vals, 50))
        assert h.percentile(99) == pytest.approx(np.percentile(vals, 99))
        assert h.total == 100 and h.sum == pytest.approx(sum(vals))

    def test_histogram_bucket_fallback_is_conservative(self):
        h = OM.Histogram(sample_cap=8)
        vals = [0.001 * (i + 1) for i in range(64)]
        for v in vals:
            h.observe(v)
        assert not h.exact
        # Bucket upper bound: never understates the true percentile.
        for q in (50, 90, 99):
            assert h.percentile(q) >= np.percentile(vals, q) * 0.999

    def test_histogram_overflow_bucket_answers_max_sample(self):
        h = OM.Histogram(bounds=(0.1, 1.0), sample_cap=4)
        for v in (5.0, 6.0, 7.0, 8.0, 9.0, 10.0):
            h.observe(v)  # all in the unbounded overflow bucket
        assert h.percentile(99) == 10.0

    def test_snapshot_flattens_histograms_summably(self):
        r = OM.MetricsRegistry()
        h = r.histogram("lat", bounds=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(5.0)
        snap = r.snapshot()
        assert snap["lat.count"] == 3.0 and snap["lat.sum"] == pytest.approx(5.55)
        np.testing.assert_array_equal(snap["lat.buckets"], [1.0, 1.0, 1.0])
        # Sum of two processes' snapshots == snapshot of the merged stream —
        # the invariant snapshot_global's psum relies on.
        r2 = OM.MetricsRegistry()
        h2 = r2.histogram("lat", bounds=(0.1, 1.0))
        h2.observe(0.2)
        snap2 = r2.snapshot()
        total = snap["lat.buckets"] + snap2["lat.buckets"]
        np.testing.assert_array_equal(total, [1.0, 2.0, 1.0])

    def test_snapshot_global_single_process_identity(self):
        from repro.launch import mesh as MM

        r = OM.MetricsRegistry()
        r.counter("a").inc(3)
        r.histogram("b", bounds=(1.0,)).observe(0.5)
        g = r.snapshot_global(MM.make_graph_mesh(1))
        local = r.snapshot()
        assert g["a"] == local["a"] == 3.0
        assert g["b.count"] == 1.0
        np.testing.assert_array_equal(
            np.asarray(g["b.buckets"]), local["b.buckets"]
        )

    def test_null_registry_inert_and_allocation_free(self):
        n = OM.NULL
        m = n.counter("x")
        assert m is n.gauge("y") is n.histogram("z")
        m.inc()
        m.set(5)
        m.observe(1.0)
        assert n.snapshot() == {} and n.names() == []
        assert n.percentiles("z") == {"p50": 0.0, "p90": 0.0, "p99": 0.0}

    def test_record_peak_rss_process_indexed_gauges(self):
        r = OM.MetricsRegistry()
        mb = OM.record_peak_rss(r, process_index=1, process_count=3)
        assert mb > 0.0
        snap = r.snapshot()
        assert snap["process.peak_rss_mb.p1"] == pytest.approx(mb)
        assert snap["process.peak_rss_mb.p0"] == 0.0
        assert snap["process.peak_rss_mb.p2"] == 0.0


# ------------------------------------------------------------- event JSONL
def _controller_with_events():
    from repro.elastic import controller as ec
    from repro.stream import IncrementalOrderer, StreamingEngine, SyntheticStream
    from repro.core.graph import rmat_graph
    from repro.core import ordering
    from repro.launch import mesh as MM

    g = rmat_graph(7, 6, seed=0)
    order = ordering.geo_order(g, seed=0)
    orderer = IncrementalOrderer(
        g.src[order].astype(np.int64), g.dst[order].astype(np.int64),
        g.num_vertices, regions=4,
    )
    engine = StreamingEngine(orderer, MM.make_graph_mesh(1))
    ctl = ec.ElasticController(4, clock=lambda: 0.0)
    ctl.attach_stream(engine)
    stream = SyntheticStream(g, batch_size=16, seed=1)
    for _ in range(3):
        ctl.ingest(stream.batch())
    ctl.add_hosts(2)  # a ScaleEvent between IngestEvents
    ctl.ingest(stream.batch())
    return ctl


class TestEventsJsonl:
    def test_round_trip_preserves_order_and_fields(self):
        ctl = _controller_with_events()
        text = ctl.events_jsonl()
        back = OL.events_from_jsonl(text)
        assert back == list(ctl.events)  # frozen dataclasses: field equality
        kinds = [type(e).__name__ for e in back]
        assert "ScaleEvent" in kinds and "IngestEvent" in kinds
        seqs = [e.seq for e in back]
        assert seqs == sorted(seqs)

    def test_drop_timings_zeroes_only_wall_fields(self):
        ctl = _controller_with_events()
        for line in ctl.events_jsonl(drop_timings=True).splitlines():
            d = json.loads(line)
            for k, v in d.items():
                if k.endswith("_s") and isinstance(v, float):
                    assert v == 0.0, f"{d['event']}.{k} not zeroed"
        # Non-timing content survives intact.
        back = OL.events_from_jsonl(ctl.events_jsonl(drop_timings=True))
        assert [e.seq for e in back] == [e.seq for e in ctl.events]
        assert [getattr(e, "kind", None) for e in back] == [
            getattr(e, "kind", None) for e in ctl.events
        ]

    def test_unknown_event_type_rejected(self):
        with pytest.raises(ValueError, match="unknown event type"):
            OL.event_from_dict({"event": "MysteryEvent"})


# ------------------------------------------------- span trees of the runtime
def _traced_stream(tracer):
    from repro.core import ordering
    from repro.core.graph import rmat_graph
    from repro.elastic import controller as ec
    from repro.launch import mesh as MM
    from repro.stream import IncrementalOrderer, StreamingEngine, SyntheticStream

    g = rmat_graph(7, 6, seed=0)
    order = ordering.geo_order(g, seed=0)
    orderer = IncrementalOrderer(
        g.src[order].astype(np.int64), g.dst[order].astype(np.int64),
        g.num_vertices, regions=4,
    )
    clock = [0.0]
    engine = StreamingEngine(orderer, MM.make_graph_mesh(1), tracer=tracer)
    ctl = ec.ElasticController(4, clock=lambda: clock[0], tracer=tracer)
    ctl.attach_stream(engine)
    return ctl, engine, SyntheticStream(g, batch_size=32, seed=3), clock


RESCALE_TREE = {
    "rescale.event": ["rescale.sync", "rescale.relayout", "rescale.gather_plan",
                      "rescale.compact", "rescale.warm", "rescale.ready"],
    "rescale.relayout": ["rescale.relayout.snapshot", "rescale.relayout.layout",
                         "rescale.relayout.gather_map"],
    "rescale.relayout.layout": [f"rescale.relayout.layout.{s}" for s in
                                ("fill", "edge_map", "region_counts", "incident")],
    "rescale.warm": ["rescale.warm.span", "rescale.warm.full", "rescale.warm.scatter"],
}


def _children(spans, parent) -> list:
    return [s.name for s in sorted(spans, key=lambda s: s.t0) if s.parent == parent.id]


def _misses(counters) -> dict:
    return {kind: c["misses"] for kind, c in counters.items()}


class TestSpanTrees:
    def test_each_scale_event_yields_the_rescale_tree(self):
        t = OT.Tracer(capacity=4096)
        ctl, engine, _, clock = _traced_stream(t)
        for event in ("add_hosts", "poll"):
            t.clear()
            before = _misses(dict(engine.program_cache_counters()))
            if event == "add_hosts":
                ev = ctl.add_hosts(2)
            else:
                clock[0] += ctl.dead_after_s + 1.0
                for h in sorted(ctl.hosts)[:4]:
                    ctl.heartbeat(h, 1)  # the two added hosts go quiet
                ev = ctl.poll()
            assert ev is not None and ev.executed
            spans = t.spans()
            roots = [s for s in spans if s.parent == -1]
            assert [s.name for s in roots] == ["rescale.event"]
            assert roots[0].counts == {"k_old": ev.k_old, "k_new": ev.k_new}
            by = {s.name: s for s in spans}
            for parent, kids in RESCALE_TREE.items():
                assert _children(spans, by[parent]) == kids, parent
            rel = by["rescale.relayout"].counts
            assert rel == {"edges": engine.orderer.num_edges,
                           "slots": engine.orderer.capacity}
            # Each warm step counts its program-cache misses: together they
            # are the event's misses outside the compact program's own.
            after = _misses(engine.program_cache_counters())
            delta = {k: after[k] - before.get(k, 0) for k in after}
            warm = sum(by[f"rescale.warm.{c}"].counts["cache_misses"]
                       for c in ("span", "full", "scatter"))
            assert warm == sum(v for k, v in delta.items() if k != "compact")
            # k 4 -> 6 is a new program signature; 6 -> 4 was warmed at start.
            assert (warm > 0) == (event == "add_hosts")
            # Every compile of the event is credited inside rescale.event.
            compiled = sum((s.counts or {}).get("compiles", 0)
                           + (s.counts or {}).get("cache_loads", 0) for s in spans)
            assert compiled >= warm

    def test_an_ingest_batch_yields_the_ingest_tree(self):
        t = OT.Tracer(capacity=4096)
        ctl, engine, stream, _ = _traced_stream(t)
        t.clear()
        stats = engine.ingest(stream.batch())
        spans = t.spans()
        by = {s.name: s for s in spans}
        assert [s.name for s in spans if s.parent == -1] == ["ingest.batch"]
        assert _children(spans, by["ingest.batch"]) == ["ingest.apply", "ingest.scatter",
                                                        "ingest.ready"]
        assert _children(spans, by["ingest.apply"]) == ["ingest.apply.delete",
                                                        "ingest.apply.insert"]
        c = by["ingest.apply"].counts
        assert (c["inserts"], c["deletes"], c["skipped"]) == (
            stats.inserted, stats.deleted, stats.skipped)
        assert c["incident_entries"] > 0 and c["free_entries"] > 0
        assert c["grows"] == 0 and c["append_fallbacks"] >= 0
        sc = by["ingest.scatter"].counts
        assert sc["ops"] == stats.scatter_ops and sc["cap"] >= sc["ops"]
        assert sc["cap"] & (sc["cap"] - 1) == 0  # a power-of-two op capacity

    def test_device_programs_carry_stable_names(self):
        """The names a profiler trace reads as ``jit_<name>``."""
        from repro.graphs import engine as GE
        from repro.launch import mesh as MM

        t = OT.Tracer(capacity=64)
        ctl, engine, stream, _ = _traced_stream(t)
        engine.ingest(stream.batch())
        ctl.add_hosts(1)
        names = {getattr(p, "__name__", "") for p in engine._programs._programs.values()}
        assert {"stream_scatter", "rescale_compact", "span_repair"} <= names
        mesh = MM.make_graph_mesh(1)
        for kind in GE.QUERY_KINDS:
            prog = GE.query_program(kind, num_vertices=engine.num_vertices, mesh=mesh)
            jitted = [c.cell_contents for c in prog.__closure__
                      if hasattr(c.cell_contents, "lower")]
            assert [f.__name__ for f in jitted] == [f"query_{kind}"]
