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
