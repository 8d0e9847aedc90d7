"""CPU tests of the per-layer metrics that read the program's spans and
counts: traced rehearsals of whole runs at a tiny size (the CPU profiler
runs here; its trace has no TPU plane, so device metrics read nothing), and
each reader on hand-made runs, including runs of a program whose spans carry
no ids or counts."""
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

import harness  # noqa: E402
import xplane  # noqa: E402
from test_chip_benchmark import run, tiny_root  # noqa: E402

BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
RESCALE = ["kron19-rescale", "kron19-rescale-x4"]
NEW = {"relayout_ms": RESCALE, "rescale_warm_ms": RESCALE, "rescale_compiles": RESCALE,
       "scatter_device_ms": ["kron19-ingest"], "incident_entries_per_insert": ["kron19-ingest"],
       "free_entries_per_update": ["kron19-ingest"]}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("spans"))


def test_each_new_metric_is_declared_for_its_cell():
    specs = {m["name"]: m for m in BENCH["per_layer"]}
    for name, cells in NEW.items():
        assert specs[name]["workloads"] == cells
        assert harness.reader(ROOT, name) is not None


@pytest.mark.parametrize("workload", ["kron19-rescale", "kron19-ingest", "kron19-rescale-x4"])
def test_a_traced_run_reads_every_new_span_metric(root, workload):
    out = run(root, workload, trace=True)
    assert out["correct"] is True
    got = out["metrics"]
    for name, cells in NEW.items():
        spec = next(m for m in BENCH["per_layer"] if m["name"] == name)
        if workload not in cells:
            assert name not in got
        elif spec["source"] == "program_span":
            assert got[name]["value"] >= 0 and got[name]["unit"] == spec["unit"], name
        else:
            assert name not in got  # no TPU plane in a CPU trace
    if workload in RESCALE:
        assert got["relayout_ms"]["value"] > 0
        assert got["rescale_compiles"]["value"] == 0  # set-up warmed both layouts
    else:
        assert got["incident_entries_per_insert"]["value"] > 0
        assert got["free_entries_per_update"]["value"] > 0


def _span(name, t0, t1, sid=-1, parent=-1, counts=None):
    from repro.obs.trace import SpanRecord

    return SpanRecord(name, name.split(".")[0], t0, t1, sid, parent, counts)


def _run(spans, kind, n=2, trace=None):
    r = harness.Run("cell", {}, {}, 1, spans=spans, trace=trace, trace_window=(0.0, 100.0))
    r.ops = [harness.Op(kind, 0.0, 1.0, {}) for _ in range(n)]
    return r


def test_rescale_readers_on_a_hand_made_tree():
    spans = [
        _span("rescale.relayout", 0.0, 3.0, 1, 0),
        _span("rescale.warm.span", 3.0, 3.5, 3, 2, {"compiles": 2, "cache_misses": 2}),
        _span("rescale.warm", 3.0, 4.0, 2, 0, {"cache_loads": 1}),
        _span("rescale.event", 0.0, 4.5, 0, -1, {"k_old": 8, "k_new": 12}),
        _span("ingest.warm.span", 5.0, 6.0, 5, 4, {"compiles": 7}),  # not under an event
        _span("ingest.warm", 5.0, 6.0, 4, -1),
    ]
    r = _run(spans, "event")
    assert harness.reader(ROOT, "relayout_ms")(r) == pytest.approx(1500.0)
    assert harness.reader(ROOT, "rescale_warm_ms")(r) == pytest.approx(500.0)
    assert harness.reader(ROOT, "rescale_compiles")(r) == pytest.approx(1.0)


def test_ingest_readers_on_hand_made_counts():
    c1 = {"inserts": 10, "deletes": 2, "skipped": 1, "incident_entries": 400,
          "free_entries": 1200}
    c2 = {"inserts": 30, "deletes": 6, "skipped": 0, "incident_entries": 200,
          "free_entries": 3600}
    r = _run([_span("ingest.apply", 0, 1, 0, -1, c1), _span("ingest.apply", 1, 2, 1, -1, c2)],
             "batch")
    assert harness.reader(ROOT, "incident_entries_per_insert")(r) == pytest.approx(600 / 40)
    assert harness.reader(ROOT, "free_entries_per_update")(r) == pytest.approx(4800 / 48)


def test_scatter_device_ms_unions_nested_ops_of_its_program():
    ops = [(10e6, 14e6, "jit_stream_scatter", "while.1"),
           (11e6, 12e6, "jit_stream_scatter", "fusion.2"),  # inside the while
           (20e6, 21e6, "jit_stream_scatter", "dynamic-update-slice.3"),
           (30e6, 35e6, "jit_rescale_compact", "fusion")]
    dev = "/device:TPU:0"
    trace = xplane.Trace(ops={dev: ops}, busy={dev: xplane.union([o[:2] for o in ops])},
                         host=[])
    r = _run([], "batch", n=5, trace=trace)
    r.trace_window = (0.0, 100e6)
    assert harness.reader(ROOT, "scatter_device_ms")(r) == pytest.approx(5.0 / 5)
    r.trace_window = (0.0, 12e6)  # only the window's part counts
    assert harness.reader(ROOT, "scatter_device_ms")(r) == pytest.approx(2.0 / 5)


class _OldSpan:
    """A span as a program without ids or counts records it."""

    def __init__(self, name):
        self.name, self.phase, self.t0, self.t1 = name, name.split(".")[0], 0.0, 1.0

    @property
    def duration_s(self):
        return 1.0


def test_readers_read_nothing_from_a_program_without_the_spans():
    old = [_OldSpan(n) for n in ("ingest.batch", "ingest.apply", "rung.monitor",
                                 "rescale.compact")]
    dev = "/device:TPU:0"
    ops = [(0.0, 5e6, "jit_apply", "while.1")]
    trace = xplane.Trace(ops={dev: ops}, busy={dev: np.array([[0.0, 5e6]])}, host=[])
    for kind in ("batch", "event"):
        r = _run(old, kind, trace=trace)
        for name in NEW:
            assert harness.reader(ROOT, name)(r) is None, name
