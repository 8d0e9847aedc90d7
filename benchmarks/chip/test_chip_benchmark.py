"""CPU tests of the chip benchmark: its references and arithmetic on
hand-checked inputs, the trace reduction on a recorded v5e trace, and
rehearsals of whole runs at a tiny configuration (the chip check skipped),
with and without the timed path broken."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

import faults  # noqa: E402
import graphgen  # noqa: E402
import harness  # noqa: E402
import peaks  # noqa: E402
import reference  # noqa: E402
import updatestream  # noqa: E402
import xplane  # noqa: E402

TRACE = os.path.join(HERE, "testdata", "v5e_searches.xplane.pb")
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
TINY_SCALE = 8


# ------------------------------------------------------------- references
def test_replay_applies_deletes_before_inserts_with_set_semantics():
    v = 10
    base = reference.edge_keys(np.array([[0, 1], [1, 2], [2, 3]]), v)
    log = [
        (np.array([[3, 4], [0, 1]]), np.array([[0, 1], [5, 6]])),  # (0,1) out then back in
        (np.array([[1, 2]]), np.array([[2, 3], [3, 4]])),  # (1,2) already live
    ]
    got = reference.replay(base, log, v)
    want = reference.edge_keys(np.array([[0, 1], [1, 2]]), v)
    assert got.tolist() == sorted(want.tolist())
    assert reference.replay(base, log[:1], v).tolist() == sorted(
        reference.edge_keys(np.array([[0, 1], [1, 2], [2, 3], [3, 4]]), v).tolist())


def test_bfs_on_a_path_and_an_island():
    v = 7
    keys = reference.edge_keys(np.array([[0, 1], [1, 2], [2, 3], [1, 4], [5, 6]]), v)
    indptr, indices = reference.csr(keys, v)
    assert reference.bfs(indptr, indices, 0).tolist() == [0, 1, 2, 3, 2, -1, -1]
    assert reference.bfs(indptr, indices, 6).tolist() == [-1] * 5 + [1, 0]
    label, comp_edges, comp_size = reference.components(keys, v)
    assert comp_edges[label[3]] == 4 and comp_size[label[3]] == 5
    assert comp_edges[label[5]] == 1 and comp_size[label[5]] == 2
    assert reference.bfs_least_bytes(4, 5) == 4 * 8 + 5 * 4


def test_replication_factor_counts_distinct_vertices_per_partition():
    # Partition 0 touches {0, 1, 2}, partition 1 touches {2, 3}; |V| = 5.
    edges = np.array([[[0, 1], [1, 2], [0, 0]], [[2, 3], [0, 0], [0, 0]]], np.int32)
    valid = np.array([[True, True, False], [True, False, False]])
    assert reference.replication_factor(edges, valid, 5) == pytest.approx(5 / 5)
    valid[0, 1] = False
    assert reference.replication_factor(edges, valid, 5) == pytest.approx(4 / 5)


def _pack(rows, width, v):
    edges = np.zeros((len(rows), width, 2), np.int32)
    mask = np.zeros((len(rows), width), np.float32)
    for r, row in enumerate(rows):
        for c, e in enumerate(row):
            edges[r, c], mask[r, c] = e, 1.0
    deg = np.zeros(v, np.float32)
    for row in rows:
        for a, b in row:
            deg[a] += 1
            deg[b] += 1
    return edges, mask, deg


def test_pack_readings_are_zero_only_for_the_right_pack():
    v = 6
    live = [[(0, 1), (1, 2)], [(3, 4)]]
    want = np.sort(reference.edge_keys(np.array([e for row in live for e in row]), v))
    e, m, d = _pack(live, 3, v)
    assert reference.pack_readings(e, m, d, 2, want, 2, v) == {
        "edges_off": 0, "degrees_off": 0, "k_off": 0}
    stale = reference.pack_readings(e, m, d, 2, want[:-1], 2, v)
    assert stale["edges_off"] == 1 and stale["degrees_off"] == 2
    assert reference.pack_readings(e, m, d, 2, want, 3, v)["k_off"] == 1
    e2, m2, d2 = _pack([[(0, 1), (1, 2)], [(3, 4), (0, 1)]], 3, v)
    assert reference.pack_readings(e2, m2, d, 2, want, 2, v)["edges_off"] == 1  # held twice
    e[1, 2] = (5, 5)  # a masked slot that is not zero
    assert reference.pack_readings(e, m, d, 2, want, 2, v)["edges_off"] == 1


# -------------------------------------------------------------- generators
def test_graph_is_simple_undirected_and_fixed_by_its_seed():
    a = graphgen.graph_edges(10, 16, [0.57, 0.19, 0.19], seed=0)
    assert np.array_equal(a, graphgen.graph_edges(10, 16, [0.57, 0.19, 0.19], seed=0))
    assert np.all(a[:, 0] < a[:, 1])
    assert np.unique(reference.edge_keys(a, 1 << 10)).size == a.shape[0]
    u = graphgen.graph_edges(10, 16, [0.25, 0.25, 0.25], seed=0)
    deg_k = np.bincount(a.ravel(), minlength=1 << 10)
    deg_u = np.bincount(u.ravel(), minlength=1 << 10)
    assert deg_k.max() > 4 * deg_u.max()  # kron has hubs, urand none
    assert u.shape[0] > 0.98 * 16 * (1 << 10)  # urand draws seldom repeat


def test_kron_relabels_both_endpoints_by_one_permutation():
    """Graph500 permutes every vertex label once: the relabelled graph has
    the degree sequence of the unrelabelled draws, so the Kronecker hub stays
    one vertex and (u, v), (v, u) draws collapse into one pair."""
    scale, init = 10, [0.57, 0.19, 0.19]
    edges = graphgen.graph_edges(scale, 16, init, seed=3)
    src, dst = graphgen.kron_pairs(scale, init, 3, np.arange(16 << scale))
    lo, hi = np.minimum(src, dst).astype(np.int64), np.maximum(src, dst).astype(np.int64)
    raw = np.unique(np.stack([lo, hi], axis=1)[lo != hi], axis=0)
    assert edges.shape == raw.shape
    deg = np.sort(np.bincount(edges.ravel(), minlength=1 << scale))
    assert np.array_equal(deg, np.sort(np.bincount(raw.ravel(), minlength=1 << scale)))
    assert deg[-1] == np.bincount(raw.ravel())[0]  # vertex 0 of the draws is the hub


def test_update_stream_bursts_follow_the_batch_index():
    base = graphgen.graph_edges(9, 8, [0.25, 0.25, 0.25], seed=0)
    s = updatestream.UpdateStream(base, 1 << 9, batch=40, delete_frac=0.25, triadic_frac=0.5,
                                  seed=9, burst_every=4, burst_factor=3, burst_delete_frac=0.5)
    sizes = [tuple(len(x) for x in s.next_batch()) for _ in range(8)]
    assert sizes == [(30, 10)] * 3 + [(60, 60)] + [(30, 10)] * 3 + [(60, 60)]
    assert s.shape(3) == (60, 60) and s.shape(4) == (10, 30)


def test_update_stream_is_fixed_by_its_seed_and_names_live_edges():
    base = graphgen.graph_edges(9, 8, [0.57, 0.19, 0.19], seed=0)
    v = 1 << 9
    seed = 2**31 + 7  # seeds run past 32 bits
    s1 = updatestream.UpdateStream(base, v, batch=64, delete_frac=0.25, triadic_frac=0.5, seed=seed)
    s2 = updatestream.UpdateStream(base, v, batch=64, delete_frac=0.25, triadic_frac=0.5, seed=seed)
    live = set(reference.edge_keys(base, v).tolist())
    for _ in range(5):
        ins, dels = s1.next_batch()
        ins2, dels2 = s2.next_batch()
        assert np.array_equal(ins, ins2) and np.array_equal(dels, dels2)
        assert dels.shape[0] == 16 and ins.shape[0] == 48
        dk, ik = reference.edge_keys(dels, v).tolist(), reference.edge_keys(ins, v).tolist()
        assert set(dk) <= live
        live -= set(dk)
        assert not set(ik) & live and np.all(ins[:, 0] < ins[:, 1])
        live |= set(ik)
    assert s1.num_edges == len(live)


def test_peaks_table_refuses_an_unknown_kind():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


# ---------------------------------------------------------- trace reduction
def test_trace_reduction_on_a_recorded_v5e_trace():
    """Three searches of two programs each (the trace has one device and
    21 operations); the numbers below were checked against the event list
    by hand."""
    t = xplane.load(TRACE)
    assert list(t.busy) == ["/device:TPU:0"]
    merged = t.busy["/device:TPU:0"]
    assert merged.shape[0] == 19 and float(np.sum(merged[:, 1] - merged[:, 0])) == 1809249.0
    (lo, hi), = t.annotations("bench.window")
    assert (lo, hi) == (54664845.0, 67729544.0)
    assert len(t.annotations("bench.search")) == 3
    # The first program pair ran before the window opened on the trace's
    # clock; the other two (603,052 + 603,062 ns) lie inside it.
    assert t.busy_s([(lo, hi)]) == pytest.approx(1206114e-9)
    assert t.idle_share([(lo, hi)]) == pytest.approx(1 - 1206114 / 13064699)
    assert t.idle_share([]) is None
    top = t.top_ops(lo, hi, 1)
    assert top[0][0] == "jit__lambda:fusion" and top[0][1] == pytest.approx((582400 + 582515) * 1e-9)
    gaps = t.idle_gaps(lo, hi, 3)
    assert [g[0] for g in gaps] == ["bench.window", "bench.search", "bench.search"]
    assert [round(g[1] * 1e9) for g in gaps] == [5178595, 3687288, 2988948]


def test_interval_arithmetic():
    m = xplane.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert m.tolist() == [[0, 3], [5, 9]]
    assert xplane.covered(m, 2, 6) == 2.0
    assert xplane.covered(m, 9, 20) == 0.0


def test_modules_describe_no_tpu_and_start_no_jax_at_import():
    code = ("import sys; sys.path[:0] = [%r]; import harness, graphgen, reference, "
            "updatestream, xplane, peaks; print('jax' in sys.modules)") % HERE
    r = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=120)
    assert r.returncode == 0 and r.stdout.strip() == "False", r.stderr


# ------------------------------------------------------------ rehearsals
def tiny_root(dst, quiet: bool = False) -> str:
    """A checkout with the benchmark, its configurations cut to scale 8 and
    its update batches to 32; ``quiet`` keeps the escalation ladder from
    firing and gives the regions room for a window's growth: a rung or a
    re-layout resyncs the pack from the host, which would mend a fault
    planted in the device scatter."""
    dst = str(dst)
    shutil.copytree(HERE, os.path.join(dst, harness.BENCH_REL),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__", "testdata"))
    os.symlink(os.path.join(ROOT, "src"), os.path.join(dst, "src"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    bench = harness.load_json(os.path.join(dst, "BENCHMARK.json"))
    for c in bench["configs"]:
        path = os.path.join(dst, c["file"])
        conf = harness.load_json(path)
        conf["graph"]["scale"] = TINY_SCALE
        if quiet:
            conf["orderer"].update(partial_drift=1e9, full_drift=1e9, slack=8.0)
        with open(path, "w") as f:
            json.dump(conf, f)
    traffic = os.path.join(dst, harness.BENCH_REL, "traffic")
    for name in os.listdir(traffic):
        mix = harness.load_json(os.path.join(traffic, name))
        if "batch" in mix:
            mix["batch"]["size"] = 32
        with open(os.path.join(traffic, name), "w") as f:
            json.dump(mix, f)
    return dst


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture(scope="module")
def quiet_root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("quiet"), quiet=True)


BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in BENCH["workloads"]]
CHIPS = {w["name"]: int(w["chips"]) for w in BENCH["workloads"]}


def seconds(workload) -> float:
    """A window long enough for a rescale cell's events."""
    traffic = {w["name"]: w["traffic"] for w in BENCH["workloads"]}.get(workload)
    if traffic is None:
        return 0.3
    mix = harness.load_json(os.path.join(ROOT, harness.BENCH_REL, "traffic", traffic + ".json"))
    return 1.5 if "event" in mix else 0.3


def in_child(code: str, chips: int) -> list:
    """Run ``code`` in a child forced to ``chips`` host devices; returns the
    objects it printed after ``RESULT``."""
    from repro.launch.multihost import force_host_device_flags

    env = dict(os.environ)
    env["XLA_FLAGS"] = force_host_device_flags(chips, env.get("XLA_FLAGS", ""))
    env["JAX_PLATFORMS"] = "cpu"
    prelude = "import json, sys, time; sys.path[:0] = [%r, %r]\n" % (HERE, os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", prelude + code], capture_output=True, text=True,
                       env=env, cwd=ROOT, timeout=600)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout[-4000:]}\nSTDERR:\n{r.stderr[-4000:]}"
    return [json.loads(x[len("RESULT "):]) for x in r.stdout.splitlines()
            if x.startswith("RESULT ")]


def run(root, workload, *, trace=False, on_window=None, seed=2**31 + 11):
    """One run: in this process, or, for a cell on more chips than this
    process has, in a child forced to that many host devices."""
    import time

    chips = CHIPS.get(workload, 1)
    if chips > 1:
        fault = "None" if on_window is None else f"faults.{on_window.__name__}"
        out, = in_child(
            "import faults, harness\n"
            f"out = harness.run_cell({root!r}, {workload!r}, {seed}, {seconds(workload)}, "
            f"{trace}, t_start=time.perf_counter(), require_tpu=False, on_window={fault})\n"
            "print('RESULT ' + json.dumps(out))\n", chips)
        return out
    return harness.run_cell(root, workload, seed, seconds(workload), trace,
                            t_start=time.perf_counter(), require_tpu=False, on_window=on_window)


def last_line(out, capsys) -> dict:
    harness.emit(out)
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", CELLS)
def test_every_mix_runs_a_whole_cell_at_a_tiny_size(root, workload, capsys):
    line = last_line(run(root, workload), capsys)
    assert set(line) == CONTRACT_KEYS | {"checks"} and list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["count"] == CHIPS[workload]
    bench = harness.load_json(os.path.join(root, "BENCHMARK.json"))
    want = {m["name"] for m in harness.metric_specs(bench, workload, trace=False)}
    assert set(line["metrics"]) == want and "setup_s" in want and len(want) >= 2
    assert all(c["value"] == 0 and c["limit"] == 0 for c in line["checks"].values())


def test_a_new_config_mix_and_metric_are_found_by_name(tmp_path, capsys):
    """A cell added as new files, plus its entry in BENCHMARK.json."""
    r = tiny_root(tmp_path)
    d = os.path.join(r, harness.BENCH_REL)
    conf = harness.load_json(os.path.join(d, "configs", "gap-urand-s19.json"))
    conf["partitions"] = 4
    with open(os.path.join(d, "configs", "new-graph.json"), "w") as f:
        json.dump(conf, f)
    mix = harness.load_json(os.path.join(d, "traffic", "bfs.json"))
    mix["round"] = [["search", 2]]
    with open(os.path.join(d, "traffic", "two-searches.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(d, "metrics", "searches_done.py"), "w") as f:
        f.write("def read(run):\n    return len(run.of('search'))\n")
    path = os.path.join(r, "BENCHMARK.json")
    bench = harness.load_json(path)
    bench["configs"].append({"name": "new-graph", "source": "test", "file": os.path.join(
        harness.BENCH_REL, "configs", "new-graph.json"), "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "new-cell", "config": "new-graph",
                               "traffic": "two-searches", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "searches_done", "unit": "searches", "better": "higher",
                               "source": "host_clock", "layer": "test", "moves": "bfs_teps",
                               "workloads": ["new-cell"]})
    for m in bench["end_to_end"]:
        if m["name"] == "bfs_teps":
            m["workloads"].append("new-cell")
    with open(path, "w") as f:
        json.dump(bench, f)
    line = last_line(run(r, "new-cell", trace=True), capsys)
    assert line["correct"] is True
    assert line["metrics"]["searches_done"]["value"] >= 2
    assert set(line) == CONTRACT_KEYS | {"breakdown", "checks"}
    assert {"busy_s", "window_s"} <= set(line["device"])


OP_EDGES = """\"\"\"edges: the device pack's live edge count, one jitted sum.\"\"\"
def setup(cell):
    cell.count = cell.jax.jit(lambda m: (m > 0).sum())


def run(cell):
    return {"live": int(cell.count(cell.eng.data.mask))}
"""
CHECK_EDGES = """\"\"\"edges: every count against the reference's live set.\"\"\"
import reference


def read(cell, run):
    want = reference.replay(cell.base_keys, cell.log, cell.v).size
    return {"edges_wrong": (sum(op.info["live"] != want for op in run.of("edges")), 0)}
"""


def test_a_new_operation_kind_and_its_check_are_found_by_name(tmp_path, capsys):
    """A mix with an operation no file had before (``ops/edges.py``), its own
    check (``checks/edges.py``) and a metric: new files only, plus the cell's
    entry in BENCHMARK.json."""
    r = tiny_root(tmp_path)
    d = os.path.join(r, harness.BENCH_REL)
    for folder, text in (("ops", OP_EDGES), ("checks", CHECK_EDGES)):
        with open(os.path.join(d, folder, "edges.py"), "w") as f:
            f.write(text)
    with open(os.path.join(d, "metrics", "counts_per_s.py"), "w") as f:
        f.write("def read(run):\n    return len(run.of('edges')) / run.window_s\n")
    mix = {"round": [["edges", 3]], "setup_rounds": 1, "checks": ["edges"],
           "control": "unchanged_ingest"}
    with open(os.path.join(d, "traffic", "count.json"), "w") as f:
        json.dump(mix, f)
    path = os.path.join(r, "BENCHMARK.json")
    bench = harness.load_json(path)
    bench["workloads"].append({"name": "count-cell", "config": "gap-urand-s19",
                               "traffic": "count", "chips": 1, "why": "test"})
    bench["end_to_end"].append({"name": "counts_per_s", "unit": "1/s", "better": "higher",
                                "bound": 0.1, "source": "host_clock",
                                "workloads": ["count-cell"]})
    with open(path, "w") as f:
        json.dump(bench, f)
    line = last_line(run(r, "count-cell"), capsys)
    assert line["correct"] is True and line["checks"]["edges_wrong"]["value"] == 0
    assert line["metrics"]["counts_per_s"]["value"] > 0 and "setup_s" in line["metrics"]


GEN_GRID = """\"\"\"grid: a square lattice of 2**scale vertices, edges in row order.\"\"\"
import numpy as np


def edges(graph):
    side = 1 << (graph["scale"] // 2)
    ids = np.arange(side * side).reshape(side, side)
    right = np.stack([ids[:, :-1].ravel(), ids[:, 1:].ravel()], axis=1)
    down = np.stack([ids[:-1, :].ravel(), ids[1:, :].ravel()], axis=1)
    return np.concatenate([right, down]).astype(np.int64)
"""
COMMIT_COUNTED = """\"\"\"counted: the ordered commit, and a note of the edges it was handed.\"\"\"
import os

from repro.stream import IncrementalOrderer
from repro.stream.incremental import StreamConfig


def orderer(cell, ordered):
    with open(os.path.join(os.path.dirname(__file__), "committed.txt"), "w") as f:
        f.write(str(ordered.shape[0]))
    return IncrementalOrderer(ordered[:, 0], ordered[:, 1], cell.v, regions=cell.k,
                              config=StreamConfig(**cell.config["orderer"]))
"""


def add_cell_on_config(r, conf: dict) -> str:
    """Write ``conf`` as configuration ``conf-test`` and a one-chip BFS cell
    on it; returns the cell's name."""
    path = os.path.join(harness.BENCH_REL, "configs", "conf-test.json")
    with open(os.path.join(r, path), "w") as f:
        json.dump(conf, f)
    bench_path = os.path.join(r, "BENCHMARK.json")
    bench = harness.load_json(bench_path)
    bench["configs"].append({"name": "conf-test", "source": "test", "file": path,
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "conf-cell", "config": "conf-test",
                               "traffic": "bfs", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "bfs_teps":
            m["workloads"].append("conf-cell")
    with open(bench_path, "w") as f:
        json.dump(bench, f)
    return "conf-cell"


def test_a_new_generator_and_commit_are_found_by_name(tmp_path, capsys):
    """A deployment whose graph generator (``generators/grid.py``) and commit
    (``commits/counted.py``) no file had before: new files only, plus the
    configuration's entry in BENCHMARK.json; its searches are checked
    against BFS over the lattice."""
    r = tiny_root(tmp_path)
    d = os.path.join(r, harness.BENCH_REL)
    for folder, name, text in (("generators", "grid", GEN_GRID),
                               ("commits", "counted", COMMIT_COUNTED)):
        with open(os.path.join(d, folder, name + ".py"), "w") as f:
            f.write(text)
    conf = harness.load_json(os.path.join(d, "configs", "gap-urand-s19.json"))
    conf["graph"]["generator"], conf["commit"] = "grid", "counted"
    line = last_line(run(r, add_cell_on_config(r, conf)), capsys)
    assert line["correct"] is True and line["checks"]["bfs_wrong"]["value"] == 0
    with open(os.path.join(d, "commits", "committed.txt")) as f:
        assert int(f.read()) == 2 * 16 * 15  # the 16 x 16 lattice's edges, each once


def test_an_unknown_generator_is_refused(tmp_path):
    """A configuration whose generator has no file fails; no other graph
    stands in for it."""
    r = tiny_root(tmp_path)
    conf = harness.load_json(os.path.join(r, harness.BENCH_REL, "configs", "gap-kron-s19.json"))
    conf["graph"]["generator"] = "lattice"
    with pytest.raises(harness.BenchError, match="generators/lattice.py"):
        run(r, add_cell_on_config(r, conf))


def add_search_cell(r, name: str, operands: list, per_layer=()) -> str:
    """A one-chip cell ``name`` on ``gap-urand-s19`` whose mix is ``bfs.json``
    with ``operands``, read by ``bfs_teps`` and ``idle_share.bfs`` and by the
    ``per_layer`` metrics named; returns the cell's name."""
    d = os.path.join(r, harness.BENCH_REL)
    mix = harness.load_json(os.path.join(d, "traffic", "bfs.json"))
    mix["search"]["operands"] = operands
    with open(os.path.join(d, "traffic", name + ".json"), "w") as f:
        json.dump(mix, f)
    path = os.path.join(r, "BENCHMARK.json")
    bench = harness.load_json(path)
    bench["workloads"].append({"name": name, "config": "gap-urand-s19", "traffic": name,
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("bfs_teps", "idle_share.bfs"):
            m["workloads"].append(name)
    bench["per_layer"] += [{"name": n, "unit": "count", "better": "higher",
                            "source": "program_span", "layer": "test", "moves": "bfs_teps",
                            "workloads": [name]} for n in per_layer]
    with open(path, "w") as f:
        json.dump(bench, f)
    return name


# Readers of a query program's own spans, as a later PR would add them.
QUERY_SPANS = "def read(run):\n    return sum(s.name == 'query.sssp' for s in run.spans) or None\n"
QUERY_ANNOTATIONS = ("def read(run):\n"
                     "    return len(run.trace.annotations('query.sssp')) or None\n")


@pytest.fixture(scope="module")
def operand_root(tmp_path_factory):
    """A tiny checkout with cell ``degrees-cell``: a search mix whose program
    reads the pack's ``degrees`` after its edges and mask."""
    r = tiny_root(tmp_path_factory.mktemp("operands"), quiet=True)
    for name, text in (("query_spans", QUERY_SPANS), ("query_annotations", QUERY_ANNOTATIONS)):
        with open(os.path.join(r, harness.BENCH_REL, "metrics", name + ".py"), "w") as f:
            f.write(text)
    add_search_cell(r, "degrees-cell", ["edges", "mask", "degrees"],
                    per_layer=["query_spans", "query_annotations"])
    return r


@pytest.fixture
def handed(monkeypatch):
    """Every query program takes ``(edges, mask, degrees, root)``: it records
    what it was handed, opens a ``query.sssp`` span on the process-global
    tracer (as a program built with no tracer of its own does), and
    searches with ``(edges, mask, root)``. Yields the list of calls."""
    from repro.graphs import engine as GE
    from repro.obs import trace as OT

    calls, real = [], GE.query_program

    def query_program(kind, **kw):
        prog = real(kind, **kw)

        def searched(edges, mask, degrees, root):
            calls.append((edges, mask, degrees, root))
            with OT.span("query.sssp"):
                return prog(edges, mask, root)
        return searched
    monkeypatch.setattr(GE, "query_program", query_program)
    return calls


def test_a_search_mix_passes_the_pack_fields_it_names(operand_root, handed, capsys):
    """A search whose program reads a third field of the pack is added as
    data and metric files: the fields arrive in the mix's order before the
    root, read from the live pack, and ``bfs_teps`` and ``idle_share.bfs``
    read the cell."""
    cells = []
    line = last_line(run(operand_root, "degrees-cell", on_window=cells.append), capsys)
    assert line["correct"] is True and line["checks"]["bfs_wrong"]["value"] == 0
    assert line["metrics"]["bfs_teps"]["value"] > 0
    data = cells[0].eng.data
    window = handed[1:]  # the first search warmed the program in set-up
    assert len(window) == line["attempted"] > 0
    for edges, mask, degrees, root in window:
        assert edges is data.edges and mask is data.mask and degrees is data.degrees
        assert isinstance(root, int) and degrees.shape == (1 << TINY_SCALE,)
    line = last_line(run(operand_root, "degrees-cell", trace=True), capsys)
    assert line["correct"] is True and "idle_share.bfs" in line["metrics"]


def test_a_traced_run_records_spans_of_a_component_without_a_tracer(operand_root, handed,
                                                                    capsys):
    """The run's tracer is the process-global one while the run lasts: a
    program that records to ``get_tracer()`` reaches ``run.spans`` and, as a
    ``query.`` annotation, the trace; an untraced run records nothing; the
    tracer before the run is back after it."""
    from repro.obs import trace as OT

    before = OT.get_tracer()
    line = last_line(run(operand_root, "degrees-cell", trace=True), capsys)
    assert OT.get_tracer() is before
    assert line["metrics"]["query_spans"]["value"] == line["attempted"] > 0
    assert line["metrics"]["query_annotations"]["value"] == line["attempted"]
    ctx, cell = harness.prepare(operand_root, "degrees-cell", 7, 0.3, False, require_tpu=False)
    try:
        assert OT.get_tracer() is ctx.tracer and not ctx.tracer.enabled
        r = harness.measure(ctx, cell, 0.3, False)
        assert r.spans == [] and len(r.of("search")) > 0
    finally:
        harness.close(ctx)
    assert OT.get_tracer() is before


def test_an_unknown_search_operand_is_refused_before_the_window(tmp_path, handed):
    from repro.obs import trace as OT

    r = tiny_root(tmp_path)
    before, windows = OT.get_tracer(), []
    with pytest.raises(harness.BenchError, match="weights"):
        run(r, add_search_cell(r, "weights-cell", ["edges", "mask", "weights"]),
            on_window=windows.append)
    assert windows == [] and handed == [] and OT.get_tracer() is before


@pytest.mark.parametrize("fault", [faults.unchanged_search, faults.altered_search,
                                   faults.early_stop])
def test_each_search_fault_takes_the_mix_operands(operand_root, handed, fault, capsys):
    line = last_line(run(operand_root, "degrees-cell", on_window=fault), capsys)
    assert line["correct"] is False and line["failed"] > 0
    assert line["checks"]["bfs_wrong"]["value"] > 0


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_generator_modules_give_graphgen_bytes_order_and_pack(root, config):
    """Each configuration's generator module returns what ``graphgen`` gives,
    byte for byte; the cached GEO order is the preprocess of those edges;
    and the ``ordered`` commit hands the engine the pack that an orderer
    built directly from them gives."""
    import jax
    from repro.core import hier_order as HO
    from repro.launch import mesh as MM
    from repro.stream import IncrementalOrderer, StreamingEngine
    from repro.stream.incremental import StreamConfig

    path = os.path.join(root, harness.BENCH_REL, "configs", config + ".json")
    conf = harness.load_json(path)
    g = conf["graph"]
    want = graphgen.graph_edges(g["scale"], g["edge_factor"], g["initiator"], g["seed"])
    got = harness.module(root, "generators", g["generator"]).edges(g)
    assert got.dtype == want.dtype == np.int64 and got.tobytes() == want.tobytes()
    base, ordered, _ = harness.graph_and_order(root, config, conf, path)
    p = conf["preprocess"]
    order, _ = HO.hier_order_edges(want, 1 << g["scale"], HO.HierConfig(
        **{k: v for k, v in p.items() if k != "sample_stride"}), sample=want[:: p["sample_stride"]])
    assert base.tobytes() == want.tobytes()
    assert ordered.tobytes() == np.asarray(order, np.int64).tobytes()

    class Cell:  # what the commit reads of ``harness.Cell``
        v, k, config = 1 << g["scale"], int(conf["partitions"]), conf

    mesh = MM.make_graph_mesh(1)
    packs = [StreamingEngine(o, mesh, **conf["engine"]).data for o in (
        harness.module(root, "commits", harness.commit_name(conf)).orderer(Cell, ordered),
        IncrementalOrderer(ordered[:, 0], ordered[:, 1], Cell.v, regions=Cell.k,
                           config=StreamConfig(**conf["orderer"])))]
    for a, b in zip(*((x.edges, x.mask, x.degrees) for x in packs)):
        assert np.asarray(jax.device_get(a)).tobytes() == np.asarray(jax.device_get(b)).tobytes()


@pytest.mark.parametrize("folder,name", [("generators", "kronecker"), ("commits", "ordered")])
def test_order_cache_key_follows_the_generator_and_commit_modules(tmp_path, folder, name):
    r = tiny_root(tmp_path)
    path = os.path.join(r, harness.BENCH_REL, "configs", "gap-kron-s19.json")
    conf = harness.load_json(path)
    before = harness._source_hash(r, conf, path)
    with open(harness.module_path(r, folder, name), "a") as f:
        f.write("# changed\n")
    assert harness._source_hash(r, conf, path) != before


def test_updates_are_counted_from_what_the_benchmark_sent(quiet_root, capsys):
    """An ingest that acknowledges each batch and applies none still reads
    the updates sent, and the pack check reads it not correct."""
    line = last_line(run(quiet_root, "kron19-ingest", on_window=faults.unchanged_ingest), capsys)
    assert line["correct"] is False
    assert line["metrics"]["updates_per_s"]["value"] > 0


@pytest.mark.parametrize("alone", [False, True])
def test_command_exits_nonzero_without_a_tpu(tmp_path, alone):
    """On the CPU, and in a directory that holds only BENCHMARK.json and the
    benchmark's files, the command fails and prints no result."""
    cwd = ROOT
    if alone:
        cwd = str(tmp_path)
        shutil.copytree(HERE, os.path.join(cwd, harness.BENCH_REL),
                        ignore=shutil.ignore_patterns(".cache", "__pycache__"))
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), cwd)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    r = subprocess.run(
        [sys.executable, *bench["command"][1:], "--workload", "urand19-bfs", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=cwd, timeout=300)
    assert r.returncode != 0
    assert '"correct"' not in r.stdout


# ----------------------------------------------------- the timed path broken
@pytest.mark.parametrize("workload,fault", [
    ("kron19-ingest", faults.unchanged_ingest),
    ("kron19-ingest", faults.half_batch),
    ("kron19-ingest", faults.altered_scatter),
    ("kron19-rescale", faults.unchanged_rescale),
    ("kron19-rescale", faults.lost_partition),
    ("kron19-rescale-x4", faults.unchanged_rescale),
    ("kron19-rescale-x4", faults.lost_partition),
    ("kron19-rescale-x4", faults.no_exchange),
    ("urand19-bfs", faults.unchanged_search),
    ("urand19-bfs", faults.altered_search),
    ("urand19-bfs", faults.early_stop),
])
def test_a_broken_timed_path_reads_not_correct(quiet_root, workload, fault, capsys):
    line = last_line(run(quiet_root, workload, on_window=fault), capsys)
    assert line["correct"] is False and line["failed"] > 0
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


@pytest.mark.parametrize("workload", ["kron19-rescale", "kron19-ingest", "urand19-bfs",
                                      "kron19-rescale-x4"])
def test_control_reads_not_correct_where_sound_seeds_read_correct(quiet_root, workload):
    """The control of ``control.py`` at a tiny size: two sound windows on
    seeds of their own, then two with the cell's control fault planted."""
    args = (quiet_root, workload, seconds(workload), [2**33 + 1, 5], [2**33 + 2, 6])
    if CHIPS[workload] > 1:
        recs = in_child("import control\n"
                        f"for r in control.readings(*{args!r}, require_tpu=False):\n"
                        "    print('RESULT ' + json.dumps(r))\n", CHIPS[workload])
    else:
        import control

        recs = list(control.readings(*args, require_tpu=False))
    assert [r["control"] is None for r in recs] == [True, True, False, False]
    assert [r["correct"] for r in recs] == [True, True, False, False]
    assert all(max(r["checks"].values()) > 0 for r in recs[2:])
