"""One run of one benchmark cell: set-up, a measured window, the check.

Everything a cell is made of is found by name, so that a cell is added by
adding files:

* ``BENCHMARK.json`` (the checkout's root) names the cell's configuration,
  traffic mix and chips, and every metric;
* ``configs/<config>.json`` holds the deployment: the graph's generator and
  its parameters, the partition count, the commit, and every option of the
  program's orderer, streaming engine, controller and preprocess, as run;
* ``generators/<generator>.py`` makes the configuration's graph (its
  ``graph.generator``): ``edges(graph)`` returns the (E, 2) int64 base edges
  from the configuration's ``graph`` group;
* ``commits/<commit>.py`` builds what the program is handed at commit (the
  configuration's ``commit``, ``ordered`` where it names none):
  ``orderer(cell, ordered)`` returns the orderer of the GEO-ordered edges
  that the streaming engine is built on;
* ``traffic/<mix>.json`` holds the mix's parameters: a ``round`` of
  ``[kind, count]`` steps repeated until the window closes, each kind's
  parameters under its own key, the ``checks`` that decide ``correct``, and
  the ``control`` fault of ``faults.py`` that ``control.py`` plants;
* ``ops/<kind>.py`` holds one kind of operation: ``setup(cell)``, run once
  before the set-up rounds; ``run(cell)``, one operation, returning what it
  did; and, where the kind draws traffic, ``reseed(cell)``, which draws it
  anew from ``cell.seed``. A search mix's ``search`` group may name the
  ``operands`` its program reads: fields of the engine's live pack, passed
  in order before the root (``["edges", "mask"]`` where it names none);
* ``checks/<check>.py`` holds one comparison with a plain reference:
  ``read(cell, run)`` returns ``{number: (value, limit)}``. A mix's search
  check, whichever it is, gives every search operation ``component_edges``
  and ``reached`` and removes ``dist`` (``checks/bfs.py``), so that the
  search metrics read any search mix;
* ``metrics/<metric>.py`` holds one metric's reader: ``read(run)`` returns
  the number, or None where the run has nothing to read.

The graph is fixed by the configuration; ``--seed`` draws only the traffic.
The program under test is the repo's ``src/repro``: ``core.hier_order``
preprocess, ``IncrementalOrderer``, ``StreamingEngine``,
``ElasticController`` and ``graphs.engine.query_program``; it sees only the
generated inputs.

The run's ``Tracer`` is the process-global one (``repro.obs.trace``) from
set-up until the run ends, so that a component built without a tracer of its
own records into ``run.spans`` too; it is disabled without ``--trace 1``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time

import numpy as np

import reference
import xplane

BENCH_REL = os.path.join("benchmarks", "chip")  # this directory, from the checkout root


class BenchError(RuntimeError):
    """The run cannot be made as asked (no chip, unknown cell, bad file)."""


# ------------------------------------------------------------ finding by name
def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(root: str, workload: str) -> tuple[dict, dict, dict, dict]:
    """(BENCHMARK.json, workload entry, configuration, traffic mix) of a cell."""
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        raise BenchError(f"no BENCHMARK.json at {root}")
    bench = load_json(path)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(os.path.join(root, conf["file"]))
    mix = load_json(os.path.join(root, BENCH_REL, "traffic", cell["traffic"] + ".json"))
    return bench, cell, config, mix


def metric_specs(bench: dict, workload: str, trace: bool) -> list:
    """The cell's metrics: end-to-end without a trace, per-layer with one."""
    specs = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in specs if "workloads" not in m or workload in m["workloads"]]


def module_path(root: str, folder: str, name: str) -> str:
    """The path of the benchmark's module ``<folder>/<name>.py``."""
    path = os.path.join(root, BENCH_REL, folder, name + ".py")
    if not os.path.exists(path):
        raise BenchError(f"no {folder}/{name}.py in {os.path.join(root, BENCH_REL)}")
    return path


def module(root: str, folder: str, name: str):
    """The benchmark's module ``<folder>/<name>.py``."""
    path = module_path(root, folder, name)
    spec = importlib.util.spec_from_file_location(f"{folder}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(root: str, name: str):
    """``metrics/<name>.py``'s ``read`` function."""
    return module(root, "metrics", name).read


# --------------------------------------------------------------- the order
def commit_name(config: dict) -> str:
    """The configuration's commit module; ``ordered`` where it names none."""
    return config.get("commit", "ordered")


def _source_hash(root: str, config: dict, config_path: str) -> str:
    """Key of the order cache: the configuration file, the graph generator
    (``graphgen.py`` and the configuration's generator module), its commit
    module and every file of the program."""
    h = hashlib.sha256()
    paths = [config_path, os.path.join(root, BENCH_REL, "graphgen.py"),
             module_path(root, "generators", config["graph"]["generator"]),
             module_path(root, "commits", commit_name(config))]
    src = os.path.join(root, "src", "repro")
    for d, dirs, files in os.walk(src):
        dirs[:] = sorted(x for x in dirs if x != "__pycache__")
        paths += [os.path.join(d, f) for f in sorted(files) if not f.endswith(".pyc")]
    for p in paths:
        h.update(os.path.relpath(p, root).encode())
        with open(p, "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:16]


def graph_and_order(root: str, name: str, config: dict, config_path: str):
    """(base edges, GEO-ordered edges, cached?): the generator's graph and the
    program's preprocess of it, cached in the checkout under a key that any
    change of the configuration, the generator, the commit or the program
    renews."""
    key = _source_hash(root, config, config_path)
    d = os.path.join(root, BENCH_REL, ".cache", "order")
    path = os.path.join(d, f"{name}.{key}.npz")
    if os.path.exists(path):
        with np.load(path) as z:
            return z["base"].astype(np.int64), z["ordered"].astype(np.int64), True
    g = config["graph"]
    base = module(root, "generators", g["generator"]).edges(g)
    from repro.core import hier_order as HO

    p = config["preprocess"]
    cfg = HO.HierConfig(**{k: v for k, v in p.items() if k != "sample_stride"})
    ordered, _ = HO.hier_order_edges(base, 1 << g["scale"], cfg,
                                     sample=base[:: p["sample_stride"]])
    os.makedirs(d, exist_ok=True)
    for old in os.listdir(d):
        if old.startswith(name + "."):
            os.remove(os.path.join(d, old))
    tmp = path + ".part.npz"
    np.savez(tmp, base=base.astype(np.int32), ordered=np.asarray(ordered, np.int32))
    os.replace(tmp, path)
    return base, np.asarray(ordered, dtype=np.int64), False


# ---------------------------------------------------------------- the run
@dataclasses.dataclass
class Op:
    kind: str  # the name of its ops/<kind>.py
    t0: float
    t1: float
    info: dict


@dataclasses.dataclass
class Run:
    """What one run measured; the metric readers read this."""

    workload: str
    config: dict
    mix: dict
    seed: int
    setup_s: float = 0.0
    window: tuple = (0.0, 0.0)  # host perf_counter seconds
    ops: list = dataclasses.field(default_factory=list)
    spans: list = dataclasses.field(default_factory=list)  # program spans in the window
    trace: object = None  # xplane.Trace of the window, with --trace 1
    trace_window: tuple = (0.0, 0.0)  # the window on the trace's clock (ns)
    found: dict = dataclasses.field(default_factory=dict)  # what the checks computed
    peaks: dict = None
    failed: int = 0

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def of(self, kind: str) -> list:
        return [op for op in self.ops if op.kind == kind]

    def traced(self, kind: str) -> list:
        """The trace's intervals (ns) of the window's ``kind`` operations."""
        return self.trace.annotations(f"bench.{kind}") if self.trace is not None else []


class FakeClock:
    """The controller's injected clock: heartbeats and liveness run on it."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


class CompileCounter:
    """Counts XLA compiles and persistent-cache loads while armed."""

    def __init__(self):
        import jax

        self.armed = False
        self.compiles = 0
        self.cache_loads = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **kw):
        if self.armed and name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, name, **kw):
        if self.armed and name == "/jax/compilation_cache/cache_hits":
            self.cache_loads += 1


def _say(msg: str) -> None:
    print(msg, flush=True)


class Cell:
    """The system under test, set up for one cell, and the runner of its
    traffic. The operations of ``ops/`` keep their state on it; the update
    log (``log``) and the device copies taken after scale events
    (``snapshots``) are what the pack check compares."""

    def __init__(self, root: str, config: dict, mix: dict, seed: int, seconds: float,
                 chips: int, tracer, base, ordered):
        import jax

        from repro.elastic import controller as EC
        from repro.launch import mesh as MM
        from repro.stream import StreamingEngine

        self.jax = jax
        self.config, self.mix, self.seed, self.seconds = config, mix, seed, seconds
        self.v = 1 << config["graph"]["scale"]
        self.k = int(config["partitions"])
        self.base = base
        self.base_keys = reference.edge_keys(base, self.v)
        self.log = []  # (inserts, deletes) of every batch acknowledged
        self.snapshots = []  # (log length, expected k, pack's k, device copies)
        t = time.perf_counter()
        self.mesh = MM.make_graph_mesh(chips)
        orderer = module(root, "commits", commit_name(config)).orderer(self, ordered)
        self.eng = StreamingEngine(orderer, self.mesh, tracer=tracer, **config["engine"])
        self.clock = FakeClock()
        self.ctl = EC.ElasticController(self.k, clock=self.clock, tracer=tracer,
                                        **config["controller"])
        self.ctl.attach_stream(self.eng)
        jax.block_until_ready(self.eng.data.edges)
        self.commit_s = time.perf_counter() - t
        t = time.perf_counter()
        self.kinds = {}
        for kind, _ in mix["round"]:
            if kind not in self.kinds:
                self.kinds[kind] = module(root, "ops", kind)
                self.kinds[kind].setup(self)
        self.generator_s = time.perf_counter() - t

    def per_round(self, kind: str) -> int:
        return sum(n for k, n in self.mix["round"] if k == kind)

    def reseed(self, seed: int) -> None:
        """Draw the traffic anew from ``seed``, over the graph as it now is."""
        self.seed = seed
        for mod in self.kinds.values():
            if hasattr(mod, "reseed"):
                mod.reseed(self)

    def rounds(self, deadline: float, ops: list, annotate, max_rounds: float = math.inf) -> None:
        """Run rounds of the mix until ``deadline`` (or ``max_rounds``): each
        round is its ``[kind, count]`` steps in order. An operation started
        before the deadline is finished. Appends an ``Op`` per operation; an
        operation may give its own ``t0`` and ``t1``."""
        done = 0
        while done < max_rounds:
            done += 1
            for kind, n in self.mix["round"]:
                for _ in range(n):
                    if time.perf_counter() >= deadline:
                        return
                    with annotate(f"bench.{kind}"):
                        t0 = time.perf_counter()
                        info = self.kinds[kind].run(self)
                        t1 = time.perf_counter()
                    ops.append(Op(kind, info.pop("t0", t0), info.pop("t1", t1), info))


# ------------------------------------------------------------- the check
def check(root: str, cell: Cell, run: Run) -> dict:
    """Readings of the mix's checks against the plain references, each with
    its limit."""
    readings = {}
    for name in cell.mix["checks"]:
        readings.update(module(root, "checks", name).read(cell, run))
    return readings


# ------------------------------------------------------------------ main
@dataclasses.dataclass
class Context:
    """What a run needs besides the cell: its specification and instruments."""

    root: str
    workload: str
    specs: list  # the metrics this run reports
    readers: dict
    devices: list  # the cell's chips
    peaks: dict
    tracer: object
    counter: CompileCounter
    previous_tracer: object  # the process-global tracer before the run


def close(ctx: Context) -> None:
    """End the run: put back the process-global tracer it replaced."""
    from repro.obs import trace as OT

    OT.set_tracer(ctx.previous_tracer)


def prepare(root: str, workload: str, seed: int, seconds: float, trace: bool, *,
            require_tpu: bool = True) -> tuple[Context, Cell]:
    """Everything before the window: find the cell, check the chip, install
    the run's tracer as the process-global one, load the graph and its
    order, commit, generate the traffic, warm up. The caller ends the run
    with ``close``; where set-up fails, it is closed here."""
    import jax

    bench, spec, config, mix = find_cell(root, workload)
    chips = int(spec["chips"])
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devs[0].platform}; this benchmark runs on the chip only")
    if len(devs) < chips:
        raise BenchError(f"{workload} needs {chips} chips, JAX found {len(devs)}")
    import peaks as P

    specs = metric_specs(bench, workload, trace)
    from repro.obs import trace as OT

    ctx = Context(root, workload, specs, {m["name"]: reader(root, m["name"]) for m in specs},
                  devs[:chips], P.peaks(devs[0].device_kind) if require_tpu else None,
                  OT.Tracer(capacity=1 << 20, enabled=trace, annotate=trace), CompileCounter(),
                  OT.get_tracer())
    OT.set_tracer(ctx.tracer)
    try:
        return ctx, _set_up(ctx, bench, spec, config, mix, seed, seconds, chips)
    except BaseException:
        close(ctx)
        raise


def _set_up(ctx: Context, bench: dict, spec: dict, config: dict, mix: dict, seed: int,
            seconds: float, chips: int) -> Cell:
    """``prepare``'s work from the order load on; returns the warm cell."""
    import jax

    root = ctx.root
    conf_file = {c["name"]: c for c in bench["configs"]}[spec["config"]]["file"]
    t = time.perf_counter()
    base, ordered, cached = graph_and_order(root, spec["config"], config,
                                            os.path.join(root, conf_file))
    _say(f"setup order_load {time.perf_counter() - t:.3f} s cached={cached} "
         f"vertices={1 << config['graph']['scale']} edges={base.shape[0]}")
    cell = Cell(root, config, mix, seed, seconds, chips, ctx.tracer, base, ordered)
    _say(f"setup commit {cell.commit_s:.3f} s k={cell.k} slots={cell.eng.orderer.capacity}")
    _say(f"setup stream_generator {cell.generator_s:.3f} s")
    t = time.perf_counter()
    warm: list = []
    cell.rounds(math.inf, warm, contextlib.nullcontext, max_rounds=mix["setup_rounds"])
    jax.block_until_ready(cell.eng.data.edges)
    cell.snapshots.clear()
    _say(f"setup warm_up {time.perf_counter() - t:.3f} s ops={len(warm)} "
         + " ".join(f"{o.kind}={o.t1 - o.t0:.3f}" for o in warm if o.kind != "batch"))
    return cell


def measure(ctx: Context, cell: Cell, seconds: float, trace: bool) -> Run:
    """The window: rounds of the mix for ``seconds``, profiled with ``trace``."""
    import jax

    run = Run(ctx.workload, cell.config, cell.mix, cell.seed, peaks=ctx.peaks)
    log_dir = os.path.join(ctx.root, BENCH_REL, ".cache", "trace", ctx.workload)
    annotate = jax.profiler.TraceAnnotation if trace else contextlib.nullcontext
    if trace:
        shutil.rmtree(log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    ctx.tracer.clear()
    ctx.counter.compiles = ctx.counter.cache_loads = 0
    ctx.counter.armed = True
    with annotate("bench.window"):
        t0 = time.perf_counter()
        cell.rounds(t0 + seconds, run.ops, annotate)
        jax.block_until_ready(cell.eng.data.edges)
        t1 = time.perf_counter()
    ctx.counter.armed = False
    run.window = (t0, t1)
    run.spans = ctx.tracer.spans()
    if trace:
        jax.profiler.stop_trace()
        run.trace = xplane.load(xplane.find(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)
        win = run.trace.annotations("bench.window")
        run.trace_window = win[0] if win else (0.0, 0.0)
    counts = {}
    for op in run.ops:
        counts[op.kind] = counts.get(op.kind, 0) + 1
    _say(f"window {run.window_s:.3f} s ops={counts} compiles={ctx.counter.compiles} "
         f"cache_loads={ctx.counter.cache_loads}")
    for kind, mod in cell.kinds.items():
        for line in mod.summary(run.of(kind)) if hasattr(mod, "summary") else ():
            _say(f"window {kind} {line}")
    return run


def finish(ctx: Context, cell: Cell, run: Run) -> dict:
    """Read the memory peak, check against the references, read the metrics;
    returns the result line's object."""
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in ctx.devices)
    t = time.perf_counter()
    readings = check(ctx.root, cell, run)
    _say(f"check {time.perf_counter() - t:.3f} s")
    metrics = {}
    for m in ctx.specs:
        val = ctx.readers[m["name"]](run)
        if val is not None:
            metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}
    d0 = ctx.devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind, "count": len(ctx.devices),
              "memory_peak_bytes": peak}
    out = {"correct": all(val <= lim for val, lim in readings.values()) and bool(run.ops),
           "attempted": len(run.ops), "failed": int(run.failed), "metrics": metrics,
           "device": device}
    if run.trace is not None:
        lo, hi = run.trace_window
        device["busy_s"] = run.trace.busy_s([(lo, hi)])
        device["window_s"] = (hi - lo) * 1e-9
        out["breakdown"] = {"device_ops": run.trace.top_ops(lo, hi),
                            "idle_gaps": run.trace.idle_gaps(lo, hi)}
    out["checks"] = {k: {"value": val, "limit": lim} for k, (val, lim) in readings.items()}
    return out


def run_cell(root: str, workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True, on_window=None) -> dict:
    """One run: set up, measure, check; returns the result line's object.
    ``on_window(cell)`` runs between set-up and window (tests break the timed
    path there)."""
    ctx, cell = prepare(root, workload, seed, seconds, trace, require_tpu=require_tpu)
    try:
        setup_s = time.perf_counter() - t_start
        _say(f"setup total {setup_s:.3f} s")
        if on_window is not None:
            on_window(cell)
        run = measure(ctx, cell, seconds, trace)
        run.setup_s = setup_s
        return finish(ctx, cell, run)
    finally:
        close(ctx)


def emit(out: dict) -> None:
    """Print the compared numbers beside their limits as the last lines of
    standard error, and the result as the last line of standard output."""
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


def main(argv=None, *, root: str, t_start: float = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(root, args.workload, args.seed, args.seconds, bool(args.trace),
                       t_start=t_start)
    except BenchError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    emit(out)
    return 0
