"""Benchmark orchestrator — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (see common.emit). Individual benches:
``python -m benchmarks.bench_quality`` etc. Select subsets with
``python -m benchmarks.run fig9 table2``.
"""
from __future__ import annotations

import os
import sys
import time
import traceback

from repro import compat

MODULES = [
    ("fig9_partition_time", "benchmarks.bench_partition_time"),
    ("fig10_11_quality", "benchmarks.bench_quality"),
    ("fig5_delta", "benchmarks.bench_delta"),
    ("fig13_migration", "benchmarks.bench_migration"),
    ("rescale_exec", "benchmarks.bench_rescale_exec"),
    ("stream_ingest", "benchmarks.bench_stream"),
    ("serve_autoscale", "benchmarks.bench_serve"),
    ("multihost", "benchmarks.bench_multihost"),
    ("fig15_scalability", "benchmarks.bench_scalability"),
    ("table2_theory", "benchmarks.bench_theory"),
    ("table6_apps", "benchmarks.bench_apps"),
    ("elastic_lm", "benchmarks.bench_elastic_lm"),
    ("roofline", "benchmarks.bench_roofline"),
]


def main() -> None:
    import importlib

    wanted = [a.lower() for a in sys.argv[1:]]
    print("name,us_per_call,derived")
    failed = []
    for tag, modname in MODULES:
        if wanted and not any(w in tag for w in wanted):
            continue
        t0 = time.time()
        mod = importlib.import_module(modname)
        try:
            mod.run()
            print(f"# {tag}: done in {time.time()-t0:.1f}s", flush=True)
        except Exception:  # run the rest, then exit nonzero: a failed bench is a bug
            traceback.print_exc()
            print(f"# {tag}: FAILED", flush=True)
            failed.append(tag)
    if failed:
        sys.exit(f"failed: {', '.join(failed)}")


if __name__ == "__main__":
    compat.use_compile_cache(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    main()
