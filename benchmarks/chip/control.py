#!/usr/bin/env python3
"""Readings that set the limits of ``correct``, on the chip, in one process.

    python3 benchmarks/chip/control.py --workload <cell> --seconds <s> \\
        --seeds <n,n,...> --control-seeds <n,n,...>

Sets the cell up once, then runs a window of ``--seconds`` for each of
``--seeds`` (the traffic drawn anew from each seed over the graph as the
previous windows left it) and checks it; then plants the cell's control
fault (``faults.control_for``) and does the same for ``--control-seeds``.
Prints one JSON line per window: the seed, the fault planted, ``correct``
and every reading. The benchmark's own runs never run this.
"""
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def readings(root, workload, seconds, seeds, controls, *, require_tpu=True):
    """Yield one record per window: ``seeds`` sound, then ``controls`` with
    the cell's control fault planted."""
    import faults
    import harness

    ctx, cell = harness.prepare(root, workload, seeds[0], seconds, False,
                                require_tpu=require_tpu)
    planted = None
    try:
        for i, seed in enumerate(list(seeds) + list(controls)):
            if i == len(seeds):
                planted = faults.control_for(cell.mix)
                planted(cell)
            if i:
                cell.reseed(seed)
            t = time.perf_counter()
            out = harness.finish(ctx, cell, harness.measure(ctx, cell, seconds, False))
            yield {"seed": seed, "control": planted.__name__ if planted else None,
                   "correct": out["correct"], "attempted": out["attempted"],
                   "checks": {k: c["value"] for k, c in out["checks"].items()},
                   "metrics": {k: m["value"] for k, m in out["metrics"].items()},
                   "s": round(time.perf_counter() - t, 3)}
    finally:
        harness.close(ctx)


def main() -> int:
    import argparse

    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(HERE, ".cache", "jax")
    import jax

    from repro import compat

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compat.use_compile_cache(ROOT)
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = [int(s) for s in args.control_seeds.split(",")]
    for rec in readings(ROOT, args.workload, args.seconds, seeds, controls):
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
