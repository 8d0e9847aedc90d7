#!/usr/bin/env python3
"""Run one cell of the chip benchmark (see BENCHMARK.json at the checkout root).

    python3 benchmarks/chip/cell.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Sets up the cell (graph, preprocess, commit, warm-up), measures for
``--seconds``, checks what the window produced against plain references, and
prints one JSON object as the last line of standard output. It runs on a TPU
only: without one it exits nonzero and prints no result.
"""
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main() -> int:
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    # The persistent compile cache lives in the checkout, at a fixed path.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(HERE, ".cache", "jax")
    import jax

    from repro import compat

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compat.use_compile_cache(ROOT)
    import harness

    return harness.main(root=ROOT, t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
