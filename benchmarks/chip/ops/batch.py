"""batch: one update batch through ``ElasticController.ingest``.

Parameters (the mix's ``batch``): ``size``, ``delete_frac``,
``triadic_frac``, optionally ``burst_every``, ``burst_factor`` and
``burst_delete_frac`` (``updatestream.UpdateStream``), and ``supply_per_s``:
the batches of the set-up rounds and of ``supply_per_s`` a second of window
are generated before the window opens, so that the generator does not share
the host with the timed path. Each acknowledged batch joins ``cell.log``.
"""
import math
import time

import numpy as np

import reference
import updatestream


def _stream(cell, edges):
    p = {k: v for k, v in cell.mix["batch"].items() if k not in ("size", "supply_per_s")}
    return updatestream.UpdateStream(edges, cell.v, batch=cell.mix["batch"]["size"],
                                     seed=cell.seed, **p)


def _supply(cell, n: int) -> None:
    """Generate update batches until ``n`` are waiting."""
    while len(cell.batches) < n:
        ins, dels = cell.stream.next_batch()
        cell.batches.append((cell.batch_cls(insert=ins, delete=dels), ins, dels))


def _presupply(cell, rounds: int) -> None:
    _supply(cell, rounds * cell.per_round("batch")
            + math.ceil(cell.seconds * cell.mix["batch"]["supply_per_s"]))


def setup(cell) -> None:
    from repro.stream import EdgeUpdateBatch

    cell.batch_cls = EdgeUpdateBatch
    cell.stream = _stream(cell, cell.base)
    cell.batches = []
    _presupply(cell, cell.mix["setup_rounds"])


def reseed(cell) -> None:
    live = reference.replay(cell.base_keys, cell.log, cell.v)
    cell.stream = _stream(cell, np.stack(np.divmod(live, np.int64(cell.v)), axis=1))
    cell.batches = []
    _presupply(cell, 0)


def run(cell) -> dict:
    lag = 0.0
    if not cell.batches:
        t = time.perf_counter()
        _supply(cell, 1)
        lag = time.perf_counter() - t
    b, ins, dels = cell.batches.pop(0)
    ev = cell.ctl.ingest(b)
    cell.log.append((ins, dels))
    # The benchmark's own count of what it sent; the pack check decides
    # whether all of it was applied.
    return {"updates": len(ins) + len(dels), "rung": ev.escalation, "generator_lag_s": lag}


def summary(ops) -> list:
    rungs = {}
    for op in ops:
        rungs[op.info["rung"]] = rungs.get(op.info["rung"], 0) + 1
    lag = sum(op.info["generator_lag_s"] for op in ops)
    return [f"rungs {rungs} generator_lag_s={lag:.3f}"] if ops else []
