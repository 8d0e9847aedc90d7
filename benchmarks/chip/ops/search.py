"""search: one graph search through the program's ``query_program``.

Parameters (the mix's ``search``): ``program`` (the query, e.g. ``sssp``,
whose hop distances are a BFS's levels), ``max_iters``, ``min_degree``, and
optionally ``operands``: the fields of the engine's live pack
(``cell.eng.data``) that the program reads, in the order it takes them;
``["edges", "mask"]`` where the mix names none. Each search reads those
fields anew, since the pack changes under ingest and rescale, and calls
``cell.prog(*operands, root)``. A field the pack lacks is a ``BenchError``
at set-up, before any search runs.

Search keys are drawn from ``cell.seed`` among the vertices of at least
``min_degree``, as Graph500's kernel 2 draws them; the searches run back to
back on the engine's live pack. Each search returns ``root``, ``iters`` and
``dist``. The mix's search check (``checks/<check>.py``, e.g. ``bfs``)
gives every search ``component_edges`` and ``reached`` and removes
``dist``; ``bfs_teps`` and ``idle_share.bfs`` read any mix whose check does.
"""
import numpy as np

import graphgen
import harness

SALT_ROOT = 401  # search-key draws
OPERANDS = ("edges", "mask")  # what a mix that names no operands passes


def setup(cell) -> None:
    from repro.graphs import engine as GE

    p = cell.mix["search"]
    cell.operands = list(p.get("operands", OPERANDS))
    missing = [f for f in cell.operands if not hasattr(cell.eng.data, f)]
    if missing:
        raise harness.BenchError(f"search operands {missing} are not fields of the engine's "
                                 f"pack ({type(cell.eng.data).__name__})")
    cell.prog = GE.query_program(p["program"], num_vertices=cell.v, mesh=cell.mesh,
                                 max_iters=p["max_iters"])
    cell.degree = np.bincount(cell.base.ravel(), minlength=cell.v)
    reseed(cell)


def reseed(cell) -> None:
    cell.roots, cell.drawn = [], 0


def _draw(cell, n: int = 4096) -> None:
    """The next ``n`` search-key draws, kept where the degree is enough."""
    idx = np.arange(cell.drawn, cell.drawn + n)
    cell.drawn += n
    keys = (graphgen.mix_hash(cell.seed, idx, 0, SALT_ROOT) % np.uint64(cell.v)).astype(np.int64)
    cell.roots += keys[cell.degree[keys] >= cell.mix["search"]["min_degree"]].tolist()


def run(cell) -> dict:
    while not cell.roots:
        _draw(cell)
    root = cell.roots.pop(0)
    data = cell.eng.data
    dist, iters = cell.prog(*[getattr(data, f) for f in cell.operands], root)
    return {"root": root, "iters": iters, "dist": dist}
