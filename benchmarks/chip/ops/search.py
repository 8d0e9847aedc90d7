"""search: one graph search through the program's ``query_program``.

Parameters (the mix's ``search``): ``program`` (the query, e.g. ``sssp``,
whose hop distances are a BFS's levels), ``max_iters``, ``min_degree``.
Search keys are drawn from ``cell.seed`` among the vertices of at least
``min_degree``, as Graph500's kernel 2 draws them; the searches run back to
back on the engine's live pack.
"""
import numpy as np

import graphgen

SALT_ROOT = 401  # search-key draws


def setup(cell) -> None:
    from repro.graphs import engine as GE

    p = cell.mix["search"]
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
    dist, iters = cell.prog(cell.eng.data.edges, cell.eng.data.mask, root)
    return {"root": root, "iters": iters, "dist": dist}
