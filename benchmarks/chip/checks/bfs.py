"""bfs: the window's searches against the plain numpy BFS.

``bfs_wrong`` counts the vertices whose distance differs from
``reference.bfs`` over the live graph, in ``check_searches`` of the window's
searches drawn from the seed (the mix's ``search``); its limit is 0.

Every mix's search check, whichever ``checks/<check>.py`` it is, keeps this
contract with the readers: it gives every search operation
``component_edges`` (the input edges of its root's component) and
``reached`` (that component's vertices), and removes ``dist``. ``bfs_teps``
and ``idle_share.bfs`` then read any such mix. ``bfs_search_roofline``
counts a BFS's bytes (``reference.bfs_least_bytes``: 8 B an edge, 4 B a
reached vertex); a search that reads more, such as a weight column, brings
a roofline metric file of its own.
"""
import numpy as np

import graphgen
import reference

SALT_PICK = 401


def read(cell, run) -> dict:
    searches = run.of("search")
    if not searches:
        return {}
    v = cell.v
    keys = reference.replay(cell.base_keys, cell.log, v)
    label, comp_edges, comp_size = reference.components(keys, v)
    for op in searches:
        c = label[op.info["root"]]
        op.info["component_edges"], op.info["reached"] = int(comp_edges[c]), int(comp_size[c])
    n = min(len(searches), int(cell.mix["search"]["check_searches"]))
    pick = np.argsort(graphgen.mix_hash(cell.seed, np.arange(len(searches)), 1, SALT_PICK))[:n]
    indptr, indices = reference.csr(keys, v)
    wrong = 0
    for i in sorted(pick.tolist()):
        op = searches[i]
        got = np.asarray(op.info["dist"])
        got = np.where(got < 1e9, got, -1).astype(np.int64)
        n_wrong = int(np.count_nonzero(got != reference.bfs(indptr, indices, op.info["root"])))
        wrong += n_wrong
        run.failed += n_wrong > 0
    for op in searches:
        del op.info["dist"]
    return {"bfs_wrong": (wrong, 0)}
