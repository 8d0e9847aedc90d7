"""Plain references that decide ``correct``, and the benchmark's own arithmetic.

Nothing here imports the program or takes anything it made. The references
are straightforward numpy over the edge list that ``graphgen`` produced and
the update log that ``updatestream`` produced:

* ``replay``: the live edge set after a log of insert/delete batches, with
  set semantics (deletes of a batch before its inserts; an insert of a live
  edge and a delete of an absent one change nothing);
* ``bfs``: level-synchronous breadth-first search over a CSR adjacency, the
  hop distance of every vertex (-1 where unreachable);
* ``components``: connected components (scipy), whose edge counts are what
  a search from a root in them must read;
* ``replication_factor``: sum over partitions of the distinct vertices their
  edges touch, over the vertex count (paper Def. 1, as
  ``core/metrics.replication_factor_ordered`` counts it);
* ``pack_readings``: what a device pack holds, against the reference edge
  set, as counts that are 0 when the pack is right (a run compares their
  sum, ``pack_off``);
* ``bfs_least_bytes``: the least bytes any BFS from a root must move: each
  edge of the root's component read once (two int32 endpoints) and each
  reached vertex's distance written once (one 4-byte word).
"""
from __future__ import annotations

import numpy as np

EDGE_READ_BYTES = 8  # two int32 endpoints
DIST_WRITE_BYTES = 4  # one 32-bit distance


def edge_keys(edges: np.ndarray, num_vertices: int) -> np.ndarray:
    edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    return edges[:, 0] * np.int64(num_vertices) + edges[:, 1]


def replay(base_keys: np.ndarray, log: list, num_vertices: int) -> np.ndarray:
    """Sorted unique keys (``u * V + v``) of the live edges after applying
    ``log``, a list of ``(inserts, deletes)`` edge arrays, to the base set.

    The last operation on a key decides whether it is live; an untouched key
    keeps its base state. Within a batch deletes precede inserts."""
    base = np.unique(np.asarray(base_keys, dtype=np.int64))
    if not log:
        return base
    keys, seq, live = [], [], []
    for b, (ins, dels) in enumerate(log):
        for arr, order, state in ((dels, 0, False), (ins, 1, True)):
            k = edge_keys(arr, num_vertices)
            keys.append(k)
            seq.append(np.full(k.size, 2 * b + order, dtype=np.int64))
            live.append(np.full(k.size, state))
    keys, seq, live = np.concatenate(keys), np.concatenate(seq), np.concatenate(live)
    order = np.lexsort((seq, keys))
    keys, live = keys[order], live[order]
    last = np.ones(keys.size, dtype=bool)
    last[:-1] = keys[1:] != keys[:-1]
    touched, touched_live = keys[last], live[last]
    kept = base[~np.isin(base, touched)]
    return np.union1d(kept, touched[touched_live])


def csr(keys: np.ndarray, num_vertices: int) -> tuple[np.ndarray, np.ndarray]:
    """Undirected CSR (indptr, indices) of an edge-key set."""
    u, v = np.divmod(np.asarray(keys, dtype=np.int64), np.int64(num_vertices))
    a = np.concatenate([u, v])
    b = np.concatenate([v, u])
    order = np.argsort(a, kind="stable")
    indptr = np.zeros(num_vertices + 1, dtype=np.int64)
    np.cumsum(np.bincount(a, minlength=num_vertices), out=indptr[1:])
    return indptr, b[order]


def bfs(indptr: np.ndarray, indices: np.ndarray, root: int) -> np.ndarray:
    """Hop distances from ``root`` (int64, -1 where unreachable)."""
    n = indptr.size - 1
    dist = np.full(n, -1, dtype=np.int64)
    dist[root] = 0
    frontier = np.asarray([root], dtype=np.int64)
    level = 0
    while frontier.size:
        starts, counts = indptr[frontier], indptr[frontier + 1] - indptr[frontier]
        total = int(counts.sum())
        if total == 0:
            break
        offs = np.repeat(starts - np.cumsum(counts) + counts, counts) + np.arange(total)
        nbr = indices[offs]
        level += 1
        dist[nbr[dist[nbr] < 0]] = level
        frontier = np.flatnonzero(dist == level)
    return dist


def components(keys: np.ndarray, num_vertices: int):
    """Connected components: (label per vertex, edges per label, vertices
    per label). A search from a root reaches its component and reads its
    edges (Graph500's count of input edges in the root's component)."""
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components

    u, v = np.divmod(np.asarray(keys, dtype=np.int64), np.int64(num_vertices))
    adj = coo_matrix((np.ones(u.size, np.int8), (u, v)), shape=(num_vertices, num_vertices))
    n, label = connected_components(adj, directed=False)
    return label, np.bincount(label[u], minlength=n), np.bincount(label, minlength=n)


def bfs_least_bytes(comp_edges: int, reached: int) -> int:
    return comp_edges * EDGE_READ_BYTES + reached * DIST_WRITE_BYTES


def replication_factor(edges: np.ndarray, valid: np.ndarray, num_vertices: int) -> float:
    """RF of a pack: ``edges`` (rows, cols, 2), ``valid`` (rows, cols) bool;
    each row is one partition."""
    rows = np.broadcast_to(np.arange(edges.shape[0])[:, None], valid.shape)[valid]
    u, v = edges[..., 0][valid].astype(np.int64), edges[..., 1][valid].astype(np.int64)
    r = np.concatenate([rows, rows]).astype(np.int64)
    w = np.concatenate([u, v])
    distinct = np.unique(r * np.int64(num_vertices) + w).size
    return distinct / float(num_vertices)


def pack_readings(edges: np.ndarray, mask: np.ndarray, degrees: np.ndarray, k: int,
                  want_keys: np.ndarray, want_k: int, num_vertices: int) -> dict:
    """Counts that are 0 when a pack holds exactly the reference edge set:

    * ``edges_off``: edges missing, extra or held twice, plus masked slots
      that are not zero and mask values other than 0 and 1;
    * ``degrees_off``: vertices whose degree differs from the reference's;
    * ``k_off``: the gap between the pack's partition count and the
      expected one, plus rows beyond the expected count that hold edges."""
    valid = mask > 0
    got = np.sort(edge_keys(edges[valid], num_vertices))
    dup = int(np.count_nonzero(got[1:] == got[:-1]))
    got_u = np.unique(got)
    want = np.asarray(want_keys, dtype=np.int64)
    off = np.setdiff1d(got_u, want, assume_unique=True).size
    off += np.setdiff1d(want, got_u, assume_unique=True).size
    stray = int(np.count_nonzero(edges[~valid].any(axis=-1))) + int(
        np.count_nonzero((mask != 0) & (mask != 1)))
    u, v = np.divmod(want, np.int64(num_vertices))
    want_deg = np.bincount(np.concatenate([u, v]), minlength=num_vertices)
    rows_used = int(np.count_nonzero(valid.any(axis=1)))
    return {
        "edges_off": int(off + dup + stray),
        "degrees_off": int(np.count_nonzero(np.asarray(degrees) != want_deg)),
        "k_off": abs(int(k) - int(want_k)) + max(0, rows_used - int(want_k)),
    }
