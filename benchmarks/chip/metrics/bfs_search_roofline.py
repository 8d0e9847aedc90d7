"""bfs_search_roofline: percent of the HBM roofline the searches reach: the
least bytes any BFS of each search must move (``reference.bfs_least_bytes``:
every edge of the root's component read once, every reached vertex's distance
written once), over the chip's HBM bandwidth, over the device-busy time
inside the searches' traced intervals."""
import reference


def read(run):
    searches = run.of("search")
    if run.trace is None or not searches or run.peaks is None:
        return None
    busy = run.trace.busy_s(run.traced("search"))
    if busy <= 0:
        return None
    least = sum(reference.bfs_least_bytes(op.info["component_edges"], op.info["reached"])
                for op in searches)
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] / busy
