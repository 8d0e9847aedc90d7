"""bfs_teps: Graph500 TEPS over the window: the input edges in each
completed search's component, summed, over the window's seconds (host
clock)."""


def read(run):
    searches = run.of("search")
    if not searches or run.window_s <= 0:
        return None
    return sum(op.info["component_edges"] for op in searches) / run.window_s
