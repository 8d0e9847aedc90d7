"""idle_share.bfs: percent of the searches' traced intervals in which no
operation ran on the device."""


def read(run):
    share = run.trace.idle_share(run.traced("search")) if run.trace is not None else None
    return None if share is None else 100.0 * share
