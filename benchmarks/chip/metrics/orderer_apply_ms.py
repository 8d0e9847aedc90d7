"""orderer_apply_ms: mean milliseconds of the program's ``ingest.apply``
span (host orderer placement of one batch) over the window's batches."""


def read(run):
    d = [s.duration_s for s in run.spans if s.name == "ingest.apply"]
    return 1e3 * sum(d) / len(d) if d else None
