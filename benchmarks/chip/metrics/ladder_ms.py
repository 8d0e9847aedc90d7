"""ladder_ms: mean milliseconds of the program's ``rung.monitor`` span (the
quality monitor and any rung it ran) over the window's batches."""


def read(run):
    d = [s.duration_s for s in run.spans if s.name == "rung.monitor"]
    return 1e3 * sum(d) / len(d) if d else None
