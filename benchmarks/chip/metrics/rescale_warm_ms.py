"""rescale_warm_ms: mean milliseconds of the program's ``rescale.warm`` span
(span-repair, full-rebuild and scatter programs warmed for the new layout)
per scale event of the window. None where the program has no such span."""


def read(run):
    events = run.of("event")
    d = [s.duration_s for s in run.spans if s.name == "rescale.warm"]
    return 1e3 * sum(d) / len(events) if d and events else None
