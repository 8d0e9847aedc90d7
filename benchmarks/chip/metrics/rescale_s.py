"""rescale_s: mean seconds from a scale decision (``add_hosts`` or the
``poll`` that finds hosts gone) until the pack serves at the new k, over the
scale events of the window (host clock)."""


def read(run):
    events = run.of("event")
    if not events:
        return None
    return sum(op.t1 - op.t0 for op in events) / len(events)
