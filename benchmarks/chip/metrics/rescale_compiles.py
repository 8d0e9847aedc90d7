"""rescale_compiles: XLA backend compiles that the program's tracer credited
to ``rescale.event`` and the spans under it (``counts["compiles"]``), per
scale event of the window. Persistent-cache loads are counted apart, as
``cache_loads``, and not here. None where the program has no such span."""


def read(run):
    events = run.of("event")
    roots = {s.id for s in run.spans if s.name == "rescale.event"}
    if not roots or not events:
        return None
    parent = {s.id: s.parent for s in run.spans}

    def under(sid) -> bool:
        while sid in parent:
            if sid in roots:
                return True
            sid = parent[sid]
        return False

    n = sum((s.counts or {}).get("compiles", 0) for s in run.spans if under(s.id))
    return n / len(events)
