"""relayout_ms: mean milliseconds of the program's ``rescale.relayout`` span
(the host orderer re-slicing its order into the new partition count: the
snapshot, the old slot map, the new layout and the gather map) per scale
event of the window. None where the program has no such span."""


def read(run):
    events = run.of("event")
    d = [s.duration_s for s in run.spans if s.name == "rescale.relayout"]
    return 1e3 * sum(d) / len(events) if d and events else None
