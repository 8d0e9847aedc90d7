"""scatter_device_ms: device milliseconds of the ingest scatter program
(``jit_stream_scatter`` in the trace's XLA modules) inside the traced
window, per update batch: the union of its operations' intervals, averaged
over the devices. None where no operation of that program ran."""
import xplane

PROGRAM = "jit_stream_scatter"


def read(run):
    batches = run.of("batch")
    if run.trace is None or not batches or not run.trace.ops:
        return None
    lo, hi = run.trace_window
    per = [xplane.covered(xplane.union([(s, e) for s, e, prog, _ in ops if prog == PROGRAM]),
                          lo, hi) for ops in run.trace.ops.values()]
    busy_ns = sum(per) / len(per)
    return 1e-6 * busy_ns / len(batches) if busy_ns > 0 else None
