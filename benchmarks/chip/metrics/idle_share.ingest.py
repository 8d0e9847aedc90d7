"""idle_share.ingest: percent of the traced window in which no operation ran
on the device, in a cell whose window is update batches."""


def read(run):
    if run.trace is None or not run.of("batch"):
        return None
    share = run.trace.idle_share([run.trace_window])
    return None if share is None else 100.0 * share
