"""updates_per_s: inserts and deletes that the benchmark sent and
``ElasticController.ingest`` acknowledged, counted from the benchmark's own
batches, over the whole window (host clock). The pack check decides whether
they were applied."""


def read(run):
    batches = run.of("batch")
    if not batches or run.window_s <= 0:
        return None
    return sum(op.info["updates"] for op in batches) / run.window_s
