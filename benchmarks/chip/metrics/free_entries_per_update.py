"""free_entries_per_update: free-list entries the host orderer read or
copied (nearest-free-slot searches and the free-slot cache's updates), over
the inserts and deletes applied, summed over the window's ``ingest.apply``
spans (``counts["free_entries"]`` / (``inserts`` + ``deletes``)). None where
the spans carry no counts."""


def read(run):
    counts = [getattr(s, "counts", None) for s in run.spans if s.name == "ingest.apply"]
    counts = [c for c in counts if c and "free_entries" in c]
    updates = sum(c["inserts"] + c["deletes"] for c in counts)
    return sum(c["free_entries"] for c in counts) / updates if updates else None
