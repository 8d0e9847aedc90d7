"""incident_entries_per_insert: incident-set entries the host orderer's
placement unioned (the median slot of an insert's endpoints), over the
inserts placed, summed over the window's ``ingest.apply`` spans
(``counts["incident_entries"]`` / ``counts["inserts"]``). None where the
spans carry no counts."""


def read(run):
    counts = [getattr(s, "counts", None) for s in run.spans if s.name == "ingest.apply"]
    counts = [c for c in counts if c and "incident_entries" in c]
    inserts = sum(c["inserts"] for c in counts)
    return sum(c["incident_entries"] for c in counts) / inserts if inserts else None
