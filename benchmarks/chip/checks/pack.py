"""pack: the device pack against the replay of the update log.

``pack_off`` sums, over the packs taken after each window event and the
pack at the window's end, the edges missing from, extra in or twice in the
pack against ``reference.replay`` of the batches acknowledged before it,
non-zero masked slots, vertices whose degree differs, and the gap between
the pack's partition count and the live hosts. Its limit is 0. The replication
factor of the final pack is left in ``run.found["rf"]``.
"""
import numpy as np

import reference


def read(cell, run) -> dict:
    d = cell.eng.data
    packs = cell.snapshots + [(len(cell.log), cell.k, d.k, (d.edges, d.mask, d.degrees))]
    parts = {"edges_off": 0, "degrees_off": 0, "k_off": 0}
    for n, k_want, k_pack, (e, m, dg) in packs:
        e, m, dg = np.asarray(e), np.asarray(m), np.asarray(dg)
        want = reference.replay(cell.base_keys, cell.log[:n], cell.v)
        r = reference.pack_readings(e, m, dg, k_pack, want, k_want, cell.v)
        run.failed += any(r.values())
        for key in parts:
            parts[key] += r[key]
        if n == len(cell.log):
            run.found["rf"] = reference.replication_factor(e, m > 0, cell.v)
    print("check packs=%d " % len(packs) + " ".join(f"{k}={x}" for k, x in parts.items()),
          flush=True)
    return {"pack_off": (sum(parts.values()), 0)}
