"""event: the next scale event of the mix's ``event`` cycle.

``{"kind": "add_hosts", "hosts": n}`` grants n hosts
(``ElasticController.add_hosts``); ``{"kind": "lose_hosts", "hosts": n}``
lets n live hosts, drawn from ``cell.seed``, miss their heartbeats on the
controller's injected clock, and ``ElasticController.poll`` finds them gone,
the path of a de-provisioned host. The event's time runs from the decision
until the pack serves at the new k; a device copy of the pack is then taken,
outside that time, for the pack check.
"""
import time

import numpy as np

import graphgen

SALT_LOST = 409  # which hosts miss their heartbeats


def setup(cell) -> None:
    cell.next_event = 0
    cell.step = 0
    cell.copy_pack = cell.jax.jit(lambda e, m, d: (e.copy(), m.copy(), d.copy()))


def run(cell) -> dict:
    cycle = cell.mix["event"]["cycle"]
    e = cycle[cell.next_event % len(cycle)]
    cell.next_event += 1
    n = int(e["hosts"])
    if e["kind"] == "add_hosts":
        t0 = time.perf_counter()
        sev = cell.ctl.add_hosts(n)
        cell.k += n
    elif e["kind"] == "lose_hosts":
        alive = sorted(h for h, s in cell.ctl.hosts.items() if s.alive)
        order = np.argsort(graphgen.mix_hash(cell.seed, cell.next_event, np.arange(len(alive)),
                                             SALT_LOST))
        lost = {alive[i] for i in order[:n]}
        cell.clock.t += cell.ctl.dead_after_s + 1.0
        cell.step += 1
        for h in alive:
            if h not in lost:
                cell.ctl.heartbeat(h, cell.step)
        t0 = time.perf_counter()
        sev = cell.ctl.poll()
        cell.k -= n
    else:
        raise ValueError(f"unknown event kind {e['kind']!r}")
    cell.jax.block_until_ready(cell.eng.data.edges)
    t1 = time.perf_counter()
    d = cell.eng.data
    cell.snapshots.append((len(cell.log), cell.k, d.k, cell.copy_pack(d.edges, d.mask, d.degrees)))
    return {"t0": t0, "t1": t1, "kind": sev.kind if sev is not None else "none", "k_new": cell.k}


def summary(ops) -> list:
    return [f"{op.info['kind']} k={op.info['k_new']} {op.t1 - op.t0:.3f} s" for op in ops]
