"""Faults planted in the timed path, for the controls and the tests.

Each takes the set-up ``harness.Cell`` and breaks what its window runs; a run
with any of them must read ``correct`` false. A traffic mix names its
``control``: the fault that ``control.py`` runs on the chip, the guarantee of
the configuration that the mix exercises, broken.
"""


def unchanged_ingest(cell):
    """Every batch is acknowledged and none is applied."""
    real = cell.eng.ingest

    def ingest(batch, **kw):
        return real(type(batch)(insert=batch.insert[:0], delete=batch.delete[:0]), **kw)
    cell.eng.ingest = ingest


def half_batch(cell):
    """The orderer applies half of each batch; the whole is acknowledged."""
    o = cell.eng.orderer
    real = o.apply

    def apply(batch):
        return real(type(batch)(insert=batch.insert[: len(batch.insert) // 2],
                                delete=batch.delete[: len(batch.delete) // 2]))
    o.apply = apply


def altered_scatter(cell):
    """The first insert of each device scatter lands with a wrong endpoint."""
    real = cell.eng._scatter

    def scatter(ops, deg):
        ops = list(ops)
        i = next((i for i, op in enumerate(ops) if op.valid), None)
        if i is not None:
            op = ops[i]
            ops[i] = type(op)(op.slot, op.u, (op.v + 1) % cell.v, op.valid)
        return real(ops, deg)
    cell.eng._scatter = scatter


def unchanged_rescale(cell):
    """A scale event is reported done and the pack keeps its old layout."""
    from repro.stream.ingest import StreamRescaleStats

    eng = cell.eng

    def rescale(k_new, **kw):
        return StreamRescaleStats(eng.k, int(k_new), eng.orderer.num_edges, 0, 0, 0, 0, 0.0)
    eng.rescale = rescale


def lost_partition(cell):
    """Every compaction of a scale event loses the first partition's edges."""
    eng = cell.eng
    real = eng._compact_program

    def compact_program(key):
        program = real(key)

        def compact(*args):
            edges, mask = program(*args)
            return edges.at[0].set(0), mask.at[0].set(0.0)
        return compact
    eng._compact_program = compact_program


def no_exchange(cell):
    """Every compaction of a scale event gathers on each chip from that
    chip's own rows only: edges whose partition moves to another chip are
    dropped, as a compact with its exchange between chips left out would."""
    import numpy as np

    eng = cell.eng
    real = eng._compact_program

    def compact_program(key):
        program = real(key)
        chips = eng.mesh.devices.size

        def compact(edges_old, src_row, src_col, validf):
            src = np.asarray(src_row)
            dst = np.arange(src.shape[0])[:, None] // (src.shape[0] // chips)
            local = src // (edges_old.shape[0] // chips) == dst
            return program(edges_old, src_row, src_col,
                           eng._host_operand(np.asarray(validf) * local))
        return compact
    eng._compact_program = compact_program


def unchanged_search(cell):
    """A search returns its initial state: only the root reached. Like each
    search fault, it takes whatever operands the mix passes, root last."""
    import jax.numpy as jnp

    def prog(*operands):
        return jnp.full((cell.v,), 1e9).at[operands[-1]].set(0.0), 1
    cell.prog = prog


def altered_search(cell):
    """A search returns the root at distance 1."""
    real = cell.prog

    def prog(*operands):
        dist, iters = real(*operands)
        return dist.at[operands[-1]].set(1.0), iters
    cell.prog = prog


def early_stop(cell):
    """Searches stop after two levels, as too low a bound on iterations
    would: the mix's own program, built with ``max_iters=2``."""
    from repro.graphs import engine as GE

    cell.prog = GE.query_program(cell.mix["search"]["program"], num_vertices=cell.v,
                                 mesh=cell.mesh, max_iters=2)


def control_for(mix: dict):
    """The control fault that the mix names (its ``control``)."""
    return globals()[mix["control"]]
