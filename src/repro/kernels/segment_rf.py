"""Pallas kernel: per-chunk distinct-vertex counting (replication factor).

TPU adaptation of the paper's RF evaluation: the CPU code would walk each
chunk with a hash set; on TPU we (i) sort each chunk's endpoint ids (XLA sort,
done by the caller/ops.py), (ii) run this kernel, which counts boundaries
``ids[i] != ids[i-1]`` per VMEM-resident block — a pure vector op on the VPU,
8×128-lane friendly.

Layout: ids is (num_chunks, width) int32, each row sorted ascending with
padding = PAD_ID (int32 max) at the tail. Output is (num_chunks,) int32
distinct counts.

Tiling: the grid walks (row groups, width blocks). A row of the stream's
objective is millions of keys wide, far past VMEM, so each step holds one
(rows, BW) block; BW is a multiple of 128 lanes, at most ``MAX_BLOCK_W``, and
the width is PAD-padded up to a multiple of it. Each row's last id of the
previous width block is carried in VMEM scratch, so a run of equal ids that
straddles a block boundary counts once; counts accumulate in the resident
output block across the width axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

PAD_ID = jnp.iinfo(jnp.int32).max

# Rows per grid step (the sublane count of an int32 vreg).
BLOCK_ROWS = 8
LANES = 128
# Widest block: (8, 2^14) int32 is 512 KiB, double-buffered well inside VMEM.
MAX_BLOCK_W = 1 << 14


def _segment_rf_kernel(ids_ref, out_ref, last_ref):
    @pl.when(pl.program_id(1) == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)
        last_ref[...] = jnp.full_like(last_ref, -1)

    ids = ids_ref[...]  # (rows, BW) int32, each row sorted
    prev = jnp.concatenate([last_ref[...], ids[:, :-1]], axis=1)
    is_new = (ids != prev) & (ids != PAD_ID)
    out_ref[...] += jnp.sum(is_new.astype(jnp.int32), axis=1, keepdims=True)
    last_ref[...] = ids[:, -1:]


def block_width(w: int) -> int:
    """Lane-aligned block width: the fewest blocks of at most MAX_BLOCK_W,
    each rounded up to 128 lanes (so padding stays under 128 per block)."""
    nb = -(-w // MAX_BLOCK_W)
    return -(-(-(-w // nb)) // LANES) * LANES


@functools.partial(jax.jit, static_argnames=("interpret",))
def segment_distinct_counts(ids_sorted: jax.Array, *, interpret: bool) -> jax.Array:
    """ids_sorted: (C, W) int32 rows sorted ascending, PAD_ID padded → (C,) counts."""
    c, w = ids_sorted.shape
    br = min(c, BLOCK_ROWS)  # a block may span all rows when there are fewer than 8
    bw = block_width(w)
    pad_c, pad_w = (-c) % br, (-w) % bw
    if pad_c or pad_w:
        ids_sorted = jnp.pad(ids_sorted, ((0, pad_c), (0, pad_w)), constant_values=PAD_ID)
    cp, wp = ids_sorted.shape
    out = pl.pallas_call(
        _segment_rf_kernel,
        grid=(cp // br, wp // bw),
        in_specs=[pl.BlockSpec((br, bw), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((cp, 1), jnp.int32),
        scratch_shapes=[pltpu.VMEM((br, 1), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name="segment_rf",
    )(ids_sorted)
    return out[:c, 0]
