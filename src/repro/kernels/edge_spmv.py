"""Pallas kernel: blocked edge-centric gather-reduce (vertex-cut SpMV).

The hot loop of every GAS-style graph application (PageRank/SSSP/WCC) is
``y[dst] += w * x[src]`` over an edge chunk. The GEO ordering guarantees each
chunk touches a *narrow vertex window* (that is exactly what low RF means), so
the TPU-native formulation is:

  per chunk: load the x-window (W_V,) into VMEM, turn the local src/dst ids
  into one-hot matrices, and run two small matmuls on the MXU:

      vals   = onehot(src_local) @ x_window            (W_E,)
      y_win  = onehot(dst_local)^T @ (w * vals)        (W_V,)

This replaces the CPU hash-scatter with systolic matmuls — the adaptation
noted in DESIGN.md §4. The caller (ops.py) pre-windows x per chunk
(XLA dynamic_slice) so every Pallas block shape is static.

Shapes: src_local/dst_local (C, W_E) int32 (padded with W_V ⇒ contributes 0),
x_windows (C, W_V) f32, weights (C, W_E) f32. Output (C, W_V) f32 partial
accumulations, scattered back to the global vector by the caller.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _spmv_kernel(src_ref, dst_ref, w_ref, x_ref, out_ref):
    src = src_ref[...]  # (1, W_E) int32 local ids in [0, W_V] — W_V = padding
    dst = dst_ref[...]
    w = w_ref[...]  # (1, W_E) f32
    x = x_ref[...]  # (1, W_V) f32
    w_e = src.shape[1]
    w_v = x.shape[1]
    # One-hot gather: (W_E, W_V) @ (W_V,) on the MXU. Padding rows are all-zero.
    cols = jax.lax.broadcasted_iota(jnp.int32, (w_e, w_v), 1)
    gather = (cols == src.reshape(w_e, 1)).astype(jnp.float32)
    vals = gather @ x.reshape(w_v, 1)  # (W_E, 1)
    vals = vals * w.reshape(w_e, 1)
    scatter = (cols == dst.reshape(w_e, 1)).astype(jnp.float32)  # (W_E, W_V)
    out_ref[...] = (scatter.T @ vals).reshape(1, w_v)


@functools.partial(jax.jit, static_argnames=("interpret",))
def spmv_blocked(src_local, dst_local, weights, x_windows, *, interpret: bool):
    """Per-chunk gather-reduce. Returns (C, W_V) partial y windows."""
    c, w_e = src_local.shape
    w_v = x_windows.shape[1]
    return pl.pallas_call(
        _spmv_kernel,
        grid=(c,),
        in_specs=[
            pl.BlockSpec((1, w_e), lambda i: (i, 0)),
            pl.BlockSpec((1, w_e), lambda i: (i, 0)),
            pl.BlockSpec((1, w_e), lambda i: (i, 0)),
            pl.BlockSpec((1, w_v), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, w_v), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((c, w_v), jnp.float32),
        interpret=interpret,
    )(src_local, dst_local, weights, x_windows)
