"""Public jit'd wrappers for the Pallas kernels.

``interpret_mode()`` is the one place that decides how a kernel runs: lowered
to Mosaic on a TPU backend, interpreted (the kernel body as plain JAX ops)
everywhere else. The kernel modules themselves take ``interpret`` with no
default.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import decode_attention as _dec
from . import edge_spmv as _spmv
from . import flash_attention as _fa
from . import segment_rf as _rf
from .segment_rf import PAD_ID

__all__ = [
    "on_tpu",
    "interpret_mode",
    "replication_factor_kernel",
    "chunked_spmv",
    "flash_attention",
    "decode_attention",
    "PAD_ID",
]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_mode() -> bool:
    return not on_tpu()


def replication_factor_kernel(src_ordered, dst_ordered, k: int, num_vertices: int) -> float:
    """RF of CEP chunks over an ordered edge list, via the segment_rf kernel.

    Chunks are padded to a common width; endpoint ids are sorted per row by
    XLA and the Pallas kernel counts distinct ids per row in VMEM.
    """
    from ..core import cep

    e = int(src_ordered.shape[0])
    bounds = np.asarray(cep.chunk_bounds(e, k))
    width = int(np.max(np.diff(bounds))) * 2
    width = max(8, int(np.ceil(width / 8)) * 8)
    rows = np.full((k, width), int(PAD_ID), dtype=np.int32)
    src_ordered = np.asarray(src_ordered)
    dst_ordered = np.asarray(dst_ordered)
    for p in range(k):
        lo, hi = bounds[p], bounds[p + 1]
        ids = np.concatenate([src_ordered[lo:hi], dst_ordered[lo:hi]]).astype(np.int32)
        rows[p, : ids.shape[0]] = ids
    rows = jnp.sort(jnp.asarray(rows), axis=1)
    counts = _rf.segment_distinct_counts(rows, interpret=interpret_mode())
    return float(jnp.sum(counts)) / float(num_vertices)


def chunked_spmv(src, dst, weights, x, chunk_bounds, window_starts, window_size: int):
    """y[dst] += w·x[src] over GEO-ordered edge chunks via the blocked kernel.

    Caller supplies per-chunk vertex-window starts; edges whose endpoints fall
    outside their chunk window are handled in a (small) XLA fallback pass so
    the kernel result is exact.
    """
    c = len(window_starts)
    w_e = int(np.max(np.diff(chunk_bounds)))
    src_l = np.full((c, w_e), window_size, dtype=np.int32)
    dst_l = np.full((c, w_e), window_size, dtype=np.int32)
    wts = np.zeros((c, w_e), dtype=np.float32)
    fallback = []  # (src, dst, w) COO triples outside windows
    src = np.asarray(src)
    dst = np.asarray(dst)
    weights = np.asarray(weights, dtype=np.float32)
    for ci in range(c):
        lo, hi = chunk_bounds[ci], chunk_bounds[ci + 1]
        ws = window_starts[ci]
        for j, e in enumerate(range(lo, hi)):
            sl, dl = src[e] - ws, dst[e] - ws
            if 0 <= sl < window_size and 0 <= dl < window_size:
                src_l[ci, j] = sl
                dst_l[ci, j] = dl
                wts[ci, j] = weights[e]
            else:
                fallback.append((src[e], dst[e], weights[e]))
    x = np.asarray(x, dtype=np.float32)
    xw = np.stack([x[ws : ws + window_size] for ws in window_starts])
    y_win = _spmv.spmv_blocked(
        jnp.asarray(src_l), jnp.asarray(dst_l), jnp.asarray(wts), jnp.asarray(xw),
        interpret=interpret_mode(),
    )
    y = np.zeros_like(x)
    y_win = np.asarray(y_win)
    for ci, ws in enumerate(window_starts):
        y[ws : ws + window_size] += y_win[ci]
    for s_, d_, w_ in fallback:
        y[d_] += w_ * x[s_]
    return y


def flash_attention(q, k, v, **kw):
    kw.setdefault("interpret", interpret_mode())
    return _fa.flash_attention(q, k, v, **kw)


def decode_attention(q, k, v, cache_len, **kw):
    kw.setdefault("interpret", interpret_mode())
    return _dec.decode_attention(q, k, v, cache_len, **kw)
