"""Pallas kernel: single-token GQA decode attention over a long KV cache.

The serve_step hot spot for decode_32k / long_500k shapes. The KV cache is
tiled along the sequence axis; each grid step emits a *partial* (o, m, l)
triple for its tile, and the caller merges partials with a numerically-stable
LSE combine. The same merge composes across devices, which is exactly how the
sequence-parallel sharded-decode path in launch/sharding.py works — the kernel
is the per-device building block.

Shapes (per call): q (B*Hkv, Gq, D) — Gq = query heads per kv head,
k/v (B*Hkv, S, D). Output partials: o (B*Hkv, nb, Gq, D), m/l (B*Hkv, nb, Gq, 1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30
DEFAULT_BLOCK_S = 512


def _decode_kernel(q_ref, k_ref, v_ref, len_ref, o_ref, m_ref, l_ref, *, scale, block_s, softcap):
    sj = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)  # (Gq, D)
    k = k_ref[0].astype(jnp.float32)  # (block_s, D)
    v = v_ref[0].astype(jnp.float32)
    s = (q @ k.T) * scale  # (Gq, block_s)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    # Mask positions beyond the true cache length (padding tail).
    pos = sj * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < len_ref[0], s, NEG_INF)
    m = jnp.max(s, axis=1, keepdims=True)  # (Gq, 1)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=1, keepdims=True)
    o_ref[0, 0] = (p @ v) / jnp.maximum(l, 1e-30)
    m_ref[0, 0] = m
    l_ref[0, 0] = l


def merge_partials(o, m, l, axis: int = 1):
    """LSE-merge partial attention outputs along ``axis`` (tiles or devices).

    o: (..., nb, Gq, D) normalized partial outputs; m/l: (..., nb, Gq, 1).
    """
    m_max = jnp.max(m, axis=axis, keepdims=True)
    w = l * jnp.exp(m - m_max)  # un-normalized weights per tile
    denom = jnp.sum(w, axis=axis, keepdims=True)
    out = jnp.sum(o * (w / jnp.maximum(denom, 1e-30)), axis=axis)
    lse = jnp.squeeze(m_max, axis) + jnp.log(jnp.squeeze(denom, axis))
    return out, lse


@functools.partial(jax.jit, static_argnames=("scale", "block_s", "softcap", "interpret"))
def decode_attention_partials(
    q: jax.Array,  # (BHkv, Gq, D)
    k: jax.Array,  # (BHkv, S, D)
    v: jax.Array,
    cache_len: jax.Array,  # (BHkv,) int32 valid lengths
    *,
    scale: float | None = None,
    block_s: int = DEFAULT_BLOCK_S,
    softcap: float | None = None,
    interpret: bool,
):
    bh, gq, d = q.shape
    s = k.shape[1]
    block_s = min(block_s, s)
    assert s % block_s == 0
    nb = s // block_s
    scale = (d**-0.5) if scale is None else scale
    kernel = functools.partial(_decode_kernel, scale=float(scale), block_s=block_s, softcap=softcap)
    o, m, l = pl.pallas_call(
        kernel,
        grid=(bh, nb),
        in_specs=[
            pl.BlockSpec((1, gq, d), lambda bi, sj: (bi, 0, 0)),
            pl.BlockSpec((1, block_s, d), lambda bi, sj: (bi, sj, 0)),
            pl.BlockSpec((1, block_s, d), lambda bi, sj: (bi, sj, 0)),
            pl.BlockSpec((1,), lambda bi, sj: (bi,)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, gq, d), lambda bi, sj: (bi, sj, 0, 0)),
            pl.BlockSpec((1, 1, gq, 1), lambda bi, sj: (bi, sj, 0, 0)),
            pl.BlockSpec((1, 1, gq, 1), lambda bi, sj: (bi, sj, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, nb, gq, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, nb, gq, 1), jnp.float32),
            jax.ShapeDtypeStruct((bh, nb, gq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, cache_len)
    return o, m, l


def decode_attention(q, k, v, cache_len, **kw):
    """Full decode attention: kernel partials + LSE merge. Returns (BHkv, Gq, D)."""
    o, m, l = decode_attention_partials(q, k, v, cache_len, **kw)
    out, _ = merge_partials(o, m, l, axis=1)
    return out
