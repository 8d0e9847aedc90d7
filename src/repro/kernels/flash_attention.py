"""Pallas kernel: causal flash attention (online softmax), TPU-tiled.

Grid is (batch*heads, num_q_blocks, num_kv_blocks); the kv dimension is the
innermost (sequential on TPU), accumulating into VMEM scratch across kv steps
and writing the output block on the last step. Supports:

  * causal masking,
  * sliding windows (gemma2/gemma3 local layers, hymba SWA),
  * attention logit soft-capping (gemma2),

so it is the shared train/prefill hot-spot kernel for the assigned archs.
Block shapes default to MXU-aligned (128, 128) tiles; accumulation is f32
regardless of input dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_KV = 128
NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, window: int | None, softcap: float | None,
    block_q: int, block_kv: int, num_kv_blocks: int,
):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
    k_pos = kj * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)

    # Skip fully-masked blocks (upper triangle / outside the local window).
    run = jnp.bool_(True)
    if causal:
        run = jnp.logical_and(run, (kj * block_kv) <= (qi * block_q + block_q - 1))
    if window is not None:
        run = jnp.logical_and(run, (kj + 1) * block_kv - 1 >= qi * block_q - window)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)  # (block_q, d)
        k = k_ref[0].astype(jnp.float32)  # (block_kv, d)
        v = v_ref[0].astype(jnp.float32)  # (block_kv, d)
        s = (q @ k.T) * scale  # (block_q, block_kv)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]  # (block_q, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # (block_q, block_kv)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + p @ v
        m_scr[...] = m_new

    @pl.when(kj == num_kv_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "block_q", "block_kv", "interpret", "scale"),
)
def flash_attention(
    q: jax.Array,  # (B, H, S, D)
    k: jax.Array,  # (B, H, S, D)  — GQA repeat done by caller/ops.py
    v: jax.Array,
    *,
    scale: float | None = None,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_kv: int = DEFAULT_BLOCK_KV,
    interpret: bool,
) -> jax.Array:
    b, h, s, d = q.shape
    assert k.shape == (b, h, s, d) and v.shape == (b, h, s, d)
    scale = (d**-0.5) if scale is None else scale
    block_q = min(block_q, s)
    block_kv = min(block_kv, s)
    assert s % block_q == 0 and s % block_kv == 0, (s, block_q, block_kv)
    bh = b * h
    qf = q.reshape(bh, s, d)
    kf = k.reshape(bh, s, d)
    vf = v.reshape(bh, s, d)
    nq = s // block_q
    nkv = s // block_kv
    kernel = functools.partial(
        _flash_kernel,
        scale=float(scale), causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_kv=block_kv, num_kv_blocks=nkv,
    )
    out = pl.pallas_call(
        kernel,
        grid=(bh, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bhi, qi, kj: (bhi, qi, 0)),
            pl.BlockSpec((1, block_kv, d), lambda bhi, qi, kj: (bhi, kj, 0)),
            pl.BlockSpec((1, block_kv, d), lambda bhi, qi, kj: (bhi, kj, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bhi, qi, kj: (bhi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, s, d)
