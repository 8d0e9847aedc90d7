"""Per-kernel allclose tests vs the pure-jnp/numpy oracles (interpret mode)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cep, metrics, ordering
from repro.core.graph import rmat_graph
from repro.kernels import decode_attention as dec
from repro.kernels import edge_spmv, flash_attention, ref, segment_rf
from repro.kernels import ops


# ---------------------------------------------------------------- segment_rf
BW = segment_rf.MAX_BLOCK_W


def _random_rows(c, w, hi=50, pad_rows=()):
    rng = np.random.default_rng(c * 100 + w)
    rows = rng.integers(0, hi, size=(c, w)).astype(np.int32)
    pad_mask = rng.random((c, w)) < 0.2
    rows[pad_mask] = segment_rf.PAD_ID
    rows[list(pad_rows)] = segment_rf.PAD_ID
    return np.sort(rows, axis=1)


def _runs_at_block_boundaries():
    w = 2 * BW + 5
    bw = segment_rf.block_width(w)
    rows = np.full((3, w), segment_rf.PAD_ID, np.int32)
    rows[0, : bw + 1] = 5  # one run ending one key into the second block
    rows[1, : bw - 1] = 1  # the id changes one key before the boundary...
    rows[1, bw - 1 : 2 * bw + 1] = 2  # ...and its run straddles the next one
    rows[2, :] = np.arange(w) // bw  # the id changes exactly at each boundary
    return rows


@pytest.mark.parametrize(
    "make_rows",
    [
        pytest.param(functools.partial(_random_rows, c, w), id=f"{c}x{w}")
        for c, w in [(4, 8), (16, 64), (7, 40), (33, 24)]
    ]
    + [
        # Widths past one block and not a multiple of it: runs of the 50 ids
        # are hundreds of keys long, so they straddle every block boundary.
        pytest.param(functools.partial(_random_rows, 3, BW + 300), id="ragged-width"),
        pytest.param(functools.partial(_random_rows, 9, 3 * BW + 1, hi=7), id="long-runs"),
        # Mostly distinct ids over an exact multiple of the block width.
        pytest.param(functools.partial(_random_rows, 3, 2 * BW, hi=1 << 20), id="distinct"),
        # All-PAD rows, in the first and in a partial second row group.
        pytest.param(
            functools.partial(_random_rows, 10, BW + 129, pad_rows=(0, 9)), id="all-pad-rows"
        ),
        # Runs of one id straddling a block boundary count once (1, 2, 3).
        pytest.param(_runs_at_block_boundaries, id="runs-at-boundaries"),
    ],
)
def test_segment_rf_kernel_matches_ref(make_rows):
    rows_sorted = make_rows()
    got = np.asarray(
        segment_rf.segment_distinct_counts(jnp.asarray(rows_sorted), interpret=True)
    )
    want = ref.segment_distinct_counts_ref(rows_sorted, int(segment_rf.PAD_ID))
    assert np.array_equal(got, want)


def test_rf_kernel_end_to_end_matches_metrics():
    g = rmat_graph(7, 6, seed=0)
    order = ordering.geo_order(g, seed=0)
    s, d = g.src[order], g.dst[order]
    for k in (4, 8, 16):
        got = ops.replication_factor_kernel(s, d, k, g.num_vertices)
        want = metrics.replication_factor_ordered(s, d, k, g.num_vertices)
        assert got == pytest.approx(want, rel=1e-6)


# ----------------------------------------------------------------- edge_spmv
@pytest.mark.parametrize("c,we,wv", [(2, 16, 32), (5, 64, 128), (3, 128, 256)])
def test_spmv_kernel_matches_ref(c, we, wv):
    rng = np.random.default_rng(c)
    src = rng.integers(0, wv + 1, size=(c, we)).astype(np.int32)  # wv == padding
    dst = rng.integers(0, wv + 1, size=(c, we)).astype(np.int32)
    w = rng.standard_normal((c, we)).astype(np.float32)
    x = rng.standard_normal((c, wv)).astype(np.float32)
    got = np.asarray(edge_spmv.spmv_blocked(
        jnp.asarray(src), jnp.asarray(dst), jnp.asarray(w), jnp.asarray(x), interpret=True
    ))
    want = ref.spmv_blocked_ref(src, dst, w, x)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_chunked_spmv_end_to_end():
    g = rmat_graph(6, 4, seed=1)
    order = ordering.geo_order(g, seed=0)
    s, d = g.src[order], g.dst[order]
    k = 4
    bounds = np.asarray(cep.chunk_bounds(g.num_edges, k))
    window = g.num_vertices  # full window → no fallback edges
    starts = [0] * k
    x = np.random.default_rng(0).standard_normal(g.num_vertices).astype(np.float32)
    w = np.ones(g.num_edges, dtype=np.float32)
    y = ops.chunked_spmv(s, d, w, x, bounds, starts, window)
    want = np.zeros_like(x)
    np.add.at(want, d, x[s])
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-4)


# ----------------------------------------------------------- flash attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,h,s,d,window,softcap",
    [
        (1, 2, 128, 64, None, None),
        (2, 1, 256, 32, None, None),
        (1, 2, 256, 64, 128, None),     # sliding window
        (1, 1, 128, 64, None, 30.0),    # gemma2-style softcap
        (2, 2, 384, 128, 256, 50.0),
    ],
)
def test_flash_attention_matches_ref(b, h, s, d, window, softcap, dtype):
    rng = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (b, h, s, d), dtype)
    k = jax.random.normal(kk, (b, h, s, d), dtype)
    v = jax.random.normal(kv, (b, h, s, d), dtype)
    got = flash_attention.flash_attention(
        q, k, v, causal=True, window=window, softcap=softcap, block_q=128, block_kv=128,
        interpret=True,
    )
    want = ref.attention_ref(q, k, v, causal=True, window=window, softcap=softcap)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )


def test_flash_attention_noncausal():
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 128, 32))
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 128, 32))
    v = jax.random.normal(jax.random.PRNGKey(3), (1, 1, 128, 32))
    got = flash_attention.flash_attention(q, k, v, causal=False, interpret=True)
    want = ref.attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------- decode attention
@pytest.mark.parametrize("bh,gq,s,d,block_s", [(2, 4, 512, 64, 128), (1, 1, 1024, 32, 256), (3, 8, 256, 128, 256)])
def test_decode_attention_matches_ref(bh, gq, s, d, block_s):
    rng = jax.random.PRNGKey(42)
    kq, kk, kv, kl = jax.random.split(rng, 4)
    q = jax.random.normal(kq, (bh, gq, d))
    k = jax.random.normal(kk, (bh, s, d))
    v = jax.random.normal(kv, (bh, s, d))
    cache_len = jax.random.randint(kl, (bh,), 1, s + 1, dtype=jnp.int32)
    got = dec.decode_attention(q, k, v, cache_len, block_s=block_s, interpret=True)
    want = ref.decode_attention_ref(q, k, v, cache_len)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_decode_merge_is_associative_across_devices():
    """The LSE merge must give identical results however tiles are grouped —
    this is what makes sequence-parallel sharded decode correct."""
    bh, gq, s, d = 2, 2, 1024, 64
    rng = jax.random.PRNGKey(7)
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (bh, gq, d))
    k = jax.random.normal(kk, (bh, s, d))
    v = jax.random.normal(kv, (bh, s, d))
    cache_len = jnp.full((bh,), s, jnp.int32)
    o, m, l = dec.decode_attention_partials(q, k, v, cache_len, block_s=128, interpret=True)
    # Merge all 8 tiles at once.
    all_at_once, _ = dec.merge_partials(o, m, l, axis=1)
    # Merge per "device" (two groups of 4), then merge the groups.
    o1, m1, l1 = o[:, :4], m[:, :4], l[:, :4]
    o2, m2, l2 = o[:, 4:], m[:, 4:], l[:, 4:]
    g1, lse1 = dec.merge_partials(o1, m1, l1, axis=1)
    g2, lse2 = dec.merge_partials(o2, m2, l2, axis=1)
    # A merged group re-enters the merge as (o=out, m=lse, l=1).
    stacked_o = jnp.stack([g1, g2], axis=1)
    stacked_m = jnp.stack([lse1, lse2], axis=1)  # lse keeps the trailing 1-dim
    stacked_l = jnp.ones_like(stacked_m)
    grouped, _ = dec.merge_partials(stacked_o, stacked_m, stacked_l, axis=1)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(all_at_once), rtol=1e-5, atol=1e-5)
