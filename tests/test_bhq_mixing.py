"""BHQ's block transform as one mixing matmul per block, against the
row-index formulation it replaced.

The oracle below is that formulation: rows permuted with ``g[perm]``, mixed
by a ``segment_sum`` Householder, scattered back with ``.at[perm].set``, the
group search a ``searchsorted`` and the per-group reductions ``segment_*``.
Both share ``_select_g`` and the SR rule, so for one key the codes may
differ only where a value sits within f32 rounding of a bin edge.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quantize_bhq_stoch, quantize_ptq_det
from repro.core.backend import qt_gemm_nt
from repro.core.bhq import _EPS, _blocked_rows, _select_g
from repro.core.quantizers import num_bins, row_dynamic_range, stochastic_round


# ---------------------------------------------------------------------------
# Oracle: the gather / segment_sum / scatter transform
# ---------------------------------------------------------------------------

def _apply_householder(x, seg, n_vec, coef):
    """y = Q x per group: y_j = x_j - n_j * coef_g * (nᵀ x)_g."""
    def one(xb, segb, nb_, cb):
        ntx = jax.ops.segment_sum(nb_ * xb, segb, num_segments=xb.shape[0])
        return xb - nb_ * cb * ntx[segb]
    return jax.vmap(one)(x, seg, n_vec, coef)


def _unpermute(x, inv_perm):
    return jax.vmap(lambda xb, pb: jnp.zeros_like(xb).at[pb].set(xb))(
        x, inv_perm)


def _largest_remainder(weights, total, valid):
    n = weights.shape[0]
    wsum = jnp.maximum(jnp.sum(weights), _EPS)
    raw = total * weights / wsum
    base = jnp.where(valid, jnp.floor(raw).astype(jnp.int32), 0)
    rem = jnp.where(valid, raw - base, -1.0)
    short = total - jnp.sum(base)
    order = jnp.argsort(-rem)
    rank = jnp.zeros(n, jnp.int32).at[order].set(jnp.arange(n, dtype=jnp.int32))
    return base + jnp.where((rank < short) & valid, 1, 0)


def _oracle_block(g, key, valid, bits):
    B = float(num_bins(bits))
    n = g.shape[0]
    mag = jnp.where(valid, jnp.max(jnp.abs(g), axis=-1), -1.0)
    perm = jnp.argsort(-mag)
    gs = g[perm]
    mag_s = jnp.maximum(mag[perm], 0.0)
    n_valid = jnp.sum(valid.astype(jnp.int32))
    rng_s = row_dynamic_range(gs)
    G = jnp.minimum(_select_g(mag_s, rng_s, n, "refined", n_valid), n_valid)
    idx = jnp.arange(n, dtype=jnp.int32)
    is_large = idx < G
    is_pad = idx >= n_valid
    w = jnp.where(is_large, mag_s, 0.0)
    n_small = jnp.maximum(n_valid - G, 0).astype(jnp.float32)
    extras = _largest_remainder(w, n_small, is_large)
    cum = jnp.cumsum(extras)
    p = jnp.clip(idx - G, 0, n - 1)
    small_seg = jnp.searchsorted(cum, p, side="right").astype(jnp.int32)
    seg = jnp.where(is_large, idx, jnp.clip(small_seg, 0, n - 1))
    seg = jnp.where(is_pad, idx, seg)
    lam1_g = jnp.where(is_large, jnp.maximum(rng_s, _EPS), 1.0)
    small_mag = jnp.where(is_large, 0.0, mag_s)
    lam2_g = jnp.maximum(
        2.0 * jax.ops.segment_max(small_mag, seg, num_segments=n), _EPS)
    m_g = jnp.maximum(jax.ops.segment_sum(jnp.ones(n), seg, num_segments=n),
                      1.0)
    denom = lam1_g ** (2 / 3) * m_g ** (-1 / 3) + lam2_g ** (2 / 3) * m_g ** (2 / 3)
    s1 = B * lam1_g ** (-1 / 3) * m_g ** (1 / 6) / denom
    s2 = B * lam2_g ** (-1 / 3) * m_g ** (1 / 6) / denom
    row_scale = jnp.where(is_large, s1[seg], s2[seg])[:, None]
    sqrt_m = jnp.sqrt(m_g)[seg]
    n_vec = (1.0 / sqrt_m - is_large.astype(jnp.float32))[:, None]
    coef_g = jnp.where(m_g > 1.5,
                       jnp.sqrt(m_g) / jnp.maximum(jnp.sqrt(m_g) - 1.0, _EPS),
                       0.0)
    coef = coef_g[seg][:, None]
    y = _apply_householder((row_scale * gs)[None], seg[None], n_vec[None],
                           coef[None])[0]
    zero = jax.ops.segment_min(jnp.min(y, axis=-1), seg, num_segments=n)[seg][:, None]
    codes = jnp.clip(stochastic_round(y - zero, key), 0.0, B).astype(jnp.uint8)
    return codes, zero, row_scale, n_vec, coef, seg, perm


def _oracle_epilogue(qt, t):
    """``Pᵀ S^{-1} Q t`` by segment_sum, division and scatter."""
    y = _apply_householder(t, qt.seg, qt.n_vec, qt.coef) / qt.row_scale
    return _unpermute(y, qt.inv_perm)


# ---------------------------------------------------------------------------
# Equivalence
# ---------------------------------------------------------------------------

def _heavy_rows(rows, d, seed):
    """Rows as a gradient's: about one in twenty is an outlier tens of times
    larger than the rest, so the group search picks nontrivial groups."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    big = jax.random.bernoulli(k2, 0.05, (rows, 1))
    scale = 30.0 * jnp.exp(jax.random.normal(k3, (rows, 1)))
    return jax.random.normal(k1, (rows, d)) * jnp.where(big, scale, 1.0)


@pytest.mark.parametrize("rows,d,block,bits", [
    (64, 24, 16, 5),        # block 16
    (512, 40, 256, 5),      # the benchmark cell's block
    (2048, 24, 1024, 8),    # the library default block
    (300, 24, 256, 5),      # ragged: the last block carries zero-padding rows
    (100, 24, 256, 4),      # fewer rows than a block: one short block
])
def test_mixing_matches_row_index_oracle(rows, d, block, bits):
    x = _heavy_rows(rows, d, seed=rows + block)
    key = jax.random.PRNGKey(7)
    qt = jax.jit(partial(quantize_bhq_stoch, bits=bits, block_rows=block))(
        x, key)

    gb, valid, _ = _blocked_rows(x, block)
    keys = jax.random.split(key, gb.shape[0])
    codes, zero, rs, nv, cf, seg, perm = jax.jit(jax.vmap(
        partial(_oracle_block, bits=bits)))(gb, keys, valid)
    assert float(jnp.max(cf)) > 0          # some group has m > 1: Q != I

    # the grouping is the same integer computation: identical
    np.testing.assert_array_equal(np.asarray(qt.seg), np.asarray(seg))
    np.testing.assert_array_equal(np.asarray(qt.inv_perm), np.asarray(perm))
    for new, old in ((qt.row_scale, rs), (qt.n_vec, nv), (qt.coef, cf)):
        np.testing.assert_allclose(np.asarray(new), np.asarray(old),
                                   rtol=1e-6, atol=0)
    # zero and codes differ only by f32 rounding of the mixed values
    scale = float(jnp.max(jnp.abs(zero))) + num_bins(bits)
    assert float(jnp.max(jnp.abs(qt.zero - zero))) <= 1e-5 * scale
    diff = np.abs(np.asarray(qt.codes, np.int32) - np.asarray(codes, np.int32))
    assert diff.max() <= 1
    assert np.mean(diff == 0) >= 0.999

    # dequantization, from the tensor's own fields, by matmul and by oracle
    def block_range(a):
        return jnp.max(a, axis=(1, 2), keepdims=True) - jnp.min(
            a, axis=(1, 2), keepdims=True)

    t = qt.codes.astype(jnp.float32) + qt.zero
    want = _oracle_epilogue(qt, t)
    got = qt.dequant().reshape(-1, d)
    n_pad = want.shape[0] * want.shape[1]
    got = jnp.pad(got, ((0, n_pad - rows), (0, 0))).reshape(want.shape)
    real = valid[..., None]
    err = jnp.where(real, jnp.abs(got - want), 0.0)
    assert bool(jnp.all(err <= 1e-5 * block_range(want)))

    t = jax.random.normal(jax.random.PRNGKey(8), qt.codes.shape[:2] + (33,))
    want = _oracle_epilogue(qt, t)
    err = jnp.abs(qt.dequant_epilogue(t) - want)
    assert bool(jnp.all(err <= 1e-5 * block_range(want)))


# ---------------------------------------------------------------------------
# Structure: no row-index work at the operand's width
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["native", "simulate"])
def test_quantize_and_epilogue_lower_to_mixing_matmuls(backend):
    """``quantize_bhq_stoch`` then ``qt_gemm_nt``'s BHQ path, lowered at
    1024 x 512 rows of dY, blocks of 256 and a 768-wide weight: no while
    loop, no scatter, no gather as wide as a row, and each per-block mixing
    matmul (an operand of shape (4, 256, 256)) at HIGHEST precision.
    ``native`` applies the inverse map to the int GEMM's output
    (``dequant_epilogue``), ``simulate`` to the codes (``dequant``)."""
    rows, d, k, block = 1024, 512, 768, 256

    def fn(g, w, key):
        gq = quantize_bhq_stoch(g, key, 5, block_rows=block)
        return qt_gemm_nt(gq, quantize_ptq_det(w, 8), backend=backend)

    # lowered for the TPU: the CPU lowering rolls the SR draw's threefry
    # rounds into a while loop of its own; the TPU's unrolls them
    text = jax.jit(fn).trace(
        jax.ShapeDtypeStruct((rows, d), jnp.float32),
        jax.ShapeDtypeStruct((k, d), jnp.float32),
        jax.ShapeDtypeStruct((2,), jnp.uint32)).lower(
            lowering_platforms=("tpu",)).as_text()
    assert "stablehlo.while" not in text
    assert "scatter" not in text
    gathers = [line for line in text.splitlines() if "stablehlo.gather" in line]
    for line in gathers:
        result = line.rsplit("->", 1)[-1]
        assert not any(f"x{w}x" in result or f"x{w}>" in result
                       or f"<{w}x" in result for w in (d, k)), line
    mixing = [line for line in text.splitlines()
              if "stablehlo.dot_general" in line
              and f"tensor<{rows // block}x{block}x{block}xf32>" in line]
    assert len(mixing) == 2, mixing            # quantize, then its inverse
    for line in mixing:
        assert "precision = [HIGHEST, HIGHEST]" in line, line
