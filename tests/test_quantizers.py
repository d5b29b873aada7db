"""Property-based tests (hypothesis) for the quantizer invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# hypothesis is an optional dev dependency (pyproject extra "test");
# without it this module must skip cleanly, not kill collection.
hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import (num_bins, quantize_bhq_stoch, quantize_psq_stoch,
                        quantize_ptq_det, quantize_ptq_stoch, sr_uniform,
                        stochastic_round)

settings.register_profile("ci", max_examples=25, deadline=None)
settings.load_profile("ci")


shapes = st.tuples(st.integers(2, 24), st.integers(2, 48))
bits_st = st.integers(2, 8)
seeds = st.integers(0, 2**30)


def _rand(shape, seed, scale):
    return jax.random.normal(jax.random.PRNGKey(seed), shape) * scale


@given(shapes, bits_st, seeds, st.floats(1e-3, 1e3))
def test_roundtrip_error_bounded_ptq(shape, bits, seed, scale):
    """|dequant(Q(x)) - x| <= bin size = R(x)/B for every entry."""
    x = _rand(shape, seed, scale)
    qt = quantize_ptq_stoch(x, jax.random.PRNGKey(seed + 1), bits)
    binsize = float(jnp.max(x) - jnp.min(x)) / num_bins(bits)
    err = float(jnp.max(jnp.abs(qt.dequant() - x)))
    assert err <= binsize * 1.001 + 1e-6


@given(shapes, bits_st, seeds, st.floats(1e-3, 1e3))
def test_roundtrip_error_bounded_psq(shape, bits, seed, scale):
    """Per-row: error bounded by that row's bin size."""
    x = _rand(shape, seed, scale)
    qt = quantize_psq_stoch(x, jax.random.PRNGKey(seed + 1), bits)
    rb = (jnp.max(x, 1) - jnp.min(x, 1)) / num_bins(bits)
    err = jnp.max(jnp.abs(qt.dequant() - x), axis=1)
    assert bool(jnp.all(err <= rb * 1.001 + 1e-6))


@given(shapes, bits_st, seeds)
def test_codes_in_range(shape, bits, seed):
    x = _rand(shape, seed, 1.0)
    for qt in (quantize_ptq_stoch(x, jax.random.PRNGKey(seed), bits),
               quantize_psq_stoch(x, jax.random.PRNGKey(seed), bits)):
        assert qt.codes.dtype == jnp.uint8
        assert int(jnp.max(qt.codes)) <= num_bins(bits)
        assert int(jnp.min(qt.codes)) >= 0


@given(shapes, seeds)
def test_deterministic_quantizer_is_deterministic(shape, seed):
    """Framework assumption (Sec. 2.1): forward quantizers are deterministic."""
    x = _rand(shape, seed, 1.0)
    a = quantize_ptq_det(x, 8).dequant()
    b = quantize_ptq_det(x, 8).dequant()
    assert bool(jnp.all(a == b))


@given(st.integers(0, 2**30))
def test_stochastic_round_unbiased_and_integer(seed):
    x = jax.random.uniform(jax.random.PRNGKey(seed), (64,)) * 10 - 5
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), 512)
    samples = jax.vmap(lambda k: stochastic_round(x, k))(keys)
    assert bool(jnp.all(samples == jnp.round(samples)))       # integers
    assert bool(jnp.all(jnp.abs(samples - x) < 1.0 + 1e-5))   # adjacent ints
    mean = jnp.mean(samples, 0)
    assert float(jnp.max(jnp.abs(mean - x))) < 0.1            # ~unbiased


def test_sr_uniform_strictly_below_one():
    """The SR rule keeps u in [0, 1) even for the top uint32 patterns
    (a plain uint32 -> f32 cast rounds those up to exactly 1.0)."""
    from repro.kernels.tiling import unit_from_bits
    top = jnp.array([0, 255, 256, 2**32 - 129, 2**32 - 1], jnp.uint32)
    u = np.asarray(unit_from_bits(top))
    assert u.dtype == np.float32
    np.testing.assert_array_equal(
        u, np.array([0.0, 0.0, 2.0**-24, 1 - 2.0**-24, 1 - 2.0**-24],
                    np.float32))
    assert float(jnp.max(sr_uniform(jax.random.PRNGKey(0), (4096,)))) < 1.0


@given(st.integers(8, 64), st.integers(2, 16), bits_st, seeds)
def test_bhq_roundtrip_and_structure(n, d, bits, seed):
    x = _rand((n, d), seed, 1.0).at[0].mul(50.0)
    qt = quantize_bhq_stoch(x, jax.random.PRNGKey(seed + 1), bits)
    assert qt.codes.dtype == jnp.uint8
    assert int(jnp.max(qt.codes)) <= num_bins(bits)
    deq = qt.dequant()
    assert deq.shape == x.shape
    assert bool(jnp.all(jnp.isfinite(deq)))
    # the block maps built from the tensor's own fields: M^{-1} M = I, where
    # M = Q S P (the transpose of P^T S Q), and the Householder Q alone (no
    # permutation, unit scales) is involutory
    from repro.core.bhq import _mixing
    hi = jax.lax.Precision.HIGHEST
    nv, cf, s = qt.n_vec[..., 0], qt.coef[..., 0], qt.row_scale[..., 0]
    m = jnp.swapaxes(jax.vmap(_mixing)(qt.inv_perm, qt.seg, nv, cf, s), 1, 2)
    blk = qt.seg.shape[-1]
    eye = jnp.eye(blk)

    def near_identity(a, b):
        # f32 product rounding: |fl(AB) - AB| <= blk * eps * |A| |B|
        err = jnp.abs(jnp.matmul(a, b, precision=hi) - eye)
        bound = 4 * blk * float(jnp.finfo(jnp.float32).eps) * jnp.matmul(
            jnp.abs(a), jnp.abs(b), precision=hi)
        return bool(jnp.all(err <= bound + 1e-7))

    assert near_identity(qt.dequant_map(), m)
    ident = jnp.broadcast_to(jnp.arange(blk), qt.seg.shape)
    q = jax.vmap(_mixing)(ident, qt.seg, nv, cf, jnp.ones_like(s))
    assert float(jnp.max(jnp.abs(q - jnp.swapaxes(q, 1, 2)))) < 1e-6
    assert near_identity(q, q)


@given(seeds)
def test_bhq_block_partition(seed):
    """Block mode must equal concatenating per-block BHQ (independence)."""
    x = _rand((32, 8), seed, 1.0)
    key = jax.random.PRNGKey(seed + 1)
    qt = quantize_bhq_stoch(x, key, 8, block_rows=16)
    assert qt.codes.shape[0] == 2                    # two blocks
    deq = qt.dequant()
    assert deq.shape == (32, 8)
    # unbiasedness per block still holds
    keys = jax.random.split(jax.random.PRNGKey(seed + 2), 256)
    mean = jnp.mean(jax.lax.map(
        lambda k: quantize_bhq_stoch(x, k, 8, block_rows=16).dequant(), keys), 0)
    assert float(jnp.max(jnp.abs(mean - x))) < 0.05 * float(jnp.max(jnp.abs(x))) + 0.05


def test_constant_input_exact():
    """Zero dynamic range: quantizer must return the constant exactly-ish."""
    x = jnp.full((8, 8), 3.25)
    for qt in (quantize_ptq_stoch(x, jax.random.PRNGKey(0), 4),
               quantize_psq_stoch(x, jax.random.PRNGKey(0), 4)):
        assert float(jnp.max(jnp.abs(qt.dequant() - x))) < 1e-5
