"""Fused quantize->GEMM->epilogue megakernels + the tiles they run at.

Three layers of evidence:

  * kernel level — the Pallas megakernels (interpret mode) and their XLA
    twins against the *composed* oracle (quantize to a QTensor, int8 GEMM,
    affine epilogue) on ragged shapes.  Tolerances are fp32-roundoff tight:
    both sides consume bit-identical codes (same ``(bits >> 8) * 2^-24`` SR
    uniforms), so the only difference is accumulation order.
  * integration level — value + gradient parity of the full ``_fqt``
    custom_vjp under ``fused=True`` across simulate/native/pallas, and a
    *tight* fused-vs-unfused check on the native backend (same codes, same
    f32 accumulation — this is the bit-identical-SR evidence: a single
    differing uniform would shift a code by a full bin).
  * tiles — the weight-gradient kernel's shape rule, rule-vs-default
    resolution, and the tiles at every kernel site of the benchmark's
    training cells, pinned so that a change to them is a deliberate one.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import QuantPolicy, fqt_matmul
from repro.core.backend import (affine_factors, apply_epilogue,
                                epilogue_coeffs, requantize_det)
from repro.core.quantizers import (quantize_psq_stoch, quantize_ptq_det,
                                   quantize_ptq_stoch)
from repro.kernels import tiling
from repro.kernels.fused_fqt import (fused_qboth_tn_matmul,
                                     fused_qboth_tn_matmul_xla,
                                     fused_qlhs_matmul, fused_qlhs_matmul_xla)
from repro.kernels.q8_matmul import q8_matmul
from repro.kernels.quantize_sr import quantize_sr_rows
from repro.kernels.tiling import pad2d_edge

RAGGED = [(33, 17, 9), (64, 128, 32)]
# the weight gradient of (40, 200, 300) is the kernel's (M, K, N) =
# (200, 40, 300): its shape rule takes bm = 256 > 128 (one row stripe) and
# bn = 384, so the unpadded operands end inside the edge blocks
RAGGED_DW = RAGGED + [(40, 200, 300)]
RAGGED_SLOW = [(130, 70, 258)]


def _compose(aq, b8, alpha_b, beta_b, trans_b=False):
    """The unfused reference: materialized codes -> GEMM -> epilogue."""
    a8 = aq.int8_codes.reshape(-1, aq.shape[-1])
    alpha_a, beta_a = affine_factors(aq.scale, aq.zero, aq.bits)
    bt = (b8.T if trans_b else b8)
    coeffs = epilogue_coeffs(a8, alpha_a, beta_a, bt, alpha_b, beta_b)
    acc = a8.astype(jnp.float32) @ bt.astype(jnp.float32)
    return apply_epilogue(acc, *coeffs)


def _fwd_case(mkn):
    M, K, N = mkn
    kx, kw, kg = jax.random.split(jax.random.PRNGKey(M * 7 + N), 3)
    x = jax.random.normal(kx, (M, K))
    w = jax.random.normal(kw, (K, N)) * 0.3
    g = jax.random.normal(kg, (M, N)) * 2.0
    return x, w, g


def _check_fwd(mkn):
    M, K, N = mkn
    x, w, _ = _fwd_case(mkn)
    wq = quantize_ptq_det(w, 8)
    w8 = wq.int8_codes
    ab, bb = affine_factors(wq.scale, wq.zero, wq.bits)
    xq = quantize_ptq_det(x, 8)
    sa = jnp.broadcast_to(xq.scale, (M, 1))
    za = jnp.broadcast_to(xq.zero, (M, 1))
    u = (ab * jnp.sum(w8.astype(jnp.int32), axis=0).astype(jnp.float32)
         + float(K) * bb)
    want = _compose(xq, w8, ab, bb)
    got_xla = fused_qlhs_matmul_xla(x, sa, za, None, w8, ab, bb, u, bits=8)
    got_pl = fused_qlhs_matmul(x, sa, za, None, w8, ab, bb, u, bits=8,
                               interpret=True)
    np.testing.assert_allclose(got_xla, want, rtol=2e-6, atol=2e-5)
    np.testing.assert_allclose(got_pl, want, rtol=2e-6, atol=2e-5)


def _check_dx(mkn):
    """SR LHS (per-row PSQ scales) against W.T — bit-identical uniforms."""
    M, K, N = mkn
    _, w, g = _fwd_case(mkn)
    wq = quantize_ptq_det(w, 8)
    w8 = wq.int8_codes
    ab, bb = affine_factors(wq.scale, wq.zero, wq.bits)
    kk = jax.random.PRNGKey(M * 13 + N)
    gq = quantize_psq_stoch(g, kk, 6)
    rbits = jax.random.bits(kk, g.shape, jnp.uint32)
    B = float((1 << 6) - 1)
    zg = jnp.min(g, axis=-1, keepdims=True)
    sg = B / jnp.maximum(jnp.max(g, axis=-1, keepdims=True) - zg, 1e-12)
    u = (ab * jnp.sum(w8.astype(jnp.int32), axis=1).astype(jnp.float32)
         + float(N) * bb)
    want = _compose(gq, w8, ab, bb, trans_b=True)
    got_xla = fused_qlhs_matmul_xla(g, sg, zg, rbits, w8, ab, bb, u,
                                    bits=6, trans_b=True)
    got_pl = fused_qlhs_matmul(g, sg, zg, rbits, w8, ab, bb, u, bits=6,
                               trans_b=True, interpret=True)
    np.testing.assert_allclose(got_xla, want, rtol=2e-6, atol=2e-5)
    np.testing.assert_allclose(got_pl, want, rtol=2e-6, atol=2e-5)


def _check_dw(mkn):
    """TN megakernel: det A + SR B quantized inside the contraction sweep."""
    M, K, N = mkn
    x, _, g = _fwd_case(mkn)
    kk = jax.random.PRNGKey(M * 29 + N)
    gq1 = quantize_ptq_stoch(g, kk, 8)
    rbits = jax.random.bits(kk, g.shape, jnp.uint32)
    xq = quantize_ptq_det(x, 8)
    aa, _ = affine_factors(xq.scale, xq.zero, 8)
    ag, bg = affine_factors(gq1.scale, gq1.zero, 8)
    coeffs = epilogue_coeffs(xq.int8_codes.T, aa,
                             affine_factors(xq.scale, xq.zero, 8)[1],
                             gq1.int8_codes, ag, bg)
    want = apply_epilogue(
        xq.int8_codes.astype(jnp.float32).T
        @ gq1.int8_codes.astype(jnp.float32), *coeffs)
    a_vec = (aa * bg) * jnp.sum(xq.int8_codes.astype(jnp.float32), axis=0)
    # jitted, as the native backend runs it: XLA then fuses the epilogue as
    # the interpreted kernel's body does (op by op rounds it differently)
    twin = jax.jit(functools.partial(fused_qboth_tn_matmul_xla, bits_a=8,
                                     bits_b=8))
    got_xla = twin(x, xq.scale, xq.zero, g, gq1.scale, gq1.zero, rbits,
                   a_vec)
    got_pl = fused_qboth_tn_matmul(x, xq.scale, xq.zero, g, gq1.scale,
                                   gq1.zero, rbits, a_vec, bits_a=8,
                                   bits_b=8, interpret=True)
    np.testing.assert_allclose(got_xla, want, rtol=2e-6, atol=2e-4)
    np.testing.assert_allclose(got_pl, want, rtol=2e-6, atol=2e-4)
    # the shape rule's tiles change only the schedule: exact int32 sums of
    # the same codes and an elementwise epilogue give the same bits as the
    # old fixed tiles and as the XLA twin
    got_fixed = fused_qboth_tn_matmul(x, xq.scale, xq.zero, g, gq1.scale,
                                      gq1.zero, rbits, a_vec, bits_a=8,
                                      bits_b=8, bm=128, bn=512, bk=512,
                                      interpret=True)
    np.testing.assert_array_equal(np.asarray(got_pl), np.asarray(got_fixed))
    np.testing.assert_array_equal(np.asarray(got_pl), np.asarray(got_xla))


@pytest.mark.parametrize("mkn", RAGGED)
def test_fused_fwd_vs_composed(mkn):
    _check_fwd(mkn)


@pytest.mark.parametrize("mkn", RAGGED)
def test_fused_dx_vs_composed(mkn):
    _check_dx(mkn)


@pytest.mark.parametrize("mkn", RAGGED_DW)
def test_fused_dw_vs_composed(mkn):
    _check_dw(mkn)


@pytest.mark.slow
@pytest.mark.parametrize("mkn", RAGGED_SLOW)
def test_fused_kernels_vs_composed_slow(mkn):
    _check_fwd(mkn)
    _check_dx(mkn)
    _check_dw(mkn)


def test_requantize_det_bit_identical():
    """The fused forward's residual contract: (x, scale, zero) rebuilds the
    exact codes the unfused path would have materialized."""
    x = jax.random.normal(jax.random.PRNGKey(3), (37, 21))
    xq = quantize_ptq_det(x, 8)
    re = requantize_det(x, xq.scale, xq.zero, 8)
    np.testing.assert_array_equal(np.asarray(xq.codes), np.asarray(re.codes))


# ---------------------------------------------------------------------------
# Integration: the full custom_vjp under fused=True
# ---------------------------------------------------------------------------

def _value_and_grads(pol, x, w, key):
    y = fqt_matmul(x, w, key, pol)
    gx, gw = jax.grad(
        lambda a, b: jnp.sum(fqt_matmul(a, b, key, pol) ** 2), (0, 1))(x, w)
    return y, gx, gw


@pytest.mark.parametrize("quant", ["ptq", "psq"])
def test_fqt_fused_gradient_parity(quant):
    m, k, n = 33, 17, 9
    kx, kw, kk = jax.random.split(jax.random.PRNGKey(m), 3)
    x = jax.random.normal(kx, (m, k))
    w = jax.random.normal(kw, (k, n)) * 0.3
    ref = _value_and_grads(
        QuantPolicy.fqt(quant, 5, backend="simulate"), x, w, kk)
    for backend in ("native", "pallas"):
        pol = QuantPolicy.fqt(quant, 5, backend=backend,
                              pallas_interpret=True, fused=True)
        out = _value_and_grads(pol, x, w, kk)
        for nm, got, want in zip(("y", "dx", "dw"), out, ref, strict=True):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-3, atol=5e-3,
                err_msg=f"{backend}/fused/{quant}/{nm}")
    # fused vs unfused on the same backend: bit-identical codes (same SR
    # uniforms), f32 accumulation both sides -> roundoff-tight
    a = _value_and_grads(
        QuantPolicy.fqt(quant, 5, backend="native", fused=True), x, w, kk)
    b = _value_and_grads(
        QuantPolicy.fqt(quant, 5, backend="native", fused=False), x, w, kk)
    for nm, got, want in zip(("y", "dx", "dw"), a, b, strict=True):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=5e-5, atol=5e-4,
                                   err_msg=f"tight fused-vs-unfused {nm}")


def test_fqt_fused_bhq_falls_back():
    """BHQ has no fused agrad kernel — the role falls back to the unfused
    path inside the same backward and still matches simulate."""
    m, k, n = 32, 16, 8
    kx, kw, kk = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(kx, (m, k))
    w = jax.random.normal(kw, (k, n)) * 0.3
    ref = _value_and_grads(
        QuantPolicy.fqt("bhq", 5, backend="simulate", bhq_block=16),
        x, w, kk)
    out = _value_and_grads(
        QuantPolicy.fqt("bhq", 5, backend="native", bhq_block=16,
                        fused=True), x, w, kk)
    for nm, got, want in zip(("y", "dx", "dw"), out, ref, strict=True):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-3, atol=5e-3, err_msg=nm)


def test_fqt_fused_qat_remat():
    """QAT under fused=True: forward fuses, backward rematerializes the
    activation codes from the (x, scale, zero) residuals."""
    m, k, n = 33, 17, 9
    kx, kw, kk = jax.random.split(jax.random.PRNGKey(9), 3)
    x = jax.random.normal(kx, (m, k))
    w = jax.random.normal(kw, (k, n)) * 0.3
    ref = _value_and_grads(QuantPolicy.qat(backend="simulate"), x, w, kk)
    out = _value_and_grads(
        QuantPolicy.qat(backend="native", fused=True), x, w, kk)
    for nm, got, want in zip(("y", "dx", "dw"), out, ref, strict=True):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-3, atol=5e-3, err_msg=nm)


# ---------------------------------------------------------------------------
# Validation errors
# ---------------------------------------------------------------------------

def test_q8_matmul_contraction_mismatch():
    x8 = jnp.zeros((8, 16), jnp.int8)
    y8 = jnp.zeros((17, 8), jnp.int8)
    v = jnp.zeros((8,)), jnp.zeros((8,))
    with pytest.raises(ValueError, match="contraction mismatch"):
        q8_matmul(x8, y8, v[0], v[1], v[0], v[1], v[0], v[1])


def test_q8_matmul_rejects_misaligned_tiles():
    x8 = jnp.zeros((64, 256), jnp.int8)
    y8 = jnp.zeros((256, 128), jnp.int8)
    m = jnp.zeros((64,))
    n = jnp.zeros((128,))
    with pytest.raises(ValueError) as ei:
        q8_matmul(x8, y8, m, n, m, n, m, n, bm=48, bn=128, bk=128)
    msg = str(ei.value)
    assert "64x256x128" in msg and "48" in msg  # shape + tile in message
    # interpret mode lifts the MXU alignment requirement
    q8_matmul(x8, y8, m, n, m, n, m, n, bm=48, bn=128, bk=128,
              interpret=True)


def test_q8_matmul_rejects_nonpositive_tiles():
    x8 = jnp.zeros((8, 128), jnp.int8)
    y8 = jnp.zeros((128, 128), jnp.int8)
    m = jnp.zeros((8,))
    n = jnp.zeros((128,))
    with pytest.raises(ValueError, match="positive"):
        q8_matmul(x8, y8, m, n, m, n, m, n, bm=0, interpret=True)


@pytest.mark.parametrize("bits", [1, 9, 0])
def test_bits_range_rejected(bits):
    x = jnp.zeros((8, 16))
    rb = jnp.zeros((8, 16), jnp.uint32)
    with pytest.raises(ValueError, match="bits"):
        quantize_sr_rows(x, rb, bits=bits, interpret=True)


def test_fused_qlhs_contraction_mismatch():
    x = jnp.zeros((8, 16))
    w8 = jnp.zeros((17, 8), jnp.int8)
    s = jnp.ones((8, 1))
    with pytest.raises(ValueError, match="contraction mismatch"):
        fused_qlhs_matmul_xla(x, s, s, None, w8, 1.0, 0.0,
                              jnp.zeros((8,)), bits=8)


# ---------------------------------------------------------------------------
# pad2d_edge / ragged-shape range regression
# ---------------------------------------------------------------------------

def test_pad2d_edge_is_range_inert():
    x = jnp.arange(1., 13.).reshape(3, 4)
    p = pad2d_edge(x, 5, 7)
    assert p.shape == (5, 7)
    np.testing.assert_array_equal(np.asarray(jnp.max(p, axis=1)[:3]),
                                  np.asarray(jnp.max(x, axis=1)))
    # zero padding would have dragged per-row min to 0 for these rows
    np.testing.assert_array_equal(np.asarray(jnp.min(p, axis=1)[:3]),
                                  np.asarray(jnp.min(x, axis=1)))
    # padded tail replicates the last real row — per-tensor range unchanged
    assert float(jnp.min(p)) == float(jnp.min(x))
    assert float(jnp.max(p)) == float(jnp.max(x))
    with pytest.raises(ValueError, match="edge-pad"):
        pad2d_edge(jnp.zeros((0, 4)), 5, 7)


def test_quantize_sr_rows_ragged_positive_rows():
    """Regression: per-row min/max inside the kernel must see edge padding,
    not zeros — all-positive rows at a ragged (non-lane-multiple) width
    would otherwise get min=0 and shifted codes."""
    key = jax.random.PRNGKey(11)
    x = jax.random.uniform(key, (5, 33)) + 2.0        # strictly positive
    rbits = jax.random.bits(key, x.shape, jnp.uint32)
    c8, scale, zero = quantize_sr_rows(x, rbits, bits=8, interpret=True)
    # oracle: the unfused per-row PSQ math on the unpadded input
    B = 255.0
    lo = jnp.min(x, axis=-1, keepdims=True)
    hi = jnp.max(x, axis=-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(zero).reshape(-1, 1),
                               np.asarray(lo), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(scale).reshape(-1, 1),
                               np.asarray(B / jnp.maximum(hi - lo, 1e-12)),
                               rtol=1e-6)
    t = jnp.asarray(scale).reshape(-1, 1) * (x - lo)
    u01 = (rbits >> 8).astype(jnp.int32).astype(jnp.float32) / 2.0 ** 24
    want = jnp.clip(jnp.floor(t + u01), 0.0, B) - 128.0
    np.testing.assert_array_equal(np.asarray(c8, dtype=np.int32),
                                  np.asarray(want, dtype=np.int32))


# ---------------------------------------------------------------------------
# Tiles
# ---------------------------------------------------------------------------

# (M, K, N) of every weight-gradient GEMM of the benchmark's cells: Granite
# q/o, k/v, gate/up, down and the tied head at 8 x 1024 tokens; statquant-tx
# qkv-sized, fc1, fc2 and the head at 32 x 512 tokens
DW_SHAPES = [(2048, 8192, 2048), (2048, 8192, 512), (2048, 8192, 8192),
             (8192, 8192, 2048), (2048, 8192, 49408), (512, 16384, 512),
             (512, 16384, 1024), (1024, 16384, 512), (512, 16384, 10240)]


@pytest.mark.parametrize("mkn", DW_SHAPES)
def test_fused_dw_rule_tiles(mkn):
    """The shape rule's tiles are aligned as the kernel requires (bm and bn
    on 128 lanes, bk on 8 sublanes), fit both VMEM budgets, read dY once
    where M <= 2048, and never stream more HBM bytes than the old fixed
    (128, 512, 512)."""
    m, k, n = mkn
    tiles = tiling.fused_tn_tiles(m, k, n)
    bm, bn, bk = tiles
    assert bm > 0 and bn > 0 and bk > 0, tiles
    assert bm % 128 == 0 and bn % 128 == 0 and bk % 8 == 0, tiles
    assert tiling.fused_tn_vmem_bytes(*tiles) <= tiling.VMEM_BUDGET_BYTES
    assert tiling.fused_tn_scoped_vmem_bytes(*tiles) <= \
        tiling.SCOPED_VMEM_BYTES
    assert bk % 32 == 0
    if m <= 2048:
        assert -(-m // bm) == 1, tiles
    assert tiling.fused_tn_hbm_bytes(m, k, n, tiles) <= \
        tiling.fused_tn_hbm_bytes(m, k, n, tiling.DEFAULT_TILES)


def test_fused_dw_rule_resolves_as_rule_and_cache_overrides():
    """``fused_dw`` resolves through its shape rule; a kernel without a
    rule gets the default, or the caller's; each lookup is recorded with
    its source."""
    shape = (2048, 8192, 49408)
    tiles = tiling.lookup_tiles("fused_dw", shape)
    assert tiles == tiling.fused_tn_tiles(*shape)
    assert tiling.RESOLVED[("fused_dw", shape)] == (tiles, "rule")
    assert tiling.lookup_tiles("fused_fwd", (7, 7, 7)) == \
        tiling.DEFAULT_TILES
    assert tiling.RESOLVED[("fused_fwd", (7, 7, 7))] == \
        (tiling.DEFAULT_TILES, "default")
    assert tiling.lookup_tiles("q8_matmul", (7, 7, 7),
                               default=(32, 128, 128)) == (32, 128, 128)


# Every (kernel, (M, K, N)) site the training cells' steps look up, with the
# tiles and source the chip runs them at: each cell's tiles change only when
# this table does.
_RULE, _DEF = "rule", "default"
CELL_SITES = {
    "tx.train.bhq5": {
        ("fused_dw", (512, 16384, 512)): ((512, 512, 1024), _RULE),
        ("fused_dw", (512, 16384, 1024)): ((512, 1024, 256), _RULE),
        ("fused_dw", (1024, 16384, 512)): ((1024, 512, 512), _RULE),
        ("fused_dw", (512, 16384, 10240)): ((512, 1792, 128), _RULE),
        ("fused_fwd", (16384, 512, 512)): ((128, 512, 512), _DEF),
        ("fused_fwd", (16384, 512, 1024)): ((128, 512, 512), _DEF),
        ("fused_fwd", (16384, 1024, 512)): ((128, 512, 512), _DEF),
        ("fused_fwd", (16384, 512, 10240)): ((128, 512, 512), _DEF),
        ("q8_matmul", (16384, 512, 512)): ((128, 512, 512), _DEF),
        ("q8_matmul", (16384, 512, 1024)): ((128, 512, 512), _DEF),
        ("q8_matmul", (16384, 1024, 512)): ((128, 512, 512), _DEF),
        ("q8_matmul", (16384, 10240, 512)): ((128, 512, 512), _DEF),
    },
    "granite2b.train.bhq5": {
        ("fused_dw", (2048, 8192, 2048)): ((2048, 512, 128), _RULE),
        ("fused_dw", (2048, 8192, 512)): ((2048, 512, 128), _RULE),
        ("fused_dw", (2048, 8192, 8192)): ((2048, 512, 128), _RULE),
        ("fused_dw", (8192, 8192, 2048)): ((2048, 512, 128), _RULE),
        ("fused_dw", (2048, 8192, 49408)): ((2048, 512, 128), _RULE),
        ("fused_fwd", (8192, 2048, 2048)): ((128, 512, 512), _DEF),
        ("fused_fwd", (8192, 2048, 512)): ((128, 512, 512), _DEF),
        ("fused_fwd", (8192, 2048, 8192)): ((128, 512, 512), _DEF),
        ("fused_fwd", (8192, 8192, 2048)): ((128, 512, 512), _DEF),
        ("fused_fwd", (8192, 2048, 49408)): ((128, 512, 512), _DEF),
        ("q8_matmul", (8192, 2048, 2048)): ((128, 512, 512), _DEF),
        ("q8_matmul", (8192, 512, 2048)): ((128, 512, 512), _DEF),
        ("q8_matmul", (8192, 2048, 8192)): ((128, 512, 512), _DEF),
        ("q8_matmul", (8192, 8192, 2048)): ((128, 512, 512), _DEF),
        ("q8_matmul", (8192, 49408, 2048)): ((128, 512, 512), _DEF),
    },
}
_SITES = [(cell, kernel, shape, want)
          for cell, sites in CELL_SITES.items()
          for (kernel, shape), want in sites.items()]


@pytest.mark.parametrize(
    "cell,kernel,shape,want", _SITES,
    ids=[f"{c.split('.')[0]}-{k}-{'x'.join(map(str, s))}"
         for c, k, s, _ in _SITES])
def test_cell_site_tiles(cell, kernel, shape, want):
    assert tiling.lookup_tiles(kernel, shape) == want[0]
    assert tiling.RESOLVED[(kernel, shape)] == want


def _cell_engine(cell):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench import model, train, train_granite
    conf_name, traffic_name, cls = {
        "tx.train.bhq5": ("statquant-tx", "train.b32s512", train.TrainCell),
        "granite2b.train.bhq5": ("granite-3-2b", "train.b8s1024",
                                 train_granite.GraniteCell),
    }[cell]
    return cls(model.load_json("configs", conf_name),
               model.load_json("traffic", traffic_name)).engine


@pytest.mark.parametrize("cell", sorted(CELL_SITES))
def test_cell_step_looks_up_exactly_the_pinned_sites(cell):
    """Tracing a cell's whole step (shapes only, nothing runs) looks up
    exactly the pinned sites, at the pinned tiles."""
    eng = _cell_engine(cell)
    batch = jax.eval_shape(lambda: eng.batch_fn(0))
    tiling.RESOLVED.clear()
    jax.eval_shape(eng.step_fn, eng.abstract_state, batch)
    assert tiling.RESOLVED == CELL_SITES[cell]
