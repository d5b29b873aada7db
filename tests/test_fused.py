"""Fused quantize->GEMM->epilogue megakernels + the tiling autotuner.

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
  * autotuner — sweep/persist/lookup plumbing with a fake timer and a
    tmpdir cache, including corrupt-cache fallback and lookup precedence.
"""

import importlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import QuantPolicy, fqt_matmul
from repro.core.backend import (affine_factors, apply_epilogue,
                                epilogue_coeffs, requantize_det)
from repro.core.quantizers import (quantize_psq_stoch, quantize_ptq_det,
                                   quantize_ptq_stoch)
# the package re-exports the autotune *function*; import the module itself
at = importlib.import_module("repro.kernels.autotune")
from repro.kernels.fused_fqt import (fused_qboth_tn_matmul,
                                     fused_qboth_tn_matmul_xla,
                                     fused_qlhs_matmul, fused_qlhs_matmul_xla)
from repro.kernels.q8_matmul import q8_matmul
from repro.kernels.quantize_sr import quantize_sr_rows
from repro.kernels.tiling import pad2d_edge

RAGGED = [(33, 17, 9), (64, 128, 32)]
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
    got_xla = fused_qboth_tn_matmul_xla(x, xq.scale, xq.zero, g, gq1.scale,
                                        gq1.zero, rbits, a_vec,
                                        bits_a=8, bits_b=8)
    got_pl = fused_qboth_tn_matmul(x, xq.scale, xq.zero, g, gq1.scale,
                                   gq1.zero, rbits, a_vec, bits_a=8,
                                   bits_b=8, interpret=True)
    np.testing.assert_allclose(got_xla, want, rtol=2e-6, atol=2e-4)
    np.testing.assert_allclose(got_pl, want, rtol=2e-6, atol=2e-4)


@pytest.mark.parametrize("mkn", RAGGED)
def test_fused_fwd_vs_composed(mkn):
    _check_fwd(mkn)


@pytest.mark.parametrize("mkn", RAGGED)
def test_fused_dx_vs_composed(mkn):
    _check_dx(mkn)


@pytest.mark.parametrize("mkn", RAGGED)
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
# Autotuner
# ---------------------------------------------------------------------------

@pytest.fixture
def tmp_cache(tmp_path, monkeypatch):
    path = tmp_path / "tuning.json"
    monkeypatch.setenv(at.ENV_CACHE, str(path))
    at.reset_cache()
    yield path
    at.reset_cache()


def test_autotune_picks_fastest_and_persists(tmp_cache):
    calls = []

    def fake_timer(tiles):
        calls.append(tiles)
        return {(32, 128, 128): 50.0, (64, 128, 128): 10.0,
                (128, 128, 128): 99.0}[tiles]

    best = at.autotune("q8_matmul", (64, 128, 128), fake_timer,
                       candidates=[(32, 128, 128), (64, 128, 128),
                                   (128, 128, 128)])
    assert best == (64, 128, 128)
    assert len(calls) == 3
    assert tmp_cache.exists()
    # a fresh cache object reads the persisted winner back
    at.reset_cache()
    assert at.lookup_tiles("q8_matmul", (64, 128, 128)) == (64, 128, 128)
    data = json.loads(tmp_cache.read_text())
    [key] = data
    assert key.startswith("q8_matmul/64x128x128/int8/")
    assert data[key]["us_per_call"] == 10.0


def test_autotune_skips_raising_candidates(tmp_cache):
    def flaky(tiles):
        if tiles[0] == 32:
            raise RuntimeError("bad tile")
        return 1.0

    best = at.autotune("q8_matmul", (64, 128, 128), flaky,
                       candidates=[(32, 128, 128), (64, 128, 128)])
    assert best == (64, 128, 128)
    with pytest.raises(ValueError, match="every candidate failed"):
        at.autotune("q8_matmul", (64, 128, 128),
                    lambda t: (_ for _ in ()).throw(RuntimeError("x")),
                    candidates=[(32, 128, 128)])


def test_corrupt_cache_falls_back(tmp_cache):
    tmp_cache.write_text("{not json")
    at.reset_cache()
    with pytest.warns(UserWarning, match="corrupt tuning cache"):
        tiles = at.lookup_tiles("q8_matmul", (512, 1024, 1024))
    # shipped default still reachable through the degraded cache
    assert tiles == at.SHIPPED_DEFAULTS["q8_matmul/512x1024x1024"]


def test_lookup_precedence(tmp_cache):
    shape = (512, 1024, 1024)
    # shipped default applies with an empty cache
    assert at.lookup_tiles("q8_matmul", shape) == \
        at.SHIPPED_DEFAULTS["q8_matmul/512x1024x1024"]
    # platform-agnostic "any" beats shipped
    at.record_tiles("q8_matmul", shape, (64, 128, 128), platform="any")
    assert at.lookup_tiles("q8_matmul", shape) == (64, 128, 128)
    # platform-specific beats "any"
    at.record_tiles("q8_matmul", shape, (32, 256, 128),
                    platform=jax.default_backend())
    assert at.lookup_tiles("q8_matmul", shape) == (32, 256, 128)
    # unknown shape/kernel falls through to the caller's default
    assert at.lookup_tiles("q8_matmul", (7, 7, 7), default=(1, 2, 3)) == \
        (1, 2, 3)


def test_unset_cache_serves_shipped_tiles_and_records_source(monkeypatch):
    """Without $REPRO_TUNING_CACHE no file is read or written: tiles come
    from what the repository ships, and each lookup records its source."""
    monkeypatch.delenv(at.ENV_CACHE, raising=False)
    at.reset_cache()
    try:
        assert at.cache_path() is None
        shape = (512, 1024, 1024)
        shipped = at.SHIPPED_DEFAULTS["q8_matmul/512x1024x1024"]
        assert at.lookup_tiles("q8_matmul", shape) == shipped
        assert at.lookup_tiles("fused_fwd", (7, 7, 7)) == at.DEFAULT_TILES
        resolved = at.get_cache().resolved
        assert resolved[("q8_matmul", "512x1024x1024", "int8")] == \
            (shipped, "shipped")
        assert resolved[("fused_fwd", "7x7x7", "int8")] == \
            (at.DEFAULT_TILES, "default")
        with pytest.raises(ValueError, match=at.ENV_CACHE):
            at.record_tiles("q8_matmul", shape, (64, 128, 128))
    finally:
        at.reset_cache()


def test_tile_candidates_respect_budget():
    cands = at.tile_candidates(4096, 4096, 4096, kind="fused_tn")
    assert cands
    for bm, bn, bk in cands:
        assert at.tile_vmem_bytes(bm, bn, bk, "fused_tn") \
            <= at.VMEM_BUDGET_BYTES
        assert bn % 128 == 0 and bk % 128 == 0
    # small problems only get tiles that fit them (rounded up)
    small = at.tile_candidates(16, 128, 128)
    assert all(bm <= 32 for bm, _, _ in small)


def test_vmem_accounting_matches_bench_row():
    bm, bn, bk = 128, 512, 512
    vecs = 4 * (2 * bm + 3 * bn)
    q8 = bm * bk + bk * bn + 8 * bm * bn + vecs
    assert at.q8_tile_vmem_bytes(bm, bn, bk) == q8
    # the fused LHS tile holds f32 X + uint32 bits instead of int8 X
    assert at.q8_tile_vmem_bytes(bm, bn, bk, fused=True) > q8
    assert at.q8_tile_vmem_bytes(bm, bn, bk, fused=True) \
        <= at.VMEM_BUDGET_BYTES


@pytest.mark.slow
def test_tune_sweep_plumbing(tmp_cache):
    """End-to-end --tune on the tiny non-TPU shape: sweeps interpret-mode
    Pallas kernels, persists winners, and lookup_tiles serves them."""
    from benchmarks.bench_kernels import tune
    winners = tune(log=lambda *a, **k: None, iters=1)
    assert winners
    at.reset_cache()
    for key_name, tiles in winners.items():
        kernel, shape = key_name.split("/")
        dims = tuple(int(d) for d in shape.split("x"))
        assert at.lookup_tiles(kernel, dims) == tuple(tiles)
