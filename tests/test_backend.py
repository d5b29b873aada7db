"""Backend parity: simulate / native / pallas(interpret) must agree.

The acceptance contract of the pluggable backend layer (core/backend.py):
for every policy the paper's recipe produces (ptq/psq/bhq gradient
quantizers, QAT), the forward GEMM and BOTH backward GEMMs run through the
selected backend and agree with the fp32 ``simulate`` path to fp32
tolerance — on tile-aligned and ragged (non-tile-multiple) shapes.  The
quantizer *codes* are bit-identical across backends (shared
``(random.bits >> 8) * 2^-24`` SR convention), so the only divergence is GEMM
accumulation order.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import QuantPolicy, fqt_matmul, qt_gemm, quantize_ptq_det

ALIGNED = (32, 16, 8)      # tile multiples all the way down
RAGGED = (33, 17, 9)       # exercises pad-and-slice in every kernel wrapper


def _xwk(mkn, seed=0):
    m, k, n = mkn
    kx, kw, kk = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(kx, (m, k)),
            jax.random.normal(kw, (k, n)) * 0.3,
            kk)


def _value_and_grads(pol, x, w, key):
    y = fqt_matmul(x, w, key, pol)
    gx, gw = jax.grad(
        lambda a, b: jnp.sum(fqt_matmul(a, b, key, pol) ** 2), (0, 1))(x, w)
    return y, gx, gw


@pytest.mark.parametrize("mkn", [ALIGNED, RAGGED],
                         ids=["aligned", "ragged"])
@pytest.mark.parametrize("quant", ["ptq", "psq", "bhq"])
def test_fqt_backend_parity(quant, mkn):
    """fwd + dX + dW agree across all three backends for every Q_b2."""
    x, w, key = _xwk(mkn)
    ref = None
    for backend in ("simulate", "native", "pallas"):
        pol = QuantPolicy.fqt(quant, 5, backend=backend, bhq_block=16,
                              pallas_interpret=True)
        out = _value_and_grads(pol, x, w, key)
        if ref is None:
            ref = out
            continue
        for name, got, want in zip(("y", "dx", "dw"), out, ref, strict=True):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-3, atol=5e-3,
                err_msg=f"{backend}/{quant}/{name} diverged from simulate")


@pytest.mark.parametrize("mkn", [ALIGNED, RAGGED],
                         ids=["aligned", "ragged"])
def test_qat_backend_parity(mkn):
    """QAT: quantized forward through each backend, fp backward — parity."""
    x, w, key = _xwk(mkn, seed=1)
    ref = None
    for backend in ("simulate", "native", "pallas"):
        pol = QuantPolicy.qat(backend=backend, pallas_interpret=True)
        out = _value_and_grads(pol, x, w, key)
        if ref is None:
            ref = out
            continue
        for name, got, want in zip(("y", "dx", "dw"), out, ref, strict=True):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-3, atol=5e-3,
                err_msg=f"{backend}/qat/{name} diverged from simulate")


def test_pallas_codes_bit_identical_to_xla():
    """The fused SR kernels and the XLA quantizers share one uniform stream:
    same key => identical codes (the basis of backend parity)."""
    from repro.core import (quantize_psq_stoch, quantize_ptq_stoch,
                            quantize_sr_rows_qt, quantize_sr_tensor_qt)
    g = jax.random.normal(jax.random.PRNGKey(3), (33, 20)) * 2.0
    key = jax.random.PRNGKey(4)
    a = quantize_psq_stoch(g, key, 6)
    b = quantize_sr_rows_qt(g, key, 6, interpret=True)
    np.testing.assert_array_equal(np.asarray(a.codes), np.asarray(b.codes))
    c = quantize_ptq_stoch(g, key, 6)
    d = quantize_sr_tensor_qt(g, key, 6, interpret=True)
    np.testing.assert_array_equal(np.asarray(c.codes), np.asarray(d.codes))


def test_qdot_duplicate_removed():
    """The epilogue algebra has exactly one home: core/backend.py."""
    import repro.core.fqt as fqt_mod
    assert not hasattr(fqt_mod, "qdot")
    from repro.core import backend
    assert callable(backend.epilogue_coeffs)


def test_pallas_fwd_matches_exact_float():
    """8-bit pallas forward ~= exact float matmul within quantization error."""
    x, w, key = _xwk((40, 24, 12), seed=2)
    pol = QuantPolicy.qat(backend="pallas", pallas_interpret=True)
    y = np.asarray(fqt_matmul(x, w, key, pol))
    exact = np.asarray(x @ w)
    rel = np.max(np.abs(y - exact)) / np.max(np.abs(exact))
    assert rel < 0.05


def test_qt_gemm_rejects_unknown_backend():
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 8))
    q = quantize_ptq_det(x, 8)
    with pytest.raises(ValueError):
        qt_gemm(q, q, backend="tpu_magic")
