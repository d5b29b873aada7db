"""Pluggable quantized-GEMM execution backend: simulate | native | pallas.

This module is the *single source* of the affine-epilogue algebra that turns
integer GEMM accumulators back into real values (previously duplicated
between ``core/fqt.py:qdot`` and ``kernels/ops.py:fused_qlinear`` with two
incompatible code layouts).  The canonical code layout is the unsigned
``QTensor`` one (codes in ``[0, 2^b-1]``, uint8); the MXU consumes
shifted-signed codes ``c8 = codes - 2^(b-1)`` and the conversion happens
exactly once, at this boundary (``QTensor.int8_codes`` /
``QTensor.from_int8``).

Writing each affine operand over shifted-signed codes,

    A-hat_ik = alpha_a,i * a8_ik + beta_a,i     (per-row or per-tensor)
    B-hat_kj = alpha_b   * b8_kj + beta_b       (per-tensor)

the exact product expands into the one epilogue form every quantized GEMM of
the paper produces (forward Eq. 3 and both backward GEMMs of Eq. 6):

    (A-hat B-hat)_ij = acc_ij*rs_i*cs_j + r2_i*u_j + a_i + b_j

    rs_i = alpha_a,i                   cs_j = alpha_b
    r2_i = beta_a,i                    u_j  = alpha_b*colsum(b8)_j + K*beta_b
    a_i  = alpha_a,i*beta_b*rowsum(a8)_i          b_j = bias (free slot)

Three backends evaluate the same algebra:

  ``simulate``  quantize-dequantize fp32 matmul — the paper's GPU simulation
                (App. E), used for accuracy/variance experiments.
  ``native``    ``lax.dot_general(int8, int8, preferred_element_type=int32)``
                (TPU MXU int8 through XLA) + the epilogue as jnp ops.
  ``pallas``    the fused Pallas TPU kernel (``kernels/q8_matmul.py``):
                int32 accumulation and the epilogue in one VMEM-resident
                pass.  ``interpret=True`` emulates on CPU.

All three are dispatched from the ``_fqt`` custom_vjp (core/fqt.py), so the
*same* quantizer algebra drives the full training step — including the BHQ
``S^{-1}`` epilogue of ``BHQTensor.dequant_epilogue`` on the dX GEMM — not
just a forward benchmark.
"""

from __future__ import annotations

from typing import Optional, Union

import jax
import jax.numpy as jnp

from ..kernels.fused_fqt import (fused_qboth_tn_matmul,
                                 fused_qboth_tn_matmul_xla,
                                 fused_qlhs_matmul, fused_qlhs_matmul_xla,
                                 fused_qlhs_packed_matmul,
                                 fused_qlhs_packed_matmul_xla)
from ..kernels.pack import PackedTensor
from ..kernels.q4_matmul import packed_matmul, packed_matmul_xla
from ..kernels.q8_matmul import q8_matmul
from ..kernels.quantize_sr import quantize_sr_rows, quantize_sr_tensor
from .bhq import BHQTensor
from .registry import BACKENDS
from .quantizers import QTensor, tensor_min_max

__all__ = [
    "BACKENDS", "resolve_interpret", "affine_factors", "epilogue_coeffs",
    "apply_epilogue", "q8_gemm", "qt_gemm", "qt_gemm_tn", "qt_gemm_nt",
    "quantize_sr_rows_qt", "quantize_sr_tensor_qt", "requantize_det",
    "fused_fqt_fwd", "fused_fqt_dw", "fused_fqt_dx",
]

_EPS = 1e-12        # matches core/quantizers._EPS — one zero-range guard


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Pallas interpret mode: explicit policy knob, else CPU/GPU => emulate."""
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# The affine-epilogue algebra (single source)
# ---------------------------------------------------------------------------

def affine_factors(scale, zero, bits: int):
    """(alpha, beta) with ``x-hat = alpha*c8 + beta`` for shifted codes c8.

    ``x-hat = codes/scale + zero`` and ``c8 = codes - 2^(b-1)``, hence
    ``alpha = 1/scale`` and ``beta = 2^(b-1)/scale + zero``.  Shapes follow
    scale/zero: scalar (per-tensor) or (rows, 1) (per-sample).
    """
    off = 1 << (bits - 1)
    alpha = 1.0 / jnp.asarray(scale, jnp.float32)
    beta = off * alpha + jnp.asarray(zero, jnp.float32)
    return alpha, beta


def _vec(v, n: int) -> jax.Array:
    """Normalize a scalar / (n,) / (n,1) coefficient to a (n,) f32 vector."""
    v = jnp.asarray(v, jnp.float32).reshape(-1)
    return v if v.shape[0] == n else jnp.broadcast_to(v, (n,))


def epilogue_coeffs(a8: jax.Array, alpha_a, beta_a,
                    b8: jax.Array, alpha_b, beta_b, bias=None):
    """The epilogue coefficient vectors (rs, cs, r2, u, a, b).

    a8: (M, K) shifted int8 codes, per-row (or per-tensor) affine factors;
    b8: (K, N) shifted int8 codes, *per-tensor* factors (the transpose of a
    per-tensor operand is still per-tensor, which is what lets the same form
    serve A@B, A.T@B and A@B.T).  ``bias`` fills the free b_j slot.
    """
    m, kdim = a8.shape
    n = b8.shape[1]
    alpha_b = jnp.asarray(alpha_b, jnp.float32).reshape(())
    beta_b = jnp.asarray(beta_b, jnp.float32).reshape(())
    rowsum = jnp.sum(a8.astype(jnp.int32), axis=1).astype(jnp.float32)
    colsum = jnp.sum(b8.astype(jnp.int32), axis=0).astype(jnp.float32)
    rs = _vec(alpha_a, m)
    r2 = _vec(beta_a, m)
    cs = jnp.broadcast_to(alpha_b, (n,))
    u = alpha_b * colsum + float(kdim) * beta_b
    a = rs * beta_b * rowsum
    b = jnp.zeros((n,), jnp.float32) if bias is None else _vec(bias, n)
    return rs, cs, r2, u, a, b


def apply_epilogue(acc: jax.Array, rs, cs, r2, u, a, b) -> jax.Array:
    """out[i,j] = acc[i,j]*rs_i*cs_j + r2_i*u_j + a_i + b_j (f32)."""
    return (acc * rs[:, None] * cs[None, :]
            + r2[:, None] * u[None, :] + a[:, None] + b[None, :])


# ---------------------------------------------------------------------------
# Code-level GEMM dispatch
# ---------------------------------------------------------------------------

def q8_gemm(a8: jax.Array, alpha_a, beta_a, b8: jax.Array, alpha_b, beta_b,
            *, backend: str, interpret: Optional[bool] = None,
            bias=None) -> jax.Array:
    """fp32 value of ``A-hat @ B-hat`` from shifted int8 codes."""
    coeffs = epilogue_coeffs(a8, alpha_a, beta_a, b8, alpha_b, beta_b, bias)
    if backend == "pallas":
        return q8_matmul(a8, b8, *coeffs, interpret=resolve_interpret(interpret))
    if backend == "native":
        acc = jax.lax.dot_general(
            a8, b8, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32).astype(jnp.float32)
        return apply_epilogue(acc, *coeffs)
    raise ValueError(f"unknown int-GEMM backend {backend!r}; "
                     f"expected one of {BACKENDS[1:]}")


# ---------------------------------------------------------------------------
# QTensor-level GEMMs — the three GEMMs of the FQT step
# ---------------------------------------------------------------------------

def _codes2d(qt: QTensor) -> jax.Array:
    return qt.int8_codes.reshape(-1, qt.shape[-1])


def qt_gemm(aq: QTensor, bq: Union[QTensor, PackedTensor], *, backend: str,
            interpret: Optional[bool] = None) -> jax.Array:
    """Forward GEMM  ``A-hat @ B-hat``  (Eq. 3: ``Q_f(X) @ Q_theta(W)``).

    A :class:`PackedTensor` B-operand stays bit-packed in HBM on the
    native/pallas backends — the packed GEMM kernels unpack tiles in VMEM
    inside the K-sweep (kernels/q4_matmul.py); ``simulate`` dequantizes
    either container.
    """
    if backend == "simulate":
        return _codes_dequant2d(aq) @ _codes_dequant2d(bq)
    if isinstance(bq, PackedTensor):
        return _packed_gemm(aq, bq, backend=backend, interpret=interpret)
    alpha_a, beta_a = affine_factors(aq.scale, aq.zero, aq.bits)
    alpha_b, beta_b = affine_factors(bq.scale, bq.zero, bq.bits)
    return q8_gemm(_codes2d(aq), alpha_a, beta_a, _codes2d(bq),
                   alpha_b, beta_b, backend=backend, interpret=interpret)


def _packed_gemm(aq: QTensor, pt: PackedTensor, *, backend: str,
                 interpret: Optional[bool] = None, bias=None) -> jax.Array:
    """``A-hat @ B-hat`` with the B codes bit-packed (kernels/q4_matmul.py).

    The epilogue coefficient vectors need the *unpacked* colsum; computing
    them through ``pt.int8_codes`` keeps the unpack transient — XLA fuses
    the shift/mask chain into the reduce, so no unpacked weight tensor
    lands in HBM and the GEMM itself streams the packed bytes.
    """
    a8 = _codes2d(aq)
    alpha_a, beta_a = affine_factors(aq.scale, aq.zero, aq.bits)
    alpha_b, beta_b = affine_factors(pt.scale, pt.zero, pt.bits)
    coeffs = epilogue_coeffs(a8, alpha_a, beta_a,
                             pt.int8_codes.reshape(-1, pt.shape[-1]),
                             alpha_b, beta_b, bias)
    packed2d = pt.packed.reshape(-1, pt.packed.shape[-1])
    if backend == "pallas":
        return packed_matmul(a8, packed2d, *coeffs, wbits=pt.bits,
                             kdim=pt.kdim,
                             interpret=resolve_interpret(interpret))
    if backend == "native":
        return packed_matmul_xla(a8, packed2d, *coeffs, wbits=pt.bits,
                                 kdim=pt.kdim)
    raise ValueError(f"unknown int-GEMM backend {backend!r}; "
                     f"expected one of {BACKENDS[1:]}")


def qt_gemm_tn(aq: QTensor, bq: QTensor, *, backend: str,
               interpret: Optional[bool] = None) -> jax.Array:
    """Weight-grad GEMM  ``A-hat.T @ B-hat``  (``Q_f(X).T @ Q_b1(dY)``).

    Both operands per-tensor (the paper's Q_b1 recipe), so transposing A
    keeps the factors scalar.
    """
    if backend == "simulate":
        return _codes_dequant2d(aq).T @ _codes_dequant2d(bq)
    alpha_a, beta_a = affine_factors(aq.scale, aq.zero, aq.bits)
    alpha_b, beta_b = affine_factors(bq.scale, bq.zero, bq.bits)
    return q8_gemm(_codes2d(aq).T, alpha_a, beta_a, _codes2d(bq),
                   alpha_b, beta_b, backend=backend, interpret=interpret)


def qt_gemm_nt(aq: Union[QTensor, BHQTensor], bq: Union[QTensor,
               PackedTensor], *, backend: str,
               interpret: Optional[bool] = None) -> jax.Array:
    """Activation-grad GEMM  ``A-hat @ B-hat.T``  (``Q_b2(dY) @ Q_theta(W).T``).

    ``aq`` may be per-row (PSQ), per-tensor (PTQ) or a :class:`BHQTensor` —
    for BHQ the ``S^{-1}`` epilogue commutes with the right-matmul
    (DESIGN.md Sec. 3): ``Q_b(g) @ B-hat.T = S^{-1}((codes + Z) @ B-hat.T)``,
    so the int GEMM runs on raw codes and ``dequant_epilogue`` mixes the
    *output* rows afterwards.

    A :class:`PackedTensor` ``bq`` unpacks transiently here (duck-typed
    ``int8_codes``): the dX contraction runs over the *lane* axis of the
    packed layout, which the packed kernels do not cover — the unpack fuses
    into the transpose read, so no packed copy persists across steps.
    """
    if backend == "simulate":
        a = aq.dequant()
        return (a.reshape(-1, a.shape[-1])
                @ _codes_dequant2d(bq).T)
    bt8 = _codes2d(bq).T
    alpha_b, beta_b = affine_factors(bq.scale, bq.zero, bq.bits)
    if isinstance(aq, BHQTensor):
        nb, blk, _ = aq.codes.shape
        a8 = aq.int8_codes.reshape(nb * blk, -1)
        # Householder-domain value = codes + zero, i.e. alpha=1, beta=off+zero
        beta_a = float(aq.int8_offset) + aq.zero.reshape(nb * blk)
        t = q8_gemm(a8, 1.0, beta_a, bt8, alpha_b, beta_b,
                    backend=backend, interpret=interpret)
        t = t.reshape(nb, blk, -1)
        # ragged inputs carry zero-padding rows in the last block — slice
        # back to the real row count after the S^{-1} epilogue
        return aq.dequant_epilogue(t).reshape(nb * blk, -1)[:aq.n_rows]
    alpha_a, beta_a = affine_factors(aq.scale, aq.zero, aq.bits)
    return q8_gemm(_codes2d(aq), alpha_a, beta_a, bt8, alpha_b, beta_b,
                   backend=backend, interpret=interpret)


def _codes_dequant2d(qt) -> jax.Array:
    d = qt.dequant()
    return d.reshape(-1, d.shape[-1])


# ---------------------------------------------------------------------------
# Fused backward quantizers (Pallas quantize_sr kernels -> canonical QTensor)
# ---------------------------------------------------------------------------

def quantize_sr_rows_qt(x2d: jax.Array, key: jax.Array, bits: int,
                        interpret: Optional[bool] = None) -> QTensor:
    """PSQ stochastic quantize through the fused one-pass kernel.

    Bit-identical to ``quantize_psq_stoch(x2d, key, bits)``: both draw the
    SR uniforms from ``jax.random.bits(key, shape)`` by ``sr_uniform``'s rule.
    """
    rbits = jax.random.bits(key, x2d.shape, jnp.uint32)
    c8, scale, zero = quantize_sr_rows(x2d, rbits, bits,
                                       interpret=resolve_interpret(interpret))
    return QTensor.from_int8(c8, scale, zero, bits, x2d.shape)


def quantize_sr_tensor_qt(x2d: jax.Array, key: jax.Array, bits: int,
                          interpret: Optional[bool] = None) -> QTensor:
    """PTQ stochastic quantize through the fused one-pass kernel."""
    rbits = jax.random.bits(key, x2d.shape, jnp.uint32)
    c8, scale, zero = quantize_sr_tensor(x2d, rbits, bits,
                                         interpret=resolve_interpret(interpret))
    return QTensor.from_int8(c8, scale, zero, bits, x2d.shape)


# ---------------------------------------------------------------------------
# Fully-fused FQT GEMMs (kernels/fused_fqt.py dispatch)
#
# The fused forward never materializes the activation's int8 codes, so its
# residuals are (x2, scale, zero); the backward *rematerializes* the codes
# deterministically when it needs them (``requantize_det`` — bit-identical
# because ptq_det is a pure function of (x, scale, zero)).
# ---------------------------------------------------------------------------

def _ptq_range(x2: jax.Array, bits: int):
    """Per-tensor (zero, scale) exactly as ``quantize_ptq_det``/``_stoch``."""
    B = float((1 << bits) - 1)
    zero, hi = tensor_min_max(x2)
    scale = B / jnp.maximum(hi - zero, _EPS)
    return zero, scale


def requantize_det(x2: jax.Array, scale, zero, bits: int) -> QTensor:
    """Rebuild the deterministic-PTQ QTensor from saved (scale, zero).

    Bit-identical to ``quantize_ptq_det(x2, bits)`` when (scale, zero) came
    from it — the backward's rematerialization of the fused forward's
    never-materialized codes (cheaper than re-reducing min/max)."""
    B = (1 << bits) - 1
    codes = jnp.clip(jnp.round(scale * (x2 - zero)), 0, B).astype(jnp.uint8)
    return QTensor(codes=codes, scale=jnp.asarray(scale),
                   zero=jnp.asarray(zero), bits=bits, shape=x2.shape)


def fused_fqt_fwd(x2: jax.Array, wq: Union[QTensor, PackedTensor],
                  bits_act: int, *, backend: str,
                  interpret: Optional[bool] = None):
    """Forward Eq. 3 ``Q_f(x2) @ W-hat`` with Q_f fused into the K-sweep.

    Returns (y, scale_x, zero_x) — the scale/zero are the residuals the
    backward uses to rematerialize the activation codes."""
    M, K = x2.shape
    zero, scale = _ptq_range(x2, bits_act)
    sa = jnp.broadcast_to(scale, (M, 1))
    za = jnp.broadcast_to(zero, (M, 1))
    w8 = wq.int8_codes.reshape(-1, wq.shape[-1])
    alpha_b, beta_b = affine_factors(wq.scale, wq.zero, wq.bits)
    colsum = jnp.sum(w8.astype(jnp.int32), axis=0).astype(jnp.float32)
    u = alpha_b * colsum + float(K) * beta_b
    if isinstance(wq, PackedTensor):
        # packed-weight fused forward: same u (the transient unpack above
        # fuses into the colsum reduce); the GEMM streams the packed bytes
        packed2d = wq.packed.reshape(-1, wq.packed.shape[-1])
        if backend == "pallas":
            y = fused_qlhs_packed_matmul(
                x2, sa, za, packed2d, alpha_b, beta_b, u, bits=bits_act,
                wbits=wq.bits, interpret=resolve_interpret(interpret))
        elif backend == "native":
            y = fused_qlhs_packed_matmul_xla(
                x2, sa, za, packed2d, alpha_b, beta_b, u, bits=bits_act,
                wbits=wq.bits)
        else:
            raise ValueError(f"unknown fused backend {backend!r}; "
                             f"expected one of {BACKENDS[1:]}")
        return y, scale, zero
    if backend == "pallas":
        y = fused_qlhs_matmul(x2, sa, za, None, w8, alpha_b, beta_b, u,
                              bits=bits_act, tune_key="fused_fwd",
                              interpret=resolve_interpret(interpret))
    elif backend == "native":
        y = fused_qlhs_matmul_xla(x2, sa, za, None, w8, alpha_b, beta_b, u,
                                  bits=bits_act)
    else:
        raise ValueError(f"unknown fused backend {backend!r}; "
                         f"expected one of {BACKENDS[1:]}")
    return y, scale, zero


def fused_fqt_dx(g2: jax.Array, key: jax.Array, spec, wq: QTensor, *,
                 backend: str, interpret: Optional[bool] = None,
                 rbits: Optional[jax.Array] = None) -> jax.Array:
    """Activation-grad GEMM ``Q_b2(g2) @ W-hat.T`` (Eq. 6) with Q_b2 (PTQ
    per-tensor or PSQ per-row) fused into the K-sweep.

    SR uniforms are the same ``random.bits(key, g2.shape)`` draw the
    unfused quantizers make for this key, so codes are bit-identical.
    ``rbits`` lets a caller prefetch that draw (it is a kernel input
    operand, not part of the quantize->GEMM->epilogue pipeline)."""
    bits = spec.bits or 8
    B = float((1 << bits) - 1)
    M, N = g2.shape
    if rbits is None:
        rbits = jax.random.bits(key, g2.shape, jnp.uint32)
    if spec.name == "psq":
        zg, hg = jax.lax.optimization_barrier(
            (jnp.min(g2, axis=-1, keepdims=True),
             jnp.max(g2, axis=-1, keepdims=True)))
        sg = B / jnp.maximum(hg - zg, _EPS)
    else:                                   # per-tensor PTQ
        zg0, sg0 = _ptq_range(g2, bits)
        zg = jnp.broadcast_to(zg0, (M, 1))
        sg = jnp.broadcast_to(sg0, (M, 1))
    w8 = wq.int8_codes.reshape(-1, wq.shape[-1])          # (Kw, N) storage
    alpha_b, beta_b = affine_factors(wq.scale, wq.zero, wq.bits)
    # B-operand is w8.T: its colsum over the contraction (N) is w8's rowsum
    rowsum = jnp.sum(w8.astype(jnp.int32), axis=1).astype(jnp.float32)
    u = alpha_b * rowsum + float(N) * beta_b              # (Kw,)
    if backend == "pallas":
        return fused_qlhs_matmul(g2, sg, zg, rbits, w8, alpha_b, beta_b, u,
                                 bits=bits, trans_b=True, tune_key="fused_dx",
                                 interpret=resolve_interpret(interpret))
    if backend == "native":
        return fused_qlhs_matmul_xla(g2, sg, zg, rbits, w8, alpha_b, beta_b,
                                     u, bits=bits, trans_b=True)
    raise ValueError(f"unknown fused backend {backend!r}; "
                     f"expected one of {BACKENDS[1:]}")


def fused_fqt_dw(x2: jax.Array, scale_x, zero_x, bits_act: int,
                 g2: jax.Array, key: jax.Array, bits_wgrad: int, *,
                 backend: str, interpret: Optional[bool] = None,
                 rbits: Optional[jax.Array] = None) -> jax.Array:
    """Weight-grad GEMM ``Q_f(x2).T @ Q_b1(g2)`` (Eq. 6) with both
    quantizes fused into the K-sweep (deterministic X, stochastic per-tensor
    dY).  The epilogue's a_i row vector needs a full column sum of X's
    codes, which the K-sweep never holds — it is rematerialized here as one
    fused XLA reduce over x2 (no int8 tensor in HBM)."""
    bits_wgrad = int(bits_wgrad)
    Bb = float((1 << bits_wgrad) - 1)
    off_b = 1 << (bits_wgrad - 1)
    off_a = 1 << (bits_act - 1)
    Ba = float((1 << bits_act) - 1)
    zg, hg = tensor_min_max(g2)
    sg = Bb / jnp.maximum(hg - zg, _EPS)
    if rbits is None:
        rbits = jax.random.bits(key, g2.shape, jnp.uint32)
    ca = jnp.clip(jnp.round(scale_x * (x2 - zero_x)), 0.0, Ba) - off_a
    alpha_a = 1.0 / scale_x
    alpha_b = 1.0 / sg
    beta_b = off_b * alpha_b + zg
    a_vec = (alpha_a * beta_b) * jnp.sum(ca, axis=0)      # (Kw,)
    if backend == "pallas":
        return fused_qboth_tn_matmul(
            x2, scale_x, zero_x, g2, sg, zg, rbits, a_vec,
            bits_a=bits_act, bits_b=bits_wgrad, tune_key="fused_dw",
            interpret=resolve_interpret(interpret))
    if backend == "native":
        return fused_qboth_tn_matmul_xla(x2, scale_x, zero_x, g2, sg, zg,
                                         rbits, a_vec, bits_a=bits_act,
                                         bits_b=bits_wgrad)
    raise ValueError(f"unknown fused backend {backend!r}; "
                     f"expected one of {BACKENDS[1:]}")
