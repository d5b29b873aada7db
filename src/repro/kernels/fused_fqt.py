"""Fused quantize -> int8 GEMM -> affine-epilogue megakernels (Pallas TPU).

The unfused FQT pipeline materializes three HBM intermediates per GEMM:
the int8 code tensor from ``quantize_sr_*``, its scale/zero vectors, and
the int32-accumulated GEMM output before the epilogue.  These kernels fuse
the whole pipeline into the GEMM's K-sweep: each (bm x bk) tile of the
float operand is quantized *in VMEM* (deterministic round-to-nearest or
stochastic rounding against prefetched ``random.bits`` uniforms), fed to
the MXU as shifted-signed int8, and the affine epilogue of
``core/backend.py`` is applied in-register on the last K step — no int8
codes, scales, or pre-epilogue accumulators ever touch HBM.

Two kernel families cover the three GEMMs of the paper (Eq. 3 / Eq. 6):

  ``fused_qlhs_matmul``      quantize the LHS on the fly against a
                             *materialized* int8 RHS (the weight codes).
                             ``trans_b=False`` is the forward
                             ``Q_f(X) @ Q_theta(W)``; ``trans_b=True`` reads
                             the RHS transposed for the activation-grad
                             ``Q_b2(dY) @ Q_theta(W).T`` (PTQ or PSQ Q_b2 —
                             per-row scale/zero vectors come in as (M, 1)).
  ``fused_qboth_tn_matmul``  quantize BOTH operands on the fly, contracting
                             over the *storage rows* (A.T @ B): the
                             weight-grad ``Q_f(X).T @ Q_b1(dY)`` with
                             deterministic A and stochastic B, both
                             per-tensor.

Quantization inside the kernels uses the exact formulas of
``core/quantizers.py`` — ``SR(t) = floor(t + u)`` with ``u`` from
``tiling.unit_from_bits``,
deterministic ``round(t)`` (round-half-even), ``clip [0, 2^b-1]``, shift
by ``-2^(b-1)`` — with scales/zeros computed *outside* on the unpadded
input, so codes are bit-identical to the unfused ``quantize_sr_*`` /
``quantize_ptq_*`` path for the same PRNG key.

Every kernel has an ``*_xla`` twin with identical quantizer math used (a)
as the ``native``-backend fused path and (b) as the test oracle.  The
twins pick the accumulation dtype per platform: int8 -> int32
``dot_general`` on TPU (the MXU path), f32 code-value GEMM elsewhere —
XLA's CPU/GPU int8 GEMMs are ~6x slower than their f32 ones (measured on
the bench host), and f32 accumulation of code products is exact up to
partial sums of 2^24 (codes are <= 2^8, products <= 2^14, so exact for
K <= 2^10 and within ~2^-24 relative beyond — noise next to quantization
error).

Tile shapes come from the persisted autotuner cache
(``kernels/autotune.py``) unless given explicitly; bad explicit tiles fail
fast in ``check_tiles`` with the shape and tile in the message.

Padding: float operands and epilogue vectors are zero-padded to tile
multiples; in-kernel masks zero the *codes* of padded contraction
rows/cols (``k*bk + iota < kdim``) so the accumulator and the row/col-sum
scratches only ever see real data.  Output rows/cols beyond the real shape
are sliced off by the wrapper.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from . import names
from .autotune import lookup_tiles
from .pack import codes_per_byte, max_safe_k_packed, unpack_tile
from .tiling import (check_bits, check_tiles, pad2d as _pad2,
                     pad_rows as _pad_rows, round_up as _round_up,
                     unit_from_bits)

__all__ = [
    "fused_qlhs_matmul", "fused_qlhs_matmul_xla",
    "fused_qboth_tn_matmul", "fused_qboth_tn_matmul_xla",
    "fused_qlhs_packed_matmul", "fused_qlhs_packed_matmul_xla",
]

# ---------------------------------------------------------------------------
# LHS-quantizing kernel: forward GEMM and activation-grad GEMM
# ---------------------------------------------------------------------------

def _qlhs_kernel(*refs, nk: int, kdim: int, nbins: float, off: int, bk: int,
                 trans_b: bool, stochastic: bool):
    if stochastic:
        (xf_ref, sa_ref, za_ref, rb_ref, y8_ref, ab_ref, bb_ref, u_ref,
         o_ref, acc_ref, rsum_ref) = refs
    else:
        (xf_ref, sa_ref, za_ref, y8_ref, ab_ref, bb_ref, u_ref,
         o_ref, acc_ref, rsum_ref) = refs

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        rsum_ref[...] = jnp.zeros_like(rsum_ref)

    # quantize this (bm, bk) float tile in VMEM — never touches HBM
    t = sa_ref[...] * (xf_ref[...] - za_ref[...])
    if stochastic:
        q = jnp.floor(t + unit_from_bits(rb_ref[...]))
    else:
        q = jnp.round(t)
    c = jnp.clip(q, 0.0, nbins) - off
    # zero the codes of padded K columns so acc and rowsum stay exact
    col = pl.program_id(2) * bk + jax.lax.broadcasted_iota(
        jnp.int32, c.shape, 1)
    c8 = jnp.where(col < kdim, c, 0.0).astype(jnp.int8)

    dims = (((1,), (1,)) if trans_b else ((1,), (0,))), ((), ())
    acc_ref[...] += jax.lax.dot_general(c8, y8_ref[...], dims,
                                        preferred_element_type=jnp.int32)
    rsum_ref[...] += jnp.sum(c8.astype(jnp.int32), axis=1, keepdims=True)

    @pl.when(pl.program_id(2) == nk - 1)
    def _epilogue():
        alpha_a = 1.0 / sa_ref[...]                       # (bm, 1)
        beta_a = off * alpha_a + za_ref[...]
        ab = ab_ref[0, 0]
        bb = bb_ref[0, 0]
        acc = acc_ref[...].astype(jnp.float32)
        a_i = (alpha_a * bb) * rsum_ref[...].astype(jnp.float32)
        o_ref[...] = acc * (alpha_a * ab) + beta_a * u_ref[...] + a_i


def fused_qlhs_matmul(xf: jax.Array, scale_a: jax.Array, zero_a: jax.Array,
                      rbits: Optional[jax.Array], y8: jax.Array,
                      alpha_b, beta_b, u_vec: jax.Array, *, bits: int,
                      trans_b: bool = False, bm: Optional[int] = None,
                      bn: Optional[int] = None, bk: Optional[int] = None,
                      interpret: bool = False,
                      tune_key: str = "fused_fwd") -> jax.Array:
    """``Q(xf) @ B-hat`` (or ``@ B-hat.T``) with the quantize fused in.

    xf: (M, K) f32; scale_a/zero_a: (M, 1) per-row (broadcast a per-tensor
    scalar to (M, 1)); rbits: (M, K) uint32 SR uniforms or ``None`` for
    deterministic round-to-nearest; y8: shifted int8 RHS codes, stored
    (K, N) or — ``trans_b=True`` — (N, K); alpha_b/beta_b: scalar affine
    factors of the RHS; u_vec: (N,) precomputed RHS epilogue column vector
    ``alpha_b * colsum(y8) + K * beta_b`` (colsum over the contraction).
    Returns (M, N) f32.  Tiles default to the autotuner cache under
    ``tune_key``.
    """
    check_bits("fused_qlhs_matmul", bits)
    M, K = xf.shape
    N, Kb = (y8.shape if trans_b else y8.shape[::-1])
    if Kb != K:
        raise ValueError(
            f"fused_qlhs_matmul: contraction mismatch — xf {xf.shape} vs "
            f"y8 {y8.shape} (trans_b={trans_b})")
    tm, tn, tk = lookup_tiles(tune_key, (M, K, N))
    bm, bn, bk = (tm if bm is None else bm, tn if bn is None else bn,
                  tk if bk is None else bk)
    bm = min(bm, _round_up(M, 8))        # f32 A tile: sublane 8
    bn = min(bn, _round_up(N, 128))
    bk = min(bk, _round_up(K, 128))
    check_tiles("fused_qlhs_matmul", (M, K, N), (bm, bn, bk),
                interpret=interpret, multiples=(8, 128, 128))
    Mp, Np, Kp = _round_up(M, bm), _round_up(N, bn), _round_up(K, bk)
    nk = Kp // bk
    nbins = float((1 << bits) - 1)
    off = 1 << (bits - 1)

    stochastic = rbits is not None
    row = lambda i, j, k: (i, 0)
    scalar = lambda i, j, k: (0, 0)
    in_specs = [pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
                pl.BlockSpec((bm, 1), row), pl.BlockSpec((bm, 1), row)]
    operands = [_pad2(xf.astype(jnp.float32), Mp, Kp),
                _pad_rows(scale_a.reshape(M, 1), Mp, edge=True),
                _pad_rows(zero_a.reshape(M, 1), Mp, edge=True)]
    if stochastic:
        in_specs.append(pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)))
        operands.append(_pad2(rbits, Mp, Kp))
    if trans_b:
        in_specs.append(pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)))
        operands.append(_pad2(y8, Np, Kp))
    else:
        in_specs.append(pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)))
        operands.append(_pad2(y8, Kp, Np))
    in_specs += [pl.BlockSpec((1, 1), scalar), pl.BlockSpec((1, 1), scalar),
                 pl.BlockSpec((1, bn), lambda i, j, k: (0, j))]
    operands += [jnp.asarray(alpha_b, jnp.float32).reshape(1, 1),
                 jnp.asarray(beta_b, jnp.float32).reshape(1, 1),
                 _pad2(u_vec.reshape(1, N), 1, Np)]

    out = pl.pallas_call(
        functools.partial(_qlhs_kernel, nk=nk, kdim=K, nbins=nbins, off=off,
                          bk=bk, trans_b=trans_b, stochastic=stochastic),
        grid=(Mp // bm, Np // bn, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32),
                        pltpu.VMEM((bm, 1), jnp.int32)],
        name=names.FUSED_QLHS,
        interpret=interpret,
    )(*operands)
    return out[:M, :N]


# ---------------------------------------------------------------------------
# Both-quantizing transposed kernel: the weight-grad GEMM
# ---------------------------------------------------------------------------

def _qboth_tn_kernel(af_ref, sa_ref, za_ref, bf_ref, sb_ref, zb_ref, rb_ref,
                     a_ref, o_ref, acc_ref, csum_ref, *, nk: int, kdim: int,
                     nbins_a: float, off_a: int, nbins_b: float, off_b: int,
                     bk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        csum_ref[...] = jnp.zeros_like(csum_ref)

    # A: (bk, bm) storage tile of X, deterministic per-tensor quantize; the
    # contraction runs over the storage rows (A.T @ B)
    ta = sa_ref[0, 0] * (af_ref[...] - za_ref[0, 0])
    ca = jnp.clip(jnp.round(ta), 0.0, nbins_a) - off_a
    row_a = pl.program_id(2) * bk + jax.lax.broadcasted_iota(
        jnp.int32, ca.shape, 0)
    ca8 = jnp.where(row_a < kdim, ca, 0.0).astype(jnp.int8)

    # B: (bk, bn) storage tile of dY, stochastic per-tensor quantize
    tb = sb_ref[0, 0] * (bf_ref[...] - zb_ref[0, 0])
    u01 = unit_from_bits(rb_ref[...])
    cb = jnp.clip(jnp.floor(tb + u01), 0.0, nbins_b) - off_b
    row_b = pl.program_id(2) * bk + jax.lax.broadcasted_iota(
        jnp.int32, cb.shape, 0)
    cb8 = jnp.where(row_b < kdim, cb, 0.0).astype(jnp.int8)

    acc_ref[...] += jax.lax.dot_general(
        ca8, cb8, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    csum_ref[...] += jnp.sum(cb8.astype(jnp.int32), axis=0, keepdims=True)

    @pl.when(pl.program_id(2) == nk - 1)
    def _epilogue():
        alpha_a = 1.0 / sa_ref[0, 0]
        beta_a = off_a * alpha_a + za_ref[0, 0]
        alpha_b = 1.0 / sb_ref[0, 0]
        beta_b = off_b * alpha_b + zb_ref[0, 0]
        u_j = alpha_b * csum_ref[...].astype(jnp.float32) \
            + float(kdim) * beta_b                         # (1, bn)
        o_ref[...] = (acc_ref[...].astype(jnp.float32) * (alpha_a * alpha_b)
                      + beta_a * u_j + a_ref[...])


def fused_qboth_tn_matmul(af: jax.Array, scale_a, zero_a, bf: jax.Array,
                          scale_b, zero_b, rbits: jax.Array,
                          a_vec: jax.Array, *, bits_a: int, bits_b: int,
                          bm: Optional[int] = None, bn: Optional[int] = None,
                          bk: Optional[int] = None, interpret: bool = False,
                          tune_key: str = "fused_dw") -> jax.Array:
    """``Q_det(af).T @ Q_sr(bf)`` with both quantizes fused into the K-sweep.

    af: (K, M) f32 storage (the GEMM contracts over the K storage rows);
    bf: (K, N) f32; scale/zero: per-tensor scalars computed on the unpadded
    inputs; rbits: (K, N) uint32 SR uniforms for the B operand; a_vec: (M,)
    precomputed epilogue row vector ``alpha_a * beta_b * colsum(ca8)``
    (colsum over K of A's shifted codes — rematerialized outside, since the
    kernel's A tile never sees a full column).  Returns (M, N) f32.
    """
    check_bits("fused_qboth_tn_matmul", bits_a)
    check_bits("fused_qboth_tn_matmul", bits_b)
    K, M = af.shape
    K2, N = bf.shape
    if K2 != K:
        raise ValueError(
            f"fused_qboth_tn_matmul: contraction mismatch — af {af.shape} "
            f"vs bf {bf.shape} (both contract over storage rows)")
    tm, tn, tk = lookup_tiles(tune_key, (M, K, N))
    bm, bn, bk = (tm if bm is None else bm, tn if bn is None else bn,
                  tk if bk is None else bk)
    # A tile is (bk, bm): bm lands on the lane dim (128), bk on the f32
    # sublane dim (8) — the transpose of the qlhs alignment
    bm = min(bm, _round_up(M, 128))
    bn = min(bn, _round_up(N, 128))
    bk = min(bk, _round_up(K, 8))
    check_tiles("fused_qboth_tn_matmul", (M, K, N), (bm, bn, bk),
                interpret=interpret, multiples=(128, 128, 8))
    Mp, Np, Kp = _round_up(M, bm), _round_up(N, bn), _round_up(K, bk)
    nk = Kp // bk
    scalar = lambda i, j, k: (0, 0)
    out = pl.pallas_call(
        functools.partial(
            _qboth_tn_kernel, nk=nk, kdim=K,
            nbins_a=float((1 << bits_a) - 1), off_a=1 << (bits_a - 1),
            nbins_b=float((1 << bits_b) - 1), off_b=1 << (bits_b - 1),
            bk=bk),
        grid=(Mp // bm, Np // bn, nk),
        in_specs=[
            pl.BlockSpec((bk, bm), lambda i, j, k: (k, i)),
            pl.BlockSpec((1, 1), scalar), pl.BlockSpec((1, 1), scalar),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, 1), scalar), pl.BlockSpec((1, 1), scalar),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32),
                        pltpu.VMEM((1, bn), jnp.int32)],
        name=names.FUSED_QBOTH_TN,
        interpret=interpret,
    )(_pad2(af.astype(jnp.float32), Kp, Mp),
      jnp.asarray(scale_a, jnp.float32).reshape(1, 1),
      jnp.asarray(zero_a, jnp.float32).reshape(1, 1),
      _pad2(bf.astype(jnp.float32), Kp, Np),
      jnp.asarray(scale_b, jnp.float32).reshape(1, 1),
      jnp.asarray(zero_b, jnp.float32).reshape(1, 1),
      _pad2(rbits, Kp, Np),
      _pad2(a_vec.reshape(M, 1), Mp, 1))
    return out[:M, :N]


# ---------------------------------------------------------------------------
# Packed-weight LHS-quantizing kernel: the forward GEMM over bit-packed W
# ---------------------------------------------------------------------------

def _qlhs_packed_kernel(xf_ref, sa_ref, za_ref, p_ref, ab_ref, bb_ref,
                        u_ref, o_ref, acc_ref, rsum_ref, *, nk: int,
                        kdim: int, nbins: float, off_a: int, wbits: int,
                        bk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        rsum_ref[...] = jnp.zeros_like(rsum_ref)

    # quantize this (bm, bk) float tile in VMEM (deterministic forward)
    t = sa_ref[...] * (xf_ref[...] - za_ref[...])
    c = jnp.clip(jnp.round(t), 0.0, nbins) - off_a
    col = pl.program_id(2) * bk + jax.lax.broadcasted_iota(
        jnp.int32, c.shape, 1)
    c8 = jnp.where(col < kdim, c, 0.0).astype(jnp.int8)

    # unpack the (bk/ppb, bn) packed weight tile in VMEM -> shifted int8
    off_b = 1 << (wbits - 1)
    w = unpack_tile(p_ref[...], wbits) - off_b
    row = pl.program_id(2) * bk + jax.lax.broadcasted_iota(
        jnp.int32, w.shape, 0)
    w8 = jnp.where(row < kdim, w, 0).astype(jnp.int8)

    acc_ref[...] += jax.lax.dot_general(c8, w8, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.int32)
    rsum_ref[...] += jnp.sum(c8.astype(jnp.int32), axis=1, keepdims=True)

    # epilogue identical to _qlhs_kernel (bit-exactness vs the unpacked
    # fused kernel rests on the matching expression tree)
    @pl.when(pl.program_id(2) == nk - 1)
    def _epilogue():
        alpha_a = 1.0 / sa_ref[...]                       # (bm, 1)
        beta_a = off_a * alpha_a + za_ref[...]
        ab = ab_ref[0, 0]
        bb = bb_ref[0, 0]
        acc = acc_ref[...].astype(jnp.float32)
        a_i = (alpha_a * bb) * rsum_ref[...].astype(jnp.float32)
        o_ref[...] = acc * (alpha_a * ab) + beta_a * u_ref[...] + a_i


def fused_qlhs_packed_matmul(xf: jax.Array, scale_a: jax.Array,
                             zero_a: jax.Array, packed: jax.Array,
                             alpha_b, beta_b, u_vec: jax.Array, *,
                             bits: int, wbits: int,
                             bm: Optional[int] = None,
                             bn: Optional[int] = None,
                             bk: Optional[int] = None,
                             interpret: bool = False,
                             tune_key: str = "fused_packed") -> jax.Array:
    """``Q_det(xf) @ W-hat`` with W bit-packed in HBM: the forward megakernel
    of the ultra-low-bit track.  Quantizes the (bm, bk) activation tile AND
    unpacks the (bk/ppb, bn) weight tile in VMEM inside the K-sweep, so no
    unpacked weight codes ever touch HBM.

    xf: (M, K) f32; scale_a/zero_a: (M, 1) (broadcast a scalar); packed:
    (ceil(K/ppb), N) uint8 at ``wbits`` codes/byte (kernels/pack.py layout);
    alpha_b/beta_b: the weight's scalar affine factors; u_vec: (N,) the
    precomputed epilogue column vector ``alpha_b*colsum(w8) + K*beta_b``
    (the colsum is a fused unpack+reduce over the packed bytes — see
    ``core/backend.fused_fqt_fwd``).  Returns (M, N) f32.
    """
    check_bits("fused_qlhs_packed_matmul", bits)
    check_bits("fused_qlhs_packed_matmul", wbits, lo=1)
    ppb = codes_per_byte(wbits)
    M, K = xf.shape
    N = packed.shape[1]
    if packed.shape[0] != -(-K // ppb):
        raise ValueError(
            f"fused_qlhs_packed_matmul: packed rows {packed.shape[0]} != "
            f"ceil({K}/{ppb}) for {wbits}-bit codes")
    safe = max_safe_k_packed(bits, wbits)
    if K > safe:
        raise ValueError(
            f"fused_qlhs_packed_matmul: K={K} overflows the int32 "
            f"accumulator for int{bits} x int{wbits} codes "
            f"(max_safe_k={safe})")
    tm, tn, tk = lookup_tiles(tune_key, (M, K, N), dtype=f"int{wbits}")
    bm, bn, bk = (tm if bm is None else bm, tn if bn is None else bn,
                  tk if bk is None else bk)
    bm = min(bm, _round_up(M, 8))        # f32 A tile: sublane 8
    bn = min(bn, _round_up(N, 128))
    bk = min(bk, _round_up(K, 128))      # ppb | 128, so ppb | bk
    check_tiles("fused_qlhs_packed_matmul", (M, K, N), (bm, bn, bk),
                interpret=interpret, multiples=(8, 128, 128))
    Mp, Np, Kp = _round_up(M, bm), _round_up(N, bn), _round_up(K, bk)
    nk = Kp // bk
    row = lambda i, j, k: (i, 0)
    scalar = lambda i, j, k: (0, 0)
    out = pl.pallas_call(
        functools.partial(_qlhs_packed_kernel, nk=nk, kdim=K,
                          nbins=float((1 << bits) - 1),
                          off_a=1 << (bits - 1), wbits=wbits, bk=bk),
        grid=(Mp // bm, Np // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bm, 1), row), pl.BlockSpec((bm, 1), row),
            pl.BlockSpec((bk // ppb, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, 1), scalar), pl.BlockSpec((1, 1), scalar),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32),
                        pltpu.VMEM((bm, 1), jnp.int32)],
        name=names.FUSED_QLHS_PACKED,
        interpret=interpret,
    )(_pad2(xf.astype(jnp.float32), Mp, Kp),
      _pad_rows(scale_a.reshape(M, 1), Mp, edge=True),
      _pad_rows(zero_a.reshape(M, 1), Mp, edge=True),
      _pad2(packed, Kp // ppb, Np),
      jnp.asarray(alpha_b, jnp.float32).reshape(1, 1),
      jnp.asarray(beta_b, jnp.float32).reshape(1, 1),
      _pad2(u_vec.reshape(1, N), 1, Np))
    return out[:M, :N]


# ---------------------------------------------------------------------------
# XLA twins — the `native`-backend fused path and the test oracles
# ---------------------------------------------------------------------------

def _codes_dot(ca: jax.Array, cb: jax.Array, dims) -> jax.Array:
    """Code GEMM with platform-adaptive accumulation (see module docstring)."""
    if jax.default_backend() == "tpu":
        acc = jax.lax.dot_general(ca.astype(jnp.int8), cb.astype(jnp.int8),
                                  dims, preferred_element_type=jnp.int32)
        return acc.astype(jnp.float32)
    return jax.lax.dot_general(ca.astype(jnp.float32),
                               cb.astype(jnp.float32), dims,
                               preferred_element_type=jnp.float32)


def fused_qlhs_matmul_xla(xf: jax.Array, scale_a: jax.Array,
                          zero_a: jax.Array, rbits: Optional[jax.Array],
                          y8: jax.Array, alpha_b, beta_b, u_vec: jax.Array,
                          *, bits: int, trans_b: bool = False) -> jax.Array:
    """XLA twin of :func:`fused_qlhs_matmul` — identical quantizer math,
    single fused elementwise+GEMM graph, no HBM int8 codes by construction
    (XLA fuses the quantize into the GEMM read on TPU; on CPU the f32
    code-value GEMM dominates either way)."""
    check_bits("fused_qlhs_matmul_xla", bits)
    N, Kb = (y8.shape if trans_b else y8.shape[::-1])
    if Kb != xf.shape[-1]:
        raise ValueError(
            f"fused_qlhs_matmul_xla: contraction mismatch — xf {xf.shape} "
            f"vs y8 {y8.shape} (trans_b={trans_b})")
    nbins = float((1 << bits) - 1)
    off = float(1 << (bits - 1))
    t = scale_a * (xf.astype(jnp.float32) - zero_a)
    if rbits is None:
        q = jnp.round(t)
    else:
        q = jnp.floor(t + unit_from_bits(rbits))
    c = jnp.clip(q, 0.0, nbins) - off
    # materialize the codes exactly once — both the GEMM and the row-sum
    # consume them, and XLA otherwise duplicates the quantize into each
    # consumer fusion (measured ~2% on the large bench shapes)
    c = jax.lax.optimization_barrier(c)
    dims = (((1,), (1,)) if trans_b else ((1,), (0,))), ((), ())
    acc = _codes_dot(c, y8, dims)
    alpha_a = 1.0 / scale_a                               # (M, 1)
    beta_a = off * alpha_a + zero_a
    ab = jnp.asarray(alpha_b, jnp.float32)
    bb = jnp.asarray(beta_b, jnp.float32)
    a_i = (alpha_a * bb) * jnp.sum(c, axis=1, keepdims=True)
    return acc * (alpha_a * ab) + beta_a * u_vec[None, :] + a_i


def fused_qboth_tn_matmul_xla(af: jax.Array, scale_a, zero_a, bf: jax.Array,
                              scale_b, zero_b, rbits: jax.Array,
                              a_vec: jax.Array, *, bits_a: int,
                              bits_b: int) -> jax.Array:
    """XLA twin of :func:`fused_qboth_tn_matmul`."""
    check_bits("fused_qboth_tn_matmul_xla", bits_a)
    check_bits("fused_qboth_tn_matmul_xla", bits_b)
    if bf.shape[0] != af.shape[0]:
        raise ValueError(
            f"fused_qboth_tn_matmul_xla: contraction mismatch — af "
            f"{af.shape} vs bf {bf.shape} (both contract over storage rows)")
    K = af.shape[0]
    nbins_a = float((1 << bits_a) - 1)
    off_a = float(1 << (bits_a - 1))
    nbins_b = float((1 << bits_b) - 1)
    off_b = float(1 << (bits_b - 1))
    sa = jnp.asarray(scale_a, jnp.float32)
    za = jnp.asarray(zero_a, jnp.float32)
    sb = jnp.asarray(scale_b, jnp.float32)
    zb = jnp.asarray(zero_b, jnp.float32)
    ca = jnp.clip(jnp.round(sa * (af.astype(jnp.float32) - za)),
                  0.0, nbins_a) - off_a
    u01 = unit_from_bits(rbits)
    cb = jnp.clip(jnp.floor(sb * (bf.astype(jnp.float32) - zb) + u01),
                  0.0, nbins_b) - off_b
    # single materialization of each code tensor (see fused_qlhs_matmul_xla)
    ca, cb = jax.lax.optimization_barrier((ca, cb))
    acc = _codes_dot(ca, cb, (((0,), (0,)), ((), ())))
    alpha_a = 1.0 / sa
    beta_a = off_a * alpha_a + za
    alpha_b = 1.0 / sb
    beta_b = off_b * alpha_b + zb
    u_j = alpha_b * jnp.sum(cb, axis=0) + float(K) * beta_b
    return acc * (alpha_a * alpha_b) + beta_a * u_j[None, :] + a_vec[:, None]


def fused_qlhs_packed_matmul_xla(xf: jax.Array, scale_a: jax.Array,
                                 zero_a: jax.Array, packed: jax.Array,
                                 alpha_b, beta_b, u_vec: jax.Array, *,
                                 bits: int, wbits: int) -> jax.Array:
    """XLA twin of :func:`fused_qlhs_packed_matmul` — identical quantizer
    and unpack math; the shift/mask unpack chain fuses into the GEMM
    operand read, so no unpacked weight tensor persists in HBM either.
    The expression tree mirrors :func:`fused_qlhs_matmul_xla` exactly."""
    check_bits("fused_qlhs_packed_matmul_xla", bits)
    check_bits("fused_qlhs_packed_matmul_xla", wbits, lo=1)
    ppb = codes_per_byte(wbits)
    M, K = xf.shape
    if packed.shape[0] != -(-K // ppb):
        raise ValueError(
            f"fused_qlhs_packed_matmul_xla: packed rows {packed.shape[0]} "
            f"!= ceil({K}/{ppb}) for {wbits}-bit codes")
    safe = max_safe_k_packed(bits, wbits)
    if K > safe:
        raise ValueError(
            f"fused_qlhs_packed_matmul_xla: K={K} overflows the int32 "
            f"accumulator for int{bits} x int{wbits} codes "
            f"(max_safe_k={safe})")
    nbins = float((1 << bits) - 1)
    off_a = float(1 << (bits - 1))
    off_b = 1 << (wbits - 1)
    t = scale_a * (xf.astype(jnp.float32) - zero_a)
    c = jnp.clip(jnp.round(t), 0.0, nbins) - off_a
    w8 = (unpack_tile(packed, wbits)[:K, :] - off_b).astype(jnp.int8)
    # one materialization each (see fused_qlhs_matmul_xla)
    c, w8 = jax.lax.optimization_barrier((c, w8))
    acc = _codes_dot(c, w8, (((1,), (0,)), ((), ())))
    alpha_a = 1.0 / scale_a                               # (M, 1)
    beta_a = off_a * alpha_a + zero_a
    ab = jnp.asarray(alpha_b, jnp.float32)
    bb = jnp.asarray(beta_b, jnp.float32)
    a_i = (alpha_a * bb) * jnp.sum(c, axis=1, keepdims=True)
    return acc * (alpha_a * ab) + beta_a * u_vec[None, :] + a_i
