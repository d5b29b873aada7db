"""Pallas TPU kernel: int8 x int8 -> int32 GEMM with fused affine epilogue.

This is the deployed form of the paper's quantized GEMMs (forward Eq. 3 and
both backward GEMMs of Eq. 6).  The MXU consumes int8 tiles and accumulates
int32 in a VMEM scratch across the K sweep; the epilogue applies

    out[i,j] = acc[i,j]*rs_i*cs_j + r2_i*u_j + a_i + b_j

Writing each affine operand as  X^ = alpha_x * Cx + beta_x  (per-row) and
W^ = alpha_w * Cw + beta_w  (per-tensor/per-channel), the exact product is

    X^W^ = (alpha_x alpha_w) CxCw  +  alpha_x beta_w rowsum(Cx)   [a_i]
         +  beta_x (alpha_w colsum(Cw) + K beta_w)                [r2_i u_j]

so ONE epilogue form covers every scale/zero-point combination the paper's
recipe produces (ops.py wires it); ``b_j`` is free for fusing a layer bias.

Tiling: (bm x bk)@(bk x bn) MXU-aligned blocks, K innermost so the int32
accumulator stays VMEM-resident.  Default 128x512x512 tiles use ~0.8 MB of
the ~16 MB/core VMEM; bigger bn/bk raise arithmetic intensity.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from . import names
from .tiling import (check_tiles, lookup_tiles, pad2d as _pad2,
                     round_up as _round_up)

__all__ = ["q8_matmul"]


def _kernel(x_ref, y_ref, rs_ref, cs_ref, r2_ref, u_ref, a_ref, b_ref,
            o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], y_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _epilogue():
        acc = acc_ref[...].astype(jnp.float32)
        o_ref[...] = (acc * (rs_ref[...] * cs_ref[...])
                      + r2_ref[...] * u_ref[...]
                      + a_ref[...] + b_ref[...])


def q8_matmul(x8: jax.Array, y8: jax.Array, rs: jax.Array, cs: jax.Array,
              r2: jax.Array, u: jax.Array, a: jax.Array, b: jax.Array,
              bm: int = None, bn: int = None, bk: int = None,
              interpret: bool = False) -> jax.Array:
    """x8: (M,K) int8; y8: (K,N) int8; rs/r2/a: (M,); cs/u/b: (N,) -> f32.

    Tiles default to ``tiling.lookup_tiles("q8_matmul", (M, K, N))``
    (explicit bm/bn/bk override it), shrink toward
    small dims (keeping MXU-friendly multiples), then every dim is
    zero-padded up to a tile multiple and the result sliced back.
    Zero-padding is exact — padded K codes contribute 0 to the accumulator
    and the epilogue coefficient vectors pad with zeros, so padded output
    rows/cols never leak.
    """
    M, K = x8.shape
    K2, N = y8.shape
    if K != K2:
        raise ValueError(f"q8_matmul: contraction mismatch — x8 {x8.shape} "
                         f"vs y8 {y8.shape}")
    tm, tn, tk = lookup_tiles("q8_matmul", (M, K, N))
    bm, bn, bk = (tm if bm is None else bm, tn if bn is None else bn,
                  tk if bk is None else bk)
    bm = min(bm, _round_up(M, 32))       # int8 sublane tile is 32
    bn = min(bn, _round_up(N, 128))      # lane dim is 128
    bk = min(bk, _round_up(K, 128))
    check_tiles("q8_matmul", (M, K, N), (bm, bn, bk), interpret=interpret,
                multiples=(32, 128, 128))
    return _q8_matmul(x8, y8, rs, cs, r2, u, a, b, bm=bm, bn=bn, bk=bk,
                      interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def _q8_matmul(x8, y8, rs, cs, r2, u, a, b, *, bm, bn, bk, interpret):
    M, K = x8.shape
    N = y8.shape[1]
    Mp, Np, Kp = _round_up(M, bm), _round_up(N, bn), _round_up(K, bk)
    x8 = _pad2(x8, Mp, Kp)
    y8 = _pad2(y8, Kp, Np)
    nk = Kp // bk
    grid = (Mp // bm, Np // bn, nk)

    row = lambda i, j, k: (i, 0)
    col = lambda i, j, k: (0, j)
    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bm, 1), row), pl.BlockSpec((1, bn), col),
            pl.BlockSpec((bm, 1), row), pl.BlockSpec((1, bn), col),
            pl.BlockSpec((bm, 1), row), pl.BlockSpec((1, bn), col),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        name=names.Q8_MATMUL,
        interpret=interpret,
    )(x8, y8,
      _pad2(rs.reshape(M, 1), Mp, 1), _pad2(cs.reshape(1, N), 1, Np),
      _pad2(r2.reshape(M, 1), Mp, 1), _pad2(u.reshape(1, N), 1, Np),
      _pad2(a.reshape(M, 1), Mp, 1), _pad2(b.reshape(1, N), 1, Np))
    return out[:M, :N]
