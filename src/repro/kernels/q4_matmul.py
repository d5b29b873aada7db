"""Pallas TPU kernel: int8 x bit-packed sub-byte -> int32 GEMM + epilogue.

The packed execution path of the ultra-low-bit track: the weight operand
stays bit-packed in HBM (``kernels/pack.py`` layout — ``ppb = 8 // bits``
codes per byte along the contraction axis) and each (bk/ppb x bn) packed
tile is unpacked *in VMEM* inside the K-sweep into shifted-signed int8
lanes for the MXU.  At 4-bit this halves the weight bytes streamed per
GEMM versus int8 codes (4x at 2-bit, 8x at 1-bit) on top of the 4x/8x
resident-memory win the serve engine takes by packing weights once at
load.

Everything else deliberately mirrors ``q8_matmul`` term for term — same
epilogue form, same precomputed (rs, cs, r2, u, a, b) coefficient vectors
from ``core/backend.epilogue_coeffs`` — so the packed kernel and its XLA
twin are *bit-exact* against the unpack-then-``q8_matmul`` oracle: the
only difference in the compiled graph is the integer unpack feeding the
MXU operand, and integer arithmetic is exact.  (An earlier variant
accumulated the epilogue col/row sums in-kernel; the expression values
were identical but XLA's FMA placement differed between the two graph
shapes, costing ~1 ulp — structural identity is what buys bit-exactness.)
The coefficient vectors need ``colsum`` of the unpacked codes; the wrapper
computes it as a fused unpack+reduce over the packed bytes (O(K*N) shifts,
no unpacked tensor materialized in HBM).

The twin's f32 code GEMM is exact while per-element products * K stay
under 2^24 — at 4-bit weights that is K <= 2^14, far above every shipped
shape (see fused_fqt.py).

Padding: packed rows beyond the logical K unpack to code 0, which is *not*
the shifted zero code, so the kernel masks ``row < kdim`` exactly like the
fused-quantize kernels mask padded K columns.
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
from .fused_fqt import _codes_dot
from .pack import codes_per_byte, max_safe_k_packed, unpack_tile
from .tiling import (check_bits, check_tiles, pad2d as _pad2,
                     round_up as _round_up)

__all__ = ["packed_matmul", "packed_matmul_xla"]


def _check_packed_gemm(name: str, x8, packed, wbits: int, kdim: int) -> int:
    """Shared shape/range validation; returns codes-per-byte."""
    ppb = codes_per_byte(wbits)
    check_bits(name, wbits, lo=1)
    if x8.shape[1] != kdim:
        raise ValueError(f"{name}: x8 {x8.shape} does not match kdim={kdim}")
    if packed.shape[0] != -(-kdim // ppb):
        raise ValueError(
            f"{name}: packed rows {packed.shape[0]} != ceil({kdim}/{ppb}) "
            f"for {wbits}-bit codes")
    safe = max_safe_k_packed(8, wbits)
    if kdim > safe:
        raise ValueError(
            f"{name}: K={kdim} overflows the int32 accumulator for "
            f"int8 x int{wbits} codes (max_safe_k={safe})")
    return ppb


def _kernel(x_ref, p_ref, rs_ref, cs_ref, r2_ref, u_ref, a_ref, b_ref,
            o_ref, acc_ref, *, nk: int, kdim: int, bits: int, bk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # unpack this packed weight tile in VMEM: (bk/ppb, bn) bytes -> (bk, bn)
    # unsigned codes -> shifted signed int8, padded K rows masked to 0
    off = 1 << (bits - 1)
    w = unpack_tile(p_ref[...], bits) - off
    row = pl.program_id(2) * bk + jax.lax.broadcasted_iota(
        jnp.int32, w.shape, 0)
    w8 = jnp.where(row < kdim, w, 0).astype(jnp.int8)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w8, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _epilogue():
        acc = acc_ref[...].astype(jnp.float32)
        o_ref[...] = (acc * (rs_ref[...] * cs_ref[...])
                      + r2_ref[...] * u_ref[...]
                      + a_ref[...] + b_ref[...])


def packed_matmul(x8: jax.Array, packed: jax.Array, rs: jax.Array,
                  cs: jax.Array, r2: jax.Array, u: jax.Array, a: jax.Array,
                  b: jax.Array, *, wbits: int, kdim: int,
                  bm: Optional[int] = None, bn: Optional[int] = None,
                  bk: Optional[int] = None,
                  interpret: bool = False) -> jax.Array:
    """``q8_matmul`` with the RHS bit-packed: x8 (M, K) shifted int8 codes;
    packed (ceil(K/ppb), N) uint8 at ``wbits`` codes/byte; rs/r2/a: (M,);
    cs/u/b: (N,) — the standard epilogue coefficient vectors of
    ``core/backend.epilogue_coeffs`` (u's colsum runs over the *unpacked*
    codes).  Returns (M, N) f32.  Tiles default to the autotuner cache under
    ``q4_matmul`` keyed by the logical (M, K, N) and an ``int{wbits}``
    dtype tag.
    """
    ppb = _check_packed_gemm("packed_matmul", x8, packed, wbits, kdim)
    del ppb
    M, K = x8.shape
    N = packed.shape[1]
    tm, tn, tk = lookup_tiles("q4_matmul", (M, K, N), dtype=f"int{wbits}")
    bm, bn, bk = (tm if bm is None else bm, tn if bn is None else bn,
                  tk if bk is None else bk)
    bm = min(bm, _round_up(M, 32))       # int8 sublane tile is 32
    bn = min(bn, _round_up(N, 128))
    bk = min(bk, _round_up(K, 128))      # ppb | 128, so ppb | bk
    check_tiles("q4_matmul", (M, K, N), (bm, bn, bk), interpret=interpret,
                multiples=(32, 128, 128))
    return _packed_matmul(x8, packed, rs, cs, r2, u, a, b, wbits=wbits,
                          bm=bm, bn=bn, bk=bk, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("wbits", "bm", "bn", "bk", "interpret"))
def _packed_matmul(x8, packed, rs, cs, r2, u, a, b, *, wbits, bm, bn, bk,
                   interpret):
    ppb = codes_per_byte(wbits)
    M, K = x8.shape
    N = packed.shape[1]
    Mp, Np, Kp = _round_up(M, bm), _round_up(N, bn), _round_up(K, bk)
    nk = Kp // bk

    row = lambda i, j, k: (i, 0)
    col = lambda i, j, k: (0, j)
    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk, kdim=K, bits=wbits, bk=bk),
        grid=(Mp // bm, Np // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk // ppb, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bm, 1), row), pl.BlockSpec((1, bn), col),
            pl.BlockSpec((bm, 1), row), pl.BlockSpec((1, bn), col),
            pl.BlockSpec((bm, 1), row), pl.BlockSpec((1, bn), col),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        name=names.PACKED_MATMUL,
        interpret=interpret,
    )(_pad2(x8, Mp, Kp), _pad2(packed, Kp // ppb, Np),
      _pad2(rs.reshape(M, 1), Mp, 1), _pad2(cs.reshape(1, N), 1, Np),
      _pad2(r2.reshape(M, 1), Mp, 1), _pad2(u.reshape(1, N), 1, Np),
      _pad2(a.reshape(M, 1), Mp, 1), _pad2(b.reshape(1, N), 1, Np))
    return out[:M, :N]


def packed_matmul_xla(x8: jax.Array, packed: jax.Array, rs: jax.Array,
                      cs: jax.Array, r2: jax.Array, u: jax.Array,
                      a: jax.Array, b: jax.Array, *, wbits: int,
                      kdim: int) -> jax.Array:
    """XLA twin of :func:`packed_matmul` — the ``native``-backend packed
    path and the CPU test oracle.  Unpacks in-graph (XLA fuses the shift/
    mask chain into the GEMM operand read), identical epilogue expression
    tree, platform-adaptive accumulation via ``_codes_dot``.  Jitted
    internally (like ``_q8_matmul``) so the epilogue compiles as one fused
    expression — eager per-op dispatch forbids the FMA contraction the
    compiled oracle performs and costs the 1-ulp bit-exactness."""
    _check_packed_gemm("packed_matmul_xla", x8, packed, wbits, kdim)
    return _packed_matmul_xla(x8, packed, rs, cs, r2, u, a, b, wbits=wbits,
                              kdim=kdim)


@functools.partial(jax.jit, static_argnames=("wbits", "kdim"))
def _packed_matmul_xla(x8, packed, rs, cs, r2, u, a, b, *, wbits, kdim):
    M = x8.shape[0]
    N = packed.shape[1]
    off = 1 << (wbits - 1)
    w8 = (unpack_tile(packed, wbits)[:kdim, :] - off).astype(jnp.int8)
    w8 = jax.lax.optimization_barrier(w8)  # one materialization of the unpack chain
    acc = _codes_dot(x8, w8, (((1,), (0,)), ((), ())))
    # keep the epilogue a separate fusion from the GEMM — mirrors the tile-
    # computation boundary of the Pallas kernel, where the accumulator is
    # materialized in VMEM before the epilogue reads it
    acc = jax.lax.optimization_barrier(acc)
    return (acc * (rs.reshape(M, 1) * cs.reshape(1, N))
            + r2.reshape(M, 1) * u.reshape(1, N)
            + a.reshape(M, 1) + b.reshape(1, N))
