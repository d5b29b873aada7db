"""Pallas TPU kernel: fused dynamic-range + scale + stochastic-round quantize.

One pass over the gradient implements the paper's PTQ/PSQ quantization step
(Sec. 3.3 / 4.1): per-row min/max reduction, affine transform, stochastic
rounding against supplied uniform bits, and int8 code emission — avoiding
three separate HBM round-trips (range pass, transform pass, round pass),
which is exactly the quantization overhead the paper measures in Sec. 4.3.

Random bits are an *input* (uint32 per element, generated with
``jax.random.bits`` outside) so the kernel is bit-exact reproducible and
interpret-testable on CPU; on hardware the input can be swapped for
``pltpu.prng_random_bits`` without changing the contract.

Per-tensor mode reuses the same kernel after a cheap global min/max reduce
(the scalar range is broadcast per row).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import names
from .tiling import (check_bits, pad2d as _pad2, pad2d_edge as _pad2_edge,
                     round_up as _round_up, unit_from_bits)

__all__ = ["quantize_sr_rows", "quantize_sr_tensor"]

_EPS = 1e-12


def _kernel(x_ref, bits_ref, codes_ref, scale_ref, zero_ref, *, B: int):
    x = x_ref[...]                                   # (bm, Np) — full rows
    # padded columns are EDGE replicas (tiling.pad2d_edge), so this min/max
    # over the padded row equals the real row's — zero padding here would
    # silently widen every row's range (and its scale) whenever the row
    # does not straddle 0
    lo = jnp.min(x, axis=1, keepdims=True)
    hi = jnp.max(x, axis=1, keepdims=True)
    scale = B / jnp.maximum(hi - lo, _EPS)           # (bm, 1)
    t = scale * (x - lo)
    # SR(t) = floor(t + u), u ~ U[0,1) from the supplied bits
    u = unit_from_bits(bits_ref[...])
    q = jnp.clip(jnp.floor(t + u), 0.0, B)
    codes_ref[...] = (q - (B + 1) // 2).astype(jnp.int8)   # shifted signed
    scale_ref[...] = scale
    zero_ref[...] = lo


def quantize_sr_rows(x: jax.Array, rbits: jax.Array, bits: int = 8,
                     bm: int = 256, interpret: bool = False):
    """Per-row (PSQ) fused quantize. x: (M, N) f32; rbits: (M, N) uint32.

    Returns (codes int8 shifted by -2^(b-1), scale (M,1), zero (M,1)):
        x ~= (codes + 2^(b-1)) / scale + zero

    Arbitrary (M, N) works: the input is edge-padded up to a block-multiple
    row count and a lane-multiple (128) column count — edge replicas repeat
    values each row already contains, so the per-row min/max (and hence
    every code) are what the unpadded oracle computes — and the outputs are
    sliced back.
    """
    check_bits("quantize_sr_rows", bits)
    return _quantize_sr_rows(x, rbits, bits=bits, bm=bm, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bits", "bm", "interpret"))
def _quantize_sr_rows(x, rbits, *, bits, bm, interpret):
    M, N = x.shape
    B = (1 << bits) - 1
    Np = _round_up(N, 128)
    bm = min(bm, M)
    # full rows must fit VMEM: bm * Np * (4 + 4 + 1) bytes
    while bm > 1 and bm * Np * 9 > 8 * 2**20:
        bm //= 2
    Mp = _round_up(M, bm)
    xp = _pad2_edge(x, Mp, Np)
    rp = _pad2(rbits, Mp, Np)
    grid = (Mp // bm,)
    codes, scale, zero = pl.pallas_call(
        functools.partial(_kernel, B=B),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, Np), lambda i: (i, 0)),
                  pl.BlockSpec((bm, Np), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((bm, Np), lambda i: (i, 0)),
                   pl.BlockSpec((bm, 1), lambda i: (i, 0)),
                   pl.BlockSpec((bm, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((Mp, Np), jnp.int8),
                   jax.ShapeDtypeStruct((Mp, 1), jnp.float32),
                   jax.ShapeDtypeStruct((Mp, 1), jnp.float32)],
        name=names.QUANTIZE_SR_ROWS,
        interpret=interpret,
    )(xp, rp)
    return codes[:M, :N], scale[:M], zero[:M]


def _tensor_kernel(x_ref, bits_ref, lo_ref, hi_ref, codes_ref, *, B: int):
    x = x_ref[...]
    scale = B / jnp.maximum(hi_ref[0, 0] - lo_ref[0, 0], _EPS)
    t = scale * (x - lo_ref[0, 0])
    u = unit_from_bits(bits_ref[...])
    q = jnp.clip(jnp.floor(t + u), 0.0, B)
    codes_ref[...] = (q - (B + 1) // 2).astype(jnp.int8)


def quantize_sr_tensor(x: jax.Array, rbits: jax.Array, bits: int = 8,
                       bm: int = 256, interpret: bool = False):
    """Per-tensor (PTQ) fused quantize. Returns (codes, scale (), zero ()).

    The global min/max reduce over the *unpadded* input, so the edge
    padding used to reach block-multiple row and lane-multiple column
    counts never widens the range.
    """
    check_bits("quantize_sr_tensor", bits)
    return _quantize_sr_tensor(x, rbits, bits=bits, bm=bm,
                               interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bits", "bm", "interpret"))
def _quantize_sr_tensor(x, rbits, *, bits, bm, interpret):
    M, N = x.shape
    B = (1 << bits) - 1
    lo = jnp.min(x).reshape(1, 1)
    hi = jnp.max(x).reshape(1, 1)
    Np = _round_up(N, 128)
    bm = min(bm, M)
    while bm > 1 and bm * Np * 9 > 8 * 2**20:
        bm //= 2
    Mp = _round_up(M, bm)
    codes = pl.pallas_call(
        functools.partial(_tensor_kernel, B=B),
        grid=(Mp // bm,),
        in_specs=[pl.BlockSpec((bm, Np), lambda i: (i, 0)),
                  pl.BlockSpec((bm, Np), lambda i: (i, 0)),
                  pl.BlockSpec((1, 1), lambda i: (0, 0)),
                  pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((bm, Np), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.int8),
        name=names.QUANTIZE_SR_TENSOR,
        interpret=interpret,
    )(_pad2_edge(x, Mp, Np), _pad2(rbits, Mp, Np), lo, hi)
    return codes[:M, :N], B / jnp.maximum(hi[0, 0] - lo[0, 0], _EPS), lo[0, 0]
