"""Pallas TPU kernel: fused affine dequantization of int8 KV-cache rows.

The serving decode step reads the *whole* resident KV cache every token; with
the int8 cache (core/kv_cache.py) that read is ¼ the HBM traffic of fp32,
but the codes must be widened back to float before the attention math.  This
kernel fuses the widen + affine rescale into the single pass that streams the
codes out of HBM — one read of (codes, scale, zero), one write of the float
rows, no intermediate f32 code tensor.

Same contract style as ``quantize_sr_*``: shifted-signed int8 codes
(``c8 = code - 2^(b-1)``), per-row ``scale``/``zero`` with
``x ~= (c8 + 2^(b-1)) / scale + zero``, and ``interpret=True`` emulation for
CPU tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import names
from .tiling import (check_bits, pad2d as _pad2, pad_rows as _pad_rows,
                     round_up as _round_up)

__all__ = ["kv_dequant_rows"]


def _kernel(codes_ref, scale_ref, zero_ref, out_ref, *, off: int):
    c = codes_ref[...].astype(jnp.float32) + off          # back to unsigned
    out_ref[...] = c / scale_ref[...] + zero_ref[...]     # (bm, Np) / (bm, 1)


def kv_dequant_rows(codes8: jax.Array, scale: jax.Array, zero: jax.Array,
                    bits: int = 8, bm: int = 256,
                    interpret: bool = False) -> jax.Array:
    """Dequantize per-row affine int8 codes. codes8: (M, N) int8 shifted by
    ``-2^(b-1)``; scale/zero: (M, 1) f32.  Returns (M, N) f32.

    Arbitrary (M, N) works: rows are edge-padded to a block multiple (edge
    padding keeps the padded scales finite), columns zero-padded to a lane
    multiple (dequantized garbage is sliced off), output sliced back.
    """
    check_bits("kv_dequant_rows", bits)
    return _kv_dequant_rows(codes8, scale, zero, bits=bits, bm=bm,
                            interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bits", "bm", "interpret"))
def _kv_dequant_rows(codes8, scale, zero, *, bits, bm, interpret):
    M, N = codes8.shape
    Np = _round_up(N, 128)
    bm = min(bm, M)
    # block must fit VMEM: bm * Np * (1 + 4 + 4 + 4) bytes
    while bm > 1 and bm * Np * 13 > 8 * 2**20:
        bm //= 2
    Mp = _round_up(M, bm)
    out = pl.pallas_call(
        functools.partial(_kernel, off=1 << (bits - 1)),
        grid=(Mp // bm,),
        in_specs=[pl.BlockSpec((bm, Np), lambda i: (i, 0)),
                  pl.BlockSpec((bm, 1), lambda i: (i, 0)),
                  pl.BlockSpec((bm, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bm, Np), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        name=names.KV_DEQUANT,
        interpret=interpret,
    )(_pad2(codes8, Mp, Np),
      _pad_rows(scale.reshape(M, 1), Mp, edge=True),
      _pad_rows(zero.reshape(M, 1), Mp, edge=True))
    return out[:M, :N]
