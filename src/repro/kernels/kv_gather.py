"""Pallas TPU kernel: block-table page gather + fused affine dequantize.

The paged serving engine (serve/paged.py) stores the int8 KV cache as
fixed-size pages in one shared pool; a request's logically-contiguous cache
is physically scattered wherever the allocator found free pages.  The decode
read therefore becomes a *gather*: walk the request's block table, pull each
page out of the pool, and widen the int8 codes back to float — and just like
the dense-slot read (kv_dequant.py), doing the widen as a separate pass
would re-materialize an f32 code tensor the size of the gathered cache.

This kernel fuses both: the block table rides the scalar-prefetch channel
(``pltpu.PrefetchScalarGridSpec``), so each grid step's *input DMA itself*
is table-driven — the codes BlockSpec's index map reads ``table[b, j]`` and
streams that physical page from HBM straight into VMEM, where the affine
rescale runs before the single output write.  No gathered-codes
intermediate ever exists in HBM.

Same codec contract as core/kv_cache.py: shifted-signed codes
(``c8 = code - 2^(b-1)``), per-row ``scale``/``zero`` with
``x ~= (c8 + 2^(b-1)) / scale + zero``, scales clamped away from zero so a
degenerate (freshly allocated, all-zero) page can never emit inf/nan, and
``interpret=True`` emulation for CPU tests.  ``kv_gather_pages_xla`` is the
exact XLA twin the simulate/native backends run.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import names
from .tiling import check_bits, round_up as _round_up

__all__ = ["kv_gather_pages", "kv_gather_pages_xla"]

_EPS = 1e-12


def _kernel(tab_ref, codes_ref, scale_ref, zero_ref, out_ref, *, off: int):
    del tab_ref          # consumed by the index maps, not the body
    c = codes_ref[...].astype(jnp.float32) + off          # back to unsigned
    out_ref[...] = c / scale_ref[...] + zero_ref[...]     # (1,bm,Dp)/(1,bm,1)


def _row_block(P: int, bm: int) -> int:
    """Largest divisor of the page size <= the tuned row block (the grid
    must step through whole pages; a tile that straddles two pages would
    need two table lookups in one index map)."""
    bm = max(1, min(bm, P))
    while P % bm:
        bm -= 1
    return bm


def kv_gather_pages(codes: jax.Array, scale: jax.Array, zero: jax.Array,
                    table: jax.Array, bits: int = 8, bm: int = 256,
                    interpret: bool = False) -> jax.Array:
    """Gather + dequantize paged int8 KV rows through a block table.

    codes: (n_pages, P, D) int8 shifted by ``-2^(b-1)``; scale/zero:
    (n_pages, P) f32; table: (B, nb) int32 physical page ids (logical block
    order).  Returns (B, nb*P, D) f32 — each request's cache, contiguous
    again.

    ``bm`` (rows per grid step) is clamped to a divisor of the page size;
    on real TPUs page sizes should be multiples of 8 so the f32 sublane
    tiling holds.  Column dim is
    zero-padded to the 128 lane width and sliced back.
    """
    check_bits("kv_gather_pages", bits)
    return _kv_gather_pages(codes, scale, zero, table, bits=bits, bm=bm,
                            interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bits", "bm", "interpret"))
def _kv_gather_pages(codes, scale, zero, table, *, bits, bm, interpret):
    n_pages, P, D = codes.shape
    B, nb = table.shape
    Dp = _round_up(D, 128)
    bm = _row_block(P, bm)
    steps = P // bm
    if Dp != D:
        codes = jnp.pad(codes, ((0, 0), (0, 0), (0, Dp - D)))
    scale3 = jnp.maximum(scale, _EPS).reshape(n_pages, P, 1)
    zero3 = zero.reshape(n_pages, P, 1)

    def page(b, j, r, tab):
        return (tab[b, j], r, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nb, steps),
        in_specs=[pl.BlockSpec((1, bm, Dp), page),
                  pl.BlockSpec((1, bm, 1), page),
                  pl.BlockSpec((1, bm, 1), page)],
        out_specs=pl.BlockSpec(
            (1, bm, Dp), lambda b, j, r, tab: (b, j * steps + r, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, off=1 << (bits - 1)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nb * P, Dp), jnp.float32),
        name=names.KV_GATHER,
        interpret=interpret,
    )(table.astype(jnp.int32), codes, scale3, zero3)
    return out[:, :, :D]


def kv_gather_pages_xla(codes: jax.Array, scale: jax.Array, zero: jax.Array,
                        table: jax.Array, bits: int = 8) -> jax.Array:
    """Pure-XLA twin of :func:`kv_gather_pages` (simulate/native backends,
    and the allclose oracle for the kernel tests)."""
    check_bits("kv_gather_pages_xla", bits)
    off = 1 << (bits - 1)
    g = codes[table]                                      # (B, nb, P, D)
    s = jnp.maximum(scale, _EPS)[table][..., None]
    z = zero[table][..., None]
    out = (g.astype(jnp.float32) + off) / s + z
    B, nb, P, D = out.shape
    return out.reshape(B, nb * P, D)
