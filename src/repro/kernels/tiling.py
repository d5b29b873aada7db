"""Shared pad-and-slice helpers for the Pallas kernel wrappers.

Grids require block-multiple dims; these helpers round shapes up and pad
operands so arbitrary (ragged) inputs work, with the wrapper slicing the
result back.  One home for the rule so a padding/alignment fix lands once.

Padding modes and why they differ
---------------------------------
``pad2d`` zero-pads.  Correct for *integer code* operands of a GEMM: padded
codes contribute 0 to the accumulator and padded rows/cols are sliced off.

``pad2d_edge`` edge-replicates in BOTH dims.  Required for *float* operands
that a quantize kernel will reduce per row (min/max -> scale): a zero-padded
column silently widens every real row's dynamic range whenever the row does
not straddle 0 (an all-positive row gains a false min of 0), so the per-row
scale — and therefore every SR code in that row — changes.  Edge replicas
repeat values the row already contains, so per-row (and global) min/max are
invariant under the padding.  This is exactly the ragged-shape interaction
the tile autotuner surfaces: lane-aligned tile candidates force column
padding of inputs whose row length is not a multiple of 128, and the
quantize kernels must stay bit-identical to the unpadded oracle
(tests/test_fused.py::test_pad_edge_preserves_row_ranges).

``pad_rows(edge=True)`` is the row-only special case (kept for the per-row
kernels whose block spans full rows).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["round_up", "pad2d", "pad2d_edge", "pad_rows", "check_tiles",
           "check_bits", "unit_from_bits"]

_TOP24_TO_UNIT = 1.0 / 16777216.0          # 2^-24


def unit_from_bits(rbits: jax.Array) -> jax.Array:
    """U[0,1) f32 uniforms from uint32 ``random.bits``: the one SR rule.

    ``(rbits >> 8) * 2^-24``: the top 24 bits are an integer below 2^24,
    exact in f32, so ``u <= 1 - 2^-24 < 1`` always.  (Casting the full
    uint32 to f32 rounds the top 128 patterns up to ``u == 1.0``, and
    Mosaic cannot lower a uint32 -> f32 cast at all; the int32 hop here
    lowers on the TPU and in XLA alike.)  Every SR site — the XLA
    quantizers, the Pallas kernels and their oracles — goes through this
    function, so codes are bit-identical across backends for a key.
    """
    top = jax.lax.shift_right_logical(rbits, jnp.uint32(8))
    return top.astype(jnp.int32).astype(jnp.float32) * _TOP24_TO_UNIT


def round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def pad2d(z: jax.Array, rows: int, cols: int) -> jax.Array:
    """Zero-pad a 2D array up to (rows, cols) (no-op when already there)."""
    r, c = z.shape
    if r == rows and c == cols:
        return z
    return jnp.pad(z, ((0, rows - r), (0, cols - c)))


def pad2d_edge(z: jax.Array, rows: int, cols: int) -> jax.Array:
    """Edge-replicate a 2D array up to (rows, cols).

    Range-inert padding for float operands of the quantize kernels: padded
    entries replicate the last real row/column, so per-row and per-tensor
    min/max computed over the padded array equal those of the real data.
    """
    r, c = z.shape
    if r == rows and c == cols:
        return z
    if r == 0 or c == 0:
        raise ValueError(
            f"cannot edge-pad an empty array of shape {z.shape} up to "
            f"({rows}, {cols}); quantize kernels need at least one real "
            f"row and column to replicate")
    return jnp.pad(z, ((0, rows - r), (0, cols - c)), mode="edge")


def pad_rows(x: jax.Array, rows: int, edge: bool = False) -> jax.Array:
    """Pad leading dim to ``rows``; ``edge=True`` replicates the last real
    row (keeps per-row min/max finite for quantize kernels)."""
    if x.shape[0] == rows:
        return x
    if edge and x.shape[0] == 0:
        raise ValueError(
            f"cannot edge-pad an empty array of shape {x.shape} up to "
            f"{rows} rows; there is no real row to replicate")
    mode = "edge" if edge else "constant"
    return jnp.pad(x, ((0, rows - x.shape[0]), (0, 0)), mode=mode)


def check_tiles(kernel: str, shape, tiles, *, interpret: bool,
                multiples=(32, 128, 128)) -> None:
    """Up-front tile validation for the GEMM kernel wrappers.

    Rejects non-positive / non-integer tile dims always, and (on real TPU
    lowering, i.e. ``interpret=False``) tiles that are not MXU-aligned.
    ``multiples`` gives the required (bm, bn, bk) alignment per kernel
    family — the sublane count of the dim that lands on a tile's second-
    minor axis and 128 for every lane-dim axis (int8 A tiles need bm%32,
    f32 A tiles bm%8; the transposed-A dW kernel instead needs bm%128 and
    only bk%8).  A bad tile surfaced by the autotuner sweep fails here with
    the shape and tile in the message instead of deep inside Mosaic
    lowering.
    """
    bm, bn, bk = tiles
    sh = "x".join(str(int(d)) for d in shape)
    for name, v in (("bm", bm), ("bn", bn), ("bk", bk)):
        if not isinstance(v, int) or isinstance(v, bool) or v <= 0:
            raise ValueError(
                f"{kernel}: tile {name}={v!r} must be a positive int "
                f"(shape {sh}, tile ({bm}, {bn}, {bk}))")
    mm, mn, mk = multiples
    if not interpret and (bm % mm or bn % mn or bk % mk):
        raise ValueError(
            f"{kernel}: tile ({bm}, {bn}, {bk}) is not MXU-aligned for "
            f"shape {sh}: needs bm % {mm} == 0, bn % {mn} == 0, "
            f"bk % {mk} == 0; pass interpret=True to lift the alignment "
            f"requirement (CPU debugging only)")


def check_bits(kernel: str, bits, lo: int = 2) -> int:
    """Validate a quantization bitwidth: an int in [lo, 8].

    The in-kernel quantizers need at least 2 bits (a 1-bit SR grid has a
    single bin boundary the round/clip algebra degenerates on), so ``lo``
    defaults to 2; the bit-packed weight kernels consume *pre-quantized*
    codes and pass ``lo=1`` to admit binary sign planes.
    """
    if not isinstance(bits, int) or isinstance(bits, bool) or \
            not lo <= bits <= 8:
        raise ValueError(
            f"{kernel}: bits={bits!r} out of range; the int8 kernels "
            f"support bitwidths {lo}..8")
    return bits
