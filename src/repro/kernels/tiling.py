"""Tiles and pad-and-slice helpers for the Pallas kernel wrappers.

Tiles
-----
:func:`lookup_tiles` is the one place a wrapper's default (bm, bn, bk)
comes from: the kernel's shape rule (:data:`SHAPE_RULES`) where it has
one, else :data:`DEFAULT_TILES`.  Each resolution is recorded with its
source (``"rule"`` or ``"default"``) in :data:`RESOLVED`, which
``chip_smoke.py`` prints.  A kernel that gets a rule brings its own VMEM
accounting with it, as the weight-gradient kernel's ``fused_tn_*`` does.
Explicit ``bm``/``bn``/``bk`` arguments of a wrapper override the lookup
and go through :func:`check_tiles`.

Padding
-------
Grids require block-multiple dims; these helpers round shapes up and pad
operands so arbitrary (ragged) inputs work, with the wrapper slicing the
result back.  One home for the rule so a padding/alignment fix lands once.

``pad2d`` zero-pads.  Correct for *integer code* operands of a GEMM: padded
codes contribute 0 to the accumulator and padded rows/cols are sliced off.

``pad2d_edge`` edge-replicates in BOTH dims.  Required for *float* operands
that a quantize kernel will reduce per row (min/max -> scale): a zero-padded
column silently widens every real row's dynamic range whenever the row does
not straddle 0 (an all-positive row gains a false min of 0), so the per-row
scale — and therefore every SR code in that row — changes.  Edge replicas
repeat values the row already contains, so per-row (and global) min/max are
invariant under the padding.  Lane-aligned tiles force column padding of
inputs whose row length is not a multiple of 128, and the quantize kernels
must stay bit-identical to the unpadded oracle
(tests/test_fused.py::test_pad_edge_preserves_row_ranges).

``pad_rows(edge=True)`` is the row-only special case (kept for the per-row
kernels whose block spans full rows).
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

__all__ = ["round_up", "pad2d", "pad2d_edge", "pad_rows", "check_tiles",
           "check_bits", "unit_from_bits", "DEFAULT_TILES",
           "VMEM_BUDGET_BYTES", "SCOPED_VMEM_BYTES", "fused_tn_vmem_bytes",
           "fused_tn_scoped_vmem_bytes", "fused_tn_hbm_bytes",
           "fused_tn_tiles", "SHAPE_RULES", "RESOLVED", "lookup_tiles"]

Tiles = Tuple[int, int, int]

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
    only bk%8).  A bad tile fails here with the shape and tile in the
    message instead of deep inside Mosaic lowering.
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


# ---------------------------------------------------------------------------
# Tiles
# ---------------------------------------------------------------------------

DEFAULT_TILES: Tiles = (128, 512, 512)

# Leave ~4 MB of the ~16 MB/core for double-buffered pipelining.
VMEM_BUDGET_BYTES = 12 * 2 ** 20

# The v5e compiler's default scoped VMEM for one Mosaic kernel.
SCOPED_VMEM_BYTES = 16 * 2 ** 20


def fused_tn_vmem_bytes(bm: int, bn: int, bk: int) -> int:
    """Resident VMEM bytes for one grid step of the weight-gradient kernel
    (both operands quantized on the fly): the f32 A (bk, bm) tile, the f32 B
    and uint32 bits (bk, bn) tiles, the f32 output block and int32
    accumulator, the column-sum scratch and the epilogue vectors."""
    vecs = 4 * (2 * bm + 3 * bn)
    out_acc = 4 * bm * bn + 4 * bm * bn
    return (4 * bk * bm + 4 * bk * bn + 4 * bk * bn
            + out_acc + 4 * bn + vecs)


def fused_tn_scoped_vmem_bytes(bm: int, bn: int, bk: int) -> int:
    """Scoped VMEM that Mosaic allocates for the weight-gradient kernel: two
    buffers each of the f32 A (bk, bm) block, the f32 B and uint32 bits
    (bk, bn) blocks and the f32 (bm, bn) output block, the int32
    accumulator, and 1 MiB for the small blocks (the (bm, 1) row vector,
    scalars, column sums).  At or above what the v5e compiler reports for
    each tile the shape rule picks for the benchmark's shapes."""
    return (2 * (4 * bk * bm + 8 * bk * bn + 4 * bm * bn) + 4 * bm * bn
            + 2 ** 20)


def fused_tn_hbm_bytes(m: int, k: int, n: int, tiles: Tiles) -> int:
    """HBM bytes the weight-gradient kernel's grid ``(M/bm, N/bn, K/bk)``
    streams: the f32 A operand (4 B an element) once per bn-column stripe,
    the f32 B operand and its uint32 SR bits (8 B) once per bm-row stripe."""
    bm, bn, _ = tiles
    mp, np_ = round_up(m, 128), round_up(n, 128)
    return 4 * k * mp * -(-np_ // bn) + 8 * k * np_ * -(-mp // bm)


@functools.lru_cache(maxsize=256)
def fused_tn_tiles(m: int, k: int, n: int) -> Tiles:
    """The ``fused_dw`` tiles from the shape: the fewest HBM bytes
    (:func:`fused_tn_hbm_bytes`) under the VMEM budget, whose real footprint
    (:func:`fused_tn_scoped_vmem_bytes`) also fits the chip's default scoped
    VMEM — a kernel's own ``vmem_limit_bytes`` no longer holds once XLA
    fuses the call into its consumer, as the layer scan does when it writes
    each weight gradient into its stacked buffer.

    bm and bn range over multiples of 128 up to the rounded-up dims; bk is
    the largest of 128, 256, ... (MXU depth; a multiple of 32 for the int8
    A tile) that fits beside them.  Ties go to fewer grid steps, then to
    less work in the edge blocks, then to the larger bm: fewer passes over
    the operand that is stochastically rounded.
    """
    mp, np_, kp = round_up(m, 128), round_up(n, 128), round_up(k, 128)
    bks = [bk for bk in (128, 256, 512, 1024, 2048) if bk <= kp]
    best, best_key = None, None
    for bm in range(128, mp + 1, 128):
        for bn in range(128, np_ + 1, 128):
            fits = [bk for bk in bks
                    if fused_tn_vmem_bytes(bm, bn, bk) <= VMEM_BUDGET_BYTES
                    and fused_tn_scoped_vmem_bytes(bm, bn, bk)
                    <= SCOPED_VMEM_BYTES]
            if not fits:
                break                     # a wider bn fits no better
            tiles = (bm, bn, fits[-1])
            # the output the grid covers, edge blocks whole: with the grid
            # steps it breaks ties in bytes toward less wasted work
            area = round_up(mp, bm) * round_up(np_, bn)
            steps = area // (bm * bn) * round_up(kp, fits[-1]) // fits[-1]
            key = (fused_tn_hbm_bytes(m, k, n, tiles), steps, area, -bm)
            if best_key is None or key < best_key:
                best, best_key = tiles, key
    return best


# Kernels whose tiles are computed from the (M, K, N) they run at.
SHAPE_RULES: Dict[str, Callable[[int, int, int], Tiles]] = {
    "fused_dw": fused_tn_tiles,
}

# (kernel, (M, K, N)) -> (tiles, source) of every lookup_tiles call.
RESOLVED: Dict[Tuple[str, Tuple[int, ...]], Tuple[Tiles, str]] = {}


def lookup_tiles(kernel: str, shape, default: Tiles = DEFAULT_TILES) -> Tiles:
    """Trace-time tiles for ``kernel`` at ``shape`` = (M, K, N): its shape
    rule's where it has one (source ``"rule"``), else ``default`` (source
    ``"default"``); recorded in :data:`RESOLVED`."""
    rule = SHAPE_RULES.get(kernel)
    if rule is None:
        tiles, source = default, "default"
    else:
        tiles, source = rule(*shape), "rule"
    RESOLVED[(kernel, tuple(shape))] = (tiles, source)
    return tiles
