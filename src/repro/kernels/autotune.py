"""Tile-shape autotuner with a persisted per-(kernel, shape, backend) cache.

The Pallas GEMM kernels (q8_matmul, fused_fqt) hard-coded one tile shape
per kernel; the right (bm, bn, bk) depends on the problem shape (how much
reuse a bigger bn/bk buys vs. the ~16 MB/core VMEM ceiling) and on the
platform.  This module owns three things:

  * the **VMEM accounting** for every kernel family (``tile_vmem_bytes`` /
    ``q8_tile_vmem_bytes``), used both to prune candidates and by the bench
    harness to report the per-tile budget;
  * the **candidate sweep** (:func:`tile_candidates`): MXU-aligned
    (bm, bn, bk) triples under the VMEM budget, and :func:`autotune`, which
    times them through an injectable timer and records the winner;
  * the **persisted cache**: a JSON file keyed
    ``<kernel>/<MxKxN>/<dtype>/<platform>`` at ``$REPRO_TUNING_CACHE``.
    Without that variable there is no file: tiles come from what the
    repository ships.  Kernel wrappers consult it at trace time via
    :func:`lookup_tiles`; a missing or corrupt file falls back to
    :data:`SHIPPED_DEFAULTS` (pre-tuned entries for the bench shapes) and
    then to the per-kernel default — never an error.  Each resolution is
    recorded with its source in ``get_cache().resolved``.

Re-tune on a new platform/shape with ``REPRO_TUNING_CACHE=<file> python -m
benchmarks.bench_kernels --tune`` (tile choice only changes performance on
TPU, where the Pallas kernels compile natively; elsewhere the sweep
exercises the plumbing and the XLA paths ignore the tiles).
"""

from __future__ import annotations

import json
import os
import tempfile
import warnings
from typing import Callable, Dict, Iterable, Optional, Tuple

import jax

__all__ = [
    "DEFAULT_TILES", "SHIPPED_DEFAULTS", "VMEM_BUDGET_BYTES",
    "KERNEL_SPECS", "validate_entry",
    "tile_vmem_bytes", "q8_tile_vmem_bytes", "tile_candidates",
    "shape_key", "cache_key", "cache_path", "TuningCache", "get_cache",
    "reset_cache", "lookup_tiles", "record_tiles", "autotune",
]

Tiles = Tuple[int, int, int]

DEFAULT_TILES: Tiles = (128, 512, 512)

# Leave ~4 MB of the ~16 MB/core for double-buffered pipelining.
VMEM_BUDGET_BYTES = 12 * 2 ** 20

ENV_CACHE = "REPRO_TUNING_CACHE"

# MXU/VPU-aligned sweep axes: bm over the sublane dim (int8 packs 32
# sublanes; f32 operands need 8), bn/bk over the 128-wide lane dim.
_BM_CANDIDATES = (32, 64, 128, 256, 512)
_LANE_CANDIDATES = (128, 256, 512, 1024)


# ---------------------------------------------------------------------------
# VMEM accounting (single source — pruning, bench reporting, docs)
# ---------------------------------------------------------------------------

def tile_vmem_bytes(bm: int, bn: int, bk: int, kind: str = "q8") -> int:
    """Resident VMEM bytes for one grid step of a kernel family.

    ``q8``           int8 A + int8 B + f32 out + int32 acc + epilogue vectors
    ``fused_lhs``    f32 A tile + uint32 SR bits + int8 B + out/acc + rowsum
                     scratch + epilogue vectors (quantize-on-the-fly LHS)
    ``fused_tn``     f32 A + f32 B + uint32 bits + out/acc + colsum scratch
                     (both operands quantized on the fly; dW kernel)
    ``packed``       int8 A + bit-packed B bytes (worst case int4: bk*bn/2)
                     + int32 unpack scratch (the shift/mask planes
                     materialize an int32 (bk, bn) tile in VMEM before the
                     int8 cast) + out/acc + row/colsum scratch + vectors
    ``fused_packed`` f32 A + packed B + int32 unpack scratch + out/acc +
                     row/colsum scratch (quantize LHS and unpack RHS in one
                     K-sweep; the forward megakernel over packed weights)
    """
    vecs = 4 * (2 * bm + 3 * bn)            # scale/zero rows + cs/u/b cols
    out_acc = 4 * bm * bn + 4 * bm * bn     # f32 out block + int32 acc
    # packed kinds: worst packable width is int4 -> bk*bn/2 packed bytes;
    # the in-VMEM unpack goes through an int32 (bk, bn) intermediate
    unpack = bk * bn // 2 + 4 * bk * bn
    if kind == "q8":
        return bm * bk + bk * bn + out_acc + vecs
    if kind == "fused_lhs":
        return (4 * bm * bk + 4 * bm * bk + bk * bn
                + out_acc + 4 * bm + vecs)
    if kind == "fused_tn":
        return (4 * bk * bm + 4 * bk * bn + 4 * bk * bn
                + out_acc + 4 * bn + vecs)
    if kind == "packed":
        return bm * bk + unpack + out_acc + 4 * bm + 4 * bn + vecs
    if kind == "fused_packed":
        return (4 * bm * bk + unpack + out_acc + 4 * bm + 4 * bn + vecs)
    raise ValueError(f"unknown kernel kind {kind!r}; expected one of "
                     f"('q8', 'fused_lhs', 'fused_tn', 'packed', "
                     f"'fused_packed')")


def q8_tile_vmem_bytes(bm: int, bn: int, bk: int, fused: bool = False) -> int:
    """The historical bench entry point (``kernel/q8_tile_vmem_bytes``)."""
    return tile_vmem_bytes(bm, bn, bk, "fused_lhs" if fused else "q8")


# What each registered kernel requires of a tile entry — the single source
# the cache loader and the static checker (analysis/kernels.py) validate
# against.  ``kind`` feeds :func:`tile_vmem_bytes`; ``multiples`` mirrors the
# ``check_tiles(..., interpret=False)`` alignment each wrapper enforces
# (q8_matmul.py / fused_fqt.py); ``kv_dequant`` is the bm-only row kernel
# (kind "rows": bn/bk must be 0, VMEM accounting lives in the wrapper).
KERNEL_SPECS: Dict[str, Dict[str, object]] = {
    "q8_matmul": {"kind": "q8", "multiples": (32, 128, 128)},
    "fused_fwd": {"kind": "fused_lhs", "multiples": (8, 128, 128)},
    "fused_dx": {"kind": "fused_lhs", "multiples": (8, 128, 128)},
    "fused_dw": {"kind": "fused_tn", "multiples": (128, 128, 8)},
    "kv_dequant": {"kind": "rows", "multiples": (8, 0, 0)},
    # paged-pool gather twin of kv_dequant (kernels/kv_gather.py): bm is the
    # rows-per-page-step block, clamped to a divisor of the page size at
    # trace time, so the same "rows" validation applies
    "kv_gather": {"kind": "rows", "multiples": (8, 0, 0)},
    # bit-packed weight family (kernels/q4_matmul.py + the packed variant in
    # kernels/fused_fqt.py); cache keys carry the code width as the dtype
    # segment (int4/int2/int1) since the packed byte layout changes with it
    "q4_matmul": {"kind": "packed", "multiples": (32, 128, 128)},
    "fused_packed": {"kind": "fused_packed", "multiples": (8, 128, 128)},
}


def validate_entry(kernel: str, tiles: Tiles,
                   budget: int = VMEM_BUDGET_BYTES):
    """Statically validate one (kernel, tiles) cache entry.

    Returns a list of problem strings (empty = legal), or ``None`` when the
    kernel is not in :data:`KERNEL_SPECS` (nothing to validate against —
    callers keep such entries and may flag them separately).
    """
    spec = KERNEL_SPECS.get(kernel)
    if spec is None:
        return None
    problems = []
    try:
        bm, bn, bk = (int(t) for t in tiles)
    except (TypeError, ValueError):
        return [f"tiles {tiles!r} are not an (bm, bn, bk) int triple"]
    mm, mn, mk = spec["multiples"]
    if spec["kind"] == "rows":
        if bm <= 0 or bm % mm:
            problems.append(f"bm={bm} must be a positive multiple of {mm}")
        if bn or bk:
            problems.append(f"bn/bk must be 0 for the row kernel, "
                            f"got ({bn}, {bk})")
        return problems
    for name, v, mult in (("bm", bm, mm), ("bn", bn, mn), ("bk", bk, mk)):
        if v <= 0:
            problems.append(f"{name}={v} must be positive")
        elif v % mult:
            problems.append(f"{name}={v} not a multiple of {mult} "
                            f"(MXU alignment, tiling.check_tiles)")
    if not problems:
        vmem = tile_vmem_bytes(bm, bn, bk, spec["kind"])
        if vmem > budget:
            problems.append(
                f"tile ({bm}, {bn}, {bk}) needs {vmem / 2**20:.1f} MiB "
                f"VMEM > budget {budget / 2**20:.1f} MiB "
                f"(kind {spec['kind']!r})")
    return problems


def tile_candidates(m: int, k: int, n: int, kind: str = "q8",
                    budget: int = VMEM_BUDGET_BYTES) -> Tuple[Tiles, ...]:
    """MXU-aligned (bm, bn, bk) triples under the VMEM budget, no larger
    than the (rounded-up) problem dims — the autotuner's sweep space."""
    from .tiling import round_up
    out = []
    for bm in _BM_CANDIDATES:
        if bm > round_up(m, 32):
            continue
        for bn in _LANE_CANDIDATES:
            if bn > round_up(n, 128):
                continue
            for bk in _LANE_CANDIDATES:
                if bk > round_up(k, 128):
                    continue
                if tile_vmem_bytes(bm, bn, bk, kind) <= budget:
                    out.append((bm, bn, bk))
    return tuple(out) or (DEFAULT_TILES,)


# ---------------------------------------------------------------------------
# The persisted cache
# ---------------------------------------------------------------------------

def shape_key(*dims) -> str:
    # string dims name shape-agnostic entries (e.g. kv_dequant's "rows")
    return "x".join(d if isinstance(d, str) else str(int(d)) for d in dims)


def cache_key(kernel: str, shape, dtype: str = "int8",
              platform: Optional[str] = None) -> str:
    if platform is None:
        platform = jax.default_backend()
    return f"{kernel}/{shape_key(*shape)}/{dtype}/{platform}"


def cache_path() -> Optional[str]:
    """The persisted cache file, or None when ``$REPRO_TUNING_CACHE`` is
    unset (then only the shipped tiles apply)."""
    path = os.environ.get(ENV_CACHE)
    return os.path.expanduser(path) if path else None


# Pre-tuned winners for the bench shapes (keys are platform-agnostic — they
# apply when the persisted cache has no platform-specific entry).  Chosen by
# VMEM/arithmetic-intensity analysis for the TPU target: the largest
# lane-aligned bn*bk under the budget, bm sized so the int8 A tile keeps the
# MXU fed without starving double-buffering.
SHIPPED_DEFAULTS: Dict[str, Tiles] = {
    "q8_matmul/512x1024x1024": (256, 512, 1024),
    "q8_matmul/1024x4096x1024": (256, 512, 1024),
    "q8_matmul/4096x1024x4096": (256, 1024, 512),
    "fused_fwd/512x1024x1024": (128, 512, 512),
    "fused_fwd/1024x4096x1024": (128, 512, 512),
    "fused_fwd/4096x1024x4096": (128, 1024, 512),
    # dx/dw keys are the GEMM-logical (M, K, N) the wrappers look up —
    # for a model GEMM (m, k, n): dx contracts n -> (m, n, k); dw contracts
    # m -> (k, m, n)
    "fused_dx/512x1024x1024": (128, 512, 512),
    "fused_dx/1024x1024x4096": (128, 512, 512),
    "fused_dx/4096x4096x1024": (128, 1024, 512),
    "fused_dw/1024x512x1024": (128, 512, 256),
    "fused_dw/4096x1024x1024": (128, 512, 256),
    "fused_dw/1024x4096x4096": (128, 512, 256),
    "kv_dequant/rows": (256, 0, 0),
    "kv_gather/rows": (256, 0, 0),
    # packed-weight family: the int32 unpack intermediate (4*bk*bn) is the
    # dominant VMEM term, so bk stays at 512 where q8_matmul could afford
    # 1024
    "q4_matmul/512x1024x1024": (256, 512, 512),
    "q4_matmul/1024x4096x1024": (256, 512, 512),
    "q4_matmul/4096x1024x4096": (256, 512, 512),
    "fused_packed/512x1024x1024": (128, 512, 512),
    "fused_packed/1024x4096x1024": (128, 512, 512),
    "fused_packed/4096x1024x4096": (128, 512, 512),
}


def _entry_tiles(entry) -> Optional[Tiles]:
    """(bm, bn, bk) from a cache entry dict, or None when malformed."""
    if not isinstance(entry, dict):
        return None
    try:
        return (int(entry["bm"]), int(entry["bn"]), int(entry["bk"]))
    except (KeyError, TypeError, ValueError):
        return None


class TuningCache:
    """Lazy-loaded JSON tile cache; corrupt or unreadable files degrade to
    an empty cache with a one-time warning (never an exception).

    Individual entries are validated on load: a malformed entry (not a
    ``{"bm", "bn", "bk"}`` dict) or one whose tiles are illegal for a
    registered kernel (:func:`validate_entry` — misaligned, over the VMEM
    budget) is DROPPED with a warning, so a stale or hand-edited cache can
    never feed an un-lowerable tile into ``lookup_tiles``.  Entries for
    kernels not in :data:`KERNEL_SPECS` are kept as-is (forward compat;
    ``python -m repro.analysis kernels`` flags them).

    ``resolved`` maps ``(kernel, shape, dtype)`` to the ``(tiles, source)``
    of every :func:`lookup_tiles` call made through this cache."""

    def __init__(self, path: Optional[str] = None):
        self.path = path or cache_path()
        self._data: Optional[dict] = None
        self.resolved: Dict[Tuple[str, str, str], Tuple[Tiles, str]] = {}

    def _load(self) -> dict:
        if self._data is not None:
            return self._data
        data: dict = {}
        if self.path and os.path.exists(self.path):
            try:
                with open(self.path) as f:
                    raw = json.load(f)
                if not isinstance(raw, dict):
                    raise ValueError(f"expected a JSON object, got "
                                     f"{type(raw).__name__}")
                data = self._validate(raw)
            except (ValueError, OSError) as e:
                warnings.warn(
                    f"ignoring corrupt tuning cache {self.path!r} ({e}); "
                    f"falling back to shipped defaults — re-tune with "
                    f"`python -m benchmarks.bench_kernels --tune`",
                    stacklevel=2)
        self._data = data
        return data

    def _validate(self, raw: dict) -> dict:
        data: dict = {}
        dropped = []
        for key, entry in raw.items():
            tiles = _entry_tiles(entry)
            if tiles is None:
                dropped.append(f"{key}: entry {entry!r} is not a "
                               f"{{bm, bn, bk}} dict")
                continue
            problems = validate_entry(str(key).split("/", 1)[0], tiles)
            if problems:          # None (unknown kernel) and [] both pass
                dropped.append(f"{key}: " + "; ".join(problems))
                continue
            data[key] = entry
        if dropped:
            listing = "\n  ".join(dropped)
            warnings.warn(
                f"dropped {len(dropped)} illegal entr"
                f"{'y' if len(dropped) == 1 else 'ies'} from tuning cache "
                f"{self.path!r}:\n  {listing}\nre-tune with "
                f"`python -m benchmarks.bench_kernels --tune`",
                stacklevel=3)
        return data

    def lookup(self, key: str) -> Optional[Tiles]:
        return _entry_tiles(self._load().get(key))

    def record(self, key: str, tiles: Tiles,
               us_per_call: Optional[float] = None) -> None:
        bm, bn, bk = tiles
        entry = {"bm": int(bm), "bn": int(bn), "bk": int(bk)}
        if us_per_call is not None:
            entry["us_per_call"] = float(us_per_call)
        self._load()[key] = entry

    def save(self) -> str:
        """Atomic write (tmp + rename) so a killed tune never corrupts."""
        if not self.path:
            raise ValueError(f"no tuning cache file to save to: set "
                             f"${ENV_CACHE}")
        data = self._load()
        d = os.path.dirname(self.path) or "."
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(data, f, indent=1, sort_keys=True)
            os.replace(tmp, self.path)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        return self.path


_CACHE: Optional[TuningCache] = None


def get_cache() -> TuningCache:
    global _CACHE
    if _CACHE is None or _CACHE.path != cache_path():
        # re-resolve when $REPRO_TUNING_CACHE changes (tests use tmpdirs)
        _CACHE = TuningCache()
    return _CACHE


def reset_cache() -> None:
    global _CACHE
    _CACHE = None


def lookup_tiles(kernel: str, shape, default: Tiles = DEFAULT_TILES,
                 dtype: str = "int8") -> Tiles:
    """Trace-time tile resolution: persisted cache (platform-specific wins
    over platform-agnostic ``any``) > shipped defaults > ``default``.  The
    result and its source (``"cache:<key>"``, ``"shipped"`` or
    ``"default"``) are recorded in ``get_cache().resolved``."""
    cache = get_cache()
    tiles, source = default, "default"
    shipped = SHIPPED_DEFAULTS.get(f"{kernel}/{shape_key(*shape)}")
    if shipped is not None:
        tiles, source = shipped, "shipped"
    for platform in ("any", jax.default_backend()):
        key = cache_key(kernel, shape, dtype, platform)
        hit = cache.lookup(key)
        if hit is not None:
            tiles, source = hit, f"cache:{key}"
    cache.resolved[(kernel, shape_key(*shape), dtype)] = (tiles, source)
    return tiles


def record_tiles(kernel: str, shape, tiles: Tiles,
                 us_per_call: Optional[float] = None, dtype: str = "int8",
                 platform: Optional[str] = None, save: bool = True) -> str:
    cache = get_cache()
    key = cache_key(kernel, shape, dtype, platform)
    cache.record(key, tiles, us_per_call)
    if save:
        cache.save()
    return key


def autotune(kernel: str, shape, run_us: Callable[[Tiles], float], *,
             candidates: Optional[Iterable[Tiles]] = None,
             dtype: str = "int8", save: bool = True,
             log: Optional[Callable[[str], None]] = None) -> Tiles:
    """Sweep ``candidates`` through ``run_us`` (a timer returning µs/call),
    persist the winner, and return it.

    ``run_us`` is injectable so unit tests drive the sweep with a fake
    timer; the bench harness passes a real ``time_us`` closure.  A candidate
    that raises is skipped (bad tile configs surfaced by the sweep are the
    wrappers' job to reject with a clear ValueError).
    """
    if candidates is None:
        m, k, n = shape
        candidates = tile_candidates(m, k, n)
    best: Optional[Tiles] = None
    best_us = float("inf")
    for tiles in candidates:
        try:
            us = float(run_us(tiles))
        except Exception as e:  # noqa: BLE001 — sweep must survive bad tiles
            if log:
                log(f"  {kernel}{tiles}: skipped ({type(e).__name__}: {e})")
            continue
        if log:
            log(f"  {kernel}{tiles}: {us:.1f} us")
        if us < best_us:
            best, best_us = tiles, us
    if best is None:
        raise ValueError(
            f"autotune({kernel!r}, {tuple(shape)}): every candidate failed; "
            f"check the kernel wrapper's tile validation")
    record_tiles(kernel, shape, best, best_us, dtype=dtype, save=save)
    return best
