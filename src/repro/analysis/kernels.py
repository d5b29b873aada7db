"""Static checker for Pallas kernel tile configurations.

Walks every tile source a kernel wrapper can resolve at trace time —
:data:`~repro.kernels.autotune.SHIPPED_DEFAULTS` plus every entry of the
persisted tuning cache (``$REPRO_TUNING_CACHE``, when set) — and verifies, without compiling anything:

  * **VMEM budget**: ``tile_vmem_bytes(bm, bn, bk, kind)`` under
    ``VMEM_BUDGET_BYTES`` for the kernel's family (autotune.KERNEL_SPECS);
  * **tile divisibility**: the exact MXU alignment each wrapper enforces
    via ``tiling.check_tiles(..., interpret=False)``;
  * **key well-formedness**: cache keys parse as
    ``<kernel>/<MxKxN>/<dtype>/<platform>`` with a legal dtype;
  * **staleness**: entries naming kernels no registered wrapper resolves.

Bad persisted entries are reported (and purged with ``--purge``); the
loader already refuses to serve illegal entries for known kernels
(autotune.TuningCache._validate), so this checker is the part that
*explains* and *cleans*, and the CI gate that keeps SHIPPED_DEFAULTS
legal as the kernels evolve.
"""

from __future__ import annotations

import dataclasses
import os
from typing import List, Optional, Tuple

from ..kernels.autotune import (KERNEL_SPECS, SHIPPED_DEFAULTS,
                                VMEM_BUDGET_BYTES, TuningCache, cache_path,
                                tile_vmem_bytes, validate_entry)

__all__ = ["KernelFinding", "KernelCheckReport", "check_kernels",
           "purge_bad_entries"]

# int4/int2/int1 tag the *packed weight* cache keys (q4_matmul /
# fused_packed families key tiles per sub-byte width)
_LEGAL_DTYPES = ("int8", "uint8", "int4", "int2", "int1", "float32",
                 "bfloat16", "float16")


@dataclasses.dataclass(frozen=True)
class KernelFinding:
    severity: str          # "error" | "stale" | "info"
    source: str            # "shipped" | "cache"
    key: str
    tiles: Optional[Tuple[int, int, int]]
    detail: str

    def __str__(self):
        t = "" if self.tiles is None else f" tiles={self.tiles}"
        return (f"[{self.severity}:{self.source}] {self.key}{t}: "
                f"{self.detail}")


@dataclasses.dataclass(frozen=True)
class KernelCheckReport:
    findings: Tuple[KernelFinding, ...]
    n_shipped: int
    n_cache: int
    cache_file: Optional[str]

    @property
    def errors(self) -> Tuple[KernelFinding, ...]:
        return tuple(f for f in self.findings if f.severity == "error")

    @property
    def ok(self) -> bool:
        return not self.errors

    def _cache_state(self) -> str:
        if not self.cache_file:
            return "no file: $REPRO_TUNING_CACHE unset"
        if os.path.exists(self.cache_file):
            return self.cache_file
        return f"{self.cache_file}, absent"

    def format(self, verbose: bool = False) -> str:
        lines = ["== kernel tile check =="]
        lines.append(f"shipped defaults: {self.n_shipped} entries; "
                     f"persisted cache: {self.n_cache} entries "
                     f"({self._cache_state()})")
        shown = [f for f in self.findings
                 if verbose or f.severity != "info"]
        lines.extend(f"  {f}" for f in shown)
        lines.append(f"tile check: "
                     f"{'OK' if self.ok else f'{len(self.errors)} error(s)'}"
                     f" ({len(self.findings)} finding(s), budget "
                     f"{VMEM_BUDGET_BYTES / 2**20:.0f} MiB)")
        return "\n".join(lines)


def _check_tiles(source: str, key: str, kernel: str,
                 tiles) -> List[KernelFinding]:
    problems = validate_entry(kernel, tiles)
    if problems is None:
        return [KernelFinding(
            "stale", source, key, tuple(tiles),
            f"kernel {kernel!r} has no registered wrapper "
            f"(KERNEL_SPECS: {', '.join(sorted(KERNEL_SPECS))}); entry is "
            f"dead weight")]
    if problems:
        return [KernelFinding("error", source, key, tuple(tiles), p)
                for p in problems]
    kind = KERNEL_SPECS[kernel]["kind"]
    if kind == "rows":
        detail = f"bm={tiles[0]} row kernel OK"
    else:
        vmem = tile_vmem_bytes(*tiles, kind)
        detail = (f"OK: {vmem / 2**20:.2f} MiB VMEM "
                  f"({100.0 * vmem / VMEM_BUDGET_BYTES:.0f}% of budget, "
                  f"kind {kind!r})")
    return [KernelFinding("info", source, key, tuple(tiles), detail)]


def _check_cache_key(key: str) -> Optional[str]:
    """Problem string when a persisted cache key is malformed, else None."""
    parts = key.split("/")
    if len(parts) != 4:
        return (f"key does not parse as <kernel>/<shape>/<dtype>/<platform> "
                f"({len(parts)} segment(s))")
    _, shape, dtype, _ = parts
    for d in shape.split("x"):
        if not (d.isdigit() or d.isidentifier()):
            return f"shape segment {shape!r} has a non-numeric, non-name dim"
    if dtype not in _LEGAL_DTYPES:
        return f"dtype {dtype!r} not in {_LEGAL_DTYPES}"
    return None


def check_kernels(path: Optional[str] = None) -> KernelCheckReport:
    """Validate shipped defaults + every persisted cache entry statically."""
    findings: List[KernelFinding] = []

    for key, tiles in sorted(SHIPPED_DEFAULTS.items()):
        kernel = key.split("/", 1)[0]
        findings.extend(_check_tiles("shipped", key, kernel, tiles))

    cache_file = os.path.expanduser(path) if path else cache_path()
    # raw read on purpose: the loader's _validate already drops illegal
    # entries, which would hide exactly what this checker must report
    import json
    raw: dict = {}
    if cache_file and os.path.exists(cache_file):
        try:
            with open(cache_file) as f:
                loaded = json.load(f)
            raw = loaded if isinstance(loaded, dict) else {}
            if not isinstance(loaded, dict):
                findings.append(KernelFinding(
                    "error", "cache", cache_file, None,
                    f"cache is not a JSON object "
                    f"(got {type(loaded).__name__})"))
        except (ValueError, OSError) as e:
            findings.append(KernelFinding(
                "error", "cache", cache_file, None,
                f"unreadable cache: {e}"))

    for key, entry in sorted(raw.items()):
        key_problem = _check_cache_key(str(key))
        if key_problem:
            findings.append(KernelFinding("error", "cache", str(key), None,
                                          key_problem))
            continue
        try:
            tiles = (int(entry["bm"]), int(entry["bn"]), int(entry["bk"]))
        except (KeyError, TypeError, ValueError):
            findings.append(KernelFinding(
                "error", "cache", str(key), None,
                f"entry {entry!r} is not a {{bm, bn, bk}} dict"))
            continue
        findings.extend(
            _check_tiles("cache", str(key), key.split("/", 1)[0], tiles))

    return KernelCheckReport(findings=tuple(findings),
                             n_shipped=len(SHIPPED_DEFAULTS),
                             n_cache=len(raw), cache_file=cache_file)


def purge_bad_entries(report: KernelCheckReport) -> int:
    """Remove every cache entry the report marks error/stale; returns the
    number purged.  Writes atomically via TuningCache.save()."""
    bad_keys = {f.key for f in report.findings
                if f.source == "cache" and f.severity in ("error", "stale")}
    if not bad_keys:
        return 0
    import json
    raw: dict = {}
    if report.cache_file and os.path.exists(report.cache_file):
        try:
            with open(report.cache_file) as f:
                loaded = json.load(f)
            if isinstance(loaded, dict):
                raw = loaded
        except (ValueError, OSError):
            raw = {}
    kept = {k: v for k, v in raw.items() if k not in bad_keys}
    cache = TuningCache(report.cache_file)
    cache._data = kept
    cache.save()
    return len(raw) - len(kept)
