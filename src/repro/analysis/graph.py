"""Recursive jaxpr walker: find every GEMM and attribute it to a marker.

``iter_gemm_sites(closed_jaxpr)`` walks a ClosedJaxpr — recursing through
``jit``/``scan``/``while``/``cond``/``custom_vjp``/``remat`` sub-jaxprs —
and yields one :class:`GemmSite` per ``dot_general`` /
``conv_general_dilated`` equation, carrying:

  * FLOPs (2*M*N*K*batch, multiplied by the trip count of enclosing scans),
  * the contraction size K and operand dtypes (the range analysis needs
    them for int32-accumulator bounds),
  * the quantization marker parsed from ``eqn.source_info.name_stack``
    (``q[path|role]`` / ``qfp[path|role]`` / ``fp[path]`` — see
    core/exempt.py), innermost marker winning,
  * a user-code ``file:line`` for leak reports.

The walk never executes anything — it is pure metadata traversal, so
auditing a billion-parameter step trace costs trace time only.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Iterator, List, Optional, Tuple

import jax

from ..core.exempt import MARKER_RE

__all__ = ["GemmSite", "iter_gemm_sites", "site_flops", "classify_stack"]

GEMM_PRIMS = ("dot_general", "conv_general_dilated")


@dataclasses.dataclass(frozen=True)
class GemmSite:
    """One GEMM equation found in the walked jaxpr."""

    primitive: str                 # "dot_general" | "conv_general_dilated"
    flops: float                   # 2*M*N*K*batch * scan-trip multiplier
    contract: int                  # K (product of contraction dims)
    mult: int                      # product of enclosing scan lengths
    lhs_dtype: str
    rhs_dtype: str
    stack: str                     # full name-stack string (outer + own)
    kind: str                      # "quantized"|"policy_fp"|"exempt"|"unmarked"
    path: Optional[str]            # marker path (None when unmarked)
    role: Optional[str]            # marker role for q/qfp (None otherwise)
    src: str                       # user-code "file:line" (best effort)
    m: int = 0                     # output rows (batch*M); 0 = unknown
    n: int = 0                     # output cols N; 0 = unknown

    @property
    def integer_gemm(self) -> bool:
        """True when both operands are integer codes (native int8 GEMM)."""
        return (self.lhs_dtype.startswith(("int", "uint"))
                and self.rhs_dtype.startswith(("int", "uint")))


def _prod(xs) -> int:
    out = 1
    for x in xs:
        out *= int(x)
    return out


def _dot_general_stats(eqn) -> Tuple[float, int, int, int]:
    """(flops-per-execution, K, M, N) for one dot_general.

    M folds the batch dims in (it is "output rows the GEMM produces"), so
    the planner's bytes-moved model sees the same m*k / k*n / m*n products
    the bench bytes column uses.
    """
    (lc, rc), (lb, _rb) = eqn.params["dimension_numbers"]
    lhs, rhs = eqn.invars[0].aval.shape, eqn.invars[1].aval.shape
    batch = _prod(lhs[i] for i in lb)
    k = _prod(lhs[i] for i in lc)
    m = _prod(d for i, d in enumerate(lhs) if i not in set(lb) | set(lc))
    n = _prod(d for i, d in enumerate(rhs) if i not in set(_rb) | set(rc))
    return 2.0 * batch * m * n * k, k, batch * m, n


def _conv_stats(eqn) -> Tuple[float, int, int, int]:
    """Approximate conv FLOPs: 2 * out-elements * (C_in/groups * K_spatial).

    (M, N) map a conv onto its implicit GEMM: N = output channels, M =
    output elements per channel — good enough for the bytes-moved model.
    """
    out = eqn.outvars[0].aval.shape
    rhs = eqn.invars[1].aval.shape            # (O, I/g, *spatial) canonical-ish
    k = _prod(rhs[1:])                        # contraction per output element
    n = int(rhs[0])
    m = max(1, _prod(out) // max(n, 1))
    return 2.0 * _prod(out) * k, int(k), m, n


def _classify(stack: str) -> Tuple[str, Optional[str], Optional[str]]:
    """(kind, path, role) from the innermost marker in a name-stack string."""
    last = None
    for m in MARKER_RE.finditer(stack):
        last = m
    if last is None:
        return "unmarked", None, None
    tag, payload = last.group(1), last.group(2)
    if tag == "fp":
        return "exempt", payload, None
    path, _, role = payload.rpartition("|")
    kind = "quantized" if tag == "q" else "policy_fp"
    return kind, path, role or None


def _src_of(eqn) -> str:
    try:
        for frame in eqn.source_info.traceback.frames:
            fn = frame.file_name
            if "/jax/" in fn or "site-packages" in fn or fn.startswith("<"):
                continue
            return f"{fn}:{frame.start_line}"
    except Exception:
        pass
    return "?"


def _sub_jaxprs(eqn) -> Iterator[Tuple[object, int]]:
    """(sub-jaxpr, trip-count multiplier) pairs hiding in eqn.params.

    ``scan`` multiplies by its static ``length``; ``while`` bodies have an
    unknown trip count and conservatively count once; ``cond`` branches all
    count (a leak in any branch is a leak).
    """
    mult = 1
    if eqn.primitive.name == "scan":
        mult = int(eqn.params.get("length", 1))
    for val in eqn.params.values():
        for sub in _as_jaxprs(val):
            yield sub, mult


def _as_jaxprs(val) -> Iterator[object]:
    if isinstance(val, jax.extend.core.ClosedJaxpr):
        yield val.jaxpr
    elif isinstance(val, jax.extend.core.Jaxpr):
        yield val
    elif isinstance(val, (tuple, list)):
        for v in val:
            yield from _as_jaxprs(v)


def _walk(jaxpr, mult: int, prefix: str, out: List[GemmSite]) -> None:
    for eqn in jaxpr.eqns:
        stack = str(eqn.source_info.name_stack)
        full = f"{prefix}/{stack}" if prefix and stack else (prefix or stack)
        prim = eqn.primitive.name
        if prim in GEMM_PRIMS:
            if prim == "dot_general":
                flops, k, m, n = _dot_general_stats(eqn)
            else:
                flops, k, m, n = _conv_stats(eqn)
            kind, path, role = _classify(full)
            out.append(GemmSite(
                primitive=prim, flops=flops * mult, contract=k, mult=mult,
                lhs_dtype=str(eqn.invars[0].aval.dtype),
                rhs_dtype=str(eqn.invars[1].aval.dtype),
                stack=full, kind=kind, path=path, role=role,
                src=_src_of(eqn), m=m, n=n))
        for sub, m in _sub_jaxprs(eqn):
            _walk(sub, mult * m, full, out)


def iter_gemm_sites(closed_jaxpr) -> Tuple[GemmSite, ...]:
    """Every GEMM equation in ``closed_jaxpr`` (recursively), attributed."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    out: List[GemmSite] = []
    _walk(jaxpr, 1, "", out)
    return tuple(out)


# the soundness + planner passes attribute non-GEMM equations with the
# same innermost-marker rule the GEMM walk uses
classify_stack = _classify


def site_flops(sites, kind: Optional[str] = None) -> float:
    """Total FLOPs over ``sites``, optionally filtered by marker kind."""
    total = math.fsum(s.flops for s in sites
                      if kind is None or s.kind == kind)
    return total
