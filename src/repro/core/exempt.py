"""Full-precision exemption registry + jaxpr-level quantization markers.

The paper's guarantees (Theorem 1 unbiasedness, the Eq. 6 variance
decomposition) only cover GEMMs that flow through the ``_fqt`` custom_vjp
under the resolved :class:`~repro.core.policy.QuantPolicy`.  Every other
matmul in the model is either an *intentional* full-precision computation
(attention scores/probs, the RWKV WKV recurrence, Mamba2 SSD state
contractions — the paper quantizes only linear layers) or a *leak* that
silently invalidates the bits-vs-variance story.

This module draws the machine-checked line between the two:

  * :func:`fp_exempt` — a context manager that (a) registers ``path`` with a
    human ``reason`` in a process-global registry and (b) opens a
    ``jax.named_scope`` marker ``fp[path]`` so every equation traced inside
    it is attributable in the jaxpr.  ``repro.analysis audit`` treats GEMMs
    under an ``fp[...]`` marker as declared-exempt; a GEMM under *no* marker
    is a contract violation.

  * :func:`quant_scope` — the marker the FQT primitive itself opens around
    each role's quantize+GEMM work: ``q[path|role]`` for quantized execution,
    ``qfp[path|role]`` for GEMMs the *resolved policy* runs in full precision
    (QAT backwards, ``None`` roles, exact-pinned layers).

Markers ride in ``eqn.source_info.name_stack`` and survive ``jax.grad``,
``custom_vjp``, ``scan``, ``remat``, ``vmap`` and ``jit`` sub-jaxprs, so the
auditor can attribute every ``dot_general`` in a full training step without
any runtime cost — ``named_scope`` is trace-time metadata only.
"""

from __future__ import annotations

import contextlib
import re
import threading
from typing import Dict, Iterator

import jax

__all__ = ["fp_exempt", "quant_scope", "key_scope", "exemption_registry",
           "clear_exemptions", "MARKER_RE", "KEY_SCOPE_RE", "GEMM_ROLES"]

# Roles a quant_scope marker may claim.  "fwd" additionally covers the
# autodiff *transposes* of an exact-pinned forward GEMM (the whole matmul —
# primal and cotangents — is full precision there, so one marker scopes all
# of it).
GEMM_ROLES = ("fwd", "wgrad", "agrad")

# q[path|role] / qfp[path|role] / fp[path] inside a name-stack string.  The
# payload never contains ']' — enforced below — so the lazy body is safe.
MARKER_RE = re.compile(r"\b(qfp|q|fp)\[([^\]]*)\]")

# qk[path]: the key-lineage marker the FQT backward opens around its
# per-site PRNG derivation (fold_in/split), so the soundness pass can name
# the layer a key-aliasing finding belongs to even though that derivation
# happens before any role scope opens.  Deliberately NOT matched by
# MARKER_RE ('qk' is not in its alternation and \b cannot split 'qk'), so
# the contract auditor ignores it.
KEY_SCOPE_RE = re.compile(r"\bqk\[([^\]]*)\]")

_LOCK = threading.Lock()
_REGISTRY: Dict[str, str] = {}


def _check_static_str(name: str, value) -> str:
    if not isinstance(value, str) or not value:
        raise TypeError(f"{name} must be a non-empty static str, got "
                        f"{value!r}; exemption paths are trace-time metadata "
                        f"and cannot be traced values")
    if "]" in value or "[" in value:
        raise ValueError(f"{name}={value!r} may not contain '[' or ']' "
                         f"(they delimit the jaxpr marker)")
    return value


@contextlib.contextmanager
def fp_exempt(path: str, reason: str) -> Iterator[None]:
    """Declare the GEMMs traced inside as intentionally full precision.

    ``path`` is the logical name the audit reports group under (e.g.
    ``"attn.sdpa"``); ``reason`` is the human justification recorded in the
    exemption registry and printed in coverage reports.  Both must be static
    strings — the repo lint rule (``repro.analysis lint``) additionally
    requires them to be *literals* at every call site so the registry is
    statically enumerable.
    """
    _check_static_str("path", path)
    if not isinstance(reason, str) or not reason.strip():
        raise TypeError(f"fp_exempt({path!r}): reason must be a non-empty "
                        f"str explaining why these GEMMs stay full precision")
    with _LOCK:
        _REGISTRY.setdefault(path, reason)
    with jax.named_scope(f"fp[{path}]"):
        yield


def quant_scope(path: str, role: str, quantized: bool):
    """Marker scope for one GEMM role of the FQT primitive.

    ``quantized=True`` emits ``q[path|role]`` (the GEMM and its quantize/
    epilogue work execute under the quantized contract); ``False`` emits
    ``qfp[path|role]`` (the resolved policy runs this role in full
    precision — QAT backward, a ``None`` role, an exact-pinned layer).
    """
    if role not in GEMM_ROLES:
        raise ValueError(f"unknown GEMM role {role!r}; expected one of "
                         f"{GEMM_ROLES}")
    # path may legitimately be "" (direct fqt_matmul calls outside a model);
    # the auditor only enforces the declared model paths.
    if "]" in path or "[" in path:
        raise ValueError(f"path={path!r} may not contain '[' or ']'")
    tag = "q" if quantized else "qfp"
    return jax.named_scope(f"{tag}[{path}|{role}]")


def key_scope(path: str):
    """Marker scope ``qk[path]`` for per-site PRNG-key derivation.

    The FQT backward derives its two SR keys (``fold_in`` + ``split``)
    *before* opening the wgrad/agrad role scopes, so those equations would
    otherwise carry an empty name stack.  The soundness pass
    (repro.analysis.soundness) uses this marker to attribute key-lineage
    findings (aliased or scan-invariant SR keys) to a layer path.
    """
    if "]" in path or "[" in path:
        raise ValueError(f"path={path!r} may not contain '[' or ']'")
    return jax.named_scope(f"qk[{path}]")


def exemption_registry() -> Dict[str, str]:
    """Snapshot of the declared exemptions: {path: reason}."""
    with _LOCK:
        return dict(_REGISTRY)


def clear_exemptions() -> None:
    """Reset the registry (test isolation only)."""
    with _LOCK:
        _REGISTRY.clear()
