"""What a launcher sets up and reports about the device it runs on.

``enable_compile_cache`` keeps JAX's persistent compilation cache in one
fixed place, so a second run of the same program skips its compiles;
``device_summary`` is the line each launcher prints first, naming the
device and whether the Pallas kernels are compiled or interpreted.
"""

from __future__ import annotations

import os
from typing import Optional

import jax

from ..core.backend import resolve_interpret

__all__ = ["CACHE_DIR", "enable_compile_cache", "device_summary"]

# <checkout>/.jax_cache — this file is <checkout>/src/repro/launch/device.py
CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir, os.pardir, ".jax_cache"))


def enable_compile_cache() -> Optional[str]:
    """Turn JAX's persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps the cache there
    already and nothing else is set.  Otherwise, on a TPU, the cache goes
    to :data:`CACHE_DIR`, one fixed directory in the checkout; elsewhere it
    stays off (None), since XLA:CPU entries reload with a warning that the
    compiling machine's features differ.  Call before the first compile:
    JAX fixes the cache location when it first uses it.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() != "tpu":
        return None
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR


def device_summary(policy=None) -> str:
    """``platform=... kind=... count=...`` of the default devices, plus
    ``pallas=compiled|interpreted`` when ``policy`` runs the Pallas
    backend."""
    dev = jax.devices()[0]
    line = (f"platform={dev.platform} kind={dev.device_kind} "
            f"count={jax.device_count()}")
    backend: Optional[str] = getattr(policy, "backend", None)
    if backend == "pallas":
        interp = resolve_interpret(policy.pallas_interpret)
        line += f" pallas={'interpreted' if interp else 'compiled'}"
    return line
