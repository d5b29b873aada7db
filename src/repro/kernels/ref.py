"""Pure-jnp oracles for the Pallas kernels (the allclose targets)."""

from __future__ import annotations

import jax.numpy as jnp

from .tiling import unit_from_bits

__all__ = ["q8_matmul_ref", "quantize_sr_rows_ref", "quantize_sr_tensor_ref"]

_EPS = 1e-12


def q8_matmul_ref(x8, y8, rs, cs, r2, u, a, b):
    """out[i,j] = (x8 @ y8)[i,j] * rs_i * cs_j + r2_i * u_j + a_i + b_j."""
    acc = (x8.astype(jnp.int32) @ y8.astype(jnp.int32)).astype(jnp.float32)
    return (acc * rs[:, None] * cs[None, :]
            + r2[:, None] * u[None, :] + a[:, None] + b[None, :])


def _sr(t, rbits):
    return jnp.floor(t + unit_from_bits(rbits))


def quantize_sr_rows_ref(x, rbits, bits=8):
    B = (1 << bits) - 1
    lo = jnp.min(x, axis=1, keepdims=True)
    hi = jnp.max(x, axis=1, keepdims=True)
    scale = B / jnp.maximum(hi - lo, _EPS)
    q = jnp.clip(_sr(scale * (x - lo), rbits), 0, B)
    codes = (q - (B + 1) // 2).astype(jnp.int8)
    return codes, scale, lo


def quantize_sr_tensor_ref(x, rbits, bits=8):
    B = (1 << bits) - 1
    lo, hi = jnp.min(x), jnp.max(x)
    scale = B / jnp.maximum(hi - lo, _EPS)
    q = jnp.clip(_sr(scale * (x - lo), rbits), 0, B)
    codes = (q - (B + 1) // 2).astype(jnp.int8)
    return codes, scale, lo


def dequant_rows_ref(codes, scale, zero, bits=8):
    off = (1 << bits) // 2
    return (codes.astype(jnp.float32) + off) / scale + zero
