"""Quantizers from the StatQuant paper (NeurIPS 2020).

Implements the quantizer family of Sec. 2-4:

  * deterministic per-tensor quantizer (``Q_f``/``Q_theta``, forward pass)
  * stochastic per-tensor quantizer  PTQ  (baseline ``Q_b``; Sec. 3.3)
  * per-sample quantizer             PSQ  (Sec. 4.1)
  * block Householder quantizer      BHQ  (Sec. 4.2, in :mod:`repro.core.bhq`)

All stochastic quantizers are *unbiased*: ``E[Q_b(x)] = x`` (the basis of
Theorem 1).  Every quantizer returns a :class:`QTensor` carrying the integer
codes plus the affine metadata needed for exact dequantization, so callers can
either materialize the dequantized float tensor (``simulate`` path — what the
paper does on GPU, Sec. E) or feed the int8 codes straight into an int8 GEMM
(``native`` path — the deployed TPU MXU execution).

Row convention: for an input of shape ``(..., D)`` the "samples" of PSQ/BHQ
are all leading axes flattened, i.e. each length-``D`` row is one sample.  For
LMs that makes per-sample == per-token, which is where the gradient sparsity
the paper exploits lives (DESIGN.md Sec. 6).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..kernels.tiling import unit_from_bits

__all__ = [
    "QTensor",
    "num_bins",
    "sr_uniform",
    "stochastic_round",
    "quantize_ptq_det",
    "quantize_ptq_stoch",
    "quantize_psq_stoch",
    "dynamic_range",
    "row_dynamic_range",
]

# Tiny epsilon guarding against zero dynamic range (constant rows quantize to
# a single code with zero variance; scale must stay finite).
_EPS = 1e-12


def num_bins(bits: int) -> int:
    """B = 2^b - 1 quantization bins (paper Sec. 3.3)."""
    return (1 << bits) - 1


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class QTensor:
    """Affine-quantized tensor ``x ~= codes / scale + zero``.

    ``codes`` are stored as int8 biased by -128 when ``bits == 8`` would
    overflow signed range; we instead keep the *unbiased* integer code in
    ``int32`` on the simulate path and a shifted ``int8`` code (code - 2^(b-1))
    on the native path.  ``scale`` / ``zero`` broadcast against ``codes``:

      * per-tensor:  scalar scale, scalar zero
      * per-sample:  scale/zero of shape ``(rows, 1)`` against flattened rows

    Dequantization is exactly ``codes / scale + zero`` (paper Eq. in Sec. 3.3:
    ``Q_b(x) = SR(S (x - Z)) / S + Z``).
    """

    codes: jax.Array          # unsigned integer codes in [0, 2^b-1], uint8
    scale: jax.Array          # S
    zero: jax.Array           # Z
    bits: int = dataclasses.field(metadata=dict(static=True))
    shape: tuple = dataclasses.field(metadata=dict(static=True))

    def dequant(self) -> jax.Array:
        flat = self.codes.astype(jnp.float32) / self.scale + self.zero
        return flat.reshape(self.shape)

    @property
    def int8_codes(self) -> jax.Array:
        """Codes shifted to signed int8 for MXU consumption (code - 2^(b-1))."""
        offset = 1 << (self.bits - 1)
        return (self.codes.astype(jnp.int16) - offset).astype(jnp.int8)

    @property
    def int8_offset(self) -> int:
        return 1 << (self.bits - 1)

    @classmethod
    def from_int8(cls, codes8: jax.Array, scale, zero, bits: int,
                  shape) -> "QTensor":
        """Boundary conversion from the kernels' shifted-signed int8 layout
        (``c8 = code - 2^(b-1)``) to the canonical unsigned layout.

        A backend GEMM consuming this tensor shifts back via ``int8_codes``;
        the round-trip is deliberate — one canonical layout at every module
        boundary is the invariant this refactor exists for, and the paired
        elementwise shifts fuse into the adjacent XLA elementwise chain,
        noise next to the O(M*N*K) GEMM they bracket."""
        off = 1 << (bits - 1)
        codes = (codes8.astype(jnp.int16) + off).astype(jnp.uint8)
        return cls(codes=codes, scale=jnp.asarray(scale),
                   zero=jnp.asarray(zero), bits=bits, shape=tuple(shape))


def tensor_min_max(x: jax.Array):
    """(min X, max X) in one fused sweep.

    Row-wise paired min/max reductions compile to a single pass over the
    tensor; the ``optimization_barrier`` stops XLA from re-associating the
    two-stage reduction back into two independent full-tensor sweeps
    (measured ~2.3x slower on CPU).  min-of-row-mins is exactly the flat
    min — no numerical change, only a faster schedule.
    """
    if x.ndim < 2:
        return jnp.min(x), jnp.max(x)
    r = x.reshape(-1, x.shape[-1])
    lo = jnp.min(r, axis=-1)
    hi = jnp.max(r, axis=-1)
    lo, hi = jax.lax.optimization_barrier((lo, hi))
    return jnp.min(lo), jnp.max(hi)


def dynamic_range(x: jax.Array) -> jax.Array:
    """R(X) = max X - min X over the whole tensor (paper Sec. 3.3)."""
    lo, hi = tensor_min_max(x)
    return hi - lo


def row_dynamic_range(x2d: jax.Array) -> jax.Array:
    """Per-row dynamic range R(x_i) for an (N, D) matrix (paper Sec. 4.1)."""
    return jnp.max(x2d, axis=-1) - jnp.min(x2d, axis=-1)


def sr_uniform(key: jax.Array, shape) -> jax.Array:
    """f32 U[0,1) uniforms for SR: ``(random.bits >> 8) * 2^-24``.

    This is the ONE rule for SR randomness across the stack
    (``kernels.tiling.unit_from_bits``): the Pallas kernels take the raw
    uint32 ``random.bits(key, shape)`` draw and apply the same rule inside,
    so for a given key the ``simulate``/``native`` XLA quantizers and the
    ``pallas`` kernels emit bit-identical codes.  The top 24 bits are exact
    in f32, so ``u < 1`` strictly.
    """
    return unit_from_bits(jax.random.bits(key, shape, jnp.uint32))


def stochastic_round(x: jax.Array, key: jax.Array) -> jax.Array:
    """SR(x): ceil w.p. frac(x), floor otherwise — unbiased (paper Sec. 3.3).

    Implemented as floor(x + u), u ~ U[0,1): E[SR(x)] = x and
    Var[SR(x)] = p(1-p) <= 1/4 (Proposition 4).
    """
    return jnp.floor(x + sr_uniform(key, x.shape))


def _flatten_rows(x: jax.Array) -> jax.Array:
    return x.reshape(-1, x.shape[-1])


# ---------------------------------------------------------------------------
# Per-tensor quantizers
# ---------------------------------------------------------------------------

def quantize_ptq_det(x: jax.Array, bits: int = 8) -> QTensor:
    """Deterministic per-tensor quantizer (forward-pass Q_f / Q_theta).

    Round-to-nearest; biased in general but deterministic, as the framework
    requires for the forward pass (Sec. 2.1 assumption).
    """
    B = num_bins(bits)
    zero, hi = tensor_min_max(x)
    scale = B / jnp.maximum(hi - zero, _EPS)
    codes = jnp.clip(jnp.round(scale * (x - zero)), 0, B).astype(jnp.uint8)
    return QTensor(codes=codes, scale=scale, zero=zero, bits=bits, shape=x.shape)


def quantize_ptq_stoch(x: jax.Array, key: jax.Array, bits: int = 8) -> QTensor:
    """PTQ: stochastic per-tensor quantizer (paper Sec. 3.3).

    Q_b(x) = SR(S (x - Z)) / S + Z with Z = min x, S = B / R(x).
    Unbiased: E[Q_b(x)] = x. Variance <= N D R(x)^2 / (4 B^2)  (Eq. 9).
    """
    B = num_bins(bits)
    zero, hi = tensor_min_max(x)
    scale = B / jnp.maximum(hi - zero, _EPS)
    t = scale * (x - zero)                      # in [0, B] by construction
    codes = stochastic_round(t, key)            # SR keeps [0, B]: frac at B is 0
    codes = jnp.clip(codes, 0, B).astype(jnp.uint8)
    return QTensor(codes=codes, scale=scale, zero=zero, bits=bits, shape=x.shape)


def quantize_psq_stoch(x: jax.Array, key: jax.Array, bits: int = 8) -> QTensor:
    """PSQ: stochastic per-sample quantizer (paper Sec. 4.1).

    S = diag(s_1..s_N), s_i = B / R(x_i) — the optimum of problem (12) for
    diagonal S (Appendix D.3). Per-row zero z_i = min x_i.  Variance
    <= D/(4B^2) * sum_i R(x_i)^2 <= PTQ's N D R(X)^2/(4B^2).
    """
    B = num_bins(bits)
    rows = _flatten_rows(x)
    zero = jnp.min(rows, axis=-1, keepdims=True)            # (N, 1)
    rng = jnp.maximum(row_dynamic_range(rows)[:, None], _EPS)
    scale = B / rng                                          # (N, 1)
    t = scale * (rows - zero)
    codes = stochastic_round(t, key)
    codes = jnp.clip(codes, 0, B).astype(jnp.uint8)
    return QTensor(codes=codes, scale=scale, zero=zero, bits=bits, shape=x.shape)


# ---------------------------------------------------------------------------
# Conditional quantizer variance (for Theorem-2 bookkeeping / benchmarks)
# ---------------------------------------------------------------------------

def ptq_variance_bound(x: jax.Array, bits: int) -> jax.Array:
    """Eq. (9): Var[Q_b(X)|X] <= N D R(X)^2 / (4 B^2)."""
    B = num_bins(bits)
    n = x.size
    return n * dynamic_range(x) ** 2 / (4.0 * B * B)


def psq_variance_bound(x: jax.Array, bits: int) -> jax.Array:
    """Appendix D.3: Var <= D/(4B^2) * sum_i R(x_i)^2."""
    B = num_bins(bits)
    rows = _flatten_rows(x)
    d = rows.shape[-1]
    return d * jnp.sum(row_dynamic_range(rows) ** 2) / (4.0 * B * B)


def sr_variance_exact(t: jax.Array) -> jax.Array:
    """Exact SR variance sum: sum_ij p(1-p), p = frac(t) (Proposition 4)."""
    p = t - jnp.floor(t)
    return jnp.sum(p * (1.0 - p))
