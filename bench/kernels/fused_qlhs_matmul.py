"""``kernels/fused_fqt.py:fused_qlhs_matmul`` — the forward GEMM (and the
fused dX under PTQ/PSQ): an f32 (m, k) activation quantized in the K-sweep
times int8 (k, n) weight codes, f32 (m, n) out.  ``stochastic`` adds the
(m, k) uint32 random bits of the SR quantizer."""

from bench.kernels import gemm_bytes_moved


def ops(m: int, k: int, n: int, stochastic: bool = False) -> float:
    return 2.0 * m * k * n


def bytes(m: int, k: int, n: int, stochastic: bool = False) -> float:
    return gemm_bytes_moved(m, k, n, 32, 8) + (4.0 * m * k if stochastic else 0)
