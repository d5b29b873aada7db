"""``kernels/q8_matmul.py:q8_matmul`` — int8 x int8 GEMM with the affine
epilogue, f32 out.  Under BHQ it is the activation-gradient GEMM: the
Householder-domain dY codes (m, n) times the weight codes, transposed."""

from bench.kernels import gemm_bytes_moved


def ops(m: int, k: int, n: int) -> float:
    return 2.0 * m * k * n


def bytes(m: int, k: int, n: int) -> float:
    return gemm_bytes_moved(m, k, n, 8, 8)
