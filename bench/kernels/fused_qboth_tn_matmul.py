"""``kernels/fused_fqt.py:fused_qboth_tn_matmul`` — the weight-gradient GEMM
X^T dY: both f32 operands, X (m, k) and dY (m, n), quantized on the fly (dY
stochastically, from (m, n) uint32 random bits), f32 (k, n) out."""


def ops(m: int, k: int, n: int) -> float:
    return 2.0 * m * k * n


def bytes(m: int, k: int, n: int) -> float:
    return 4.0 * m * k + 4.0 * m * n + 4.0 * m * n + 4.0 * k * n
