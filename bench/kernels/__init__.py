"""Operations and HBM bytes of one call of each kernel, from its shapes.

``bench/kernels/<kernel>.py`` defines ``ops(**shape)`` and
``bytes(**shape)``.  The bytes are the least traffic the call needs: each
operand read once and the result written once.
"""

import importlib


def load(name: str):
    return importlib.import_module(f"bench.kernels.{name}")


def gemm_bytes_moved(m: int, k: int, n: int, lhs_bits: int, rhs_bits: int,
                     out_bytes: int = 4) -> float:
    """HBM bytes of one (m, k) x (k, n) GEMM: both operands in at their
    widths, the result out (``repro.analysis.planner.gemm_bytes_moved``)."""
    return m * k * lhs_bits / 8.0 + k * n * rhs_bits / 8.0 + out_bytes * m * n
