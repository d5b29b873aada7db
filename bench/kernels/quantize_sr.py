"""``kernels/quantize_sr.py`` — stochastic-rounding quantizer of an f32
(rows, cols) tensor: the tensor and its uint32 random bits in, int8 codes
(plus a scale and zero per row) out.  No matrix work: bytes bound."""


def ops(rows: int, cols: int) -> float:
    return 0.0


def bytes(rows: int, cols: int) -> float:
    return 4.0 * rows * cols + 4.0 * rows * cols + rows * cols + 8.0 * rows
