"""Pallas TPU kernels for the paper's compute hot-spots.

  q8_matmul.py     int8 x int8 -> int32 GEMM + fused affine epilogue
  q4_matmul.py     int8 x bit-packed sub-byte GEMM (unpack in VMEM)
  pack.py          bit-plane pack/unpack + the PackedTensor container
  fused_fqt.py     quantize -> GEMM -> epilogue megakernels (no HBM codes)
  quantize_sr.py   fused dynamic-range + scale + stochastic-round quantize
  kv_dequant.py    fused affine dequantize of int8 KV-cache rows
  kv_gather.py     block-table page gather + dequantize (paged serving)
  tiling.py        tiles (shape rule or default) + pad-and-slice helpers
  ops.py           wrappers wiring kernels to the quantizer algebra
  ref.py           pure-jnp oracles (the allclose targets)

NOTE: ``ops`` is intentionally NOT imported here — it depends on
``repro.core.backend`` (which imports the kernel modules below), so eager
import would cycle.  Use ``from repro.kernels.ops import ...``.
"""

from .fused_fqt import (fused_qboth_tn_matmul, fused_qboth_tn_matmul_xla,
                        fused_qlhs_matmul, fused_qlhs_matmul_xla,
                        fused_qlhs_packed_matmul,
                        fused_qlhs_packed_matmul_xla)
from .kv_dequant import kv_dequant_rows
from .kv_gather import kv_gather_pages, kv_gather_pages_xla
from .pack import (PackedTensor, codes_per_byte, max_safe_k_packed,
                   pack_codes, pack_qtensor, packed_nbytes, unpack_codes)
from .q4_matmul import packed_matmul, packed_matmul_xla
from .q8_matmul import q8_matmul
from .quantize_sr import quantize_sr_rows, quantize_sr_tensor
from .tiling import lookup_tiles

__all__ = [
    "q8_matmul", "quantize_sr_rows", "quantize_sr_tensor", "kv_dequant_rows",
    "kv_gather_pages", "kv_gather_pages_xla",
    "fused_qlhs_matmul", "fused_qlhs_matmul_xla", "fused_qboth_tn_matmul",
    "fused_qboth_tn_matmul_xla", "fused_qlhs_packed_matmul",
    "fused_qlhs_packed_matmul_xla", "lookup_tiles",
    "PackedTensor", "codes_per_byte", "pack_codes", "unpack_codes",
    "pack_qtensor", "packed_nbytes", "max_safe_k_packed",
    "packed_matmul", "packed_matmul_xla",
]
