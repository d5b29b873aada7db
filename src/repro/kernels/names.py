"""The stable name every Pallas kernel passes to ``pl.pallas_call(name=)``.

The name becomes the kernel's HLO instruction name (``%q8_matmul.3 =
custom-call(...)``) and joins its ``op_name`` metadata
(``.../q[path|role]/.../q8_matmul/pallas_call``), so a compiled step and a
profiler trace name each kernel call after its public function, whatever
the kernel body is called.  Trace readers match these names exactly.
"""

FUSED_QLHS = "fused_qlhs_matmul"
FUSED_QBOTH_TN = "fused_qboth_tn_matmul"
FUSED_QLHS_PACKED = "fused_qlhs_packed_matmul"
Q8_MATMUL = "q8_matmul"
PACKED_MATMUL = "packed_matmul"
QUANTIZE_SR_ROWS = "quantize_sr_rows"
QUANTIZE_SR_TENSOR = "quantize_sr_tensor"
KV_GATHER = "kv_gather_pages"
KV_DEQUANT = "kv_dequant_rows"

KERNEL_NAMES = (FUSED_QLHS, FUSED_QBOTH_TN, FUSED_QLHS_PACKED, Q8_MATMUL,
                PACKED_MATMUL, QUANTIZE_SR_ROWS, QUANTIZE_SR_TENSOR,
                KV_GATHER, KV_DEQUANT)
