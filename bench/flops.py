"""Model FLOPs: the operations the model needs, counted from its sizes.

Matrix multiplications at 2 FLOPs per multiply-add over the real vocabulary
(the padded head columns are not the model's work), plus causal attention:
a token at position ``i`` scores and mixes ``i + 1`` keys, ``4 * H * hd *
(i + 1)`` FLOPs per layer forward.  Training counts forward and backward
(3x forward); recomputation is not counted.  The embedding gather, norms and
softmax are left out, as is usual.
"""

from __future__ import annotations


def matmul_params(m: dict) -> int:
    """Weights that take part in a matrix multiplication per token."""
    d, ff, L = m["d_model"], m["d_ff"], m["n_layers"]
    qkv = d * (m["n_heads"] + 2 * m["n_kv_heads"]) * m["head_dim"]
    out = m["n_heads"] * m["head_dim"] * d
    mlp = (3 if m["act"] == "swiglu" else 2) * d * ff
    return L * (qkv + out + mlp) + d * m["vocab_size"]


def attention_fwd(m: dict, positions: int) -> float:
    """Forward attention FLOPs of a token that attends ``positions`` keys."""
    return 4.0 * m["n_layers"] * m["n_heads"] * m["head_dim"] * positions


def train_per_token(m: dict, seq: int) -> float:
    """Forward + backward FLOPs per token of a causal sequence of ``seq``."""
    mean_keys = (seq + 1) / 2.0
    return 3.0 * (2.0 * matmul_params(m) + attention_fwd(m, mean_keys))
