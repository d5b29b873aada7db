"""Granite-3.0 2B base: GQA, SwiGLU, RMSNorm, tied embeddings and four
muP-style scalars [hf:ibm-granite/granite-3.0-2b-base; hf].

Published values from
https://huggingface.co/ibm-granite/granite-3.0-2b-base/blob/main/config.json
(model_type ``granite``; IBM, "Granite 3.0 Language Models", Oct 2024):
40 layers, hidden 2048, 32 query and 8 key/value heads of 64, intermediate
8192, vocab 49155, RMSNorm eps 1e-5, RoPE theta 10000, no bias, 4096
positions, tied word embeddings, ``embedding_multiplier`` 12.0,
``attention_multiplier`` 0.015625, ``residual_multiplier`` 0.22,
``logits_scaling`` 8.0.  The published ``attention_dropout`` is 0.1; the
program has no dropout, so it runs at 0.
"""
from .base import ArchConfig

CONFIG = ArchConfig(
    name="granite-3-2b", family="dense", n_layers=40, d_model=2048,
    n_heads=32, n_kv_heads=8, head_dim=64, d_ff=8192, vocab_size=49_155,
    act="swiglu", norm="rmsnorm", qkv_bias=False, rope="standard",
    rope_theta=10_000.0, embedding_multiplier=12.0, residual_multiplier=0.22,
    attention_multiplier=0.015625, logits_scaling=8.0, tie_embeddings=True,
    source="hf:ibm-granite/granite-3.0-2b-base; hf",
)
SMOKE = CONFIG.reduced()
