"""GQA attention with FQT projections, KV cache, and cross-attention.

All four projections (Q, K, V, O) are FQT linear layers (the paper quantizes
every linear GEMM); the attention math itself (scores/softmax/value-mix) is
full-precision, exactly like the paper's transformer setting where only
linear layers are quantized.

KV caches are stored *flattened* as ``(B, S, n_kv*head_dim)`` so the tensor-
parallel `model` axis always divides the sharded dim (DESIGN.md Sec. 4).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..core import (QuantPolicy, fp_exempt, get_quantizer, kv_fresh_code,
                    resolve_kv_cache_spec)
from .common import dense, init_dense
from .embeddings import apply_mrope, apply_rope

__all__ = ["init_attention", "attention", "decode_attention",
           "init_kv_cache", "init_kv_cache_quant", "cross_attention_kv",
           "init_paged_kv_pool", "paged_decode_attention"]

_NEG = -1e30


def init_attention(key, cfg: ArchConfig) -> dict:
    hd = cfg.hd
    ks = jax.random.split(key, 4)
    return {
        "wq": init_dense(ks[0], cfg.d_model, cfg.n_heads * hd, cfg.qkv_bias),
        "wk": init_dense(ks[1], cfg.d_model, cfg.n_kv_heads * hd, cfg.qkv_bias),
        "wv": init_dense(ks[2], cfg.d_model, cfg.n_kv_heads * hd, cfg.qkv_bias),
        "wo": init_dense(ks[3], cfg.n_heads * hd, cfg.d_model, False),
    }


def _qkv(p, x, key, policy, cfg, positions, path="attn"):
    B, T, _ = x.shape
    hd, H, KV = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    q = dense(p["wq"], x, key, policy, 1, f"{path}.wq").reshape(B, T, H, hd)
    k = dense(p["wk"], x, key, policy, 2, f"{path}.wk").reshape(B, T, KV, hd)
    v = dense(p["wv"], x, key, policy, 3, f"{path}.wv").reshape(B, T, KV, hd)
    if cfg.rope == "standard":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    elif cfg.rope == "mrope":
        q = apply_mrope(q, positions, cfg.rope_theta)
        k = apply_mrope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa(q, k, v, mask, scale=None):
    """q: (B,T,KV,G,hd), k/v: (B,S,KV,hd), mask: broadcast (B,1,1,T,S).

    ``scale`` multiplies the scores (``ArchConfig.attention_multiplier``;
    None: 1/sqrt(hd))."""
    with fp_exempt("attn.sdpa",
                   "attention scores/probs GEMMs stay full precision — the "
                   "paper quantizes only linear layers (Sec. 2.1 setting)"):
        if scale is None:
            scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(q.dtype)
        scores = jnp.einsum("btkgh,bskh->bkgts", q * scale, k)
        scores = jnp.where(mask, scores, _NEG)
        probs = jax.nn.softmax(scores.astype(jnp.float32),
                               axis=-1).astype(q.dtype)
        out = jnp.einsum("bkgts,bskh->btkgh", probs, v)
        return out


def _apply_attn_hint(q, k, v, sdpa_hint):
    """Context-parallel constraint (ShardingPlan.attn_shardings): q sharded
    over query-time on the model axis; k/v gathered.  Removes the score
    all-reduce GSPMD otherwise emits when heads don't divide the TP axis."""
    if sdpa_hint is None:
        return q, k, v
    hint = sdpa_hint(q.shape[0], q.shape[1], k.shape[1], q.shape[2],
                     k.shape[2], q.shape[3])
    if hint is None:
        return q, k, v
    q_sh, kv_sh = hint
    q = jax.lax.with_sharding_constraint(q, q_sh)
    k = jax.lax.with_sharding_constraint(k, kv_sh)
    v = jax.lax.with_sharding_constraint(v, kv_sh)
    return q, k, v


def attention(p: dict, x: jax.Array, key, policy: QuantPolicy,
              cfg: ArchConfig, positions: jax.Array,
              causal: bool = True,
              kv_override: Optional[tuple] = None,
              return_kv: bool = False, sdpa_hint=None, path: str = "attn"):
    """Full-sequence attention (train / prefill / encoder).

    kv_override: (k, v) of shape (B, S, KV, hd) — cross-attention.
    return_kv: also return the (rotated) k, v for cache initialization.
    path: logical position for per-layer policy resolution; the four
    projections resolve as ``{path}.wq/.wk/.wv/.wo``.
    """
    B, T, _ = x.shape
    hd, H, KV = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    G = H // KV
    if kv_override is not None:
        q = dense(p["wq"], x, key, policy, 1, f"{path}.wq").reshape(B, T, H, hd)
        if cfg.rope == "standard":
            q = apply_rope(q, positions, cfg.rope_theta)
        elif cfg.rope == "mrope":
            q = apply_mrope(q, positions, cfg.rope_theta)
        k, v = kv_override
    else:
        q, k, v = _qkv(p, x, key, policy, cfg, positions, path)
    q, k, v = _apply_attn_hint(q, k, v, sdpa_hint)
    S = k.shape[1]
    if causal:
        mask = (jnp.arange(T)[:, None] >= jnp.arange(S)[None, :])
        mask = mask[None, None, None]
    else:
        mask = jnp.ones((1, 1, 1, T, S), bool)
    out = _sdpa(q.reshape(B, T, KV, G, hd), k, v, mask,
                cfg.attention_multiplier)
    out = out.reshape(B, T, H * hd)
    y = dense(p["wo"], out, key, policy, 4, f"{path}.wo")
    if return_kv:
        return y, (k, v)
    return y


def cross_attention_kv(p: dict, enc_out: jax.Array, key,
                       policy: QuantPolicy, cfg: ArchConfig,
                       path: str = "attn"):
    """Precompute the encoder-side K/V for decoder cross-attention."""
    B, S, _ = enc_out.shape
    hd, KV = cfg.hd, cfg.n_kv_heads
    k = dense(p["wk"], enc_out, key, policy, 2,
              f"{path}.wk").reshape(B, S, KV, hd)
    v = dense(p["wv"], enc_out, key, policy, 3,
              f"{path}.wv").reshape(B, S, KV, hd)
    return k, v


# ---------------------------------------------------------------------------
# Decode path (single new token, flattened KV cache)
# ---------------------------------------------------------------------------

def init_kv_cache(cfg: ArchConfig, batch: int, max_seq: int,
                  dtype=jnp.float32) -> dict:
    flat = cfg.n_kv_heads * cfg.hd
    return {
        "k": jnp.zeros((batch, max_seq, flat), dtype),
        "v": jnp.zeros((batch, max_seq, flat), dtype),
    }


def init_kv_cache_quant(cfg: ArchConfig, batch: int, max_seq: int,
                        bits: int = 8) -> dict:
    """int8-quantized KV cache (core/kv_cache.py codec): each of k/v stores
    shifted-signed int8 codes plus one (scale, zero) pair per (batch,
    position) row — ~4x less HBM per resident slot than the fp32 cache.

    Fresh rows must dequantize to *exact* zeros (scale=1, zero=0, codes at
    ``kv_fresh_code`` = the shifted-signed zero point): the paged engine
    gathers unwritten pool rows and relies on ``0 * masked_prob == 0`` — a
    scale of 0 here would turn the masked garbage into inf/nan and poison
    the softmax of every co-resident slot.
    """
    flat = cfg.n_kv_heads * cfg.hd
    fresh = kv_fresh_code(bits)

    def one():
        return {"codes": jnp.full((batch, max_seq, flat), fresh, jnp.int8),
                "scale": jnp.ones((batch, max_seq), jnp.float32),
                "zero": jnp.zeros((batch, max_seq), jnp.float32)}
    return {"k": one(), "v": one()}


def init_paged_kv_pool(cfg: ArchConfig, n_pages: int, page_size: int,
                       bits: int = 8) -> dict:
    """One layer's shared page pool for the paged serving engine: the int8
    KV codec of :func:`init_kv_cache_quant` laid out as ``n_pages`` fixed
    ``page_size``-row pages instead of per-slot lanes.  Physical pages are
    handed to requests by the host-side allocator (serve/paged.py); this
    tensor never knows which request owns which page.

    Fresh pages dequantize to exact zeros (``kv_fresh_code`` + scale 1) —
    the gather path reads *every* table entry, including never-written
    garbage pages, and masked positions only stay harmless if their values
    are finite (``0 * inf`` would be NaN in the value mix).
    """
    flat = cfg.n_kv_heads * cfg.hd
    fresh = kv_fresh_code(bits)

    def one():
        return {"codes": jnp.full((n_pages, page_size, flat), fresh,
                                  jnp.int8),
                "scale": jnp.ones((n_pages, page_size), jnp.float32),
                "zero": jnp.zeros((n_pages, page_size), jnp.float32)}
    return {"k": one(), "v": one()}


def _is_quant_kv(cache: dict) -> bool:
    return isinstance(cache["k"], dict)


def decode_attention(p: dict, x: jax.Array, cache: dict, index: jax.Array,
                     key, policy: QuantPolicy, cfg: ArchConfig,
                     path: str = "attn", kv_quant=None):
    """One-token attention step. x: (B, 1, d).

    ``index``: scalar position shared by the whole batch (the classic
    decode loop) or a ``(B,)`` vector of per-slot positions (continuous
    batching — every slot sits at its own depth in its own sequence).

    ``cache`` is either the fp ``init_kv_cache`` layout or the int8
    ``init_kv_cache_quant`` layout (detected structurally); for the latter
    the new row is quantized on write and the resident cache dequantized on
    read through the execution backend selected by ``policy.backend``
    (``pallas`` = the fused ``kv_dequant_rows`` kernel).  ``kv_quant``
    optionally names the registered cache quantizer (default ``kv_int8:8``).

    Returns (y, new_cache). Each slot attends over positions <= its index.
    """
    B = x.shape[0]
    hd, H, KV = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    G = H // KV
    pos = jnp.broadcast_to(jnp.asarray(index, jnp.int32).reshape(-1), (B,))
    positions = pos[:, None]
    if cfg.rope == "mrope":
        positions = jnp.broadcast_to(positions[None], (3, B, 1))
    q, k_new, v_new = _qkv(p, x, key, policy, cfg, positions, path)
    flat = KV * hd
    bidx = jnp.arange(B)
    rows_k = k_new.reshape(B, flat)
    rows_v = v_new.reshape(B, flat)
    if _is_quant_kv(cache):
        spec = resolve_kv_cache_spec(True if kv_quant is None else kv_quant)
        qz = get_quantizer(spec.name)
        bits = spec.bits or 8

        def put(side, rows):
            codes, scale, zero = qz.quantize_rows(rows, bits)
            return {"codes": side["codes"].at[bidx, pos].set(codes),
                    "scale": side["scale"].at[bidx, pos].set(scale),
                    "zero": side["zero"].at[bidx, pos].set(zero)}
        cache = {"k": put(cache["k"], rows_k), "v": put(cache["v"], rows_v)}
        S = cache["k"]["codes"].shape[1]

        def get(side):
            rows = qz.dequant_rows(side["codes"], side["scale"], side["zero"],
                                   bits, backend=policy.backend,
                                   interpret=policy.pallas_interpret)
            return rows.reshape(B, S, KV, hd).astype(x.dtype)
        k, v = get(cache["k"]), get(cache["v"])
    else:
        cache = {
            "k": cache["k"].at[bidx, pos].set(rows_k.astype(cache["k"].dtype)),
            "v": cache["v"].at[bidx, pos].set(rows_v.astype(cache["v"].dtype)),
        }
        S = cache["k"].shape[1]
        k = cache["k"].reshape(B, S, KV, hd).astype(x.dtype)
        v = cache["v"].reshape(B, S, KV, hd).astype(x.dtype)
    mask = (jnp.arange(S)[None, :] <= pos[:, None])          # (B, S)
    mask = mask[:, None, None, None, :]                      # (B,1,1,1,S)
    out = _sdpa(q.reshape(B, 1, KV, G, hd), k, v, mask,
                cfg.attention_multiplier)
    y = dense(p["wo"], out.reshape(B, 1, H * hd), key, policy, 4,
              f"{path}.wo")
    return y, cache


def paged_decode_attention(p: dict, x: jax.Array, pool: dict,
                           table: jax.Array, start: jax.Array, key,
                           policy: QuantPolicy, cfg: ArchConfig,
                           path: str = "attn", kv_quant=None):
    """Multi-token attention step over a paged int8 KV pool. x: (B, C, d).

    The one compute primitive of the paged serving engine — ``C`` is what
    varies by use, not the code path:

      * ``C = 1``      plain continuous-batching decode
      * ``C = chunk``  one chunked-prefill slab (long prompts stream in)
      * ``C = k + 1``  the speculative-decode verify pass

    ``pool``: one layer of :func:`init_paged_kv_pool`; ``table``: (B, nb)
    int32 physical page ids in logical-block order (pad unallocated blocks
    with the engine's garbage page); ``start``: (B,) int32 position of each
    row's first token.  Row ``c`` writes position ``start + c`` into its
    page (quantize-on-write, same codec as the dense decode path), then the
    whole table is gathered + dequantized — the Pallas backend streams
    pages via the block-table-prefetch kernel (kernels/kv_gather.py),
    simulate/native run its XLA twin — and position ``start + c`` attends
    over everything ``<= start + c``.  Because the chunk's own rows are
    scattered before the gather, intra-chunk causality falls out of the
    same position mask, and a ``C = 1`` step is arithmetically identical to
    the dense-lane :func:`decode_attention` step.

    Positions are clamped to the table's span ``nb * P - 1``; clamped
    (padding) rows write garbage to the last row, which stays masked until
    a real token is fed at that position — and that write happens before
    the mask ever exposes it.

    Returns (y (B, C, d_model), new pool).
    """
    B, C, _ = x.shape
    hd, H, KV = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    G = H // KV
    nb = table.shape[1]
    P = pool["k"]["codes"].shape[1]
    S = nb * P
    start = jnp.asarray(start, jnp.int32).reshape(B)
    offs = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]  # (B, C)
    positions = offs
    if cfg.rope == "mrope":
        positions = jnp.broadcast_to(positions[None], (3, B, C))
    q, k_new, v_new = _qkv(p, x, key, policy, cfg, positions, path)

    spec = resolve_kv_cache_spec(True if kv_quant is None else kv_quant)
    qz = get_quantizer(spec.name)
    bits = spec.bits or 8
    flat = KV * hd
    offs_w = jnp.minimum(offs, S - 1)
    pids = jnp.take_along_axis(table, offs_w // P, axis=1)           # (B, C)
    rows = offs_w % P

    def put(side, rows_f):
        codes, scale, zero = qz.quantize_rows(rows_f.reshape(B, C, flat),
                                              bits)
        return {"codes": side["codes"].at[pids, rows].set(codes),
                "scale": side["scale"].at[pids, rows].set(scale),
                "zero": side["zero"].at[pids, rows].set(zero)}
    pool = {"k": put(pool["k"], k_new), "v": put(pool["v"], v_new)}

    if policy.backend == "pallas":
        from ..core.backend import resolve_interpret
        from ..kernels.kv_gather import kv_gather_pages
        interp = resolve_interpret(policy.pallas_interpret)

        def get(side):
            return kv_gather_pages(side["codes"], side["scale"],
                                   side["zero"], table, bits=bits,
                                   interpret=interp)
    else:
        from ..kernels.kv_gather import kv_gather_pages_xla

        def get(side):
            return kv_gather_pages_xla(side["codes"], side["scale"],
                                       side["zero"], table, bits=bits)
    k = get(pool["k"]).reshape(B, S, KV, hd).astype(x.dtype)
    v = get(pool["v"]).reshape(B, S, KV, hd).astype(x.dtype)

    mask = (jnp.arange(S, dtype=jnp.int32)[None, None, :]
            <= offs[:, :, None])                             # (B, C, S)
    mask = mask[:, None, None]                               # (B,1,1,C,S)
    out = _sdpa(q.reshape(B, C, KV, G, hd), k, v, mask,
                cfg.attention_multiplier)
    y = dense(p["wo"], out.reshape(B, C, H * hd), key, policy, 4,
              f"{path}.wo")
    return y, pool
