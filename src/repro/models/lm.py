"""Decoder-only LM covering the dense / MoE / VLM / RWKV6 / Zamba2-hybrid
families.  Per-layer parameters are stacked ``(L, ...)`` and the stack runs
under ``lax.scan`` so HLO size is depth-independent (DESIGN.md Sec. 4).

Three entry points per model:
  * loss      — full-sequence training loss (teacher forcing)
  * prefill   — full-sequence forward returning last-position logits + cache
  * decode    — one-token step with cache
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..core import QuantPolicy, fp_exempt
from ..layers import (apply_norm, attention, decode_attention, dense, embed,
                      init_attention, init_embedding, init_kv_cache,
                      init_kv_cache_quant, init_lm_head, init_mamba2_layer,
                      init_mamba2_state, init_mlp, init_moe, init_norm,
                      init_paged_kv_pool, init_rwkv_layer, init_rwkv_state,
                      lm_head, mamba2_decode_step, mamba2_layer, mlp,
                      moe_block, paged_decode_attention, rwkv_decode_step,
                      rwkv_layer)

__all__ = ["init_lm_params", "lm_loss", "lm_prefill", "lm_decode",
           "init_lm_cache", "init_lm_cache_quant", "cross_entropy",
           "scan_or_loop", "init_lm_paged_pool", "lm_paged_decode"]


def scan_or_loop(body, carry, xs, unroll: bool):
    """lax.scan, or an unrolled python loop when ``unroll`` (dry-run probes:
    XLA cost analysis counts while-loop bodies once, so probes unroll)."""
    if not unroll:
        return jax.lax.scan(body, carry, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        xi = jax.tree.map(lambda a, i=i: a[i], xs)
        carry, y = body(carry, xi)
        ys.append(y)
    stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *ys)
    return carry, stacked


def _constrain(h, sharding):
    if sharding is not None:
        return jax.lax.with_sharding_constraint(h, sharding)
    return h


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_tx_layer(key, cfg: ArchConfig) -> dict:
    ka, km, k1, k2 = jax.random.split(key, 4)
    p = {"ln1": init_norm(cfg.d_model, cfg.norm),
         "attn": init_attention(ka, cfg),
         "ln2": init_norm(cfg.d_model, cfg.norm)}
    if cfg.moe_experts:
        p["moe"] = init_moe(km, cfg)
    else:
        p["mlp"] = init_mlp(km, cfg.d_model, cfg.d_ff, cfg.act)
    return p


def init_lm_params(key, cfg: ArchConfig) -> dict:
    ke, kl, kh, ks = jax.random.split(key, 4)
    params = {"embed": init_embedding(ke, cfg),
              "final_norm": init_norm(cfg.d_model, cfg.norm)}
    if not cfg.tie_embeddings:
        params["lm_head"] = init_lm_head(kh, cfg)
    if cfg.family == "hybrid":
        n_outer = cfg.n_layers // cfg.hybrid_period
        inner = cfg.hybrid_period
        lkeys = jax.random.split(kl, n_outer * inner).reshape(n_outer, inner, -1)
        params["layers"] = jax.vmap(jax.vmap(
            lambda k: init_mamba2_layer(k, cfg)))(lkeys)
        fkeys = jax.random.split(jax.random.fold_in(kl, 1), n_outer)
        params["fuse"] = jax.vmap(
            lambda k: {"w": jax.random.normal(k, (2 * cfg.d_model, cfg.d_model))
                       * (0.5 / jnp.sqrt(cfg.d_model))})(fkeys)
        params["shared"] = _init_tx_layer(ks, cfg)     # ONE shared block
    elif cfg.ssm_kind == "rwkv6":
        lkeys = jax.random.split(kl, cfg.n_layers)
        params["layers"] = jax.vmap(lambda k: init_rwkv_layer(k, cfg))(lkeys)
    else:
        lkeys = jax.random.split(kl, cfg.n_layers)
        params["layers"] = jax.vmap(lambda k: _init_tx_layer(k, cfg))(lkeys)
    return params


# ---------------------------------------------------------------------------
# Layer application (full-sequence)
# ---------------------------------------------------------------------------

def _residual(h, y, cfg: ArchConfig):
    """``h + residual_multiplier * y``, in the residual stream's dtype."""
    if cfg.residual_multiplier != 1.0:
        y = y * cfg.residual_multiplier
    return h + y.astype(h.dtype)


def _block(p, h, attend, key, policy, cfg, path, moe_hint=None):
    """One pre-norm decoder block, the body of training, prefill and both
    decodes: ``attend`` (normed input -> (output, what the caller keeps:
    k/v, a cache or a pool)) on one residual branch, the MLP or MoE on the
    other.  Returns (h, aux loss, kept)."""
    att, kept = attend(apply_norm(p["ln1"], h, cfg.norm))
    h = _residual(h, att, cfg)
    x = apply_norm(p["ln2"], h, cfg.norm)
    if cfg.moe_experts:
        y, aux = moe_block(p["moe"], x, key, policy, cfg, moe_hint=moe_hint,
                           path=f"{path}.moe")
    else:
        y, aux = mlp(p["mlp"], x, key, policy, cfg.act,
                     path=f"{path}.mlp"), 0.0
    return _residual(h, y, cfg), aux, kept


def _tx_layer(p, h, key, policy, cfg, positions, state=None, sdpa_hint=None,
              moe_hint=None, path="layers"):
    """(pre-norm attention + MLP/MoE). state: optional kv dict for prefill.

    path: policy-resolution prefix for this block's GEMMs — the scanned
    stack shares one trace, so all stacked layers resolve at the same
    ``layers.*`` paths (the hybrid model's shared block uses ``shared.*``).
    """
    def attend(x):
        if state is None:
            return attention(p["attn"], x, key, policy, cfg, positions,
                             sdpa_hint=sdpa_hint, path=f"{path}.attn"), None
        att, (k, v) = attention(p["attn"], x, key, policy, cfg, positions,
                                return_kv=True, sdpa_hint=sdpa_hint,
                                path=f"{path}.attn")
        B, S = k.shape[0], k.shape[1]
        return att, {"k": k.reshape(B, S, -1), "v": v.reshape(B, S, -1)}
    return _block(p, h, attend, key, policy, cfg, path, moe_hint)


def _forward_seq(params, h, key, policy: QuantPolicy, cfg: ArchConfig,
                 positions, want_cache: bool, remat: bool = False,
                 act_sharding=None, sdpa_hint=None, moe_hint=None):
    """Scan the layer stack over a full sequence.

    Returns (h, aux_loss, cache_or_None). h: (B, T, d).
    act_sharding: optional NamedSharding for the residual stream between
    layers — sequence parallelism (DESIGN.md Sec. 4): P(dp, "model", None)
    shards the token dim over the TP axis, cutting saved-activation memory
    and norm compute by the TP degree."""
    B = h.shape[0]
    h = _constrain(h, act_sharding)

    if cfg.family == "hybrid":
        return _forward_hybrid(params, h, key, policy, cfg, positions,
                               want_cache, remat, act_sharding, sdpa_hint)

    if cfg.ssm_kind == "rwkv6":
        def body(carry, xs):
            hh = carry
            lp, lk = xs
            hh, st = rwkv_layer(lp, hh, lk, policy, cfg, path="layers.rwkv")
            return _constrain(hh, act_sharding), (st if want_cache else 0)
        if remat:
            body = jax.checkpoint(body)
        keys = jax.random.split(key, cfg.n_layers)
        h, states = scan_or_loop(body, h, (params["layers"], keys),
                                 cfg.unroll_scan)
        return h, 0.0, (states if want_cache else None)

    def body(carry, xs):
        hh, aux = carry
        lp, lk = xs
        hh, a, kv = _tx_layer(lp, hh, lk, policy, cfg, positions,
                              state=({} if want_cache else None),
                              sdpa_hint=sdpa_hint, moe_hint=moe_hint)
        return (_constrain(hh, act_sharding), aux + a), (kv if want_cache else 0)
    if remat:
        body = jax.checkpoint(body)
    keys = jax.random.split(key, cfg.n_layers)
    (h, aux), kvs = scan_or_loop(body, (h, 0.0), (params["layers"], keys),
                                 cfg.unroll_scan)
    return h, aux, (kvs if want_cache else None)


def _forward_hybrid(params, h, key, policy, cfg, positions, want_cache,
                    remat=False, act_sharding=None, sdpa_hint=None):
    """Zamba2: scan of [hybrid_period x mamba2] + shared attn block."""
    n_outer = cfg.n_layers // cfg.hybrid_period
    h0 = h                                       # residual stream input
    shared = params["shared"]

    def outer_body(carry, xs):
        hh = carry
        (mp, fuse, okey) = xs
        ikeys = jax.random.split(okey, cfg.hybrid_period + 1)

        def inner_body(ih, ixs):
            lp, lk = ixs
            ih, st = mamba2_layer(lp, ih, lk, policy, cfg,
                                  path="layers.mamba")
            return _constrain(ih, act_sharding), (st if want_cache else 0)
        hh, msts = scan_or_loop(inner_body, hh,
                                (mp, ikeys[:cfg.hybrid_period]),
                                cfg.unroll_scan)
        # shared attention block on concat(h, h0), fused back to d_model.
        # The fuse projection is a linear layer like any other — it runs
        # through `dense` so FQT covers it (path "layers.fuse"; the first
        # quantization-contract audit flagged the old raw `@` as a leak).
        skey = ikeys[-1]
        z = dense(fuse, jnp.concatenate([hh, h0], axis=-1), skey, policy,
                  0x70, "layers.fuse")
        if want_cache:
            z2, _, kv = _tx_layer(shared, z, skey, policy, cfg, positions,
                                  state={}, sdpa_hint=sdpa_hint,
                                  path="shared")
        else:
            z2, _, kv = _tx_layer(shared, z, skey, policy, cfg, positions,
                                  sdpa_hint=sdpa_hint, path="shared")
        hh = hh + z2.astype(hh.dtype)
        return _constrain(hh, act_sharding), ((msts, kv) if want_cache else 0)

    if remat:
        outer_body = jax.checkpoint(outer_body)
    okeys = jax.random.split(key, n_outer)
    h, caches = scan_or_loop(outer_body, h,
                             (params["layers"], params["fuse"], okeys),
                             cfg.unroll_scan)
    return h, 0.0, (caches if want_cache else None)


# ---------------------------------------------------------------------------
# Embedding-or-token inputs
# ---------------------------------------------------------------------------

def _input_embed(params, batch, cfg: ArchConfig):
    if "embeds" in batch:                        # VLM stub frontend
        h = batch["embeds"]
    else:
        h = embed(params["embed"], batch["tokens"])
    if cfg.embedding_multiplier != 1.0:
        h = h * cfg.embedding_multiplier
    return h


def _head(params, h, key, policy, cfg: ArchConfig):
    """Output logits over ``logits_scaling``.  The head GEMM resolves at
    ``lm_head`` whether its weight is its own leaf or, with tied
    embeddings, the embedding table transposed — then the table's gradient
    is the gather's plus the head's."""
    w = (params["embed"]["table"].T if cfg.tie_embeddings
         else params["lm_head"]["w"])
    logits = lm_head({"w": w}, h, key, policy)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


def _positions(batch, cfg, B, T):
    if "positions" in batch:
        return batch["positions"]
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    if cfg.rope == "mrope":
        pos = jnp.broadcast_to(pos[None], (3, B, T))
    return pos


def _log_likelihood(logits: jax.Array, labels: jax.Array,
                    vocab_size: int) -> jax.Array:
    """Per-token log-probability of the label, padding columns masked."""
    with fp_exempt("lm_head.ce", "the head's log-softmax and cross-entropy "
                   "are elementwise and reductions, no linear layer"):
        vp = logits.shape[-1]
        if vp > vocab_size:
            neg = jnp.full((vp - vocab_size,), -1e30, logits.dtype)
            logits = logits.at[..., vocab_size:].set(neg)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  vocab_size: int) -> jax.Array:
    """Mean next-token CE with padded-vocab masking."""
    return -jnp.mean(_log_likelihood(logits, labels, vocab_size))


def _chunk_rows_sharding(act_sharding):
    """Sharding for flattened token rows, derived from the residual-stream
    sharding.  The (B,T,d)->(rows,d) reshape mixes the data- and model-axis
    shards, which breaks GSPMD propagation and silently REPLICATES the head
    GEMMs (measured 16x flops, EXPERIMENTS.md Perf iteration 1) — an explicit
    constraint on the chunked rows restores sharding."""
    if act_sharding is None:
        return None
    axes = []
    for part in tuple(act_sharding.spec)[:2]:
        if part is None:
            continue
        axes.extend(part if isinstance(part, (tuple, list)) else [part])
    if not axes:
        return None
    from jax.sharding import NamedSharding, PartitionSpec
    return NamedSharding(act_sharding.mesh,
                         PartitionSpec(None, tuple(axes), None))


def chunked_head_loss(params, h, labels, key, policy, cfg,
                      n_chunks: int, unroll: bool,
                      act_sharding=None) -> jax.Array:
    """lm_head projection + CE over token chunks.

    At 150-250k vocab, materializing full (tokens x vocab) logits plus the
    FQT backward's SR uniforms and codes for the head gradient dominates HBM
    (the dry-run profile showed ~40 GiB/device of head-path tensors).
    Chunking bounds every head-path tensor to tokens/n_chunks; the chunk loop
    is a scan, so the backward (including the quantized head-grad GEMMs)
    streams too.
    """
    d = h.shape[-1]
    h2 = h.reshape(-1, d)
    y2 = labels.reshape(-1)
    R = h2.shape[0]
    if n_chunks <= 1 or R % n_chunks != 0:
        return cross_entropy(_head(params, h, key, policy, cfg), labels,
                             cfg.vocab_size)
    hc = h2.reshape(n_chunks, R // n_chunks, d)
    yc = y2.reshape(n_chunks, R // n_chunks)
    rows_sh = _chunk_rows_sharding(act_sharding)
    if rows_sh is not None:
        n_shards = 1
        for ax in tuple(rows_sh.spec)[1]:
            n_shards *= rows_sh.mesh.shape[ax]
        if (R // n_chunks) % n_shards == 0:
            hc = jax.lax.with_sharding_constraint(hc, rows_sh)

    def body(acc, xs):
        h_c, y_c, c_idx = xs
        # per-chunk fold: Theorem 1 needs the head-grad SR draws independent
        # across chunks — reusing `key` verbatim here made every chunk's
        # quantization noise identical (caught by repro.analysis soundness)
        logits = _head(params, h_c, jax.random.fold_in(key, c_idx), policy,
                       cfg)
        return acc + jnp.sum(_log_likelihood(logits, y_c, cfg.vocab_size)), 0

    total, _ = scan_or_loop(body, jnp.float32(0.0),
                            (hc, yc, jnp.arange(n_chunks)), unroll)
    return -total / R


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def lm_loss(params, batch, key, policy: QuantPolicy, cfg: ArchConfig,
            remat: bool = False, dtype=None, act_sharding=None,
            sdpa_hint=None, moe_hint=None, loss_chunks: int = 1):
    h = _input_embed(params, batch, cfg)
    if dtype is not None:
        h = h.astype(dtype)
    B, T = h.shape[0], h.shape[1]
    pos = _positions(batch, cfg, B, T)
    h, aux, _ = _forward_seq(params, h, key, policy, cfg, pos,
                             want_cache=False, remat=remat,
                             act_sharding=act_sharding, sdpa_hint=sdpa_hint,
                             moe_hint=moe_hint)
    h = apply_norm(params["final_norm"], h, cfg.norm)
    loss = chunked_head_loss(params, h, batch["labels"], key, policy, cfg,
                             loss_chunks, cfg.unroll_scan,
                             act_sharding=act_sharding)
    if cfg.moe_experts:
        loss = loss + 0.01 * aux / cfg.n_layers
    return loss, {"ce": loss, "aux": aux}


def init_lm_cache(cfg: ArchConfig, batch: int, max_seq: int,
                  dtype=jnp.float32):
    """Abstract-safe cache constructor (works under jax.eval_shape)."""
    if cfg.family == "hybrid":
        n_outer = cfg.n_layers // cfg.hybrid_period
        mam = jax.tree.map(
            lambda x: jnp.broadcast_to(
                x, (n_outer, cfg.hybrid_period) + x.shape),
            init_mamba2_state(cfg, batch, dtype))
        kv = jax.tree.map(lambda x: jnp.broadcast_to(x, (n_outer,) + x.shape),
                          init_kv_cache(cfg, batch, max_seq, dtype))
        return {"mamba": mam, "kv": kv, "index": jnp.zeros((), jnp.int32)}
    if cfg.ssm_kind == "rwkv6":
        st = jax.tree.map(lambda x: jnp.broadcast_to(x, (cfg.n_layers,) + x.shape),
                          init_rwkv_state(cfg, batch, dtype))
        return {"state": st, "index": jnp.zeros((), jnp.int32)}
    kv = jax.tree.map(lambda x: jnp.broadcast_to(x, (cfg.n_layers,) + x.shape),
                      init_kv_cache(cfg, batch, max_seq, dtype))
    return {"kv": kv, "index": jnp.zeros((), jnp.int32)}


def init_lm_cache_quant(cfg: ArchConfig, batch: int, max_seq: int):
    """int8-quantized variant of :func:`init_lm_cache` (serving decode).

    Only the transformer families carry a KV cache to quantize; the
    recurrent state of rwkv6/hybrid models is read-modify-write every step
    and stays full precision.  ``index`` is a per-slot ``(batch,)`` vector —
    the continuous-batching engine steps every slot at its own position.
    """
    if cfg.family == "hybrid" or cfg.ssm_kind == "rwkv6":
        raise ValueError(
            f"{cfg.name}: quantized KV caches need a transformer KV cache; "
            f"family={cfg.family!r}/ssm_kind={cfg.ssm_kind!r} keeps dense "
            f"recurrent state")
    kv = jax.tree.map(lambda x: jnp.broadcast_to(x, (cfg.n_layers,) + x.shape),
                      init_kv_cache_quant(cfg, batch, max_seq))
    return {"kv": kv, "index": jnp.zeros((batch,), jnp.int32)}


def init_lm_paged_pool(cfg: ArchConfig, n_pages: int, page_size: int):
    """Stacked ``(L, ...)`` paged int8 KV pool for the paged serving engine
    (serve/paged.py): one shared set of ``n_pages`` physical pages per
    layer, sliced alongside the layer stack by ``lax.scan``.  One block
    table indexes all layers — page id ``i`` names row ``i`` of every
    layer's pool, so the allocator hands out one id per logical block, not
    one per (layer, block).
    """
    if cfg.family == "hybrid" or cfg.ssm_kind == "rwkv6":
        raise ValueError(
            f"{cfg.name}: paged KV pools need a transformer KV cache; "
            f"family={cfg.family!r}/ssm_kind={cfg.ssm_kind!r} keeps dense "
            f"recurrent state")
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (cfg.n_layers,) + x.shape),
        init_paged_kv_pool(cfg, n_pages, page_size))


def lm_paged_decode(params, pool, batch, policy: QuantPolicy,
                    cfg: ArchConfig, table, start, kv_quant=None):
    """Paged multi-token forward: the engine's one compute primitive.

    batch: ``tokens (B, C)``; ``table``: (B, nb) int32 block tables;
    ``start``: (B,) int32 position of each row's first token.  ``C = 1`` is
    plain decode, ``C = chunk`` is chunked prefill, ``C = k + 1`` is the
    speculative verify — see :func:`repro.layers.paged_decode_attention`.
    Returns (logits (B, C, Vp), new pool).
    """
    key = jax.random.PRNGKey(0)       # fwd quantizers are deterministic
    h = _input_embed(params, batch, cfg).astype(jnp.float32)

    def body(hh, xs):
        lp, pool_l, lk = xs
        hh, _, pool_l = _block(lp, hh, lambda x: paged_decode_attention(
            lp["attn"], x, pool_l, table, start, lk, policy, cfg,
            path="layers.attn", kv_quant=kv_quant), lk, policy, cfg, "layers")
        return hh, pool_l
    keys = jax.random.split(key, cfg.n_layers)
    h, pools = scan_or_loop(body, h, (params["layers"], pool, keys),
                            cfg.unroll_scan)
    h = apply_norm(params["final_norm"], h, cfg.norm)
    return _head(params, h, key, policy, cfg), pools


def lm_prefill(params, batch, policy: QuantPolicy, cfg: ArchConfig,
               max_seq: Optional[int] = None, dtype=None, sdpa_hint=None,
               last_pos=None):
    """Forward the prompt; return (last-position logits, cache).

    ``last_pos``: optional ``(B,)`` int32 — take each row's logits at that
    position instead of ``T - 1`` (serving engines right-pad prompts into
    length buckets; the true last token then sits before the padding).
    """
    key = jax.random.PRNGKey(0)                   # fwd quantizers are deterministic
    h = _input_embed(params, batch, cfg)
    if dtype is not None:
        h = h.astype(dtype)
    B, T = h.shape[0], h.shape[1]
    max_seq = max_seq or T
    pos = _positions(batch, cfg, B, T)
    h, _, cache = _forward_seq(params, h, key, policy, cfg, pos,
                               want_cache=True, sdpa_hint=sdpa_hint)
    h = apply_norm(params["final_norm"], h, cfg.norm)
    h_last = (h[:, -1:] if last_pos is None
              else h[jnp.arange(B), last_pos][:, None])
    logits = _head(params, h_last, key, policy, cfg)

    index = jnp.asarray(T, jnp.int32)
    if cfg.family == "hybrid":
        msts, kvs = cache
        kvs = _pad_kv(kvs, max_seq)
        out = {"mamba": msts, "kv": kvs, "index": index}
    elif cfg.ssm_kind == "rwkv6":
        out = {"state": cache, "index": index}
    else:
        out = {"kv": _pad_kv(cache, max_seq), "index": index}
    return logits, out


def _pad_kv(kvs, max_seq):
    def pad(x):                                   # (L, B, T, f) -> (L, B, S, f)
        T = x.shape[2]
        if T == max_seq:
            return x
        return jnp.pad(x, ((0, 0), (0, 0), (0, max_seq - T), (0, 0)))
    return jax.tree.map(pad, kvs)


def _cache_dtype(cache):
    for _path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        if leaf.dtype in (jnp.bfloat16, jnp.float32, jnp.float16):
            return leaf.dtype
    return jnp.float32


def lm_decode(params, cache, batch, policy: QuantPolicy, cfg: ArchConfig,
              positions=None, kv_quant=None):
    """One-token decode step: batch has `tokens` (B,1) or `embeds` (B,1,d).

    ``positions``: optional ``(B,)`` per-slot positions overriding the
    cache's own ``index`` — the continuous-batching serving engine owns the
    slot positions and passes them every step.  ``kv_quant`` names the cache
    quantizer when ``cache`` uses the int8 layout (``init_lm_cache_quant``).
    """
    key = jax.random.PRNGKey(0)
    h = _input_embed(params, batch, cfg).astype(_cache_dtype(cache))
    B = h.shape[0]
    index = cache["index"] if positions is None else positions

    if cfg.family == "hybrid":
        h0 = h
        shared = params["shared"]

        def outer(carry, xs):
            hh = carry
            mp, fuse, mst, kvc, okey = xs
            ikeys = jax.random.split(okey, cfg.hybrid_period + 1)

            def inner(ih, ixs):
                lp, lst, lk = ixs
                ih, st = mamba2_decode_step(lp, ih, lst, lk, policy, cfg,
                                            path="layers.mamba")
                return ih, st
            hh, msts = scan_or_loop(inner, hh,
                                    (mp, mst, ikeys[:cfg.hybrid_period]),
                                    cfg.unroll_scan)
            z = dense(fuse, jnp.concatenate([hh, h0], axis=-1), ikeys[-1],
                      policy, 0x70, "layers.fuse")
            z, _, kvc = _block(shared, z, lambda x: decode_attention(
                shared["attn"], x, kvc, index, ikeys[-1], policy, cfg,
                path="shared.attn"), ikeys[-1], policy, cfg, "shared")
            hh = hh + z
            return hh, (msts, kvc)
        n_outer = cfg.n_layers // cfg.hybrid_period
        okeys = jax.random.split(key, n_outer)
        h, (msts, kvs) = scan_or_loop(
            outer, h, (params["layers"], params["fuse"], cache["mamba"],
                       cache["kv"], okeys), cfg.unroll_scan)
        new_cache = {"mamba": msts, "kv": kvs, "index": index + 1}
    elif cfg.ssm_kind == "rwkv6":
        def body(hh, xs):
            lp, lst, lk = xs
            hh, st = rwkv_decode_step(lp, hh, lst, lk, policy, cfg,
                                      path="layers.rwkv")
            return hh, st
        keys = jax.random.split(key, cfg.n_layers)
        h, sts = scan_or_loop(body, h, (params["layers"], cache["state"],
                                        keys), cfg.unroll_scan)
        new_cache = {"state": sts, "index": index + 1}
    else:
        def body(hh, xs):
            lp, kvc, lk = xs
            hh, _, kvc = _block(lp, hh, lambda x: decode_attention(
                lp["attn"], x, kvc, index, lk, policy, cfg,
                path="layers.attn", kv_quant=kv_quant), lk, policy, cfg,
                "layers")
            return hh, kvc
        keys = jax.random.split(key, cfg.n_layers)
        h, kvs = scan_or_loop(body, h, (params["layers"], cache["kv"], keys),
                              cfg.unroll_scan)
        new_cache = {"kv": kvs, "index": index + 1}

    h = apply_norm(params["final_norm"], h, cfg.norm)
    return _head(params, h, key, policy, cfg), new_cache
