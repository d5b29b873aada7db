"""Serving CLI: a thin driver over the continuous-batching engine.

Inference quantization (paper Sec. 1): weights/activations run through the
deterministic forward quantizers; no gradient path.  The engine
(:mod:`repro.serve`) owns the scheduling — a fixed pool of decode slots kept
at full static batch, per-request prefill, EOS/length eviction — and the
optional int8 KV cache; this module parses arguments, builds (or restores)
the parameters, submits a mixed-length synthetic workload, and reports
throughput + per-token latency percentiles.  ``--paged`` swaps in the
paged-pool engine (block tables, prefix reuse, chunked prefill, optional
``--spec-decode`` self-speculative decoding — serve/paged.py).

``generate`` is the legacy static-batch helper (prefill once, decode the
whole batch in lockstep) kept for the examples; it now stops early once
every row has emitted ``eos_id`` instead of always burning ``max_new``
steps.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config
from ..core import QuantPolicy
from ..models import build_model
from ..serve import ServeEngine
from .device import device_summary, enable_compile_cache

__all__ = ["generate", "main"]


def generate(model, params, batch, policy: QuantPolicy, *, max_new: int,
             max_seq: int, greedy: bool = True, key=None, eos_id=None):
    """Prefill the prompt then greedy-decode up to ``max_new`` tokens.

    Returns (B, n) with n <= max_new: decoding stops as soon as every row
    has emitted ``eos_id`` (rows that finish early keep emitting ``eos_id``
    while the rest of the batch drains).  ``eos_id=None`` disables early
    stopping and always returns (B, max_new).
    """
    cfg = model.cfg
    prefill = jax.jit(lambda p, b: model.prefill(p, b, policy, max_seq))
    decode = jax.jit(lambda p, c, b: model.decode(p, c, b, policy),
                     donate_argnums=(1,))

    logits, cache = prefill(params, batch)
    out = []
    tok = jnp.argmax(logits[:, -1, :cfg.vocab_size], axis=-1)[:, None]
    B = tok.shape[0]
    finished = jnp.zeros((B,), bool)
    for _ in range(max_new):
        if eos_id is not None:
            finished = finished | (tok[:, 0] == eos_id)
            tok = jnp.where(finished[:, None], eos_id, tok)
        out.append(tok)
        if eos_id is not None and bool(finished.all()):
            break
        dbatch = {"tokens": tok.astype(jnp.int32)}
        if cfg.family == "vlm":
            # stub frontend: decode steps feed token embeddings directly
            dbatch = {"embeds": params["embed"]["table"][tok[:, 0]][:, None]}
        logits, cache = decode(params, cache, dbatch)
        tok = jnp.argmax(logits[:, -1, :cfg.vocab_size], axis=-1)[:, None]
    return jnp.concatenate(out, axis=1)


def _latency_stats(step_times):
    dts = np.asarray([dt for dt, n in step_times if n > 0])
    if dts.size == 0:
        return 0.0, 0.0
    return float(np.percentile(dts, 50)), float(np.percentile(dts, 95))


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="continuous-batching quantized serving driver")
    ap.add_argument("--arch", default="statquant-tx")
    ap.add_argument("--smoke", dest="smoke", action="store_true",
                    help="reduced config (default)")
    ap.add_argument("--full", dest="smoke", action="store_false",
                    help="full-size config")
    ap.set_defaults(smoke=True)
    ap.add_argument("--slots", type=int, default=4,
                    help="decode-slot pool size (static decode batch)")
    ap.add_argument("--max-seq", type=int, default=64,
                    help="per-slot KV cache length")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--min-prompt", type=int, default=4)
    ap.add_argument("--max-prompt", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="<= 0 => greedy")
    ap.add_argument("--top-k", type=int, default=0, help="<= 0 => disabled")
    ap.add_argument("--top-p", type=float, default=0.0,
                    help="nucleus sampling mass; outside (0,1) => disabled")
    ap.add_argument("--paged", action="store_true",
                    help="paged int8 KV engine (serve/paged.py): shared "
                         "page pool + block tables + prefix reuse instead "
                         "of one max-seq lane per slot")
    ap.add_argument("--page-size", type=int, default=8,
                    help="rows per KV page (paged mode)")
    ap.add_argument("--pages", type=int, default=None,
                    help="page-pool size; default sizes for slots lanes")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked-prefill width (paged mode); default = "
                         "whole-prompt prefill")
    ap.add_argument("--spec-decode", action="store_true",
                    help="self-speculative decoding (paged mode): draft = "
                         "same params under an aggressive low-bit policy")
    ap.add_argument("--spec-k", type=int, default=3,
                    help="draft tokens proposed per verify step")
    ap.add_argument("--eos", type=int, default=None,
                    help="EOS token id (evicts the slot on emission)")
    ap.add_argument("--kv-cache", choices=["int8", "fp32"], default="int8",
                    help="KV-cache storage: int8 = ~4x more resident slots "
                         "at equal HBM (core/kv_cache.py)")
    ap.add_argument("--weight-bits", type=int, default=None,
                    choices=[8, 4, 2],
                    help="bit-pack every dense kernel once at load "
                         "(kernels/pack.py): resident GEMM weights drop to "
                         "bits/32 of fp32 and decode unpacks tiles "
                         "in-kernel; omit to keep fp32 weights with "
                         "per-step forward quantization")
    ap.add_argument("--backend", default="simulate",
                    choices=["simulate", "native", "pallas"],
                    help="execution backend for the quantized ops, "
                         "including the int8-KV dequant")
    ap.add_argument("--ckpt-dir", default=None,
                    help="restore params from an engine TrainState "
                         "checkpoint instead of random init")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    policy = QuantPolicy.qat(backend=args.backend)  # fwd-only quantization
    print(f"[serve] {cfg.name} {'smoke' if args.smoke else 'full'} widths; "
          f"{device_summary(policy)}")
    kv_quant = args.kv_cache == "int8"
    if args.paged and not kv_quant:
        ap.error("--paged requires --kv-cache int8 (pages store the codec)")
    for flag, name in ((args.spec_decode, "--spec-decode"),
                       (args.prefill_chunk, "--prefill-chunk"),
                       (args.pages, "--pages")):
        if flag and not args.paged:
            ap.error(f"{name} needs --paged")
    kw = dict(policy=policy, slots=args.slots, max_seq=args.max_seq,
              kv_quant=kv_quant, eos_id=args.eos, seed=args.seed,
              weight_bits=args.weight_bits)
    if args.paged:
        kw.update(paged=True, page_size=args.page_size, pages=args.pages,
                  prefill_chunk=args.prefill_chunk,
                  spec_decode=args.spec_decode, spec_k=args.spec_k)
    if args.ckpt_dir:
        eng = ServeEngine.from_checkpoint(cfg, args.ckpt_dir, **kw)
    else:
        params = build_model(cfg).init(jax.random.PRNGKey(args.seed))
        eng = ServeEngine(cfg, params, **kw)

    if args.weight_bits is not None:
        from ..serve.engine import weight_nbytes
        print(f"[serve] packed w{args.weight_bits} weights: "
              f"{weight_nbytes(eng.params)} resident bytes")

    # warmup: compile the decode step AND every prefill/insert length
    # bucket the workload can hit, off the clock
    hi = min(args.max_prompt, args.max_seq - 1)
    lo = min(args.min_prompt, hi)
    b = 1
    while b < hi:
        b *= 2
        if b >= lo:
            eng.submit([1] * min(b, hi), max_new=2)
    eng.submit([1], max_new=2)
    eng.run()
    eng.step_times.clear()

    rng = np.random.RandomState(args.seed)
    for _ in range(args.requests):
        plen = int(rng.randint(lo, hi + 1))
        prompt = rng.randint(0, cfg.vocab_size, size=plen)
        eng.submit(prompt, max_new=args.max_new,
                   temperature=args.temperature, top_k=args.top_k,
                   top_p=args.top_p)

    t0 = time.time()
    completions = eng.run()
    dt = time.time() - t0
    n_tok = sum(len(c.tokens) for c in completions.values())
    p50, p95 = _latency_stats(eng.step_times)
    print(f"[serve] {len(completions)} requests, {n_tok} tokens in "
          f"{dt:.2f}s ({n_tok / max(dt, 1e-9):.1f} tok/s, "
          f"kv={args.kv_cache}, slots={args.slots})")
    print(f"[serve] per-token latency p50 {p50 * 1e3:.2f}ms "
          f"p95 {p95 * 1e3:.2f}ms")
    by_reason = {}
    for c in completions.values():
        by_reason[c.reason] = by_reason.get(c.reason, 0) + 1
    print(f"[serve] finish reasons: {by_reason}")
    if args.paged:
        st = eng.pool_stats()
        print(f"[serve] paged: {st['pages_in_use']}/{st['n_pages']} pages "
              f"resident (peak {st['peak_pages_in_use']}), "
              f"prefix hits {st['prefix_hits']}, cow {st['cow_copies']}, "
              f"preemptions {st['preemptions']}")
        if args.spec_decode:
            sp = eng.spec_stats
            print(f"[serve] spec: {sp.spec_steps} rounds, acceptance "
                  f"{sp.acceptance_rate:.2f}, {sp.emitted} tokens emitted")
    if completions:
        rid0 = min(completions)
        print("[serve] sample:", completions[rid0].tokens[:16])


if __name__ == "__main__":
    main()
