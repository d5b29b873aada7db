"""Serving-engine benchmark: throughput, per-token latency, and the
resident-slot arithmetic of the int8 KV cache.

Three measurements, dumped to ``BENCH_serve.json``:

  * ``variants`` — for fp32-cache vs int8-cache at several slot counts:
    sustained tok/s and p50/p95 per-token (step) latency through the full
    continuous-batching engine on a mixed-length workload (compile steps
    excluded via a warmup drain).
  * ``memory`` — per-slot KV-cache bytes for each variant and the resident
    slot counts a fixed HBM budget buys: the int8 cache stores 1 byte/entry
    plus one (scale, zero) pair per row vs 4 bytes/entry fp32, so at equal
    memory it holds ~4x the slots (>= 2x is the acceptance bar).
  * ``parity`` — stepwise decode vs prefill logits on all three execution
    backends: exact (fp) decode must match prefill to float tolerance, and
    the int8-KV drift must stay within a small multiple of the fp-path
    quantized-forward drift.
  * ``weight_memory`` — resident bytes of the dense GEMM kernels fp32 vs
    bit-packed (kernels/pack.py) at 8/4/2 bits; >= 4x reduction at 4-bit
    is the acceptance bar (4-bit packs 2 codes/byte -> ~8x vs fp32, plus
    one affine pair per tensor/layer).
  * ``paged`` — the paged engine (serve/paged.py): throughput/latency rows
    next to the dense-slot ones; an *equal-HBM residency* run (a paged
    pool of exactly the dense engine's KV bytes serving twice the lanes —
    peak concurrently-resident requests is the acceptance number, >= 2x);
    and a **Poisson open-loop overload** run — arrivals at ~2x the
    measured service rate on a virtual clock assembled from measured step
    wall times, reporting per-request p50/p95/p99 latency and page-pool
    utilization, with speculative decode off and on.  The Poisson
    percentiles characterize a latency *distribution* under a fixed
    arrival seed, not a head-to-head comparison, so they are single-pass
    (min-of-iters does not apply); the service-rate estimate feeding
    lambda is itself a full closed-loop drain.

Throughput/latency are min-of-iters: each variant's timed workload runs
``ITERS`` times and the best iteration is reported, so one scheduler hiccup
(GC, page cache, a noisy neighbour on the 1-core CI host) cannot invert a
comparison — a single-run version of this bench once showed int8-KV slower
than fp32 at slots=8 for exactly that reason.

Wall-clock numbers are XLA-path only (interpret-mode Pallas timing on CPU is
meaningless); the pallas parity row
runs the fused dequant kernel in interpret mode for *numerics*, not speed.
"""

from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import QuantPolicy, kv_cache_bytes_per_row
from repro.kernels.pack import PackedTensor
from repro.models import build_model
from repro.serve import ServeEngine
from repro.serve.engine import pack_dense_weights

BENCH_JSON = os.environ.get("BENCH_SERVE_JSON", "BENCH_serve.json")
SLOT_COUNTS = (2, 4, 8)
MAX_SEQ = 48
MAX_NEW = 16
REQUESTS_PER_SLOT = 3
ITERS = 3                      # timed repeats per variant; best one reported
HBM_BUDGET = 64 << 30          # 64 GiB: the resident-slot arithmetic budget
PAGE_SIZE = 8
N_POISSON = 32                 # completed requests per open-loop pass
POISSON_MAX_NEW = 8
OVERLOAD = 2.0                 # arrival rate as a multiple of service rate


def _submit_workload(eng, cfg, n_requests: int, seed: int = 0):
    rng = np.random.RandomState(seed)
    for _ in range(n_requests):
        plen = int(rng.randint(4, 17))
        eng.submit(rng.randint(0, cfg.vocab_size, size=plen),
                   max_new=MAX_NEW)


def _run_variant(cfg, params, kv_quant: bool, slots: int,
                 weight_bits=None, paged: bool = False, **paged_kw) -> dict:
    kw = dict(page_size=PAGE_SIZE, **paged_kw) if paged else {}
    eng = ServeEngine(cfg, params, policy=QuantPolicy.qat(), slots=slots,
                      max_seq=MAX_SEQ, kv_quant=kv_quant, seed=0,
                      weight_bits=weight_bits, paged=paged, **kw)
    # warmup drain: compiles the decode step + the prefill/insert buckets
    _submit_workload(eng, cfg, slots, seed=1)
    eng.run()
    # min-of-iters: the same deterministic workload ITERS times; keep the
    # iteration with the smallest summed step time (see module docstring)
    best = None
    for _ in range(ITERS):
        eng.step_times.clear()
        _submit_workload(eng, cfg, REQUESTS_PER_SLOT * slots, seed=0)
        out = eng.run()
        dts = np.asarray([dt for dt, n in eng.step_times if n > 0])
        emitted = sum(n for _, n in eng.step_times)
        total = float(np.sum(dts)) if dts.size else 0.0
        if best is None or (total and total < best[0]):
            best = (total, dts, emitted, out)
    total, dts, emitted, out = best
    n_tok = sum(len(c.tokens) for c in out.values())
    row = {
        "slots": slots,
        "kv": ("paged_int8" if paged else "int8") if kv_quant else "fp32",
        "requests": len(out),
        "tokens": n_tok,
        "iters": ITERS,
        "tok_per_sec": emitted / total if total else 0.0,
        "p50_ms": float(np.percentile(dts, 50)) * 1e3 if dts.size else 0.0,
        "p95_ms": float(np.percentile(dts, 95)) * 1e3 if dts.size else 0.0,
    }
    if weight_bits is not None:
        row["weight_bits"] = weight_bits
    if paged:
        row["pool"] = eng.pool_stats()
        if eng.spec_decode:
            row["spec"] = eng.spec_stats.as_dict()
    return row


def _paged_residency_record(cfg, params, dense_slots: int = 4) -> dict:
    """Equal-HBM residency: give the paged engine EXACTLY the dense
    engine's KV byte budget (``dense_slots * max_seq`` rows, garbage page
    included) but twice the decode lanes, and drive a short-request
    workload through it.  The dense engine can never hold more than
    ``dense_slots`` requests in that budget — every lane pins ``max_seq``
    rows whether used or not; the paged engine holds whatever actually
    fits, and the measured peak concurrent residency is the acceptance
    number (>= 2x)."""
    nb = MAX_SEQ // PAGE_SIZE
    pool_pages = dense_slots * nb          # total rows == dense engine's
    eng = ServeEngine(cfg, params, policy=QuantPolicy.qat(),
                      slots=2 * dense_slots, max_seq=MAX_SEQ, kv_quant=True,
                      seed=0, paged=True, page_size=PAGE_SIZE,
                      pages=pool_pages)
    rng = np.random.RandomState(7)
    for _ in range(4 * dense_slots):
        plen = int(rng.randint(4, 13))
        eng.submit(rng.randint(0, cfg.vocab_size, size=plen), max_new=8)
    peak_resident = 0
    while eng.queued or eng.active_slots:
        eng.step()
        peak_resident = max(peak_resident, eng.active_slots)
    eng.run()
    stats = eng.pool_stats()
    return {
        "dense_resident_at_equal_hbm": dense_slots,
        "paged_peak_resident": peak_resident,
        "resident_ratio": peak_resident / dense_slots,
        "pool_rows": pool_pages * PAGE_SIZE,
        "dense_rows": dense_slots * MAX_SEQ,
        "peak_page_utilization": stats["peak_utilization"],
        "preemptions": stats["preemptions"],
    }


def _poisson_record(cfg, params, spec: bool) -> dict:
    """Open-loop Poisson arrivals at ``OVERLOAD``x the measured service
    rate, on a virtual clock: each engine step advances the clock by its
    measured wall time, and a request's latency is completion time minus
    its (virtual) arrival time.  Sustained overload means the backlog
    grows and the tail percentiles reflect queueing, not just service."""
    eng = ServeEngine(cfg, params, policy=QuantPolicy.qat(), slots=4,
                      max_seq=MAX_SEQ, kv_quant=True, seed=0, paged=True,
                      page_size=PAGE_SIZE, spec_decode=spec, spec_k=3)
    rng = np.random.RandomState(11)

    def prompt():
        return rng.randint(0, cfg.vocab_size, size=int(rng.randint(4, 13)))

    # warmup + service-rate estimate: closed-loop drain of a full pool
    for _ in range(8):
        eng.submit(prompt(), max_new=POISSON_MAX_NEW)
    eng.run()
    t0 = time.perf_counter()
    for _ in range(12):
        eng.submit(prompt(), max_new=POISSON_MAX_NEW)
    eng.run()
    service_rate = 12 / (time.perf_counter() - t0)     # requests / sec

    lam = OVERLOAD * service_rate
    arrivals = np.cumsum(rng.exponential(1.0 / lam, size=N_POISSON))
    eng.spec_stats = type(eng.spec_stats)()            # reset accounting
    eng.page_usage.clear()
    now, submitted, seen = 0.0, 0, set()
    arrival_of, latencies = {}, []
    while len(latencies) < N_POISSON:
        while submitted < N_POISSON and arrivals[submitted] <= now:
            rid = eng.submit(prompt(), max_new=POISSON_MAX_NEW)
            arrival_of[rid] = arrivals[submitted]
            submitted += 1
        if not eng.active_slots and not eng.queued:
            if submitted >= N_POISSON:
                break                                  # nothing left to do
            now = arrivals[submitted]                  # idle: jump ahead
            continue
        t0 = time.perf_counter()
        eng.step()
        now += time.perf_counter() - t0
        for rid in eng.completions():
            if rid not in seen:
                seen.add(rid)
                latencies.append(now - arrival_of[rid])
    eng.run()                                          # drain + clear
    lat = np.asarray(latencies) * 1e3                  # ms
    stats = eng.pool_stats()
    rec = {
        "spec_decode": spec,
        "requests": N_POISSON,
        "service_rate_req_per_s": service_rate,
        "arrival_rate_req_per_s": lam,
        "overload": OVERLOAD,
        "latency_p50_ms": float(np.percentile(lat, 50)),
        "latency_p95_ms": float(np.percentile(lat, 95)),
        "latency_p99_ms": float(np.percentile(lat, 99)),
        "mean_page_utilization": stats["mean_utilization"],
        "peak_page_utilization": stats["peak_utilization"],
        "preemptions": stats["preemptions"],
        "prefix_hits": stats["prefix_hits"],
    }
    if spec:
        rec["spec"] = eng.spec_stats.as_dict()
    return rec


def _memory_record(cfg) -> dict:
    flat = cfg.n_kv_heads * cfg.hd
    rows_per_slot = 2 * cfg.n_layers * MAX_SEQ          # k and v, every layer
    per_slot = {
        "fp32": rows_per_slot * kv_cache_bytes_per_row(flat, False),
        "int8": rows_per_slot * kv_cache_bytes_per_row(flat, True),
    }
    resident = {k: HBM_BUDGET // v for k, v in per_slot.items()}
    return {
        "kv_rows_per_slot": rows_per_slot,
        "bytes_per_slot": per_slot,
        "hbm_budget_bytes": HBM_BUDGET,
        "resident_slots_at_budget": resident,
        "slot_ratio_int8_over_fp32": resident["int8"] / resident["fp32"],
    }


def _weight_memory_record(cfg, params) -> dict:
    """Resident bytes of the dense GEMM kernels: fp32 vs bit-packed."""
    dense_fp = 0

    def walk(node):
        nonlocal dense_fp
        if isinstance(node, dict):
            for k, v in node.items():
                if k == "w" and getattr(v, "ndim", 0) >= 2:
                    dense_fp += int(v.nbytes)
                else:
                    walk(v)

    walk(params)
    packed = {}
    for bits in (8, 4, 2):
        pb = sum(int(leaf.nbytes) for leaf in jax.tree.leaves(
            pack_dense_weights(params, bits),
            is_leaf=lambda x: isinstance(x, PackedTensor))
            if isinstance(leaf, PackedTensor))
        packed[str(bits)] = {"bytes": pb,
                             "reduction_vs_fp32": dense_fp / pb}
    return {"dense_fp32_bytes": dense_fp, "packed": packed}


def _parity_record(cfg, params) -> dict:
    """Stepwise decode vs prefill logits, per backend, fp and int8-KV."""
    model = build_model(cfg)
    B, T = 2, 8
    toks = jax.random.randint(jax.random.PRNGKey(3), (B, T), 0,
                              cfg.vocab_size)
    out = {}
    for backend in ("simulate", "native", "pallas"):
        pol = QuantPolicy.qat(backend=backend)
        exact = QuantPolicy(enabled=False, backend=backend)
        row = {}
        for name, policy in (("exact", exact), ("qat", pol)):
            lg_pre, _ = model.prefill(params, {"tokens": toks}, policy,
                                      max_seq=T + 2)
            scale = float(jnp.max(jnp.abs(lg_pre))) + 1e-9
            for kv, init in (("fp32", model.init_cache),
                             ("int8", model.init_cache_quant)):
                if kv == "int8":
                    cache = init(cfg, B, T + 2)
                else:
                    cache = init(cfg, B, T + 2)
                    cache["index"] = jnp.zeros((B,), jnp.int32)
                pos = jnp.zeros((B,), jnp.int32)
                lg = None
                for t in range(T):
                    lg, cache = model.decode(
                        params, cache, {"tokens": toks[:, t:t + 1]}, policy,
                        positions=pos)
                    pos = pos + 1
                # exact policy never touches the int8 cache quantizers'
                # forward GEMMs, but the cache codec still rounds — only
                # the fp cache must match to float tolerance
                row[f"{name}_{kv}_max_abs"] = float(
                    jnp.max(jnp.abs(lg - lg_pre)))
                row[f"{name}_{kv}_rel"] = float(
                    jnp.max(jnp.abs(lg - lg_pre))) / scale
        row["pass"] = (row["exact_fp32_max_abs"] < 1e-4
                       and row["qat_fp32_rel"] < 0.05
                       and row["qat_int8_rel"] < 0.10)
        out[backend] = row
    return out


def run():
    cfg = get_config("statquant-tx", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    record = {"arch": cfg.name, "max_seq": MAX_SEQ, "max_new": MAX_NEW,
              "variants": [], "memory": _memory_record(cfg),
              "weight_memory": _weight_memory_record(cfg, params),
              "parity": _parity_record(cfg, params)}
    rows = []
    for slots in SLOT_COUNTS:
        for kv_quant in (False, True):
            v = _run_variant(cfg, params, kv_quant, slots)
            record["variants"].append(v)
            rows.append((f"serve/{v['kv']}_slots={slots}",
                         v["p50_ms"] * 1e3, v["tok_per_sec"]))
    # packed-weight variant: int8 KV + 4-bit packed dense kernels
    v = _run_variant(cfg, params, True, 4, weight_bits=4)
    record["variants"].append(v)
    rows.append(("serve/int8_slots=4_w4", v["p50_ms"] * 1e3,
                 v["tok_per_sec"]))

    # paged engine: throughput rows, equal-HBM residency, Poisson overload
    paged_variants = []
    for slots in SLOT_COUNTS:
        v = _run_variant(cfg, params, True, slots, paged=True)
        paged_variants.append(v)
        rows.append((f"serve/paged_int8_slots={slots}", v["p50_ms"] * 1e3,
                     v["tok_per_sec"]))
    v = _run_variant(cfg, params, True, 4, paged=True, spec_decode=True,
                     spec_k=3)
    paged_variants.append(v)
    rows.append(("serve/paged_int8_slots=4_spec", v["p50_ms"] * 1e3,
                 v["tok_per_sec"]))
    residency = _paged_residency_record(cfg, params)
    poisson = {"spec_off": _poisson_record(cfg, params, spec=False),
               "spec_on": _poisson_record(cfg, params, spec=True)}
    record["paged"] = {"page_size": PAGE_SIZE, "variants": paged_variants,
                       "residency": residency, "poisson": poisson}
    rows.append(("serve/paged_poisson_p99_off",
                 poisson["spec_off"]["latency_p99_ms"] * 1e3,
                 residency["resident_ratio"]))
    rows.append(("serve/paged_poisson_p99_on",
                 poisson["spec_on"]["latency_p99_ms"] * 1e3,
                 poisson["spec_on"]["spec"]["acceptance_rate"]))

    ratio = record["memory"]["slot_ratio_int8_over_fp32"]
    w4 = record["weight_memory"]["packed"]["4"]["reduction_vs_fp32"]
    record["acceptance"] = {
        "slot_ratio_ge_2x": ratio >= 2.0,
        "packed_w4_reduction_ge_4x": w4 >= 4.0,
        "parity_all_backends": all(v["pass"]
                                   for v in record["parity"].values()),
        "paged_resident_ge_2x": residency["resident_ratio"] >= 2.0,
    }
    with open(BENCH_JSON, "w") as f:
        json.dump(record, f, indent=1)
    return rows
