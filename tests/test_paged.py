"""Paged int8 KV serving suite (ISSUE-10 acceptance surface).

  * codec degeneracy: fresh pages dequantize to exact 0.0 and a zero scale
    can never produce inf/NaN (the masked-garbage soundness condition)
  * page-gather kernel: Pallas block-table gather vs its XLA twin, bitwise
  * paged engine vs dense engine: token-for-token identical completions at
    equal seeds, on all three backends
  * chunked prefill and prefix reuse (shared pages, copy-on-write) leave
    tokens unchanged; refcount/table invariants hold under churn,
    preemption, and LRU eviction
  * self-speculative decode emits exactly the target's greedy tokens
  * top-p sampling semantics + the (seed, rid, token-idx) determinism
    contract

Pallas cases run in interpret mode and are slow-marked per repo
convention.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import QuantPolicy, dequant_kv_rows, kv_fresh_code, \
    quantize_kv_rows
from repro.kernels import kv_gather_pages, kv_gather_pages_xla
from repro.models import build_model
from repro.serve import PagedServeEngine, PagePool, PrefixCache, \
    ServeEngine, greedy_accept, sample_tokens, slot_keys
from repro.serve.paged import GARBAGE_PAGE

CFG = get_config("statquant-tx", smoke=True)
MODEL = build_model(CFG)
PARAMS = MODEL.init(jax.random.PRNGKey(0))

BACKENDS = [("simulate", ()), ("native", ()),
            ("pallas", (pytest.mark.slow,))]
EXACT = QuantPolicy.exact()


def _prompts(sizes, seed=0, shared=0):
    rng = np.random.default_rng(seed)
    sys_p = list(rng.integers(0, CFG.vocab_size, size=shared)) if shared \
        else []
    return [sys_p + list(rng.integers(0, CFG.vocab_size, size=n))
            for n in sizes]


def _run(paged, prompts, policy=EXACT, slots=2, max_seq=32, seed=0,
         max_new=6, temperature=0.0, check=True, **kw):
    eng = ServeEngine(CFG, PARAMS, policy=policy, slots=slots,
                      max_seq=max_seq, kv_quant=True, seed=seed,
                      paged=paged, **kw)
    for p in prompts:
        eng.submit(p, max_new=max_new, temperature=temperature, top_k=8)
    out = eng.run()
    if paged and check:
        eng.check_invariants()
    tokens = {r: out[r].tokens for r in sorted(out)}
    return (tokens, eng) if paged else tokens


# ---------------------------------------------------------------------------
# Codec degeneracy: fresh pages and zero scales
# ---------------------------------------------------------------------------

def test_fresh_code_dequants_to_exact_zero():
    """A fresh page (codes = kv_fresh_code, scale = 1, zero = 0) must
    dequantize to exactly 0.0 — masked lanes still enter the attention
    matmul, and 0 * finite is the only safe product."""
    for bits in (8, 4, 2):
        codes = jnp.full((3, 5), kv_fresh_code(bits), jnp.int8)
        out = dequant_kv_rows(codes, jnp.ones((3,)), jnp.zeros((3,)),
                              bits=bits)
        assert float(jnp.max(jnp.abs(out))) == 0.0


def test_zero_scale_never_inf():
    """scale == 0 (all-constant row, or an uninitialized page row) must
    clamp, not divide to inf: one inf times a zero mask weight is NaN and
    poisons the whole attention row."""
    codes = jnp.zeros((4, 8), jnp.int8)
    out = dequant_kv_rows(codes, jnp.zeros((4,)), jnp.full((4,), 2.0))
    assert bool(jnp.all(jnp.isfinite(out)))
    # constant rows round-trip through quantize -> dequant to their value
    x = jnp.full((2, 8), 3.25)
    q = quantize_kv_rows(x)
    back = dequant_kv_rows(*q)
    assert float(jnp.max(jnp.abs(back - x))) < 1e-2


# ---------------------------------------------------------------------------
# Page-gather kernel: Pallas vs XLA twin
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("P,D,bm", [(8, 32, None), (16, 48, 4), (4, 8, 64)])
def test_kv_gather_pallas_matches_xla(P, D, bm):
    rng = np.random.default_rng(3)
    n_pages, B, nb = 10, 3, 4
    codes = jnp.asarray(rng.integers(-128, 128, (n_pages, P, D)), jnp.int8)
    scale = jnp.asarray(rng.uniform(0.5, 2.0, (n_pages, P)), jnp.float32)
    zero = jnp.asarray(rng.normal(size=(n_pages, P)), jnp.float32)
    # include page 0 repeats and a zero scale row: both must stay finite
    scale = scale.at[0].set(0.0)
    table = jnp.asarray(rng.integers(0, n_pages, (B, nb)), jnp.int32)
    table = table.at[0, 0].set(0)
    kw = {} if bm is None else {"bm": bm}      # None: the wrapper's own bm
    got = kv_gather_pages(codes, scale, zero, table, interpret=True, **kw)
    ref = kv_gather_pages_xla(codes, scale, zero, table)
    assert got.shape == (B, nb * P, D)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert float(jnp.max(jnp.abs(got - ref))) == 0.0


# ---------------------------------------------------------------------------
# Paged <-> dense engine parity
# ---------------------------------------------------------------------------

def test_paged_dispatch():
    eng = ServeEngine(CFG, PARAMS, policy=EXACT, slots=2, max_seq=16,
                      kv_quant=True, paged=True)
    assert isinstance(eng, PagedServeEngine)
    assert not isinstance(ServeEngine(CFG, PARAMS, policy=EXACT, slots=2,
                                      max_seq=16, kv_quant=True),
                          PagedServeEngine)


def test_paged_requires_kv_codec():
    with pytest.raises(ValueError, match="kv_quant"):
        ServeEngine(CFG, PARAMS, policy=EXACT, slots=2, max_seq=16,
                    kv_quant=False, paged=True)


@pytest.mark.parametrize("backend", [pytest.param(b, marks=m)
                                     for b, m in BACKENDS])
def test_paged_matches_dense_tokens(backend):
    """The acceptance bar: paged=True is token-for-token identical to the
    dense-slot engine at equal seeds (greedy and temperature lanes)."""
    pol = QuantPolicy(enabled=False, backend=backend)
    prompts = _prompts((3, 7, 5, 9), seed=1)
    dense = _run(False, prompts, policy=pol, temperature=0.7)
    paged, _ = _run(True, prompts, policy=pol, temperature=0.7)
    assert paged == dense


def test_paged_matches_dense_greedy_many_requests():
    prompts = _prompts((4, 11, 2, 8, 6, 13), seed=2)
    dense = _run(False, prompts, slots=3, max_new=8)
    paged, eng = _run(True, prompts, slots=3, max_new=8, page_size=8)
    assert paged == dense
    # after the drain only registry-held prompt pages stay resident
    eng._prefix.clear(eng.pool_host)
    assert eng.pool_host.in_use == 0


# ---------------------------------------------------------------------------
# Chunked prefill / prefix reuse / preemption
# ---------------------------------------------------------------------------

def test_chunked_prefill_same_tokens():
    prompts = _prompts((13, 9, 17), seed=3)
    dense = _run(False, prompts)
    for chunk in (4, 8):
        paged, eng = _run(True, prompts, prefill_chunk=chunk, page_size=4)
        assert paged == dense, f"chunk={chunk}"
        assert eng.pool_stats()["n_pages"] == eng.n_pages


def test_prefix_reuse_and_cow_same_tokens():
    """Prompts sharing a 13-token system prefix (page_size 4 => full-page
    sharing at the 12 boundary) plus prompts extending an earlier prompt
    past a partial page (forcing copy-on-write of the divergence page)
    must still emit dense-identical tokens."""
    rng = np.random.default_rng(4)
    base = list(rng.integers(0, CFG.vocab_size, size=13))
    prompts = [base + list(rng.integers(0, CFG.vocab_size, size=n))
               for n in (4, 6)]
    # extensions of prompts[0] (len 17, 17 % 4 != 0): adopting its
    # full-prompt registry entry crosses a partial boundary -> COW
    prompts += [prompts[0] + list(rng.integers(0, CFG.vocab_size, size=n))
                for n in (3, 5)]
    dense = _run(False, prompts)
    paged, eng = _run(True, prompts, page_size=4)
    assert paged == dense
    stats = eng.pool_stats()
    assert stats["prefix_hits"] >= 2
    # 13 % 4 != 0: at least one adoption crosses a partial boundary
    assert stats["cow_copies"] >= 1


def test_preemption_under_tiny_pool_same_tokens():
    """A pool that can only hold ~one request forces preemption churn; the
    resume path must still produce dense-identical completions."""
    prompts = _prompts((7, 12, 5), seed=5, shared=0)
    dense = _run(False, prompts, max_new=10)
    paged, eng = _run(True, prompts, max_new=10, page_size=4,
                      pages=1 + 8, prefill_chunk=4)
    assert paged == dense
    assert eng.pool_stats()["preemptions"] >= 1


def test_pool_too_small_raises():
    eng = ServeEngine(CFG, PARAMS, policy=EXACT, slots=1, max_seq=16,
                      kv_quant=True, paged=True, page_size=4, pages=2)
    eng.submit(list(range(1, 12)), max_new=4)
    with pytest.raises(RuntimeError, match="page pool"):
        eng.run()


# ---------------------------------------------------------------------------
# PagePool / PrefixCache invariants
# ---------------------------------------------------------------------------

def test_page_pool_refcounts():
    pool = PagePool(6, 4)
    a, b = pool.alloc(), pool.alloc()
    assert a != GARBAGE_PAGE and b != GARBAGE_PAGE and a != b
    assert pool.in_use == 2
    pool.incref(a)
    pool.decref(a)
    assert pool.in_use == 2                   # still held once
    pool.decref(a)
    assert pool.in_use == 1                   # freed
    # garbage page ref ops are no-ops
    pool.incref(GARBAGE_PAGE)
    pool.decref(GARBAGE_PAGE)
    # exhaustion returns None, freed pages come back
    got = [pool.alloc() for _ in range(10)]
    assert got.count(None) == 6              # 4 real pages, then dry
    pool.check([[b]], [tuple(p for p in got if p is not None)])


def test_prefix_cache_lru_and_refcounts():
    pool = PagePool(10, 4)
    cache = PrefixCache(max_entries=2)
    pages = [pool.alloc() for _ in range(3)]
    cache.register((1, 2, 3, 4), (pages[0],), pool)
    cache.register((1, 2, 3, 4, 5, 6, 7, 8), (pages[0], pages[1]), pool)
    assert pool.refs[pages[0]] == 3          # owner + two entries
    # longest strict-prefix lookup (m <= len(ctx) - 1)
    m, got = cache.lookup((1, 2, 3, 4, 5, 6, 7, 8, 9))
    assert m == 8 and got == (pages[0], pages[1])
    m, _ = cache.lookup((1, 2, 3, 4, 5))
    assert m == 4
    assert cache.lookup((9, 9, 9, 9, 9))[0] == 0
    # capacity eviction decrefs
    cache.register((7, 7, 7, 7), (pages[2],), pool)   # evicts LRU
    assert len(cache.entries) == 2
    cache.clear(pool)
    assert pool.refs[pages[0]] == 1 and pool.refs[pages[1]] == 1
    for p in pages:
        pool.decref(p)
    assert pool.in_use == 0


def test_churn_invariants():
    """Heavy mixed workload (sharing + tiny pool + chunking): after every
    drain the refcount cross-check must pass and the pool must be empty
    except for registry-held pages."""
    eng = ServeEngine(CFG, PARAMS, policy=EXACT, slots=3, max_seq=32,
                      kv_quant=True, seed=0, paged=True, page_size=4,
                      pages=1 + 14, prefill_chunk=8, prefix_entries=4)
    rng = np.random.default_rng(6)
    shared = list(rng.integers(0, CFG.vocab_size, size=9))
    for round_ in range(3):
        for n in (3, 6, 2, 9):
            eng.submit(shared + list(rng.integers(0, CFG.vocab_size,
                                                  size=n)), max_new=5)
        out = eng.run()
        assert len(out) == 4
        eng.check_invariants()
    registry_pages = {p for pages in eng._prefix.registered_pages()
                      for p in pages}
    assert eng.pool_host.in_use == len(registry_pages)


# ---------------------------------------------------------------------------
# Self-speculative decode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 3])
def test_spec_decode_matches_target_greedy(k):
    prompts = _prompts((5, 9, 3), seed=7)
    dense = _run(False, prompts, max_new=9)
    paged, eng = _run(True, prompts, max_new=9, spec_decode=True, spec_k=k)
    assert paged == dense
    st = eng.spec_stats
    assert st.spec_steps > 0
    assert 0.0 <= st.acceptance_rate <= 1.0
    assert st.emitted >= st.spec_steps        # every round emits >= 1


def test_spec_decode_temperature_lanes_match_plain():
    """Temperature slots inside a spec batch take exactly one token from
    the verify logits under the standard (rid, count) key — identical to
    the plain paged engine's sampling."""
    prompts = _prompts((5, 6), seed=8)
    plain, _ = _run(True, prompts, max_new=6, temperature=0.9)
    spec, _ = _run(True, prompts, max_new=6, temperature=0.9,
                   spec_decode=True, spec_k=2)
    assert spec == plain


def test_greedy_accept_semantics():
    assert greedy_accept(np.array([5, 6]), np.array([5, 6, 7])) == [5, 6, 7]
    assert greedy_accept(np.array([5, 6]), np.array([5, 9, 7])) == [5, 9]
    assert greedy_accept(np.array([4, 6]), np.array([5, 6, 7])) == [5]


def test_spec_qat_runs_clean():
    """Under a quantized target policy the draft disagrees more (that is
    the point); the engine must still drain with sane acceptance."""
    prompts = _prompts((6, 4), seed=9)
    tokens, eng = _run(True, prompts, policy=QuantPolicy.qat(), max_new=6,
                       spec_decode=True, spec_k=2)
    assert all(len(t) == 6 for t in tokens.values())
    assert eng.spec_stats.proposed > 0


# ---------------------------------------------------------------------------
# Top-p sampling
# ---------------------------------------------------------------------------

def _sample_batch(logits, top_p, seed=0, temp=1.0, top_k=0, n=256):
    B, V = logits.shape
    outs = []
    for i in range(n):
        keys = slot_keys(jax.random.PRNGKey(seed), jnp.full((B,), i,
                                                            jnp.int32),
                         jnp.zeros((B,), jnp.int32))
        outs.append(np.asarray(sample_tokens(
            logits, keys, jnp.full((B,), temp), jnp.full((B,), top_k,
                                                         jnp.int32),
            V, jnp.full((B,), top_p))))
    return np.stack(outs)


def test_top_p_restricts_support():
    # token 0 holds ~73% mass, token 1 ~27%; top_p = 0.5 keeps only token 0
    logits = jnp.asarray([[2.0, 1.0, -3.0, -3.0]])
    assert set(_sample_batch(logits, 0.5).ravel()) == {0}
    # top_p = 0.9 needs two tokens to cover the mass
    support = set(_sample_batch(logits, 0.9).ravel())
    assert support == {0, 1}
    # out-of-range values disable the filter entirely: identical draws to
    # the no-top_p path (same keys, same uniforms)
    B, V = logits.shape
    keys = slot_keys(jax.random.PRNGKey(0), jnp.arange(B, dtype=jnp.int32),
                     jnp.zeros((B,), jnp.int32))
    none = sample_tokens(logits, keys, jnp.ones((B,)),
                         jnp.zeros((B,), jnp.int32), V)
    for off in (0.0, 1.0, 1.5, -0.2):
        got = sample_tokens(logits, keys, jnp.ones((B,)),
                            jnp.zeros((B,), jnp.int32), V,
                            jnp.full((B,), off))
        assert np.array_equal(np.asarray(got), np.asarray(none)), off


def test_top_p_deterministic_and_composes_with_top_k():
    logits = jnp.asarray(np.random.default_rng(10).normal(size=(2, 16)),
                         jnp.float32)
    a = _sample_batch(logits, 0.8, n=32)
    b = _sample_batch(logits, 0.8, n=32)
    assert np.array_equal(a, b)
    # top-k=1 forces greedy regardless of top-p
    g = _sample_batch(logits, 0.8, top_k=1, n=8)
    assert np.array_equal(g, np.broadcast_to(
        np.asarray(jnp.argmax(logits, -1)), g.shape))


def test_top_p_through_engine_deterministic():
    prompts = _prompts((5, 7), seed=11)
    kw = dict(max_new=6, temperature=0.8, check=False)
    eng1 = ServeEngine(CFG, PARAMS, policy=EXACT, slots=2, max_seq=32,
                       kv_quant=True, seed=3, paged=True)
    eng2 = ServeEngine(CFG, PARAMS, policy=EXACT, slots=2, max_seq=32,
                       kv_quant=True, seed=3, paged=True)
    for eng in (eng1, eng2):
        for p in prompts:
            eng.submit(p, max_new=6, temperature=0.8, top_p=0.7)
    o1, o2 = eng1.run(), eng2.run()
    assert {r: o1[r].tokens for r in o1} == {r: o2[r].tokens for r in o2}
