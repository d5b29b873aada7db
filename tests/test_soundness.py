"""Statistical-soundness verifier (analysis/soundness.py).

Three things make the pass trustworthy, and each gets pinned here:
green on the real training graphs (model + engine step, every SR round
its own stream), red with the right rule when a Theorem 1 precondition
is broken (registry/plumbing mutations + synthetic repros of the bugs
the pass has caught), and the engine's concrete PRNG fold chain really
producing the distinct keys the static pass certifies.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import (check_model, check_soundness_fn, check_step,
                            soundness_selftest)
from repro.configs import get_config
from repro.core import QuantPolicy
from repro.core.exempt import quant_scope
from repro.core.quantizers import quantize_ptq_stoch

FQT8 = QuantPolicy.fqt("bhq", 8)


# ---------------------------------------------------------------------------
# Green on the real graphs
# ---------------------------------------------------------------------------

def test_model_grad_is_sound():
    cfg = get_config("statquant-tx", smoke=True)
    rep = check_model(cfg, FQT8)
    assert rep.ok, rep.format(verbose=True)
    assert rep.n_sr_rounds > 0
    assert rep.n_det_rounds > 0          # the deterministic forward rounds
    # every SR round consumes its own PRNG stream
    assert rep.n_streams == rep.n_sr_rounds


def test_engine_step_microbatch_keys_are_sound():
    """Full engine step with accum_steps=2: the microbatch ``fold_in``
    keys inside the accumulation scan must vary with the iteration
    (SND003) and stay distinct across microbatches x sites (SND002)."""
    cfg = get_config("statquant-tx", smoke=True)
    rep = check_step(cfg, FQT8, accum_steps=2)
    assert rep.ok, rep.format(verbose=True)
    assert rep.n_sr_rounds > 0
    assert rep.n_streams == rep.n_sr_rounds


def test_tied_granite_head_keys_independent():
    """Granite's tied head draws its SR keys at ``lm_head`` from the step's
    key, apart from every layer's: each of the 8 quantized sites (7 in the
    layer body, the head) has its wgrad and agrad scopes, and every SR
    round its own stream."""
    cfg = get_config("granite-3-2b", smoke=True)
    assert cfg.tie_embeddings
    rep = check_model(cfg, FQT8)
    assert rep.ok, rep.format(verbose=True)
    assert rep.n_grad_scopes == 2 * 8
    assert rep.n_streams == rep.n_sr_rounds
    step = check_step(cfg, FQT8, accum_steps=2)
    assert step.ok, step.format(verbose=True)
    assert step.n_streams == step.n_sr_rounds


def test_whisper_self_cross_attention_keys_independent():
    """Regression: the decoder once passed one layer key to both self- and
    cross-attention, whose per-site qkey tags collide — SND002 caught it.
    The fixed graph must give every SR round a distinct stream."""
    cfg = get_config("whisper-medium", smoke=True)
    rep = check_model(cfg, FQT8)
    assert rep.ok, rep.format(verbose=True)
    assert rep.n_streams == rep.n_sr_rounds


# ---------------------------------------------------------------------------
# Red on mutations (the pass has teeth)
# ---------------------------------------------------------------------------

def test_mutation_selftest_turns_red_with_right_rules():
    cfg = get_config("statquant-tx", smoke=True)
    st = soundness_selftest(cfg, FQT8)
    assert st.ok, st.detail
    assert st.clean.ok
    expected = {"det-agrad": "SND001", "aliased-keys": "SND002",
                "double-quant": "SND004", "sr-forward": "SND005"}
    assert set(st.mutated) == set(expected)
    for mutation, rule in expected.items():
        rep = st.mutated[mutation]
        assert not rep.ok, mutation
        hits = [f for f in rep.findings if f.rule == rule]
        assert hits, (mutation, rule, rep.format(verbose=True))
        # findings must name a real layer path, not "?"
        assert any(f.path not in ("?", "") for f in hits), mutation


def test_shared_key_across_sites_is_snd002():
    """Two SR draws from the very same key alias their noise — the exact
    bug class the whisper self/cross-attention fix addressed."""
    def bad(x, key):
        with quant_scope("toy.a", "agrad", True):
            qa = quantize_ptq_stoch(x, key, 8)
        with quant_scope("toy.b", "agrad", True):
            qb = quantize_ptq_stoch(2.0 * x, key, 8)
        return qa.dequant().sum() + qb.dequant().sum()

    x = jnp.linspace(-1.0, 1.0, 64).reshape(8, 8)
    rep = check_soundness_fn(bad, (x, jax.random.PRNGKey(0)), "shared-key")
    assert not rep.ok
    hits = [f for f in rep.findings if f.rule == "SND002"]
    assert hits and any("toy." in f.path for f in hits), \
        rep.format(verbose=True)

    def good(x, key):
        with quant_scope("toy.a", "agrad", True):
            qa = quantize_ptq_stoch(x, jax.random.fold_in(key, 0), 8)
        with quant_scope("toy.b", "agrad", True):
            qb = quantize_ptq_stoch(2.0 * x, jax.random.fold_in(key, 1), 8)
        return qa.dequant().sum() + qb.dequant().sum()

    assert check_soundness_fn(good, (x, jax.random.PRNGKey(0)), "split").ok


def test_scan_invariant_key_is_snd003():
    """Regression for the chunked-head-loss bug: an SR key that is constant
    across a scan replays the same noise every chunk."""
    xs = jnp.linspace(-1.0, 1.0, 4 * 64).reshape(4, 8, 8)

    def bad(xs, key):
        def body(c, xc):
            with quant_scope("toy.head", "agrad", True):
                q = quantize_ptq_stoch(xc, key, 8)   # same key every chunk
            return c + q.dequant().sum(), ()
        out, _ = jax.lax.scan(body, jnp.float32(0.0), xs)
        return out

    rep = check_soundness_fn(bad, (xs, jax.random.PRNGKey(0)), "scan-reuse")
    assert not rep.ok
    assert any(f.rule == "SND003" for f in rep.findings), \
        rep.format(verbose=True)

    def good(xs, key):
        def body(c, ix):
            i, xc = ix
            with quant_scope("toy.head", "agrad", True):
                q = quantize_ptq_stoch(xc, jax.random.fold_in(key, i), 8)
            return c + q.dequant().sum(), ()
        out, _ = jax.lax.scan(body, jnp.float32(0.0),
                              (jnp.arange(xs.shape[0]), xs))
        return out

    assert check_soundness_fn(good, (xs, jax.random.PRNGKey(0)),
                              "scan-fold").ok


def test_det_round_on_gradient_path_is_snd001():
    from repro.core.quantizers import quantize_ptq_det

    def bad(x):
        with quant_scope("toy.w", "wgrad", True):
            q = quantize_ptq_det(x, 8)
        return q.dequant().sum()

    rep = check_soundness_fn(bad, (jnp.linspace(-1.0, 1.0, 64).reshape(8, 8),),
                             "det-wgrad")
    assert not rep.ok
    assert any(f.rule == "SND001" and f.path == "toy.w"
               for f in rep.findings), rep.format(verbose=True)


# ---------------------------------------------------------------------------
# Concrete key independence (the fold chain the engine actually runs)
# ---------------------------------------------------------------------------

def _key_fingerprint(k):
    try:
        data = jax.random.key_data(k)
    except TypeError:
        data = jnp.asarray(k)
    return tuple(int(v) for v in np.asarray(data).ravel())


def test_fold_in_grid_has_no_collisions():
    """fold_in(fold_in(seed, rid), token_idx) over an 8x64 grid: all 512
    derived keys (and their uniform-bits streams) are distinct."""
    seed = jax.random.PRNGKey(0)
    fingerprints, streams = set(), set()
    for rid in range(8):
        kr = jax.random.fold_in(seed, rid)
        for t in range(64):
            k = jax.random.fold_in(kr, t)
            fingerprints.add(_key_fingerprint(k))
            streams.add(tuple(np.asarray(
                jax.random.bits(k, (2,), jnp.uint32)).tolist()))
    assert len(fingerprints) == 8 * 64
    assert len(streams) == 8 * 64


def test_engine_fold_chain_distinct_across_microbatches_and_sites():
    """The engine's concrete derivation — split(rng) -> fold_in(microbatch)
    -> split(layers) -> qkey tag -> _fqt_bwd split — yields pairwise
    distinct keys and distinct random-bits streams over the whole
    microbatches x layers x sites x legs grid."""
    from repro.layers.common import qkey

    base = jax.random.split(jax.random.PRNGKey(7), 3)[0]
    keys = []
    for micro in range(2):
        mk = jax.random.fold_in(base, micro)
        for lk in jax.random.split(mk, 2):          # two layers
            for tag in (1, 2, 3, 4, 0x10):          # attn + mlp sites
                site = qkey(lk, tag)
                k1, k2 = jax.random.split(jax.random.fold_in(site, 0x5151))
                keys.extend([k1, k2])
    fingerprints = {_key_fingerprint(k) for k in keys}
    assert len(fingerprints) == len(keys)
    streams = {tuple(np.asarray(jax.random.bits(k, (2,), jnp.uint32)).tolist())
               for k in keys}
    assert len(streams) == len(keys)


# ---------------------------------------------------------------------------
# Report plumbing
# ---------------------------------------------------------------------------

def test_report_serializes_to_json():
    cfg = get_config("statquant-tx", smoke=True)
    rep = check_model(cfg, FQT8, grad=False)
    doc = rep.to_dict()
    assert doc["ok"] is True
    json.dumps(doc)   # must be JSON-serializable for --format json


def test_cli_soundness_json(capsys):
    from repro.analysis.__main__ import main
    rc = main(["soundness", "--config", "statquant-tx", "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert doc["tool"] == "soundness" and doc["ok"]
    assert all(r["ok"] for r in doc["reports"])
