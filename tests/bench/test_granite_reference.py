"""Granite's plain reference (``bench/reference/granite_lm.py``) against the
program on the CPU at the smoke size, with the configuration file's scalars
and tied head: the training loss and gradients, paged prefill-then-decode and
dense decode.  Each of the four scalars and the tying is needed: the program
without any one of them leaves these tolerances.  Then whole runs of the
cell's runner (``bench/train_granite.py``)."""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import faults, run, train_granite
from bench import model as bm
from bench.gen import train_batches
from bench.reference import granite_lm
from conftest import tiny_conf

KNOBS = {"embedding_multiplier": 1.0, "residual_multiplier": 1.0,
         "attention_multiplier": None, "logits_scaling": 1.0,
         "tie_embeddings": False}
TRAFFIC = {"kind": "granite_train_batches", "batch": 4, "seq": 32,
           "chunk_seconds": 0.2, "tokens": "uniform"}


@pytest.fixture
def granite_tiny():
    return tiny_conf("granite-3-2b", n_kv_heads=2)


def _cfg(conf, off=None):
    """The program's config; ``off`` names a knob put back to its default."""
    cfg = bm.arch_config(conf)
    return dataclasses.replace(cfg, **{off: KNOBS[off]}) if off else cfg


def _params(conf, seed, cfg):
    """The seeded tied weights; an untied program gets the table as its
    head, so its forward is the tied one and only the gradient can tell."""
    params = train_granite.init_params(conf["model"], seed)
    if not cfg.tie_embeddings:
        params = dict(params, lm_head={"w": params["embed"]["table"].T})
    return params


def _program_loss_grads(cfg, params, batch, pol):
    from repro.models import build_model
    mdl = build_model(cfg)
    return jax.value_and_grad(
        lambda p: mdl.loss(p, batch, jax.random.PRNGKey(0), pol)[0])(params)


def _loss_grads_match(conf, off=None) -> bool:
    from repro.core import QuantPolicy
    m, cfg = conf["model"], _cfg(conf, off)
    params = _params(conf, 3, cfg)
    batch = train_batches.batch_fn(TRAFFIC, m, 3)(0)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_g = granite_lm.loss_and_grads(
            train_granite.init_params(m, 3), batch, m, rows=2)
        loss, g = _program_loss_grads(cfg, params, batch, QuantPolicy.exact())
    g = {k: v for k, v in g.items() if k != "lm_head"}
    ok = np.allclose(float(loss), float(ref_loss), rtol=1e-5, atol=0)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(ref_g), strict=True):
        ok = ok and np.allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                atol=2e-6)
    return ok


def test_reference_matches_program_loss_and_grads(granite_tiny):
    from repro.core import QuantPolicy
    assert _loss_grads_match(granite_tiny)
    # the 8-bit FQT step on the simulate backend: within quantization noise
    conf, m = granite_tiny, granite_tiny["model"]
    params = train_granite.init_params(m, 3)
    batch = train_batches.batch_fn(TRAFFIC, m, 3)(0)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_g = granite_lm.loss_and_grads(params, batch, m, rows=2)
    q_loss, q_g = _program_loss_grads(
        _cfg(conf), params, batch,
        QuantPolicy.fqt("bhq", 8, bhq_block=32, backend="simulate"))
    assert abs(float(q_loss) - float(ref_loss)) / float(ref_loss) < 2e-3
    ng = [float(jnp.linalg.norm(x)) for x in jax.tree.leaves(q_g)]
    nr = [float(jnp.linalg.norm(x)) for x in jax.tree.leaves(ref_g)]
    med = float(np.median(nr))
    assert max(abs(a - b) / max(b, med) for a, b in zip(ng, nr, strict=True)) < 0.1


def _decode_logits(conf, off=None, paged=True):
    """(paged prefill-then-decode or None, dense prefill-then-decode,
    reference from the prompt's last position, reference) logits of one
    30-token sequence over the real vocabulary.  The table is
    drawn at std 1, so the logits are of order 1 and the int8 pages'
    tolerance means what it means for the other configuration."""
    from repro.core import QuantPolicy
    from repro.models import build_model
    m, cfg = conf["model"], _cfg(conf, off)
    mdl = build_model(cfg)
    params = _params(conf, 5, cfg)
    params["embed"] = {"table": params["embed"]["table"] * 50.0}
    if "lm_head" in params:
        params["lm_head"] = {"w": params["embed"]["table"].T}
    seq = np.random.default_rng(0).integers(0, m["vocab_size"], 30)
    seq = jnp.asarray(seq, jnp.int32)[None]
    n_prompt, pol = 20, QuantPolicy.exact()

    P, nb = 8, 6
    if paged:
        pool = mdl.init_paged_pool(cfg, 1 + nb, P)
        table = jnp.arange(1, nb + 1, dtype=jnp.int32)[None]
        lg, pool = mdl.paged_decode(params, pool,
                                    {"tokens": seq[:, :n_prompt]}, pol, table,
                                    jnp.zeros((1,), jnp.int32))
        paged = [lg[0]]
    lg, cache = mdl.prefill(params, {"tokens": seq[:, :n_prompt]}, pol,
                            max_seq=seq.shape[1])
    dense = [lg[0]]
    for t in range(n_prompt, seq.shape[1]):
        tok = {"tokens": seq[:, t:t + 1]}
        if paged:
            lg, pool = mdl.paged_decode(params, pool, tok, pol, table,
                                        jnp.full((1,), t, jnp.int32))
            paged.append(lg[0])
        lg, cache = mdl.decode(params, cache, tok, pol)
        dense.append(lg[0])
    V = m["vocab_size"]
    ref_params = dict(params)
    ref_params.pop("lm_head", None)
    ref = np.asarray(granite_lm.sequence_logits(ref_params, seq, m))[0, :, :V]
    if paged:
        paged = np.concatenate([np.asarray(x) for x in paged])[:, :V]
    dense = np.concatenate([np.asarray(x) for x in dense])[:, :V]
    return paged, dense, ref[n_prompt - 1:], ref


def test_reference_matches_paged_and_dense_decode(granite_tiny):
    paged, dense, ref_tail, ref = _decode_logits(granite_tiny)
    assert paged.shape == ref.shape and dense.shape == ref_tail.shape
    # int8 KV pages: per-row 8-bit keys and values, the only rounding here
    np.testing.assert_allclose(paged, ref, atol=5e-2)
    assert np.abs(paged - ref).max() > 0      # the pages really are int8
    assert np.abs(ref).max() > 1.0            # logits of order 1
    np.testing.assert_allclose(dense, ref_tail, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("off", sorted(KNOBS))
def test_program_without_any_one_knob_leaves_the_tolerances(granite_tiny,
                                                            off):
    """Each scalar, and the tying, is applied: without it the loss or the
    gradients leave the tolerances above, and without a scalar the dense
    decode's logits do too (an untied head holding the table computes the
    tied forward, so the tying shows in the table's gradient alone)."""
    assert not _loss_grads_match(granite_tiny, off)
    if off != "tie_embeddings":
        _, dense, ref_tail, _ = _decode_logits(granite_tiny, off, paged=False)
        assert not np.allclose(dense, ref_tail, rtol=1e-4, atol=1e-4)


def _run(conf, **overrides):
    """One untraced run of the cell as ``BENCHMARK.json`` declares it, at
    the smoke size."""
    res = run.run_cell("granite2b.train.bhq5", 2 ** 33 + 5, 0.3, False,
                       require_chip=False,
                       overrides={"conf": conf, "traffic": TRAFFIC,
                                  **overrides})
    out = res.pop("_out")
    return res, out


def test_sound_granite_run_is_correct_and_a_broken_one_is_not(granite_tiny):
    res, out = _run(granite_tiny)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert out["lowerings_in_window"] == 0
    res, _ = _run(granite_tiny, step_wrap=faults.state_unchanged)
    assert not res["correct"], res["checks"]


def test_runner_refuses_a_program_that_disagrees_with_the_file(
        granite_tiny, monkeypatch):
    """Before anything compiles: a scalar held at another value or missing
    from the program's config, or a program whose weight layout is not the
    one the benchmark builds."""
    cfg = bm.arch_config(granite_tiny)
    train_granite.check_program(granite_tiny, cfg)
    for key, value in (("logits_scaling", 1.0), ("tie_embeddings", False)):
        with pytest.raises(ValueError, match=key):
            train_granite.check_program(
                granite_tiny, dataclasses.replace(cfg, **{key: value}))
    lacking = types.SimpleNamespace(**{
        f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
        if f.name != "residual_multiplier"})
    with pytest.raises(ValueError, match="residual_multiplier is missing"):
        train_granite.check_program(granite_tiny, lacking)
    monkeypatch.setattr(train_granite, "init_params", bm.init_params)
    with pytest.raises(ValueError, match="layout"):
        train_granite.check_program(granite_tiny, cfg)
