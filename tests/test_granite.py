"""Granite-3.0's scalars and tied head in the program (``ArchConfig``'s
``embedding_multiplier``, ``residual_multiplier``, ``attention_multiplier``,
``logits_scaling``, ``tie_embeddings``): the published values, the tied
layout and gradient, and the defaults, with which every other
configuration computes what it computed before the fields existed."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import QuantPolicy
from repro.models import build_model, lm

GRANITE = get_config("granite-3-2b", smoke=True)
FQT8 = QuantPolicy.fqt("bhq", 8, bhq_block=16)


def _batch(cfg, seed=0, B=2, T=8):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (B, T + 1), 0,
                              cfg.vocab_size)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def test_published_values():
    cfg = get_config("granite-3-2b")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
            cfg.d_ff, cfg.vocab_size) == (40, 2048, 32, 8, 64, 8192, 49155)
    assert (cfg.act, cfg.norm, cfg.qkv_bias, cfg.rope_theta) == (
        "swiglu", "rmsnorm", False, 10_000.0)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling) == (
        12.0, 0.22, 0.015625, 8.0)
    assert cfg.tie_embeddings and cfg.padded_vocab == 49408


def test_tied_layout_has_no_head_leaf():
    params = build_model(GRANITE).init(jax.random.PRNGKey(0))
    assert "lm_head" not in params
    untied = dataclasses.replace(GRANITE, tie_embeddings=False)
    ref = build_model(untied).init(jax.random.PRNGKey(0))
    assert ref["lm_head"]["w"].shape == (GRANITE.d_model,
                                         GRANITE.padded_vocab)
    # tying takes the head away and leaves every other leaf as it was
    for a, b in zip(jax.tree.leaves(params),
                    jax.tree.leaves({k: v for k, v in ref.items()
                                     if k != "lm_head"}), strict=True):
        assert np.array_equal(a, b)


@pytest.mark.parametrize("policy", [QuantPolicy.exact(), FQT8],
                         ids=["exact", "fqt8"])
def test_tied_table_gradient_is_gather_plus_head(policy):
    """One gradient for the table: the embedding gather's plus the head
    GEMM's, which resolves at ``lm_head`` with the same SR key whichever
    leaf holds the weight."""
    key = jax.random.PRNGKey(1)
    tied = build_model(GRANITE)
    params = tied.init(key)
    untied = build_model(dataclasses.replace(GRANITE, tie_embeddings=False))
    params_u = dict(params, lm_head={"w": params["embed"]["table"].T})
    batch = _batch(GRANITE)

    def grads(mdl, p):
        return jax.value_and_grad(
            lambda q: mdl.loss(q, batch, key, policy)[0])(p)
    loss, g = grads(tied, params)
    loss_u, g_u = grads(untied, params_u)
    assert float(loss) == pytest.approx(float(loss_u), rel=1e-6)
    np.testing.assert_allclose(
        g["embed"]["table"], g_u["embed"]["table"] + g_u["lm_head"]["w"].T,
        rtol=1e-5, atol=1e-8)
    for a, b in zip(jax.tree.leaves(g["layers"]), jax.tree.leaves(g_u["layers"]),
                    strict=True):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-8)


def test_logits_scaling_divides_the_logits():
    params = build_model(GRANITE).init(jax.random.PRNGKey(2))
    batch = {"tokens": _batch(GRANITE)["tokens"]}
    one = build_model(dataclasses.replace(GRANITE, logits_scaling=1.0))
    lg8, _ = build_model(GRANITE).prefill(params, batch, QuantPolicy.exact())
    lg1, _ = one.prefill(params, batch, QuantPolicy.exact())
    np.testing.assert_allclose(lg8 * 8.0, lg1, rtol=1e-6, atol=1e-7)


def _old_helpers(monkeypatch):
    """The program as it was before the scalars: the residual, the input
    embedding and the head written without them."""
    monkeypatch.setattr(lm, "_residual", lambda h, y, cfg: h + y.astype(
        h.dtype))
    monkeypatch.setattr(lm, "_head", lambda params, h, key, policy, cfg:
                        lm.lm_head(params["lm_head"], h, key, policy))
    monkeypatch.setattr(lm, "_input_embed", lambda params, batch, cfg:
                        batch["embeds"] if "embeds" in batch
                        else lm.embed(params["embed"], batch["tokens"]))


@pytest.mark.parametrize("policy", [QuantPolicy.exact(), FQT8],
                         ids=["exact", "fqt8"])
def test_defaults_compute_what_the_program_computed(monkeypatch, policy):
    """``statquant-tx`` with the new fields at their defaults: loss and
    gradients bit-identical to the same model with the helpers that apply
    the scalars replaced by the expressions they replaced."""
    cfg = get_config("statquant-tx", smoke=True)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling,
            cfg.tie_embeddings) == (1.0, 1.0, None, 1.0, False)
    mdl = build_model(cfg)
    key = jax.random.PRNGKey(3)
    params = mdl.init(key)
    batch = _batch(cfg, seed=4)

    def run():
        return jax.value_and_grad(
            lambda p: mdl.loss(p, batch, key, policy, remat=True)[0])(params)
    loss, g = run()
    with monkeypatch.context() as mp:
        _old_helpers(mp)
        loss0, g0 = run()
    assert np.array_equal(loss, loss0)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g0), strict=True):
        assert np.array_equal(a, b)


def test_decode_paths_apply_the_scalars_like_the_full_forward():
    """Prefill then dense decode and the paged forward give the training
    forward's logits: the scalars sit in the one block body all three
    share."""
    params = build_model(GRANITE).init(jax.random.PRNGKey(5))
    mdl = build_model(GRANITE)
    toks = _batch(GRANITE, seed=6, B=1, T=12)["tokens"]
    pol = QuantPolicy.exact()
    full, _ = mdl.prefill(params, {"tokens": toks}, pol)
    lg, cache = mdl.prefill(params, {"tokens": toks[:, :-1]}, pol,
                            max_seq=toks.shape[1])
    lg, _ = mdl.decode(params, cache, {"tokens": toks[:, -1:]}, pol)
    np.testing.assert_allclose(lg, full, rtol=1e-5, atol=1e-6)
    pool = mdl.init_paged_pool(GRANITE, 3, 8)
    table = jnp.array([[1, 2]], jnp.int32)
    lg, _ = mdl.paged_decode(params, pool, {"tokens": toks}, pol, table,
                             jnp.zeros((1,), jnp.int32))
    np.testing.assert_allclose(lg[:, -1:], full, atol=2e-3)
