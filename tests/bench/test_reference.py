"""The plain reference against the program on the CPU at the smoke size:
the training loss and gradients of ``Engine``'s model under ``simulate``,
and the logits of paged prefill-then-decode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import model as bm
from bench.gen import train_batches
from bench.reference import dense_lm
from conftest import TRAIN_TRAFFIC


def _program_loss_grads(conf, params, batch, pol):
    from repro.models import build_model
    mdl = build_model(bm.arch_config(conf))

    def f(p):
        return mdl.loss(p, batch, jax.random.PRNGKey(0), pol)[0]
    return jax.value_and_grad(f)(params)


@pytest.mark.parametrize("which", ["tx_tiny", "swiglu_tiny"])
def test_reference_matches_program_loss_and_grads(which, request):
    from repro.core import QuantPolicy
    conf = request.getfixturevalue(which)
    m = conf["model"]
    params = bm.init_params(m, 3)
    batch = train_batches.batch_fn(TRAIN_TRAFFIC, m, 3)(0)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_g = dense_lm.loss_and_grads(params, batch, m, rows=2)
        # unquantized: the same function to f32 rounding
        loss, g = _program_loss_grads(conf, params, batch,
                                      QuantPolicy.exact())
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(ref_g), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-6)
    # the 8-bit FQT step on the simulate backend: within quantization noise
    q_loss, q_g = _program_loss_grads(
        conf, params, batch, QuantPolicy.fqt("bhq", 8, bhq_block=32,
                                             backend="simulate"))
    assert abs(float(q_loss) - float(ref_loss)) / float(ref_loss) < 2e-3
    ng = [float(jnp.linalg.norm(x)) for x in jax.tree.leaves(q_g)]
    nr = [float(jnp.linalg.norm(x)) for x in jax.tree.leaves(ref_g)]
    med = float(np.median(nr))
    assert max(abs(a - b) / max(b, med) for a, b in zip(ng, nr, strict=True)) < 0.1


def test_reference_matches_paged_prefill_then_decode(tx_tiny):
    from repro.core import QuantPolicy
    from repro.models import build_model
    conf, m = tx_tiny, tx_tiny["model"]
    cfg = bm.arch_config(conf)
    mdl = build_model(cfg)
    params = bm.init_params(m, 5)
    P, nb = 8, 6
    pool = mdl.init_paged_pool(cfg, 1 + nb, P)
    table = jnp.arange(1, nb + 1, dtype=jnp.int32)[None]
    rng = np.random.default_rng(0)
    seq = rng.integers(0, m["vocab_size"], 30).astype(np.int32)
    n_prompt = 20
    pol = QuantPolicy.exact()
    lg, pool = mdl.paged_decode(params, pool, {"tokens": seq[None, :n_prompt]},
                                pol, table, jnp.zeros((1,), jnp.int32))
    got = [np.asarray(lg[0])]
    for t in range(n_prompt, len(seq)):
        lg, pool = mdl.paged_decode(params, pool, {"tokens": seq[None, t:t + 1]},
                                    pol, table, jnp.full((1,), t, jnp.int32))
        got.append(np.asarray(lg[0]))
    got = np.concatenate(got)[:, :m["vocab_size"]]
    ref = np.asarray(dense_lm.sequence_logits(params, seq[None], m))[0]
    ref = ref[:, :m["vocab_size"]]
    # int8 KV pages: per-row 8-bit keys and values, the only rounding here
    np.testing.assert_allclose(got, ref, atol=5e-2)
    assert np.abs(got - ref).max() > 0      # the pages really are int8
