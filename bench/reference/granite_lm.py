"""Plain float32 reference of Granite-3.0's decoder (Hugging Face
``GraniteForCausalLM``, model_type ``granite``).

Straightforward ``jax.numpy`` under ``jax.default_matmul_precision("highest")``:
no kernels, no quantizers, no cache, nothing imported from the program.  The
block is ``dense_lm``'s SwiGLU/RMSNorm decoder with grouped key/value heads
and rotary positions, plus Granite's four scalars and its tied head:

    h0     = embedding_multiplier * E[x]
    h     += residual_multiplier * attn(norm(h)),  scores = attention_multiplier * q.k
    h     += residual_multiplier * mlp(norm(h))
    logits = norm(h) @ E.T / logits_scaling            (tie_embeddings)

Departures from the published model: dropout is 0 (the published
``attention_dropout`` is 0.1), and the vocabulary is padded to a multiple of
``vocab_pad_to`` with the padding columns of the logits masked.

Training: the mean next-token cross-entropy, its gradients, global-norm
clipping and AdamW with linear warm-up, over blocks of sequences so that the
timed batch fits.  Serving: the logits of every position of a sequence.
``m`` is the ``model`` section of a configuration file, scalars included.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.dense_lm import NEG, _linear, _norm, _rope, adamw, clip, \
    lr_at


def _layer(p, h, m):
    B, T, _ = h.shape
    H, KV, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    r = m["residual_multiplier"]
    x = _norm(p["ln1"], h, m["norm"])
    q = _rope(_linear(p["attn"]["wq"], x).reshape(B, T, H, hd), m["rope_theta"])
    k = _rope(_linear(p["attn"]["wk"], x).reshape(B, T, KV, hd), m["rope_theta"])
    v = _linear(p["attn"]["wv"], x).reshape(B, T, KV, hd)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q, k) * m["attention_multiplier"]
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal[None, None], s, NEG)
    a = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v)
    h = h + r * _linear(p["attn"]["wo"], a.reshape(B, T, H * hd))
    x = _norm(p["ln2"], h, m["norm"])
    f = _linear(p["mlp"]["down"], jax.nn.silu(_linear(p["mlp"]["gate"], x))
                * _linear(p["mlp"]["up"], x))
    return h + r * f


def logits(params, tokens, m):
    """(B, T) tokens -> (B, T, Vp) float32 logits, padding columns masked."""
    h = params["embed"]["table"][tokens] * m["embedding_multiplier"]

    def body(h, lp):
        return jax.checkpoint(lambda hh: _layer(lp, hh, m))(h), None
    h, _ = jax.lax.scan(body, h, params["layers"])
    w = (params["embed"]["table"].T if m["tie_embeddings"]
         else params["lm_head"]["w"])
    out = _norm(params["final_norm"], h, m["norm"]) @ w / m["logits_scaling"]
    vp = out.shape[-1]
    return jnp.where(jnp.arange(vp) < m["vocab_size"], out, NEG)


def _ce_sum(params, tokens, labels, m):
    logp = jax.nn.log_softmax(logits(params, tokens, m), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], -1))


def loss_and_grads(params, batch, m, rows: int):
    """Mean cross-entropy over the batch and its gradient, accumulated over
    blocks of ``rows`` sequences."""
    tokens, labels = batch["tokens"], batch["labels"]
    B = tokens.shape[0]
    if B % rows:
        raise ValueError(f"batch {B} is not a multiple of the block {rows}")
    tb = tokens.reshape(B // rows, rows, -1)
    lb = labels.reshape(B // rows, rows, -1)

    def body(acc, xs):
        loss, grads = acc
        l, g = jax.value_and_grad(_ce_sum)(params, xs[0], xs[1], m)
        return (loss + l, jax.tree.map(jnp.add, grads, g)), None
    zero = (jnp.float32(0.0), jax.tree.map(jnp.zeros_like, params))
    (loss, grads), _ = jax.lax.scan(body, zero, (tb, lb))
    n = tokens.size
    return loss / n, jax.tree.map(lambda g: g / n, grads)


def train_steps(params, batches, m, conf_train, rows: int, n_steps: int = 3):
    """Follow the first ``n_steps`` optimizer steps of the program.

    Returns (losses, first clipped gradient, params after ``n_steps``).
    """
    eng, hp = conf_train["engine"], conf_train["adamw"]
    with jax.default_matmul_precision("highest"):
        lg = jax.jit(lambda p, b: loss_and_grads(p, b, m, rows))
        step = jax.jit(adamw, static_argnums=(4,))
        mom = jax.tree.map(jnp.zeros_like, params)
        vel = jax.tree.map(jnp.zeros_like, params)
        losses, first = [], None
        for i in range(n_steps):
            loss, grads = lg(params, batches[i])
            grads = clip(grads, eng["clip_norm"])
            if first is None:
                first = grads
            losses.append(float(loss))
            params, mom, vel = step(params, mom, vel, grads, i + 1,
                                    jnp.float32(lr_at(i, eng)), hp)
    return losses, first, params


def sequence_logits(params, tokens, m):
    """(B, T) -> (B, T, Vp) logits at the reference's precision."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, t: logits(p, t, m))(params, tokens)
