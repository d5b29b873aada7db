"""Plain float32 reference of the dense decoder the benchmark runs.

Straightforward ``jax.numpy`` under ``jax.default_matmul_precision("highest")``:
no kernels, no quantizers, no cache, nothing imported from the program.  It
follows the block the repository trains and serves (``_tx_layer``): pre-norm
attention with rotary positions and grouped key/value heads, then a
gelu (tanh form) or SwiGLU feed-forward, a final norm and an untied output
head over the padded vocabulary, whose padding columns are masked.

Training: the mean next-token cross-entropy, its gradients, global-norm
clipping and AdamW with linear warm-up, computed over blocks of sequences so
that the timed batch fits.  Serving: the logits of every position of a
sequence.  ``m`` is the ``model`` section of a configuration file.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NORM_EPS = 1e-5
NEG = -1e30


def _norm(p, x, kind):
    if kind == "layernorm":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + NORM_EPS) * p["g"] + p["b"]
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(ms + NORM_EPS) * p["g"]


def _linear(p, x):
    y = x @ p["w"]
    return y + p["b"] if "b" in p else y


def _rope(x, theta):
    """x: (B, T, heads, hd); rotate-half form, positions 0..T-1."""
    hd, T = x.shape[-1], x.shape[1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _layer(p, h, m):
    B, T, _ = h.shape
    H, KV, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    x = _norm(p["ln1"], h, m["norm"])
    q = _rope(_linear(p["attn"]["wq"], x).reshape(B, T, H, hd), m["rope_theta"])
    k = _rope(_linear(p["attn"]["wk"], x).reshape(B, T, KV, hd), m["rope_theta"])
    v = _linear(p["attn"]["wv"], x).reshape(B, T, KV, hd)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(hd)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal[None, None], s, NEG)
    a = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v)
    h = h + _linear(p["attn"]["wo"], a.reshape(B, T, H * hd))
    x = _norm(p["ln2"], h, m["norm"])
    if m["act"] == "swiglu":
        f = _linear(p["mlp"]["down"], jax.nn.silu(_linear(p["mlp"]["gate"], x))
                    * _linear(p["mlp"]["up"], x))
    else:
        f = _linear(p["mlp"]["fc2"], _gelu_tanh(_linear(p["mlp"]["fc1"], x)))
    return h + f


def logits(params, tokens, m):
    """(B, T) tokens -> (B, T, Vp) float32 logits, padding columns masked."""
    h = params["embed"]["table"][tokens]

    def body(h, lp):
        return jax.checkpoint(lambda hh: _layer(lp, hh, m))(h), None
    h, _ = jax.lax.scan(body, h, params["layers"])
    out = _norm(params["final_norm"], h, m["norm"]) @ params["lm_head"]["w"]
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
    n = tokens.size
    tb = tokens.reshape(B // rows, rows, -1)
    lb = labels.reshape(B // rows, rows, -1)

    def body(acc, xs):
        loss, grads = acc
        l, g = jax.value_and_grad(_ce_sum)(params, xs[0], xs[1], m)
        return (loss + l, jax.tree.map(jnp.add, grads, g)), None
    zero = (jnp.float32(0.0), jax.tree.map(jnp.zeros_like, params))
    (loss, grads), _ = jax.lax.scan(body, zero, (tb, lb))
    return loss / n, jax.tree.map(lambda g: g / n, grads)


def lr_at(step: int, engine: dict) -> float:
    """Linear warm-up over a twentieth of the schedule, then cosine decay."""
    total = engine["schedule_steps"]
    warm = max(total // 20, 1)
    if step < warm:
        return engine["lr"] * step / warm
    prog = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return engine["lr"] * 0.5 * (1.0 + math.cos(math.pi * prog))


def clip(grads, max_norm: float):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-12))
    return jax.tree.map(lambda g: g * scale, grads)


def adamw(params, mom, vel, grads, t: int, lr: float, hp: dict):
    """AdamW step ``t`` (1-based) with decoupled weight decay."""
    b1, b2, eps, wd = hp["b1"], hp["b2"], hp["eps"], hp["weight_decay"]
    mom = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, mom, grads)
    vel = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, vel, grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree.map(
        lambda p, a, b: p - lr * ((a / c1) / (jnp.sqrt(b / c2) + eps) + wd * p),
        params, mom, vel)
    return params, mom, vel


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
