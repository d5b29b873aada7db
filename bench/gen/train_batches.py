"""Training batches: ``batch`` rows of ``seq + 1`` tokens drawn uniformly from
the vocabulary, a pure function of (seed, step), made on the device in one
jitted call per step.  ``tokens`` are the first ``seq``, ``labels`` the
next-token shift; every row of every step differs."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench.model import seed_key

MODE = "train"


@partial(jax.jit, static_argnums=(2, 3, 4))
def _make(key, step, batch: int, seq: int, vocab: int):
    toks = jax.random.randint(jax.random.fold_in(key, step), (batch, seq + 1),
                              0, vocab, jnp.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def batch_fn(traffic: dict, m: dict, seed: int):
    if traffic.get("tokens", "uniform") != "uniform":
        raise ValueError(f"unknown token distribution {traffic['tokens']!r}")
    key = seed_key(seed)
    shape = (traffic["batch"], traffic["seq"], m["vocab_size"])
    return lambda step: _make(key, jnp.int32(step), *shape)
