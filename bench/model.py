"""A configuration file turned into what the system under test takes: its
``ArchConfig``, its ``QuantPolicy``, and weights made from the seed.

The weights are the benchmark's own: one jitted call builds every leaf on the
device from ``--seed``, in the layout the program's dense decoder stores
(stacked ``(L, ...)`` layers).  The plain reference reads the same tree, so
neither side takes anything the other made.
"""

from __future__ import annotations

import functools
import json
import os

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))


def load_json(kind: str, name: str) -> dict:
    """``bench/<kind>/<name>.json``, e.g. ``configs/statquant-tx``."""
    with open(os.path.join(HERE, kind, f"{name}.json")) as f:
        return json.load(f)


def padded_vocab(m: dict) -> int:
    p = m["vocab_pad_to"]
    return (m["vocab_size"] + p - 1) // p * p


def arch_config(conf: dict):
    """The program's ``ArchConfig`` for a configuration file: the registry
    entry with the file's sizes written over it."""
    import dataclasses
    from repro.configs import get_config
    m = conf["model"]
    base = get_config(conf["arch"])
    keys = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
            "vocab_size", "vocab_pad_to", "act", "norm", "qkv_bias",
            "rope_theta")
    cfg = dataclasses.replace(base, **{k: m[k] for k in keys})
    if cfg.family != "dense" or cfg.rope != "standard":
        raise ValueError(f"{conf['arch']}: the reference covers the dense "
                         f"RoPE decoder only (family {cfg.family}, rope "
                         f"{cfg.rope})")
    return cfg


def policy(spec: dict):
    """``QuantPolicy`` from ``{"factory": "fqt"|"qat", ...keyword args}``."""
    from repro.core import QuantPolicy
    kw = {k: v for k, v in spec.items() if k != "factory"}
    return getattr(QuantPolicy, spec["factory"])(**kw)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def param_shapes(m: dict) -> dict:
    """Leaf shapes of the dense decoder, in the program's tree layout."""
    L, d, ff = m["n_layers"], m["d_model"], m["d_ff"]
    H, KV, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    vp = padded_vocab(m)

    def norm(*lead):
        n = {"g": lead + (d,)}
        if m["norm"] == "layernorm":
            n["b"] = lead + (d,)
        return n

    def lin(din, dout, bias):
        p = {"w": (L, din, dout)}
        if bias:
            p["b"] = (L, dout)
        return p

    bias = m["qkv_bias"]
    if m["act"] == "swiglu":
        mlp = {"gate": lin(d, ff, False), "up": lin(d, ff, False),
               "down": lin(ff, d, False)}
    else:
        mlp = {"fc1": lin(d, ff, False), "fc2": lin(ff, d, False)}
    return {
        "embed": {"table": (vp, d)},
        "final_norm": norm(),
        "lm_head": {"w": (d, vp)},
        "layers": {
            "ln1": norm(L), "ln2": norm(L), "mlp": mlp,
            "attn": {"wq": lin(d, H * hd, bias), "wk": lin(d, KV * hd, bias),
                     "wv": lin(d, KV * hd, bias),
                     "wo": lin(H * hd, d, False)}},
    }


def _is_shape(x):
    return isinstance(x, tuple)


def init_params(m: dict, seed: int):
    """Every leaf from the seed in one jitted call, f32 on the device."""
    return _builder(json.dumps(m, sort_keys=True))(seed_key(seed))


@functools.lru_cache(maxsize=None)
def _builder(m_json: str):
    """The jitted weight builder of one model, compiled once."""
    m = json.loads(m_json)
    shapes = param_shapes(m)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes, is_leaf=_is_shape)[0]]
    treedef = jax.tree.structure(shapes, is_leaf=_is_shape)
    leaves = jax.tree.leaves(shapes, is_leaf=_is_shape)

    def build(key):
        out = []
        for i, (path, shape) in enumerate(zip(paths, leaves, strict=True)):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            last = path.rsplit("[", 1)[-1]
            if "table" in last:
                out.append(0.02 * z)
            elif "'g'" in last:
                out.append(1.0 + 0.05 * z)
            elif "'b'" in last:
                out.append(0.02 * z)
            else:                                   # a kernel: (..., din, dout)
                out.append(z / jnp.sqrt(jnp.float32(shape[-2])))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(build)


def check_layout(params, model) -> None:
    """The benchmark's tree must be the one the program initialises."""
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), params)
    exp = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), want)
    if got != exp:
        raise ValueError(f"benchmark weights do not match the program's "
                         f"layout:\n{got}\nvs\n{exp}")
