"""Beyond-paper: unbiased quantized gradient all-reduce for data parallelism.

The paper's Theorem 1 only needs ``Q_b`` unbiased and independent across
sources of randomness.  A *communication* quantizer satisfies the same
contract: if every device quantizes its chunk unbiasedly before the exchange,
the resulting SGD gradient remains an unbiased estimator of the QAT gradient,
and Theorem 2 gains one additive variance term (reported by
:func:`compression_variance_bound`).

Wire protocol (2-phase compressed all-reduce, DESIGN.md Sec. 4):

  1. range agreement: ``psum`` of per-chunk min/max (negligible bytes);
  2. ``all_to_all`` of **int8** codes — device j receives everyone's j-th
     chunk (int8 on the wire, no in-flight accumulation so no overflow);
  3. local dequant + sum in fp32; re-quantize the *sum* (again unbiased);
  4. ``all_gather`` of **int8** codes of the reduced chunks.

Wire bytes: 2 x size x 1B  vs fp32 ring all-reduce's 2 x size x 4B — a 4x
reduction on the cross-pod (DCI) axis, visible in the dry-run HLO.

Runs under ``shard_map``; the caller supplies the mesh axis (we use ``pod``).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .quantizers import num_bins, stochastic_round

__all__ = ["compressed_psum", "compressed_grad_allreduce",
           "compression_variance_bound"]

_EPS = 1e-12


def _quantize_chunks(x: jax.Array, lo: jax.Array, hi: jax.Array,
                     key: jax.Array, bits: int):
    """Per-chunk affine stochastic quantize; x: (n_chunks, chunk)."""
    B = num_bins(bits)
    scale = B / jnp.maximum(hi - lo, _EPS)                    # (n_chunks, 1)
    codes = stochastic_round(scale * (x - lo), key)
    codes = jnp.clip(codes, 0, B) - (1 << (bits - 1))
    return codes.astype(jnp.int8), scale


def _dequant(codes: jax.Array, scale: jax.Array, lo: jax.Array, bits: int):
    off = 1 << (bits - 1)
    return (codes.astype(jnp.float32) + off) / scale + lo


def compressed_psum(x: jax.Array, key: jax.Array, axis_name: str,
                    bits: int = 8) -> jax.Array:
    """Unbiased int8 all-reduce of ``x`` over ``axis_name``.

    Must be called inside shard_map with ``axis_name`` in scope.  ``x`` is the
    device-local gradient (replica view, same shape everywhere).
    """
    n = jax.lax.psum(1, axis_name)
    size = x.size
    pad = (-size) % n
    flat = jnp.pad(x.reshape(-1), (0, pad))
    chunks = flat.reshape(n, -1)                              # row j -> device j

    # phase 1: per-chunk range agreement (tiny fp32 psum)
    lo = jnp.min(chunks, axis=1, keepdims=True)
    hi = jnp.max(chunks, axis=1, keepdims=True)

    k1, k2 = jax.random.split(jax.random.fold_in(key, jax.lax.axis_index(axis_name)))
    codes, scale = _quantize_chunks(chunks, lo, hi, k1, bits)

    # phase 2: int8 all_to_all — device j collects everyone's chunk j
    codes_t = jax.lax.all_to_all(codes[:, None], axis_name, split_axis=0,
                                 concat_axis=1, tiled=False)   # (1, n, chunk)
    meta = jnp.concatenate([scale, lo], axis=1)                # (n, 2)
    meta_t = jax.lax.all_to_all(meta[:, None], axis_name, split_axis=0,
                                concat_axis=1)                 # (1, n, 2)

    # phase 3: local dequant-sum, re-quantize the reduced chunk
    deq = _dequant(codes_t[0], meta_t[0, :, 0:1], meta_t[0, :, 1:2], bits)
    red = jnp.sum(deq, axis=0, keepdims=True)                  # (1, chunk)
    rlo, rhi = jnp.min(red, axis=1, keepdims=True), jnp.max(red, axis=1, keepdims=True)
    rcodes, rscale = _quantize_chunks(red, rlo, rhi, k2, bits)

    # phase 4: int8 all_gather of reduced chunks + tiny meta gather
    all_codes = jax.lax.all_gather(rcodes[0], axis_name)       # (n, chunk)
    all_meta = jax.lax.all_gather(
        jnp.concatenate([rscale, rlo], axis=1)[0], axis_name)  # (n, 2)
    out = _dequant(all_codes, all_meta[:, 0:1], all_meta[:, 1:2], bits)
    return out.reshape(-1)[:size].reshape(x.shape)


def compressed_grad_allreduce(grads, mesh, axis_name: str, key: jax.Array,
                              bits: int = 8, mean: bool = True):
    """Apply compressed_psum to every leaf of a gradient pytree.

    Entry point used by the training step when ``policy.compress_dp_grads``;
    wraps shard_map over ``axis_name`` with all other axes replicated.
    """
    n = mesh.shape[axis_name]

    def per_leaf(path, g, k):
        def body(gl, kl):
            out = compressed_psum(gl, kl[0], axis_name, bits)
            return out / n if mean else out
        spec = P()  # replica view along the compression axis
        return jax.shard_map(
            body, mesh=mesh, in_specs=(spec, P(axis_name)),
            out_specs=spec, check_vma=False)(g, jax.random.split(k, n))

    leaves, treedef = jax.tree.flatten(grads)
    keys = jax.random.split(key, len(leaves))
    out = [per_leaf(i, g, k) for i, (g, k) in enumerate(zip(leaves, keys, strict=True))]
    return jax.tree.unflatten(treedef, out)


def compression_variance_bound(x: jax.Array, bits: int, n_devices: int):
    """Additive Theorem-2 style variance from the 2-phase compression.

    Each of the two SR stages contributes <= size * R^2 / (4 B^2) per chunk;
    ranges shrink per-chunk so this is loose but cheap.
    """
    B = num_bins(bits)
    r = jnp.max(x) - jnp.min(x)
    return 2.0 * x.size * (r ** 2) / (4.0 * B * B)
