"""Block Householder Quantizer (BHQ) — StatQuant Sec. 4.2 / Appendix D.4-D.5.

The paper's construction, adapted to TPU/XLA static shapes (DESIGN.md Sec. 3):

  1. sort rows by magnitude ``M_i = ||g_i||_inf`` (descending);
  2. pick the number of groups ``G`` by minimizing the paper's variance proxy
     ``(sum_{i<=G} M_i)^2 / (N - G)`` — vectorized over *all* candidate G with
     one prefix sum instead of the paper's CPU loop;
  3. group ``i`` = the i-th largest row + ``~(N-G) * M_i / sum M`` small rows
     (largest-remainder integerization so sizes sum to N);
  4. scale rows by ``diag(s1, s2, ..., s2)`` with the Lagrangian-optimal
     ``s1 ∝ λ1^{-1/3} m^{1/6}``, ``s2 ∝ λ2^{-1/3} m^{1/6}`` (Appendix D.4),
     then apply the group Householder ``Q = I - 2 n nᵀ / ||n||²``,
     ``n = 1/√m - e1``;
  5. stochastically round with a per-group zero point.

Steps 1-3 and the scales of step 4 work on the block's (n,) row statistics
only: the sort permutes the magnitudes and ranges, never the (n, D) rows,
and every gather, search and per-group reduction over them is a compare
against an (n, n) one-hot or same-group mask.  The rows meet the transform
once, as one dense per-block mixing matrix ``M = Q·S·P`` (P the sort
permutation) applied with a batched f32 matmul at ``Precision.HIGHEST`` —
the TPU's default f32 dot is one bf16 pass, a lower precision than the
quantizer's f32 arithmetic.  ``Q`` is symmetric and involutory, so
dequantization applies ``M^{-1} = Pᵀ·S^{-1}·Q`` the same way:
unbiasedness ``E[Q_b(g)] = g`` holds exactly for any grouping (Theorem 1
requirement).  The mixing costs ``2 * block_rows`` FLOPs per element of the
operand in each direction (six bf16 passes each at HIGHEST): linear in the
rows, with no row-wise gathers or scatters at the operand's width.

For large N (LM token rows) the grouping runs independently over row blocks of
``block_rows`` via ``vmap`` — bounding the sort and mixing cost and keeping
the paper's N≈128-row regime per group search.  Ragged row counts
(``n % block_rows != 0``) are padded up to the next block multiple with
all-zero rows: zero rows sort last, carry zero grouping weight, and the
per-block transform stays linear and invertible, so unbiasedness of the
*real* rows is exact; dequantization slices the padding back off.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from .quantizers import num_bins, stochastic_round, row_dynamic_range

__all__ = ["BHQTensor", "quantize_bhq_stoch", "bhq_variance_bound",
           "bhq_exact_variance"]

_EPS = 1e-12


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BHQTensor:
    """Quantized tensor under the block Householder transform.

    Dequantization is ``Pᵀ · diag(1/s) · Q · (codes + Z)`` per block, where
    ``Q`` is the (involutory) per-group Householder mix and ``Pᵀ`` returns
    the sorted rows to their original order (:meth:`dequant_map`).  All
    fields are flat over ``(n_blocks, block_rows, D)``.
    """

    codes: jax.Array        # (nb, n, D) uint8 in [0, B]
    zero: jax.Array         # (nb, n, 1) per-row zero (== its group zero)
    row_scale: jax.Array    # (nb, n, 1) s1 for large rows, s2 otherwise
    n_vec: jax.Array        # (nb, n, 1) Householder normal entry per row
    coef: jax.Array         # (nb, n, 1) 2/||n||² of the row's group (0 if m==1)
    seg: jax.Array          # (nb, n) group id per sorted row
    inv_perm: jax.Array     # (nb, n) maps sorted position -> original row
    bits: int = dataclasses.field(metadata=dict(static=True))
    shape: tuple = dataclasses.field(metadata=dict(static=True))

    @property
    def n_rows(self) -> int:
        """Real (unpadded) row count — blocks may carry zero-padding rows."""
        return math.prod(self.shape[:-1]) if len(self.shape) > 1 else 1

    def dequant(self) -> jax.Array:
        t = self.codes.astype(jnp.float32) + self.zero
        out = self.dequant_epilogue(t)
        return out.reshape(-1, self.shape[-1])[:self.n_rows].reshape(self.shape)

    @property
    def int8_codes(self) -> jax.Array:
        offset = 1 << (self.bits - 1)
        return (self.codes.astype(jnp.int16) - offset).astype(jnp.int8)

    @property
    def int8_offset(self) -> int:
        return 1 << (self.bits - 1)

    def dequant_map(self) -> jax.Array:
        """``M^{-1} = Pᵀ·S^{-1}·Q`` per block, (nb, n, n): original row by
        sorted row, built from the tensor's own fields."""
        return jax.vmap(_mixing)(self.inv_perm, self.seg, self.n_vec[..., 0],
                                 self.coef[..., 0], 1.0 / self.row_scale[..., 0])

    def dequant_epilogue(self, t: jax.Array) -> jax.Array:
        """Apply ``Pᵀ·S^{-1}·Q`` to ``t`` (same row layout as codes).

        Used by the native int8 GEMM path: ``Q_b(g) @ Wᵀ`` is computed as
        ``S^{-1}((codes + Z) @ Wᵀ)`` — the int GEMM runs on raw codes and this
        epilogue mixes the *output* rows with one batched matmul
        (DESIGN.md Sec. 3).
        """
        return lax.dot_general(self.dequant_map(), t,
                               (((2,), (1,)), ((0,), (0,))),
                               precision=lax.Precision.HIGHEST)


def _pick(mask: jax.Array, v: jax.Array) -> jax.Array:
    """``sum_k mask[j, k] * v[k]`` per row j: the gather ``v[idx]`` when each
    row of ``mask`` is one-hot (every other term is an exact zero), a group
    sum when ``mask`` is a same-group mask.  No gather or scatter."""
    return jnp.sum(jnp.where(mask, v[None, :], 0), axis=-1)


def _mixing(perm: jax.Array, seg: jax.Array, n_vec: jax.Array,
            coef: jax.Array, w: jax.Array) -> jax.Array:
    """``Pᵀ·diag(w)·Q`` for one block, as an (n, n) array indexed (original
    row o, sorted row k).

    ``perm`` maps sorted position -> original row, ``Q_jk = δ_jk - c_j n_j
    n_k`` within a group (0 across groups, ``c`` constant per group, so
    ``Q`` is symmetric).  Entry ``(o, k)`` is ``w_r Q_rk`` for the sorted
    position ``r`` of row ``o``.  With ``w = 1/s`` it is the dequantization
    map; its transpose with ``w = s`` is the quantization map ``Q·S·P``.
    """
    n = perm.shape[0]
    at = jnp.arange(n, dtype=perm.dtype)[:, None] == perm[None, :]   # [o, r]
    u = _pick(at, w * coef * n_vec)          # w_r c_r n_r at original row o
    seg_o = _pick(at, seg)                   # group of original row o
    diag = jnp.where(at, w[None, :], 0.0)
    return diag - jnp.where(seg_o[:, None] == seg[None, :],
                            u[:, None] * n_vec[None, :], 0.0)


def _largest_remainder(weights: jax.Array, total: jax.Array,
                       valid: jax.Array) -> jax.Array:
    """Integerize ``total * weights`` (sum over valid == total), static shape.

    weights: (n,) nonneg, zero where ~valid. Returns int32 sizes (n,).
    """
    n = weights.shape[0]
    wsum = jnp.maximum(jnp.sum(weights), _EPS)
    raw = total * weights / wsum
    base = jnp.floor(raw).astype(jnp.int32)
    base = jnp.where(valid, base, 0)
    rem = raw - base
    rem = jnp.where(valid, rem, -1.0)
    short = total - jnp.sum(base)
    # give +1 to the `short` largest remainders: rank = position in a stable
    # descending sort of rem, counted as the rows that come before each row
    idx = jnp.arange(n)
    before = ((rem[None, :] > rem[:, None])
              | ((rem[None, :] == rem[:, None]) & (idx[None, :] < idx[:, None])))
    rank = jnp.sum(before, axis=-1, dtype=jnp.int32)
    return base + jnp.where((rank < short) & valid, 1, 0)


def _g_candidates(n: int):
    """Static candidate group counts: 1, 2, 4, ... n//2, and n.

    G = n (singleton groups, Q = I) makes BHQ degrade exactly to PSQ —
    essential when row magnitudes are uniform (early training), where any
    grouping with m >= 2 *amplifies* variance ~m^2 (Appendix D.4 bound with
    lambda2 ~ lambda1).  Caught by tests/test_system.py."""
    cands, g = [], 1
    while g <= max(n // 2, 1):
        cands.append(g)
        g *= 2
    if n not in cands:
        cands.append(n)
    return cands


def _select_g(mag_s: jax.Array, rng_s: jax.Array, n: int, g_search: str,
              n_valid=None):
    """Pick the number of groups G.

    ``n_valid``: traced count of real rows (<= the static block size n) —
    ragged blocks carry inert zero-padding rows that must not count as
    small-row budget in either proxy; candidates G > n_valid are masked out.

    ``paper``   — the paper's Appendix-D.5 proxy (sum_{i<=G} M_i)^2/(N-G)
                  for G < N, which idealizes lambda2 ~ 0 and can badly
                  mis-group when several comparable outliers exist.  The
                  PSQ-degenerate candidate G = N (no small rows — the proxy's
                  denominator vanishes) is scored with its *exact* variance
                  sum, sum_i R(x_i)^2 (singleton groups, Q = I: each row's
                  conditional SR variance is D/(4B^2) * R_i^2 and the shared
                  D/(4B^2) factor drops out of the argmin).
    ``refined`` — (default) score each candidate G with the *full* D.4 bound
                  per group, sum_i (l1_i^{2/3} m_i^{-1/3} + l2^{2/3} m_i^{2/3})^3
                  with l1_i = R(row_i), l2 = 2 M_{G+1}, m_i the heuristic
                  proportional group size.  O(N) per candidate, log2(N)
                  candidates.  DESIGN.md Sec. 6 records this adaptation.
    """
    nv = jnp.asarray(n if n_valid is None else n_valid, jnp.float32)
    if g_search == "paper":
        csum = jnp.cumsum(mag_s)
        gs_idx = jnp.arange(1, n, dtype=jnp.float32)
        score = (csum[:-1] ** 2) / jnp.maximum(nv - gs_idx, 1.0)
        score = jnp.where(gs_idx < nv, score, jnp.inf)   # G in [1, nv-1]
        score_n = jnp.sum(rng_s ** 2)[None]              # G = nv: exact (PSQ)
        score = jnp.concatenate([score, score_n])
        best = jnp.argmin(score).astype(jnp.int32)
        return jnp.where(best == n - 1, nv.astype(jnp.int32), best + 1)
    idx = jnp.arange(n, dtype=jnp.float32)
    scores = []
    cands = _g_candidates(n)
    for G in cands:
        mask = idx < G
        msum = jnp.maximum(jnp.sum(jnp.where(mask, mag_s, 0.0)), _EPS)
        m_i = 1.0 + jnp.maximum(nv - G, 0.0) * mag_s / msum   # heuristic sizes
        lam1 = jnp.maximum(rng_s, _EPS)
        lam2 = 2.0 * (mag_s[G] if G < n else 0.0) + _EPS
        term = (lam1 ** (2 / 3) * m_i ** (-1 / 3)
                + lam2 ** (2 / 3) * m_i ** (2 / 3)) ** 3
        score = jnp.sum(jnp.where(mask, term, 0.0))
        scores.append(jnp.where(G <= nv, score, jnp.inf))
    best = jnp.argmin(jnp.stack(scores))
    return jnp.asarray(cands, dtype=jnp.int32)[best]


def _bhq_transform(g: jax.Array, valid: jax.Array, bits: int, g_search: str):
    """The deterministic part of BHQ over one (n, D) block: sort, group,
    scale, Householder.  Returns ``(y, zero, row_scale, n_vec, coef, seg,
    perm)`` where ``y - zero`` is the tensor the stochastic round consumes —
    shared by :func:`_bhq_block` (quantize) and :func:`bhq_exact_variance`
    (exact conditional variance needs the pre-round values).

    ``valid``: (n,) mask of real rows.  Zero-padding rows (ragged inputs)
    sort last and sit in *singleton* groups of their own (Q = I, zero
    scaled value): mixing them into real groups would let a group's small
    rows be all-zero, collapsing its lambda2 and over-scaling the large row
    into deterministic clipping — a bias, not just variance.
    """
    B = float(num_bins(bits))
    n = g.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)

    # --- step 1: sort rows by infinity-norm magnitude, descending ----------
    # only the (n,) row statistics are permuted; the rows themselves meet
    # the sort once, inside the mixing matrix of step 5
    mag = jnp.max(jnp.abs(g), axis=-1)                       # M_i
    mag = jnp.where(valid, mag, -1.0)                        # pads strictly last
    perm = jnp.argsort(-mag).astype(jnp.int32)               # sorted -> original
    sort = perm[:, None] == idx[None, :]                     # one-hot [sorted, orig]
    mag_s = jnp.maximum(_pick(sort, mag), 0.0)
    n_valid = jnp.sum(valid.astype(jnp.int32))

    # --- step 2: choose the number of groups G ------------------------------
    rng_s = _pick(sort, row_dynamic_range(g))
    G = _select_g(mag_s, rng_s, n, g_search, n_valid)        # traced scalar
    G = jnp.minimum(G, n_valid)          # group only among the real rows

    is_large = idx < G
    is_pad = idx >= n_valid

    # --- step 3: group sizes ∝ magnitude, largest-remainder -----------------
    w = jnp.where(is_large, mag_s, 0.0)
    n_small = jnp.maximum(n_valid - G, 0).astype(jnp.float32)
    extras = _largest_remainder(w, n_small, is_large)
    # small row p (p = j - G in sorted order) joins group searchsorted(cum, p,
    # side="right"): the count of cum entries <= p, compared all at once
    cum = jnp.cumsum(extras)                                  # (n,)
    p = jnp.clip(idx - G, 0, n - 1)
    small_seg = jnp.sum(cum[None, :] <= p[:, None], axis=-1, dtype=jnp.int32)
    seg = jnp.where(is_large, idx, jnp.clip(small_seg, 0, n - 1))
    seg = jnp.where(is_pad, idx, seg)                         # pads: singletons
    same = seg[:, None] == seg[None, :]                       # same-group mask
    own = seg[:, None] == idx[None, :]                        # row -> its group id

    # --- step 4: optimal scales (Appendix D.4), per row of each group --------
    lam1 = jnp.maximum(rng_s, _EPS)                           # per sorted row; rows < G are the large ones
    lam1_g = _pick(own, jnp.where(is_large, lam1, 1.0))       # λ1 of the group's large row
    small_mag = jnp.where(is_large, 0.0, mag_s)
    lam2_g = 2.0 * jnp.max(jnp.where(same, small_mag[None, :], -jnp.inf), axis=-1)
    lam2_g = jnp.maximum(lam2_g, _EPS)

    m_g = jnp.maximum(jnp.sum(same, axis=-1).astype(jnp.float32), 1.0)
    denom = lam1_g ** (2 / 3) * m_g ** (-1 / 3) + lam2_g ** (2 / 3) * m_g ** (2 / 3)
    s1 = B * lam1_g ** (-1 / 3) * m_g ** (1 / 6) / denom
    s2 = B * lam2_g ** (-1 / 3) * m_g ** (1 / 6) / denom

    row_scale = jnp.where(is_large, s1, s2)[:, None]          # (n,1)

    # Householder normal: n_j = 1/sqrt(m) - [j is the group's large row]
    sqrt_m = jnp.sqrt(m_g)
    n_vec = (1.0 / sqrt_m - is_large.astype(jnp.float32))[:, None]
    # 2/||n||² = sqrt(m)/(sqrt(m)-1); zero for singleton groups (Q = I)
    coef = jnp.where(m_g > 1.5, sqrt_m / jnp.maximum(sqrt_m - 1.0, _EPS), 0.0)[:, None]

    # --- step 5: y = Q·S·P·g in one mixing matmul + per-group zero ----------
    mix = _mixing(perm, seg, n_vec[:, 0], coef[:, 0], row_scale[:, 0])
    y = lax.dot_general(mix, g, (((0,), (0,)), ((), ())),
                        precision=lax.Precision.HIGHEST)      # sorted rows
    row_min = jnp.min(y, axis=-1)
    zero = jnp.min(jnp.where(same, row_min[None, :], jnp.inf), axis=-1)[:, None]
    return y, zero, row_scale, n_vec, coef, seg, perm


def _bhq_block(g: jax.Array, key: jax.Array, valid: jax.Array, bits: int,
               g_search: str):
    """BHQ over one (n, D) block. Returns fields for BHQTensor (block-local)."""
    B = float(num_bins(bits))
    y, zero, row_scale, n_vec, coef, seg, perm = _bhq_transform(
        g, valid, bits, g_search)
    codes = stochastic_round(y - zero, key)
    codes = jnp.clip(codes, 0.0, B).astype(jnp.uint8)
    inv_perm = perm  # y rows are in sorted order; dequant_map maps them back
    return codes, zero, row_scale, n_vec, coef, seg, inv_perm


def _blocked_rows(x: jax.Array, block_rows: int):
    """Flatten to rows and zero-pad up to a ``block_rows`` multiple.

    Returns ``(blocks (nb, blk, D), valid (nb, blk), n_real)``.  A single
    short input (n <= block_rows) stays one unpadded block; larger ragged
    inputs pad so the per-block group search keeps the paper's
    ~block_rows-row regime instead of silently collapsing to one all-n
    block (unbounded sort cost).
    """
    rows = x.reshape(-1, x.shape[-1])
    n = rows.shape[0]
    blk = block_rows if n > block_rows else n
    n_pad = -(-n // blk) * blk
    if n_pad != n:
        rows = jnp.pad(rows, ((0, n_pad - n), (0, 0)))
    nb = n_pad // blk
    valid = (jnp.arange(n_pad) < n).reshape(nb, blk)
    return rows.reshape(nb, blk, x.shape[-1]), valid, n


def quantize_bhq_stoch(x: jax.Array, key: jax.Array, bits: int = 8,
                       block_rows: int = 1024,
                       g_search: str = "refined") -> BHQTensor:
    """BHQ over row blocks. x: (..., D) -> rows = prod(leading dims).

    Ragged row counts pad with zero rows (zero grouping weight; sliced off
    again by ``dequant``/``dequant_epilogue`` consumers) — unbiasedness of
    the real rows is exact for any grouping, padded or not.
    """
    shape = x.shape
    gb, valid, _ = _blocked_rows(x, block_rows)
    keys = jax.random.split(key, gb.shape[0])
    codes, zero, rs, nv, cf, seg, ip = jax.vmap(
        partial(_bhq_block, bits=bits, g_search=g_search))(gb, keys, valid)
    return BHQTensor(codes=codes, zero=zero, row_scale=rs, n_vec=nv, coef=cf,
                     seg=seg, inv_perm=ip, bits=bits, shape=shape)


def bhq_variance_bound(qt: BHQTensor) -> jax.Array:
    """Eq. (13): Var <= D/4 * ||S^{-1}||_F^2 = D/4 * sum_j (1/s_j)^2.

    (The Householder factor is orthogonal, so ||S^{-1}||_F = ||diag(1/s)||_F.)
    """
    d = qt.shape[-1]
    return d / 4.0 * jnp.sum(1.0 / qt.row_scale ** 2)


def _block_exact_variance(g: jax.Array, retained: jax.Array, *, bits: int,
                          g_search: str) -> jax.Array:
    """Exact conditional variance contributed by one (n, D) block.

    The dequantized noise is ``S^{-1} eps = diag(1/s) Q eps`` with independent
    SR noise ``Var[eps_kd] = p(1-p)``, ``p = frac(y - zero)`` (Proposition 4).
    Summing over the *retained* output rows j (zero-padding rows excluded):

        Var = sum_k w_k * colnorm_k
        w_k       = sum_d p(1-p)_kd
        colnorm_k = sum_{j ret} (Q_jk / s_j)^2
                  = ret_k (1 - 2 c n_k^2)/s_k^2 + c^2 n_k^2 sum_{j in g, ret} n_j^2/s_j^2

    using ``Q_jk = delta_jk - c n_j n_k`` within a group (0 across groups).
    """
    n = g.shape[0]
    y, zero, row_scale, n_vec, coef, seg, perm = _bhq_transform(
        g, retained > 0, bits, g_search)
    t = y - zero
    p = t - jnp.floor(t)
    w = jnp.sum(p * (1.0 - p), axis=-1)                       # (n,)
    s = row_scale[:, 0]
    nv = n_vec[:, 0]
    c = coef[:, 0]
    ret = _pick(perm[:, None] == jnp.arange(n)[None, :], retained)   # sorted order
    a = _pick(seg[:, None] == seg[None, :], ret * nv ** 2 / s ** 2)  # group sums
    colnorm = ret * (1.0 - 2.0 * c * nv ** 2) / s ** 2 + c ** 2 * nv ** 2 * a
    return jnp.sum(w * colnorm)


def bhq_exact_variance(x: jax.Array, bits: int = 8, block_rows: int = 1024,
                       g_search: str = "refined") -> jax.Array:
    """Exact conditional ``Var[Q_b(x) | x]`` summed over entries.

    The BHQ transform is deterministic given ``x``; only the stochastic
    rounding injects noise, so the exact variance is the SR ``sum p(1-p)``
    (Proposition 4) pushed through the ``S^{-1}`` columns — see
    :func:`_block_exact_variance`.  Exact modulo the (rare) code clipping at
    the bin boundaries, the same caveat as :func:`~repro.core.quantizers.
    sr_variance_exact`.
    """
    gb, valid, _ = _blocked_rows(x, block_rows)
    per_block = jax.vmap(partial(_block_exact_variance, bits=bits,
                                 g_search=g_search))(
        gb, valid.astype(jnp.float32))
    return jnp.sum(per_block)
