"""Static variance-budget precision planner (paper Sec. 4, made a solver).

The paper's Eq. 6/8 decomposition says total FQT gradient variance is the
sum of independent per-site quantization variances, and Sec. 4 shows the
*quantizer family* (PTQ -> PSQ -> BHQ) and the *bitwidth* trade variance
against bytes moved per site.  That makes mixed-precision planning a
classic budgeted-allocation problem — and a *static* one: everything it
needs (GEMM shapes, scan trip counts, closed-form variances) is available
at trace time.

Per quantized gradient GEMM site this module combines

  * shape-derived dims (m, k, n, scan multiplicity) from the traced jaxpr
    (analysis/graph.py — the same walk the contract auditor uses),
  * the exact conditional variance of each candidate quantizer/width from
    :func:`repro.core.theory.quantizer_variance` (Proposition 4 closed
    forms), evaluated on a fixed-seed Gaussian proxy of the SR operand,
  * a bytes-moved cost model (:func:`gemm_bytes_moved`: operands in at
    their code width, f32 result out),

into per-site (variance, bytes) candidates at each legal width {8, 4, 2},
prunes the Pareto-dominated ones, and solves

    minimize  sum_site Var[site]   s.t.  sum_site bytes[site] <= budget

with greedy marginal-variance-per-byte descent plus an exact
multiple-choice-knapsack DP for small models.  The result is a
ready-to-use ``QuantPolicy.overrides`` mapping; ``python -m repro.analysis
plan`` prints it and writes JSON that ``launch/train.py --override-file``
consumes directly.

Candidate legality follows the execution contract, not wishful thinking:
wgrad (``Q_b1``) must be per-tensor (``qt_gemm_tn`` contracts over the row
axis per-row scales live on — core/backend.py), so only PTQ; agrad
(``Q_b2``) admits PTQ/PSQ/BHQ; widths are clamped by the int32-accumulator
bound (:func:`repro.core.analysis.ranges.max_safe_k`).
"""

from __future__ import annotations

import dataclasses
import json
import math
import re
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..core.policy import QuantPolicy, overrides_to_json
from ..core.theory import quantizer_variance
from .graph import iter_gemm_sites
from .ranges import max_safe_k

__all__ = ["gemm_bytes_moved", "legal_widths", "PlanSite", "Candidate",
           "PlanEntry", "Plan", "collect_plan_sites", "site_candidates",
           "plan_model"]

_GRAD_ROLES = ("wgrad", "agrad")
_WIDTHS = (8, 4, 2)
# SR-operand sample cap: variance is evaluated on a fixed-seed Gaussian
# proxy no larger than this and scaled linearly to the true element count
# (iid entries => sum-variance is ~linear in size; the ~log(d) drift of the
# dynamic range is noise at planning precision)
_SAMPLE_CAP = 1 << 16


def gemm_bytes_moved(m: int, k: int, n: int, lhs_bits: int,
                     rhs_bits: int, out_bytes: int = 4) -> float:
    """HBM bytes one (m, k) x (k, n) GEMM moves: packed sub-byte operands
    in, fp32 (by default) result out (f32 = 32/32, int8 = 8/8, packed
    W4/W2/W1 = 8/wbits)."""
    return m * k * lhs_bits / 8.0 + k * n * rhs_bits / 8.0 + out_bytes * m * n


def legal_widths(role: str, k: int, *, partner_bits: int = 8,
                 widths: Sequence[int] = _WIDTHS) -> Tuple[int, ...]:
    """Widths from ``widths`` legal for ``role`` at contraction size ``k``.

    Backward roles admit [2, 8] (1-bit SR degenerates; only the forward
    weight may go binary — GemmQuantConfig.validate), and the int32
    accumulator must survive ``k`` worst-case products with the partner
    operand's width (analysis/ranges.max_safe_k).
    """
    lo = 1 if role == "fwd_weight" else 2
    out = []
    for b in widths:
        if not lo <= b <= 8:
            continue
        if role == "wgrad":
            pair = (partner_bits, b)        # lhs = saved fwd act, rhs = dY
        elif role == "agrad":
            pair = (b, partner_bits)        # lhs = dY, rhs = saved weight
        else:
            pair = (b, partner_bits)
        if k <= max_safe_k(*pair):
            out.append(b)
    return tuple(out)


@dataclasses.dataclass(frozen=True)
class PlanSite:
    """One quantized gradient GEMM group: the main (max-FLOPs) GEMM of a
    (path, role) marker scope in the traced backward graph."""

    path: str
    role: str                 # "wgrad" | "agrad"
    m: int                    # GEMM output rows
    k: int                    # contraction size
    n: int                    # GEMM output cols
    mult: int                 # enclosing-scan trip count
    flops: float
    partner_bits: int = 8     # width of the non-SR operand (saved fwd tensor)

    @property
    def sr_shape(self) -> Tuple[int, int]:
        """Shape of the operand the plan's SR quantizer rounds (always the
        incoming gradient dY): wgrad contracts over it -> (k, n); agrad
        carries it on the lhs -> (m, k)."""
        return (self.k, self.n) if self.role == "wgrad" else (self.m, self.k)

    def bytes_at(self, bits: int) -> float:
        if self.role == "wgrad":
            lhs, rhs = self.partner_bits, bits
        else:
            lhs, rhs = bits, self.partner_bits
        return gemm_bytes_moved(self.m, self.k, self.n, lhs, rhs) * self.mult


@dataclasses.dataclass(frozen=True)
class Candidate:
    quantizer: str
    bits: int
    variance: float           # predicted total Var (x scan multiplicity)
    bytes_moved: float        # bytes for the whole group (x multiplicity)


@dataclasses.dataclass(frozen=True)
class PlanEntry:
    path: str
    role: str
    quantizer: str
    bits: int
    variance: float
    bytes_moved: float


@dataclasses.dataclass(frozen=True)
class Plan:
    arch: str
    budget_bytes: float
    entries: Tuple[PlanEntry, ...]
    total_bytes: float
    total_variance: float
    baseline_bytes: float         # uniform 8-bit PTQ on every site
    baseline_variance: float
    solver: str                   # "greedy" | "dp"
    feasible: bool                # total_bytes <= budget_bytes

    def overrides(self) -> Dict[str, dict]:
        """``{pattern: {role: "name:bits"}}`` ready for
        ``QuantPolicy(overrides=...)`` — patterns are exact-match anchors
        over the layer path."""
        by_path: Dict[str, dict] = {}
        for e in self.entries:
            by_path.setdefault(f"^{re.escape(e.path)}$", {})[e.role] = \
                f"{e.quantizer}:{e.bits}"
        return by_path

    def to_dict(self) -> dict:
        return {
            "version": 1,
            "arch": self.arch,
            "solver": self.solver,
            "feasible": self.feasible,
            "budget_bytes": self.budget_bytes,
            "total_bytes": self.total_bytes,
            "predicted_variance": self.total_variance,
            "baseline": {"bytes": self.baseline_bytes,
                         "variance": self.baseline_variance,
                         "policy": "uniform ptq:8 on every gradient site"},
            "overrides": overrides_to_json(self.overrides()),
            "sites": [dataclasses.asdict(e) for e in self.entries],
        }

    def to_json(self, **kw) -> str:
        return json.dumps(self.to_dict(), indent=kw.pop("indent", 2), **kw)

    def format(self) -> str:
        lines = [f"== precision plan: {self.arch} ==",
                 f"budget {self.budget_bytes:.3e} B | plan "
                 f"{self.total_bytes:.3e} B | uniform-8 baseline "
                 f"{self.baseline_bytes:.3e} B",
                 f"predicted grad variance {self.total_variance:.4e} "
                 f"(baseline {self.baseline_variance:.4e}, "
                 f"{'-' if self.total_variance <= self.baseline_variance else '+'}"
                 f"{abs(1 - self.total_variance / max(self.baseline_variance, 1e-30)) * 100:.1f}%)"
                 f" | solver={self.solver}"
                 f"{'' if self.feasible else ' | OVER BUDGET'}"]
        lines.append(f"{'path':<28}{'role':<7}{'quant':<6}{'bits':>4}"
                     f"{'bytes':>12}{'variance':>12}")
        for e in sorted(self.entries, key=lambda e: (e.path, e.role)):
            lines.append(f"{e.path:<28}{e.role:<7}{e.quantizer:<6}"
                         f"{e.bits:>4}{e.bytes_moved:>12.3e}"
                         f"{e.variance:>12.4e}")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# Site collection (trace -> PlanSite list)
# ---------------------------------------------------------------------------

def collect_plan_sites(cfg, policy: QuantPolicy, *, batch_size: int = 2,
                       seq_len: int = 8) -> Tuple[PlanSite, ...]:
    """Trace ``cfg``'s loss gradient under ``policy`` and distill one
    :class:`PlanSite` per quantized (path, role) gradient scope — the
    max-FLOPs GEMM of the scope (satellite quantize/epilogue dots in the
    same scope ride along with its choice)."""
    from ..models.api import build_model
    from .audit import _loss_args

    model = build_model(cfg)
    params, batch = _loss_args(model, batch_size, seq_len)
    key = jax.random.PRNGKey(0)

    def loss_fn(p, b):
        loss, _ = model.loss(p, b, key, policy)
        return loss

    closed = jax.make_jaxpr(jax.grad(loss_fn))(params, batch)
    best: Dict[Tuple[str, str], object] = {}
    for s in iter_gemm_sites(closed):
        if s.kind != "quantized" or s.role not in _GRAD_ROLES:
            continue
        if s.m <= 0 or s.n <= 0:
            continue
        gk = (s.path or "?", s.role)
        if gk not in best or s.flops > best[gk].flops:
            best[gk] = s
    sites = []
    for (path, role), s in sorted(best.items()):
        resolved = policy.resolve(path)
        partner = resolved.fwd_act if role == "wgrad" else resolved.fwd_weight
        sites.append(PlanSite(
            path=path, role=role, m=s.m, k=s.contract, n=s.n, mult=s.mult,
            flops=s.flops,
            partner_bits=(partner.bits or 8) if partner is not None else 8))
    return tuple(sites)


# ---------------------------------------------------------------------------
# Candidates
# ---------------------------------------------------------------------------

def _variance_proxy(shape: Tuple[int, int], quantizer: str, bits: int,
                    **params) -> float:
    """Closed-form Var[Q_b(g)|g] on a fixed-seed Gaussian proxy of the SR
    operand, scaled to the true element count when the proxy is capped."""
    rows, cols = shape
    sr = min(rows, max(1, _SAMPLE_CAP // max(cols, 1)))
    if sr * cols > _SAMPLE_CAP and cols > _SAMPLE_CAP:
        cols_s = _SAMPLE_CAP
    else:
        cols_s = cols
    x = jax.random.normal(jax.random.PRNGKey(0), (sr, cols_s), jnp.float32)
    v = float(quantizer_variance(x, quantizer, bits, **params))
    return v * (rows * cols) / (sr * cols_s)


def site_candidates(site: PlanSite, policy: QuantPolicy) -> \
        Tuple[Candidate, ...]:
    """Pareto-pruned (variance, bytes) candidates for one site.

    wgrad is PTQ-only (``qt_gemm_tn`` needs per-tensor scales on both
    operands — per-row scales would sit on the contraction axis); agrad
    ranges over PTQ/PSQ/BHQ.  Widths are accumulator-safe per
    :func:`legal_widths`.
    """
    names = ("ptq",) if site.role == "wgrad" else ("ptq", "psq", "bhq")
    resolved = policy.resolve(site.path)
    base = getattr(resolved, site.role)
    block_rows = base.param("block_rows", policy.bhq_block) \
        if base is not None else policy.bhq_block
    cands: List[Candidate] = []
    for bits in legal_widths(site.role, site.k,
                             partner_bits=site.partner_bits):
        nbytes = site.bytes_at(bits)
        for name in names:
            params = {"block_rows": block_rows} if name == "bhq" else {}
            var = _variance_proxy(site.sr_shape, name, bits, **params) \
                * site.mult
            cands.append(Candidate(name, bits, var, nbytes))
    # Pareto prune: drop any candidate beaten (<= on both axes, < on one)
    kept = [c for c in cands
            if not any((o.variance <= c.variance and
                        o.bytes_moved <= c.bytes_moved and
                        (o.variance < c.variance or
                         o.bytes_moved < c.bytes_moved))
                       for o in cands)]
    kept.sort(key=lambda c: (-c.bytes_moved, c.variance))
    return tuple(kept)


# ---------------------------------------------------------------------------
# Solvers
# ---------------------------------------------------------------------------

def _solve_greedy(tables: Sequence[Sequence[Candidate]],
                  budget: float) -> Tuple[List[int], bool]:
    """Start every site at its min-variance candidate, then repeatedly take
    the downgrade with the smallest marginal variance per byte saved until
    the plan fits."""
    choice = [min(range(len(t)), key=lambda j: (t[j].variance,
                                                t[j].bytes_moved))
              for t in tables]
    total = sum(t[c].bytes_moved for t, c in zip(tables, choice))
    while total > budget:
        best = None                   # (slope, i, j)
        for i, t in enumerate(tables):
            cur = t[choice[i]]
            for j, c in enumerate(t):
                saved = cur.bytes_moved - c.bytes_moved
                if saved <= 0:
                    continue
                slope = (c.variance - cur.variance) / saved
                if best is None or slope < best[0]:
                    best = (slope, i, j)
        if best is None:
            return choice, False      # nothing left to shrink: over budget
        _, i, j = best
        total -= tables[i][choice[i]].bytes_moved - tables[i][j].bytes_moved
        choice[i] = j
    return choice, True


def _solve_dp(tables: Sequence[Sequence[Candidate]], budget: float,
              resolution: int = 2048) -> Optional[List[int]]:
    """Exact multiple-choice knapsack on a discretized byte axis (each
    site's cost rounds *up* one unit, so the result never overshoots the
    real budget).  Returns None when infeasible at this resolution."""
    unit = max(1.0, budget / resolution)
    cap = int(budget // unit)
    inf = math.inf
    # dp[u] = (min variance using <= u units, back-pointers)
    var = [0.0] + [inf] * cap
    back: List[List[Optional[Tuple[int, int]]]] = \
        [[None] * (cap + 1)]
    for t in tables:
        nvar = [inf] * (cap + 1)
        nback: List[Optional[Tuple[int, int]]] = [None] * (cap + 1)
        costs = [int(math.ceil(c.bytes_moved / unit)) for c in t]
        for u in range(cap + 1):
            if var[u] is inf:
                continue
            for j, cu in enumerate(costs):
                u2 = u + cu
                if u2 > cap:
                    continue
                v2 = var[u] + t[j].variance
                if v2 < nvar[u2]:
                    nvar[u2] = v2
                    nback[u2] = (u, j)
        var = nvar
        back.append(nback)
    best_u = min((u for u in range(cap + 1) if var[u] is not inf),
                 key=lambda u: var[u], default=None)
    if best_u is None:
        return None
    choice: List[int] = []
    u = best_u
    for i in range(len(tables), 0, -1):
        prev_u, j = back[i][u]
        choice.append(j)
        u = prev_u
    choice.reverse()
    return choice


def plan_model(cfg, policy: Optional[QuantPolicy] = None, *,
               budget_bytes: Optional[float] = None,
               budget_frac: Optional[float] = None,
               batch_size: int = 2, seq_len: int = 8,
               solver: str = "auto") -> Plan:
    """Plan per-site gradient precision for ``cfg`` under a bytes budget.

    ``policy`` supplies the forward widths and BHQ block size the candidates
    assume (default: uniform 8-bit FQT).  The budget defaults to the
    uniform-8-bit plan's bytes (``budget_frac`` scales it; ``budget_bytes``
    overrides it outright) — at that default the planner must *beat* uniform
    variance at equal bytes, which is the paper's Sec. 4 claim.
    """
    if solver not in ("auto", "greedy", "dp"):
        raise ValueError(f"unknown solver {solver!r}")
    policy = policy or QuantPolicy.fqt("ptq", 8)
    sites = collect_plan_sites(cfg, policy, batch_size=batch_size,
                               seq_len=seq_len)
    if not sites:
        raise ValueError(
            f"no quantized gradient GEMMs found for {cfg.name!r} under this "
            f"policy — is the backward quantized (FQT, not QAT/exact)?")
    tables = [site_candidates(s, policy) for s in sites]

    # uniform 8-bit PTQ baseline (the paper's default recipe)
    base_b = base_v = 0.0
    for s, t in zip(sites, tables):
        cand = next((c for c in t if c.quantizer == "ptq" and c.bits == 8),
                    None)
        base_b += s.bytes_at(8)
        base_v += cand.variance if cand is not None else \
            _variance_proxy(s.sr_shape, "ptq", 8) * s.mult
    budget = float(budget_bytes) if budget_bytes is not None else \
        base_b * (budget_frac if budget_frac is not None else 1.0)

    g_choice, g_ok = _solve_greedy(tables, budget)
    choice, used, ok = g_choice, "greedy", g_ok
    if solver in ("auto", "dp") and len(sites) <= 32:
        d_choice = _solve_dp(tables, budget)
        if d_choice is not None:
            d_var = sum(t[j].variance for t, j in zip(tables, d_choice))
            g_var = sum(t[j].variance for t, j in zip(tables, g_choice))
            if solver == "dp" or not g_ok or d_var < g_var:
                choice, used, ok = d_choice, "dp", True
        elif solver == "dp":
            used = "dp"

    entries = tuple(
        PlanEntry(path=s.path, role=s.role, quantizer=t[j].quantizer,
                  bits=t[j].bits, variance=t[j].variance,
                  bytes_moved=t[j].bytes_moved)
        for s, t, j in zip(sites, tables, choice))
    total_b = sum(e.bytes_moved for e in entries)
    total_v = sum(e.variance for e in entries)
    return Plan(arch=cfg.name, budget_bytes=budget, entries=entries,
                total_bytes=total_b, total_variance=total_v,
                baseline_bytes=base_b, baseline_variance=base_v,
                solver=used, feasible=ok and total_b <= budget * (1 + 1e-9))
