"""Statistical-soundness verifier: are the FQT gradients actually unbiased?

The paper's central result (Theorem 1) — the FQT gradient is an unbiased
estimator of the QAT gradient — holds only under preconditions the
contract auditor (analysis/audit.py) never checks:

  1. every gradient-path quantization rounds **stochastically**,
  2. the SR draws are **independent** across sites and across microbatch /
     chunk / layer folds (distinct PRNG streams),
  3. nothing **re-quantizes an already-quantized tensor** (the second
     round adds variance the Eq. 8 budget never sees — and is biased
     whenever it rounds deterministically),
  4. the **forward** pass rounds deterministically (SR there adds variance
     with no bias to fix, paper Sec. 2.1).

This module checks all four *statically*, by abstract interpretation over
the traced jaxpr.  The interpreter assigns every intermediate an abstract
value carrying

  * **key lineage** — a symbolic expression over ``random_fold_in`` /
    ``random_split`` / slice chains rooted at the trace inputs, so two SR
    draws with structurally equal lineage provably consume the same key;
  * **loop variance** — the set of enclosing ``scan`` s whose iteration
    the value depends on (via carry or xs), so a key that is constant
    across a length->1 scan (microbatch accumulation, the layer stack, the
    chunked head loss) is detected as a reused stream;
  * **randomness taint** — which ``random_bits`` draws feed the value, so
    ``floor`` is classified SR vs deterministic;
  * **quantization taint** — whether the value is an affine/elementwise
    image of a quantizer's rounded codes (propagated only through
    value-preserving ops and scalar-ish affine factors; any GEMM or
    reduction clears it), so quantize-of-dequant chains are detected.

Rounding events are attributed to ``q[path|role]`` markers exactly like
the GEMM walk (analysis/graph.py); the ``qk[path]`` key-derivation marker
(core/exempt.py) attributes lineage findings that occur before a role
scope opens.  Everything runs at trace time — no device, no parameters.

Rules (all severity "error"):

  SND001  deterministic rounding on a wgrad/agrad path: a quantized
          gradient-role scope whose rounds are all deterministic.
  SND002  SR key aliasing: two SR draws with identical key lineage
          (or one uniform tensor consumed by two rounds).
  SND003  scan-invariant SR key: an SR draw inside a scan of length > 1
          whose key lineage does not vary with the iteration — the same
          noise is replayed every microbatch/chunk/layer.
  SND004  double quantization: a quantizer round whose input is already
          an affine image of another quantizer's codes.
  SND005  stochastic rounding in the forward pass.

``soundness_selftest`` proves the pass has teeth by mutating the live
quantizer registry / key plumbing (det-rounded agrad, aliased SR keys,
quantize-of-dequant, SR forward) and asserting each mutation turns the
pass red naming the offending site — mirroring PR 7's red/green pattern.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, List, Optional, Sequence, Tuple

import jax

from ..core.exempt import KEY_SCOPE_RE
from .graph import classify_stack

try:
    from jax.extend.core import Literal as _Literal
except ImportError:                                   # pragma: no cover
    from jax.core import Literal as _Literal

__all__ = ["SoundnessFinding", "SoundnessReport", "check_soundness_fn",
           "check_model", "check_step", "soundness_selftest",
           "SoundnessSelftest"]

_GRAD_ROLES = ("wgrad", "agrad")

# ops through which a value keeps its identity (key lineage) and its
# quantization taint: pure layout / dtype changes
_PRESERVE = ("convert_element_type", "copy", "reshape", "squeeze",
             "expand_dims", "broadcast_in_dim", "transpose", "rev",
             "reduce_precision")

# ops that clear randomness AND quantization taint: the output is a
# contraction/selection over many inputs, not an affine image of one
_KILL = ("dot_general", "conv_general_dilated", "reduce_sum", "reduce_max",
         "reduce_min", "reduce_prod", "reduce_and", "reduce_or", "argmax",
         "argmin", "sort", "cumsum", "cumprod", "cummax", "cummin",
         "gather", "scatter", "scatter_add")


@dataclasses.dataclass
class _AVal:
    """Abstract value of one jaxpr intermediate."""

    lineage: tuple                  # symbolic identity (hashable)
    varies: frozenset = frozenset()   # ids of enclosing scans it varies with
    rand: frozenset = frozenset()     # BitsEvent ids it depends on
    taint: frozenset = frozenset()    # quantizer sites whose codes it images


@dataclasses.dataclass(frozen=True)
class _BitsEvent:
    """One ``random_bits`` draw (the uniform behind one SR round)."""

    eid: int
    lineage: tuple                  # key operand lineage
    varies: frozenset               # key operand loop-variance
    site: str                       # "path|role" / "path|qk" / "?"
    src: str
    scans: Tuple[Tuple[int, int], ...]   # enclosing (scan_id, length)


@dataclasses.dataclass(frozen=True)
class _RoundEvent:
    """One ``floor``/``round`` equation."""

    sr: bool                        # input depends on random bits
    kind: str                       # marker kind ("quantized"/"policy_fp"/..)
    path: str
    role: Optional[str]
    src: str
    bits: frozenset                 # BitsEvent ids feeding the input
    tainted_by: frozenset           # quantizer sites already imaged in input

    @property
    def site(self) -> str:
        return f"{self.path}|{self.role}" if self.role else (self.path or "?")


@dataclasses.dataclass(frozen=True)
class SoundnessFinding:
    rule: str                # SND001..SND005
    severity: str            # "error"
    path: str
    role: Optional[str]
    detail: str
    src: str

    def __str__(self):
        role = f"|{self.role}" if self.role else ""
        return f"[{self.rule}] {self.path}{role} ({self.src}): {self.detail}"


@dataclasses.dataclass(frozen=True)
class SoundnessReport:
    title: str
    findings: Tuple[SoundnessFinding, ...]
    n_sr_rounds: int         # stochastic rounding events in the graph
    n_det_rounds: int        # deterministic rounding events
    n_streams: int           # distinct SR key lineages
    n_grad_scopes: int       # quantized wgrad/agrad scopes seen

    @property
    def ok(self) -> bool:
        return not self.findings

    def format(self, verbose: bool = False) -> str:
        lines = [f"== soundness: {self.title} ==",
                 f"rounding events: {self.n_sr_rounds} stochastic / "
                 f"{self.n_det_rounds} deterministic; "
                 f"{self.n_streams} distinct SR key streams across "
                 f"{self.n_grad_scopes} quantized gradient scopes"]
        if self.findings:
            lines.append(f"VIOLATIONS ({len(self.findings)}):")
            lines.extend(f"  {f}" for f in self.findings)
        else:
            lines.append("soundness: OK (unbiasedness preconditions hold)")
        return "\n".join(lines)

    def to_dict(self) -> dict:
        return {
            "title": self.title, "ok": self.ok,
            "counters": {"sr_rounds": self.n_sr_rounds,
                         "det_rounds": self.n_det_rounds,
                         "sr_streams": self.n_streams,
                         "grad_scopes": self.n_grad_scopes},
            "findings": [dataclasses.asdict(f) for f in self.findings],
        }


# ---------------------------------------------------------------------------
# The abstract interpreter
# ---------------------------------------------------------------------------

def _src_of(eqn) -> str:
    try:
        for frame in eqn.source_info.traceback.frames:
            fn = frame.file_name
            if "/jax/" in fn or "site-packages" in fn or fn.startswith("<"):
                continue
            return f"{fn}:{frame.start_line}"
    except Exception:
        pass
    return "?"


def _site_of(stack: str) -> Tuple[str, str, Optional[str], str]:
    """(kind, path, role, site-string) from a full name-stack string.

    Falls back to the ``qk[path]`` key-derivation marker when no
    ``q``/``qfp``/``fp`` marker encloses the equation.
    """
    kind, path, role = classify_stack(stack)
    if kind == "unmarked":
        qk = None
        for m in KEY_SCOPE_RE.finditer(stack):
            qk = m
        if qk is not None:
            return "keyscope", qk.group(1), None, f"{qk.group(1)}|qk"
    site = f"{path}|{role}" if role else (path or "?")
    return kind, path or "?", role, site


class _Interp:
    def __init__(self):
        self._ids = itertools.count()
        self.bits: Dict[int, _BitsEvent] = {}
        self.rounds: List[_RoundEvent] = []

    # -- env helpers -----------------------------------------------------
    def fresh(self, tag: str = "op") -> tuple:
        return (tag, next(self._ids))

    def read(self, env, atom) -> _AVal:
        if isinstance(atom, _Literal):
            val = atom.val
            try:
                key = val.item() if hasattr(val, "item") else val
                hash(key)
            except Exception:
                key = None
            return _AVal(lineage=("lit", key))
        try:
            return env[atom]
        except KeyError:
            # unbound var (shouldn't happen; be forgiving in an analyzer)
            av = _AVal(lineage=self.fresh("unbound"))
            env[atom] = av
            return av

    # -- interprocedural run --------------------------------------------
    def run_closed(self, closed, in_avals, prefix, scans) -> List[_AVal]:
        jaxpr = getattr(closed, "jaxpr", closed)
        env: Dict[object, _AVal] = {}
        for cv in jaxpr.constvars:
            env[cv] = _AVal(lineage=self.fresh("const"))
        if len(jaxpr.invars) != len(in_avals):
            # arity mismatch (consts folded differently than expected):
            # degrade gracefully to fresh roots rather than crash the pass
            in_avals = [_AVal(lineage=self.fresh("arg"))
                        for _ in jaxpr.invars]
        for v, av in zip(jaxpr.invars, in_avals, strict=True):
            env[v] = av
        self.run_eqns(jaxpr, env, prefix, scans)
        return [self.read(env, v) for v in jaxpr.outvars]

    def run_eqns(self, jaxpr, env, prefix, scans) -> None:
        for eqn in jaxpr.eqns:
            stack = str(eqn.source_info.name_stack)
            full = (f"{prefix}/{stack}" if prefix and stack
                    else (prefix or stack))
            self.eqn(eqn, env, full, scans)

    # -- one equation ----------------------------------------------------
    def eqn(self, eqn, env, full, scans) -> None:
        prim = eqn.primitive.name
        ins = [self.read(env, a) for a in eqn.invars]
        varies = frozenset().union(*(a.varies for a in ins)) if ins \
            else frozenset()
        rand = frozenset().union(*(a.rand for a in ins)) if ins \
            else frozenset()

        handler = getattr(self, f"_p_{prim}", None)
        if handler is not None:
            handler(eqn, env, ins, full, scans, varies, rand)
            return
        if prim in ("jit", "closed_call", "core_call", "remat2",
                    "checkpoint", "custom_jvp_call", "custom_vjp_call",
                    "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr"):
            self._call_like(eqn, env, ins, full, scans)
            return
        if prim == "pallas_call":
            self._pallas(eqn, env, ins, full, scans, varies, rand)
            return
        if prim in _PRESERVE and len(ins) == 1:
            env[eqn.outvars[0]] = _AVal(lineage=ins[0].lineage, varies=varies,
                                        rand=rand, taint=ins[0].taint)
            return
        if prim == "slice" and len(ins) == 1:
            start = tuple(int(s) for s in eqn.params.get("start_indices", ()))
            env[eqn.outvars[0]] = _AVal(
                lineage=("at", ins[0].lineage, start), varies=varies,
                rand=rand, taint=ins[0].taint)
            return
        kill = prim in _KILL
        taint = (frozenset() if kill or not ins
                 else frozenset().union(*(a.taint for a in ins)))
        for ov in eqn.outvars:
            env[ov] = _AVal(
                lineage=self.fresh(), varies=varies,
                rand=frozenset() if kill else rand, taint=taint)

    # -- PRNG primitives -------------------------------------------------
    def _p_random_wrap(self, eqn, env, ins, full, scans, varies, rand):
        env[eqn.outvars[0]] = _AVal(lineage=ins[0].lineage, varies=varies,
                                    rand=rand, taint=frozenset())

    _p_random_unwrap = _p_random_wrap

    def _p_random_fold_in(self, eqn, env, ins, full, scans, varies, rand):
        key_l = ins[0].lineage
        data_l = ins[1].lineage if len(ins) > 1 else ("lit", None)
        env[eqn.outvars[0]] = _AVal(lineage=("fold", key_l, data_l),
                                    varies=varies, rand=rand)

    def _p_random_split(self, eqn, env, ins, full, scans, varies, rand):
        env[eqn.outvars[0]] = _AVal(lineage=("split", ins[0].lineage),
                                    varies=varies, rand=rand)

    def _p_random_bits(self, eqn, env, ins, full, scans, varies, rand):
        eid = next(self._ids)
        _kind, _path, _role, site = _site_of(full)
        self.bits[eid] = _BitsEvent(
            eid=eid, lineage=ins[0].lineage, varies=ins[0].varies, site=site,
            src=_src_of(eqn),
            scans=tuple((sid, ln) for sid, ln in scans if ln > 1))
        env[eqn.outvars[0]] = _AVal(lineage=self.fresh("bits"),
                                    varies=varies, rand=frozenset({eid}))

    def _p_random_seed(self, eqn, env, ins, full, scans, varies, rand):
        env[eqn.outvars[0]] = _AVal(lineage=("seed", ins[0].lineage),
                                    varies=varies, rand=rand)

    # -- rounding --------------------------------------------------------
    def _round_event(self, eqn, env, ins, full, det: bool):
        kind, path, role, _site = _site_of(full)
        sr = bool(ins[0].rand) and not det
        self.rounds.append(_RoundEvent(
            sr=sr, kind=kind, path=path, role=role, src=_src_of(eqn),
            bits=ins[0].rand, tainted_by=ins[0].taint))
        taint = ins[0].taint
        if kind == "quantized":
            taint = taint | {f"{path}|{role}" if role else path}
        env[eqn.outvars[0]] = _AVal(lineage=self.fresh("round"),
                                    varies=ins[0].varies, rand=ins[0].rand,
                                    taint=taint)

    def _p_floor(self, eqn, env, ins, full, scans, varies, rand):
        self._round_event(eqn, env, ins, full, det=False)

    def _p_round(self, eqn, env, ins, full, scans, varies, rand):
        self._round_event(eqn, env, ins, full, det=True)

    # -- higher-order ----------------------------------------------------
    def _call_like(self, eqn, env, ins, full, scans) -> None:
        for pname in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
            sub = eqn.params.get(pname)
            if sub is None:
                continue
            jaxpr = getattr(sub, "jaxpr", sub)
            if len(jaxpr.invars) != len(ins):
                continue
            outs = self.run_closed(sub, ins, full, scans)
            if len(outs) == len(eqn.outvars):
                for ov, av in zip(eqn.outvars, outs, strict=True):
                    env[ov] = av
                return
        self._opaque(eqn, env, ins)

    def _opaque(self, eqn, env, ins) -> None:
        varies = frozenset().union(*(a.varies for a in ins)) if ins \
            else frozenset()
        rand = frozenset().union(*(a.rand for a in ins)) if ins \
            else frozenset()
        for ov in eqn.outvars:
            env[ov] = _AVal(lineage=self.fresh("opaque"), varies=varies,
                            rand=rand)

    def _p_scan(self, eqn, env, ins, full, scans, varies, rand):
        closed = eqn.params["jaxpr"]
        body = getattr(closed, "jaxpr", closed)
        n_consts = int(eqn.params.get("num_consts", 0))
        n_carry = int(eqn.params.get("num_carry", 0))
        length = int(eqn.params.get("length", 1))
        sid = next(self._ids)
        body_in: List[_AVal] = []
        for i, av in enumerate(ins):
            if i < n_consts:
                body_in.append(av)
            elif i < n_consts + n_carry:
                body_in.append(_AVal(lineage=("carry", sid, i),
                                     varies=av.varies | {sid},
                                     rand=av.rand))
            else:
                body_in.append(_AVal(lineage=("xs", sid, av.lineage),
                                     varies=av.varies | {sid},
                                     rand=av.rand))
        outs = self.run_closed(closed, body_in, full,
                               scans + ((sid, length),))
        # scan outputs keep a lineage derived from the body outvar's, so two
        # outputs stacking the *same* body value (e.g. the per-site SR keys
        # the forward scan saves as residuals for the backward scan) stay
        # provably equal across the scan boundary.  Final-carry outputs and
        # stacked-ys outputs are distinct value classes even for one body
        # outvar, hence the separate tags.
        for j, ov in enumerate(eqn.outvars):
            if j < len(outs):
                o = outs[j]
                tag = "scanfin" if j < n_carry else "scanstack"
                env[ov] = _AVal(lineage=(tag, sid, o.lineage), varies=varies,
                                rand=rand | o.rand)
            else:
                env[ov] = _AVal(lineage=self.fresh("scan_out"),
                                varies=varies, rand=rand)

    def _p_while(self, eqn, env, ins, full, scans, varies, rand):
        body = eqn.params.get("body_jaxpr")
        cn = int(eqn.params.get("cond_nconsts", 0))
        bn = int(eqn.params.get("body_nconsts", 0))
        if body is not None:
            bj = getattr(body, "jaxpr", body)
            carry_ins = ins[cn + bn:]
            body_in = list(ins[cn:cn + bn]) + [
                _AVal(lineage=("wcarry", next(self._ids)),
                      varies=a.varies, rand=a.rand) for a in carry_ins]
            if len(bj.invars) == len(body_in):
                self.run_closed(body, body_in, full, scans)
        self._opaque(eqn, env, ins)

    def _p_cond(self, eqn, env, ins, full, scans, varies, rand):
        branch_rand = frozenset()
        for br in eqn.params.get("branches", ()):
            bj = getattr(br, "jaxpr", br)
            if len(bj.invars) == len(ins) - 1:
                outs = self.run_closed(br, ins[1:], full, scans)
                branch_rand |= frozenset().union(
                    *(o.rand for o in outs)) if outs else frozenset()
        for ov in eqn.outvars:
            env[ov] = _AVal(lineage=self.fresh("cond_out"), varies=varies,
                            rand=rand | branch_rand)

    def _pallas(self, eqn, env, ins, full, scans, varies, rand):
        """Opaque kernel heuristic: a Pallas kernel whose body floors and
        whose operands carry random bits is one fused SR round; the exact
        ref dataflow inside the kernel is not interpreted."""
        kernel = eqn.params.get("jaxpr")
        prims = set()

        def collect(j):
            jx = getattr(j, "jaxpr", j)
            for e in getattr(jx, "eqns", ()):
                prims.add(e.primitive.name)
                for v in e.params.values():
                    if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
                        collect(v)

        if kernel is not None:
            collect(kernel)
        kind, path, role, _site = _site_of(full)
        if "floor" in prims or "round" in prims:
            self.rounds.append(_RoundEvent(
                sr=bool(rand) and "floor" in prims, kind=kind, path=path,
                role=role, src=_src_of(eqn), bits=rand,
                tainted_by=frozenset().union(*(a.taint for a in ins))
                if ins else frozenset()))
        for ov in eqn.outvars:
            env[ov] = _AVal(lineage=self.fresh("pallas"), varies=varies)


# ---------------------------------------------------------------------------
# Rule evaluation
# ---------------------------------------------------------------------------

def _evaluate(interp: _Interp, title: str) -> SoundnessReport:
    findings: List[SoundnessFinding] = []

    # SND001: quantized gradient scope with only deterministic rounds
    scopes: Dict[Tuple[str, str], List[_RoundEvent]] = {}
    for ev in interp.rounds:
        if ev.kind == "quantized" and ev.role in _GRAD_ROLES:
            scopes.setdefault((ev.path, ev.role), []).append(ev)
    for (path, role), evs in sorted(scopes.items()):
        if not any(e.sr for e in evs):
            findings.append(SoundnessFinding(
                "SND001", "error", path, role,
                f"all {len(evs)} rounding op(s) in this quantized "
                f"gradient scope are deterministic — the {role} "
                f"quantization is biased (Theorem 1 needs stochastic "
                f"rounding on every gradient path)", evs[0].src))

    # SND002a: two SR draws with identical key lineage
    sr_bits = [interp.bits[b] for ev in interp.rounds if ev.sr
               for b in sorted(ev.bits) if b in interp.bits]
    seen_ids = set()
    by_lineage: Dict[tuple, List[_BitsEvent]] = {}
    for be in sr_bits:
        if be.eid in seen_ids:
            continue
        seen_ids.add(be.eid)
        by_lineage.setdefault(be.lineage, []).append(be)
    for lineage, group in sorted(by_lineage.items(),
                                 key=lambda kv: str(kv[0])):
        if len(group) > 1:
            sites = sorted({b.site for b in group})
            path = sites[0].split("|")[0]
            findings.append(SoundnessFinding(
                "SND002", "error", path, None,
                f"{len(group)} SR draws share one PRNG key (identical "
                f"fold_in/split lineage) across sites {sites} — their "
                f"rounding noise is correlated, breaking the independence "
                f"Theorem 1 assumes", group[0].src))

    # SND002b: one uniform tensor consumed by several rounding ops
    uses: Dict[int, List[_RoundEvent]] = {}
    for ev in interp.rounds:
        if not ev.sr:
            continue
        for b in ev.bits:
            uses.setdefault(b, []).append(ev)
    for eid, evs in sorted(uses.items()):
        direct = [e for e in evs if not e.tainted_by]
        if len(direct) > 1:
            sites = sorted({e.site for e in direct})
            findings.append(SoundnessFinding(
                "SND002", "error", sites[0].split("|")[0], None,
                f"one random_bits tensor feeds {len(direct)} rounding ops "
                f"at sites {sites} — SR draws must be fresh per tensor",
                direct[0].src))

    # SND003: SR key constant across an enclosing scan
    for be in sorted({b.eid for b in sr_bits}):
        ev = interp.bits[be]
        for sid, length in ev.scans:
            if sid not in ev.varies:
                path, _, role = ev.site.partition("|")
                findings.append(SoundnessFinding(
                    "SND003", "error", path, role or None,
                    f"SR key lineage is invariant across the {length} "
                    f"iterations of an enclosing scan — identical "
                    f"quantization noise is replayed every iteration "
                    f"(microbatch/chunk/layer fold reuse)", ev.src))
                break

    # SND004: quantize-of-dequant
    for ev in interp.rounds:
        if ev.kind == "quantized" and ev.tainted_by:
            findings.append(SoundnessFinding(
                "SND004", "error", ev.path, ev.role,
                f"double quantization: this round's input is already an "
                f"affine image of quantized codes from "
                f"{sorted(ev.tainted_by)} — re-quantizing adds variance "
                f"outside the Eq. 8 budget (and bias when deterministic)",
                ev.src))

    # SND005: stochastic rounding in the forward pass
    for ev in interp.rounds:
        if ev.kind == "quantized" and ev.role == "fwd" and ev.sr:
            findings.append(SoundnessFinding(
                "SND005", "error", ev.path, "fwd",
                "stochastic rounding in the forward pass — forward "
                "quantizers must be deterministic (SR here adds variance "
                "with no bias to correct, paper Sec. 2.1)", ev.src))

    n_sr = sum(1 for e in interp.rounds if e.sr)
    n_det = len(interp.rounds) - n_sr
    streams = {interp.bits[b].lineage for e in interp.rounds if e.sr
               for b in e.bits if b in interp.bits}
    return SoundnessReport(
        title=title, findings=tuple(findings), n_sr_rounds=n_sr,
        n_det_rounds=n_det, n_streams=len(streams),
        n_grad_scopes=len(scopes))


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

def check_soundness_fn(fn, args, title: str = "fn") -> SoundnessReport:
    """Trace ``fn(*args)`` (args may be ShapeDtypeStructs) and verify the
    unbiasedness preconditions over the resulting jaxpr."""
    closed = jax.make_jaxpr(fn)(*args)
    interp = _Interp()
    roots = [_AVal(lineage=("arg", i)) for i in range(len(closed.jaxpr.invars))]
    env: Dict[object, _AVal] = {}
    for i, cv in enumerate(closed.jaxpr.constvars):
        env[cv] = _AVal(lineage=("const", i))
    for v, av in zip(closed.jaxpr.invars, roots, strict=True):
        env[v] = av
    interp.run_eqns(closed.jaxpr, env, "", ())
    return _evaluate(interp, title)


def check_model(cfg, policy, *, grad: bool = True, batch_size: int = 2,
                seq_len: int = 8, title: Optional[str] = None,
                loss_kwargs: Optional[dict] = None) -> SoundnessReport:
    """Soundness-check ``cfg``'s training graph under ``policy`` (loss fwd
    plus bwd when ``grad``).  Pure tracing, same harness as audit_model."""
    from ..models.api import build_model
    from .audit import _loss_args

    model = build_model(cfg)
    params, batch = _loss_args(model, batch_size, seq_len)
    key = jax.random.PRNGKey(0)
    kw = dict(loss_kwargs or {})

    def loss_fn(p, b):
        loss, _ = model.loss(p, b, key, policy, **kw)
        return loss

    fn = jax.grad(loss_fn) if grad else loss_fn
    return check_soundness_fn(
        fn, (params, batch),
        title=title or f"{cfg.name} [{policy.backend}"
                       f"{'' if grad else ', fwd-only'}]")


def check_step(cfg, policy, *, batch_size: int = 2, seq_len: int = 8,
               accum_steps: int = 2,
               title: Optional[str] = None) -> SoundnessReport:
    """Soundness-check a full engine step (engine/step.py) — the default
    ``accum_steps=2`` puts the microbatch ``fold_in`` keys inside a real
    accumulation scan so SND003 has something to check."""
    import jax.numpy as jnp

    from ..engine import TrainState, make_step_fn
    from ..models.api import build_model
    from ..optim import adamw, cosine_schedule
    from .audit import _loss_args

    model = build_model(cfg)
    opt = adamw()
    step_fn = make_step_fn(model, policy, opt, cosine_schedule(1e-3, 10),
                           remat=False, accum_steps=accum_steps)
    params, batch = _loss_args(model, batch_size * accum_steps, seq_len)
    state = jax.eval_shape(
        lambda p: TrainState(params=p, opt_state=opt.init(p),
                             step=jnp.zeros((), jnp.int32),
                             rng=jax.random.PRNGKey(0)), params)
    return check_soundness_fn(
        step_fn, (state, batch),
        title=title or f"{cfg.name} engine step "
                       f"[{policy.backend}, accum={accum_steps}]")


# ---------------------------------------------------------------------------
# Mutation self-test
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SoundnessSelftest:
    ok: bool
    detail: str
    clean: SoundnessReport
    mutated: Dict[str, SoundnessReport]


def _expect(report: SoundnessReport, rule: str, problems: List[str],
            mutation: str) -> None:
    hits = [f for f in report.findings if f.rule == rule]
    if not hits:
        problems.append(f"{mutation}: no {rule} finding "
                        f"(got {sorted({f.rule for f in report.findings})})")
    elif all(f.path in ("?", "") for f in hits):
        problems.append(f"{mutation}: {rule} fired but names no layer path")


def soundness_selftest(cfg, policy) -> SoundnessSelftest:
    """Four registry/plumbing mutations, each of which must turn the pass
    red with the matching rule naming a real site, while the unmutated
    graph stays green:

      det-agrad       swap the agrad quantizer's SR for round-to-nearest
      aliased-keys    make ``qkey`` ignore its per-site tag
      double-quant    re-quantize the agrad quantizer's own dequant
      sr-forward      give the forward quantizer a stochastic round
    """
    import importlib

    import jax.numpy as jnp

    from ..core.quantizers import quantize_ptq_det, quantize_ptq_stoch
    from ..core.registry import Quantizer, get_quantizer, register_quantizer
    from ..models.api import model_quant_paths

    paths = model_quant_paths(cfg)
    agrad_spec = policy.resolve(paths[0]).agrad
    if agrad_spec is None:
        raise ValueError("soundness_selftest needs an FQT policy "
                         "(the agrad role must be quantized)")
    aname = agrad_spec.name
    common = importlib.import_module(
        ".layers.common", package=__package__.rsplit(".", 1)[0])

    class _DetAgrad(Quantizer):
        name = aname
        stochastic = True          # still receives the key; ignores it

        def quantize(self, x2d, key, spec, *, backend, interpret=None):
            return quantize_ptq_det(x2d, spec.bits or 8)

    class _DoubleQuant(Quantizer):
        name = aname
        stochastic = True

        def quantize(self, x2d, key, spec, *, backend, interpret=None):
            inner = quantize_ptq_stoch(x2d, key, spec.bits or 8)
            return quantize_ptq_stoch(inner.dequant(),
                                      jax.random.fold_in(key, 1),
                                      spec.bits or 8)

    class _StochFwd(Quantizer):
        name = "ptq_det"
        stochastic = False         # fwd roles pass key=None; derive one

        def quantize(self, x2d, key, spec, *, backend, interpret=None):
            kk = jax.random.fold_in(jax.random.PRNGKey(0),
                                    x2d.ravel()[0].astype(jnp.int32))
            return quantize_ptq_stoch(x2d, kk, spec.bits or 8)

    clean = check_model(cfg, policy)
    problems: List[str] = []
    if not clean.ok:
        problems.append(
            "unmutated graph is red: "
            + "; ".join(str(f) for f in clean.findings[:3]))
    if clean.n_sr_rounds == 0:
        problems.append("unmutated graph shows no SR rounds — the policy "
                        "quantizes no gradients, nothing to verify")

    mutated: Dict[str, SoundnessReport] = {}

    def with_quantizer(qname, impostor, mutation):
        orig = get_quantizer(qname)
        register_quantizer(qname, impostor, overwrite=True)
        try:
            rep = check_model(cfg, policy,
                              title=f"{cfg.name} MUTATED({mutation})")
        finally:
            register_quantizer(qname, orig, overwrite=True)
        mutated[mutation] = rep
        return rep

    _expect(with_quantizer(aname, _DetAgrad(), "det-agrad"),
            "SND001", problems, "det-agrad")
    _expect(with_quantizer(aname, _DoubleQuant(), "double-quant"),
            "SND004", problems, "double-quant")
    _expect(with_quantizer("ptq_det", _StochFwd(), "sr-forward"),
            "SND005", problems, "sr-forward")

    real_qkey = common.qkey
    common.qkey = lambda key, tag: jax.random.fold_in(key, 0)
    try:
        rep = check_model(cfg, policy, title=f"{cfg.name} MUTATED(aliased)")
    finally:
        common.qkey = real_qkey
    mutated["aliased-keys"] = rep
    _expect(rep, "SND002", problems, "aliased-keys")

    ok = not problems
    detail = ("soundness self-test OK: det-agrad->SND001, "
              "aliased-keys->SND002, double-quant->SND004, "
              "sr-forward->SND005 all turn the pass red naming a site; "
              "clean graph green"
              if ok else "; ".join(problems))
    return SoundnessSelftest(ok=ok, detail=detail, clean=clean,
                             mutated=mutated)
