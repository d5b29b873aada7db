"""CLI: ``python -m repro.analysis {audit|soundness|plan|lint}``.

Exit status is the contract: 0 = clean, 1 = violations — CI gates on it
(.github/workflows/ci.yml ``analysis`` job).  Everything runs on CPU at
trace time; no accelerator, no parameter materialization.  Every
subcommand accepts ``--format json`` for machine-readable findings
(rule id, path, severity); human text stays the default.

  audit      jaxpr-level quantization-contract audit of one or more
             configs under a policy; ``--selftest`` additionally runs the
             mutation self-test (a deliberately leaked GEMM must turn the
             audit red); ``--step`` audits the full engine step.
  soundness  statistical-soundness verifier: abstract interpretation of
             the traced graph checking the Theorem 1 unbiasedness
             preconditions — SR on every gradient path, independent SR
             key streams (no aliasing, no scan-invariant reuse), no
             double quantization, deterministic forward.  ``--selftest``
             mutates the quantizer registry / key plumbing and asserts
             each mutation turns the pass red naming the site.
  plan       variance-budget precision planner: per-site (variance,
             bytes) candidates from the closed-form quantizer variances
             + the bench bytes-moved model, solved under ``--budget-bytes``
             (greedy + exact DP); writes QuantPolicy.overrides JSON for
             ``launch/train.py --override-file``.
  lint       AST rules RPR001-003 over src/repro/{layers,models}.
"""

from __future__ import annotations

import argparse
import json
import sys


def _build_policy(name: str, backend: str):
    from ..core import QuantPolicy
    factories = {
        "exact": lambda: QuantPolicy.exact(),
        "qat": lambda: QuantPolicy.qat(backend=backend),
        "fqt8": lambda: QuantPolicy.fqt("bhq", 8, backend=backend),
        "fqt4": lambda: QuantPolicy.fqt("bhq", 4, backend=backend),
        "fqt2": lambda: QuantPolicy.fqt("bhq", 2, backend=backend),
    }
    if name not in factories:
        raise SystemExit(f"unknown policy {name!r}; "
                         f"choose from {sorted(factories)}")
    return factories[name]()


def _configs(ns, default):
    from ..configs import ALL_NAMES
    configs = ns.config or default
    bad = [c for c in configs if c not in ALL_NAMES]
    if bad:
        raise SystemExit(f"unknown config(s) {bad}; choose from {ALL_NAMES}")
    return configs


def _emit(ns, doc: dict, text: str) -> None:
    if getattr(ns, "format", "text") == "json":
        print(json.dumps(doc, indent=2, default=str))
    else:
        print(text)


def _cmd_audit(ns) -> int:
    from ..configs import get_config
    from .audit import audit_model, audit_step, mutation_selftest

    policy = _build_policy(ns.policy, ns.backend)
    rc = 0
    reports, texts = [], []
    for name in _configs(ns, ["statquant-tx", "whisper-medium"]):
        cfg = get_config(name, smoke=not ns.full_size)
        if ns.step:
            report = audit_step(cfg, policy)
        else:
            report = audit_model(cfg, policy, grad=not ns.fwd_only)
        findings = (
            [{"rule": f"audit/{v.kind}", "severity": "error", "path": v.path,
              "role": v.role, "detail": v.detail} for v in report.violations]
            + [{"rule": "range", "severity": f.severity, "path": f.path,
                "role": f.role, "detail": f.detail}
               for f in report.range_findings if not f.ok])
        reports.append({"title": report.title, "ok": report.ok,
                        "findings": findings})
        texts.append(report.format(verbose=ns.verbose))
        if not report.ok:
            rc = 1
        if ns.selftest:
            result = mutation_selftest(cfg, policy)
            reports[-1]["selftest"] = {"ok": result.ok,
                                       "detail": result.detail}
            texts.append(f"== mutation self-test: {name} ==\n{result.detail}")
            if not result.ok:
                texts.append(result.mutated.format())
                rc = 1
    _emit(ns, {"tool": "audit", "ok": rc == 0, "reports": reports},
          "\n\n".join(texts))
    return rc


def _cmd_soundness(ns) -> int:
    from ..configs import get_config
    from .soundness import check_model, check_step, soundness_selftest

    policy = _build_policy(ns.policy, ns.backend)
    rc = 0
    reports, texts = [], []
    for name in _configs(ns, ["statquant-tx", "whisper-medium"]):
        cfg = get_config(name, smoke=not ns.full_size)
        if ns.step:
            report = check_step(cfg, policy, accum_steps=ns.accum)
        else:
            report = check_model(cfg, policy)
        reports.append(report.to_dict())
        texts.append(report.format(verbose=ns.verbose))
        if not report.ok:
            rc = 1
        if ns.selftest:
            result = soundness_selftest(cfg, policy)
            reports[-1]["selftest"] = {
                "ok": result.ok, "detail": result.detail,
                "mutations": {k: v.to_dict()
                              for k, v in result.mutated.items()}}
            texts.append(f"== soundness self-test: {name} ==\n"
                         f"{result.detail}")
            if not result.ok:
                rc = 1
    _emit(ns, {"tool": "soundness", "ok": rc == 0, "reports": reports},
          "\n\n".join(texts))
    return rc


def _cmd_plan(ns) -> int:
    from ..configs import get_config
    from .planner import plan_model

    policy = _build_policy(ns.policy, ns.backend)
    [name] = _configs(ns, ["statquant-tx"])
    cfg = get_config(name, smoke=not ns.full_size)
    plan = plan_model(cfg, policy, budget_bytes=ns.budget_bytes,
                      budget_frac=ns.budget_frac, solver=ns.solver)
    if ns.out:
        with open(ns.out, "w") as fh:
            fh.write(plan.to_json() + "\n")
    _emit(ns, plan.to_dict(),
          plan.format() + (f"\nwrote {ns.out}" if ns.out else ""))
    return 0 if plan.feasible else 1


def _cmd_lint(ns) -> int:
    from .lint import lint_tree

    findings = lint_tree(ns.root or None)
    n = len(findings)
    doc = {"tool": "lint", "ok": not findings,
           "findings": [{"rule": f.rule, "severity": "error",
                         "path": f"{f.file}:{f.line}", "detail": f.message}
                        for f in findings]}
    text = "\n".join(str(f) for f in findings)
    text += ("\n" if text else "") + (f"lint: {n} finding(s)" if n
                                      else "lint: OK")
    _emit(ns, doc, text)
    return 1 if findings else 0


def _add_common(p, step_help: str):
    p.add_argument("--config", action="append",
                   help="arch config name (repeatable; default: the two "
                        "smoke configs statquant-tx + whisper-medium)")
    p.add_argument("--policy", default="fqt8",
                   choices=["exact", "qat", "fqt8", "fqt4", "fqt2"])
    p.add_argument("--backend", default="simulate",
                   choices=["simulate", "native", "pallas"])
    p.add_argument("--step", action="store_true", help=step_help)
    p.add_argument("--full-size", action="store_true",
                   help="use the full config instead of its smoke variant")
    p.add_argument("--format", default="text", choices=["text", "json"],
                   help="output format (json: rule id, path, severity)")
    p.add_argument("-v", "--verbose", action="store_true")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="Static analysis of the quantization contract: "
                    "contract audit, statistical-soundness verifier, "
                    "variance-budget precision planner, repo lint.")
    sub = parser.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("audit", help="jaxpr quantization-contract audit")
    _add_common(p, "audit the full engine step (loss+grad+optimizer)")
    p.add_argument("--selftest", action="store_true",
                   help="also run the mutation self-test")
    p.add_argument("--fwd-only", action="store_true",
                   help="trace the forward only (no gradient contract)")
    p.set_defaults(fn=_cmd_audit)

    p = sub.add_parser(
        "soundness",
        help="statistical-soundness verifier (Theorem 1 preconditions)")
    _add_common(p, "verify the full engine step (microbatch fold keys)")
    p.add_argument("--accum", type=int, default=2,
                   help="accum_steps for --step (default 2: exercises the "
                        "microbatch fold_in scan)")
    p.add_argument("--selftest", action="store_true",
                   help="mutate the quantizer registry / key plumbing and "
                        "assert each mutation turns the pass red")
    p.set_defaults(fn=_cmd_soundness)

    p = sub.add_parser(
        "plan", help="variance-budget precision planner (one config)")
    _add_common(p, argparse.SUPPRESS)
    p.add_argument("--budget-bytes", type=float, default=None,
                   help="bytes-moved budget over all gradient GEMMs "
                        "(default: the uniform-8-bit plan's bytes)")
    p.add_argument("--budget-frac", type=float, default=None,
                   help="budget as a fraction of the uniform-8-bit bytes")
    p.add_argument("--solver", default="auto",
                   choices=["auto", "greedy", "dp"])
    p.add_argument("--out", default=None, metavar="FILE",
                   help="write the plan JSON here (consumed by "
                        "launch/train.py --override-file)")
    p.add_argument("--smoke", action="store_true",
                   help="use the smoke config variant (the default; "
                        "--full-size overrides)")
    p.set_defaults(fn=_cmd_plan)

    p = sub.add_parser("lint", help="AST contract rules RPR001-003")
    p.add_argument("--root", action="append",
                   help="directory to lint (repeatable; default: "
                        "src/repro/layers + src/repro/models)")
    p.add_argument("--format", default="text", choices=["text", "json"])
    p.set_defaults(fn=_cmd_lint)

    ns = parser.parse_args(argv)
    return ns.fn(ns)


if __name__ == "__main__":
    sys.exit(main())
