"""``repro.analysis`` — static verification of the quantization contract.

The quantization layer (core/fqt.py + core/policy.py) promises "every
linear-layer GEMM runs under the resolved policy; everything else is
declared full-precision".  Nothing at runtime *checks* that promise: a
layer that silently calls ``jnp.dot`` trains fine, converges fine, and
reports FQT numbers that are quietly part-fp32.  This package closes the
loop without touching a device:

  ``audit``      (:mod:`.audit`)  trace to jaxpr, attribute every GEMM via
                 the ``q[path|role]``/``qfp``/``fp`` name-stack markers,
                 and diff against ``QuantPolicy.resolve`` + the
                 ``fp_exempt`` registry; FLOP-weighted coverage; mutation
                 self-test.
  ``soundness``  (:mod:`.soundness`)  abstract interpretation of the
                 traced graph verifying the Theorem 1 unbiasedness
                 preconditions: stochastic rounding on every gradient
                 path, independent SR key streams (no aliasing, no
                 scan-invariant microbatch/chunk/layer reuse), no double
                 quantization, deterministic forward (rules SND001-005);
                 its own red/green mutation self-test.
  ``planner``    (:mod:`.planner`)  variance-budget precision planner:
                 per-site (variance, bytes) candidates from the
                 Proposition 4 closed forms + the bench bytes-moved model,
                 solved greedily and by exact DP into ready-to-train
                 ``QuantPolicy.overrides`` JSON.
  ``ranges``     (:mod:`.ranges`)  int32-accumulator overflow bounds for
                 intM x intN GEMMs (asymmetric widths), scale-degeneracy
                 checks.
  ``tracing``    (:mod:`.tracing`) retrace counter + donation verifier for
                 the jitted engine step.
  ``lint``       (:mod:`.lint`)    AST rules RPR001-003 over layers/models.

CLI: ``python -m repro.analysis {audit|soundness|plan|lint}``
(see __main__.py); every subcommand accepts ``--format json``; exits
non-zero on any violation, so CI gates on it.
"""

from .audit import (AuditReport, SelftestResult, Violation, audit_fn,
                    audit_model, audit_step, mutation_selftest)
from .graph import GemmSite, classify_stack, iter_gemm_sites, site_flops
from .lint import LintFinding, lint_file, lint_source, lint_tree
from .planner import (Candidate, Plan, PlanEntry, PlanSite,
                      collect_plan_sites, gemm_bytes_moved, legal_widths,
                      plan_model, site_candidates)
from .ranges import (RangeFinding, accumulator_bound, check_sites,
                     headroom_bits, max_safe_k, signed_code_bound)
from .soundness import (SoundnessFinding, SoundnessReport,
                        SoundnessSelftest, check_model, check_soundness_fn,
                        check_step, soundness_selftest)
from .tracing import (DonationReport, RetraceGuard, check_donation,
                      check_step_donation)

__all__ = [
    "AuditReport", "Violation", "SelftestResult",
    "audit_fn", "audit_model", "audit_step", "mutation_selftest",
    "GemmSite", "iter_gemm_sites", "site_flops", "classify_stack",
    "SoundnessFinding", "SoundnessReport", "SoundnessSelftest",
    "check_soundness_fn", "check_model", "check_step", "soundness_selftest",
    "Plan", "PlanEntry", "PlanSite", "Candidate", "plan_model",
    "collect_plan_sites", "site_candidates", "gemm_bytes_moved",
    "legal_widths",
    "RangeFinding", "check_sites", "accumulator_bound", "max_safe_k",
    "headroom_bits", "signed_code_bound",
    "LintFinding", "lint_source", "lint_file", "lint_tree",
    "RetraceGuard", "DonationReport", "check_donation",
    "check_step_donation",
]
