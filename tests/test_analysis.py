"""repro.analysis: contract auditor, range analysis, lint.

The auditor's own correctness is established adversarially: the mutation
self-test plants a raw ``jnp.dot`` in an MLP and the audit must turn red
*naming that layer path*, then recover green at 100% coverage on the
unmutated tree.  Range bounds are cross-checked against brute-force
extreme-value integer GEMMs (real int32/int16 wraparound, not a model of
it).
"""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (RetraceGuard, audit_fn, audit_model, audit_step,
                            check_donation, lint_source, lint_tree,
                            mutation_selftest)
from repro.analysis.ranges import (accumulator_bound, check_scale_inputs,
                                   headroom_bits, max_safe_k,
                                   signed_code_bound)
from repro.configs import get_config
from repro.core import QuantPolicy, fp_exempt, quant_scope

FQT8 = QuantPolicy.fqt("bhq", 8)

sd = jax.ShapeDtypeStruct
f32 = jnp.float32


# ---------------------------------------------------------------------------
# Auditor: clean trees across families and backends
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["simulate", "native", "pallas"])
def test_audit_lm_clean_all_backends(backend):
    cfg = get_config("statquant-tx", smoke=True)
    report = audit_model(cfg, QuantPolicy.fqt("bhq", 8, backend=backend))
    assert report.ok, report.format()
    assert report.coverage == 1.0
    # all three roles present and fully quantized
    roles = report.role_flops()
    assert set(roles) == {"fwd", "wgrad", "agrad"}
    assert all(v["policy_fp"] == 0.0 for v in roles.values())
    # the declared sdpa exemption is the only fp GEMM
    assert set(report.exemptions) == {"attn.sdpa"}


@pytest.mark.parametrize("arch", [
    pytest.param("whisper-medium", marks=pytest.mark.slow),
    "olmoe-1b-7b",
])
def test_audit_families_clean(arch):
    cfg = get_config(arch, smoke=True)
    report = audit_model(cfg, FQT8)
    assert report.ok, report.format()
    assert report.coverage == 1.0


def test_audit_tied_granite_head_resolves_at_lm_head():
    """Granite's tied head (the embedding table, transposed) is a GEMM like
    any other: every GEMM goes through the policy, the head's three roles
    resolve at ``lm_head``, and its loss's ``fp[lm_head.ce]`` scope holds
    no GEMM."""
    cfg = get_config("granite-3-2b", smoke=True)
    assert cfg.tie_embeddings
    report = audit_model(cfg, FQT8)
    assert report.ok, report.format()
    assert report.coverage == 1.0
    assert {s.role for s in report.sites if s.path == "lm_head"} == {
        "fwd", "wgrad", "agrad"}
    assert all(s.kind == "quantized" for s in report.sites
               if s.path == "lm_head")
    assert set(report.exemptions) == {"attn.sdpa"}
    # pinned exact by an override, the tied head is declared full precision
    exact_head = audit_model(cfg, QuantPolicy.fqt(
        "bhq", 8, overrides={r"lm_head": "exact"}))
    assert exact_head.ok, exact_head.format()
    assert {s.kind for s in exact_head.sites if s.path == "lm_head"} == {
        "policy_fp"}


def test_audit_exact_and_qat():
    cfg = get_config("statquant-tx", smoke=True)
    exact = audit_model(cfg, QuantPolicy.exact())
    assert exact.ok, exact.format()
    assert exact.flops("quantized") == 0.0

    qat = audit_model(cfg, QuantPolicy.qat())
    assert qat.ok, qat.format()
    roles = qat.role_flops()
    # QAT: forward quantized, both backward GEMMs declared full precision
    assert roles["fwd"]["policy_fp"] == 0.0
    assert roles["fwd"]["quantized"] > 0.0
    assert roles["wgrad"]["quantized"] == 0.0
    assert roles["agrad"]["quantized"] == 0.0


@pytest.mark.slow
def test_audit_engine_step_clean():
    cfg = get_config("statquant-tx", smoke=True)
    report = audit_step(cfg, FQT8)
    assert report.ok, report.format()
    assert report.coverage == 1.0


def test_mutation_selftest():
    cfg = get_config("statquant-tx", smoke=True)
    result = mutation_selftest(cfg, FQT8)
    assert result.ok, result.detail
    # red run names the leaked path explicitly
    assert any(v.path == result.target_path
               for v in result.mutated.violations)
    assert any(v.kind == "unmarked-gemm" for v in result.mutated.violations)
    assert result.clean.coverage == 1.0


# ---------------------------------------------------------------------------
# Auditor: violation taxonomy on synthetic functions
# ---------------------------------------------------------------------------

def test_audit_fn_flags_unmarked_gemm():
    def f(x, w):
        return x @ w

    report = audit_fn(f, (sd((4, 8), f32), sd((8, 4), f32)),
                      policy=FQT8, paths=(), grad_traced=False)
    assert not report.ok
    [v] = report.violations
    assert v.kind == "unmarked-gemm"
    assert "fp_exempt" in v.detail


def test_audit_fn_accepts_exempt_gemm():
    def f(x, w):
        with fp_exempt("test.block", "synthetic exemption for the test"):
            return x @ w

    report = audit_fn(f, (sd((4, 8), f32), sd((8, 4), f32)),
                      policy=FQT8, paths=(), grad_traced=False)
    assert report.ok, report.format()
    assert report.exemptions["test.block"].startswith("synthetic")
    assert report.coverage == 1.0            # no non-exempt GEMMs at all


def test_audit_fn_contract_mismatch_and_missing():
    def f(x, w):
        with quant_scope("p1", "fwd", quantized=False):  # graph says fp
            return x @ w

    report = audit_fn(f, (sd((4, 8), f32), sd((8, 4), f32)),
                      policy=FQT8, paths=("p1", "p2"), grad_traced=False)
    kinds = {(v.kind, v.path) for v in report.violations}
    # p1 runs fp while the policy resolves quantized; p2 never appears
    assert ("contract-mismatch", "p1") in kinds
    assert ("declared-missing", "p2") in kinds


def test_audit_fn_undeclared_path():
    def f(x, w):
        with quant_scope("ghost", "fwd", quantized=True):
            return x @ w

    report = audit_fn(f, (sd((4, 8), f32), sd((8, 4), f32)),
                      policy=FQT8, paths=(), grad_traced=False)
    assert any(v.kind == "undeclared-path" and v.path == "ghost"
               for v in report.violations)


# ---------------------------------------------------------------------------
# Range analysis vs brute-force extreme-value GEMMs
# ---------------------------------------------------------------------------

def test_max_safe_k_int8():
    assert signed_code_bound(8) == 128
    assert max_safe_k(8, 8) == 131071
    assert accumulator_bound(131071, 8, 8) <= 2**31 - 1
    assert accumulator_bound(131072, 8, 8) > 2**31 - 1
    assert headroom_bits(131071, 8, 8) >= 0.0 > headroom_bits(131072, 8, 8)


def test_int32_wraparound_at_predicted_k():
    """The bound is exact: K = max_safe_k is the last safe contraction for
    worst-case int8 codes; K+1 wraps the int32 accumulator in a real
    dot_general."""
    k_safe = max_safe_k(8, 8)
    dims = (((1,), (0,)), ((), ()))

    def worst(k):
        a = jnp.full((1, k), -128, jnp.int8)
        b = jnp.full((k, 1), -128, jnp.int8)
        return int(jax.lax.dot_general(
            a, b, dims, preferred_element_type=jnp.int32)[0, 0])

    assert worst(k_safe) == accumulator_bound(k_safe, 8, 8)   # no wrap
    assert worst(k_safe + 1) < 0                              # wrapped


def test_int32_wraparound_at_predicted_k_asymmetric_8x4():
    """Same exactness for the W4A8-style asymmetric pair: max_safe_k(8, 4)
    is the last safe contraction for int8 codes against worst-case 4-bit
    codes (|c| = 8), and K+1 wraps for real."""
    k_safe = max_safe_k(8, 4)
    assert k_safe == (2**31 - 1) // (128 * 8) == 16 * max_safe_k(8, 8) + 15
    dims = (((1,), (0,)), ((), ()))

    def worst(k):
        a = jnp.full((1, k), -128, jnp.int8)
        b = jnp.full((k, 1), -signed_code_bound(4), jnp.int8)
        return int(jax.lax.dot_general(
            a, b, dims, preferred_element_type=jnp.int32)[0, 0])

    assert worst(k_safe) == accumulator_bound(k_safe, 8, 4)   # no wrap
    assert worst(k_safe + 1) < 0                              # wrapped


def test_w4a8_widens_the_checked_bound():
    """check_sites bounds each role by the *policy* widths: a contraction
    that overflows an 8x8 agrad GEMM is certified safe once the weights go
    4-bit (W4A8), without retracing anything."""
    from repro.analysis import GemmSite
    from repro.analysis.ranges import check_sites

    k = max_safe_k(8, 8) + 1
    assert k <= max_safe_k(8, 4)
    site = GemmSite(primitive="dot_general", flops=2.0 * k, contract=k,
                    mult=1, lhs_dtype="float32", rhs_dtype="float32",
                    stack="q[layers.0.mlp|agrad]", kind="quantized",
                    path="layers.0.mlp", role="agrad", src="test", m=4, n=4)
    red = check_sites([site], QuantPolicy.fqt("bhq", 8))
    assert any(f.severity == "overflow" and not f.ok
               and (f.lhs_bits, f.rhs_bits) == (8, 8) for f in red)
    green = check_sites([site], QuantPolicy.fqt("bhq", 8, weight_bits=4))
    assert all(f.ok for f in green)
    assert any((f.lhs_bits, f.rhs_bits) == (8, 4) for f in green)


def test_int16_wraparound_brute_force_low_bits():
    """Same bound at 4 bits against a int16 accumulator, checked by numpy
    wraparound — exercises the acc_bits generality."""
    k_safe = max_safe_k(4, 4, acc_bits=16)
    assert k_safe == (2**15 - 1) // (8 * 8)
    prod = np.int16(signed_code_bound(4)) * np.int16(signed_code_bound(4))
    safe = np.full(k_safe, prod, np.int16).sum(dtype=np.int16)
    assert int(safe) == accumulator_bound(k_safe, 4, 4)
    wrapped = np.full(k_safe + 1, prod, np.int16).sum(dtype=np.int16)
    assert int(wrapped) < 0


def test_int2_int4_bounds_scale():
    # lower bitwidths buy quadratically more contraction headroom
    assert max_safe_k(4, 4) == (2**31 - 1) // 64
    assert max_safe_k(2, 2) == (2**31 - 1) // 4
    assert max_safe_k(4, 8) == (2**31 - 1) // (8 * 128)


def test_scale_degeneracy():
    flagged = check_scale_inputs([("w", 0.0), ("x", 1e-13), ("ok", 0.5)])
    assert len(flagged) == 2
    assert flagged[0].startswith("w:") and flagged[1].startswith("x:")


def test_range_check_rides_the_audit():
    """An int-dtype GEMM with K over the bound turns the audit red even
    when the marker contract is satisfied."""
    k_bad = max_safe_k(8, 8) + 1

    def f(a, b):
        with fp_exempt("test.intgemm", "stress the accumulator bound"):
            return jax.lax.dot_general(
                a, b, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)

    report = audit_fn(f, (sd((1, k_bad), jnp.int8), sd((k_bad, 1), jnp.int8)),
                      policy=FQT8, paths=(), grad_traced=False)
    assert not report.ok
    assert any(f_.severity == "overflow" and not f_.ok
               for f_ in report.range_findings)


# ---------------------------------------------------------------------------
# Retrace + donation guards
# ---------------------------------------------------------------------------

def test_retrace_guard():
    guard = RetraceGuard(jax.jit(lambda x: x * 2))
    x = jnp.ones((4,))
    guard(x)
    guard(x)
    guard.assert_no_retrace()                 # first compile is expected
    assert guard.compiles in ([0], [])        # [] only if cache pre-warmed

    guard(jnp.ones((8,)))                     # new shape => retrace
    assert guard.retraces == 1
    with pytest.raises(AssertionError, match="retraced on call"):
        guard.assert_no_retrace()


def test_check_donation_consumes_buffers():
    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(state, dx):
        return jax.tree.map(lambda x: x + dx, state), jnp.sum(state["w"])

    state = {"w": jnp.ones((8, 8)), "m": jnp.zeros((8, 8))}
    (_new, _aux), report = check_donation(step, state, 1.0)
    assert report.n_donated == 2
    assert report.ok, report.detail


def test_check_donation_detects_dropped_donation():
    # no donation: the inputs stay alive and the report says so
    @jax.jit
    def step(state, dx):
        return jax.tree.map(lambda x: x + dx, state), 0.0

    state = {"w": jnp.ones((8, 8))}
    _, report = check_donation(step, state, 1.0)
    assert report.n_deleted == 0
    assert not report.ok
    assert "dropped" in report.detail


# ---------------------------------------------------------------------------
# Lint rules
# ---------------------------------------------------------------------------

def test_lint_repo_tree_is_clean():
    findings = lint_tree()
    assert findings == [], "\n".join(str(f) for f in findings)


def test_lint_rpr001_pathless_dense():
    src = """
def layer(p, x, key, policy):
    a = dense(p["w1"], x, key, policy, 1, "layers.up")      # ok
    b = dense(p["w2"], x, key, policy, 2)                   # missing path
    c = dense(p["w3"], x, key, policy, 3, path="")          # empty path
    d = fqt_matmul(x, p["w4"], key, policy)                 # missing path
    return a + b + c + d
"""
    rules = [f.rule for f in lint_source(src)]
    assert rules == ["RPR001", "RPR001", "RPR001"]


def test_lint_rpr002_raw_gemm():
    src = """
import jax.numpy as jnp

def bad(x, w):
    return jnp.einsum("ij,jk->ik", x, w) + x @ w

def good(x, w):
    with fp_exempt("m.block", "documented reason"):
        return jnp.dot(x, w) + x @ w
"""
    findings = lint_source(src)
    assert [f.rule for f in findings] == ["RPR002", "RPR002"]
    assert all(f.line == 5 for f in findings)


def test_lint_rpr003_nonliteral_exempt():
    src = """
def f(x, w, name):
    with fp_exempt("a." + name, "reason"):       # computed path
        return x @ w

def g(x, w):
    with fp_exempt("a.b"):                        # missing reason
        return x @ w

def h(x, w):
    with fp_exempt("a.c", SHARED_REASON):         # UPPER constant ok
        return x @ w
"""
    rules = [f.rule for f in lint_source(src)]
    assert rules == ["RPR003", "RPR003"]


def test_lint_syntax_error_reported():
    [f] = lint_source("def broken(:\n")
    assert f.rule == "RPR000"


def test_lint_kernel_mode_contract():
    src = """
import jax.numpy as jnp
from jax import lax

def good(a, b):
    return lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                           preferred_element_type=jnp.int32)

def widened(a, b):
    return lax.dot_general(a, b, (((1,), (0,)), ((), ())))  # implicit acc

def raw(a, b):
    return jnp.matmul(a, b) + a @ b
"""
    rules = [f.rule for f in lint_source(src, mode="kernel")]
    assert rules == ["RPR002", "RPR002", "RPR002"]
    # contract mode would also demand fp_exempt; kernel mode accepts a
    # bare dot_general as long as the accumulator dtype is explicit
    assert lint_source(
        "def f(a, b):\n"
        "    return dot_general(a, b, d,"
        " preferred_element_type=jnp.int32)\n", mode="kernel") == []


# ---------------------------------------------------------------------------
# CLI --format json
# ---------------------------------------------------------------------------

def test_cli_lint_json(capsys):
    from repro.analysis.__main__ import main
    rc = main(["lint", "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert doc["tool"] == "lint" and doc["ok"] == (rc == 0)
    assert isinstance(doc["findings"], list)
