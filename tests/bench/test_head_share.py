"""``head_share.train``: the output head's share of the traced step's
device self time, from the head's ``q[lm_head|role]``/``qk[lm_head]``
scopes and the loss's ``fp[lm_head.ce]``; nothing where the program does not
mark its loss."""

import gzip
import os

import pytest

from bench import attribution, run, trace
from conftest import ROOT
from test_attribution import _reduce

READ = run.load_reader("head_share.train")
HEAD_FWD = "jit(step_fn)/jvp(q[lm_head|fwd])/fused_qlhs_matmul/pallas_call"
HEAD_AGRAD = "jit(step_fn)/transpose(jvp(q[lm_head|agrad]))/mul"
HEAD_KEY = "jit(step_fn)/transpose(jvp(qk[lm_head]))/threefry2x32"
CE = "jit(step_fn)/jvp(fp[lm_head.ce])/reduce_max"
CE_BWD = "jit(step_fn)/transpose(jvp(fp[lm_head.ce]))/exp"
LAYER = "jit(step_fn)/jvp()/while/body/q[layers.mlp.up|fwd]/mul"


def _run_with(ops):
    return {"out": {"trace": _reduce(ops, (0, 10 ** 9)), "traced_steps": 1}}


def test_head_share_by_hand():
    r = _run_with([("fused_qlhs_matmul.7", 0, 300, HEAD_FWD),
                   ("fusion.1", 300, 100, HEAD_AGRAD),
                   ("fusion.2", 400, 50, HEAD_KEY),
                   ("fusion.3", 450, 150, CE),
                   ("fusion.4", 600, 100, CE_BWD),
                   ("fusion.5", 700, 250, LAYER),
                   ("copy.1", 950, 50, "")])
    assert READ(r) == pytest.approx(70.0)
    # the shares of the attribution still add up over the same busy time
    assert r["out"]["attribution"]["self_s"] == pytest.approx(1000e-9)


def test_head_share_needs_the_loss_scope():
    """A program whose loss carries no ``fp[lm_head.ce]`` (the tree before
    it) gives nothing: its share would leave the loss out."""
    r = _run_with([("fused_qlhs_matmul.7", 0, 300, HEAD_FWD),
                   ("fusion.5", 300, 250, LAYER)])
    assert READ(r) is None


@pytest.fixture(scope="module")
def chip_trace():
    from jax.profiler import ProfileData
    data = os.path.join(ROOT, "bench", "testdata")
    with gzip.open(os.path.join(data, "tx.train.bhq5.xplane.pb.gz")) as f:
        reduced = trace.reduce_profile(
            ProfileData.from_serialized_xspace(f.read()))
    with gzip.open(os.path.join(data, "tx.train.bhq5.step_hlo.txt.gz"),
                   "rt") as f:
        return reduced, f.read()


def test_head_share_on_the_chip_trace(chip_trace, monkeypatch):
    """The kept chip trace of ``tx.train.bhq5`` (a tree whose loss carries
    no scope) reads nothing; with the loss's scope in the step's text, the
    head's kernels, quantizers and keys read by their compiled scopes."""
    reduced, hlo = chip_trace
    runrec = {"out": {"trace": reduced, "traced_steps": 3}}
    monkeypatch.setattr(attribution, "compiled_step", lambda run: hlo)
    assert READ(runrec) is None
    marked = hlo + ('\n%ce (p: f32[4]) -> f32[4] {\n  ROOT %exp.0 = f32[4]{0} '
                    'exponential(%p), metadata={op_name="jit(step_fn)/'
                    'jvp(fp[lm_head.ce])/exp"}\n}\n')
    monkeypatch.setattr(attribution, "compiled_step", lambda run: marked)
    runrec = {"out": {"trace": reduced, "traced_steps": 3}}
    share = READ(runrec)
    assert share == pytest.approx(31.778, abs=1e-3)
