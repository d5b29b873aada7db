"""Peaks, kernel operations and bytes, and model FLOPs against hand-worked
shapes; the device checks of ``bench/run.py``."""

import json
import os
import subprocess
import sys

import pytest

from bench import flops, kernels, readers
from conftest import ROOT


def test_peaks_table_is_keyed_by_device_kind_with_its_source():
    peaks = json.load(open(os.path.join(ROOT, "bench", "peaks.json")))
    v5e = peaks["TPU v5 lite"]
    assert v5e["source"] == "Google Cloud documentation, TPU v5e"
    assert (v5e["bf16_flops"], v5e["int8_ops"]) == (197e12, 393e12)
    assert (v5e["hbm_bytes_per_s"], v5e["hbm_bytes"]) == (819e9, 16e9)


@pytest.mark.parametrize("kernel,shape,ops,nbytes", [
    # f32 (8, 16) lhs + int8 (16, 4) rhs + f32 (8, 4) out
    ("fused_qlhs_matmul", {"m": 8, "k": 16, "n": 4}, 1024, 512 + 64 + 128),
    # + the (8, 16) uint32 random bits of the SR quantizer
    ("fused_qlhs_matmul", {"m": 8, "k": 16, "n": 4, "stochastic": True},
     1024, 512 + 64 + 128 + 512),
    # f32 X (8, 16), f32 dY (8, 4) and its bits, f32 (16, 4) out
    ("fused_qboth_tn_matmul", {"m": 8, "k": 16, "n": 4}, 1024,
     512 + 128 + 128 + 256),
    ("q8_matmul", {"m": 8, "k": 16, "n": 4}, 1024, 128 + 64 + 128),
    # f32 in + uint32 bits in + int8 codes out + scale and zero per row
    ("quantize_sr", {"rows": 8, "cols": 16}, 0, 512 + 512 + 128 + 64),
])
def test_kernel_costs_by_hand(kernel, shape, ops, nbytes):
    mod = kernels.load(kernel)
    assert mod.ops(**shape) == ops
    assert mod.bytes(**shape) == nbytes


def test_roofline_takes_the_larger_bound():
    peaks = {"int8_ops": 100.0, "hbm_bytes_per_s": 10.0}
    # ops 1024 / 100 = 10.24 s against bytes 704 / 10 = 70.4 s
    assert readers.roofline_seconds(
        "fused_qlhs_matmul", {"m": 8, "k": 16, "n": 4}, peaks) == 70.4


M = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 1,
     "head_dim": 4, "d_ff": 16, "vocab_size": 10, "vocab_pad_to": 8,
     "act": "swiglu"}


def test_model_flops_by_hand():
    # per layer: q 8x8 + k,v 2 x 8x4 + o 8x8 + 3 x 8x16 = 576; head 8x10
    assert flops.matmul_params(M) == 2 * 576 + 80
    # attention: 4 * L * H * hd per key = 64
    assert flops.attention_fwd(M, 3) == 192
    # seq 3: mean keys 2 -> 3 * (2 * 1232 + 128)
    assert flops.train_per_token(M, 3) == 3 * (2 * 1232 + 128)


def test_fqt_step_calls_count_remat_forward_twice():
    calls = readers.fqt_step_calls(M, tokens=12, remat=True)
    fwd = dict((s["k"], s["n"]) for s, _ in calls["fused_qlhs_matmul"])
    assert sum(c for _, c in calls["fused_qlhs_matmul"]) == 7 * 2 * 2 + 1
    assert sum(c for _, c in calls["fused_qboth_tn_matmul"]) == 7 * 2 + 1
    assert fwd[8] == 16 or fwd[8] in (4, 8, 16)      # d -> (q, k/v, gate/up)
    head = calls["q8_matmul"][-1][0]
    assert head == {"m": 12, "k": 16, "n": 8}        # dX of the padded head


def _cli(args, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run([sys.executable, os.path.join("bench", "run.py")]
                          + args, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=300)


def test_run_exits_nonzero_without_an_accelerator():
    p = _cli(["--workload", "tx.train.bhq5", "--seed", "1", "--seconds", "1",
              "--trace", "0"], {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


def test_unknown_device_kind_is_an_error(monkeypatch):
    import jax
    from bench import run

    class Dev:
        platform, device_kind = "tpu", "TPU v99 imaginary"
    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    with pytest.raises(run.NoChip, match="not in bench/peaks.json"):
        run.device_info(1, require_chip=True)
    monkeypatch.setattr(jax, "devices", lambda: [Dev(), Dev()])
    Dev.device_kind = "TPU v5 lite"
    with pytest.raises(run.NoChip, match="needs 4 chips"):
        run.device_info(4, require_chip=True)
