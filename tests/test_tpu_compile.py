"""Compile the main-path Pallas kernels for a described TPU v5e.

Interpret mode (every other kernel test) never runs Mosaic, so it cannot
see what the chip's compiler refuses: an unsupported cast, a slice not
aligned to the tiling, a block over the VMEM limit.  These tests compile
each kernel of the training and serving path at ``statquant-tx``'s
published widths for a v5e chip that is described, not attached — nothing
runs, so they say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process may load the TPU compiler library, and under several pytest
workers only the worker given this file may do so.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config

CFG = get_config("statquant-tx")
D, FF = CFG.d_model, CFG.d_ff
TOKENS = 4096                       # 8 sequences x 512 tokens per step
PAGE, PAGES, TABLE_W = 16, 512, 32  # serving pool: 8 lanes x 512 rows


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler installed
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, sharding, kernel, *shapes):
    """Compile ``fn`` and check that the kernel is there under its name:
    ``pallas_call(name=)`` becomes the HLO instruction's name."""
    specs = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert kernel in _kernel_calls(compiled.as_text())
    return compiled


def _kernel_calls(hlo: str) -> dict:
    """{kernel name: custom calls} of a compiled module's text."""
    calls = {}
    for name in re.findall(r"%([\w\-]+?)(?:\.\d+)? = [^\n]*? custom-call\("
                           r'[^\n]*custom_call_target="tpu_custom_call"', hlo):
        calls[name] = calls.get(name, 0) + 1
    return calls


def _w8_coeffs(n):
    return (jnp.float32(0.01), jnp.float32(0.5), jnp.ones((n,), jnp.float32))


F32, I8, U32, I32 = jnp.float32, jnp.int8, jnp.uint32, jnp.int32


@pytest.mark.parametrize("role", ["fwd", "dx"])
def test_fused_qlhs_matmul(one_chip, role):
    """Forward (deterministic, X @ W) and activation grad (stochastic,
    per-row PSQ scales, dY @ W.T) of the d_model -> d_ff GEMM."""
    from repro.kernels.fused_fqt import fused_qlhs_matmul
    dx = role == "dx"
    m, k = (TOKENS, FF) if dx else (TOKENS, D)

    def fn(x, s, z, rb, w8):
        ab, bb, u = _w8_coeffs(D if dx else FF)
        return fused_qlhs_matmul(x, s, z, rb if dx else None, w8, ab, bb, u,
                                 bits=5 if dx else 8, trans_b=dx,
                                 tune_key=f"fused_{role}")

    _compile(fn, one_chip, "fused_qlhs_matmul", ((m, k), F32), ((m, 1), F32),
             ((m, 1), F32), ((m, k), U32), ((D, FF), I8))


def test_fused_qboth_tn_matmul(one_chip):
    """Weight grad X.T @ dY with both operands quantized in the K-sweep."""
    from repro.kernels.fused_fqt import fused_qboth_tn_matmul

    def fn(x, g, rb, a_vec):
        return fused_qboth_tn_matmul(x, 0.1, -1.0, g, 0.2, -2.0, rb, a_vec,
                                     bits_a=8, bits_b=5)

    _compile(fn, one_chip, "fused_qboth_tn_matmul", ((TOKENS, D), F32),
             ((TOKENS, FF), F32), ((TOKENS, FF), U32), ((D,), F32))


@pytest.mark.parametrize("n", [8192, 49408], ids=["gate", "head"])
def test_fused_qboth_tn_matmul_granite(one_chip, n):
    """Granite-3.0-2B's weight gradients at 8 x 1024 tokens: the gate
    (2048 x 8192) and the tied head (2048 x 49408, not a multiple of the
    rule's bn).  The shape rule puts all 2048 output rows in one stripe,
    so dY and its SR bits leave HBM once; the tiles fit the compiler's
    default scoped VMEM, and no operand is padded nor the output sliced."""
    from repro.kernels.tiling import fused_tn_tiles
    from repro.kernels.fused_fqt import fused_qboth_tn_matmul
    m, k = 2048, 8192
    assert fused_tn_tiles(m, k, n)[0] == m

    def fn(x, g, rb, a_vec):
        return fused_qboth_tn_matmul(x, 0.1, -1.0, g, 0.2, -2.0, rb, a_vec,
                                     bits_a=8, bits_b=5)

    hlo = _compile(fn, one_chip, "fused_qboth_tn_matmul", ((k, m), F32),
                   ((k, n), F32), ((k, n), U32), ((m,), F32)).as_text()
    assert not re.search(r"= \S+ (pad|slice)\(", hlo), hlo


@pytest.mark.parametrize("mode", ["rows", "tensor"])
def test_quantize_sr(one_chip, mode):
    from repro.kernels import quantize_sr as q
    fn = q.quantize_sr_rows if mode == "rows" else q.quantize_sr_tensor
    _compile(lambda x, rb: fn(x, rb, 5), one_chip, f"quantize_sr_{mode}",
             ((TOKENS, FF), F32), ((TOKENS, FF), U32))


def test_q8_matmul(one_chip):
    from repro.kernels.q8_matmul import q8_matmul
    m, k, n = TOKENS, D, FF
    _compile(q8_matmul, one_chip, "q8_matmul", ((m, k), I8), ((k, n), I8),
             ((m,), F32), ((n,), F32), ((m,), F32), ((n,), F32),
             ((m,), F32), ((n,), F32))


def test_kv_gather_pages(one_chip):
    from repro.kernels.kv_gather import kv_gather_pages
    flat = CFG.n_kv_heads * CFG.hd
    _compile(kv_gather_pages, one_chip, "kv_gather_pages",
             ((PAGES, PAGE, flat), I8),
             ((PAGES, PAGE), F32), ((PAGES, PAGE), F32), ((8, TABLE_W), I32))


def test_kv_dequant_rows(one_chip):
    from repro.kernels.kv_dequant import kv_dequant_rows
    rows = 8 * PAGE * TABLE_W
    _compile(kv_dequant_rows, one_chip, "kv_dequant_rows",
             ((rows, CFG.n_kv_heads * CFG.hd), I8), ((rows, 1), F32),
             ((rows, 1), F32))


def _compile_step(one_chip, quantizer, cfg=CFG, seq=512, remat=False,
                  **kw):
    """The whole FQT step of ``cfg`` (``statquant-tx``) at 8 x ``seq``
    tokens."""
    from repro.core import QuantPolicy
    from repro.data import make_batch_for
    from repro.engine import abstract_train_state, make_step_fn
    from repro.models import build_model
    from repro.optim import adamw, cosine_schedule

    model, opt = build_model(cfg), adamw()
    pol = QuantPolicy.fqt(quantizer, 5, backend="pallas",
                          pallas_interpret=False, **kw)
    step = make_step_fn(model, pol, opt, cosine_schedule(3e-3, 10),
                        remat=remat)

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    state = place(abstract_train_state(model, opt))
    batch = place(jax.eval_shape(lambda: make_batch_for(cfg, 8, seq)))
    return jax.jit(step, donate_argnums=(0,)).lower(state, batch).compile()


def test_psq_train_step(one_chip):
    """The whole FQT step with PSQ activation gradients at 4096 tokens.

    The per-row range of fc1's dY feeds the fused dX kernel; without the
    optimization barrier that pins its min/max reductions, the TPU
    compiler's bf16-propagation pass crashes the process (SIGILL) on this
    step.  A crash here takes the test worker down with it."""
    compiled = _compile_step(one_chip, "psq")
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def bhq_step_hlo(one_chip):
    """The benchmark cell's step (5-bit BHQ, blocks of 256 rows) at 4096
    tokens, compiled once for the tests below."""
    return _compile_step(one_chip, "bhq", bhq_block=256).as_text()


def test_bhq_train_step_names_kernels_and_markers(bhq_step_hlo):
    """The benchmark cell's step (5-bit BHQ, blocks of 256 rows) at 4096
    tokens: each GEMM kernel is an instruction named after it, once per
    quantized site of the layer body and once for the head, and its
    ``op_name`` carries the FQT seam's ``q[path|role]`` marker; the marker
    reaches BHQ's sorts in the backward, and ``fp[attn.sdpa]`` reaches the
    attention forward and backward."""
    hlo = bhq_step_hlo
    sites = 7                    # wq wk wv wo fc1 fc2 in the scan, lm_head
    assert _kernel_calls(hlo) == {"fused_qlhs_matmul": sites,
                                  "fused_qboth_tn_matmul": sites,
                                  "q8_matmul": sites}
    role = {"fused_qlhs_matmul": "fwd", "fused_qboth_tn_matmul": "wgrad",
            "q8_matmul": "agrad"}
    for kernel, r in role.items():
        names = re.findall(rf"%{kernel}(?:\.\d+)? = [^\n]*? custom-call\("
                           rf'[^\n]*op_name="([^"]*)"', hlo)
        assert len(names) == sites
        for name in names:
            assert re.search(rf"\bq\[[^\]|]+\|{r}\]\)*/.*{kernel}/pallas_call",
                             name), name
    # XLA may fuse a kernel with the slice update of its output; the fusion
    # that runs it takes the kernel's name and op_name
    for kernel, opcode, name in re.findall(
            r"%(fused_qlhs_matmul|fused_qboth_tn_matmul|q8_matmul)(?:\.\d+)? = "
            r'[^\n]*? ([\w\-]+)\([^\n]*op_name="([^"]*)"', hlo):
        assert opcode in ("custom-call", "fusion"), (kernel, opcode)
        assert name.endswith(f"/{kernel}/pallas_call"), name
    op_names = re.findall(r'op_name="([^"]*)"', hlo)
    sorts = re.findall(r'= [^\n]*? sort\([^\n]*op_name="([^"]*)"', hlo)
    assert any(re.search(r"transpose\(jvp.*q\[[^\]]+\|agrad\]", n)
               for n in sorts)
    for grad in ("jvp()", "transpose(jvp())"):
        assert any(n.startswith(f"jit(step_fn)/{grad}/")
                   and "/fp[attn.sdpa]/" in n for n in op_names), grad


def test_bhq_agrad_compiles_to_no_gather_scatter_or_loop(bhq_step_hlo):
    """BHQ's activation-gradient quantizer, as the chip's compiler leaves
    it: no scatter or ``while`` under a ``q[...|agrad]`` scope (the step's
    remaining ones are the embedding gradient and the layer scans), no
    gather of more than one value per block (the group search picks its
    candidate count with one), and the per-block mixing matmuls, quantize
    and inverse at each of the 7 sites, at HIGHEST operand precision."""
    agrad = [line for line in bhq_step_hlo.splitlines()
             if re.search(r"\|agrad\]", line)]
    for op in ("scatter", "while"):
        hits = [line for line in agrad if re.search(rf"= [^\n]*? {op}\(", line)]
        assert not hits, hits[:2]
    for line in agrad:
        m = re.search(r"= \w+\[([\d,]*)\]\S* gather\(", line)
        if m:
            size = 1
            for dim in filter(None, m.group(1).split(",")):
                size *= int(dim)
            assert size <= TOKENS // 256, line
    mixing = [line for line in agrad
              if re.search(r"= \w+\[16,256,\d+\]\S* convolution\(", line)]
    assert len(mixing) >= 2 * 7, len(mixing)
    for line in mixing:
        assert "operand_precision={highest,highest}" in line, line


def test_granite_step_fits_one_chip(one_chip):
    """The step of ``granite2b.train.bhq5``: Granite-3.0-2B at its published
    widths, 5 of its 40 layers, tied head, 8 x 1024 tokens with remat, 5-bit
    BHQ on compiled Pallas.  Temporaries plus arguments stay under 15 GiB
    of the chip's 16, and each GEMM kernel is there at every quantized site
    (the layer body's 7 and the head).  Remat recomputes the body's forward
    GEMMs in the backward scan but for ``down``: its output is only the
    layer's output, which the backward does not need.  The weight-gradient
    kernel reads the head's 8192 x 49408 dY and SR bits unpadded."""
    import dataclasses
    cfg = dataclasses.replace(get_config("granite-3-2b"), n_layers=5)
    compiled = _compile_step(one_chip, "bhq", cfg=cfg, seq=1024, remat=True,
                             bhq_block=256)
    mem = compiled.memory_analysis()
    used = mem.temp_size_in_bytes + mem.argument_size_in_bytes
    assert used < 15 * 2 ** 30, used / 2 ** 30
    hlo = compiled.as_text()
    assert _kernel_calls(hlo) == {
        "fused_qlhs_matmul": 7 + 6 + 1, "fused_qboth_tn_matmul": 7 + 1,
        "q8_matmul": 7 + 1}
    assert not re.search(r"= [fu]32\[8192,\d+\]\S* pad\(", hlo)
