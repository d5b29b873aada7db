"""Smoke run of the main path on a TPU: FQT training and paged serving.

    python chip_smoke.py              # one chip: training + paged serving
    python chip_smoke.py --chips 4    # only the 2x2 sharded training step,
                                      # against the same step on one chip

``statquant-tx`` at its published widths (6 layers, d_model 512, 4 heads,
d_ff 1024, vocab 10k), random weights from ``--seed``:

* training — ``Engine`` steps under the paper's 5-bit BHQ recipe on the
  compiled Pallas kernels, then PTQ, PSQ and QAT; every loss and gradient
  norm finite, the compiled step holding ``tpu_custom_call`` kernels, and
  the step-0 loss equal (rtol 1e-3) to the ``simulate`` backend's, whose
  forward quantizers are the same;
* serving — ``ServeEngine(..., paged=True)`` on the int8 KV pool answers 8
  greedy requests; every token in vocabulary, and a second engine at the
  same seed answers the same tokens.

Everything runs in this one process.  It exits non-zero, printing no
result, when JAX finds no TPU.  The last stdout line is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))

TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 16, 512, 5   # 8192 tokens per step
MESH_STEPS = 3
SERVE_SLOTS, SERVE_PAGE, SERVE_MAX_SEQ = 8, 16, 256
SERVE_REQUESTS, SERVE_NEW = 8, 32
PROMPT_LEN = (32, 128)
STEP0_RTOL = 1e-3


def _policies(QuantPolicy):
    """(name, policy) of the training phase: the paper's 5-bit BHQ recipe
    first, then the other gradient quantizers and QAT."""
    kw = dict(backend="pallas", pallas_interpret=False)
    return (("bhq", QuantPolicy.fqt("bhq", 5, bhq_block=256, **kw)),
            ("ptq", QuantPolicy.fqt("ptq", 5, **kw)),
            ("psq", QuantPolicy.fqt("psq", 5, **kw)),
            ("qat", QuantPolicy.qat(**kw)))


def _finite(name, **values):
    for key, v in values.items():
        if not math.isfinite(v):
            raise AssertionError(f"{name}: {key} is not finite ({v})")


def train_phase(cfg, seed):
    import jax
    from repro.core import QuantPolicy
    from repro.engine import Engine

    kw = dict(steps=TRAIN_STEPS, batch_size=TRAIN_BATCH, seq_len=TRAIN_SEQ,
              seed=seed, log_fn=None)
    policies = _policies(QuantPolicy)
    # the reference: same Engine, same seed and batch, f32 fake-quant GEMMs
    ref = Engine(cfg, QuantPolicy.fqt("bhq", 5, bhq_block=256,
                                      backend="simulate"), **kw)
    _, mets = ref.step_fn(ref.init_state(), ref.loader.get(0))
    ref_loss = float(mets["loss"])
    del ref
    print(f"[train] simulate reference step-0 loss {ref_loss:.6f}",
          flush=True)

    for name, pol in policies:
        eng = Engine(cfg, pol, **kw)
        state = eng.init_state()
        step = eng.step_fn.lower(state, eng.loader.get(0)).compile()
        n_kernels = step.as_text().count(
            'custom_call_target="tpu_custom_call"')
        if n_kernels == 0:
            raise AssertionError(f"{name}: no tpu_custom_call in the step")
        for i in range(TRAIN_STEPS):
            state, mets = step(state, eng.loader.get(i))
            loss, gnorm = float(mets["loss"]), float(mets["grad_norm"])
            _finite(f"train {name} step {i}", loss=loss, grad_norm=gnorm)
            print(f"[train] {name} step {i} loss {loss:.6f} "
                  f"gnorm {gnorm:.6f} tpu_custom_call {n_kernels}",
                  flush=True)
            if i == 0 and not math.isclose(loss, ref_loss,
                                           rel_tol=STEP0_RTOL):
                raise AssertionError(
                    f"{name}: step-0 loss {loss} vs simulate {ref_loss} "
                    f"(rtol {STEP0_RTOL})")
        del eng, state, step
        jax.clear_caches()


def serve_phase(cfg, seed):
    import jax
    import numpy as np
    from repro.core import QuantPolicy
    from repro.models import build_model
    from repro.serve import ServeEngine

    params = build_model(cfg).init(jax.random.PRNGKey(seed))
    policy = QuantPolicy.qat(backend="pallas", pallas_interpret=False)
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, cfg.vocab_size,
                           size=rng.randint(PROMPT_LEN[0], PROMPT_LEN[1] + 1))
               for _ in range(SERVE_REQUESTS)]

    def answer():
        eng = ServeEngine(cfg, params, paged=True, policy=policy,
                          slots=SERVE_SLOTS, max_seq=SERVE_MAX_SEQ,
                          page_size=SERVE_PAGE, seed=seed)
        rids = [eng.submit(p, max_new=SERVE_NEW) for p in prompts]
        done = eng.run()
        eng.check_invariants()
        return [done[r].tokens for r in rids], eng.pool_stats()

    first, stats = answer()
    for p, toks in zip(prompts, first, strict=True):
        if len(toks) != SERVE_NEW:
            raise AssertionError(f"request of {len(p)} prompt tokens "
                                 f"answered {len(toks)}/{SERVE_NEW} tokens")
        if not all(0 <= t < cfg.vocab_size for t in toks):
            raise AssertionError(f"token out of vocabulary: {toks}")
    print(f"[serve] {len(first)} requests, prompt lengths "
          f"{[len(p) for p in prompts]}, {SERVE_NEW} tokens each; pages "
          f"peak {stats['peak_pages_in_use']}/{stats['n_pages']}", flush=True)
    print(f"[serve] request 0 tokens {first[0]}", flush=True)
    second, _ = answer()
    if second != first:
        raise AssertionError("a second engine at the same seed answered "
                             "different tokens")
    print("[serve] second engine at the same seed: identical tokens",
          flush=True)


def mesh_phase(cfg, seed):
    """The sharded step on a 2x2 (data, model) mesh with 2 microbatches,
    against the same step unsharded on one device."""
    import jax
    import numpy as np
    from repro.core import QuantPolicy
    from repro.engine import Engine
    from repro.launch.mesh import make_test_mesh

    # Mosaic kernels are not partitioned by GSPMD, so the sharded step
    # runs the XLA int8 backend (Engine refuses compiled Pallas on a mesh)
    pol = QuantPolicy.fqt("bhq", 5, bhq_block=256, backend="native")
    kw = dict(steps=MESH_STEPS, batch_size=TRAIN_BATCH, seq_len=TRAIN_SEQ,
              accum_steps=2, seed=seed, log_every=1, log_fn=print)
    sharded = Engine(cfg, pol, mesh=make_test_mesh(2, 2), **kw)
    h_mesh = sharded.run()
    spread = {len(leaf.sharding.device_set)
              for leaf in jax.tree.leaves(sharded.state.params)}
    if spread != {4}:
        raise AssertionError(f"params live on {spread} devices, not 4")
    h_one = Engine(cfg, pol, **kw).run()
    print(f"[mesh] 2x2 losses {[l for _, l in h_mesh]}", flush=True)
    print(f"[mesh] one-device losses {[l for _, l in h_one]}", flush=True)
    for _, loss in h_mesh + h_one:
        _finite("mesh", loss=loss)
    # the tolerances of tests/test_engine.py's sharded parity check
    np.testing.assert_allclose(h_mesh[0][1], h_one[0][1], rtol=1e-4)
    np.testing.assert_allclose([l for _, l in h_mesh],
                               [l for _, l in h_one], rtol=2e-3, atol=2e-3)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no src/repro next to {__file__}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    import jax
    from repro.configs import get_config
    from repro.kernels.autotune import get_cache
    from repro.launch.device import device_summary, enable_compile_cache

    cache_dir = enable_compile_cache()
    print(f"jax {jax.__version__}; devices {jax.devices()}", flush=True)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if jax.device_count() < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {jax.device_count()}", file=sys.stderr)
        return 1
    print(f"[device] {device_summary()}; compile cache {cache_dir}",
          flush=True)

    cfg = get_config("statquant-tx")
    if args.chips == 4:
        mesh_phase(cfg, args.seed)
    else:
        train_phase(cfg, args.seed)
        serve_phase(cfg, args.seed)
    for (kernel, shape, dtype), (tiles, source) in sorted(
            get_cache().resolved.items()):
        print(f"[tiles] {kernel} {shape} {dtype}: {tiles} from {source}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
