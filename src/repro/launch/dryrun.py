import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: AOT lower + compile every (arch x shape x mesh) cell.

The two lines above MUST precede any jax import: jax locks the device count
at first init, and the production meshes need 512 host-platform placeholder
devices.  (Tests/benches never import this module — they see 1 device.)

Per cell this script:
  1. builds abstract params (jax.eval_shape — no allocation),
  2. builds ShapeDtypeStruct inputs (model.input_specs, bf16 activations),
  3. jits train_step / prefill / decode with the sharding plan's
     in/out_shardings (+ sequence parallelism on the residual stream),
     ``.lower()`` s and ``.compile()`` s it,
  4. records memory_analysis / cost_analysis / parsed collective bytes,
  5. corrects the per-device FLOP/byte/collective totals for XLA's
     count-scan-bodies-once behaviour by compiling tiny UNROLLED probe
     variants (1 and 2 layers at full width) and composing
     total = stem + n_layers * body   — exact w.r.t. XLA's own counting,
  6. derives the three roofline terms (launch/roofline.py) and writes
     experiments/dryrun/<arch>__<shape>__<mesh>.json.

Failures (sharding mismatch, OOM at compile, unsupported collective) are
bugs in the system — the run aborts loudly.
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

# NOTE: rbg PRNG was evaluated for the SR uniforms and REJECTED: on the
# XLA:CPU AOT backend it blows buffer assignment up ~40x (2 TiB vs 50 GiB
# temp for minitron train_4k). threefry + loss-chunking is the right config;
# see EXPERIMENTS.md perf log.

from ..configs import ARCH_NAMES, SHAPES, get_config, shape_grid
from ..core import QuantPolicy
from ..engine import abstract_train_state, jit_step, make_step_fn
from ..models import build_model
from ..optim import sgd
from ..sharding import make_plan
from .mesh import make_production_mesh
from .roofline import collective_bytes, model_flops, roofline_terms

__all__ = ["run_cell", "main"]

ACT_DTYPE = jnp.bfloat16


def count_params(abstract_params) -> int:
    import math
    return sum(math.prod(l.shape) for l in jax.tree.leaves(abstract_params))


def active_param_frac(cfg) -> float:
    """MoE: fraction of expert params active per token (top-k / E)."""
    if not cfg.moe_experts:
        return 1.0
    d, ff, E, K = cfg.d_model, cfg.d_ff, cfg.moe_experts, cfg.moe_topk
    expert = (3 if cfg.act == "swiglu" else 2) * d * ff * E
    hd = cfg.hd
    attn = d * (cfg.n_heads + 2 * cfg.n_kv_heads) * hd + cfg.n_heads * hd * d
    dense_part = attn + d * E
    total = expert + dense_part
    return (expert * (K / E) + dense_part) / total


def _act_sharding(plan, shape):
    """Sequence-parallel residual-stream sharding for train cells."""
    if shape.kind != "train":
        return None
    dp = plan._dp(shape.global_batch)
    if dp is None:
        return None
    return NamedSharding(plan.mesh, P(dp, plan.model_axis, None))


def _compile(cfg, shape, plan, policy, opt, sp: bool = True,
             extra_kwargs: dict | None = None):
    """Lower + compile one module; returns (compiled, abstract_params)."""
    extra_kwargs = extra_kwargs or {}
    model = build_model(cfg)
    abstract_params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    p_specs = plan.param_specs(abstract_params)
    specs_in = model.input_specs(shape, dtype=ACT_DTYPE)
    b_specs = plan.batch_specs(specs_in["batch"])

    if shape.kind == "train":
        act_sh = _act_sharding(plan, shape) if sp else None
        extra_kwargs = dict(extra_kwargs)
        compress_axis = extra_kwargs.pop("compress_axis", None)
        remat = extra_kwargs.pop("remat", True)
        accum_steps = extra_kwargs.pop("accum_steps", 1)
        astate = abstract_train_state(model, opt)
        step_fn = make_step_fn(
            model, policy, opt, lambda s: 1e-3, remat=remat,
            accum_steps=accum_steps, mesh=plan.mesh,
            compress_axis=compress_axis,
            loss_kwargs={"dtype": ACT_DTYPE, "act_sharding": act_sh,
                         "loss_chunks": 16, **extra_kwargs})
        jf = jit_step(step_fn, plan=plan, abstract_state=astate,
                      batch_shardings=plan.shardings(b_specs))
        lowered = jf.lower(astate, specs_in["batch"])
    elif shape.kind == "prefill":
        jf = jax.jit(
            lambda params, batch: model.prefill(params, batch, policy,
                                                dtype=ACT_DTYPE,
                                                **extra_kwargs),
            in_shardings=(plan.shardings(p_specs), plan.shardings(b_specs)))
        lowered = jf.lower(abstract_params, specs_in["batch"])
    else:
        c_specs = plan.cache_specs(specs_in["cache"])
        jf = jax.jit(
            lambda params, cache, batch: model.decode(params, cache, batch,
                                                      policy),
            in_shardings=(plan.shardings(p_specs), plan.shardings(c_specs),
                          plan.shardings(b_specs)),
            out_shardings=(None, plan.shardings(c_specs)),
            donate_argnums=(1,))
        lowered = jf.lower(abstract_params, specs_in["cache"],
                           specs_in["batch"])
    return lowered.compile(), abstract_params


def _metrics(compiled) -> dict:
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):       # older jax: one dict per device
        cost = cost[0] if cost else {}
    coll = collective_bytes(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": {k: coll[k] for k in coll if k != "counts"},
            "coll_counts": coll["counts"]}


def _combine(stem, bodies):
    """corrected = stem + sum_i n_i * body_i  (elementwise over metrics)."""
    out = {"flops": stem["flops"], "bytes": stem["bytes"],
           "coll": dict(stem["coll"]),
           "coll_counts": dict(stem["coll_counts"])}
    for n, b in bodies:
        out["flops"] += n * b["flops"]
        out["bytes"] += n * b["bytes"]
        for k in out["coll"]:
            out["coll"][k] += n * b["coll"][k]
        for k in out["coll_counts"]:
            out["coll_counts"][k] += n * b["coll_counts"][k]
    return out


def _diff(m2, m1):
    return {"flops": m2["flops"] - m1["flops"],
            "bytes": m2["bytes"] - m1["bytes"],
            "coll": {k: m2["coll"][k] - m1["coll"][k] for k in m2["coll"]},
            "coll_counts": {k: m2["coll_counts"][k] - m1["coll_counts"][k]
                            for k in m2["coll_counts"]}}


def _probe_corrected(cfg, shape, plan, policy, opt, sp=True,
                     log=lambda *a: None, extra_kwargs=None):
    """Scan-corrected per-device metrics via unrolled 1/2-layer probes."""
    def probe(**over):
        pc = dataclasses.replace(cfg, unroll_scan=True, **over)
        t0 = time.time()
        compiled, _ = _compile(pc, shape, plan, policy, opt, sp=sp,
                               extra_kwargs=extra_kwargs)
        log(f"    probe {over} compiled in {time.time()-t0:.0f}s")
        return _metrics(compiled)

    if cfg.family == "audio":
        m11 = probe(n_layers=1, enc_layers=1)
        m21 = probe(n_layers=1, enc_layers=2)
        m12 = probe(n_layers=2, enc_layers=1)
        enc_b, dec_b = _diff(m21, m11), _diff(m12, m11)
        stem = _combine(m11, [(-1, enc_b), (-1, dec_b)])
        return _combine(stem, [(cfg.enc_layers, enc_b),
                               (cfg.n_layers, dec_b)])
    if cfg.family == "hybrid":
        p = cfg.hybrid_period
        m1 = probe(n_layers=p)
        m2 = probe(n_layers=2 * p)
        body = _diff(m2, m1)
        stem = _combine(m1, [(-1, body)])
        return _combine(stem, [(cfg.n_layers // p, body)])
    m1 = probe(n_layers=1)
    m2 = probe(n_layers=2)
    body = _diff(m2, m1)
    stem = _combine(m1, [(-1, body)])
    return _combine(stem, [(cfg.n_layers, body)])


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             policy: QuantPolicy | None = None, mesh=None,
             correct_scan: bool = True, sp: bool = True,
             verbose: bool = True, extra_kwargs: dict | None = None) -> dict:
    """Lower + compile one cell; return the roofline record."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    policy = policy or QuantPolicy.fqt("bhq", 5, backend="native",
                                       bhq_block=1024)
    mesh = mesh if mesh is not None else make_production_mesh(
        multi_pod=multi_pod)
    n_chips = 1
    for v in mesh.shape.values():
        n_chips *= v
    plan = make_plan(mesh)
    opt = sgd(momentum=0.9)
    log = (lambda *a: print(*a, flush=True)) if verbose else (lambda *a: None)

    t0 = time.time()
    with mesh:
        compiled, aparams = _compile(cfg, shape, plan, policy, opt, sp=sp,
                                     extra_kwargs=extra_kwargs)
        t_full = time.time() - t0
        raw = _metrics(compiled)
        mem = compiled.memory_analysis()
        if correct_scan:
            m = _probe_corrected(cfg, shape, plan, policy, opt, sp=sp,
                                 log=log, extra_kwargs=extra_kwargs)
        else:
            m = raw

    n_params = count_params(aparams)
    n_tokens = (shape.global_batch * shape.seq_len
                if shape.kind in ("train", "prefill") else shape.global_batch)
    mf = model_flops(n_params, n_tokens,
                     "train" if shape.kind == "train" else "fwd",
                     active_frac=active_param_frac(cfg))
    terms = roofline_terms(m["flops"], m["bytes"], m["coll"]["total"],
                           backend=policy.backend)
    hbm_gb = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
              + mem.output_size_in_bytes - mem.alias_size_in_bytes) / 2**30
    record = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": shape.kind, "n_chips": n_chips, "n_params": n_params,
        "active_frac": active_param_frac(cfg),
        "per_device": {
            "flops": m["flops"], "bytes_accessed": m["bytes"],
            "collective_bytes": m["coll"]["total"],
            "collectives": {k: v for k, v in m["coll"].items()
                            if k != "total"},
            "collective_counts": m["coll_counts"],
            "raw_uncorrected": {"flops": raw["flops"], "bytes": raw["bytes"],
                                "collective_bytes": raw["coll"]["total"]},
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "hbm_gib": round(hbm_gb, 2),
        },
        "model_flops_global": mf,
        "useful_flops_ratio": (mf / n_chips / m["flops"]) if m["flops"] else None,
        "roofline": terms,
        "compile_s": round(t_full, 1),
        "scan_corrected": correct_scan,
        "seq_parallel": sp,
    }
    if verbose:
        log(f"[dryrun] {arch:22s} {shape_name:12s} {record['mesh']:8s} ok "
            f"c={terms['compute_s']*1e3:8.1f}ms m={terms['memory_s']*1e3:8.1f}ms "
            f"n={terms['collective_s']*1e3:8.1f}ms dom={terms['bottleneck']:10s} "
            f"hbm={hbm_gb:6.2f}GiB useful={record['useful_flops_ratio'] or 0:.3f} "
            f"(compile {t_full:.0f}s)")
    return record


def main(argv=None):
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi",
                                                       "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--quant", default="bhq")
    ap.add_argument("--grad-bits", type=int, default=5)
    ap.add_argument("--backend", default="native",
                    choices=["simulate", "native", "pallas"],
                    help="quantized-GEMM execution backend (core/backend.py)")
    ap.add_argument("--no-sp", dest="sp", action="store_false")
    ap.add_argument("--no-correct", dest="correct", action="store_false")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args(argv)

    os.makedirs(args.out, exist_ok=True)
    archs = ARCH_NAMES if args.arch == "all" else args.arch.split(",")
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    policy = QuantPolicy.fqt(args.quant, args.grad_bits,
                             backend=args.backend, bhq_block=1024)

    failures = []
    for arch in archs:
        cfg = get_config(arch)
        shapes = ([s.name for s in shape_grid(cfg)]
                  if args.shape == "all" else args.shape.split(","))
        for shape_name in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape_name}__{'multi' if mp else 'single'}"
                path = os.path.join(args.out, tag + ".json")
                if args.skip_existing and os.path.exists(path):
                    print(f"[dryrun] skip {tag} (exists)", flush=True)
                    continue
                try:
                    # roofline table is single-pod; multi-pod proves the pod
                    # axis shards (compile-only, no probes)
                    rec = run_cell(arch, shape_name, multi_pod=mp,
                                   policy=policy, sp=args.sp,
                                   correct_scan=(args.correct and not mp))
                    with open(path, "w") as f:
                        json.dump(rec, f, indent=1)
                except Exception as e:
                    traceback.print_exc()
                    failures.append((tag, f"{type(e).__name__}: {e}"))
                    print(f"[dryrun] FAIL {tag}", flush=True)
    if failures:
        print(f"\n[dryrun] {len(failures)} FAILURES:")
        for tag, err in failures:
            print(f"  {tag}: {err[:200]}")
        raise SystemExit(1)
    print("\n[dryrun] all cells compiled OK")


if __name__ == "__main__":
    main()
