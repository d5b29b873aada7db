"""Training CLI on top of the engine (:mod:`repro.engine`).

The step/loop construction lives in ``repro.engine`` — this module only
parses arguments, resolves the policy, and drives ``Engine.run()``.

``train_loop`` is kept as a thin compatibility wrapper (same signature the
examples/tests/benches always used, plus ``mesh=``/``accum_steps=``/
``donate=``); ``make_train_step`` is gone — use
:func:`repro.engine.make_step_fn`, which takes/returns a
:class:`~repro.engine.TrainState`.
"""

from __future__ import annotations

import argparse

from ..configs import get_config
from ..core import QuantPolicy
from ..engine import Engine
from ..runtime import PreemptionHandler
from .device import device_summary, enable_compile_cache

__all__ = ["train_loop", "main"]


def train_loop(cfg, policy: QuantPolicy, *, steps: int, batch_size: int,
               seq_len: int, lr: float = 3e-3, opt_name: str = "adamw",
               ckpt_dir: str | None = None, ckpt_every: int = 100,
               log_every: int = 10, seed: int = 0, remat: bool = False,
               resume: bool = True, preemption: PreemptionHandler | None = None,
               log_fn=print, **engine_kwargs):
    """Compatibility wrapper over ``Engine(...).run()``.

    Returns ``(params, opt_state, history)`` like the pre-engine loop,
    except history now has one ``(step, loss)`` entry per *executed* step
    (the old loop sampled it at ``log_every``; logging is still sampled).
    Extra kwargs (``mesh=``, ``accum_steps=``, ``donate=``, ...) pass
    through to :class:`~repro.engine.Engine`.
    """
    eng = Engine(cfg, policy, steps=steps, batch_size=batch_size,
                 seq_len=seq_len, lr=lr, opt_name=opt_name,
                 ckpt_dir=ckpt_dir, ckpt_every=ckpt_every,
                 log_every=log_every, seed=seed, remat=remat, resume=resume,
                 preemption=preemption, log_fn=log_fn, **engine_kwargs)
    history = eng.run()
    return eng.state.params, eng.state.opt_state, history


def parse_override(text: str):
    """One ``--override`` CLI entry -> (path_regex, override-ish).

    Grammar (right-hand side of ``PATTERN=...``):
      ``exact``             pin every matching layer to full precision
      ``bits:B``            rewrite the bitwidth of every quantized role
      ``ROLE:QUANT[:B]``    set one role (fwd/fwd_act/fwd_weight/wgrad/agrad)
                            to a registered quantizer, e.g. ``agrad:bhq:4``
    e.g. ``--override 'lm_head|embed=exact' --override 'layers.mlp=agrad:bhq:4'``
    """
    pattern, sep, rhs = text.partition("=")
    if not sep or not pattern or not rhs:
        raise argparse.ArgumentTypeError(
            f"{text!r}: expected PATTERN=SPEC")
    if rhs == "exact":
        value = "exact"
    else:
        head, _, rest = rhs.partition(":")
        if head == "bits":
            value = int(rest)
        elif rest:
            value = {head: rest}      # "agrad:bhq:4" -> {"agrad": "bhq:4"}
        else:
            raise argparse.ArgumentTypeError(
                f"{text!r}: expected exact | bits:B | ROLE:QUANT[:B]")
    # validate eagerly (regex, role names, spec shape) so argparse turns a
    # bad value into a clean usage error, not a traceback at policy time
    from ..core.policy import _normalize_overrides
    try:
        _normalize_overrides(((pattern, value),))
    except ValueError as e:
        raise argparse.ArgumentTypeError(str(e)) from None
    return pattern, value


def parse_mesh(text: str):
    """``--mesh DATAxMODEL`` (e.g. ``2x2``) -> (data, model)."""
    try:
        data, model = (int(v) for v in text.lower().split("x"))
        if data < 1 or model < 1:
            raise ValueError
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"{text!r}: expected DATAxMODEL, e.g. 2x2") from None
    return data, model


def main(argv=None):
    ap = argparse.ArgumentParser(description="FQT training driver")
    ap.add_argument("--arch", default="statquant-tx")
    ap.add_argument("--smoke", action="store_true", default=True,
                    help="reduced config (default)")
    ap.add_argument("--full", dest="smoke", action="store_false",
                    help="the config's published widths")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8,
                    help="GLOBAL batch per optimizer step")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--opt", default="adamw", choices=["adamw", "sgd"])
    ap.add_argument("--quant", default="bhq", choices=["ptq", "psq", "bhq",
                                                       "qat", "exact"])
    ap.add_argument("--grad-bits", type=int, default=5)
    ap.add_argument("--backend", default="simulate",
                    choices=["simulate", "native", "pallas"],
                    help="quantized-GEMM execution backend (core/backend.py);"
                         " pallas = fused kernels for fwd AND both bwd GEMMs")
    ap.add_argument("--mesh", type=parse_mesh, default=None,
                    metavar="DATAxMODEL",
                    help="train sharded on a (data, model) mesh; needs that "
                         "many devices (CPU: XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--accum", type=int, default=1,
                    help="gradient-accumulation microbatches per step")
    ap.add_argument("--no-donate", dest="donate", action="store_false",
                    help="disable TrainState buffer donation (debugging)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--override", action="append", default=[],
                    metavar="PATTERN=SPEC", type=parse_override,
                    help="per-layer policy override (repeatable, applied in "
                         "order): PATTERN=exact | PATTERN=bits:B | "
                         "PATTERN=ROLE:QUANT[:B]  e.g. 'lm_head=exact' "
                         "'layers.mlp=agrad:bhq:4'")
    ap.add_argument("--override-file", default=None, metavar="PLAN.json",
                    help="load per-layer overrides from a JSON file — the "
                         "format `python -m repro.analysis plan --out` "
                         "writes (applied before any --override, so CLI "
                         "entries win)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    file_overrides = ()
    if args.override_file:
        import json

        from ..core.policy import overrides_from_json
        with open(args.override_file) as fh:
            doc = json.load(fh)
        try:
            file_overrides = overrides_from_json(doc)
        except (TypeError, ValueError, KeyError) as e:
            ap.error(f"--override-file {args.override_file}: {e}")
    overrides = tuple(file_overrides) + tuple(args.override)

    if args.quant == "exact":
        if overrides:
            ap.error("--override/--override-file have no effect with "
                     "--quant exact (the policy quantizes nothing)")
        policy = QuantPolicy.exact()
    elif args.quant == "qat":
        policy = QuantPolicy.qat(backend=args.backend, overrides=overrides)
    else:
        policy = QuantPolicy.fqt(args.quant, args.grad_bits, bhq_block=256,
                                 backend=args.backend, overrides=overrides)

    cfg = get_config(args.arch, smoke=args.smoke)
    print(f"[train] {cfg.name} {'smoke' if args.smoke else 'full'} widths; "
          f"{device_summary(policy)}")
    if overrides:
        from ..models import model_quant_paths
        print("[train] resolved per-layer quantizer specs:")
        for path, desc in policy.spec_table(model_quant_paths(cfg)):
            print(f"  {path:32s} {desc}")

    mesh = None
    if args.mesh is not None:
        import jax
        from .mesh import make_test_mesh
        data, model = args.mesh
        if data * model > jax.device_count():
            ap.error(f"--mesh {data}x{model} needs {data*model} devices, "
                     f"have {jax.device_count()} (set XLA_FLAGS="
                     f"--xla_force_host_platform_device_count={data*model})")
        mesh = make_test_mesh(data, model)

    prm = PreemptionHandler(install=True)
    eng = Engine(cfg, policy, steps=args.steps, batch_size=args.batch,
                 seq_len=args.seq, lr=args.lr, opt_name=args.opt,
                 accum_steps=args.accum, mesh=mesh, donate=args.donate,
                 ckpt_dir=args.ckpt_dir, preemption=prm)
    eng.run()


if __name__ == "__main__":
    main()
