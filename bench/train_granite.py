"""Training cells of a Granite configuration: ``bench/train.py``'s runner
with the two things a tied head and Granite's scalars change, the weight
layout (no ``lm_head`` leaf under ``tie_embeddings``) and the plain
reference (``bench/reference/granite_lm.py``).  The ``Engine``, its
lowering, the steps the reference follows, the window and the output are
``train.py``'s.

The reference reads the scalars from the configuration file and the program
from its registry entry (``bench/model.py:arch_config``), so the cell refuses
to start where the two disagree, or where the program's weight layout is not
the tied one, before anything compiles.
"""

from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp

from bench import check, model, trace, train
from bench.gen import train_batches
from bench.reference import granite_lm

SCALARS = ("embedding_multiplier", "residual_multiplier",
           "attention_multiplier", "logits_scaling", "tie_embeddings")


def init_params(m: dict, seed: int):
    """The benchmark's seeded weights in the tied layout: the dense
    decoder's leaves, less ``lm_head`` under ``tie_embeddings``."""
    params = model.init_params(m, seed)
    if m["tie_embeddings"]:
        params = {k: v for k, v in params.items() if k != "lm_head"}
    return params


def check_program(conf: dict, cfg) -> None:
    """The program's ``ArchConfig`` carries the file's scalars, and its
    model initialises the layout the benchmark builds."""
    from repro.models import build_model
    m = conf["model"]
    for key in SCALARS:
        if not hasattr(cfg, key):
            raise ValueError(f"{conf['arch']}: the program's {key} is "
                             f"missing, the configuration file's {m[key]!r}")
        if getattr(cfg, key) != m[key]:
            raise ValueError(f"{conf['arch']}: the program's {key} is "
                             f"{getattr(cfg, key)!r}, the configuration "
                             f"file's {m[key]!r}")
    model.check_layout(jax.eval_shape(lambda: init_params(m, 0)),
                       build_model(cfg))


class GraniteCell(train.TrainCell):
    def __init__(self, conf: dict, traffic: dict, policy_spec: dict = None,
                 step_wrap=None):
        check_program(conf, model.arch_config(conf))
        super().__init__(conf, traffic, policy_spec, step_wrap)

    def prime(self, seed: int) -> dict:
        """``TrainCell.prime`` on the tied weights."""
        from repro.engine.state import TrainState
        eng = self.engine
        self._seed_box[0] = seed
        params = init_params(self.m, seed)
        opt_state = jax.jit(eng.opt.init)(params)
        eng.state = TrainState(params=params, opt_state=opt_state,
                               step=jnp.zeros((), jnp.int32),
                               rng=model.seed_key(seed + 1))
        del params, opt_state
        hist = eng.run(1)
        b1 = self.conf["train"]["adamw"]["b1"]
        grad = {k: v / (1.0 - b1) for k, v in
                check.leaf_norms(eng.state.opt_state["m"]).items()}
        hist += eng.run(train.PRIME_STEPS)
        p0 = init_params(self.m, seed)
        change = check.leaf_norms(jax.tree.map(jnp.subtract,
                                               eng.state.params, p0))
        del p0
        return {"losses": [loss for _, loss in hist[:train.PRIME_STEPS]],
                "grad": grad, "change": change}


def reference(conf: dict, traffic: dict, seed: int) -> dict:
    """``granite_lm`` over the same weights and batches."""
    m = conf["model"]
    make = train_batches.batch_fn(traffic, m, seed)
    batches = [make(i) for i in range(train.PRIME_STEPS)]
    rows = traffic["batch"]
    while rows > 1 and (rows * traffic["seq"] > train.REF_BLOCK_TOKENS
                        or traffic["batch"] % rows):
        rows -= 1
    params = init_params(m, seed)
    losses, first, last = granite_lm.train_steps(params, batches, m,
                                                 conf["train"], rows,
                                                 train.PRIME_STEPS)
    change = check.leaf_norms(jax.tree.map(jnp.subtract, last, params))
    return {"losses": losses, "grad": check.leaf_norms(first),
            "change": change}


def run(ctx: dict) -> dict:
    """One run of a Granite training cell; see ``bench/run.py`` for
    ``ctx``."""
    conf, traffic = ctx["conf"], ctx["traffic"]
    cell = GraniteCell(conf, traffic, policy_spec=ctx.get("policy"),
                       step_wrap=ctx.get("step_wrap"))
    seed = ctx["seed"]
    prog = cell.prime(seed)
    chunk = cell.chunk_steps(traffic["chunk_seconds"])
    setup_s = time.time() - ctx["t_start"]

    lowered = ctx["lowerings"]()
    steps, elapsed = cell.run_window(ctx["seconds"], chunk)
    out = {"setup_s": setup_s, "steps": steps, "window_s": elapsed,
           "lowerings_in_window": ctx["lowerings"]() - lowered,
           "tokens": steps * cell.tokens_per_step,
           "train_tokens_per_s": steps * cell.tokens_per_step / elapsed,
           "attempted": steps, "failed": 0, "chunk_steps": chunk,
           "losses": prog["losses"]}
    if ctx["trace"]:
        tdir = ctx["trace_dir"]
        trace.start(tdir)
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            t_steps, _ = cell.run_window(1e-9, chunk)
        trace.stop()
        out["traced_steps"] = t_steps
        out["trace"] = trace.reduce_dir(tdir, keep=ctx.get("keep_trace"))
    out["memory_peak_bytes"] = ctx["memory_peak"]()
    cell.free()
    del cell
    gc.collect()
    ref = reference(conf, traffic, seed)
    out["readings"] = check.train_readings(prog, ref)
    return out
