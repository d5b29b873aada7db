"""Training cells: the program's ``Engine`` on its normal path.

Set-up builds one ``Engine`` (compiled step, loader and prefetcher), gives it
a state made from the seed's weights, and drives it through its first three
steps with ``Engine.run``: those steps are what the reference checks (each
step's loss, the first gradient as AdamW received it — its first moment
over ``1 - b1`` — and each leaf's change after the three).  The same engine
then runs the measured window in chunks of steps of about
``chunk_seconds`` each; the window ends at the last chunk's
``block_until_ready``.
"""

from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp

from bench import check, model, trace
from bench.gen import train_batches
from bench.reference import dense_lm

PRIME_STEPS = 3          # steps the reference follows
RATE_STEPS = 2           # steps timed in set-up to size the chunks
REF_BLOCK_TOKENS = 2048  # tokens per block of the reference's batch


class TrainCell:
    """One compiled training engine, re-seedable for calibration runs."""

    def __init__(self, conf: dict, traffic: dict, policy_spec: dict = None,
                 step_wrap=None):
        from repro.engine import Engine
        self.conf, self.traffic = conf, traffic
        self.m = conf["model"]
        tr = conf["train"]
        self.cfg = model.arch_config(conf)
        self.policy = model.policy(policy_spec or tr["policy"])
        eng_kw = tr["engine"]
        self._seed_box = [0]
        self._batches = {}
        self.engine = Engine(
            self.cfg, self.policy, steps=eng_kw["schedule_steps"],
            batch_size=traffic["batch"], seq_len=traffic["seq"],
            lr=eng_kw["lr"], clip_norm=eng_kw["clip_norm"],
            remat=eng_kw["remat"], batch_fn=self._batch, log_fn=None,
            log_every=10 ** 9)
        hp = tr["adamw"]
        if hp != {"b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1}:
            raise ValueError("the engine's AdamW runs its defaults; the "
                             "configuration states other hyperparameters")
        if step_wrap is not None:          # a planted fault (tests, controls)
            self.engine.step_fn = step_wrap(self.engine.step_fn)
        self.tokens_per_step = traffic["batch"] * traffic["seq"]

    def _batch(self, step):
        seed = self._seed_box[0]
        fn = self._batches.get(seed)
        if fn is None:
            self._batches = {seed: train_batches.batch_fn(
                self.traffic, self.m, seed)}
            fn = self._batches[seed]
        return fn(step)

    def prime(self, seed: int) -> dict:
        """Seeded state, then the first ``PRIME_STEPS`` steps through
        ``Engine.run``; returns what the reference checks."""
        from repro.engine.state import TrainState
        eng = self.engine
        self._seed_box[0] = seed
        params = model.init_params(self.m, seed)
        model.check_layout(params, eng.model)
        opt_state = jax.jit(eng.opt.init)(params)
        eng.state = TrainState(params=params, opt_state=opt_state,
                               step=jnp.zeros((), jnp.int32),
                               rng=model.seed_key(seed + 1))
        del params, opt_state
        hist = eng.run(1)
        b1 = self.conf["train"]["adamw"]["b1"]
        grad = {k: v / (1.0 - b1) for k, v in
                check.leaf_norms(eng.state.opt_state["m"]).items()}
        hist += eng.run(PRIME_STEPS)
        p0 = model.init_params(self.m, seed)
        change = check.leaf_norms(jax.tree.map(jnp.subtract,
                                               eng.state.params, p0))
        del p0
        return {"losses": [loss for _, loss in hist[:PRIME_STEPS]],
                "grad": grad, "change": change}

    def chunk_steps(self, seconds: float) -> int:
        """Steps per chunk of about ``seconds``, from two timed steps."""
        eng = self.engine
        start = int(eng.state.step)
        t = time.perf_counter()
        eng.run(start + RATE_STEPS)
        jax.block_until_ready(eng.state)
        per_step = (time.perf_counter() - t) / RATE_STEPS
        return max(1, int(round(seconds / per_step)))

    def run_window(self, seconds: float, chunk: int) -> tuple:
        """Chunks of ``chunk`` steps until ``seconds`` have passed;
        returns (steps, elapsed seconds)."""
        eng = self.engine
        cur = int(eng.state.step)
        steps = 0
        t0 = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench.train_chunk"):
                eng.run(cur + chunk)
            cur += chunk
            steps += chunk
            if time.perf_counter() - t0 >= seconds:
                break
        jax.block_until_ready(eng.state)
        return steps, time.perf_counter() - t0

    def free(self):
        self.engine.state = None
        gc.collect()


def reference(conf: dict, traffic: dict, seed: int, batch=None) -> dict:
    """The plain f32 reference over the same weights and batches."""
    m = conf["model"]
    make = batch or train_batches.batch_fn(traffic, m, seed)
    batches = [make(i) for i in range(PRIME_STEPS)]
    rows = traffic["batch"]
    while rows > 1 and (rows * traffic["seq"] > REF_BLOCK_TOKENS
                        or traffic["batch"] % rows):
        rows -= 1
    params = model.init_params(m, seed)
    losses, first, last = dense_lm.train_steps(params, batches, m,
                                               conf["train"], rows,
                                               PRIME_STEPS)
    change = check.leaf_norms(jax.tree.map(jnp.subtract, last, params))
    return {"losses": losses, "grad": check.leaf_norms(first),
            "change": change}


def run(ctx: dict) -> dict:
    """One run of a training cell; see ``bench/run.py`` for ``ctx``."""
    conf, traffic = ctx["conf"], ctx["traffic"]
    cell = TrainCell(conf, traffic, policy_spec=ctx.get("policy"),
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
