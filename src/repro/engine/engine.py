"""Engine: one object that owns the production training loop.

Wires together everything previous layers built — the FQT step
(:mod:`repro.engine.step`), the sharding plan, donated-buffer compilation,
the data pipeline with prefetch, async checkpointing of the *whole*
TrainState, preemption handling, and straggler monitoring — behind::

    eng = Engine(cfg, policy, steps=1000, batch_size=32, seq_len=256,
                 mesh=make_test_mesh(2, 2), accum_steps=4,
                 ckpt_dir="/ckpts")
    history = eng.run()

Resume semantics: the checkpoint holds ``(params, opt_state, step, rng)``.
On restore, the data loader fast-forwards to ``step`` (batches are
seed-by-step, so the stream continues exactly where it stopped) and the rng
stream continues from the saved key — a run that is preempted and resumed is
bit-identical to one that never stopped.

``Engine.run`` opens host spans on the profiler's clock
(``jax.profiler.TraceAnnotation``), so a device trace says what the host was
doing in each idle gap: ``repro.engine.batch`` (waiting on the prefetcher),
``repro.engine.dispatch`` (enqueueing the step), ``repro.engine.drain``
(blocking on the pending losses), ``repro.engine.checkpoint`` (saving or
waiting on a save) and ``repro.engine.prefetch`` (starting or stopping the
prefetch thread).  With no profiler running a span costs about a
microsecond.
"""

from __future__ import annotations

import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from ..checkpoint import CheckpointManager
from ..core import QuantPolicy, resolve_interpret
from ..data import Prefetcher, ShardedLoader, make_batch_for
from ..models import build_model
from ..optim import Optimizer, adamw, cosine_schedule, sgd
from ..runtime import PreemptionHandler, StragglerMonitor
from ..sharding import make_plan
from .state import (TrainState, abstract_train_state, init_train_state,
                    state_shardings)
from .step import jit_step, make_step_fn

__all__ = ["Engine"]


class Engine:
    """Builds the compiled step once and runs the full training loop.

    batch_size is the *global* batch per optimizer step; with
    ``accum_steps=k`` the step consumes it as k sequential microbatches of
    ``batch_size // k`` (lax.scan, independent SR keys per microbatch).

    ``batch_fn(step) -> batch`` must be a *pure, side-effect-free function
    of step* (the repo's determinism contract, data/synthetic.py) — resume
    fast-forwards by re-seeding from ``state.step``, and on the mesh path
    ``batch_fn(0)`` is called once concretely at construction to derive
    batch shardings (that batch is discarded).  Stateful iterators cannot
    resume and are not supported.
    """

    def __init__(self, cfg, policy: QuantPolicy, *, steps: int,
                 batch_size: int, seq_len: int, lr: float = 3e-3,
                 opt_name: str = "adamw", opt: Optional[Optimizer] = None,
                 accum_steps: int = 1, mesh=None, remat: bool = False,
                 donate: bool = True, clip_norm: float = 1.0,
                 compress_axis: Optional[str] = None,
                 loss_kwargs: Optional[dict] = None,
                 ckpt_dir: Optional[str] = None, ckpt_every: int = 100,
                 keep: int = 3, log_every: int = 10, seed: int = 0,
                 resume: bool = True,
                 preemption: Optional[PreemptionHandler] = None,
                 straggler: Optional[StragglerMonitor] = None,
                 straggler_probe: Optional[Callable[[float], list]] = None,
                 batch_fn: Optional[Callable[[int], dict]] = None,
                 log_fn=print):
        if batch_size % accum_steps:
            raise ValueError(f"batch_size={batch_size} not divisible by "
                             f"accum_steps={accum_steps}")
        self.cfg = cfg
        self.policy = policy
        self.steps = steps
        self.seed = seed
        self.resume = resume
        self.log_every = log_every
        self.log_fn = log_fn or (lambda *a: None)
        self.preemption = preemption
        # Straggler detection needs the *fleet's* per-host step times — on a
        # real cluster the scheduler's heartbeats supply them via
        # ``straggler_probe(local_dt) -> [dt_host0, ...]``.  Without a probe
        # there is nothing meaningful to feed the monitor (a host can't see
        # the fleet median from its own clock), so it stays idle.
        self.straggler = straggler or StragglerMonitor(
            n_hosts=jax.process_count())
        self.straggler_probe = straggler_probe

        self.model = build_model(cfg)
        self.opt = opt or (adamw() if opt_name == "adamw"
                           else sgd(momentum=0.9))
        self.lr_fn = cosine_schedule(lr, steps,
                                     warmup_steps=max(steps // 20, 1))

        if (mesh is not None and policy.enabled and policy.backend == "pallas"
                and not resolve_interpret(policy.pallas_interpret)):
            raise ValueError(
                "the pallas backend cannot train on a mesh: Mosaic kernels "
                "are not partitioned by GSPMD; use backend='native' (XLA "
                "int8) or 'simulate' with mesh=")
        self.mesh = mesh
        self.plan = make_plan(mesh) if mesh is not None else None
        self.abstract_state = abstract_train_state(self.model, self.opt, seed)
        self.shardings = (state_shardings(self.plan, self.abstract_state)
                          if self.plan else None)

        self.batch_fn = batch_fn or (
            lambda s: make_batch_for(cfg, batch_size, seq_len,
                                     step=s, seed=seed))
        batch_sh = None
        if self.plan is not None:
            ab = jax.eval_shape(lambda: self.batch_fn(0))
            batch_sh = self.plan.shardings(self.plan.batch_specs(ab))
        self.loader = ShardedLoader(self.batch_fn, shardings=batch_sh)

        step_fn = make_step_fn(
            self.model, policy, self.opt, self.lr_fn, clip_norm=clip_norm,
            remat=remat, accum_steps=accum_steps, mesh=mesh,
            compress_axis=compress_axis, loss_kwargs=loss_kwargs)
        self.step_fn = jit_step(step_fn, plan=self.plan,
                                abstract_state=self.abstract_state,
                                batch_shardings=batch_sh, donate=donate)

        self.ckpt = (CheckpointManager(ckpt_dir, keep=keep)
                     if ckpt_dir else None)
        self.ckpt_every = ckpt_every
        self.state: Optional[TrainState] = None

    # -- state lifecycle ----------------------------------------------------
    def init_state(self) -> TrainState:
        def init():
            return init_train_state(self.model, self.opt, self.seed)
        if self.shardings is None:
            return init()
        # built in place with the plan's shardings: no device ever holds
        # the whole state (the same values — threefry is partitionable)
        return jax.jit(init, out_shardings=self.shardings)()

    def restore_state(self, step: Optional[int] = None) -> TrainState:
        """Restore the full TrainState (elastic: onto this engine's mesh,
        whatever mesh wrote the checkpoint).

        Pre-engine checkpoints ({params, opt} only, no step/rng leaves)
        migrate: step comes from the checkpoint index, the rng stream
        restarts (SR draws after resume differ from the unpreempted run —
        logged, since the bit-identical-resume guarantee needs a
        full-state checkpoint)."""
        step = step if step is not None else self.ckpt.latest_step()
        target = self.abstract_state.as_dict()
        sh = self.shardings.as_dict() if self.shardings is not None else None
        legacy = "step" not in self.ckpt.load_meta(step)["keys"]
        if legacy:
            target = {k: target[k] for k in ("params", "opt")}
            sh = sh and {k: sh[k] for k in ("params", "opt")}
        tree = self.ckpt.restore(step, target, shardings=sh)
        if legacy:
            self.log_fn(f"[engine] legacy checkpoint (no step/rng) at "
                        f"step {step}: resuming data stream, restarting "
                        f"rng stream")
            tree = {**tree, "step": jnp.asarray(step, jnp.int32),
                    "rng": jax.random.fold_in(
                        jax.random.PRNGKey(self.seed), step)}
        return TrainState.from_dict(tree)

    def _startup_state(self) -> TrainState:
        if self.ckpt and self.resume and self.ckpt.latest_step() is not None:
            state = self.restore_state()
            self.log_fn(f"[engine] resumed from step {int(state.step)}")
            return state
        return self.init_state()

    def _save(self, state: TrainState, asynchronous: bool = True):
        self.ckpt.save(int(state.step), state.as_dict(),
                       extra={"data_step": int(state.step)},
                       asynchronous=asynchronous)

    # -- the loop -----------------------------------------------------------
    def run(self, steps: Optional[int] = None):
        """Train until ``steps``; returns history [(step, loss), ...] with
        one entry per executed step.

        (The pre-engine loop sampled history at ``log_every``; here only
        *logging* is sampled — losses are kept as device scalars during the
        loop so the host syncs only on log/checkpoint steps, preserving
        async dispatch.)"""
        steps = steps if steps is not None else self.steps
        state = self.state if self.state is not None else self._startup_state()
        start = int(state.step)
        with TraceAnnotation("repro.engine.prefetch"):
            pf = Prefetcher(self.loader, depth=2, start_step=start)
        history = []                      # (step, float loss)
        pending = []                      # (step, device-scalar loss)

        def drain():
            # convert at points that sync anyway, so the steady-state loop
            # never blocks on a loss transfer and buffers don't pile up
            with TraceAnnotation("repro.engine.drain"):
                history.extend((s, float(l)) for s, l in pending)
            pending.clear()

        t0 = time.time()
        try:
            for step in range(start, steps):
                t_step = time.time()
                with TraceAnnotation("repro.engine.batch"):
                    batch = pf.next()
                with TraceAnnotation("repro.engine.dispatch"):
                    state, mets = self.step_fn(state, batch)
                pending.append((step, mets["loss"]))
                if self.straggler_probe is not None:
                    self.straggler.record(
                        self.straggler_probe(time.time() - t_step))
                    slow = self.straggler.stragglers()
                    if slow:
                        self.log_fn(f"[engine] stragglers: {slow}")
                if step % self.log_every == 0 or step == steps - 1:
                    drain()
                    self.log_fn(
                        f"[engine] step {step:5d} "
                        f"loss {history[-1][1]:8.4f} "
                        f"gnorm {float(mets['grad_norm']):8.3f} "
                        f"({time.time()-t0:.1f}s)")
                if self.ckpt and (step + 1) % self.ckpt_every == 0:
                    drain()
                    with TraceAnnotation("repro.engine.checkpoint"):
                        self._save(state)
                if self.preemption and self.preemption.should_stop:
                    if self.ckpt:
                        # drain any in-flight async save first — the sync
                        # save path does not, and both write step_<N>.tmp
                        with TraceAnnotation("repro.engine.checkpoint"):
                            self.ckpt.wait()
                            if (step + 1) % self.ckpt_every != 0:
                                self._save(state, asynchronous=False)
                    self.log_fn(f"[engine] preempted at step {step + 1}; "
                                f"checkpointed")
                    break
        finally:
            with TraceAnnotation("repro.engine.prefetch"):
                pf.stop()
            if self.ckpt:
                with TraceAnnotation("repro.engine.checkpoint"):
                    self.ckpt.wait()
            self.state = state
            drain()
        return history
