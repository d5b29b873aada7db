"""TrainState: the single pytree a training step consumes and produces.

Bundling ``(params, opt_state, step, rng)`` into one registered-dataclass
pytree is what makes the production step shape possible:

  * ``jax.jit(..., donate_argnums=(0,))`` donates the *whole* state — params
    and optimizer moments are updated in place, halving peak HBM for the
    update (``analysis.tracing.check_step_donation`` checks that the
    donated buffers die);
  * ``state_shardings(plan, abstract)`` derives one sharding tree for the
    state from the :class:`~repro.sharding.ShardingPlan` param rules, so
    ``in_shardings == out_shardings`` and jit never inserts resharding
    collectives around the step;
  * ``step`` and ``rng`` live *inside* the checkpointed state, so a resumed
    run continues the exact same data stream and SR noise stream instead of
    replaying batch 0 with fresh keys.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["TrainState", "init_train_state", "abstract_train_state",
           "state_specs", "state_shardings"]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jax.Array                  # () int32 — optimizer step count
    rng: jax.Array                   # PRNG key; split every step, never reused

    # Checkpoints store the dict form: stable flat paths ("params/...",
    # "opt/...", "step", "rng") independent of this class's field order.
    def as_dict(self) -> dict:
        return {"params": self.params, "opt": self.opt_state,
                "step": self.step, "rng": self.rng}

    @staticmethod
    def from_dict(d: dict) -> "TrainState":
        return TrainState(params=d["params"], opt_state=d["opt"],
                          step=d["step"], rng=d["rng"])


def init_train_state(model, opt, seed: int = 0) -> TrainState:
    """Fresh state: params from ``model.init``, zeroed opt state, step 0,
    and an rng stream independent of the init key."""
    init_key, rng = jax.random.split(jax.random.PRNGKey(seed))
    params = model.init(init_key)
    return TrainState(params=params, opt_state=opt.init(params),
                      step=jnp.zeros((), jnp.int32), rng=rng)


def abstract_train_state(model, opt, seed: int = 0) -> TrainState:
    """ShapeDtypeStruct skeleton (no allocation) — for shardings, lowering,
    and checkpoint restore targets."""
    return jax.eval_shape(lambda: init_train_state(model, opt, seed))


def state_specs(plan, abstract_state: TrainState) -> TrainState:
    """PartitionSpec tree for a TrainState.

    Optimizer moments mirror the param tree path-for-path, so the plan's
    substring rules apply verbatim; ``step``/``rng`` are replicated scalars.
    """
    return TrainState(
        params=plan.param_specs(abstract_state.params),
        opt_state=plan.param_specs(abstract_state.opt_state),
        step=P(), rng=P())


def state_shardings(plan, abstract_state: TrainState) -> TrainState:
    """NamedSharding tree for jit in/out_shardings and checkpoint restore."""
    return plan.shardings(state_specs(plan, abstract_state))
