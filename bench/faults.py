"""Faults planted under the timed path, to show that the comparison catches
them (the benchmark's tests, and ``calibrate.py`` on the chip).

A step that returns its state unchanged; a step that leaves out half of
the batch and takes the mean over the rest.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def state_unchanged(step):
    def f(state, batch):
        _, mets = step(jax.tree.map(jnp.copy, state), batch)
        return state, mets
    return f


def half_batch(step):
    def f(state, batch):
        return step(state, jax.tree.map(lambda x: x[: x.shape[0] // 2],
                                        batch))
    return f


TRAIN = {"state_unchanged": state_unchanged, "half_batch": half_batch}
