"""The comparison that decides ``correct``: what the timed path produced
against the plain reference, each number beside its limit.

Limits live in ``bench/limits/<workload>.json`` as ``{number: limit}``, set
from the readings in ``PERF.md`` (sound runs of the program over a dozen
seeds below, the lower-precision control and the planted faults above).
Only the numbers that file names are compared; the others are reported.
"""

from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# a leaf whose reference gradient is under this share of the median leaf's
# moves by round-off alone under Adam, and is left out of the leaf checks
DEAD_LEAF = 1e-3


def load_limits(workload: str) -> dict:
    with open(os.path.join(HERE, "limits", f"{workload}.json")) as f:
        return json.load(f)


def leaf_norms(tree) -> dict:
    """{path: float norm} of a pytree of arrays (device or host)."""
    import jax
    import jax.numpy as jnp
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.device_get([jnp.sqrt(jnp.sum(jnp.square(
        jnp.asarray(x, jnp.float32)))) for _, x in flat])
    return {jax.tree_util.keystr(p): float(n)
            for (p, _), n in zip(flat, norms, strict=True)}


def leaf_gaps(prog: dict, ref: dict, ref_grad: dict) -> dict:
    """{leaf: | |prog| - |ref| | / max(|ref|, median |ref|)} over the live
    leaves: those whose reference gradient is at least ``DEAD_LEAF`` of the
    median leaf's."""
    med_g = float(np.median(list(ref_grad.values())))
    live = [k for k in ref if ref_grad[k] >= DEAD_LEAF * med_g]
    med = float(np.median([ref[k] for k in live]))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in live}


def train_readings(prog: dict, ref: dict) -> dict:
    """prog/ref: {"losses": [3], "grad": {leaf: norm}, "change": {leaf:
    norm}} -> the numbers: the worst relative loss gap of the three steps,
    and for the first gradient and the change after three steps the worst
    leaf's gap and the median leaf's (``PERF.md`` says which are compared
    and why)."""
    loss = max(abs(a - b) / abs(b) for a, b in
               zip(prog["losses"], ref["losses"], strict=True))
    out = {"loss_rel": loss, "_worst": {}}
    for what in ("grad", "change"):
        gaps = leaf_gaps(prog[what], ref[what], ref["grad"])
        worst = max(gaps, key=gaps.get)
        out[f"{what}_norm_gap"] = gaps[worst]
        out[f"{what}_norm_gap_median"] = float(np.median(list(gaps.values())))
        out["_worst"][what] = worst
    return out


def judge(readings: dict, limits: dict) -> tuple:
    """(correct, [{name, value, limit}, ...]) — every limited number must
    be finite and at most its limit."""
    rows, ok = [], True
    for name, limit in limits.items():
        value = readings.get(name)
        good = value is not None and np.isfinite(value) and value <= limit
        ok = ok and bool(good)
        rows.append({"name": name, "value": value, "limit": limit})
    return ok, rows
