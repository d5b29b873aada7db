"""The FQT GEMM kernels' share of their roofline in the traced steps: the
least time of the GEMM calls the steps make (``bench/readers.py``) over the
measured self time of those kernels, found by name
(``bench/attribution.py``).  Where the measured calls per kernel differ
from the calls the steps make, the reader returns None; both counts go to
standard error and to the run record (``out["gemm_calls"]``)."""

import sys

from bench import attribution, readers


def read(run):
    att = attribution.of_run(run)
    out = run["out"]
    steps = out.get("traced_steps")
    if att is None or not steps:
        return None
    tokens = run["traffic"]["batch"] * run["traffic"]["seq"]
    remat = run["conf"]["train"]["engine"]["remat"]
    per_step = readers.fqt_step_calls(run["model"], tokens, remat)
    expected = {k: steps * sum(c for _, c in v) for k, v in per_step.items()}
    measured = {k: att["kernel_calls"].get(k, 0) for k in per_step}
    out["gemm_calls"] = {"expected": expected, "measured": measured}
    print(f"[fqt_gemm_roofline.train] GEMM calls in {steps} traced steps: "
          f"measured {measured}, expected {expected}", file=sys.stderr)
    if measured != expected:
        return None
    least = steps * sum(
        calls * readers.roofline_seconds(k, shape, run["peaks"])
        for k, v in per_step.items() for shape, calls in v)
    spent = sum(att["kernel_s"][k] for k in per_step)
    return 100.0 * least / spent if spent > 0 else None
