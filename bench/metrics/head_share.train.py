"""Share of the traced training step's device self time in the output head:
the ops whose scope carries one of the head's markers, ``q[lm_head|role]``
(its three GEMM kernels, quantizers and epilogues), ``qk[lm_head]`` (its SR
keys) or ``fp[lm_head.ce]`` (the log-softmax and cross-entropy), over the
busy self time of ``bench/attribution.py``.  Scopes are found as the
attribution finds them.  None where no scope carries ``fp[lm_head.ce]``: the
program does not mark its loss, and the share would leave it out."""

import re
import sys

from bench import attribution

HEAD = re.compile(r"\b(?:qfp|qk|q|fp)\[lm_head[|.\]]")
CE = re.compile(r"\bfp\[lm_head\.ce\]")


def read(run):
    att = attribution.of_run(run)
    if att is None or att["self_s"] <= 0:
        return None
    tr = run["out"]["trace"]
    names = None
    if att["scopes_from"] != "trace":
        names = attribution.op_names(attribution.compiled_step(run))
    scopes = names.values() if names else [r["scope"] or ""
                                           for r in tr["ops"].values()]
    if not any(CE.search(s) for s in scopes):
        return None
    n = tr["devices"]
    head = {}
    for op, rec in tr["ops"].items():
        if attribution.base_name(op) in attribution.CONTAINERS:
            continue
        scope = rec["scope"] or ""
        if names:
            scope = names.get(attribution.hlo_name(op), scope)
        if HEAD.search(scope):
            cat = attribution.category(op, scope)
            head[cat] = head.get(cat, 0.0) + rec["seconds"] / n
    print(f"[head_share.train] head self seconds by category {head}",
          file=sys.stderr)
    return 100.0 * sum(head.values()) / att["self_s"]
