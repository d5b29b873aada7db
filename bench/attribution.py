"""Device time of the traced step by layer of the program, by self time.

Reads the reduction of ``bench/trace.py`` (``ops``: per operation name its
count, device seconds and scope; ``busy_s``; ``devices``).  On a device's
``XLA Ops`` line a control-flow op (``while``, ``conditional``, ``call``)
spans the ops of its body, which are events of their own; every other op
contains none.  So an op's self time is its own length, a container's is
what its body leaves, and the self times of all ops add up to the busy
union.  The containers' part is that union less the leaves.

Each op goes to one category, first match wins:

* ``gemm`` — an FQT GEMM kernel, by its HLO instruction name: the Pallas
  kernels pass ``name=`` (``repro/kernels/names.py``), which XLA makes the
  instruction's name (``fused_qlhs_matmul.3``), and a fusion that runs the
  kernel together with the slice update of its output takes that name too;
* ``quant`` — an op whose scope (the HLO ``op_name``) carries the FQT
  seam's ``q[path|role]`` or ``qk[path]`` marker;
* ``attn`` — an op under ``fp[attn.sdpa]``, the full-precision attention;
* ``other`` — the rest, and the containers' own time.

An op's scope is its ``tf_op`` where the trace has one.  A TPU trace names
each op by its HLO text and carries no ``op_name``; there the scopes come
from the compiled step's text (``op_names``), compiled once more after the
window from the persistent cache.  The TPU compiler's scatter fusions keep
no ``op_name`` of their own; such a fusion takes the op_names of the
instructions fused into it.
"""

from __future__ import annotations

import re
import sys
import time
from typing import Optional

GEMM_KERNELS = ("fused_qlhs_matmul", "fused_qboth_tn_matmul",
                "fused_qlhs_packed_matmul", "q8_matmul", "packed_matmul")
KERNELS = GEMM_KERNELS + ("quantize_sr_rows", "quantize_sr_tensor",
                          "kv_gather_pages", "kv_dequant_rows")
CONTAINERS = ("while", "conditional", "call")
CATEGORIES = ("gemm", "quant", "attn", "other")
CONTAINER_ROW = "(while/conditional/call own time)"

_QUANT = re.compile(r"\bqk?\[")
_ATTN = re.compile(r"\bfp\[attn\.sdpa\]")
_MARKER = re.compile(r"\b(q|qk|qfp|fp)\[")
OVERLAP_TOLERANCE = 0.01     # leaves may exceed the busy union by this share
_COMPUTATION = re.compile(r"(?:ENTRY )?%([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"\s+(?:ROOT )?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")


def hlo_name(op: str) -> str:
    """The HLO instruction name of an op, whether the trace names it
    ``fusion.118`` or by its text ``%fusion.118 = f32[...] fusion(...)``."""
    m = re.match(r"%?([^\s=]+)", op)
    return m.group(1) if m else op


def base_name(op: str) -> str:
    """The instruction name without XLA's ``.N`` suffix."""
    return re.sub(r"\.\d+$", "", hlo_name(op))


def category(op: str, scope: str) -> str:
    if base_name(op) in GEMM_KERNELS:
        return "gemm"
    if _QUANT.search(scope or ""):
        return "quant"
    if _ATTN.search(scope or ""):
        return "attn"
    return "other"


def op_names(hlo: str) -> dict:
    """{instruction name: scope} of a compiled module's text: its
    ``op_name``, joined by ``;`` with the op_names that carry a marker of
    the instructions fused into it where its own carries none."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            cur = comps[head.group(1)] = []
            continue
        ins = _INSTRUCTION.match(line)
        if ins and cur is not None:
            on, calls = _OP_NAME.search(line), _CALLS.search(line)
            cur.append((ins.group(1), on.group(1) if on else "",
                        calls.group(1) if calls else None))
    inner = {}

    def marked(comp):
        if comp not in inner:
            inner[comp] = []
            for _, on, calls in comps.get(comp, ()):
                inner[comp] += ([on] if _MARKER.search(on) else []) + (
                    marked(calls) if calls else [])
        return inner[comp]

    scopes = {}
    for instructions in comps.values():
        for name, on, calls in instructions:
            if calls and not _MARKER.search(on):
                on = ";".join(dict.fromkeys([on] + marked(calls)))
            scopes[name] = on
    return scopes


def compiled_step(run: dict) -> str:
    """The text of the training cell's compiled step, compiled again from
    its abstract state and batch (the lowering equals the run's, so the
    persistent compile cache holds it)."""
    import jax

    from bench.train import TrainCell
    eng = TrainCell(run["conf"], run["traffic"]).engine
    batch = jax.eval_shape(lambda: eng.batch_fn(0))
    return eng.step_fn.lower(eng.abstract_state, batch).compile().as_text()


def attribute(reduced: dict, names: Optional[dict] = None,
              top: int = 10) -> Optional[dict]:
    """``{category: seconds}`` of self time per device, the kernel calls
    and self seconds per kernel name, and the ``top`` largest ``other``
    ops; None where the trace holds no device op, or where the leaves add
    up to more than the busy union (then ops overlap and self time cannot
    be told from the totals).  ``names`` (``op_names``) gives the scope of
    an op whose own carries no marker."""
    if not reduced or not reduced.get("devices") or not reduced["ops"]:
        return None
    n = reduced["devices"]
    seconds = dict.fromkeys(CATEGORIES, 0.0)
    calls, kernel_s, other = {}, {}, []
    leaves = marked = 0.0
    for op, rec in reduced["ops"].items():
        base = base_name(op)
        if base in CONTAINERS:
            continue
        s = rec["seconds"] / n
        leaves += s
        scope = rec["scope"] or ""
        if names and not _MARKER.search(scope):
            scope = names.get(hlo_name(op), scope)
        cat = category(op, scope)
        seconds[cat] += s
        if _MARKER.search(scope):
            marked += s
        if base in KERNELS:
            calls[base] = calls.get(base, 0) + rec["count"] // n
            kernel_s[base] = kernel_s.get(base, 0.0) + s
        if cat == "other":
            other.append([op, s])
    busy = reduced["busy_s"]
    containers = busy - leaves
    if containers < -OVERLAP_TOLERANCE * busy:
        print(f"[attribution] leaves {leaves!r} s exceed the busy union "
              f"{busy!r} s: ops overlap, no self time", file=sys.stderr)
        return None
    containers = max(containers, 0.0)
    seconds["other"] += containers
    other.append([CONTAINER_ROW, containers])
    other.sort(key=lambda row: -row[1])
    return {"seconds": seconds, "self_s": sum(seconds.values()),
            "marked_s": marked, "kernel_calls": calls, "kernel_s": kernel_s,
            "other_top": other[:top]}


def of_run(run: dict) -> Optional[dict]:
    """The attribution of a traced run, computed once and kept in the run
    record (``out["attribution"]``) beside the readers that use it; where
    the trace's ops carry no marker, with the compiled step's scopes."""
    out = run["out"]
    if "attribution" not in out:
        tr, names, source = out.get("trace"), None, "trace"
        if tr and tr.get("devices") and not any(
                _MARKER.search(r["scope"] or "") for r in tr["ops"].values()):
            t = time.perf_counter()
            names = op_names(compiled_step(run))
            source = f"compiled step ({time.perf_counter() - t:.1f} s)"
        att = attribute(tr, names)
        if att is not None:
            att["scopes_from"] = source
        out["attribution"] = att
    return out["attribution"]


def share(run: dict, cat: str) -> Optional[float]:
    """Percent of the step's device self time in category ``cat``; None
    where no op's scope carries a program marker (the scopes did not reach
    the trace, and every share would read 0)."""
    att = of_run(run)
    if att is None or att["self_s"] <= 0 or att["marked_s"] <= 0:
        return None
    return 100.0 * att["seconds"][cat] / att["self_s"]
