"""Profiler capture and the reduction of one trace to device numbers.

A traced window is bracketed by the host span ``bench.trace_window``; every
other host span the benchmark opens is named ``bench.<what>``.  The
reduction reads the ``.xplane.pb`` through ``jax.profiler.ProfileData`` and
gives, per device plane that ran operations:

* ``busy_s`` — length of the union of the operation intervals on the
  device's ``XLA Ops`` line inside the window, averaged over the devices;
* ``window_s`` — length of the window;
* ``ops`` — per operation name: count, device seconds and its scope
  (the op's ``tf_op`` / ``long_name`` metadata, where the trace has it);
* ``gaps`` — the longest idle gaps, each labelled by the innermost
  ``bench.*`` host span that covers its middle.
"""

from __future__ import annotations

import glob
import os
import shutil
from typing import Optional

WINDOW = "bench.trace_window"
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:"
SCOPE_STATS = ("tf_op", "long_name", "hlo_op")


def start(directory: str) -> None:
    import jax
    os.makedirs(directory, exist_ok=True)
    jax.profiler.start_trace(directory)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def find_xplane(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def _union(intervals):
    """Merged, sorted (start, end) list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _stat_str(stats, keys) -> str:
    d = {}
    for k, v in stats:
        d[k] = v
    for k in keys:
        if k in d and isinstance(d[k], str):
            return d[k]
    return ""


def reduce_profile(pd, max_gaps: int = 10) -> dict:
    """Reduce a ``ProfileData`` to the numbers above (seconds)."""
    host_spans = []
    window = None
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name == WINDOW:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif name.startswith("bench."):
                    host_spans.append((ev.start_ns,
                                       ev.start_ns + ev.duration_ns, name))
    if window is None:
        raise ValueError(f"the trace has no {WINDOW!r} host span")
    w0, w1 = window
    devices = []
    ops: dict = {}
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        intervals = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s = ev.start_ns
                e = s + ev.duration_ns
                if e <= w0 or s >= w1:
                    continue
                s, e = max(s, w0), min(e, w1)
                intervals.append((s, e))
                rec = ops.get(ev.name)
                if rec is None:
                    rec = ops[ev.name] = {"count": 0, "seconds": 0.0,
                                          "scope": _stat_str(ev.stats,
                                                             SCOPE_STATS)}
                rec["count"] += 1
                rec["seconds"] += (e - s) * 1e-9
        if intervals:
            devices.append((plane.name, _union(intervals)))
    if not devices:
        return {"busy_s": 0.0, "window_s": (w1 - w0) * 1e-9, "ops": {},
                "gaps": [], "devices": 0}
    busy = sum(sum(e - s for s, e in u) for _, u in devices) / len(devices)
    # idle gaps of the first device, labelled by the host's innermost span
    union = devices[0][1]
    gaps, prev = [], w0
    for s, e in union + [[w1, w1]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    labelled = []
    for s, e in gaps[:max_gaps]:
        mid = (s + e) // 2
        cover = [sp for sp in host_spans if sp[0] <= mid < sp[1]]
        label = (min(cover, key=lambda sp: sp[1] - sp[0])[2] if cover
                 else "no bench span")
        labelled.append([label, (e - s) * 1e-9])
    return {"busy_s": busy * 1e-9, "window_s": (w1 - w0) * 1e-9, "ops": ops,
            "gaps": labelled, "devices": len(devices)}


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path))


def reduce_dir(directory: str, keep: Optional[str] = None) -> dict:
    """Reduce the trace in ``directory``, optionally copy its ``.xplane.pb``
    to ``keep``, and delete the directory."""
    try:
        path = find_xplane(directory)
        if keep:
            os.makedirs(os.path.dirname(os.path.abspath(keep)), exist_ok=True)
            shutil.copyfile(path, keep)
        return reduce_file(path)
    finally:
        shutil.rmtree(directory, ignore_errors=True)


def top_ops(reduced: dict, n: int = 10) -> list:
    """[[name, seconds], ...] of the ``n`` operations that took longest."""
    items = sorted(reduced["ops"].items(), key=lambda kv: -kv[1]["seconds"])
    return [[name, rec["seconds"]] for name, rec in items[:n]]
