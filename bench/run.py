"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Steps: turn on the compile cache inside the checkout; name the device and
refuse to run without an accelerator, with fewer chips than the cell asks
for, or on a ``device_kind`` missing from ``bench/peaks.json``; build the
weights from the seed; warm up the cell's own shapes; measure for
``--seconds``; check the timed path's output against the plain reference;
print one JSON line.  With ``--trace 0`` its metrics are the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read from a
short profiler window by ``bench/metrics/<metric>.py``.  The numbers
compared with the reference, each beside its limit, are the last lines on
standard error and the result line's last key, ``checks``.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse      # noqa: E402
import importlib.util  # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402
import tempfile      # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")


class NoChip(RuntimeError):
    """No accelerator, too few chips, or a device the peaks table lacks."""


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def cell_metrics(bench: dict, cell: dict, trace: bool) -> list:
    """The metric entries this cell reports in this kind of run."""
    e2e = bench["end_to_end"]
    if not trace:
        return [x for x in e2e
                if "workloads" not in x or cell["name"] in x["workloads"]]
    mine = {x["name"] for x in cell_metrics(bench, cell, False)}

    def reports(x):
        if "workloads" in x:
            return cell["name"] in x["workloads"]
        return x["moves"] in mine
    return [x for x in bench["per_layer"] if reports(x)]


def load_reader(name: str):
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def device_info(chips: int, require_chip: bool) -> dict:
    import jax
    devs = jax.devices()
    dev = devs[0]
    peaks_all = json.load(open(os.path.join(BENCH, "peaks.json")))
    if require_chip:
        if dev.platform not in ("tpu", "gpu"):
            raise NoChip(f"no accelerator: JAX platform {dev.platform!r}")
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
        if dev.device_kind not in peaks_all:
            raise NoChip(f"device_kind {dev.device_kind!r} is not in "
                         f"bench/peaks.json")
    peaks = peaks_all.get(dev.device_kind) or next(iter(peaks_all.values()))
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips, "devices": devs[:chips], "peaks": peaks}


def memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        try:
            stats = d.memory_stats() or {}
        except Exception:                 # the CPU backend reports none
            stats = {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


LOWERINGS = [0]      # jaxpr -> MLIR lowerings: one per compile or cache load


def count_lowerings() -> None:
    """Count every lowering from here on (``LOWERINGS[0]``): the runners
    record how many happen inside the measured window, which should be 0."""
    import jax

    def listen(event, duration, **kw):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            LOWERINGS[0] += 1
    jax.monitoring.register_event_duration_secs_listener(listen)


def enable_cache() -> str:
    import jax
    from repro.launch.device import enable_compile_cache
    where = enable_compile_cache()
    if where:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: str = ROOT, require_chip: bool = True, bench=None,
             overrides=None, t_start: float = None, keep_trace=None) -> dict:
    """One run; returns the result object (``overrides`` swaps in test
    configurations, a control policy or a planted fault)."""
    from bench import check, model
    from bench.gen import load as load_gen
    bench = bench or load_benchmark(root)
    cell = find(bench["workloads"], workload, "workload")
    find(bench["configs"], cell["config"], "config")     # declared there
    ov = overrides or {}
    conf = ov.get("conf") or model.load_json("configs", cell["config"])
    traffic = ov.get("traffic") or model.load_json("traffic", cell["traffic"])
    dev = device_info(cell["chips"], require_chip)
    gen = load_gen(traffic["kind"])
    runner = __import__(f"bench.{gen.MODE}", fromlist=["run"])
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    ctx = {"conf": conf, "traffic": traffic, "gen": gen, "seed": seed,
           "seconds": seconds, "trace": trace, "trace_dir": tdir,
           "t_start": T_START if t_start is None else t_start,
           "memory_peak": lambda: memory_peak(dev["devices"]),
           "lowerings": lambda: LOWERINGS[0],
           "keep_trace": keep_trace, **{k: v for k, v in ov.items()
                                        if k not in ("conf", "traffic")}}
    out = runner.run(ctx)
    limits = ov.get("limits") or check.load_limits(workload)
    correct, rows = check.judge(out["readings"], limits)
    metrics = {}
    run = {"cell": cell, "conf": conf, "traffic": traffic, "out": out,
           "peaks": dev["peaks"], "model": conf["model"]}
    for entry in cell_metrics(bench, cell, trace):
        if trace:
            value = load_reader(entry["name"])(run)
        else:
            value = out.get(entry["name"])
        if value is not None:
            metrics[entry["name"]] = {"value": float(value),
                                      "unit": entry["unit"]}
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"],
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": bool(correct), "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": metrics,
              "device": device}
    if trace and "trace" in out:
        from bench import trace as tr
        device["busy_s"] = out["trace"]["busy_s"]
        device["window_s"] = out["trace"]["window_s"]
        result["breakdown"] = {"device_ops": tr.top_ops(out["trace"]),
                               "idle_gaps": out["trace"]["gaps"]}
    result["checks"] = rows
    result["_out"] = out
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the raw .xplane.pb of a --trace 1 run here")
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"bench: no src/repro in {ROOT}: nothing to measure",
              file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    sys.path.insert(0, ROOT)
    cache = enable_cache()
    count_lowerings()
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), keep_trace=args.keep_trace)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    out = result.pop("_out")
    print(f"[bench] compile cache {cache}; record "
          + json.dumps({k: v for k, v in out.items()
                        if k not in ("trace", "traced", "readings")},
                       default=str), file=sys.stderr)
    for row in result["checks"]:
        print(f"check {row['name']} {row['value']!r} limit {row['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
