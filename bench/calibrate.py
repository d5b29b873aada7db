"""Readings the correctness limits are set from, on the chip at a cell's
own size: sound runs of the program over many seeds, the control (the
program at the next lower precision, ``control_policy`` in the
configuration file) and the planted faults of ``bench/faults.py``.

    python3 bench/calibrate.py --workload tx.train.bhq5 --seeds 1,2,3 \
        --control-seeds 4,5,6 --fault-seeds 7,8,9

Training cells need no measured window: each seed primes the compiled
engine through its first three steps and runs the reference.  One JSON
line per reading; the limit files in ``bench/limits/`` are not read.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _ints(s):
    return [int(x) for x in s.split(",") if x]


def emit(**kw):
    print(json.dumps(kw, default=str), flush=True)


def train(bench, cell, seeds, control, fault_seeds):
    from bench import check, faults, model
    from bench import train as tr
    conf = model.load_json("configs", cell["config"])
    traffic = model.load_json("traffic", cell["traffic"])
    plans = [("sound", None, None, seeds),
             ("control", conf["train"]["control_policy"], None, control),
             ("half_batch", None, faults.half_batch, fault_seeds)]
    for kind, pol, wrap, ss in plans:
        if not ss:
            continue
        c = tr.TrainCell(conf, traffic, policy_spec=pol, step_wrap=wrap)
        for seed in ss:
            t = time.time()
            prog = c.prime(seed)
            c.free()
            ref = tr.reference(conf, traffic, seed)
            r = check.train_readings(prog, ref)
            emit(kind=kind, seed=seed, seconds=time.time() - t,
                 losses=prog["losses"], ref_losses=ref["losses"], **r)
        c.free()
        del c


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, default=[])
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--fault-seeds", type=_ints, default=[])
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)
    from bench import run
    run.enable_cache()
    bench = run.load_benchmark()
    cell = run.find(bench["workloads"], args.workload, "workload")
    run.device_info(cell["chips"], require_chip=True)
    train(bench, cell, args.seeds, args.control_seeds, args.fault_seeds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
