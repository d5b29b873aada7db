"""Chip benchmark of the FQT training step.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once on the accelerator it is started on
and prints one JSON result line.  Everything that belongs to one model
configuration (``configs/``), traffic mix (``traffic/`` read by a generator in
``gen/``), per-layer metric (``metrics/``) or kernel cost model
(``kernels/``) lives in a file of its own, found by name.
"""
