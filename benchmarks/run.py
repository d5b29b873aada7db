"""Benchmark harness — one suite per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Suites:

  fig3_*     quantizer variance vs bitwidth            (paper Fig. 3a / 5a)
  fig4_*     quantization bin-size distributions       (paper Fig. 4)
  table1_*   convergence vs (quantizer x bits)         (paper Table 1 proxy)
  wag_*      ultra-low-bit (W, A, G) sweep w/ theory overlay (DoReFa-style)
  serve_*    continuous-batching serving (fp32 vs int8 KV cache)

Select suites with ``python -m benchmarks.run fig3 table1 ...`` (default all).
"""

from __future__ import annotations

import sys
import traceback


def main() -> None:
    from . import bench_bins, bench_convergence, bench_serve, bench_variance

    suites = {
        "fig3": bench_variance.run,
        "fig4": bench_bins.run,
        "table1": bench_convergence.run,
        "wag": bench_convergence.wag_matrix,
        "serve": bench_serve.run,
    }
    selected = sys.argv[1:] or list(suites)
    print("name,us_per_call,derived")
    failures = []
    for name in selected:
        if name not in suites:
            print(f"# unknown suite {name}", file=sys.stderr)
            continue
        try:
            for row, us, derived in suites[name]():
                print(f"{row},{us:.2f},{derived:.6g}", flush=True)
        except Exception:
            traceback.print_exc()
            failures.append(name)
    if failures:
        raise SystemExit(f"benchmark suites failed: {failures}")


if __name__ == "__main__":
    main()
