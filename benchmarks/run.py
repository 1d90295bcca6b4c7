"""Benchmark harness entry point: one suite per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (us_per_call is measured wall time
where measurable, estimated latency otherwise; 'derived' carries the
speedups/II/schedules the paper tables report).

  PYTHONPATH=src python -m benchmarks.run [--suite all|fast|<name>]
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

SUITES = [
    ("kernels", "bench_kernels", {}),            # measured wall time
    ("polybench", "bench_polybench", {}),        # Table III
    ("manual_vs_dse", "bench_manual_vs_dse", {}),  # Table IV
    ("scaling", "bench_scaling", {}),            # Fig 12
    ("stencils", "bench_stencils", {}),          # Table VII
    ("image", "bench_apps", {}),                 # Table V/VI (+ Fig 13 DNN)
    ("ablation", "bench_ablation", {}),          # Fig 14
    ("loc", "bench_loc", {}),                    # Fig 15
    ("roofline", "bench_roofline", {}),          # deliverable (g)
    ("dse_speed", "bench_dse_speed", {}),        # incremental-DSE speedup
]

# Suites still too slow for --suite fast.  The DNN conv-stack suite
# ("image") used to live here; the incremental DSE engine + layer-shape
# dedup brought it inside the fast budget.  If a suite misses the budget on
# your machine, `--suite <name>` still runs any single suite directly.
FAST_SKIP = set()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--suite", default="all")
    args = ap.parse_args()

    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    failures = 0
    for name, module, kwargs in SUITES:
        if args.suite not in ("all", "fast", name):
            continue
        if args.suite == "fast" and name in FAST_SKIP:
            continue
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{module}", fromlist=["csv_rows"])
            for line in mod.csv_rows(**kwargs):
                print(line)
            print(f"# suite {name} done in {time.time() - t0:.1f}s",
                  file=sys.stderr)
        except Exception:
            failures += 1
            print(f"# suite {name} FAILED:", file=sys.stderr)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
