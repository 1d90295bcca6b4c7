#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json on the chip this process finds.

  python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
      --seconds <run_seconds> --trace <0|1> [--trace-dir DIR]

Set-up (imports, device, inputs, ``pom.compile``, XLA compile or cache
load, one warm-up call) is timed from the start of this file; then the
cell's entry is called in a closed loop for ``--seconds``; then what the
window produced is compared with the configuration's plain reference.
The last line of standard output is the result as JSON; the numbers
compared, each beside its limit, are the last lines of standard error.
``--trace 1`` reports the per-layer metrics from a profiler trace of the
window (kept in ``--trace-dir`` when given) instead of the end-to-end
ones. With no TPU, fewer chips than the cell asks for, or a device kind
that ``peaks.json`` lacks, it exits 2 and prints no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None)
    args = ap.parse_args(argv)

    cell = harness.resolve(harness.load_spec(), args.workload)
    harness.configure_jax()
    import jax
    import repro  # noqa: F401  (the system under test must be here)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"run.py: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    try:
        peaks = harness.peaks_for(devices[0].device_kind)
    except KeyError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), devices=devices[:cell.chips],
                              peaks=peaks, t0=T0, trace_dir=args.trace_dir)
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
