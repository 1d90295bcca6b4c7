#!/usr/bin/env python3
"""Record a traced run of one cell with the program's call spans in its
profile, and read the metrics that need them.

  python3 benchmarks/chip/tests/record_trace.py --workload <cell> \\
      --seed <n> --seconds <s> --out DIR

It is ``run.py --trace 1 --trace-dir DIR`` with the compile's telemetry
session kept open through the window, so that the program's
``backend.execute`` / ``backend.bufs`` spans are on the profile's host
plane. DIR receives the profile and ``spans.json``, the session's span
events up to the end of the warm-up call. The last line of standard
output is the run's result with ``dispatch_ms``, ``idle_dispatch_pct``,
``xla_setup_s`` and the run-id offset bounds added under ``recorded``.
``tests/data/`` keeps one such recording.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import harness, runalign  # noqa: E402

NEW = ("dispatch_ms", "idle_dispatch_pct", "xla_setup_s")


def setup_spans(events):
    """The span events up to the end of the first call (the warm-up)."""
    calls = [e for e in events if e["name"] == "backend.execute"]
    if not calls:
        return []
    end = calls[0]["ts"] + calls[0]["dur"]
    return [e for e in events
            if e.get("ph") == "X" and e["ts"] + e["dur"] <= end]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    cell = harness.resolve(harness.load_spec(), args.workload)
    harness.configure_jax()
    import jax
    from repro.core import telemetry
    devices = jax.devices()
    peaks = harness.peaks_for(devices[0].device_kind)
    stop = telemetry.stop_trace
    # the harness closes the session after the warm-up: keep it open
    telemetry.stop_trace = lambda export=True: telemetry._TRACER
    try:
        result = harness.run_cell(cell, args.seed, args.seconds, True,
                                  devices=devices[:cell.chips], peaks=peaks,
                                  t0=T0, trace_dir=args.out)
    finally:
        telemetry.stop_trace = stop
    spans = setup_spans(stop(export=False).events)
    with open(Path(args.out) / "spans.json", "w") as f:
        json.dump(spans, f)

    ctx = SimpleNamespace(spans=spans, profile=args.out)
    recorded = {name: harness.load_module(
        harness.HERE / "metrics" / f"{name}.py").read(ctx) for name in NEW}
    profile = runalign.load(args.out)
    recorded["offset_bounds_ms"] = {
        dev: None if b is None else [1e-6 * b[0], 1e-6 * b[1]]
        for dev, b in ((dev, runalign.offset_bounds(runs))
                       for dev, runs in sorted(profile.runs.items()))}
    result["recorded"] = recorded
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
