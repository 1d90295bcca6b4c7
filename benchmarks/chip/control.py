#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from; the benchmark's
own runs do not run this.

  python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3

For each seed, in one process: the cell's input sets; the program's
error, through the cell's own compiled entry at the cell's own size, one
call per input set as the window makes them (the lower reading); and the
control's error, the configuration's plain reference computed in the
precision below the one it states, put in the program's place (the upper
reading). Both are compared with the reference by the harness's own
``compare``. Prints one line per seed and a JSON summary last.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import harness  # noqa: E402


def readings(cell, seeds, log=print):
    """{seed: (program checks, control checks)} over ``seeds``."""
    import jax
    entry = None if cell.builds else harness.build_entry(cell)
    ref, ctl = harness.reference_fn(cell), harness.control_fn(cell)
    out = {}
    for seed in seeds:
        sets = harness.make_inputs(cell, seed)
        # a build program's entry is made from, and holds, its inputs
        run = harness.build_entry(cell, sets) if cell.builds else entry
        writes = sorted(jax.eval_shape(ref, sets[0]))
        got = {i: {w: r[w] for w in writes}
               for i, r in enumerate(jax.block_until_ready(run(s))
                                     for s in sets)}
        del run
        low = {i: ctl(s) for i, s in enumerate(sets)}
        limits = cell.config["limits"]
        out[seed] = (harness.compare(got, sets, ref, limits),
                     harness.compare(low, sets, ref, limits))
        log(f"seed {seed}: program "
            + " ".join(f"{k}={v['value']!r}" for k, v in out[seed][0].items())
            + "; control "
            + " ".join(f"{k}={v['value']!r}" for k, v in out[seed][1].items()))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    args = ap.parse_args(argv)
    cell = harness.resolve(harness.load_spec(), args.workload)
    harness.configure_jax()
    seeds = [int(s) for s in args.seeds.split(",")]
    res = readings(cell, seeds)
    names = sorted(next(iter(res.values()))[0])
    summary = {n: {"program_max": max(p[n]["value"] for p, _ in res.values()),
                   "control_min": min(c[n]["value"] for _, c in res.values()),
                   "limit": next(iter(res.values()))[0][n]["limit"]}
               for n in names if n.startswith("max_rel_err.")}
    print(json.dumps({"workload": args.workload, "seeds": seeds,
                      "readings": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
