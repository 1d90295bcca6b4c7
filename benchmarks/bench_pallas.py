"""Pallas serving-path suite: what the compiled/batched executors buy.

Three column groups, matching the serving stack's three claims:

* **serving** — per workload: the legacy per-statement interpret wall
  (``PallasProgram.__call__``), the whole-program ``jitted()`` wall (one
  traced XLA computation), and the compiled-Mosaic wall.  Where Pallas
  is interpreted (``repro.runtime.pallas_interpret()``: the CPU backend)
  the compiled columns are ``null`` — recorded, not faked.
* **batching** — per workload: B sequential interpret invocations vs one
  ``batched(B)`` dispatch (``jit(vmap(step))``), with throughputs and
  the speedup.  The acceptance gate: the batched dispatch beats the B
  sequential interpret runs on *every* workload.
* **scan** — ``conv_chain(scan_tail=K)`` trace+lower time with
  scan-over-layers on (``ScanRegion`` → ``lax.scan``) vs off
  (``POM_PALLAS_SCAN=0``, fully unrolled), plus the traced-program size
  and a bit-for-bit numerics identity check between the two executors.

``--check`` is the CI smoke: small sizes, asserting only the
machine-independent facts — batched speedup >= 1 on every workload,
scan == unrolled bit-for-bit, and the scan trace being no larger than
the unrolled trace.  Wall-clock columns are machine-dependent and not
gated.  The full run emits ``BENCH_pallas.json`` (atomic write) next to
the repo root.
"""
from __future__ import annotations

import os
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.core import caching
from repro.core.designdb import atomic_write_json
from repro.core.pipeline import compile as pcompile

from . import workloads

BATCH = 8           # batched(B) dispatch size (full run)
REPS = 3            # timed repetitions per executor; best-of is reported
SCAN_TAIL = 5       # isomorphic conv/relu layers appended to conv_chain


def _cases(small: bool) -> List[Tuple[str, Callable]]:
    # sizes chosen so the legacy interpret path stays tractable; the
    # full run only scales the squarish kernels up.
    n = 16 if small else 32
    m = 12 if small else 20
    return [
        ("gemm", lambda: workloads.gemm(n)),
        ("bicg", lambda: workloads.bicg(n)),
        ("gesummv", lambda: workloads.gesummv(n)),
        ("2mm", lambda: workloads.mm2(n)),
        ("3mm", lambda: workloads.mm3(n)),
        ("jacobi1d", lambda: workloads.jacobi1d(3 * n, 4)),
        ("jacobi2d", lambda: workloads.jacobi2d(m, 3)),
        ("heat1d", lambda: workloads.heat1d(3 * n, 4)),
        ("seidel", lambda: workloads.seidel(m, 3)),
        ("edge_detect", lambda: workloads.edge_detect(m)),
        ("gaussian", lambda: workloads.gaussian(m)),
        ("blur", lambda: workloads.blur(m)),
        ("conv", lambda: workloads.conv_nest("conv", 8, 4, 6, 6)),
    ]


def _inputs(fn, seed: int = 0) -> Dict[str, np.ndarray]:
    rng = np.random.default_rng(seed)
    written = {s.store.array.name for s in fn.statements}
    return {p.name: rng.standard_normal(p.shape).astype(np.float32)
            for p in fn.placeholders.values() if p.name not in written}


def _batch_inputs(fn, b: int, seed: int = 0) -> Dict[str, np.ndarray]:
    rng = np.random.default_rng(seed)
    written = {s.store.array.name for s in fn.statements}
    return {p.name: rng.standard_normal((b,) + tuple(p.shape))
            .astype(np.float32)
            for p in fn.placeholders.values() if p.name not in written}


def _block(out) -> None:
    import jax
    jax.block_until_ready(out)


def _best_wall(run: Callable[[], object], reps: int = REPS) -> float:
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        _block(run())
        best = min(best, time.perf_counter() - t0)
    return best


def _program(builder, interpret: Optional[bool] = None):
    caching.clear_all()
    caching.reset_counts()
    pf = builder()
    kw = {} if interpret is None else {"interpret": interpret}
    return pcompile(pf.fn, target="pallas", outputs=pf.outputs, **kw)


# --------------------------------------------------------------------------
# group 1: interpret vs jitted vs compiled wall
# --------------------------------------------------------------------------
def run_serving(small: bool = False) -> List[Dict]:
    from repro.runtime import pallas_interpret
    rows = []
    for name, build in _cases(small):
        prog = _program(build, interpret=True)
        args = _inputs(prog.fn)
        interp_s = _best_wall(lambda: prog(args))
        jit_s: Optional[float] = None
        if prog.traceable():
            run = prog.jitted()
            _block(run(args))                       # compile outside timing
            jit_s = _best_wall(lambda: run(args))
        compiled_s: Optional[float] = None
        if not pallas_interpret():
            cprog = _program(build, interpret=False)
            crun = cprog.jitted()
            _block(crun(args))
            compiled_s = _best_wall(lambda: crun(args))
        rows.append({
            "workload": name,
            "interpret_wall_s": round(interp_s, 6),
            "jit_wall_s": None if jit_s is None else round(jit_s, 6),
            "compiled_wall_s": (None if compiled_s is None
                                else round(compiled_s, 6)),
            "jit_speedup": (None if jit_s is None
                            else round(interp_s / max(jit_s, 1e-9), 1)),
        })
    return rows


# --------------------------------------------------------------------------
# group 2: batch-1 vs batch-N throughput
# --------------------------------------------------------------------------
def run_batching(small: bool = False, batch: int = BATCH) -> List[Dict]:
    rows = []
    for name, build in _cases(small):
        prog = _program(build, interpret=True)
        bargs = _batch_inputs(prog.fn, batch)
        lanes = [{k: v[i] for k, v in bargs.items()} for i in range(batch)]

        def seq():
            return [prog(lane) for lane in lanes]

        seq_s = _best_wall(seq)
        runner = prog.batched(batch)
        _block(runner(bargs))                       # compile outside timing
        bat_s = _best_wall(lambda: runner(bargs))
        rows.append({
            "workload": name,
            "batch": batch,
            "sequential_interpret_s": round(seq_s, 6),
            "batched_s": round(bat_s, 6),
            "seq_throughput_inv_s": round(batch / max(seq_s, 1e-9), 1),
            "batched_throughput_inv_s": round(batch / max(bat_s, 1e-9), 1),
            "speedup": round(seq_s / max(bat_s, 1e-9), 1),
        })
    return rows


# --------------------------------------------------------------------------
# group 3: scan-over-layers vs unrolled trace+lower time
# --------------------------------------------------------------------------
def _conv_chain_program(scan: bool, small: bool):
    import jax
    hw = 8 if small else 10
    tail = 3 if small else SCAN_TAIL
    old = os.environ.get("POM_PALLAS_SCAN")
    os.environ["POM_PALLAS_SCAN"] = "1" if scan else "0"
    try:
        prog = _program(lambda: workloads.conv_chain(
            hw=hw, chans=(3, 4, 4), scan_tail=tail))
    finally:
        if old is None:
            os.environ.pop("POM_PALLAS_SCAN", None)
        else:
            os.environ["POM_PALLAS_SCAN"] = old
    assert prog.traceable()
    spec = {ph.name: jax.ShapeDtypeStruct(ph.shape, np.float32)
            for ph in prog.fn.placeholders.values()}
    t0 = time.perf_counter()
    jaxpr = jax.make_jaxpr(prog._step)(spec)
    trace_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.jit(prog._step).lower(spec).compile()
    lower_s = time.perf_counter() - t0
    return prog, trace_s, lower_s, len(str(jaxpr)), tail


def run_scan(small: bool = False) -> Dict:
    scan_prog, scan_trace, scan_lower, scan_len, tail = \
        _conv_chain_program(True, small)
    unrl_prog, unrl_trace, unrl_lower, unrl_len, _ = \
        _conv_chain_program(False, small)
    args = _inputs(scan_prog.fn)
    a = scan_prog.jitted()(args)
    b = unrl_prog.jitted()(args)
    identical = (set(a) == set(b) and
                 all(np.array_equal(np.asarray(a[k]), np.asarray(b[k]))
                     for k in a))
    return {
        "workload": f"conv_chain(scan_tail={tail})",
        "scan_trace_s": round(scan_trace, 6),
        "unrolled_trace_s": round(unrl_trace, 6),
        "scan_lower_s": round(scan_lower, 6),
        "unrolled_lower_s": round(unrl_lower, 6),
        "trace_speedup": round(unrl_trace / max(scan_trace, 1e-9), 2),
        "scan_jaxpr_chars": scan_len,
        "unrolled_jaxpr_chars": unrl_len,
        "numerics_identical": identical,
    }


# --------------------------------------------------------------------------
def _host() -> Dict:
    import jax
    from repro.runtime import pallas_interpret
    return {
        "mosaic_supported": not pallas_interpret(),
        "local_devices": jax.local_device_count(),
        "jax": jax.__version__,
    }


def check(small: bool = True) -> int:
    """CI smoke: machine-independent facts only (tolerant of hosts
    without compiled Mosaic support — the compiled columns are null)."""
    failures = 0
    for row in run_batching(small=small, batch=4):
        if row["speedup"] < 1.0:
            print(f"FAIL batching {row['workload']}: batched(4) "
                  f"{row['batched_s']}s slower than 4 sequential "
                  f"interpret runs {row['sequential_interpret_s']}s")
            failures += 1
    scan = run_scan(small=small)
    if not scan["numerics_identical"]:
        print("FAIL scan: scanned executor != unrolled executor")
        failures += 1
    if scan["scan_jaxpr_chars"] > scan["unrolled_jaxpr_chars"]:
        print(f"FAIL scan: traced program grew "
              f"({scan['scan_jaxpr_chars']} > "
              f"{scan['unrolled_jaxpr_chars']} jaxpr chars)")
        failures += 1
    status = "OK" if not failures else "FAIL"
    print(f"bench_pallas --check {status}: "
          f"scan_trace={scan['scan_trace_s']}s "
          f"unrolled_trace={scan['unrolled_trace_s']}s "
          f"identical={scan['numerics_identical']}")
    return failures


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--check", action="store_true",
                    help="small smoke: batched(B) beats B sequential "
                         "interpret runs on every workload, scan == "
                         "unrolled bit-for-bit, scan trace no larger; "
                         "non-zero exit on failure")
    args = ap.parse_args()
    if args.check:
        raise SystemExit(1 if check() else 0)
    snap = {"suite": "pallas",
            "host": _host(),
            "serving": run_serving(),
            "batching": run_batching(),
            "scan": run_scan()}
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_pallas.json")
    atomic_write_json(path, snap)
    for row in snap["serving"]:
        print(f"pallas/serving,{row['workload']},"
              f"interpret={row['interpret_wall_s']}s;"
              f"jit={row['jit_wall_s']}s;"
              f"compiled={row['compiled_wall_s']};"
              f"jit_speedup={row['jit_speedup']}x")
    for row in snap["batching"]:
        print(f"pallas/batching,{row['workload']},B={row['batch']},"
              f"seq={row['sequential_interpret_s']}s;"
              f"batched={row['batched_s']}s;speedup={row['speedup']}x")
    s = snap["scan"]
    print(f"pallas/scan,{s['workload']},"
          f"trace={s['unrolled_trace_s']}s->{s['scan_trace_s']}s;"
          f"jaxpr={s['unrolled_jaxpr_chars']}->{s['scan_jaxpr_chars']};"
          f"identical={s['numerics_identical']}")


if __name__ == "__main__":
    main()
