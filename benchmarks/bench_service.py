"""Compile-service replay suite: what the design database buys a server.

The resilience layer's service path (``pom.serve()`` /
``CompileService``) addresses finished designs by the name-canonical
content key of the program + design-relevant options
(``designdb.function_key``), so a repeat compile of a program any
process has seen before is served in O(lookup) — no graph build, no
polyhedral analysis, no search.  This suite measures that claim against
replay traffic shaped like a real service workload:

* **replay trace** — each workload compiled ``REPLAY`` times against one
  persistent db (fresh per run): the first request per workload is a
  cold miss, every repeat a hit.  Reported: hit rate, cold-compile p50,
  hit p50/p99, and the hit speedup (cold p50 / hit p50 — the acceptance
  gate is ≥ 50×, measured runs are O(1000×)).

``--check`` is the CI smoke: a small replay trace, asserting the exact
expected hit rate and the ≥ 50× hit speedup; exits non-zero on failure.
The full run emits ``BENCH_service.json`` (atomic write) next to the
repo root.  Latency columns are wall-clock and machine-dependent; the
``--check`` gate only tests the machine-independent facts (hit rate,
hit/cold ratio, hit-report identity).
"""
from __future__ import annotations

import os
from typing import Callable, Dict, List, Tuple

from repro.core import caching
from repro.core.pipeline import CompileService
from repro.core.designdb import DesignDB, atomic_write_json

from .workloads import bicg, gemm, mm3

REPLAY = 3          # requests per workload in the replay trace


def _trace_workloads(small: bool) -> List[Tuple[str, Callable]]:
    n = 64 if small else 256
    return [
        ("gemm", lambda: gemm(n).fn),
        ("bicg", lambda: bicg(n).fn),
        ("3mm", lambda: mm3(n // 2).fn),
    ]


def _percentile(xs: List[float], q: float) -> float:
    if not xs:
        return 0.0
    xs = sorted(xs)
    idx = min(len(xs) - 1, int(round(q * (len(xs) - 1))))
    return xs[idx]


def run_replay(small: bool = False) -> Dict:
    """Replay trace against one fresh db: per-request latency + hit rate."""
    caching.clear_all()
    caching.reset_counts()
    svc = CompileService(db=DesignDB())   # in-memory db, fresh per run
    cold, hot = [], []
    first_report: Dict[str, object] = {}
    identical = True
    for rep in range(REPLAY):
        for name, build in _trace_workloads(small):
            res = svc.compile_one(build(), max_parallel=64)
            (hot if res.from_db else cold).append(res.seconds)
            if name in first_report:
                identical = identical and res.report == first_report[name]
            else:
                first_report[name] = res.report
    n_req = REPLAY * len(_trace_workloads(small))
    cold_p50, hit_p50 = _percentile(cold, 0.5), _percentile(hot, 0.5)
    return {
        "requests": n_req,
        "hit_rate": round(len(hot) / n_req, 4),
        "expected_hit_rate": round((REPLAY - 1) / REPLAY, 4),
        "cold_p50_s": round(cold_p50, 6),
        "hit_p50_s": round(hit_p50, 6),
        "hit_p99_s": round(_percentile(hot, 0.99), 6),
        "hit_speedup": round(cold_p50 / max(hit_p50, 1e-9), 1),
        "hit_reports_identical": identical,
        "db_stats": {"hits": svc.stats.hits, "misses": svc.stats.misses,
                     "writes": svc.stats.writes,
                     "quarantined": svc.stats.quarantined},
    }


def check(small: bool = True) -> int:
    """CI smoke: machine-independent facts only."""
    failures = 0
    rep = run_replay(small=small)
    if rep["hit_rate"] != rep["expected_hit_rate"]:
        print(f"FAIL hit_rate {rep['hit_rate']} != "
              f"expected {rep['expected_hit_rate']}")
        failures += 1
    if rep["hit_speedup"] < 50.0:
        print(f"FAIL hit_speedup {rep['hit_speedup']}x < 50x")
        failures += 1
    if not rep["hit_reports_identical"]:
        print("FAIL db-hit report differs from cold compile")
        failures += 1
    status = "OK" if not failures else "FAIL"
    print(f"bench_service --check {status}: hit_rate={rep['hit_rate']} "
          f"hit_speedup={rep['hit_speedup']}x")
    return failures


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--check", action="store_true",
                    help="small-trace smoke: exact hit rate, >=50x hit "
                         "speedup, hit-report identity; non-zero exit on "
                         "failure")
    args = ap.parse_args()
    if args.check:
        raise SystemExit(1 if check() else 0)
    snap = {"suite": "service", "replay": run_replay()}
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_service.json")
    atomic_write_json(path, snap)
    rep = snap["replay"]
    print(f"service/replay,{rep['requests']} req,"
          f"hit_rate={rep['hit_rate']};cold_p50={rep['cold_p50_s']}s;"
          f"hit_p50={rep['hit_p50_s']}s;hit_p99={rep['hit_p99_s']}s;"
          f"hit_speedup={rep['hit_speedup']}x")


if __name__ == "__main__":
    main()
