"""DSE-speed suite: measures what the incremental engine buys, per workload.

``auto_dse`` is the pipeline entry point for search (its two stages run
as ``pipeline.PassManager`` passes with counter-neutral per-stage
verifiers), so this suite measures the full pipeline-routed engine; the
evaluation counts below are unchanged from the pre-pipeline engine by
construction.

For each workload the suite runs ``auto_dse`` twice on fresh builds:

  * **baseline** — every cache disabled (``repro.core.caching.disabled()``
    + ``HlsModel(cache=False)``), i.e. the pre-incremental engine;
  * **incremental** — caches enabled, started cold
    (``caching.clear_all()``), so no state leaks between workloads.

and reports wall-seconds plus two evaluation counters:

  * ``full_node_evals`` — per-node cost computations that performed a fresh
    recurrence-II/dependence analysis (every node computation in the
    baseline engine is one of these);
  * ``analysis_evals`` — all fresh full-cost analyses run by the engine:
    self-dependence derivations, legality checks, trip-count (FM bound)
    derivations, and the recurrence-II computations above.  This is the
    suite's headline "cost-model evaluation count": it counts exactly the
    polyhedral work the pre-PR engine redid from scratch per candidate.

Counters, unlike wall time, are stable on shared hardware; both engines
must produce identical action logs and DesignReports (checked here and in
``tests/test_incremental_dse.py``).

Bound-and-confirm columns: ``incremental_confirmed_evals`` /
``incremental_pruned_candidates`` count the rung candidates that reached
a full ``node_report`` confirmation vs those the admissible closed-form
latency lower bound pruned (``POM_BOUND_PRUNE``); each strategy row's
telemetry carries the same pair.  The ``--check`` gate fails on a >10%
confirmed-eval regression alongside the analysis-eval gate.

The ``conv_stack`` workload mirrors ``bench_apps.run_dnn``'s per-layer
pattern (unoptimized report + full-budget DSE + split-budget DSE over a
ResNet-style stack with repeated layer shapes) — the exact load that made
the ``image`` suite too slow for fast mode before this engine existed.
``conv_chain`` is the same stack as ONE multi-statement function, the
task-level-pipelining workload.

Dataflow columns: per workload, the DSE'd designs are re-aggregated under
``dataflow=False`` (sequential sum of fusion groups) and ``dataflow=True``
(streaming task graph), recording summed latency and BRAM18 per mode plus
the number of applied regions — the latency/BRAM price of task overlap.

Search-strategy columns: each workload is additionally searched with
``greedy``, ``beam:2``, ``beam:4`` and ``beam:8``, recording wall-seconds *and* best design cost (summed report latency),
so the snapshot tracks search **quality** alongside search speed.  Each
strategy wall is the best (min) of ``STRATEGY_REPEATS`` cold-cache
repeats, interleaved round-robin across strategies so machine drift
lands evenly — and the ``beam_scaling`` ratio (``beam8`` wall /
``greedy`` wall) is the headline number for the cross-state wave:
instead of the naive ~8x it sits near 1x where sibling states collapse
onto shared rungs (gemm) and ~2-3x where the eight states genuinely
diverge (3mm evaluates ~6x the rungs of ``beam:1``; the
transformed-node/whole-design caches absorb the rest).  The
``fusion_prepass`` section runs graph-level fusion
(``graph_passes=("fuse",)``) ahead of DSE on the multi-statement
workloads and records the final cost against the default flow, where
stage 1 distributes conflicting fusion groups and conservatively
re-fuses (the paper's split-interchange-merge).

Emits ``BENCH_dse_speed.json`` next to the repo root for snapshot diffing.
"""
from __future__ import annotations

import json
import os
import time
from typing import Callable, Dict, List, Tuple

from repro.core import caching
from repro.core.cost_model import XC7Z020, HlsModel
from repro.core.designdb import atomic_write_json
from repro.core.dse import auto_dse

from .workloads import bicg, conv_chain, conv_nest, gemm, mm2, mm3

# ResNet18-style critical-layer sub-stack (out_ch, in_ch, H=W) with the
# repetition pattern real nets have; sized to keep the suite fast.
CONV_STACK: List[Tuple[int, int, int]] = (
    [(64, 3, 32)] + [(64, 64, 16)] * 4 + [(128, 64, 8)] + [(128, 128, 8)] * 3
)


def _conv_builders() -> List[Callable]:
    return [
        (lambda oc=oc, ic=ic, hw=hw, i=i:
         conv_nest(f"conv{i}", oc, ic, hw, hw).fn)
        for i, (oc, ic, hw) in enumerate(CONV_STACK)
    ]


def _run_workload(builders: List[Callable], max_parallel: int,
                  dnn_style: bool) -> Dict:
    """One engine pass over a workload's functions; returns measurements."""
    half = {k: v / 2 for k, v in XC7Z020.items()}
    t0 = time.perf_counter()
    full_evals = 0
    analytic_evals = 0
    confirmed = 0
    pruned = 0
    actions: List[List[str]] = []
    latencies: List[int] = []
    for build in builders:
        runs = [(XC7Z020, True)]
        if dnn_style:
            runs = [(XC7Z020, False), (XC7Z020, True), (half, True)]
        for resources, do_dse in runs:
            fn = build()
            model = HlsModel(resources, cache=caching.ENABLED)
            if do_dse:
                res = auto_dse(fn, max_parallel=max_parallel,
                               resources=resources, model=model)
                actions.append(list(res.actions))
                latencies.append(res.report.latency)
            else:
                latencies.append(model.design_report(fn).latency)
            full_evals += model.stats.full_node_evals
            analytic_evals += model.stats.analytic_node_evals
            confirmed += model.stats.confirmed_evals
            pruned += model.stats.pruned_candidates
    seconds = time.perf_counter() - t0
    c = caching.COUNTS
    analysis = (c["selfdep_evals"] + c["legal_evals"] + c["trip_evals"]
                + full_evals)
    transfers = (c["selfdep_transfers"] + c["legal_transfers"]
                 + c["trip_transfers"] + analytic_evals)
    return {"seconds": seconds, "full_node_evals": full_evals,
            "analysis_evals": analysis, "transfers": transfers,
            "confirmed_evals": confirmed, "pruned_candidates": pruned,
            "actions": actions, "latencies": latencies}


# search strategies measured per workload: label -> auto_dse kwargs
STRATEGY_SPECS: List[Tuple[str, Dict]] = [
    ("greedy", {}),
    ("beam2", {"strategy": "beam", "beam_width": 2}),
    ("beam4", {"strategy": "beam:4"}),
    ("beam8", {"strategy": "beam:8"}),
]

STRATEGY_REPEATS = 3


def _measure_strategies(builders: List[Callable],
                        max_parallel: int) -> Dict[str, Dict]:
    """Full-budget DSE per strategy per function, repeated
    ``STRATEGY_REPEATS`` times with cold caches each (``clear_all`` per
    repeat), reporting the **minimum** wall across repeats — the min is
    the standard noise filter on shared hardware — plus best design cost
    (identical across repeats by the determinism invariants).

    Each strategy row also carries a ``telemetry`` column summed from the
    per-run ``report.telemetry`` snapshots (see ``dse.auto_dse``): fresh
    analysis evals, cross-state dedup credits, and the bound-and-confirm
    pair.
    Counters are deterministic across cold-cache repeats, so the last
    repeat's sums stand for all of them."""
    out: Dict[str, Dict] = {}
    walls: Dict[str, List[float]] = {label: [] for label, _ in STRATEGY_SPECS}
    # repeats are interleaved round-robin across strategies (repeat 1 of
    # every strategy, then repeat 2, ...) so slow machine drift within the
    # measurement window lands evenly on every column instead of
    # penalizing whichever strategy happens to run last
    for rep in range(STRATEGY_REPEATS):
        for label, kw in STRATEGY_SPECS:
            caching.clear_all()
            caching.reset_counts()
            cost = 0
            resources: Dict[str, float] = {}
            tel = {"analysis_evals": 0, "dedup_credits": 0,
                   "confirmed_evals": 0, "pruned_candidates": 0}
            t0 = time.perf_counter()
            for build in builders:
                res = auto_dse(build(), max_parallel=max_parallel, **kw)
                cost += res.report.latency
                for k, v in res.report.resource_totals().items():
                    resources[k] = resources.get(k, 0) + v
                t = res.report.telemetry or {}
                tel["analysis_evals"] += t.get("analysis_evals", 0)
                tel["dedup_credits"] += (t.get("wave") or {}).get(
                    "cands_credited", 0)
                tel["confirmed_evals"] += (t.get("cost") or {}).get(
                    "confirmed_evals", 0)
                tel["pruned_candidates"] += (t.get("cost") or {}).get(
                    "pruned_candidates", 0)
            walls[label].append(time.perf_counter() - t0)
            out[label] = {"seconds": 0.0,
                          "repeats": STRATEGY_REPEATS,
                          "best_cost": cost, "resources": resources,
                          "telemetry": tel}
    for label, _ in STRATEGY_SPECS:
        out[label]["seconds"] = round(min(walls[label]), 3)
    out["beam_cost_le_greedy"] = (
        out["beam2"]["best_cost"] <= out["greedy"]["best_cost"]
        and out["beam4"]["best_cost"] <= out["greedy"]["best_cost"]
        and out["beam8"]["best_cost"] <= out["greedy"]["best_cost"])
    # wall-clock price of widening the beam 8x over the greedy trajectory
    # (cross-state dedup + the transformed-node/whole-design caches are
    # what keep this near 1 instead of near 8)
    out["beam_scaling"] = round(
        out["beam8"]["seconds"] / max(out["greedy"]["seconds"], 1e-9), 2)
    return out


def _measure_dataflow(builders: List[Callable],
                      max_parallel: int) -> Dict[str, float]:
    """Task-level-pipelining columns: per workload, the summed latency and
    BRAM18 of the DSE'd designs under the sequential aggregation
    (``dataflow=False``) and the streaming task-graph aggregation
    (``dataflow=True``), plus how many functions actually formed an
    applied dataflow region.  Single-task functions report equal numbers
    by construction."""
    caching.clear_all()
    caching.reset_counts()
    out = {"latency_off": 0, "latency_on": 0,
           "bram18_off": 0, "bram18_on": 0, "regions_applied": 0}
    for build in builders:
        fn = build()
        model = HlsModel()
        auto_dse(fn, max_parallel=max_parallel, model=model)
        fn.dataflow = False
        off = model.design_report(fn)
        fn.dataflow = True
        on = model.design_report(fn)
        out["latency_off"] += off.latency
        out["latency_on"] += on.latency
        out["bram18_off"] += off.bram18
        out["bram18_on"] += on.bram18
        if on.dataflow is not None and on.dataflow.applied:
            out["regions_applied"] += 1
    out["latency_speedup"] = round(
        out["latency_off"] / max(out["latency_on"], 1), 2)
    return out


def measure(name: str, builders: List[Callable], max_parallel: int = 256,
            dnn_style: bool = False) -> Dict:
    caching.clear_all()
    caching.reset_counts()
    with caching.disabled():
        base = _run_workload(builders, max_parallel, dnn_style)
    caching.clear_all()
    caching.reset_counts()
    inc = _run_workload(builders, max_parallel, dnn_style)
    identical = (base["actions"] == inc["actions"]
                 and base["latencies"] == inc["latencies"])
    return {
        "workload": name,
        "baseline_seconds": round(base["seconds"], 3),
        "incremental_seconds": round(inc["seconds"], 3),
        "wall_speedup": round(base["seconds"] / max(inc["seconds"], 1e-9), 2),
        "baseline_full_node_evals": base["full_node_evals"],
        "incremental_full_node_evals": inc["full_node_evals"],
        "baseline_analysis_evals": base["analysis_evals"],
        "incremental_analysis_evals": inc["analysis_evals"],
        "analysis_eval_reduction": round(
            base["analysis_evals"] / max(inc["analysis_evals"], 1), 2),
        "incremental_transfers": inc["transfers"],
        "incremental_confirmed_evals": inc["confirmed_evals"],
        "incremental_pruned_candidates": inc["pruned_candidates"],
        "identical_results": identical,
        "strategies": _measure_strategies(builders, max_parallel),
        "dataflow": _measure_dataflow(builders, max_parallel),
    }


def measure_fusion_prepass(name: str, build: Callable,
                           max_parallel: int = 64) -> Dict:
    """Graph-level fusion ahead of DSE vs the default flow (stage 1's
    distribute-then-refuse), same workload, cold caches each."""
    caching.clear_all()
    t0 = time.perf_counter()
    plain = auto_dse(build(), max_parallel=max_parallel)
    t_plain = time.perf_counter() - t0
    caching.clear_all()
    t0 = time.perf_counter()
    fused = auto_dse(build(), max_parallel=max_parallel,
                     graph_passes=("fuse",))
    t_fused = time.perf_counter() - t0
    return {
        "workload": name,
        "stage1_flow_latency": plain.report.latency,
        "prefuse_flow_latency": fused.report.latency,
        "stage1_flow_seconds": round(t_plain, 3),
        "prefuse_flow_seconds": round(t_fused, 3),
        "prefuse_stage1_actions": fused.stage1_log.actions[:4],
        "cost_no_worse": fused.report.latency <= plain.report.latency,
    }


def _suites() -> List[Tuple]:
    return [
        ("gemm", [lambda: gemm(512).fn], 256, False),
        ("bicg", [lambda: bicg(512).fn], 256, False),
        ("3mm", [lambda: mm3(256).fn], 256, False),
        ("conv_stack", _conv_builders(), 64, True),
        # the multi-statement conv stack in ONE function: the task-level
        # pipelining (dataflow) workload — conv/relu chains + rescale
        ("conv_chain", [lambda: conv_chain(20, (3, 8, 8)).fn], 16, False),
    ]


def run_all() -> List[Dict]:
    return [measure(name, builders, mp, dnn)
            for name, builders, mp, dnn in _suites()]


# --------------------------------------------------------------------------
# counter-only mode: the CI perf gate
# --------------------------------------------------------------------------
def counters_only() -> List[Dict]:
    """One incremental engine pass per workload, counters only (no
    uncached baselines, no per-strategy wall-time runs): analysis-eval
    counts are machine-independent, so this is the cheap regression gate
    CI compares against the committed snapshot."""
    out = []
    for name, builders, mp, dnn in _suites():
        caching.clear_all()
        caching.reset_counts()
        inc = _run_workload(builders, mp, dnn)
        out.append({"workload": name,
                    "incremental_analysis_evals": inc["analysis_evals"],
                    "incremental_full_node_evals": inc["full_node_evals"],
                    "incremental_transfers": inc["transfers"],
                    "incremental_confirmed_evals": inc["confirmed_evals"],
                    "incremental_pruned_candidates":
                        inc["pruned_candidates"]})
    return out


def check_against_snapshot(path: str, tolerance: float = 0.10) -> int:
    """Fail (non-zero) if any workload's ``incremental_analysis_evals`` or
    ``incremental_confirmed_evals`` regresses more than ``tolerance`` above
    the committed snapshot.  Snapshots written before bound-and-confirm
    pruning existed lack the confirmed-eval column; those skip that gate
    (regenerating the snapshot arms it)."""
    with open(path) as fh:
        snap = {r["workload"]: r for r in json.load(fh)["results"]}
    failures = 0
    for row in counters_only():
        name = row["workload"]
        ref = snap.get(name)
        if ref is None:
            print(f"{name}: not in snapshot, measured "
                  f"{row['incremental_analysis_evals']} (new workload?)")
            continue
        for col, short in (("incremental_analysis_evals", "analysis_evals"),
                           ("incremental_confirmed_evals",
                            "confirmed_evals")):
            committed = ref.get(col)
            if committed is None:
                print(f"{name}: {short} not in snapshot (pre-pruning "
                      f"snapshot?), measured {row[col]}")
                continue
            measured = row[col]
            limit = int(committed * (1 + tolerance))
            status = "OK" if measured <= limit else "REGRESSED"
            if measured > limit:
                failures += 1
            print(f"{name}: {short} {measured} vs committed {committed} "
                  f"(limit {limit}) {status}")
    return failures


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--check", action="store_true",
                    help="counter-only run, compared against the committed "
                         "BENCH_dse_speed.json; exits non-zero on a >10%% "
                         "analysis-eval or confirmed-eval regression")
    ap.add_argument("--snapshot", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_dse_speed.json"))
    ap.add_argument("--tolerance", type=float, default=0.10)
    args = ap.parse_args()
    if args.check:
        failures = check_against_snapshot(args.snapshot, args.tolerance)
        raise SystemExit(1 if failures else 0)
    for line in csv_rows():
        print(line)


def run_fusion_compare() -> List[Dict]:
    cases = [("2mm", lambda: mm2(128).fn), ("3mm", lambda: mm3(128).fn)]
    return [measure_fusion_prepass(name, build) for name, build in cases]


def csv_rows() -> List[str]:
    rows = run_all()
    fusion = run_fusion_compare()
    snap = {"suite": "dse_speed", "results": rows, "fusion_prepass": fusion}
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_dse_speed.json")
    # atomic: an interrupted run must not corrupt the committed snapshot
    # that the --check CI gate diffs against
    atomic_write_json(path, snap)
    out = []
    for r in rows:
        strat = r["strategies"]
        df = r["dataflow"]
        out.append(
            f"dse_speed/{r['workload']},{r['incremental_seconds'] * 1e6:.0f},"
            f"wall_speedup={r['wall_speedup']}x;"
            f"analysis_evals={r['baseline_analysis_evals']}->"
            f"{r['incremental_analysis_evals']}"
            f"({r['analysis_eval_reduction']}x);"
            f"full_node_evals={r['baseline_full_node_evals']}->"
            f"{r['incremental_full_node_evals']};"
            f"confirmed_evals={r['incremental_confirmed_evals']}"
            f"(+{r['incremental_pruned_candidates']} pruned);"
            f"identical={r['identical_results']};"
            f"greedy_cost={strat['greedy']['best_cost']};"
            f"beam2_cost={strat['beam2']['best_cost']};"
            f"beam4_cost={strat['beam4']['best_cost']};"
            f"beam8_cost={strat['beam8']['best_cost']};"
            f"beam8_wall={strat['beam8']['seconds']};"
            f"beam_scaling={strat['beam_scaling']}x;"
            f"beam_le_greedy={strat['beam_cost_le_greedy']};"
            f"dataflow_lat={df['latency_off']}->{df['latency_on']}"
            f"({df['latency_speedup']}x);"
            f"dataflow_bram18={df['bram18_off']}->{df['bram18_on']};"
            f"dataflow_regions={df['regions_applied']}")
    for r in fusion:
        out.append(
            f"dse_speed/fuse_prepass_{r['workload']},"
            f"{r['prefuse_flow_seconds'] * 1e6:.0f},"
            f"stage1_lat={r['stage1_flow_latency']};"
            f"prefuse_lat={r['prefuse_flow_latency']};"
            f"no_worse={r['cost_no_worse']}")
    return out


if __name__ == "__main__":
    main()
