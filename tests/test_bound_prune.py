"""Bound-and-confirm rung evaluation (``POM_BOUND_PRUNE``).

The pruning invariants this file pins:

  * **Bit-identity.**  With pruning on, the selected designs, actions,
    reports, tile sizes and stage logs are identical to exhaustive
    evaluation (``caching.bound_prune_disabled()``) on every workload,
    for every strategy — pruning only skips candidates
    whose admissible latency lower bound proves they cannot win.
  * **Admissibility.**  For every rung candidate on every workload,
    ``ClosedFormII.ii(factors)`` is <= the full design report's node II
    (the achieved II also folds in memory-port pressure), and
    ``HlsModel.latency_lower_bound`` is <= the achieved bottleneck-node
    latency.  Candidates the transfer algebra cannot bound (``None``)
    are always confirmed, never pruned.
  * **Accounting.**  ``confirmed_evals + pruned_candidates`` under
    pruning equals ``confirmed_evals`` of the exhaustive run, and
    pruning actually fires (``pruned_candidates > 0``) on the dense
    workloads.
"""
import os
import subprocess
import sys

import pytest

from benchmarks import workloads
from repro.core import caching
from repro.core.cost_model import HlsModel
from repro.core.dse import auto_dse
from repro.core.search import (GreedySearch, SerialEvaluator, _bound_plan,
                               unroll_candidates, _unroll_candidates_cached)

CASES = {
    "gemm": lambda: workloads.gemm(24),
    "bicg": lambda: workloads.bicg(24),
    "gesummv": lambda: workloads.gesummv(24),
    "2mm": lambda: workloads.mm2(16),
    "3mm": lambda: workloads.mm3(16),
    "jacobi1d": lambda: workloads.jacobi1d(48, 4),
    "jacobi2d": lambda: workloads.jacobi2d(10, 3),
    "heat1d": lambda: workloads.heat1d(48, 4),
    "seidel": lambda: workloads.seidel(10, 3),
    "edge_detect": lambda: workloads.edge_detect(14),
    "gaussian": lambda: workloads.gaussian(14),
    "blur": lambda: workloads.blur(14),
    "conv": lambda: workloads.conv_nest("conv", 8, 4, 6, 6),
}


def _run(build, strategy=None, **kw):
    caching.clear_all()
    caching.reset_counts()
    model = HlsModel()
    res = auto_dse(build().fn, max_parallel=16, model=model,
                   strategy=strategy, **kw)
    return res, model.stats


def _result_tuple(res):
    rep = res.report
    nodes = tuple(sorted(
        (n.name, n.latency, n.ii, n.depth, n.dsp, n.lut, n.trip_product)
        for n in rep.nodes.values()))
    return (rep.latency, rep.dsp, rep.lut, rep.ff, rep.bram_bits,
            rep.feasible, nodes, tuple(res.actions),
            tuple(res.stage1_log.actions),
            tuple(sorted((k, tuple(v)) for k, v in res.tile_sizes.items())))


# --------------------------------------------------------------------------
# bit-identity: pruning on vs exhaustive, every workload / strategy
# --------------------------------------------------------------------------
# beam:4's waves reach the per-key cutoff max (sibling states at different
# pre-rung bottleneck latencies sharing one rung), which beam:2 barely does
@pytest.mark.parametrize("strategy", ["greedy", "beam:2", "beam:4"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_bit_identical_to_exhaustive(name, strategy):
    assert caching.bound_prune_on()
    on, s_on = _run(CASES[name], strategy)
    with caching.bound_prune_disabled():
        off, s_off = _run(CASES[name], strategy)
    assert _result_tuple(on) == _result_tuple(off)
    # every exhaustive confirmation is either confirmed or provably pruned
    assert (s_on.confirmed_evals + s_on.pruned_candidates
            == s_off.confirmed_evals)
    assert s_off.pruned_candidates == 0


# --------------------------------------------------------------------------
# admissibility property: bound <= achieved, None always confirmed
# --------------------------------------------------------------------------
class _CheckingEvaluator(SerialEvaluator):
    """Evaluates every candidate exhaustively (no pruning) and checks the
    closed-form bound against the achieved full-report numbers."""

    def __init__(self):
        self.checked = 0          # candidates with a closed-form bound
        self.unbounded = 0        # inexact-transfer (None) candidates

    def evaluate(self, ctx, st, s, uid, P, sweep=None, cutoff=None,
                 branching=False):
        factor_list = [tuple(f) for f in unroll_candidates(P)]
        cands = self.evaluate_factors(ctx, st, s, uid, factor_list, sweep)
        if sweep is None:
            return cands
        for c in cands:
            node = c.report.nodes[s.name]
            cf = sweep.ii(c.factors)
            lb = ctx.model.latency_lower_bound(sweep, c.factors)
            if cf is None:
                self.unbounded += 1
                assert lb is None, (s.name, c.factors)
            else:
                assert cf <= node.ii, (s.name, c.factors, cf, node.ii)
            if lb is not None:
                self.checked += 1
                assert lb <= node.latency, (s.name, c.factors, lb,
                                            node.latency)
        # a None bound survives any cutoff: it can never be pruned
        bounds = [ctx.model.latency_lower_bound(sweep, f)
                  for f in factor_list]
        if any(b is None for b in bounds):
            reps = [c.report.latency for c in cands if c.report.feasible]
            cut = min(reps) if reps else 1
            _, frontier = _bound_plan(ctx.model, sweep, factor_list, cut)
            for i, b in enumerate(bounds):
                if b is None:
                    assert i in frontier
        return cands


@pytest.mark.parametrize("name", sorted(CASES))
def test_bound_is_admissible(name):
    ev = _CheckingEvaluator()
    _run(CASES[name], GreedySearch(evaluator=ev))
    # non-vacuity: the dense workloads must exercise the closed form
    if name in ("gemm", "bicg", "gesummv", "2mm", "3mm", "conv"):
        assert ev.checked > 0


# --------------------------------------------------------------------------
# counters, telemetry, escape hatch
# --------------------------------------------------------------------------
def test_pruning_fires_and_is_counted():
    res, stats = _run(CASES["gemm"], "greedy")
    assert stats.pruned_candidates > 0
    assert stats.confirmed_evals > 0
    # gemm's rungs are recurrence-dominated: pruning confirms under half
    assert stats.confirmed_evals * 2 <= (stats.confirmed_evals
                                         + stats.pruned_candidates)
    cost = res.report.telemetry["cost"]
    assert cost["confirmed_evals"] == stats.confirmed_evals
    assert cost["pruned_candidates"] == stats.pruned_candidates
    assert res.report.telemetry["bound_prune"] is True
    d = stats.as_dict()
    assert d["confirmed_evals"] == stats.confirmed_evals
    assert d["pruned_candidates"] == stats.pruned_candidates


def test_escape_hatch_disables_pruning():
    with caching.bound_prune_disabled():
        assert not caching.bound_prune_on()
        res, stats = _run(CASES["gemm"], "greedy")
    assert stats.pruned_candidates == 0
    assert res.report.telemetry["bound_prune"] is False
    assert caching.bound_prune_on()


def test_pruning_rides_on_analytic_layer():
    # no sweep without the analytic transfer layer -> nothing to bound
    with caching.analytic_disabled():
        assert not caching.bound_prune_on()
        _, stats = _run(CASES["gemm"], "greedy")
    assert stats.pruned_candidates == 0


def test_env_var_respected():
    env = dict(os.environ, POM_BOUND_PRUNE="0")
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    code = ("from repro.core import caching; "
            "assert caching.BOUND_PRUNE is False; "
            "assert caching.bound_prune_on() is False")
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   cwd=os.path.dirname(os.path.dirname(__file__)))


# --------------------------------------------------------------------------
# unroll_candidates memoization (defensive copy)
# --------------------------------------------------------------------------
def test_unroll_candidates_memoized_with_defensive_copy():
    _unroll_candidates_cached.cache_clear()
    a = unroll_candidates(16)
    info0 = _unroll_candidates_cached.cache_info()
    b = unroll_candidates(16)
    info1 = _unroll_candidates_cached.cache_info()
    assert info1.hits == info0.hits + 1
    assert a == b and a is not b          # fresh list per call
    a.append((999,))                       # caller mutation is harmless
    assert unroll_candidates(16) == b
