"""Tests for the pluggable DSE search subsystem (``core/search.py``).

Equivalence invariants:
  * ``strategy="greedy"`` (the default) is bit-identical to the
    pre-subsystem ladder — pinned transitively through
    ``tests/test_incremental_dse.py`` and the count budgets in
    ``tests/test_perf_smoke.py``; here we additionally pin that the
    explicit strategy spellings agree with the default.
  * ``beam_width=1`` is bit-identical to greedy on every workload
    (schedules, reports, action logs, tile sizes).
  * ``beam`` (k >= 2) never returns a design with cost worse than greedy.
  * Cross-state dedup fires: sibling beam states proposing the same
    (base design, statement, P) rung share one evaluation.
  * ``ParetoArchive`` keeps exactly the non-dominated feasible points.
"""
import pytest

from benchmarks import workloads
from repro.core import caching
from repro.core.cost_model import HlsModel
from repro.core.dse import auto_dse
from repro.core.search import (BeamSearch, DesignPoint, GreedySearch,
                               ParetoArchive, STRATEGIES, resolve_strategy)

# every workload family, sized to keep the suite quick (polyhedral work is
# extent-independent)
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
    return res, dict(caching.COUNTS), model.stats


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
# strategy equivalence
# --------------------------------------------------------------------------
@pytest.mark.parametrize("name", sorted(CASES))
def test_beam_width1_bit_identical_to_greedy(name):
    g, _, _ = _run(CASES[name])
    b, _, _ = _run(CASES[name], strategy="beam", beam_width=1)
    assert _result_tuple(g) == _result_tuple(b)
    assert b.strategy == "beam:1"


@pytest.mark.parametrize("width", (2, 3, 4))
@pytest.mark.parametrize("name", sorted(CASES))
def test_beam_never_worse_than_greedy(name, width):
    g, _, _ = _run(CASES[name])
    b, _, _ = _run(CASES[name], strategy="beam", beam_width=width)
    assert b.report.feasible
    assert b.report.latency <= g.report.latency, (
        f"beam:{width} regressed {name}: "
        f"{b.report.latency} > greedy {g.report.latency}")
    # alt branches must re-apply factors from the clean per-node base
    # (never compound splits), so achieved unroll products stay within
    # the ladder's max_parallel budget
    for sname, tiles in b.tile_sizes.items():
        prod = 1
        for f in tiles:
            prod *= f
        assert prod <= 16, (
            f"beam:{width} {name}/{sname}: unroll product {prod} "
            f"exceeds max_parallel=16 (dirty base snapshot)")


# --------------------------------------------------------------------------
# cross-state dedup (evaluate once, credit all states)
# --------------------------------------------------------------------------
def test_wave_dedup_fires_and_beats_naive_fanout():
    strat = resolve_strategy("beam:8")
    assert isinstance(strat, BeamSearch)
    _run(CASES["blur"], strategy=strat)
    ws = strat.wave_stats
    assert ws["cands_credited"] > 0, (
        "sibling beam states never shared a rung evaluation")
    naive = ws["cands_evaluated"] + ws["cands_credited"]
    assert ws["cands_evaluated"] < naive


# --------------------------------------------------------------------------
# Pareto archive
# --------------------------------------------------------------------------
def _pt(lat, dsp, bram, sig):
    return DesignPoint(lat, dsp, bram, sig, "test", True)


def test_pareto_archive_dominance_pruning():
    a = ParetoArchive()
    p1 = _pt(100, 10, 4, ("a",))
    p2 = _pt(50, 20, 4, ("b",))     # trades latency for DSP: kept
    p3 = _pt(120, 12, 4, ("c",))    # dominated by p1: pruned on arrival
    p4 = _pt(40, 10, 4, ("d",))     # dominates p1 and p2
    assert a._insert(p1) is p1
    assert a._insert(p2) is p2
    assert a._insert(p3) is None
    assert a._insert(p4) is p4
    front = a.frontier()
    assert p4 in front and p1 not in front and p2 not in front
    # equal-objective points are deduplicated
    assert a._insert(_pt(40, 10, 4, ("e",))) is None
    # incomparable point joins the frontier
    p5 = _pt(60, 5, 4, ("f",))
    assert a._insert(p5) is p5
    assert set(a.frontier()) == {p4, p5}
    for p in a.frontier():
        assert not any(q.dominates(p) for q in a.frontier())


def test_archive_collects_frontier_during_dse():
    res, _, _ = _run(CASES["bicg"], archive=True)
    arch = res.archive
    assert arch is not None and arch.evaluated > 3
    front = arch.frontier()
    assert front, "DSE evaluated designs but archived none"
    # the returned design is on the frontier's latency axis
    assert front[0].latency <= res.report.latency
    # frontier is mutually non-dominated
    for p in front:
        assert not any(q.dominates(p) for q in front)
    # lower-parallelism designs trade latency for resources: the frontier
    # should expose more than a single point on these workloads
    assert len(front) >= 2


def test_pareto_dump_hook(tmp_path, monkeypatch):
    import json
    dest = tmp_path / "pareto.json"
    monkeypatch.setenv("POM_DUMP_PARETO", str(dest))
    res, _, _ = _run(CASES["gemm"])
    payload = json.loads(dest.read_text())
    assert payload["evaluated"] > 0
    assert payload["frontier"]
    assert res.archive is not None


# --------------------------------------------------------------------------
# registry / selection plumbing
# --------------------------------------------------------------------------
def test_registry_contents():
    assert set(STRATEGIES) == {"greedy", "beam"}
    # an unregistered name or beam segment is rejected, naming the
    # registered strategies / the grammar; there is no workers= kwarg
    with pytest.raises(ValueError, match=r"registered: \['beam', 'greedy'\]"):
        resolve_strategy("parallel")
    with pytest.raises(ValueError, match=r"beam\[:k\]\[:latency\|scalar\]"):
        resolve_strategy("beam:4:parallel")
    with pytest.raises(TypeError, match="workers"):
        auto_dse(CASES["gemm"]().fn, workers=2)


def test_resolve_strategy_specs():
    assert isinstance(resolve_strategy(None), GreedySearch)
    assert isinstance(resolve_strategy("greedy"), GreedySearch)
    b = resolve_strategy("beam:4")
    assert isinstance(b, BeamSearch) and b.width == 4
    with pytest.raises(ValueError, match="unknown DSE strategy 'parallel'"):
        resolve_strategy("parallel:3")
    inst = BeamSearch(width=7)
    assert resolve_strategy(inst) is inst
    with pytest.raises(ValueError):
        resolve_strategy("annealing")
    # stray parameter on a parameterless strategy: rejected, names the spec
    with pytest.raises(ValueError, match="greedy:2"):
        resolve_strategy("greedy:2")


def test_resolve_strategy_kwarg_env_precedence(monkeypatch):
    # call-site kwargs are more explicit than the ambient environment:
    # beam_width selects beam
    monkeypatch.setenv("POM_DSE_STRATEGY", "greedy")
    s = resolve_strategy(None, beam_width=2)
    assert isinstance(s, BeamSearch) and s.width == 2
    # explicit spec + matching kwarg: kwarg overrides the :k suffix
    s = resolve_strategy("beam:3", beam_width=5)
    assert isinstance(s, BeamSearch) and s.width == 5
    # neither a workers= kwarg nor an unregistered env spec is accepted
    with pytest.raises(TypeError, match="workers"):
        resolve_strategy("beam:3", workers=2)
    monkeypatch.setenv("POM_DSE_STRATEGY", "parallel:8")
    with pytest.raises(ValueError, match=r"registered: \['beam', 'greedy'\]"):
        resolve_strategy(None)


def test_resolve_strategy_beam_grammar():
    # width-less rank segments: beam:scalar keeps the default width
    s = resolve_strategy("beam:scalar")
    assert isinstance(s, BeamSearch) and s.width == 2 and s.rank == "scalar"
    s = resolve_strategy("beam:4:scalar")
    assert s.width == 4 and s.rank == "scalar"
    # segments compose in any order
    s = resolve_strategy("beam:scalar:4")
    assert s.width == 4 and s.rank == "scalar"
    s = resolve_strategy("beam:latency")
    assert s.width == 2 and s.rank == "latency"
    # beam_width kwarg still overrides a width-less rank spec
    s = resolve_strategy("beam:scalar", beam_width=6)
    assert s.width == 6 and s.rank == "scalar"


def test_resolve_strategy_beam_grammar_errors():
    # duplicate / unknown segments are rejected and name the original spec
    for bad in ("beam:4:2", "beam:scalar:latency", "beam:parallel",
                "beam:fast", "beam:4:bogus"):
        with pytest.raises(ValueError, match="beam"):
            resolve_strategy(bad)


def test_env_var_selects_strategy(monkeypatch):
    monkeypatch.setenv("POM_DSE_STRATEGY", "beam:2")
    s = resolve_strategy(None)
    assert isinstance(s, BeamSearch) and s.width == 2
    res, _, _ = _run(CASES["gemm"])
    assert res.strategy == "beam:2"


def test_stage2_pipeline_pass_registry():
    from repro.core.pipeline import STAGE2_PASSES, Stage2BeamDSE, stage2_pass
    assert set(STAGE2_PASSES) == {"greedy", "beam"}
    p = stage2_pass("beam:3")
    assert isinstance(p, Stage2BeamDSE) and p.strategy == "beam:3"
    with pytest.raises(ValueError, match=r"registered: \['beam', 'greedy'\]"):
        stage2_pass("parallel")
    with pytest.raises(ValueError):
        stage2_pass("bogus")
    with pytest.raises(ValueError, match="greedy:2"):
        stage2_pass("greedy:2")


def test_compile_with_beam_strategy_dse():
    from repro.core.pipeline import compile
    code = compile(CASES["gemm"]().fn, target="hls", dse=True,
                   strategy="beam:2", max_parallel=8)
    assert "#pragma" in code and "pipeline" in code.lower()


# --------------------------------------------------------------------------
# outputs / dead-op elimination through the DSL (PR 2 follow-on)
# --------------------------------------------------------------------------
def test_outputs_prunes_dangling_ops_in_dse():
    from repro.core import dsl as pom
    n = 12
    with pom.function("net", outputs=["out"]) as f:
        i, j = pom.var("i", 0, n), pom.var("j", 0, n)
        i2, j2 = pom.var("i2", 0, n), pom.var("j2", 0, n)
        i3, j3 = pom.var("i3", 0, n), pom.var("j3", 0, n)
        img = pom.placeholder("img", (n, n))
        t1 = pom.placeholder("t1", (n, n))
        t2 = pom.placeholder("t2", (n, n))
        out = pom.placeholder("out", (n, n))
        pom.compute("a", [i, j], img(i, j) * 2.0, t1(i, j))
        pom.compute("dead", [i2, j2], img(i2, j2) + 1.0, t2(i2, j2))
        pom.compute("b", [i3, j3], t1(i3, j3) + 3.0, out(i3, j3))
    assert f.outputs == ["out"]
    res = f.auto_DSE(max_parallel=8)
    assert sorted(res.report.nodes) == ["a", "b"]


def test_unknown_output_name_is_rejected():
    # a typo in outputs= must raise, not silently DCE the whole program
    from repro.core import dsl as pom
    from repro.core.pipeline import VerifyError
    n = 8
    with pom.function("net", outputs=["resutl"]) as f:   # typo
        i, j = pom.var("i", 0, n), pom.var("j", 0, n)
        img = pom.placeholder("img", (n, n))
        result = pom.placeholder("result", (n, n))
        pom.compute("a", [i, j], img(i, j) * 2.0, result(i, j))
    with pytest.raises(VerifyError, match="resutl"):
        f.auto_DSE(max_parallel=8)
    with pytest.raises(VerifyError, match="resutl"):
        f.codegen("jax")


def test_outputs_default_is_conservative():
    from repro.core import dsl as pom
    n = 8
    with pom.function("net") as f:
        i, j = pom.var("i", 0, n), pom.var("j", 0, n)
        img = pom.placeholder("img", (n, n))
        t = pom.placeholder("t", (n, n))
        pom.compute("a", [i, j], img(i, j) * 2.0, t(i, j))
    res = f.auto_DSE(max_parallel=8)
    assert sorted(res.report.nodes) == ["a"]


def test_outputs_jax_semantics_unchanged():
    import numpy as np
    from repro.core import dsl as pom
    n = 8
    with pom.function("net", outputs=["out"]) as f:
        i, j = pom.var("i", 0, n), pom.var("j", 0, n)
        i2, j2 = pom.var("i2", 0, n), pom.var("j2", 0, n)
        img = pom.placeholder("img", (n, n))
        t2 = pom.placeholder("t2", (n, n))
        out = pom.placeholder("out", (n, n))
        pom.compute("live", [i, j], img(i, j) * 2.0, out(i, j))
        pom.compute("dead", [i2, j2], img(i2, j2) + 1.0, t2(i2, j2))
    run = f.codegen("jax")
    res = run({"img": np.ones((n, n))})
    assert np.allclose(res["out"], 2.0)
