"""Compiled Pallas serving path tests.

Covers the serving-path stack end to end:

  * the one interpret decision (``repro.runtime.pallas_interpret``: the
    CPU backend interprets) and the runner cache keyed by mode; a compiled
    runner that fails raises, naming the statement, and stays cached;
  * ``PallasProgram``: interpreted ``__call__`` parity, compiled
    ``__call__`` running the traced step, whole-program tracing
    (``jitted()``) on all 13 workloads and on a DSE-split gemm,
    ``batched(B)`` equal bit-for-bit to B sequential jitted runs,
    ``TraceError`` for untraceable programs, and compiled-vs-interpret
    numerical parity (skipped, from a fixture, where Pallas interprets);
  * scan-over-layers: ``graph_ir.detect_scan_chains`` role derivation,
    ``ScanRegion`` loop-IR plumbing (verify, describe, HLS annotation,
    oracle execution), scan == unrolled bit-for-bit, and
    ``POM_PALLAS_SCAN=0`` keeping the AST region-free;
  * steady-state ``II_region``: reported for every dataflow-eligible
    workload, always <= the single-shot latency, serialized through the
    design db and the Pareto archive.
"""
import os
import warnings

import numpy as np
import pytest

from benchmarks import workloads
from repro.core import backend_pallas as bp
from repro.core import caching
from repro.core import dsl as pom
from repro.core import graph_ir
from repro.core.astbuild import build_ast
from repro.core.backend_hls import emit_hls
from repro.core.backend_jax import compile_jax
from repro.core.backend_pallas import PallasProgram, TraceError
from repro.core.cost_model import HlsModel
from repro.core.errors import PomWarning
from repro.core.loop_ir import ScanRegion, describe, walk
from repro.core.pipeline import compile as pcompile
from repro.runtime import pallas_interpret


@pytest.fixture(autouse=True)
def _fresh_caches():
    caching.clear_all()
    caching.reset_counts()
    yield


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


def _inputs(fn, seed=0):
    rng = np.random.default_rng(seed)
    written = {s.store.array.name for s in fn.statements}
    return {p.name: rng.standard_normal(p.shape).astype(np.float32)
            for p in fn.placeholders.values() if p.name not in written}


def _outputs(fn):
    return {s.store.array.name for s in fn.statements}


# --------------------------------------------------------------------------
# probe + artifact surface
# --------------------------------------------------------------------------
def test_mosaic_probe_is_stable_and_bool():
    import jax
    a, b = pallas_interpret(), pallas_interpret()
    assert isinstance(a, bool) and a == b
    assert a == (jax.default_backend() == "cpu")


def test_interpret_env_tristate(monkeypatch):
    # the platform decides; no environment variable overrides it
    monkeypatch.setenv("POM_PALLAS_INTERPRET", "0")
    prog = pcompile(workloads.gemm(8).fn, target="pallas")
    assert prog.interpret is pallas_interpret()
    assert pcompile(workloads.gemm(8).fn, target="pallas",
                    interpret=True).interpret is True


def test_artifact_is_program_and_legacy_callable():
    f = workloads.gemm(8)
    prog = pcompile(f.fn, target="pallas", interpret=True)
    assert isinstance(prog, PallasProgram)
    arrs = _inputs(f.fn)
    out = prog(dict(arrs))
    ref = compile_jax(f.fn, build_ast(f.fn))(dict(arrs))
    np.testing.assert_allclose(np.asarray(out["C"], dtype=np.float64),
                               ref["C"], rtol=1e-5, atol=1e-5)


def test_compiled_artifact_call_runs_the_traced_step():
    """Compiled, calling the artifact runs ``jitted()``: the untiled gemm's
    (1, 1) blocks are refused and the nest vectorizes on the device; no
    per-statement plan and no host oracle."""
    f = workloads.gemm(256)
    prog = pcompile(f.fn, target="pallas", interpret=False)
    assert prog.mode == "traced" and prog.interpret is False
    arrs = _inputs(f.fn)
    out = prog(dict(arrs))
    assert prog._jit is not None
    a = {k: np.asarray(v, dtype=np.float64) for k, v in arrs.items()}
    np.testing.assert_allclose(np.asarray(out["C"], dtype=np.float64),
                               a["A"] @ a["B"], rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# whole-program tracing: jitted() on all 13 workloads
# --------------------------------------------------------------------------
@pytest.mark.parametrize("name", sorted(CASES))
def test_jitted_matches_oracle(name):
    f = CASES[name]()
    prog = pcompile(f.fn, target="pallas", interpret=True)
    assert prog.traceable(), f"{name}: serving path fell back"
    arrs = _inputs(f.fn)
    got = prog.jitted()(dict(arrs))
    ref = compile_jax(f.fn, build_ast(f.fn))(
        {k: np.asarray(v, dtype=np.float64) for k, v in arrs.items()})
    for k in _outputs(f.fn):
        np.testing.assert_allclose(
            np.asarray(got[k], dtype=np.float64), ref[k],
            rtol=1e-4, atol=1e-4, err_msg=f"{name}:{k}")


@pytest.fixture
def compiled_pallas():
    if pallas_interpret():
        pytest.skip("Pallas is interpreted on the CPU backend")


@pytest.mark.parametrize("name", sorted(CASES))
def test_compiled_matches_interpret(name, compiled_pallas):
    f = CASES[name]()
    arrs = _inputs(f.fn)
    fi = CASES[name]()
    interp = pcompile(fi.fn, target="pallas", interpret=True)
    comp = pcompile(f.fn, target="pallas", interpret=False)
    a = interp.jitted()(dict(arrs))
    b = comp.jitted()(dict(arrs))
    for k in _outputs(f.fn):
        np.testing.assert_allclose(np.asarray(a[k]), np.asarray(b[k]),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"{name}:{k}")


# --------------------------------------------------------------------------
# batched execution
# --------------------------------------------------------------------------
@pytest.mark.parametrize("name", ["gemm", "2mm", "blur", "conv"])
def test_batched_equals_sequential_bitforbit(name):
    B = 3
    f = CASES[name]()
    prog = pcompile(f.fn, target="pallas", interpret=True)
    singles = [_inputs(f.fn, seed=s) for s in range(B)]
    batched = {k: np.stack([s[k] for s in singles])
               for k in singles[0]}
    run = prog.jitted()
    seq = [run(dict(s)) for s in singles]
    out = prog.batched(B)(batched)
    for k in _outputs(f.fn):
        got = np.asarray(out[k])
        assert got.shape[0] == B
        for i in range(B):
            assert np.array_equal(got[i], np.asarray(seq[i][k])), \
                f"{name}:{k} batch lane {i} differs from sequential run"


def test_batched_rejects_wrong_batch():
    f = workloads.gemm(8)
    prog = pcompile(f.fn, target="pallas", interpret=True)
    br = prog.batched(4)
    arrs = {k: np.stack([v, v]) for k, v in _inputs(f.fn).items()}
    with pytest.raises(ValueError, match="built for batch 4"):
        br(arrs)


def test_untraceable_program_falls_back_sequential(monkeypatch):
    """No fallback: an untraceable program has no jitted() or batched()
    executor, and both raise TraceError naming the program and cause."""
    from repro.core import backend_pallas as bp

    def untraceable(fn, ast, interpret):
        raise bp.TraceError("no JAX rendition")

    monkeypatch.setattr(bp, "_build_step", untraceable)
    f = workloads.gemm(8)
    prog = pcompile(f.fn, target="pallas", interpret=True)
    assert not prog.traceable()
    with pytest.raises(TraceError, match="gemm: .*no JAX rendition"):
        prog.batched(2)
    with pytest.raises(TraceError, match="gemm"):
        prog.jitted()
    # the legacy per-statement path is unaffected
    ref = prog(dict(_inputs(f.fn)))
    assert np.asarray(ref["C"]).shape == (8, 8)


def test_dse_split_nest_vectorizes():
    """A DSE-split nest (i = 32*i_o + i_u, bounds min/max of i_o) traces
    with no loop: its bounds are constant over the enclosing box and the
    store is a mixed-radix encoding of (i_o, i_u)."""
    import jax
    n = 64
    f = workloads.gemm(n)
    prog = pcompile(f.fn, target="pallas", interpret=True, dse=True)
    assert any(" i_u " in line for line in describe(prog.ast).splitlines())
    spec = {p.name: jax.ShapeDtypeStruct(p.shape, np.float32)
            for p in f.fn.placeholders.values()}
    assert prog.traceable()
    assert "while" not in str(jax.make_jaxpr(prog._step)(spec))
    arrs = _inputs(f.fn)
    got = prog.jitted()(dict(arrs))
    ref = compile_jax(f.fn, build_ast(f.fn))(
        {k: np.asarray(v, dtype=np.float64) for k, v in arrs.items()})
    np.testing.assert_allclose(np.asarray(got["C"], dtype=np.float64),
                               ref["C"], rtol=1e-4, atol=1e-4)


def _reversed_fn(n=12):
    """-1 coefficients in a load and in a transposed store."""
    with pom.function("reversed") as f:
        i, j = pom.var("i", 0, n), pom.var("j", 1, n)
        A = pom.placeholder("A", (n, n))
        B = pom.placeholder("B", (n, n))
        pom.compute("rev", [i, j], 2.0 * A(n - 1 - i, j - 1), B(j, n - 1 - i))
    return f


def _shifted_fn(n=10, steps=3):
    """Nests under a live time loop whose accesses carry its var ``t``:
    windows and points with traced starts."""
    with pom.function("shifted") as f:
        t, i = pom.var("t", 0, steps), pom.var("i", 0, n)
        t2, i2 = pom.var("t2", 0, steps), pom.var("i2", 0, n)
        A = pom.placeholder("A", (steps + n,))
        B = pom.placeholder("B", (n,))
        C = pom.placeholder("C", (steps, n))
        s1 = pom.compute("s1", [t, i], A(t + i) + B(i), C(t, i))
        s2 = pom.compute("s2", [t2, i2], 0.5 * C(t2, i2) + B(i2), B(i2))
        s2.after(s1, 0)
    return f


_SLICE_CASES = {
    "gaussian": (CASES["gaussian"], True),
    "blur": (CASES["blur"], True),
    "jacobi2d": (CASES["jacobi2d"], True),
    "edge_detect": (CASES["edge_detect"], True),
    "conv": (CASES["conv"], False),          # img(c, y + r, x + s) gathers
    "reversed": (_reversed_fn, True),
    "shifted": (_shifted_fn, True),
}


@pytest.mark.parametrize("name", sorted(_SLICE_CASES))
def test_unit_stride_accesses_lower_to_slices(name):
    """A vectorized nest's accesses whose every index position is one loop
    var with coefficient +-1 (plus constants and outer loop vars) or no
    loop var trace to slices and dynamic_update_slice, with no gather or
    scatter; a windowed conv access keeps its index grids.  Both equal
    the oracle."""
    import jax
    from repro.core import telemetry
    make, sliced = _SLICE_CASES[name]
    f = make()
    prog = pcompile(f.fn, target="pallas", interpret=False)
    assert prog.traceable()
    spec = {p.name: jax.ShapeDtypeStruct(p.shape, np.float32)
            for p in f.fn.placeholders.values()}
    gathers = telemetry.counter("vec.gather_access").value
    jaxpr = str(jax.make_jaxpr(prog._step)(spec))
    if sliced:
        assert "gather" not in jaxpr and "scatter" not in jaxpr
    else:
        assert "gather" in jaxpr
        jax.make_jaxpr(bp._build_step(f.fn, prog.ast, False))(spec)
        assert telemetry.counter("vec.gather_access").value - gathers >= 1
    arrs = _inputs(f.fn)
    got = prog.jitted()(dict(arrs))
    ref = compile_jax(f.fn, build_ast(f.fn))(
        {k: np.asarray(v, dtype=np.float64) for k, v in arrs.items()})
    for k in _outputs(f.fn):
        np.testing.assert_allclose(
            np.asarray(got[k], dtype=np.float64), ref[k],
            rtol=1e-4, atol=1e-4, err_msg=f"{name}:{k}")


def _dse_gemm():
    return workloads.gemm(64), {"dse": True}


def _reversed_split_fn(n=12):
    """A -1 coefficient through a hand split: ``A(n-1-i, j)`` and
    ``B(j, n-1-i)`` with ``i = 4*i0 + i1``; the value of ``i`` itself
    pins each element to its row."""
    with pom.function("reversed_split") as f:
        i, j = pom.var("i", 0, n), pom.var("j", 0, 5)
        A = pom.placeholder("A", (n, 5))
        B = pom.placeholder("B", (5, n))
        s = pom.compute("rev", [i, j], 2.0 * A(n - 1 - i, j) + i,
                        B(j, n - 1 - i))
    s.split("i", 4, "i0", "i1")
    return f, {}


def _sparse_digits_fn(n=6):
    """``A(4*i + j)`` with ``j`` in 0..1: two vars that do not tile one
    range."""
    with pom.function("sparse_digits") as f:
        i, j = pom.var("i", 0, n), pom.var("j", 0, 2)
        A = pom.placeholder("A", (4 * n,))
        B = pom.placeholder("B", (n, 2))
        pom.compute("pick", [i, j], A(4 * i + j) + 1.0, B(i, j))
    return f, {}


def _mixed_signs_fn(n=6):
    """``A(4*i - j + 3)``: dense digits of opposite signs."""
    with pom.function("mixed_signs") as f:
        i, j = pom.var("i", 0, n), pom.var("j", 0, 4)
        A = pom.placeholder("A", (4 * n,))
        B = pom.placeholder("B", (n, 4))
        pom.compute("pick", [i, j], A(4 * i - j + 3) + 1.0, B(i, j))
    return f, {}


# name -> (program and compile options,
#          (slice, gather, digit window) accesses counted per trace)
_DIGIT_CASES = {
    "dse_gemm": (_dse_gemm, (3, 0, 2)),
    "reversed_split": (_reversed_split_fn, (2, 0, 2)),
    "sparse_digits": (_sparse_digits_fn, (1, 1, 0)),
    "mixed_signs": (_mixed_signs_fn, (1, 1, 0)),
}


@pytest.mark.parametrize("name", sorted(_DIGIT_CASES))
def test_split_positions_lower_to_digit_windows(name):
    """An index position whose loop vars are the dense mixed-radix digits
    of one range (a DSE split's ``32*i_o + i_u``, a hand split through a
    -1 coefficient) is a window: one trace counts it in
    ``vec.digit_window_access`` and the step has no gather or scatter.
    Digits that leave holes (``4*i + j``, ``j`` in 0..1) or mix signs
    keep their gather.  Each equals the oracle."""
    import jax
    from repro.core import telemetry
    make, counts = _DIGIT_CASES[name]
    f, opts = make()
    prog = pcompile(f.fn, target="pallas", interpret=False, **opts)
    assert prog.traceable()
    spec = {p.name: jax.ShapeDtypeStruct(p.shape, np.float32)
            for p in f.fn.placeholders.values()}
    names = ("vec.slice_access", "vec.gather_access",
             "vec.digit_window_access")
    before = telemetry.REGISTRY.counter_values("vec.")
    jaxpr = str(jax.make_jaxpr(bp._build_step(f.fn, prog.ast, False))(spec))
    after = telemetry.REGISTRY.counter_values("vec.")
    assert tuple(after.get(k, 0) - before.get(k, 0) for k in names) == counts
    assert "scatter" not in jaxpr
    assert ("gather" in jaxpr) == bool(counts[1])
    arrs = _inputs(f.fn)
    got = prog.jitted()(dict(arrs))
    ref = compile_jax(f.fn, build_ast(f.fn))(
        {k: np.asarray(v, dtype=np.float64) for k, v in arrs.items()})
    for k in _outputs(f.fn):
        np.testing.assert_allclose(
            np.asarray(got[k], dtype=np.float64), ref[k],
            rtol=1e-4, atol=1e-4, err_msg=f"{name}:{k}")


def test_slice_access_counters_per_trace():
    """One trace of the gaussian step counts its 9 loads and 1 store as
    slice accesses and none as gather accesses."""
    import jax
    from repro.core import telemetry
    f = workloads.gaussian(14)
    prog = pcompile(f.fn, target="pallas", interpret=True)
    step = bp._build_step(f.fn, prog.ast, prog.interpret)
    spec = {p.name: jax.ShapeDtypeStruct(p.shape, np.float32)
            for p in f.fn.placeholders.values()}
    before = telemetry.REGISTRY.counter_values("vec.")
    jax.make_jaxpr(step)(spec)
    after = pom.metrics()["counters"]
    assert after["vec.slice_access"] - before.get("vec.slice_access", 0) == 10
    assert after.get("vec.gather_access", 0) \
        - before.get("vec.gather_access", 0) == 0
    assert after.get("vec.digit_window_access", 0) \
        - before.get("vec.digit_window_access", 0) == 0


def test_service_pallas_runner_caches_executors(tmp_path):
    svc = pom.serve(path=str(tmp_path / "db"))
    f = workloads.gemm(8)
    r1 = svc.pallas_runner(f, batch_size=2)
    r2 = svc.pallas_runner(workloads.gemm(8), batch_size=2)
    assert r1 is r2                # same design key + batch -> same executor
    r3 = svc.pallas_runner(workloads.gemm(8))
    assert r3 is not r1
    singles = [_inputs(f.fn, seed=s) for s in range(2)]
    out = r1({k: np.stack([s[k] for s in singles]) for k in singles[0]})
    for i, s in enumerate(singles):
        np.testing.assert_allclose(np.asarray(out["C"][i]),
                                   np.asarray(r3(dict(s))["C"]),
                                   rtol=1e-5, atol=1e-5)


def test_dsl_runner_shortcut():
    f = workloads.gemm(8)
    run = f.runner()
    arrs = _inputs(f.fn)
    ref = pcompile(workloads.gemm(8).fn, target="pallas",
                   interpret=True).jitted()(dict(arrs))
    np.testing.assert_allclose(np.asarray(run(dict(arrs))["C"]),
                               np.asarray(ref["C"]), rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# runner cache keyed by mode; compiled failures raise
# --------------------------------------------------------------------------
def _stmt_cache_key(s, mode):
    from repro.core.ir import loads_of
    arrays_sig = tuple((a.name, a.shape, a.dtype.name)
                       for a in [s.store.array]
                       + [ld.array for ld in loads_of(s.body)])
    return (s.schedule_signature(), arrays_sig, mode)


def test_runner_cache_keys_distinguish_modes():
    from repro.core import backend_pallas as bp
    f = workloads.gemm(8)
    s = f.fn.statements[0]
    s.unrolls["j"] = 8
    bp.lower_stmt_pallas(s, interpret=True)
    assert _stmt_cache_key(s, "interpret") in bp._PALLAS_RUNNER_CACHE
    assert _stmt_cache_key(s, "compiled") not in bp._PALLAS_RUNNER_CACHE


def test_pin_to_interpret_evicts_compiled_cache_entry():
    """No pin: a compiled runner whose kernel fails raises with the
    statement's name, never switches to interpret, and keeps its slot."""
    from repro.core import backend_pallas as bp
    from repro.core import faultinject
    f = workloads.gemm(8)
    s = f.fn.statements[0]
    for d in ("i", "j", "k"):       # one (8, 8) block per array: tileable
        s.unrolls[d] = 8
    runner = bp.lower_stmt_pallas(s, interpret=False)
    key = _stmt_cache_key(s, "compiled")
    assert key in bp._PALLAS_RUNNER_CACHE
    arrs = {k: np.asarray(v) for k, v in _inputs(f.fn).items()}
    arrs["C"] = np.zeros((8, 8), dtype=np.float32)
    with faultinject.injected("backend.lower", "error", max_fires=1):
        with warnings.catch_warnings():
            warnings.simplefilter("error", PomWarning)
            with pytest.raises(bp.PallasLowerError,
                               match="^s: compiled Pallas kernel failed"):
                runner(arrs)
    assert key in bp._PALLAS_RUNNER_CACHE
    assert _stmt_cache_key(s, "interpret") not in bp._PALLAS_RUNNER_CACHE
    assert bp.lower_stmt_pallas(s, interpret=False) is runner


# --------------------------------------------------------------------------
# scan-over-layers
# --------------------------------------------------------------------------
def _tail_fn(scan_tail=3, hw=8):
    return workloads.conv_chain(hw=hw, chans=(3, 4, 4), scan_tail=scan_tail)


def test_detect_scan_chains_roles():
    f = _tail_fn()
    chains = graph_ir.detect_scan_chains(f.fn)
    assert len(chains) == 1
    c = chains[0]
    assert c.n == 3 and c.period == 2
    assert c.carry_in is not None and c.carry_out is not None
    stacked = dict(c.reads)
    assert any(len(set(v)) == c.n for v in stacked.values())  # weights
    for _, per in c.writes:
        assert len(per) == c.n and len(set(per)) == c.n


def test_no_chain_without_tail_or_with_scan_off(monkeypatch):
    assert graph_ir.detect_scan_chains(
        workloads.conv_chain(hw=8, chans=(3, 4, 4)).fn) == []
    f = _tail_fn()
    ast = build_ast(f.fn)
    assert any(isinstance(n, ScanRegion) for n in walk(ast))
    monkeypatch.setenv("POM_PALLAS_SCAN", "0")
    ast_off = build_ast(_tail_fn().fn)
    assert not any(isinstance(n, ScanRegion) for n in walk(ast_off))


def test_scan_region_plumbing():
    f = _tail_fn()
    ast = build_ast(f.fn)
    regions = [n for n in walk(ast) if isinstance(n, ScanRegion)]
    assert len(regions) == 1
    r = regions[0]
    assert len(r.body) == r.n * r.template_len
    assert "scan region" in describe(ast)
    hls = emit_hls(f.fn, ast)
    assert "// scan region: 3 isomorphic blocks" in hls


def test_scan_equals_unrolled_bitforbit(monkeypatch):
    f = _tail_fn()
    prog = pcompile(f.fn, target="pallas", interpret=True)
    assert any(isinstance(n, ScanRegion) for n in walk(prog.ast))
    assert prog.traceable()
    arrs = _inputs(f.fn, seed=1)
    got = prog.jitted()(dict(arrs))
    monkeypatch.setenv("POM_PALLAS_SCAN", "0")
    caching.clear_all()
    prog_u = pcompile(_tail_fn().fn, target="pallas", interpret=True)
    assert not any(isinstance(n, ScanRegion) for n in walk(prog_u.ast))
    ref = prog_u.jitted()(dict(arrs))
    for k in _outputs(f.fn):
        assert np.array_equal(np.asarray(got[k]), np.asarray(ref[k])), \
            f"{k}: scan-over-layers changed numerics"


def test_scan_region_oracle_and_legacy_exact():
    f = _tail_fn()
    ast = build_ast(f.fn)
    arrs = {k: np.asarray(v, dtype=np.float64)
            for k, v in _inputs(f.fn, seed=2).items()}
    got = compile_jax(f.fn, ast)(dict(arrs))
    f2 = _tail_fn()
    ref = compile_jax(f2.fn, build_ast(f2.fn, scan=False))(dict(arrs))
    for k in _outputs(f.fn):
        assert np.array_equal(got[k], ref[k])


def test_scan_shrinks_the_traced_program():
    import jax
    f = _tail_fn(scan_tail=6)
    prog = pcompile(f.fn, target="pallas", interpret=True)
    assert prog.traceable()
    fu = _tail_fn(scan_tail=6)
    caching.clear_all()
    os.environ["POM_PALLAS_SCAN"] = "0"
    try:
        prog_u = pcompile(fu.fn, target="pallas", interpret=True)
    finally:
        del os.environ["POM_PALLAS_SCAN"]
    assert prog_u.traceable()
    spec = {p.name: jax.ShapeDtypeStruct(p.shape, np.float32)
            for p in f.fn.placeholders.values()}
    n_scan = len(str(jax.make_jaxpr(prog._step)(spec).jaxpr))
    n_unroll = len(str(jax.make_jaxpr(prog_u._step)(spec).jaxpr))
    assert n_scan < n_unroll, (n_scan, n_unroll)


# --------------------------------------------------------------------------
# steady-state II_region
# --------------------------------------------------------------------------
DATAFLOW_CASES = ["conv_chain", "blur", "edge_detect", "gaussian",
                  "2mm", "3mm", "bicg"]


def _build_df(name):
    if name == "conv_chain":
        return workloads.conv_chain(hw=8, chans=(3, 4, 4))
    return CASES[name]()


@pytest.mark.parametrize("name", DATAFLOW_CASES)
def test_ii_region_reported_and_bounded(name):
    f = _build_df(name)
    info = graph_ir.analyze_task_graph(f.fn)
    rep = HlsModel().design_report(f.fn)
    assert rep.ii_region > 0
    assert rep.ii_region <= rep.latency
    if info.eligible and rep.dataflow is not None:
        assert rep.dataflow.ii_region > 0
        assert rep.dataflow.ii_region <= rep.dataflow.region_latency


def test_ii_region_sequential_equals_latency():
    f = workloads.gemm(16)        # single task: no region, II = latency
    rep = HlsModel().design_report(f.fn)
    assert rep.dataflow is None or not rep.dataflow.applied
    assert rep.ii_region == rep.latency


def test_ii_region_seq_edge_serializes():
    from repro.core.cost_model import DataflowReport
    r = DataflowReport(True, 2, 100, 80, ii_region=70)
    assert r.ii_region == 70
    # default keeps old payloads loadable
    assert DataflowReport(False, 1, 5, 5).ii_region == 0


def test_ii_region_roundtrips_designdb_and_archive():
    from repro.core import designdb
    from repro.core.search import ParetoArchive
    f = _build_df("conv_chain")
    rep = HlsModel().design_report(f.fn)
    back = designdb.report_from_json(designdb.report_to_json(rep))
    assert back.ii_region == rep.ii_region
    if rep.dataflow is not None:
        assert back.dataflow.ii_region == rep.dataflow.ii_region
    arch = ParetoArchive()
    pt = arch.add(f.fn, rep)
    if pt is not None:
        assert pt.ii_region == rep.ii_region
        assert arch.to_json()["frontier"][0]["ii_region"] == pt.ii_region
