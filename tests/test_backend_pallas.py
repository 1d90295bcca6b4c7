"""POM schedule -> Pallas lowering, validated in interpret mode vs oracles."""
import glob
import os

import jax
import numpy as np
import pytest

from repro.core import dsl as pom
from repro.core import telemetry
from repro.core.backend_pallas import (PallasLowerError,
                                      _lower_stmt_pallas_compute,
                                      lower_stmt_pallas)


def _sched_gemm(n=32, ti=8, tj=8, tk=8):
    with pom.function("gemm") as f:
        i, j, k = pom.var("i", 0, n), pom.var("j", 0, n), pom.var("k", 0, n)
        A = pom.placeholder("A", (n, n))
        B = pom.placeholder("B", (n, n))
        C = pom.placeholder("C", (n, n))
        s = pom.compute("s", [i, j, k], A(i, j) + B(i, k) * C(k, j), A(i, j))
    # POM schedule: tile all three dims, unroll the intra-tile loops
    s.tile("i", "j", ti, tj, "i0", "j0", "i1", "j1")
    s.split("k", tk, "k0", "k1")
    s.interchange("k1", "j0") if False else None
    # move intra-tile loops innermost: order (i0, j0, k0, i1, j1, k1)
    st = s.stmt
    order = ["i0", "j0", "k0", "i1", "j1", "k1"]
    st.domain = st.domain.permute(order)
    s.unroll("i1", ti)
    s.unroll("j1", tj)
    s.unroll("k1", tk)
    s.pipeline("k0", 1)
    return f, s


def test_gemm_pallas_matches_numpy():
    n = 32
    f, s = _sched_gemm(n)
    run = lower_stmt_pallas(s.stmt, interpret=True)
    rng = np.random.default_rng(0)
    b = rng.normal(size=(n, n)).astype(np.float32)
    c = rng.normal(size=(n, n)).astype(np.float32)
    a0 = rng.normal(size=(n, n)).astype(np.float32)
    out = run({"A": a0, "B": b, "C": c})
    np.testing.assert_allclose(np.asarray(out), a0 + b @ c, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n,t", [(16, 4), (64, 16), (128, 32)])
def test_gemm_pallas_shape_sweep(n, t):
    f, s = _sched_gemm(n, t, t, t)
    run = lower_stmt_pallas(s.stmt, interpret=True)
    rng = np.random.default_rng(n)
    b = rng.normal(size=(n, n)).astype(np.float32)
    c = rng.normal(size=(n, n)).astype(np.float32)
    out = run({"A": np.zeros((n, n), np.float32), "B": b, "C": c})
    np.testing.assert_allclose(np.asarray(out), b @ c, rtol=1e-4, atol=1e-4)


def test_matvec_pallas():
    """BICG-like q = A @ p with tiled (i, j)."""
    n, t = 64, 16
    with pom.function("mv") as f:
        i, j = pom.var("i", 0, n), pom.var("j", 0, n)
        A = pom.placeholder("A", (n, n))
        p = pom.placeholder("p", (n,))
        q = pom.placeholder("q", (n,))
        s = pom.compute("s", [i, j], q(i) + A(i, j) * p(j), q(i))
    s.tile("i", "j", t, t, "i0", "j0", "i1", "j1")
    s.unroll("i1", t)
    s.unroll("j1", t)
    s.pipeline("j0", 1)
    run = lower_stmt_pallas(s.stmt, interpret=True)
    rng = np.random.default_rng(1)
    a = rng.normal(size=(n, n)).astype(np.float32)
    pv = rng.normal(size=(n,)).astype(np.float32)
    out = run({"A": a, "p": pv, "q": np.zeros(n, np.float32)})
    np.testing.assert_allclose(np.asarray(out), a @ pv, rtol=1e-4, atol=1e-4)


def test_unsupported_pattern_raises():
    n = 8
    with pom.function("st") as f:
        i = pom.var("i", 1, n - 1)
        A = pom.placeholder("A", (n,))
        B = pom.placeholder("B", (n,))
        s = pom.compute("s", [i], A(i - 1) + A(i + 1), B(i))
    with pytest.raises(PallasLowerError):
        lower_stmt_pallas(s.stmt)


def _untiled_gemm(n):
    with pom.function("gemm") as f:
        i, j, k = pom.var("i", 0, n), pom.var("j", 0, n), pom.var("k", 0, n)
        A = pom.placeholder("A", (n, n))
        B = pom.placeholder("B", (n, n))
        C = pom.placeholder("C", (n, n))
        s = pom.compute("s", [i, j, k], C(i, j) + A(i, k) * B(k, j), C(i, j))
    return f, s


def test_compiled_lowering_refuses_untileable_block():
    """An untiled nest gives (1, 1) blocks: Mosaic would refuse them, so a
    compiled lowering raises; the interpreter takes any block."""
    f, s = _untiled_gemm(256)
    with pytest.raises(PallasLowerError, match=r"s: .*\(1, 1\).*tiling"):
        _lower_stmt_pallas_compute(s.stmt, interpret=False)
    assert callable(_lower_stmt_pallas_compute(s.stmt, interpret=True))


@pytest.mark.parametrize("n,t,ok", [(256, 128, True), (256, 8, False),
                                    (64, 16, False), (64, 64, True)])
def test_compiled_lowering_tiling_rule(n, t, ok):
    """Last two block dims: multiples of (8, 128) or the whole array."""
    f, s = _sched_gemm(n, t, t, t)
    if ok:
        assert callable(_lower_stmt_pallas_compute(s.stmt, interpret=False))
    else:
        with pytest.raises(PallasLowerError, match="tiling"):
            _lower_stmt_pallas_compute(s.stmt, interpret=False)


def test_compiled_lowering_refuses_rank1_partial_block():
    n, t = 256, 16
    with pom.function("mv") as f:
        i, j = pom.var("i", 0, n), pom.var("j", 0, n)
        A = pom.placeholder("A", (n, n))
        p = pom.placeholder("p", (n,))
        q = pom.placeholder("q", (n,))
        s = pom.compute("s", [i, j], q(i) + A(i, j) * p(j), q(i))
    s.split("i", t, "i0", "i1")
    s.unroll("i1", t)
    s.unroll("j", n)
    with pytest.raises(PallasLowerError, match="rank-1 block"):
        _lower_stmt_pallas_compute(s.stmt, interpret=False)


def test_compiled_lowering_refuses_blocks_over_vmem():
    """Whole-array (4096, 4096) f32 blocks tile, but four of them
    double-buffered need 512 MiB of VMEM."""
    f, s = _untiled_gemm(4096)
    for d in ("i", "j", "k"):
        s.unroll(d, 4096)
    with pytest.raises(PallasLowerError, match="VMEM"):
        _lower_stmt_pallas_compute(s.stmt, interpret=False)


def _jitted_gemm(n=32):
    from repro.core.pipeline import compile as pom_compile
    f, _ = _untiled_gemm(n)
    run = pom_compile(f, target="pallas").jitted()
    rng = np.random.default_rng(2)
    arrays = {k: rng.normal(size=(n, n)).astype(np.float32) for k in "ABC"}
    return run, arrays


@pytest.fixture
def session():
    t = telemetry.start_trace(os.devnull)
    yield t
    if telemetry.on():
        telemetry.stop_trace(export=False)


def test_call_spans_reach_the_profilers_host_plane(tmp_path, session):
    """Under a session, one ``jitted()`` call puts ``backend.execute``,
    with ``backend.bufs`` inside it, on the profile's /host:CPU plane."""
    from jax.profiler import ProfileData
    run, arrays = _jitted_gemm()
    jax.block_until_ready(run(arrays))                  # compile outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(run(arrays))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = [p for p in ProfileData.from_file(path).planes
            if p.name == "/host:CPU"]
    events = {e.name: (e.start_ns, e.start_ns + e.duration_ns)
              for p in host for line in p.lines for e in line.events
              if e.name.startswith("backend.")}
    (x0, x1), (b0, b1) = events["backend.execute"], events["backend.bufs"]
    assert x0 <= b0 < b1 <= x1 and b1 - b0 < x1 - x0


def test_first_call_records_and_counts_xla_compiles(session):
    """The first call's compile is an ``xla.compile`` span naming its
    function, nested in ``backend.execute``, and counts in
    ``xla.compiles``; a second call with the same shapes compiles
    nothing."""
    compiles = telemetry.REGISTRY.counter("xla.compiles")
    run, arrays = _jitted_gemm()
    c0 = compiles.value
    jax.block_until_ready(run(arrays))
    c1 = compiles.value
    jax.block_until_ready(run(arrays))
    assert c1 > c0 and compiles.value == c1
    events = session.events
    (first, _) = [e for e in events if e["name"] == "backend.execute"]
    xla = [e for e in events if e["name"] == "xla.compile"
           and first["ts"] <= e["ts"]
           and e["ts"] + e["dur"] <= first["ts"] + first["dur"]]
    assert xla and all(e["args"]["fun"] for e in xla)
    assert {"xla.lower", "xla.compile"} <= {e["name"] for e in events}


def test_compiles_are_counted_with_no_session():
    assert not telemetry.on()
    compiles = telemetry.REGISTRY.counter("xla.compiles")
    run, arrays = _jitted_gemm(16)
    c0 = compiles.value
    jax.block_until_ready(run(arrays))
    assert compiles.value > c0
