"""POM schedule -> Pallas lowering, validated in interpret mode vs oracles."""
import numpy as np
import pytest

from repro.core import dsl as pom
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
