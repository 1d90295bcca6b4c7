"""Compile-service tests (``pipeline.CompileService`` / ``pom.serve``).

Contract: a db hit serves the *same* outcome as the cold compile
(report, actions, tile sizes) in O(lookup) without mutating the input
function; the address is canonical (statement names don't split it,
a different strategy does); and with ``POM_DESIGN_DB`` unset the layer is a
per-process memo — fully inert for everyone not calling it.
"""
import os

import pytest

from benchmarks import workloads
from repro.core import caching
from repro.core import dsl as pom
from repro.core.pipeline import CompileService, compile_many, serve


@pytest.fixture(autouse=True)
def _cold_caches():
    caching.clear_all()
    caching.reset_counts()
    yield


def test_miss_then_hit(tmp_path):
    svc = serve(path=str(tmp_path / "db"))
    r1 = svc.compile_one(workloads.gemm(24).fn, max_parallel=16)
    r2 = svc.compile_one(workloads.gemm(24).fn, max_parallel=16)
    assert not r1.from_db and r2.from_db
    assert r1.key == r2.key
    assert r2.report == r1.report
    assert r2.actions == r1.actions
    assert r2.tile_sizes == r1.tile_sizes
    assert (svc.stats.hits, svc.stats.misses) == (1, 1)
    # a genuinely different strategy is a different address
    r3 = svc.compile_one(workloads.gemm(24).fn, max_parallel=16,
                         strategy="beam", beam_width=2)
    assert not r3.from_db and r3.key != r1.key


def test_hit_does_not_mutate_function(tmp_path):
    svc = serve(path=str(tmp_path / "db"))
    svc.compile_one(workloads.gemm(24).fn, max_parallel=16)
    fn = workloads.gemm(24).fn
    res = svc.compile_one(fn, max_parallel=16)
    assert res.from_db
    for s in fn.statements:
        assert not s.unrolls, "db hit must leave the input unscheduled"


def test_hit_survives_process_boundary(tmp_path):
    # a second service over the same path = a second process's view
    r1 = serve(path=str(tmp_path / "db")).compile_one(
        workloads.bicg(24).fn, max_parallel=16)
    r2 = serve(path=str(tmp_path / "db")).compile_one(
        workloads.bicg(24).fn, max_parallel=16)
    assert not r1.from_db and r2.from_db
    assert r2.report == r1.report


def test_key_canonical_across_renamings(tmp_path):
    def build(sname, arr):
        n = 24
        with pom.function("f") as f:
            i = pom.var("i", 0, n); j = pom.var("j", 0, n)
            k = pom.var("k", 0, n)
            A = pom.placeholder(arr[0], (n, n))
            B = pom.placeholder(arr[1], (n, n))
            C = pom.placeholder(arr[2], (n, n))
            pom.compute(sname, [i, j, k], C(i, j) + A(i, k) * B(k, j),
                        C(i, j))
        return f.fn

    svc = serve(path=str(tmp_path / "db"))
    r1 = svc.compile_one(build("s", ("A", "B", "C")), max_parallel=16)
    r2 = svc.compile_one(build("prod", ("X", "Y", "Z")), max_parallel=16)
    assert r2.from_db and r2.key == r1.key


def test_compile_many_replay(tmp_path):
    svc = serve(path=str(tmp_path / "db"))
    fns = [workloads.gemm(24).fn, workloads.bicg(24).fn,
           workloads.gemm(24).fn]
    results = compile_many(fns, service=svc, max_parallel=16)
    assert [r.from_db for r in results] == [False, False, True]
    assert results[2].report == results[0].report


def test_service_defaults_flow_through(tmp_path):
    svc = serve(path=str(tmp_path / "db"), max_parallel=16)
    r1 = svc.compile_one(workloads.gemm(24).fn)
    r2 = svc.compile_one(workloads.gemm(24).fn, max_parallel=16)
    assert r2.from_db and r2.key == r1.key


def test_memo_only_without_path_or_env(tmp_path, monkeypatch):
    monkeypatch.delenv("POM_DESIGN_DB", raising=False)
    svc = serve()
    assert svc.db.path is None
    r1 = svc.compile_one(workloads.gemm(24).fn, max_parallel=16)
    r2 = svc.compile_one(workloads.gemm(24).fn, max_parallel=16)
    assert not r1.from_db and r2.from_db
    assert not list(tmp_path.iterdir())


def test_env_selects_db_path(tmp_path, monkeypatch):
    monkeypatch.setenv("POM_DESIGN_DB", str(tmp_path / "envdb"))
    svc = serve()
    svc.compile_one(workloads.gemm(24).fn, max_parallel=16)
    assert (tmp_path / "envdb" / "designs").exists()


def test_pom_namespace_exports():
    assert pom.serve is serve
    assert pom.compile_many is compile_many
    assert pom.CompileService is CompileService
