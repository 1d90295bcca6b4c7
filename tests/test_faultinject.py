"""Fault-injection suite: every recovery path exercised, results pinned.

For each injection site the invariant is the same: the run *completes*,
the recovery path actually fires (spec counters), and the final
schedule/report is **bit-identical** to the fault-free serial run —
faults may cost retries and warnings, never correctness.

Sites covered (``core/faultinject.py``):
  * ``worker.dispatch`` crash / hang / pickle — supervised pool kills
    the worker, retries the candidates, and under sustained failures
    degrades to the serial evaluator with a structured ``PomWarning``.
  * ``designdb.read`` truncate / bitflip / error and ``designdb.write``
    torn writes — checksum/JSON validation quarantines the entry and the
    design is recomputed.
  * ``backend.lower`` — a compiled Mosaic failure raises
    ``PallasLowerError`` naming the statement; nothing falls back.
"""
import os
import warnings

import pytest

from benchmarks import workloads
from repro.core import caching, faultinject
from repro.core.cost_model import HlsModel
from repro.core.dse import auto_dse
from repro.core.errors import PomWarning


@pytest.fixture(autouse=True)
def _clean_faults():
    faultinject.clear()
    yield
    faultinject.clear()


def _run(build, strategy=None, **kw):
    caching.clear_all()
    caching.reset_counts()
    model = HlsModel()
    res = auto_dse(build().fn, max_parallel=16, model=model,
                   strategy=strategy, **kw)
    return res


def _result_tuple(res):
    rep = res.report
    nodes = tuple(sorted(
        (n.name, n.latency, n.ii, n.depth, n.dsp, n.lut, n.trip_product)
        for n in rep.nodes.values()))
    return (rep.latency, rep.dsp, rep.lut, rep.ff, rep.bram_bits,
            rep.feasible, nodes, tuple(res.actions),
            tuple(sorted((k, tuple(v)) for k, v in res.tile_sizes.items())))


# --------------------------------------------------------------------------
# the harness itself
# --------------------------------------------------------------------------
def test_parse_spec():
    s = faultinject.parse_spec("worker.dispatch:crash")
    assert (s.site, s.kind, s.p) == ("worker.dispatch", "crash", 1.0)
    s = faultinject.parse_spec("designdb.read:bitflip:0.25")
    assert (s.site, s.kind, s.p) == ("designdb.read", "bitflip", 0.25)


@pytest.mark.parametrize("bad", ["nosuch:crash", "worker.dispatch:nope",
                                 "justasite"])
def test_parse_spec_rejects_unknown(bad):
    with pytest.raises(ValueError):
        faultinject.parse_spec(bad)


def test_roll_is_deterministic_and_capped():
    a = faultinject.FaultSpec("worker.dispatch", "crash", p=0.3, seed=11)
    b = faultinject.FaultSpec("worker.dispatch", "crash", p=0.3, seed=11)
    assert [a.roll() for _ in range(50)] == [b.roll() for _ in range(50)]
    c = faultinject.FaultSpec("worker.dispatch", "crash", max_fires=2)
    assert [c.roll() for _ in range(5)] == [True, True, False, False, False]
    assert c.fires == 2 and c.checks == 5


def test_env_spec_parsing(monkeypatch):
    monkeypatch.setenv("POM_FAULT", "designdb.read:truncate:0.5")
    assert faultinject.active()
    monkeypatch.setenv("POM_FAULT", "")
    assert not faultinject.active()
    monkeypatch.delenv("POM_FAULT", raising=False)
    assert faultinject.fires("designdb.read") is None


def test_inert_when_nothing_installed():
    for site in faultinject.SITES:
        assert faultinject.fires(site) is None


# --------------------------------------------------------------------------
# worker.dispatch: crash / hang / pickle — bit-identical recovery
# --------------------------------------------------------------------------
def test_worker_crash_recovers_bit_identical():
    ref = _result_tuple(_run(lambda: workloads.gemm(24)))
    with faultinject.injected("worker.dispatch", "crash",
                              max_fires=1) as spec:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", PomWarning)
            res = _run(lambda: workloads.gemm(24), strategy="parallel",
                       workers=2)
    assert spec.fires == 1, "crash fault never fired (no pooled rung?)"
    assert _result_tuple(res) == ref


def test_worker_hang_recovers_bit_identical(monkeypatch):
    monkeypatch.setenv("POM_WORKER_DEADLINE_S", "0.5")
    ref = _result_tuple(_run(lambda: workloads.bicg(24)))
    with faultinject.injected("worker.dispatch", "hang",
                              max_fires=1) as spec:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", PomWarning)
            res = _run(lambda: workloads.bicg(24), strategy="parallel",
                       workers=2)
    assert spec.fires == 1
    assert _result_tuple(res) == ref


def test_worker_pickle_error_recovers_bit_identical():
    ref = _result_tuple(_run(lambda: workloads.mm3(16)))
    with faultinject.injected("worker.dispatch", "pickle",
                              max_fires=1) as spec:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", PomWarning)
            res = _run(lambda: workloads.mm3(16), strategy="parallel",
                       workers=2)
    assert spec.fires == 1
    assert _result_tuple(res) == ref


def test_sustained_crashes_degrade_to_serial(monkeypatch):
    # every dispatch poisoned -> consecutive failures exhaust the budget
    # -> the evaluator degrades to the serial path with a structured
    # warning, and the search still completes bit-identical to serial
    monkeypatch.setenv("POM_WORKER_MAX_FAILURES", "2")
    monkeypatch.setenv("POM_WORKER_RETRY_BACKOFF_S", "0")
    ref = _result_tuple(_run(lambda: workloads.gemm(24)))
    with faultinject.injected("worker.dispatch", "crash") as spec:
        with pytest.warns(PomWarning, match="degraded_to_serial"):
            res = _run(lambda: workloads.gemm(24), strategy="parallel",
                       workers=2)
    assert spec.fires >= 2
    assert _result_tuple(res) == ref


def test_crash_rate_parallel_counters_still_equal_serial():
    # a 10% seeded crash rate: retries must not double-book analyses
    caching.clear_all(); caching.reset_counts()
    gm = HlsModel()
    g = auto_dse(workloads.gemm(24).fn, max_parallel=16, model=gm)
    gc = dict(caching.COUNTS)
    caching.clear_all(); caching.reset_counts()
    pm = HlsModel()
    with faultinject.injected("worker.dispatch", "crash", p=0.10, seed=7):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", PomWarning)
            p = auto_dse(workloads.gemm(24).fn, max_parallel=16, model=pm,
                         strategy="parallel", workers=2)
    assert _result_tuple(p) == _result_tuple(g)
    for k in ("selfdep_evals", "legal_evals", "trip_evals", "access_evals"):
        assert caching.COUNTS[k] == gc[k]
    assert pm.stats == gm.stats


# --------------------------------------------------------------------------
# designdb: torn/corrupted entries quarantined and recomputed
# --------------------------------------------------------------------------
def _db_with_entry(tmp_path):
    from repro.core import designdb
    db = designdb.DesignDB(str(tmp_path / "db"))
    key = "ab" + "0" * 62
    db.put(key, {"x": 1, "y": [1, 2, 3]})
    db.forget(key)
    return db, key


@pytest.mark.parametrize("kind", ["truncate", "bitflip", "error"])
def test_db_read_corruption_quarantines(tmp_path, kind):
    db, key = _db_with_entry(tmp_path)
    with faultinject.injected("designdb.read", kind, max_fires=1):
        with pytest.warns(PomWarning, match="entry_quarantined"):
            assert db.get(key) is None
    assert db.stats.quarantined == 1
    # recompute-and-rewrite heals the entry
    db.put(key, {"x": 1, "y": [1, 2, 3]})
    db.forget(key)
    assert db.get(key) == {"x": 1, "y": [1, 2, 3]}


@pytest.mark.parametrize("kind", ["truncate", "bitflip"])
def test_db_torn_write_detected_on_read(tmp_path, kind):
    from repro.core import designdb
    db = designdb.DesignDB(str(tmp_path / "db"))
    key = "cd" + "1" * 62
    with faultinject.injected("designdb.write", kind, max_fires=1):
        db.put(key, {"payload": "value"})
    db.forget(key)
    with pytest.warns(PomWarning, match="entry_quarantined"):
        assert db.get(key) is None
    assert db.stats.quarantined == 1


def test_service_recomputes_after_quarantine(tmp_path):
    from repro.core.pipeline import CompileService
    svc = CompileService(path=str(tmp_path / "db"))
    build = lambda: workloads.gemm(24).fn
    r1 = svc.compile_one(build(), max_parallel=16)
    svc.db.forget(r1.key)
    with faultinject.injected("designdb.read", "bitflip", max_fires=1):
        with pytest.warns(PomWarning, match="entry_quarantined"):
            caching.clear_all(); caching.reset_counts()
            r2 = svc.compile_one(build(), max_parallel=16)
    assert not r2.from_db            # quarantined -> recomputed
    assert r2.report == r1.report
    assert svc.stats.quarantined == 1
    r3 = svc.compile_one(build(), max_parallel=16)
    assert r3.from_db                # healed by the recompute's write


# --------------------------------------------------------------------------
# backend.lower: a compiled kernel failure raises (no interpret fallback)
# --------------------------------------------------------------------------
def test_backend_lower_falls_back_to_interpret():
    np = pytest.importorskip("numpy")
    from repro.core.backend_pallas import PallasLowerError, lower_stmt_pallas
    f = workloads.gemm(8).fn
    s = f.statements[0]
    for d in ("i", "j", "k"):
        s.unrolls[d] = 8
    arrays = {"A": np.random.rand(8, 8).astype("float32"),
              "B": np.random.rand(8, 8).astype("float32"),
              "C": np.random.rand(8, 8).astype("float32")}
    run = lower_stmt_pallas(s, interpret=False)
    with faultinject.injected("backend.lower", "error", max_fires=1) as spec:
        with warnings.catch_warnings():
            warnings.simplefilter("error", PomWarning)
            with pytest.raises(PallasLowerError,
                               match="^s: .*injected Mosaic lowering"):
                run(arrays)
    assert spec.fires == 1
    # the interpret lowering of the same statement is a separate runner
    ref = arrays["C"] + arrays["A"] @ arrays["B"]
    out = lower_stmt_pallas(s, interpret=True)(arrays)
    assert np.allclose(np.asarray(out), ref, atol=1e-4)
