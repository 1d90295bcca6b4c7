"""Fault-injection suite: every recovery path exercised, results pinned.

For each injection site the invariant is the same: the recovery path
actually fires (spec counters), and a recovered result is
**bit-identical** to the fault-free run — faults may cost a recompute
and a warning, never correctness.

Sites covered (``core/faultinject.py``):
  * ``designdb.read`` truncate / bitflip / error and ``designdb.write``
    torn writes — checksum/JSON validation quarantines the entry and the
    design is recomputed.
  * ``backend.lower`` — a compiled Mosaic failure raises
    ``PallasLowerError`` naming the statement; nothing falls back.
"""
import warnings

import pytest

from benchmarks import workloads
from repro.core import caching, faultinject
from repro.core.errors import PomWarning


@pytest.fixture(autouse=True)
def _clean_faults():
    faultinject.clear()
    yield
    faultinject.clear()


# --------------------------------------------------------------------------
# the harness itself
# --------------------------------------------------------------------------
def test_parse_spec():
    s = faultinject.parse_spec("designdb.write:truncate")
    assert (s.site, s.kind, s.p) == ("designdb.write", "truncate", 1.0)
    s = faultinject.parse_spec("designdb.read:bitflip:0.25")
    assert (s.site, s.kind, s.p) == ("designdb.read", "bitflip", 0.25)


# unknown sites and kinds are rejected
@pytest.mark.parametrize("bad", ["nosuch:truncate", "designdb.read:nope",
                                 "worker.dispatch:crash",
                                 "designdb.read:crash", "justasite"])
def test_parse_spec_rejects_unknown(bad):
    with pytest.raises(ValueError):
        faultinject.parse_spec(bad)


def test_roll_is_deterministic_and_capped():
    a = faultinject.FaultSpec("designdb.read", "bitflip", p=0.3, seed=11)
    b = faultinject.FaultSpec("designdb.read", "bitflip", p=0.3, seed=11)
    assert [a.roll() for _ in range(50)] == [b.roll() for _ in range(50)]
    c = faultinject.FaultSpec("designdb.read", "bitflip", max_fires=2)
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
