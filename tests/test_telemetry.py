"""Unified telemetry layer: span tracing, metrics registry, trace export.

Acceptance criteria of the observability PR:

  * a traced ``beam:4`` run through the compile service produces a valid
    Chrome trace-event JSON containing pipeline-pass, rung/wave,
    candidate, and designdb spans on one named process track;
  * tracing never changes the search: traced and untraced runs pick the
    same design with the same evaluation counters, on every workload
    under ``greedy`` and ``beam:4`` (the traced rung and wave wrappers are
    the search's only fork), and the disabled path is pay-for-use
    (null-span singleton, no per-call allocation);
  * ``warn_structured`` routes through the telemetry event API — one
    emission path feeding both ``PomWarning`` and the trace/registry;
  * ``CompileService`` maintains live per-request p50/p99 split hit/miss;
  * ``POM_TRACE=-`` and ``POM_DUMP_PARETO=-`` share the stdout dump
    helper (explicit flush, no stray buffering).
"""
import json
import os

import pytest

from benchmarks import workloads as W
from repro.core import caching, telemetry
from repro.core import dsl as pom
from repro.core.dse import auto_dse
from repro.core.errors import PomWarning, warn_structured
from repro.core.pipeline import CompileService
from repro.core.cost_model import HlsModel
from repro.core.search import ParetoArchive
from test_search import CASES


@pytest.fixture(autouse=True)
def _no_leaked_session():
    """Every test starts and ends without an active trace session."""
    if telemetry.on():
        telemetry.stop_trace(export=False)
    yield
    if telemetry.on():
        telemetry.stop_trace(export=False)


def _fresh():
    caching.clear_all()
    caching.reset_counts()


# --------------------------------------------------------------------------
# acceptance: traced beam service request → valid Chrome trace
# --------------------------------------------------------------------------
def test_traced_beam_parallel_service_chrome_trace(tmp_path):
    _fresh()
    tp = str(tmp_path / "trace.json")
    svc = CompileService(path=str(tmp_path / "db"), trace_path=tp)
    svc.compile_one(W.conv_chain(16, (3, 8, 8)).fn, target="hls",
                    max_parallel=16, strategy="beam:4")
    data = json.load(open(tp))          # json.load itself validates
    evs = data["traceEvents"]
    assert isinstance(evs, list) and evs
    for e in evs:
        assert {"name", "ph", "pid", "tid"} <= set(e)
        if e["ph"] == "X":
            assert e["dur"] >= 0
    names = {e["name"] for e in evs}
    assert any(n.startswith("pass.") for n in names)          # pipeline
    assert "stage2.rung" in names and "stage2.wave" in names  # DSE
    assert "stage2.candidate" in names
    assert "designdb.get" in names and "designdb.put" in names
    assert "service.request" in names and "auto_dse" in names
    # one process, one named track
    assert {e["pid"] for e in evs} == {os.getpid()}
    tracks = [e["args"]["name"] for e in evs if e["name"] == "process_name"]
    assert tracks == ["pom"]


def _dse(name, strategy, **kw):
    _fresh()
    model = HlsModel()
    res = auto_dse(CASES[name]().fn, max_parallel=16, model=model,
                   strategy=strategy, **kw)
    return res, dict(caching.COUNTS), model.stats


@pytest.mark.parametrize("strategy", ["greedy", "beam:4"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_traced_run_bit_identical_to_untraced(tmp_path, name, strategy):
    off, c_off, s_off = _dse(name, strategy)
    on, c_on, s_on = _dse(name, strategy,
                          trace_path=str(tmp_path / "t.json"))
    assert off.report == on.report      # telemetry field excluded (compare=False)
    assert off.actions == on.actions
    assert off.tile_sizes == on.tile_sizes
    # tracing records observations only: no counter moves with it
    assert c_off == c_on
    assert s_off == s_on


def test_report_telemetry_attached_even_untraced():
    _fresh()
    res = auto_dse(W.gemm(16).fn, strategy="greedy")
    tel = res.report.telemetry
    assert tel["strategy"] == "greedy"
    assert tel["analysis_evals"] >= 1
    assert tel["cost"]["full_node_evals"] >= 1
    assert tel["dse_seconds"] > 0


# --------------------------------------------------------------------------
# pay-for-use disabled path
# --------------------------------------------------------------------------
def test_disabled_span_is_shared_null_singleton():
    assert not telemetry.on()
    s1 = telemetry.span("anything", _cat="x", arbitrary=1)
    s2 = telemetry.span("else")
    assert s1 is s2                     # no per-call allocation
    with s1 as sp:
        assert not sp                   # falsy: `if sp:` guards stay cheap
        sp.add(ignored=True)            # no-op, never raises
    telemetry.event("nobody.listens", field=3)   # no-op without a session


def test_profiler_annotations_only_inside_a_session(tmp_path, monkeypatch):
    """A session mirrors each span as a ``jax.profiler.TraceAnnotation``;
    with no session the span is the null singleton and none is made."""
    import jax.profiler
    made = []

    class Counting:
        def __init__(self, name):
            made.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    with telemetry.span("backend.execute") as sp:
        assert sp is telemetry._NULL_SPAN
    assert made == []
    telemetry.start_trace(str(tmp_path / "t.json"))
    with telemetry.span("outer"):
        with telemetry.span("inner"):
            pass
    telemetry.stop_trace(export=False)
    assert made == ["outer", "inner"]
    with telemetry.span("after"):
        pass
    assert made == ["outer", "inner"]


def test_start_stop_trace_lifecycle(tmp_path):
    tp = str(tmp_path / "t.json")
    telemetry.start_trace(tp)
    assert telemetry.on()
    with pytest.raises(RuntimeError):
        telemetry.start_trace(tp)       # no nested sessions
    with telemetry.span("outer", _cat="t") as sp:
        sp.add(k=1)
        telemetry.event("inner", _cat="t")
    telemetry.stop_trace()
    assert not telemetry.on()
    data = json.load(open(tp))
    names = [e["name"] for e in data["traceEvents"]]
    assert "outer" in names and "inner" in names


def test_maybe_trace_joins_active_session(tmp_path):
    """compile()/auto_dse() inside a service session must not tear the
    session down — maybe_trace only owns a session it started."""
    tp = str(tmp_path / "t.json")
    telemetry.start_trace(tp)
    with telemetry.maybe_trace(str(tmp_path / "other.json")):
        assert telemetry.on()
    assert telemetry.on()               # still the service's session
    telemetry.stop_trace(export=False)
    assert not os.path.exists(str(tmp_path / "other.json"))


# --------------------------------------------------------------------------
# warn_structured → telemetry event API (single emission path)
# --------------------------------------------------------------------------
def test_warn_structured_keeps_format_adds_ts():
    with pytest.warns(PomWarning, match=r"\[pom:unit_test\] ts_check a=1"):
        warn_structured("unit_test", "ts_check", a=1)
    with pytest.warns(PomWarning) as rec:
        warn_structured("unit_test", "ts_check", a=1)
    msg = str(rec[0].message)
    assert " ts=" in msg
    float(msg.rsplit("ts=", 1)[1])      # monotonic timestamp parses


def test_warn_structured_counts_and_traces(tmp_path):
    c0 = telemetry.REGISTRY.counter("warnings.unit_test").value
    telemetry.start_trace(str(tmp_path / "t.json"))
    with pytest.warns(PomWarning):
        warn_structured("unit_test", "traced_warn", x=2)
    telemetry.stop_trace(export=False)
    assert telemetry.REGISTRY.counter("warnings.unit_test").value == c0 + 1


# --------------------------------------------------------------------------
# metrics registry
# --------------------------------------------------------------------------
def test_registry_counter_gauge_histogram():
    r = telemetry.Registry()
    r.counter("c").inc()
    r.counter("c").inc(4)
    h = r.histogram("h")
    for v in range(100):
        h.observe(float(v))
    snap = r.snapshot()
    assert snap["counters"]["c"] == 5
    hj = snap["histograms"]["h"]
    assert hj["count"] == 100 and hj["min"] == 0.0 and hj["max"] == 99.0
    assert 40 <= hj["p50"] <= 60 and hj["p99"] >= 90


def test_histogram_decimation_keeps_exact_count():
    h = telemetry.Histogram()
    n = telemetry.Histogram.MAX_SAMPLES * 3 + 7
    for v in range(n):
        h.observe(float(v))
    j = h.to_json()
    assert j["count"] == n              # exact even after sample halving
    assert j["min"] == 0.0 and j["max"] == float(n - 1)
    assert len(h.samples) <= telemetry.Histogram.MAX_SAMPLES


def test_pom_metrics_snapshot():
    snap = pom.metrics()
    assert {"counters", "histograms", "caching", "tracing"} <= set(snap)
    assert snap["tracing"]["active"] is False
    json.dumps(snap)                    # snapshot is JSON-serializable


def test_service_latency_histograms(tmp_path):
    _fresh()
    svc = CompileService(path=str(tmp_path / "db"))
    svc.compile_one(W.gemm(12).fn)      # miss
    svc.compile_one(W.gemm(12).fn)      # hit
    m = svc.metrics()
    assert m["db"]["hits"] == 1 and m["db"]["misses"] == 1
    for kind in ("hit", "miss"):
        h = m["requests"][kind]
        assert h["count"] == 1
        assert h["p50"] == h["p99"] == h["min"] == h["max"]
    assert m["requests"]["hit"]["p50"] < m["requests"]["miss"]["p50"]


# --------------------------------------------------------------------------
# stdout dump helper shared by POM_TRACE=- and POM_DUMP_PARETO=-
# --------------------------------------------------------------------------
def test_trace_dash_prints_summary_tree(capsys):
    _fresh()
    auto_dse(W.gemm(16).fn, trace_path="-")
    out = capsys.readouterr().out
    assert "# POM trace:" in out
    assert "auto_dse" in out and "pass.dse-stage2" in out


def test_pareto_dash_prints_to_stdout(capsys):
    _fresh()
    res = auto_dse(W.gemm(16).fn, archive=True)
    res.archive.dump("-")
    out = capsys.readouterr().out
    data = json.loads(out)              # the full JSON reached stdout
    assert data["frontier"]


def test_dump_stream_flushes(tmp_path):
    # "-"/"stdout"/"stderr" write + flush; anything else is a file path
    p = tmp_path / "x.txt"
    telemetry.dump_stream("payload", str(p))
    assert p.read_text() == "payload\n"
