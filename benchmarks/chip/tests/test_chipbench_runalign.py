"""Host and device on one clock by the runs' ``run_id`` pairing
(``runalign.py``), and the readers of the program's spans
(``dispatch_ms``, ``idle_dispatch_pct``, ``xla_setup_s``), on synthetic
profiles and on two traces of ``gemm_4096.tiled`` recorded on a TPU v5
lite: ``data/gemm_4096_tiled.xplane.pb`` by ``run.py --trace 1`` (7 calls,
no program spans), and ``data/gemm_4096_tiled_calls.*`` by
``tests/record_trace.py --seconds 0.05`` (11 calls, with the program's
call spans on the host plane, and the compile session's spans, recorded
with an empty compilation cache)."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench_small import ROOT  # noqa: F401  (puts the repo on sys.path)

from benchmarks.chip import harness, runalign, xplane

DATA = Path(__file__).resolve().parent / "data"
TRACE = DATA / "gemm_4096_tiled.xplane.pb"
CALLS = DATA / "gemm_4096_tiled_calls.xplane.pb"
METRICS = harness.HERE / "metrics"


def reader(name):
    return harness.load_module(METRICS / f"{name}.py").read


@pytest.fixture(scope="module")
def profile():
    return runalign.load(str(TRACE))


def test_run_id_bounds_of_the_recorded_trace(profile):
    """Seven runs, each paired with its enqueue and completion callback,
    put the host 1.406-1.818 ms ahead of the device; the heuristic shift
    of ``xplane.clock_shift`` (0.882 ms) lies outside that."""
    (runs,) = profile.runs.values()
    assert [r.run_id for r in runs] == list(range(7, 14))
    lo, hi = runalign.offset_bounds(runs)
    assert (lo, hi) == (1406176.0, 1818042.0)
    ops, dev_runs, host = xplane.load(str(TRACE))
    heuristic = -xplane.clock_shift(dev_runs["/device:TPU:0"], host)
    assert heuristic == 882259.0 and heuristic < lo


def test_existing_readings_of_the_recorded_trace_are_unchanged():
    """Every metric and breakdown entry the harness reads from the
    recorded trace, pinned to what it read before the run-id alignment
    existed: the alignment adds readings and moves none."""
    summary = xplane.read(str(TRACE))
    cell = harness.resolve(harness.load_spec(), "gemm_4096.tiled")
    ctx = harness.Context(
        cell=cell.name, steps=summary.runs,
        step_s=summary.window_s / summary.runs, lanes=1,
        work=cell.program.work(cell.config, cell.traffic),
        peaks=harness.peaks_for("TPU v5 lite"), spans=[], trace=summary)
    got = {k: v["value"] for k, v in harness.read_metrics(cell, ctx).items()}
    assert got == pytest.approx({"contraction_roofline": 22.932886780390483,
                                 "fusion_ms": 0.39558399999999994,
                                 "idle_pct": 25.48911461326764,
                                 "step_mfu_pct": 15.121236157089973},
                                rel=1e-12)
    ops = [("step.1 pallas tpu_custom_call", 0.021295259),
           ("copy.2 copy", 0.001469632), ("copy.3 copy", 0.000716209),
           ("copy-done copy-done", 0.000583237),
           ("copy-start copy-start", 1e-08)]
    gaps = [("host in wait, all gaps", 0.007324258),
            ("host in dispatch, all gaps", 0.000825715),
            ("host between calls, all gaps", 8.2098e-05),
            ("longest gap, mostly host in wait", 0.001257098)]
    for got_pairs, pinned in ((summary.top_ops, ops),
                              (summary.idle_gaps, gaps)):
        assert [k for k, _ in got_pairs] == [k for k, _ in pinned]
        assert [v for _, v in got_pairs] == pytest.approx(
            [v for _, v in pinned], rel=1e-12)


def test_a_trace_without_program_spans_reads_nothing(profile):
    """The recorded trace predates the mirrored spans: both profile
    readers give nothing, as does a context with no profile."""
    assert profile.calls == []
    ctx = SimpleNamespace(spans=[], profile=str(TRACE))
    assert reader("dispatch_ms")(ctx) is None
    assert reader("idle_dispatch_pct")(ctx) is None
    bare = SimpleNamespace(spans=[])
    assert reader("dispatch_ms")(bare) is None
    assert reader("idle_dispatch_pct")(bare) is None


def _run(rid, start, end, enqueue, complete):
    return runalign.Run(rid, start, end, enqueue, complete)


def _synthetic(runs, calls):
    """One device, window [0, 100) on the host, ops at [20, 40) and
    [70, 90) on the device."""
    return runalign.Profile(ops={"/device:TPU:0": [(20, 40), (70, 90)]},
                            runs={"/device:TPU:0": runs},
                            window=(0.0, 100.0), calls=calls)


def test_synthetic_profile_reads_the_known_answer():
    # host 5 ahead: runs enqueued at host 23 and 73, seen done at 47, 97
    runs = [_run(1, 20, 40, 25, 47), _run(2, 70, 90, 75, 97)]
    assert runalign.offset_bounds(runs) == (5, 7)
    # calls on the host [10, 28) and [60, 78): device [5, 23), [55, 73),
    # of which [5, 20) and [55, 70) are idle on the device
    p = _synthetic(runs, [(10.0, 28.0), (60.0, 78.0)])
    assert runalign.dispatch_ms(p) == pytest.approx(18e-6)
    assert runalign.idle_dispatch_pct(p) == pytest.approx(30.0)


def test_empty_interval_or_no_pairs_reads_nothing():
    """A run that ends after its completion callback is seen, against
    the other's bounds, leaves no offset: the reader gives None rather
    than guessing one."""
    runs = [_run(1, 20, 40, 25, 47), _run(2, 70, 90, 80, 92)]
    assert runalign.offset_bounds(runs) is None
    calls = [(10.0, 28.0), (60.0, 78.0)]
    assert runalign.idle_dispatch_pct(_synthetic(runs, calls)) is None
    assert runalign.offset_bounds([]) is None
    assert runalign.idle_dispatch_pct(_synthetic([], calls)) is None
    # the mean call time needs no offset
    assert runalign.dispatch_ms(_synthetic(runs, calls)) is not None


def _x(name, ts, dur, **args):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "args": args}


def test_xla_setup_counts_only_what_lies_in_a_call():
    spans = [_x("xla.trace", 0, 50, fun="draw"),          # the inputs' jit
             _x("xla.trace", 100, 40, fun="step"),        # jitted()'s trace
             _x("backend.execute", 200, 1000),
             _x("backend.bufs", 200, 10),
             _x("xla.trace", 220, 100, fun="step"),
             _x("xla.trace", 230, 20, fun="add"),         # nested: once
             _x("xla.lower", 330, 200, fun="jit(step)"),
             _x("xla.compile", 540, 600, fun="jit(step)"),
             _x("xla.compile", 1300, 500, fun="jit(ref)")]
    read = reader("xla_setup_s")
    assert read(SimpleNamespace(spans=spans)) == pytest.approx(900e-6)
    assert read(SimpleNamespace(spans=spans[:2])) is None
    assert read(SimpleNamespace(spans=[])) is None


def test_readers_on_a_trace_with_the_programs_call_spans():
    """Each of the 11 calls is one ``backend.execute`` span, inside the
    harness's ``bench.dispatch`` and holding one ``backend.bufs``; the
    readers land in the ranges predicted before the recording (PERF.md),
    and the idle time inside calls is part of all idle time."""
    p = runalign.load(str(CALLS))
    assert len(p.calls) == 11
    (runs,) = p.runs.values()
    assert len(runs) == 11 and runalign.offset_bounds(runs) is not None
    ctx = SimpleNamespace(
        profile=str(CALLS),
        spans=json.loads((DATA / "gemm_4096_tiled_calls.spans.json")
                         .read_text()))
    dispatch = reader("dispatch_ms")(ctx)
    idle_dispatch = reader("idle_dispatch_pct")(ctx)
    summary = xplane.read(str(CALLS))
    idle = 100.0 * (1.0 - summary.busy_s / summary.window_s)
    assert 0.35 < dispatch < 0.6
    assert dispatch < 1e3 * summary.window_s / summary.runs
    assert 7 < idle_dispatch < 11 and idle_dispatch <= idle
    # the step's compile, the only XLA work inside the warm-up call
    assert 0.05 < reader("xla_setup_s")(ctx) < 1.0
    assert summary.top_ops[0][0] == "s.1 pallas tpu_custom_call"


def test_call_spans_nest_in_the_harness_dispatch():
    from jax.profiler import ProfileData
    host = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                  for plane in ProfileData.from_file(str(CALLS)).planes
                  if plane.name.startswith("/host:")
                  for line in plane.lines for e in line.events
                  if e.name in ("bench.dispatch", "backend.execute",
                                "backend.bufs"))
    dispatches = [(s, e) for s, e, n in host if n == "bench.dispatch"]
    for name in ("backend.execute", "backend.bufs"):
        spans = [(s, e) for s, e, n in host if n == name]
        assert len(spans) == len(dispatches) == 11
        assert all(d0 <= s and e <= d1
                   for (s, e), (d0, d1) in zip(spans, dispatches))
