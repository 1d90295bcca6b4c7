"""The reduction from a profiler trace to the per-layer metrics, on a trace
of ``gemm_4096.tiled`` recorded on a TPU v5 lite by ``run.py --trace 1``
(``data/gemm_4096_tiled.xplane.pb``)."""
from pathlib import Path

import pytest

from chipbench_small import ROOT  # noqa: F401  (puts the repo on sys.path)

from benchmarks.chip import harness, xplane

TRACE = Path(__file__).resolve().parent / "data" / "gemm_4096_tiled.xplane.pb"
KERNEL_HLO = ('%step.1 = f32[4096,4096]{1,0:T(8,128)} custom-call('
              'f32[4096,4096]{1,0:T(8,128)} %bufs__A__.1), '
              'custom_call_target="tpu_custom_call"')


@pytest.fixture(scope="module")
def summary():
    return xplane.read(str(TRACE))


def test_op_label():
    assert xplane.op_label(KERNEL_HLO) == "step.1 pallas tpu_custom_call"
    assert xplane.op_label(
        "%fusion.7 = f32[16760836]{0:T(1024)} fusion(f32[4096,4096]"
        "{1,0:T(8,128)S(1)} %custom-call.18), kind=kCustom") \
        == "fusion.7 fusion"
    assert xplane.op_label(
        "%copy-start = (f32[8]{0:T(8)S(1)}, u32[]{:S(2)}) copy-start("
        "f32[8]{0} %b)") == "copy-start copy-start"


def test_union_and_clock_shift():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    host = [("bench.dispatch", 10.0, 1.0), ("bench.dispatch", 30.0, 1.0)]
    assert xplane.clock_shift([8.0, 31.0], host) == -2.0
    assert xplane.clock_shift([12.0, 31.0], host) == 0.0
    assert xplane.clock_shift([12.0], host) == 0.0


def test_ops_that_enclose_others_are_not_counted_twice():
    op = xplane.Op
    loop = op("while.1 while", 0, 100, False)
    inner = [op("fusion.1 fusion", 5, 20, False),
             op("call.2 pallas tpu_custom_call", 30, 60, True),
             op("copy.3 copy", 90, 10, False)]      # ends with the loop
    after = op("fusion.4 fusion", 100, 7, False)   # starts as it ends
    got = xplane.leaves([loop] + inner + [after])
    assert got == inner + [after]
    # an async slice beside a fusion is no control flow: both count
    beside = [op("fusion.5 fusion", 120, 30, False),
              op("slice-done async-done", 125, 10, False)]
    assert xplane.leaves(beside) == beside
    summary = xplane.summarize(
        {"/device:TPU:0": [loop] + inner + [after] + beside}, {},
        [("bench.window", 0, 200)])
    assert summary.op_s == pytest.approx(137e-9)
    assert summary.kernel_s == pytest.approx(60e-9)
    assert summary.busy_s == pytest.approx(137e-9)
    assert [name for name, _ in summary.top_ops][0] == \
        "call.2 pallas tpu_custom_call"


def test_gaps_are_attributed_to_host_activity():
    inner = xplane._Spans([(0, 4, "bench.dispatch"), (4, 10, "bench.wait"),
                           (12, 14, "bench.dispatch")])
    parts = xplane.attribute(inner, (0, 20), 8, 22)
    assert parts == {"host in wait": 2, "host in dispatch": 2,
                     "host between calls": 8, "host outside the window": 2}


def test_summary_of_the_recorded_trace(summary):
    assert summary.devices == 1
    assert 0 < summary.kernel_s < summary.op_s
    assert summary.busy_s <= summary.op_s + 1e-12
    assert 0 < summary.busy_s < summary.window_s
    assert summary.top_ops[0][0].endswith("pallas tpu_custom_call")
    assert len(summary.top_ops) <= 10 and len(summary.idle_gaps) <= 10
    gap_s = sum(s for label, s in summary.idle_gaps
                if label.endswith("all gaps"))
    assert gap_s == pytest.approx(summary.window_s - summary.busy_s,
                                  rel=1e-6)


def _ctx(summary, spans=()):
    cell = harness.resolve(harness.load_spec(), "gemm_4096.tiled")
    steps = summary.runs            # one program run a call
    return harness.Context(
        cell=cell.name, steps=steps, step_s=summary.window_s / steps,
        lanes=1, work=cell.program.work(cell.config, cell.traffic),
        peaks=harness.peaks_for("TPU v5 lite"), spans=list(spans),
        trace=summary)


def test_per_layer_metrics_of_the_recorded_trace(summary):
    ctx = _ctx(summary)
    cell = harness.resolve(harness.load_spec(), "gemm_4096.tiled")
    got = harness.read_metrics(cell, ctx)
    # a kernel of ~3.04 ms against a 0.698 ms bound
    assert 15 < got["contraction_roofline"]["value"] < 30
    assert 0 < got["step_mfu_pct"]["value"] < got["contraction_roofline"]["value"]
    assert 0 < got["idle_pct"]["value"] < 100
    assert 0 < got["fusion_ms"]["value"] < 1.0
    assert "dse_s" not in got and "passes_s" not in got   # no spans given
    for m in got.values():
        assert m["value"] <= 100 or m["unit"] != "%"


def test_span_readers():
    spans = [{"name": "pass.build-graph", "ph": "X", "dur": 1000.0},
             {"name": "pass.dse-stage1", "ph": "X", "dur": 2e6},
             {"name": "pass.dse-stage2", "ph": "X", "dur": 5e5},
             {"name": "pass.lower-pallas", "ph": "X", "dur": 3000.0},
             {"name": "compile", "ph": "X", "dur": 9e6}]
    ctx = harness.Context(cell="gemm_4096.dse", steps=1, step_s=1.0, lanes=1,
                          work={}, peaks={}, spans=spans, trace=None)
    metrics = harness.HERE / "metrics"
    dse = harness.load_module(metrics / "dse_s.py").read(ctx)
    passes = harness.load_module(metrics / "passes_s.py").read(ctx)
    assert dse == pytest.approx(2.5) and passes == pytest.approx(0.004)
    ctx.spans = spans[:1]
    assert harness.load_module(metrics / "dse_s.py").read(ctx) is None
