"""Reduce a JAX profiler trace (``*.xplane.pb``) of one window to numbers.

The device planes (``/device:TPU:<n>``) hold one event per XLA operation
on their ``XLA Ops`` line and one per program run on ``XLA Modules``;
the host plane holds the harness's ``bench.*`` annotations. A Pallas
kernel is the operation whose HLO is a ``tpu_custom_call`` (the kernels
carry no stable names yet, so they are found by kind).

Device and host clocks in one profile differ by up to about a
millisecond. The host's times are shifted onto the device's by the
least shift that starts no program run before the dispatch that issued
it began (runs and dispatches paired in order).
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

KERNEL = 'custom_call_target="tpu_custom_call"'
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
TOP = 10
# opcodes whose device event spans the ops they run
CONTROL_FLOW = ("while", "conditional", "call")


@dataclass
class Op:
    name: str
    start_ns: float
    dur_ns: float
    kernel: bool


@dataclass
class Summary:
    window_s: float                  # the host's bench.window annotation
    busy_s: float                    # union of device op intervals
    op_s: float                      # sum of leaf device op durations
    kernel_s: float                  # of which Pallas kernels
    runs: int                        # program runs (XLA Modules events)
    devices: int
    top_ops: List[Tuple[str, float]]     # by total time, seconds
    idle_gaps: List[Tuple[str, float]]   # by what the host was doing


def op_label(hlo: str) -> str:
    """``%fusion.7 = f32[..] fusion(...), ...`` -> ``fusion.7 fusion``;
    a Pallas kernel is marked as such."""
    name, _, rest = hlo.partition(" = ")
    m = _OPCODE.search(" " + rest)
    opcode = m.group(1) if m else "?"
    if KERNEL in rest:
        opcode = "pallas tpu_custom_call"
    return f"{name.lstrip('%')} {opcode}"


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def leaves(ops: List[Op]) -> List[Op]:
    """The ops less the control-flow ops (``while``, ``conditional``,
    ``call``) that enclose another op: the ``while`` of a scan over
    layers spans the ops of its body, which would otherwise be counted
    twice. Other ops may overlap (async copies and slices run beside a
    fusion) and are all counted."""
    parents, open_ = set(), []
    for i, o in sorted(enumerate(ops), key=lambda io: (io[1].start_ns,
                                                       -io[1].dur_ns)):
        end = o.start_ns + o.dur_ns
        while open_ and open_[-1][1] <= o.start_ns:
            open_.pop()
        if open_ and end <= open_[-1][1]:
            parents.add(open_[-1][0])
        if o.name.rsplit(" ", 1)[-1] in CONTROL_FLOW:
            open_.append((i, end))
    return [o for i, o in enumerate(ops) if i not in parents]


def find_profile(path: str) -> str:
    if os.path.isfile(path):
        return path
    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {path}")
    return files[-1]


def load(path: str):
    """(device ops per device, device run starts per device, host
    annotations) from a profile file or the directory it was written to."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_profile(path))
    ops: Dict[str, List[Op]] = {}
    runs: Dict[str, List[float]] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] = [
                        Op(op_label(e.name), e.start_ns, e.duration_ns,
                           KERNEL in e.name) for e in line.events]
                elif line.name == "XLA Modules":
                    runs[plane.name] = sorted(e.start_ns for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.duration_ns)
                         for e in line.events if e.name.startswith("bench.")]
    return ops, runs, host


def clock_shift(runs: List[float], host) -> float:
    """ns to add to host times to put them on the device's clock."""
    dispatch = sorted(s for n, s, _ in host if n == "bench.dispatch")
    if not runs or len(runs) != len(dispatch):
        return 0.0
    return min(0.0, min(r - d for r, d in zip(runs, dispatch)))


def attribute(inner, window, g0: float, g1: float) -> Dict[str, float]:
    """ns of the device gap [g0, g1) by what the host was doing: in one of
    the harness's annotations (``inner``: sorted, disjoint (start, end,
    name)), between them inside the window, or outside it."""
    out: Dict[str, float] = {}
    covered = 0.0
    i = max(bisect.bisect_right(inner.starts, g0) - 1, 0)
    while i < len(inner.spans) and inner.spans[i][0] < g1:
        s, e, name = inner.spans[i]
        ov = min(g1, e) - max(g0, s)
        if ov > 0:
            label = "host in " + name[len("bench."):]
            out[label] = out.get(label, 0.0) + ov
            covered += ov
        i += 1
    w0, w1 = window
    in_window = max(0.0, min(g1, w1) - max(g0, w0))
    if in_window - covered > 0:
        out["host between calls"] = in_window - covered
    if (g1 - g0) - in_window > 0:
        out["host outside the window"] = (g1 - g0) - in_window
    return out


class _Spans:
    def __init__(self, spans):
        self.spans = sorted(spans)
        self.starts = [s for s, _, _ in self.spans]


def summarize(ops: Dict[str, List[Op]], runs: Dict[str, List[float]],
              host) -> Summary:
    windows = [(s, d) for n, s, d in host if n == "bench.window"]
    if not windows or not ops:
        raise ValueError("trace has no bench.window annotation or no "
                         "device operations")
    window_start, window_ns = windows[0]
    busy, op_ns, kernel_ns = [], 0.0, 0.0
    per_op: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    longest: Optional[Tuple[str, float]] = None
    for dev, dev_ops in sorted(ops.items()):
        spans = union([(o.start_ns, o.start_ns + o.dur_ns) for o in dev_ops])
        busy.append(sum(e - s for s, e in spans))
        for o in leaves(dev_ops):
            op_ns += o.dur_ns
            kernel_ns += o.dur_ns if o.kernel else 0.0
            per_op[o.name] = per_op.get(o.name, 0.0) + o.dur_ns
        shift = clock_shift(runs.get(dev, []), host)
        inner = _Spans([(s + shift, s + shift + d, n) for n, s, d in host
                        if n != "bench.window"])
        window = (window_start + shift, window_start + shift + window_ns)
        edges = [(window[0], window[0])] + spans + [(window[1], window[1])]
        for (_, g0), (g1, _) in zip(edges, edges[1:]):
            if g1 <= g0:
                continue
            parts = attribute(inner, window, g0, g1)
            for label, ns in parts.items():
                gaps[label] = gaps.get(label, 0.0) + ns
            if longest is None or g1 - g0 > longest[1]:
                longest = (max(parts, key=parts.get), g1 - g0)
    n = len(ops)
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:TOP - 1]
    idle = [(f"{label}, all gaps", ns * 1e-9 / n) for label, ns in idle]
    if longest is not None:
        idle.append((f"longest gap, mostly {longest[0]}", longest[1] * 1e-9))
    return Summary(window_s=window_ns * 1e-9, busy_s=sum(busy) * 1e-9 / n,
                   op_s=op_ns * 1e-9 / n, kernel_s=kernel_ns * 1e-9 / n,
                   runs=sum(len(r) for r in runs.values()) // n, devices=n, top_ops=[(k, v * 1e-9) for k, v in top],
                   idle_gaps=idle)


def read(path: str) -> Summary:
    return summarize(*load(path))
