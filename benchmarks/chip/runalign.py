"""Put a profile's host plane on its device's clock by each program run's
``run_id``, and read the program's call spans against the device.

Every program run on a device (an ``XLA Modules`` event) carries a
``run_id``. On the host, PJRT's ``DoEnqueueProgram``, which submits the
run, and ``CompleteCallbacks``, which sees it finish, carry the same one.
A run starts no earlier than its enqueue and ends no later than its
completion callback, so each paired run bounds the host-minus-device
offset ``o`` (host time - ``o`` = device time):

    enqueue start - run start  <=  o  <=  callback start - run end

The offset used is the lower end of the interval that every run allows:
the run that starts soonest after its enqueue starts at it. Where the
interval is empty, or no run pairs, there is no offset, and nothing that
needs one is read.

The program's call spans are ``core/telemetry.py``'s ``backend.execute``
spans, which a telemetry session mirrors onto the profile's host plane
(``jax.profiler.TraceAnnotation``); the window is the harness's
``bench.window`` annotation.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from benchmarks.chip.xplane import find_profile, union

CALL = "backend.execute"
ENQUEUE = "DoEnqueueProgram"
COMPLETE = "CompleteCallbacks"

Interval = Tuple[float, float]


@dataclass
class Run:
    run_id: int
    start_ns: float              # device clock
    end_ns: float
    enqueue_ns: float            # host clock: DoEnqueueProgram start
    complete_ns: float           # host clock: CompleteCallbacks start


@dataclass
class Profile:
    ops: Dict[str, List[Interval]]       # device -> XLA op intervals
    runs: Dict[str, List[Run]]           # device -> runs paired by run_id
    window: Optional[Interval]           # host: bench.window
    calls: List[Interval]                # host: backend.execute spans


def _ordinal(plane: str) -> int:
    return int(plane.rsplit(":", 1)[1])


@functools.lru_cache(maxsize=4)
def load(path: str) -> Profile:
    """The device ops and runs, the host's window, call spans and the
    runs' enqueue and completion times, from a profile file or the
    directory it was written to."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_profile(path))
    ops: Dict[str, List[Interval]] = {}
    device_runs: Dict[str, Dict[int, Interval]] = {}
    host: Dict[str, Dict[Tuple[int, int], float]] = {ENQUEUE: {},
                                                     COMPLETE: {}}
    window: Optional[Interval] = None
    calls: List[Interval] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] = [(e.start_ns, e.start_ns + e.duration_ns)
                                       for e in line.events]
                elif line.name == "XLA Modules":
                    device_runs[plane.name] = {
                        dict(e.stats)["run_id"]:
                            (e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in host:
                        st = dict(e.stats)
                        key = (st.get("device_ordinal", 0), st["run_id"])
                        host[e.name][key] = e.start_ns
                    elif e.name == CALL:
                        calls.append((e.start_ns, e.start_ns + e.duration_ns))
                    elif e.name == "bench.window" and window is None:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
    runs = {}
    for dev, by_id in device_runs.items():
        n = _ordinal(dev)
        runs[dev] = [Run(rid, s, e, host[ENQUEUE][(n, rid)],
                         host[COMPLETE][(n, rid)])
                     for rid, (s, e) in sorted(by_id.items())
                     if (n, rid) in host[ENQUEUE]
                     and (n, rid) in host[COMPLETE]]
    return Profile(ops=ops, runs=runs, window=window, calls=sorted(calls))


def offset_bounds(runs: List[Run]) -> Optional[Interval]:
    """(least, greatest) host-minus-device offset in ns that every run
    allows; None when no run pairs or the runs allow none."""
    if not runs:
        return None
    lo = max(r.enqueue_ns - r.start_ns for r in runs)
    hi = min(r.complete_ns - r.end_ns for r in runs)
    return (lo, hi) if lo <= hi else None


def _in_window(calls: List[Interval], window: Interval) -> List[Interval]:
    return [(s, e) for s, e in calls if window[0] <= s and e <= window[1]]


def _overlap_ns(a: List[Interval], b: List[Interval]) -> float:
    """Total overlap of two sorted lists of disjoint intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _idle(busy: List[Interval], window: Interval) -> List[Interval]:
    """The parts of ``window`` that no interval of ``busy`` (sorted,
    disjoint) covers."""
    out, t = [], window[0]
    for s, e in busy:
        if s > t:
            out.append((t, min(s, window[1])))
        t = max(t, e)
        if t >= window[1]:
            break
    if t < window[1]:
        out.append((t, window[1]))
    return [(s, e) for s, e in out if e > s]


def dispatch_ms(p: Profile) -> Optional[float]:
    """Mean time of the program's calls that lie in the window, in ms."""
    if p.window is None:
        return None
    calls = _in_window(p.calls, p.window)
    if not calls:
        return None
    return 1e-6 * sum(e - s for s, e in calls) / len(calls)


def idle_dispatch_pct(p: Profile) -> Optional[float]:
    """Share of the window, in %, in which the device runs no op while the
    host is inside one of the program's calls, averaged over devices."""
    if p.window is None or not p.ops:
        return None
    calls = _in_window(p.calls, p.window)
    if not calls:
        return None
    shares = []
    for dev, dev_ops in sorted(p.ops.items()):
        bounds = offset_bounds(p.runs.get(dev, []))
        if bounds is None:
            return None
        o = bounds[0]
        window = (p.window[0] - o, p.window[1] - o)
        dev_calls = union([(s - o, e - o) for s, e in calls])
        idle = _idle(union(dev_ops), window)
        shares.append(_overlap_ns(idle, dev_calls)
                      / (window[1] - window[0]))
    return 100.0 * sum(shares) / len(shares)
