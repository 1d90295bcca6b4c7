"""One run of one cell: set-up, a timed closed-loop window, the check of
what the window produced against the plain reference, and the metrics.

Everything a cell is made of is found by name from ``BENCHMARK.json``
(see README.md):

* ``configs/<config>.json`` — sizes, named schedules, the precision the
  configuration computes in, and the limit of each number compared;
* ``<program>.py`` beside it — the DSL program (or a ``build`` that makes
  the entry itself), its inputs, the plain reference, the
  lower-precision control and ``work()``;
* ``traffic/<traffic>.json`` — which entry the window drives, with which
  schedule, batch (or, for a ``build`` program, requests) and number of
  input sets;
* ``metrics/<metric>.py`` — ``read(ctx)`` for each per-layer metric.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from collections import Counter
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
# fixed and inside the checkout: the path is part of the cache's key
JAX_CACHE_DIR = HERE / ".jax_cache"
# JAX monitoring event of every XLA compile or persistent-cache load
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a config program or metric reader from its file."""
    name = f"chipbench_{path.parent.name}_{path.stem}".replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_spec(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclass
class Cell:
    name: str
    chips: int
    config: dict                 # configs/<config>.json
    program: Any                 # <program>.py beside the config's file
    traffic: dict                # traffic/<traffic>.json
    end_to_end: List[dict]       # BENCHMARK.json entries this cell reports
    per_layer: List[dict]

    @property
    def builds(self) -> bool:
        """Whether the program builds its own entry (``build``) instead of
        being a DSL program that ``pom.compile`` lowers."""
        return hasattr(self.program, "build")

    @property
    def lanes(self) -> int:
        """Program invocations in one step (one call of the entry): the
        vmapped lanes (``batch``) of a compiled program; 1 for a ``build``
        program, which counts its requests in ``work()``."""
        return self.traffic.get("batch", 1)


def resolve(spec: dict, name: str, root: Path = ROOT) -> Cell:
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config_file = root / entry["file"]
    config = load_json(config_file)
    traffic = load_json(HERE / "traffic" / f"{w['traffic']}.json")
    if (traffic["loop"], traffic["callers"]) != ("closed", 1):
        raise ValueError(f"{w['traffic']}: the harness runs one caller in a "
                         "closed loop")
    cell = Cell(
        name=name, chips=w["chips"], config=config,
        program=load_module(config_file.parent / f"{config['program']}.py"),
        traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if applies(m, name)])
    if cell.builds and "batch" in traffic:
        raise ValueError(f"{w['traffic']}: 'batch' is the vmapped lanes of a "
                         "compiled program; a build program names its "
                         "requests under a key of its own")
    return cell


def peaks_for(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; a kind not in the table is
    an error, never a default."""
    table = load_json(HERE / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in peaks.json (known: {sorted(table)})")
    return table[device_kind]


def roofline_s(work: dict, peaks: dict, lanes: int = 1) -> float:
    """Least time the chip could take: FLOPs at the bf16 peak or the
    minimum bytes at the HBM peak, whichever is longer."""
    return lanes * max(work["flops"] / peaks["flops_per_s"],
                       work["bytes"] / peaks["hbm_bytes_per_s"])


def configure_jax() -> None:
    """Before JAX first compiles: the persistent compilation cache at the
    fixed in-checkout directory, every program cached however fast it
    compiled, and no TPU runtime logs under a fixed /tmp path."""
    os.environ["TPU_LOG_DIR"] = "disabled"
    import jax
    jax.config.update("jax_compilation_cache_dir", str(JAX_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class JaxEvents:
    """Counts of JAX's monitoring events (compiles, cache hits/misses)."""

    def __init__(self):
        import jax
        self.counts: Counter = Counter()
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        self.counts[name] += 1

    def _duration(self, name, _secs, **_):
        self.counts[name] += 1

    def snapshot(self) -> Counter:
        return Counter(self.counts)


def seed_key(seed: int):
    """A JAX key from any whole number, including ones over 32 bits."""
    import jax
    import numpy as np
    words = np.random.SeedSequence(seed % 2**128).generate_state(2)
    return jax.random.fold_in(jax.random.key(int(words[0] >> 1)),
                              int(words[1] >> 1))


def max_rel_err(got, ref) -> float:
    """max |got - ref| / max |ref| over the whole array (NaN if any)."""
    import jax.numpy as jnp
    got = jnp.asarray(got, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(ref)), jnp.float32(1e-30))
    return float(jnp.max(jnp.abs(got - ref)) / scale)


def compare(outputs: Dict[int, dict], sets: List[dict], reference,
            limits: dict) -> Dict[str, dict]:
    """Every array each input set's last output holds, against the
    reference over that set's inputs: the worst error per array. Only
    the sets the window used are due."""
    import numpy as np
    errs: Dict[str, list] = {}
    for i, out in sorted(outputs.items()):
        ref = reference(sets[i])
        for name, got in out.items():
            errs.setdefault(name, []).append(max_rel_err(got, ref[name]))
    limit = limits["max_rel_err"]
    return {f"max_rel_err.{name}": {"value": float(np.max(v)), "limit": limit}
            for name, v in sorted(errs.items())}


def build_entry(cell: Cell, sets: Optional[List[dict]] = None):
    """The entry the window drives. A program with ``build(config,
    traffic, sets)`` makes it itself from the drawn input sets; any other
    is compiled with its schedule, and the entry is the traffic's entry of
    the artifact: ``jitted()`` or ``batched(B)``."""
    if cell.builds:
        return cell.program.build(cell.config, cell.traffic, sets)
    from repro.core.pipeline import compile as pom_compile
    fn, options = cell.program.program(cell.config, cell.traffic["schedule"])
    prog = pom_compile(fn, target="pallas", **options)
    kind = cell.traffic["entry"]
    if kind == "jitted":
        return prog.jitted()
    if kind == "batched":
        return prog.batched(cell.traffic["batch"])
    raise ValueError(f"unknown entry {kind!r}")


def make_inputs(cell: Cell, seed: int) -> List[dict]:
    """The traffic's input sets, drawn on the device from ``seed`` in one
    jitted call."""
    import jax
    n_sets = cell.traffic["input_sets"]

    def draw(key):
        return [cell.program.inputs(jax.random.fold_in(key, i), cell.config,
                                    cell.traffic) for i in range(n_sets)]
    return jax.block_until_ready(jax.jit(draw)(seed_key(seed)))


def reference_fn(cell: Cell) -> Callable:
    import jax
    ref = lambda a: cell.program.reference(a, cell.config)  # noqa: E731
    return jax.jit(jax.vmap(ref) if "batch" in cell.traffic else ref)


def control_fn(cell: Cell) -> Callable:
    import jax
    ctl = lambda a: cell.program.control(a, cell.config)  # noqa: E731
    return jax.jit(jax.vmap(ctl) if "batch" in cell.traffic else ctl)


@dataclass
class Window:
    seconds: float = 0.0         # from opening to the end of the last step
    step_s: List[float] = field(default_factory=list)
    outputs: Dict[int, dict] = field(default_factory=dict)
    failures: List[str] = field(default_factory=list)


def annotations(on: bool):
    import jax
    if on:
        return jax.profiler.TraceAnnotation
    return lambda _name: contextlib.nullcontext()


def drive(entry, sets: List[dict], writes, seconds: float,
          annotate) -> Window:
    """The closed loop: one caller, each call blocked on before the next
    starts, input sets in turn, until ``seconds`` have passed. Keeps the
    written arrays of each set's last call."""
    import jax
    w = Window()
    k = 0
    t_open = t = time.perf_counter()
    with annotate("bench.window"):
        while t - t_open < seconds:
            i = k % len(sets)
            try:
                with annotate("bench.dispatch"):
                    out = entry(sets[i])
                with annotate("bench.wait"):
                    out = jax.block_until_ready(out)
                w.outputs[i] = {name: out[name] for name in writes}
            except Exception as e:  # a failed call is counted, not fatal
                w.failures.append(f"{type(e).__name__}: {e}")
            k += 1
            t_end = time.perf_counter()
            w.step_s.append(t_end - t)
            t = t_end
    w.seconds = t - t_open
    return w


def longest_call(step_s: List[float]) -> str:
    """The slowest call and when in the window it started."""
    if not step_s:
        return "none"
    k = max(range(len(step_s)), key=step_s.__getitem__)
    return f"{1e3 * step_s[k]:.3f} ms, starting at {sum(step_s[:k]):.3f} s"


def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def percentile(values: List[float], q: float) -> float:
    import numpy as np
    return float(np.percentile(np.asarray(values), q))


@dataclass
class Context:
    """What a per-layer metric's ``read(ctx)`` may read."""
    cell: str
    steps: int                   # calls completed in the traced window
    step_s: float                # the traced window's time per step (host)
    lanes: int                   # program invocations per step
    work: dict                   # config program's work(): per invocation
    peaks: dict                  # peaks.json entry of the device
    spans: List[dict]            # telemetry span events of the compile
    trace: Any                   # xplane.Summary of the window


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             devices, peaks: dict, t0: float,
             trace_dir: Optional[str] = None,
             log=lambda msg: print(msg, flush=True)) -> dict:
    """Set-up, window, check and metrics of one run; returns the result
    line. The caller has checked the devices."""
    import jax
    from repro.core import telemetry

    events = JaxEvents()
    if trace:
        telemetry.start_trace(os.devnull)
    try:
        if cell.builds:          # the entry is made from the drawn inputs
            sets = make_inputs(cell, seed)
            entry = build_entry(cell, sets)
        else:
            entry = build_entry(cell)
            sets = make_inputs(cell, seed)
        writes = sorted(jax.eval_shape(reference_fn(cell), sets[0]))
        jax.block_until_ready(entry(sets[0]))          # compile + warm-up
    finally:
        spans = telemetry.stop_trace(export=False).events if trace else []
    setup = events.snapshot()

    profile_dir = None
    if trace:
        profile_dir = trace_dir or tempfile.mkdtemp(prefix="chipbench-")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # annotations, not every call
        jax.profiler.start_trace(profile_dir, profiler_options=options)
    setup_s = time.perf_counter() - t0
    try:
        win = drive(entry, sets, writes, seconds, annotations(trace))
    finally:
        if trace:
            jax.profiler.stop_trace()
    in_window = events.snapshot() - setup
    peak = memory_peak(devices)
    del entry
    log(f"set-up {setup_s:.3f} s: compile cache hits "
        f"{setup[CACHE_HIT_EVENT]}, misses {setup[CACHE_MISS_EVENT]}, "
        f"compiles {setup[COMPILE_EVENT]}; window {win.seconds:.3f} s, "
        f"{len(win.step_s)} steps, {len(win.failures)} failed, "
        f"compiles in window {in_window[COMPILE_EVENT]}; longest call "
        f"{longest_call(win.step_s)}")
    for msg in win.failures[:3]:
        log(f"failed call: {msg}")

    checks = compare(win.outputs, sets, reference_fn(cell),
                     cell.config["limits"])
    checks["window_without_output"] = {"value": int(not win.outputs),
                                       "limit": 0}
    checks["calls_failed"] = {"value": len(win.failures), "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(win.step_s),
              "failed": len(win.failures)}
    steps = len(win.step_s)
    if not trace:
        values = {"setup_s": setup_s,
                  "step_ms": 1e3 * win.seconds / max(steps, 1),
                  "step_ms_p95": 1e3 * percentile(win.step_s, 95)
                  if steps else None}
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    else:
        from benchmarks.chip import xplane
        summary = xplane.read(profile_dir)
        if trace_dir is None:
            shutil.rmtree(profile_dir, ignore_errors=True)
        ctx = Context(cell=cell.name, steps=steps,
                      step_s=win.seconds / max(steps, 1), lanes=cell.lanes,
                      work=cell.program.work(cell.config, cell.traffic),
                      peaks=peaks,
                      spans=spans, trace=summary)
        result["metrics"] = read_metrics(cell, ctx)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.top_ops,
                               "idle_gaps": summary.idle_gaps}
    result["device"] = device
    result["checks"] = checks
    return result


def read_metrics(cell: Cell, ctx: Context) -> dict:
    out = {}
    for m in cell.per_layer:
        value = load_module(HERE / "metrics" / f"{m['name']}.py").read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def emit(result: dict) -> None:
    """The numbers compared, each beside its limit, as the last lines of
    standard error; the result as the last line of standard output."""
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
