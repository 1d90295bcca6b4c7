"""XLA's share of set-up: the trace, lowering and compile or cache load
of the program's step, in seconds. The union of the ``xla.*`` spans
(``core/telemetry.py`` records them from ``jax.monitoring``) that lie in
a ``backend.execute`` span of the compile session, which is the warm-up
call; the harness's own jits (inputs, reference) lie outside it. Nothing
when no such span was recorded."""
from benchmarks.chip.xplane import union


def read(ctx):
    calls = [(e["ts"], e["ts"] + e["dur"]) for e in ctx.spans
             if e.get("ph") == "X" and e["name"] == "backend.execute"]
    xla = [(e["ts"], e["ts"] + e["dur"]) for e in ctx.spans
           if e.get("ph") == "X" and e["name"].startswith("xla.")]
    inside = [(s, e) for s, e in xla
              if any(c0 <= s and e <= c1 for c0, c1 in calls)]
    if not inside:
        return None
    return sum(e - s for s, e in union(inside)) * 1e-6
