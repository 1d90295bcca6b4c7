"""Time of the compile's lowering passes: every ``pass.*`` span that
``core/telemetry.py`` records except the DSE's, in seconds (graph, poly,
loop IR and the ``lower-pallas`` backend pass; XLA's own compile runs at
the first call and is not in it)."""


def read(ctx):
    us = [e["dur"] for e in ctx.spans
          if e.get("ph") == "X" and e["name"].startswith("pass.")
          and not e["name"].startswith("pass.dse-")]
    return sum(us) * 1e-6 if us else None
