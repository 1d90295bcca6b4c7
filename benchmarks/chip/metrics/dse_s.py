"""DSE time of the cell's compile: the ``pass.dse-*`` spans (Stage-1 and
stage-2 DSE passes of ``core/pipeline.py``) that ``core/telemetry.py``
records, in seconds. Nothing when the schedule runs no DSE."""


def read(ctx):
    us = [e["dur"] for e in ctx.spans
          if e.get("ph") == "X" and e["name"].startswith("pass.dse-")]
    return sum(us) * 1e-6 if us else None
