"""Share of the traced window, in %, in which the device runs no op while
the host is inside one of the program's ``backend.execute`` spans: device
idle time that the program's own call path holds. Host and device are put
on one clock by the runs' ``run_id`` pairing (``runalign.py``). Reads
``ctx.profile``, the path of the window's profile; nothing without one,
without such spans, or where the runs allow no offset."""
from benchmarks.chip import runalign


def read(ctx):
    path = getattr(ctx, "profile", None)
    return runalign.idle_dispatch_pct(runalign.load(path)) if path else None
