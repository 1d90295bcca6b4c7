"""Host time of one call of the program, in ms: the mean of the
``backend.execute`` spans (``core/telemetry.py``, mirrored onto the
profile's host plane while a telemetry session is open) that lie in the
traced window. It holds building the call's buffers (``backend.bufs``)
and dispatching the step. Reads ``ctx.profile``, the path of the
window's profile; nothing without one or without such spans."""
from benchmarks.chip import runalign


def read(ctx):
    path = getattr(ctx, "profile", None)
    return runalign.dispatch_ms(runalign.load(path)) if path else None
