"""Share of its roofline that the compiled contraction kernel reaches, in
%: the statement's least time on the chip (``work()["contraction"]``
against ``peaks.json``) over the device time per step of the Pallas
kernels (``tpu_custom_call``) in the trace. Nothing when no kernel ran or
the configuration has no contraction."""
from benchmarks.chip.harness import roofline_s


def read(ctx):
    work = ctx.work.get("contraction")
    if work is None or ctx.trace.kernel_s <= 0 or ctx.steps == 0:
        return None
    per_step = ctx.trace.kernel_s / ctx.steps
    return 100.0 * roofline_s(work, ctx.peaks, ctx.lanes) / per_step
