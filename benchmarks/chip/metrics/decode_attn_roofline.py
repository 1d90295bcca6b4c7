"""Share of its roofline that the Pallas decode-attention kernel reaches,
in %: the least time of the step's attention over the cache
(``work()["attention"]``: its FLOPs and the K/V bytes read once, against
``peaks.json``) over the device time per step of the Pallas kernels
(``tpu_custom_call``) in the trace. In a decode step the attention
kernel is the only Pallas kernel. Nothing when no kernel ran or the
program has no attention part."""
from benchmarks.chip.harness import roofline_s


def read(ctx):
    work = ctx.work.get("attention")
    if work is None or ctx.trace.kernel_s <= 0 or ctx.steps == 0:
        return None
    per_step = ctx.trace.kernel_s / ctx.steps
    return 100.0 * roofline_s(work, ctx.peaks, ctx.lanes) / per_step
