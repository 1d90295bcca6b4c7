"""The whole step's share of the chip's peak, in %: the program's least
time on the chip (``work()["total"]`` of every lane, against
``peaks.json``: FLOPs at the bf16 peak or the minimum bytes at the HBM
peak, whichever is longer) over the traced window's time per step. It
counts the algorithm's work from its shapes, whatever implements it."""
from benchmarks.chip.harness import roofline_s


def read(ctx):
    if ctx.steps == 0 or ctx.step_s <= 0:
        return None
    return 100.0 * roofline_s(ctx.work["total"], ctx.peaks, ctx.lanes) / ctx.step_s
