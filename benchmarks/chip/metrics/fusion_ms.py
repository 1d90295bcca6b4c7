"""Device time per step of every operation that is not a Pallas kernel:
the vectorized XLA path of ``backend_pallas._build_step`` (gathers,
reductions, scatters) and XLA's copies, in ms."""


def read(ctx):
    if ctx.steps == 0:
        return None
    other = ctx.trace.op_s - ctx.trace.kernel_s
    return 1e3 * other / ctx.steps if other > 0 else None
