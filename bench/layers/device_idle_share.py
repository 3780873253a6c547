"""Share of the window in which no operation ran, on the idlest chip."""

from bench import tracing


def read(ctx):
    if not ctx.planes:
        return None
    length = ctx.t1 - ctx.t0
    return max(1.0 - tracing.covered(tracing.busy(p), ctx.t0, ctx.t1) / length
               for p in ctx.planes)
