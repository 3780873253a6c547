"""Host time per fit: the `bench.fit` span around `run_abc` less the time
in it during which the busiest chip ran an operation, averaged over fits."""

from bench import tracing


def read(ctx):
    fits = ctx.fit_spans()
    if not fits or not ctx.planes:
        return None
    busy = [tracing.busy(p) for p in ctx.planes]
    host = [(e - s) - max(tracing.covered(b, s, e) for b in busy)
            for s, e, _ in fits]
    return sum(host) / len(host) / 1e6
