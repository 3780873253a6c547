"""Share of the traced waves whose compaction of accepted rows took the
full scatter (the `abc.accept_fallback` scope inside `abc.accept`) instead
of the bounded window, on the chip where most waves did.

A wave that falls back runs its scatter's operations after its simulation,
so the merged runs under the scope inside the wave-loop executable that lie
between the same two simulation runs count one wave. The program's wave
loop is lowered to see whether it has the branch at all: a program without
it gives nothing, one with it that never took it reads 0.0."""

import numpy as np

from bench import scopes, tracing

SCOPE = "abc.accept_fallback"


def _has_branch() -> bool:
    runners = scopes._program_runners()
    return bool(runners) and SCOPE in scopes.loop_lowering(
        runners[0]).as_text(debug_info=True)


def fallback_waves(plane, module: str, t0: float, t1: float) -> int:
    """Waves of `plane` inside [t0, t1] with an operation under the scope."""
    runs = tracing.clip(scopes.scope_intervals(plane, SCOPE, module), t0, t1)
    ends = scopes.scope_intervals(plane, "abc.simulate", module)[:, 1]
    return len(np.unique(np.searchsorted(ends, runs[:, 0])))


def read(ctx):
    waves = ctx.traced_waves()
    if not waves or not _has_branch():
        return None
    scopes.attach(ctx)
    return max((fallback_waves(p, ctx.wave_module, ctx.t0, ctx.t1)
                for p in ctx.planes), default=0) / waves
