"""Device time of the wave-loop executable per wave, on the slowest chip.

The executable is found by its jitted name (`jit_<function>` of the wave
runner); the waves are those of the fits whose spans lie in the trace."""

from bench import tracing


def read(ctx):
    waves = ctx.traced_waves()
    per_chip = [tracing.covered(tracing.module_intervals(p, ctx.wave_module),
                                ctx.t0, ctx.t1) for p in ctx.planes]
    if not waves or not any(per_chip):
        return None
    return max(per_chip) / waves / 1e6
