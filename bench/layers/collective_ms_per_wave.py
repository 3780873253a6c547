"""Device time of the exchanges between chips inside the wave-loop
executable per wave, on the chip that spends most in them."""

from bench import tracing


def read(ctx):
    waves = ctx.traced_waves()
    per_chip = [tracing.covered(tracing.op_intervals(p, tracing.COLLECTIVE,
                                                     ctx.wave_module),
                                ctx.t0, ctx.t1) for p in ctx.planes]
    if not waves or not any(per_chip):
        return None
    return max(per_chip) / waves / 1e6
