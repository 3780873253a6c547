"""Device time of the regional simulator's mobility contraction
(`epi.couple`, nested in `abc.simulate`) inside the wave-loop executable per
wave, on the slowest chip. A program without regions, or without the scope,
leaves nothing to read."""

from bench import scopes


def read(ctx):
    return scopes.ms_per_wave(ctx, "epi.couple")
