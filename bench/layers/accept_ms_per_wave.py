"""Device time of acceptance and compaction (`abc.accept`) inside the
wave-loop executable per wave, on the slowest chip."""

from bench import scopes


def read(ctx):
    return scopes.ms_per_wave(ctx, "abc.accept")
