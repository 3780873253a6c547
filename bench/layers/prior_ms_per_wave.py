"""Device time of the prior draw (`abc.prior`) inside the wave-loop
executable per wave, on the slowest chip.

On a TPU v5e the scope holds one fusion that draws theta (threefry bits,
uniform, scaling into the prior's box), which the simulator reads as it is,
and the copy of theta into the layout of the accept buffer's scatter, which
XLA gives the draw's `op_name` and which takes about two thirds of the time.
The scatters read that copy; they draw nothing again."""

from bench import scopes


def read(ctx):
    return scopes.ms_per_wave(ctx, "abc.prior")
