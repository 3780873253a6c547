"""Host time of the fit driver's buffer set-up and upload (`abc.init`
spans) per traced fit."""

from bench import scopes


def read(ctx):
    return scopes.ms_per_fit(ctx, "abc.init")
