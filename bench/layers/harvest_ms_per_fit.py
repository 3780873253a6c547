"""Host time of the fit driver's fetch of the accept buffers (`abc.harvest`
spans) per traced fit."""

from bench import scopes


def read(ctx):
    return scopes.ms_per_fit(ctx, "abc.harvest")
