"""Device time of the simulator (`abc.simulate`) inside the wave-loop
executable, on the slowest chip, per sample-day: over the `sample_days`
counters (waves x batch per chip x days) of the traced fits' `abc.harvest`
spans."""

from bench import scopes


def read(ctx):
    days = sum(float(s[2].get("sample_days", 0))
               for s in scopes.fit_spans(ctx, "abc.harvest"))
    ns = scopes.scope_ns(ctx, "abc.simulate") if days else 0.0
    if not ns:
        return None
    return ns / days
