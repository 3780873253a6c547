"""Device time of the simulator (`abc.simulate`) inside the wave-loop
executable, on the slowest chip, per region-day: over the `region_days`
counters (waves x batch per chip x days x regions) of the traced fits'
`abc.harvest` spans. A program that writes no such counter leaves nothing
to read."""

from bench import scopes


def read(ctx):
    days = sum(float(s[2].get("region_days", 0))
               for s in scopes.fit_spans(ctx, "abc.harvest"))
    ns = scopes.scope_ns(ctx, "abc.simulate") if days else 0.0
    if not ns:
        return None
    return ns / days
