"""Share of the tokens the front door sent to its pools in the window
that its Python slow path answered, in %: Δ``frontdoor.slow.raw_entries``
over the sum of Δ``frontdoor.pool.<id>.tokens`` (each pool's native
relay and slow path together). None where the router has no such
counters."""


def read(ctx):
    c = ctx["counters"]
    sent = sum(v for k, v in c.items()
               if k.startswith("frontdoor.pool.") and k.endswith(".tokens"))
    if "frontdoor.slow.raw_entries" not in c or sent <= 0:
        return None
    return 100.0 * c["frontdoor.slow.raw_entries"] / sent
