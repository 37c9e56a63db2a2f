"""Share of the window's semantic-cache lookups that hit, in %, from the
cache's own hit and miss counters (``proxy.stats()["cache"]``)."""


def read(ctx):
    h = ctx.after["cache_hits"] - ctx.before["cache_hits"]
    m = ctx.after["cache_misses"] - ctx.before["cache_misses"]
    return float(100.0 * h / (h + m)) if h + m else None
