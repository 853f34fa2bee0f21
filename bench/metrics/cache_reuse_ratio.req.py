"""(cache hits + coalesced followers) / requests over the window's tick reports."""


def read(ctx):
    requests = sum(t["requests"] for t in ctx["ticks"])
    if not requests:
        return None
    return sum(t["cache_hits"] + t["coalesced"] for t in ctx["ticks"]) / requests
