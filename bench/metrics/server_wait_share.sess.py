"""Share of the window the server's reactor waited with nothing ready:
self time of ``server.wait`` (the ``select`` call).  At a fixed offered load,
more wait is more headroom."""

from bench.trace import self_times


def read(ctx):
    t0, t1 = ctx["window"]
    own = self_times(ctx["spans"], t0, t1)
    return own["server.wait"] / ctx["window_s"] if "server.wait" in own else None
