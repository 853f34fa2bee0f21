"""Share of the window the server spent in its own wire frame handling
(``wire.frame`` self time: decode, journal append, encode, send), request cells."""

from bench.trace import self_times


def read(ctx):
    t0, t1 = ctx["window"]
    return self_times(ctx["spans"], t0, t1).get("wire.frame", 0.0) / ctx["window_s"]
