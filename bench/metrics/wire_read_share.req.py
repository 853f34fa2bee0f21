"""Share of the window the server spent reading frames: self time of
``wire.read`` (receive, buffer handling and frame decode, before
``wire.frame`` opens)."""

from bench.trace import self_times


def read(ctx):
    t0, t1 = ctx["window"]
    own = self_times(ctx["spans"], t0, t1)
    return own["wire.read"] / ctx["window_s"] if "wire.read" in own else None
