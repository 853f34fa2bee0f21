"""Share of the window spent in the session engine's solve flushes."""

from bench.trace import total_times


def read(ctx):
    t0, t1 = ctx["window"]
    return total_times(ctx["spans"], t0, t1, "stage.solve_flush")[0] / ctx["window_s"]
