"""Mean ``stage.solve_flush`` time per flush in the window, in ms (it ends
in the device result's transfer to the host, so it holds the device time)."""

from bench.trace import total_times


def read(ctx):
    t0, t1 = ctx["window"]
    total, flushes = total_times(ctx["spans"], t0, t1, "stage.solve_flush")
    return 1e3 * total / len(flushes) if flushes else None
