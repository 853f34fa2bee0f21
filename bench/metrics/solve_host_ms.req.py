"""Mean host time of a solve flush in the window, in ms: ``stage.solve_flush``
less its ``solve.wait`` (the wait for the device's results), which leaves
padding, dtype conversion and enqueueing the copies and the program."""

from bench.trace import total_times


def read(ctx):
    t0, t1 = ctx["window"]
    _, flushes = total_times(ctx["spans"], t0, t1, "stage.solve_flush")
    wait: dict = {}
    for s in ctx["spans"]:
        if s["name"] == "solve.wait":
            wait[s["parent_id"]] = wait.get(s["parent_id"], 0.0) + s["dur"]
    host = [f["dur"] - wait[f["span_id"]] for f in flushes if f["span_id"] in wait]
    return 1e3 * sum(host) / len(host) if host else None
