"""Mean time to write one tenant's placement-cache snapshot in the window, in
ms: the ``snapshot.save`` spans (encode and atomic file write) of the
server's snapshot passes. A program without the span reports nothing."""

from bench.trace import total_times


def read(ctx):
    t0, t1 = ctx["window"]
    total, saves = total_times(ctx["spans"], t0, t1, "snapshot.save")
    return 1e3 * total / len(saves) if saves else None
