"""95th percentile of the server-side queue wait, in ms: from the scheduler
accepting a request to the broker tick that drains it (``queue_wait_s`` of
``broker.tick``), over every request drained by a tick that starts in the
window."""

import numpy as np

from bench.trace import total_times


def read(ctx):
    t0, t1 = ctx["window"]
    _, ticks = total_times(ctx["spans"], t0, t1, "broker.tick")
    waits = [w for s in ticks for w in s["attrs"].get("queue_wait_s", ())]
    if not waits:
        return None
    return 1e3 * float(np.percentile(waits, 95, method="inverted_cdf"))
