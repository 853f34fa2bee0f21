"""1 - (union of the device's op intervals) / traced window, averaged over
the device planes."""

from bench.trace import busy_seconds


def read(ctx):
    dev = ctx["device"]
    if dev is None or not dev["ops"] or not ctx.get("trace_window_s"):
        return None
    busy = busy_seconds(dev["ops"]) / dev["planes"]
    return 1.0 - busy / ctx["trace_window_s"]
