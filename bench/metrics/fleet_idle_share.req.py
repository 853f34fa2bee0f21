"""Mean idle share of the chips of a fleet over the traced span:
1 - (sum of the ``XLA Modules`` durations over all device planes / planes)
/ traced span, which is the mean of each chip's own idle share.

The sum stands in for one union per plane, which the device trace as read
here does not keep apart. It assumes that the programs of one plane do not
overlap: a chip runs one program at a time. The ``XLA Ops`` of one plane do
overlap (a ``while`` op spans the ops of its body), so their durations
cannot be summed."""


def read(ctx):
    dev = ctx["device"]
    if dev is None or not dev["modules"] or not dev["planes"] or not ctx.get("trace_window_s"):
        return None
    busy = sum(b - a for _, a, b in dev["modules"]) * 1e-9 / dev["planes"]
    return 1.0 - busy / ctx["trace_window_s"]
