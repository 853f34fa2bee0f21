"""Share of its roofline that the device's solve programs reach, in %.

The least time the chip could take for the window's solves, the larger of
their operations over peak FLOP/s and their compulsory bytes over peak
HBM bandwidth (``bench.trace.solve_ops_bytes`` from each flushed graph's
true vertex count and each flush's batch), over the device time of the
solve programs in the trace.  Only flushes that started inside the traced
sub-window are counted, against the programs the trace holds.
"""

from bench.trace import peaks, solve_ops_bytes, total_times

PROGRAMS = ("jit__mcop_batch_impl", "jit_fused", "mcop_stoer_wagner")


def read(ctx):
    dev = ctx["device"]
    if dev is None:
        return None
    t0, t1 = ctx["trace_span"]
    _, flushes = total_times(ctx["spans"], t0, t1, "stage.solve_flush")
    device_ns = sum(b - a for name, a, b in dev["modules"] if name.startswith(PROGRAMS))
    graphs = sum(int(s["attrs"].get("batch", 0)) for s in flushes)
    if not graphs or not device_ns:
        return None
    peak = peaks(ctx["device_kind"])
    ops, nbytes = solve_ops_bytes(ctx["profile_n"], ctx["profile_pinned"])
    least = max(graphs * ops / peak["flops_per_s"], graphs * nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / (device_ns * 1e-9)
