"""Share of its roofline that the solver fleet's sharded programs reach, in %.

The least time one chip could take for the solves of the flushes that
started inside the traced span, the larger of their operations over peak
FLOP/s and their compulsory bytes over peak HBM bandwidth
(``bench.trace.solve_ops_bytes`` from the graph's true vertex count and
each flush's true batch: inert padding rows are no work), over the device
time of the sharded programs summed over the chips' planes: work over the
chip-seconds spent, as ``solve_roofline`` reads one chip. A program whose
sharded modules have other names reports nothing.
"""

from bench.trace import peaks, solve_ops_bytes, total_times

# as a TPU v5e trace names the modules: "jit__mcop_fleet_solve(16736540198262285577)"
PROGRAMS = ("jit__mcop_fleet_solve", "jit__mcop_fleet_fused")


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
