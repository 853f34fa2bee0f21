"""Share of the solver fleet's rows that are inert padding: sum of ``pad``
over sum of ``k + pad`` of the ``solve.shard_pack`` spans that start in the
window. A program without the span reports nothing."""

from bench.trace import total_times


def read(ctx):
    t0, t1 = ctx["window"]
    _, packs = total_times(ctx["spans"], t0, t1, "solve.shard_pack")
    rows = sum(s["attrs"]["k"] + s["attrs"]["pad"] for s in packs)
    return sum(s["attrs"]["pad"] for s in packs) / rows if rows else None
