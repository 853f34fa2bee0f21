"""Mean host time to pack one sharded solve flush, in ms: the
``solve.shard_pack`` spans (inert padding, round-robin permutation and the
dispatch enqueue) that start in the window. A program without the span
reports nothing."""

from bench.trace import total_times


def read(ctx):
    t0, t1 = ctx["window"]
    total, packs = total_times(ctx["spans"], t0, t1, "solve.shard_pack")
    return 1e3 * total / len(packs) if packs else None
