"""Share of the window the broker tick spent on the host: self time of
``broker.tick`` and its materialize, cache probe, pricing and commit stages."""

from bench.trace import self_times

STAGES = ("broker.tick", "stage.materialize", "stage.cache_probe", "stage.pricing", "stage.commit")


def read(ctx):
    t0, t1 = ctx["window"]
    own = self_times(ctx["spans"], t0, t1)
    return sum(own.get(s, 0.0) for s in STAGES) / ctx["window_s"]
