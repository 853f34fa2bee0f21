"""Array-native session engine: SessionBatch tick ≡ serial object path.

The PR-6 acceptance suite.  The strict parity tests compare one
vectorized ``tick_sessions``/``BatchSessionGroup`` tick against K
``BrokerSession`` observe loops on the *reference* backend with ``==``
(no tolerances): events, placements, prices, cut values and shared-cache
counters must all be bit-identical across the Fig.-2 topologies × three
cost models.  Around the tentpole: traffic determinism under a fixed
seed, the vectorized cache API (`get_many`/`put_many`), the
load-adaptive WFQ hook, device-resident pricing telemetry, and the
atomic-tick failure containment.
"""

import numpy as np
import pytest

from repro.core import (
    AppProfile,
    EnergyModel,
    EnvQuantizer,
    Environment,
    PlacementCache,
    ResponseTimeModel,
    SessionBatch,
    WeightedModel,
    linear_graph,
    loop_graph,
    mesh_graph,
    tick_sessions,
    tree_graph,
)
from repro.core.cost_models import EnvArrays
from repro.core import session_batch as session_batch_mod
from repro.service import (
    OffloadBroker,
    TrafficGenerator,
    WeightedFairScheduler,
    run_batch_workload,
    run_workload,
    user_traces,
)

pytestmark = pytest.mark.service

FIG2_TOPOLOGIES = {
    "linear": lambda: linear_graph(9, rng=np.random.default_rng(1)),
    "loop": lambda: loop_graph(8, rng=np.random.default_rng(2)),
    "tree": lambda: tree_graph(10, rng=np.random.default_rng(3)),
    "mesh": lambda: mesh_graph(3, 3, rng=np.random.default_rng(4)),
}

MODELS = {
    "time": ResponseTimeModel,
    "energy": EnergyModel,
    "weighted": lambda: WeightedModel(0.35),
}

EVENT_FIELDS = (
    "step",
    "repartitioned",
    "cache_hit",
    "partial_cost",
    "no_offload_cost",
    "full_offload_cost",
    "gain",
)


def _broker(**kw) -> OffloadBroker:
    kw.setdefault("backend", "reference")
    kw.setdefault("clock", lambda: 0.0)
    return OffloadBroker(**kw)


def _run_object_path(profile, model, traces, *, backend="reference"):
    broker = _broker(backend=backend)
    broker.register("app", profile, model)
    report = run_workload(
        broker,
        "app",
        n_users=len(traces),
        steps=len(traces[0]),
        threshold=0.15,
        min_interval=2,
        traces=traces,
    )
    return report, broker


def _run_batch_path(profile, model, traces, *, backend="reference"):
    k, steps = len(traces), len(traces[0])
    broker = _broker(backend=backend)
    broker.register("app", profile, model)
    group = broker.register_batch("app", k, threshold=0.15, min_interval=2)
    for t in range(steps):
        envs = EnvArrays.from_envs([traces[u][t] for u in range(k)])
        group.observe(envs, arrived=np.arange(k) if t == 0 else None)
        broker.tick()
    return group.drain(), broker


# ----------------------------------------------------------------------
# Tentpole parity: batched tick ≡ serial observe loops, bitwise
# ----------------------------------------------------------------------


@pytest.mark.parametrize("topology", sorted(FIG2_TOPOLOGIES))
@pytest.mark.parametrize("model_name", sorted(MODELS))
def test_batch_tick_matches_object_sessions(topology, model_name):
    """One vectorized tick per step produces events (steps, flags,
    masks, every price, every cut value) and shared-cache counters
    bit-identical to K per-object BrokerSessions observing the same
    traces — ``==``, no tolerances."""
    profile = AppProfile.from_wcg_times(FIG2_TOPOLOGIES[topology]())
    traces = user_traces(5, 7, seed=11)
    object_report, ob = _run_object_path(profile, MODELS[model_name](), traces)
    batch_reports, bb = _run_batch_path(profile, MODELS[model_name](), traces)

    assert len(batch_reports) == 7
    for t, rep in enumerate(batch_reports):
        for u in range(5):
            got, want = rep.event(u), object_report.events[u][t]
            for f in EVENT_FIELDS:
                assert getattr(got, f) == getattr(want, f), (t, u, f)
            assert got.result.min_cut == want.result.min_cut, (t, u)
            assert np.array_equal(got.result.local_mask, want.result.local_mask)
            assert got.env == want.env
    assert bb.tenant("app").cache.stats == ob.tenant("app").cache.stats


def test_batch_tick_matches_object_sessions_on_jax_backend():
    """Same parity on the f32 jax backend for the placements and every
    f64 host-priced number.  (The installed cut value of a solved
    session is the solver's f32 output, which the two paths compute from
    differently-rounded f32 weights — same caveat as ``solve_envs`` —
    so it alone is compared within f32 resolution.)"""
    profile = AppProfile.from_wcg_times(FIG2_TOPOLOGIES["linear"]())
    traces = user_traces(4, 6, seed=3)
    object_report, _ = _run_object_path(
        profile, ResponseTimeModel(), traces, backend="jax"
    )
    batch_reports, _ = _run_batch_path(
        profile, ResponseTimeModel(), traces, backend="jax"
    )
    for t, rep in enumerate(batch_reports):
        for u in range(4):
            got, want = rep.event(u), object_report.events[u][t]
            for f in EVENT_FIELDS:
                assert getattr(got, f) == getattr(want, f), (t, u, f)
            assert np.array_equal(got.result.local_mask, want.result.local_mask)
            assert got.result.min_cut == pytest.approx(
                want.result.min_cut, rel=1e-5
            )


def test_fresh_sessions_partition_on_first_observation():
    profile = AppProfile.from_wcg_times(FIG2_TOPOLOGIES["tree"]())
    batch = SessionBatch.create(4, profile.n, threshold=0.15, min_interval=2)
    batch.activate(np.arange(3))  # slot 3 stays idle
    cache = PlacementCache(EnvQuantizer())
    envs = EnvArrays.from_envs([Environment.symmetric(2.0, 3.0)] * 4)
    rep = tick_sessions(
        batch, envs, profile=profile, model=ResponseTimeModel(),
        cache=cache, backend="reference",
    )
    assert rep.repartitioned.tolist() == [True, True, True, False]
    assert rep.solved == 1 and rep.coalesced == 2  # one bin, one solve
    assert not rep.active[3] and batch.steps[3] == 0


# ----------------------------------------------------------------------
# Traffic: Poisson arrivals + geometric churn, deterministic under seed
# ----------------------------------------------------------------------


def test_traffic_generator_replays_bit_identically():
    a = TrafficGenerator(64, seed=9, arrival_rate=3.0, churn=0.1)
    b = TrafficGenerator(64, seed=9, arrival_rate=3.0, churn=0.1)
    for _ in range(10):
        ta, tb = a.step(), b.step()
        assert np.array_equal(ta.active, tb.active)
        assert np.array_equal(ta.arrived, tb.arrived)
        assert np.array_equal(ta.departed, tb.departed)
        for fa, fb in zip(ta.envs, tb.envs):
            assert np.array_equal(fa, fb)


def test_churning_batch_workload_is_deterministic_under_fixed_seed():
    profile = AppProfile.from_wcg_times(FIG2_TOPOLOGIES["loop"]())

    def drive():
        broker = _broker()
        broker.register("app", profile, ResponseTimeModel())
        group = broker.register_batch("app", 48, threshold=0.15, min_interval=2)
        reports = run_batch_workload(
            broker, group, steps=10, seed=5, churn=0.08, arrival_rate=2.0
        )
        return reports, broker.tenant("app").cache.stats

    r1, s1 = drive()
    r2, s2 = drive()
    assert s1 == s2
    assert [int(r.active.sum()) for r in r1] == [int(r.active.sum()) for r in r2]
    for a, b in zip(r1, r2):
        assert np.array_equal(a.placements, b.placements)
        assert np.array_equal(a.partial_cost, b.partial_cost)
        assert np.array_equal(a.min_cut, b.min_cut, equal_nan=True)
        assert np.array_equal(a.repartitioned, b.repartitioned)
    # churn actually happened: some sessions departed and slots turned over
    assert any(r.active.sum() != r1[0].active.sum() for r in r1)


def test_departed_sessions_are_not_observed_and_slots_recycle():
    profile = AppProfile.from_wcg_times(FIG2_TOPOLOGIES["mesh"]())
    batch = SessionBatch.create(2, profile.n, min_interval=1)
    cache = PlacementCache(EnvQuantizer())
    envs = EnvArrays.from_envs([Environment.symmetric(2.0, 3.0)] * 2)
    batch.activate([0, 1])
    tick_sessions(batch, envs, profile=profile, model=ResponseTimeModel(),
                  cache=cache, backend="reference")
    steps_before = batch.steps.copy()
    batch.deactivate([1])
    rep = tick_sessions(batch, envs, profile=profile, model=ResponseTimeModel(),
                        cache=cache, backend="reference")
    assert batch.steps[1] == steps_before[1]  # clock frozen while departed
    assert not rep.repartitioned[1]
    batch.activate([1])  # slot turns over: fresh session, due immediately
    rep2 = tick_sessions(batch, envs, profile=profile, model=ResponseTimeModel(),
                         cache=cache, backend="reference")
    assert rep2.repartitioned[1] and rep2.steps[1] == 1


# ----------------------------------------------------------------------
# Vectorized cache API: get_many/put_many ≡ scalar loop
# ----------------------------------------------------------------------


def test_get_many_put_many_match_scalar_loop_exactly():
    """Batch probe/insert must leave hit/miss counters, stored masks and
    LRU recency identical to the equivalent scalar get/put loop."""
    rng = np.random.default_rng(0)
    envs = [
        Environment.symmetric(float(b), float(s))
        for b, s in zip(
            np.geomspace(0.3, 9.0, 12), 1.5 + rng.random(12) * 3.0
        )
    ]
    masks = rng.random((12, 7)) < 0.5

    scalar = PlacementCache(EnvQuantizer(), capacity=8)
    batch = PlacementCache(EnvQuantizer(), capacity=8)
    for env, mask in zip(envs, masks):
        scalar.put(env, mask)
    batch.put_many(EnvArrays.from_envs(envs), masks)
    assert scalar.stats == batch.stats
    assert list(scalar._entries) == list(batch._entries)
    for key in scalar._entries:
        assert np.array_equal(scalar._entries[key], batch._entries[key])

    probe = envs[::2] + [Environment.symmetric(123.0, 9.0)]  # mix hit/miss
    scalar_out = [scalar.get(env, expected_n=7) for env in probe]
    batch_out = batch.get_many(EnvArrays.from_envs(probe), expected_n=7)
    assert scalar.stats == batch.stats
    assert len(scalar_out) == len(batch_out)
    for a, b in zip(scalar_out, batch_out):
        assert (a is None) == (b is None)
        if a is not None:
            assert np.array_equal(a, b)
    assert list(scalar._entries) == list(batch._entries)  # same LRU order


def test_keys_batch_matches_scalar_key():
    q = EnvQuantizer()
    envs = [
        Environment(2.0, 1.7, 3.0),
        Environment(0.31, 0.29, 1.5, p_compute=1.1, p_idle=0.2, p_transfer=1.9),
        Environment.symmetric(8.0, 3.0),
    ]
    cache = PlacementCache(q)
    batch_keys = cache.keys_batch(EnvArrays.from_envs(envs))
    assert batch_keys == [cache.key(e) for e in envs]


# ----------------------------------------------------------------------
# Load-adaptive WFQ weights
# ----------------------------------------------------------------------


def test_adaptive_weights_track_inverse_recent_latency():
    """weight = base × mean-EWMA / own-EWMA: a tenant whose ticks keep
    consuming the solver (high service latency) is damped, a light one
    boosted; static-weight tenants are untouched."""
    s = WeightedFairScheduler()
    s.ensure_tenant("heavy", weight=1.0)
    s.ensure_tenant("light", weight=1.0)
    s.ensure_tenant("static", weight=2.0)
    s.set_adaptive("heavy", alpha=0.5, floor=0.25, ceiling=4.0)
    s.set_adaptive("light", alpha=0.5, floor=0.25, ceiling=4.0)
    for _ in range(6):
        s.observe_latency("heavy", 0.9)
        s.observe_latency("light", 0.1)
    assert s.weight("heavy") < 1.0 < s.weight("light")
    assert s.weight("light") <= 4.0 and s.weight("heavy") >= 0.25
    assert s.weight("static") == 2.0


def test_adaptive_weight_values_and_clamps():
    s = WeightedFairScheduler()
    s.ensure_tenant("heavy", weight=1.0)
    s.ensure_tenant("light", weight=1.0)
    s.set_adaptive("heavy", alpha=1.0, floor=0.5, ceiling=2.0)
    s.set_adaptive("light", alpha=1.0, floor=0.5, ceiling=2.0)
    s.observe_latency("heavy", 1.0)
    s.observe_latency("light", 0.01)
    # mean = 0.505: heavy gets 0.505/1.0, light 0.505/0.01 clamped at 2×
    assert s.weight("heavy") == pytest.approx(0.505, rel=1e-9)
    assert s.weight("light") == 2.0  # clamped at base × ceiling


def test_broker_feeds_group_latency_into_adaptive_weights(monkeypatch):
    profile = AppProfile.from_wcg_times(FIG2_TOPOLOGIES["linear"]())
    broker = _broker()
    broker.register("a", profile, ResponseTimeModel(), adaptive_weight=True)
    broker.register("b", profile, ResponseTimeModel(), adaptive_weight=True)
    ga = broker.register_batch("a", 8, min_interval=1)
    gb = broker.register_batch("b", 8, min_interval=1)
    seen = []
    monkeypatch.setattr(
        broker._scheduler,
        "observe_latency",
        lambda name, seconds: seen.append((name, float(seconds))),
    )
    envs = EnvArrays.from_envs([Environment.symmetric(2.0, 3.0)] * 8)
    ga.observe(envs, arrived=np.arange(8))
    gb.observe(envs, arrived=np.arange(8))
    broker.tick()
    assert [name for name, _ in seen] == ["a", "b"]  # every group reported
    assert all(lat >= 0.0 for _, lat in seen)


# ----------------------------------------------------------------------
# Atomicity + pytree plumbing
# ----------------------------------------------------------------------


def test_failed_solve_restores_state_and_tick_retries_identically(monkeypatch):
    profile = AppProfile.from_wcg_times(FIG2_TOPOLOGIES["tree"]())
    model = ResponseTimeModel()
    envs = EnvArrays.from_envs(
        [Environment.symmetric(float(b), 3.0) for b in np.geomspace(0.5, 6.0, 5)]
    )

    def drive(fail_first):
        batch = SessionBatch.create(5, profile.n, min_interval=1)
        batch.activate(np.arange(5))
        cache = PlacementCache(EnvQuantizer())
        calls = {"n": 0}
        real = session_batch_mod.solve_envs

        def flaky(*a, **kw):
            calls["n"] += 1
            if fail_first and calls["n"] == 1:
                raise RuntimeError("transient device error")
            return real(*a, **kw)

        monkeypatch.setattr(session_batch_mod, "solve_envs", flaky)
        if fail_first:
            with pytest.raises(RuntimeError, match="transient"):
                tick_sessions(batch, envs, profile=profile, model=model,
                              cache=cache, backend="reference")
            # full rollback: no counters, no clocks, no anchors
            assert cache.stats.lookups == 0
            assert batch.steps.sum() == 0 and not batch.has_partition.any()
        rep = tick_sessions(batch, envs, profile=profile, model=model,
                            cache=cache, backend="reference")
        monkeypatch.setattr(session_batch_mod, "solve_envs", real)
        return rep, cache.stats

    clean, clean_stats = drive(fail_first=False)
    retried, retried_stats = drive(fail_first=True)
    assert clean_stats == retried_stats  # no double counting on retry
    assert np.array_equal(clean.placements, retried.placements)
    assert np.array_equal(clean.partial_cost, retried.partial_cost)
    assert np.array_equal(clean.steps, retried.steps)


def test_session_batch_is_a_registered_pytree():
    import jax

    batch = SessionBatch.create(6, 9, threshold=0.2, min_interval=3)
    batch.activate([0, 2])
    leaves, treedef = jax.tree_util.tree_flatten(batch)
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert rebuilt.n == 9
    assert rebuilt.threshold == 0.2 and rebuilt.min_interval == 3
    assert np.array_equal(rebuilt.active, batch.active)
    # identity tree_map round-trips every array leaf
    mapped = jax.tree_util.tree_map(lambda x: x, batch)
    assert np.array_equal(mapped.placements, batch.placements)


def test_tick_report_telemetry_counts_batched_sessions():
    profile = AppProfile.from_wcg_times(FIG2_TOPOLOGIES["linear"]())
    broker = _broker()
    broker.register("app", profile, ResponseTimeModel())
    group = broker.register_batch("app", 10, min_interval=1)
    group.observe(
        EnvArrays.from_envs([Environment.symmetric(2.0, 3.0)] * 10),
        arrived=np.arange(7),
    )
    report = broker.tick()
    assert report.batch_groups == 1
    assert report.batch_sessions == 7
    assert report.batch_solved == 1          # one shared bin
    assert report.batch_hits == 6            # the coalesced followers
    assert broker.telemetry.batch_sessions == 7
