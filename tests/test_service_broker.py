"""Offload broker service layer: coalescing ticks, broker↔serial parity,
cache persistence / warm restarts, elastic wiring, telemetry.

Everything here is deterministic (fake clocks, seeded traces) and runs
in tier-1 under the ``service`` marker.
"""

import dataclasses
import json

import numpy as np
import pytest

from repro.core import (
    AdaptiveController,
    AppProfile,
    Environment,
    EnvQuantizer,
    PlacementCache,
    ResponseTimeModel,
    face_recognition_graph,
    mcop_reference,
    profile_fingerprint,
    random_wcg,
)
from repro.core.placement_cache import SNAPSHOT_VERSION
from repro.service import (
    BrokerSession,
    OffloadBroker,
    run_workload,
    user_traces,
)
from repro.service import broker as broker_mod

pytestmark = pytest.mark.service


def _face_profile() -> AppProfile:
    return AppProfile.from_wcg_times(
        face_recognition_graph(speedup=1.0, bandwidth_mbps=1.0)
    )


def _profile(n: int, seed: int) -> AppProfile:
    return AppProfile.from_wcg_times(random_wcg(n, rng=np.random.default_rng(seed)))


def _broker(**kw) -> OffloadBroker:
    kw.setdefault("backend", "reference")
    kw.setdefault("clock", lambda: 0.0)
    return OffloadBroker(**kw)


# ----------------------------------------------------------------------
# Tick mechanics: coalescing and one dispatch per bucket
# ----------------------------------------------------------------------


def test_tick_issues_at_most_one_mcop_batch_call_per_bucket(monkeypatch):
    """R requests across K bins and two shape buckets → exactly one
    mcop_batch call per bucket, every future resolved correctly."""
    calls = []
    real = broker_mod.mcop_batch

    def counting(graphs, **kw):
        calls.append((len(graphs), kw.get("buckets")))
        return real(graphs, **kw)

    monkeypatch.setattr(broker_mod, "mcop_batch", counting)

    broker = _broker()
    small = _profile(8, seed=0)    # bucket 16
    large = _profile(40, seed=1)   # bucket 64
    broker.register("small", small, ResponseTimeModel())
    broker.register("large", large, ResponseTimeModel())

    futures = []
    envs = [Environment.symmetric(bw, 3.0) for bw in (8.0, 1.2, 0.3)]
    for env in envs:  # 3 distinct bins per tenant, 2 requests per bin
        for _ in range(2):
            futures.append(("small", env, broker.submit("small", env)))
            futures.append(("large", env, broker.submit("large", env)))

    report = broker.tick()
    assert report.requests == 12
    assert report.solved == 6          # one representative per (tenant, bin)
    assert report.coalesced == 6
    assert report.dispatches == 2      # one per bucket: 16 and 64
    assert report.buckets == (16, 64)
    assert len(calls) == 2
    assert sorted(n for n, _ in calls) == [3, 3]

    profs = {"small": small, "large": large}
    for name, env, fut in futures:
        assert fut.done
        g = ResponseTimeModel().build(profs[name], env)
        ref = mcop_reference(g)
        got = fut.result.result
        # same optimum (broker clamps, reference cut equals it here)
        assert got.min_cut == pytest.approx(
            min(ref.min_cut, g.total_cost(np.ones(g.n, bool))), rel=1e-9
        )


def test_layer_split_tenant_flushes_at_bucket_128(granite_layer_split):
    """A 90-vertex tenant (Granite-34B-Code split per layer) flushes in
    the 128 bucket: the tick report, the flush span and the dispatch
    timer's label all say so.  An 8-vertex tenant still flushes at 16."""
    from repro.obs import MetricsRegistry, Tracer

    assert granite_layer_split.n == 90
    tracer, metrics = Tracer(), MetricsRegistry()
    broker = _broker(backend="jax", tracer=tracer, metrics=metrics)
    broker.register("granite", granite_layer_split, ResponseTimeModel())
    broker.register("small", _profile(8, seed=0), ResponseTimeModel())

    buckets = []
    for name in ("granite", "small"):
        fut = broker.submit(name, Environment.symmetric(30.0, 4.0))
        report = broker.tick()
        assert fut.done and report.dispatches == 1
        buckets.append(report.buckets)
    assert buckets == [(128,), (16,)]
    flushes = tracer.spans("stage.solve_flush")
    assert [s.attrs["bucket"] for s in flushes] == [128, 16]
    for m in (128, 16):
        timer = metrics.get_histogram(
            "mcop_dispatch_duration_s", backend="jax", bucket=m, devices=1
        )
        assert timer is not None and timer.count == 1


def test_second_tick_serves_same_bins_from_cache(monkeypatch):
    calls = []
    real = broker_mod.mcop_batch
    monkeypatch.setattr(
        broker_mod,
        "mcop_batch",
        lambda graphs, **kw: calls.append(len(graphs)) or real(graphs, **kw),
    )
    broker = _broker()
    broker.register("app", _face_profile(), ResponseTimeModel())
    env = Environment.symmetric(5.0, 3.0)
    f1 = broker.submit("app", env)
    broker.tick()
    # same quantizer bin, slightly different measurement
    f2 = broker.submit("app", Environment.symmetric(5.05, 3.0))
    r = broker.tick()
    assert r.dispatches == 0 and r.cache_hits == 1 and len(calls) == 1
    assert f2.result.cache_hit and not f2.result.coalesced
    assert (f2.result.result.local_mask == f1.result.result.local_mask).all()


def test_failed_dispatch_requeues_unresolved_requests(monkeypatch):
    """A solve exception must not strand waiters: unresolved requests go
    back on the queue and the next tick retries (already-served cache
    hits stay resolved)."""
    broker = _broker()
    broker.register("app", _face_profile(), ResponseTimeModel())
    warm_env = Environment.symmetric(8.0, 3.0)
    broker.submit("app", warm_env)
    broker.tick()  # populate the cache for the warm bin

    real = broker_mod.mcop_batch
    boom = {"armed": True}

    def flaky(graphs, **kw):
        if boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("transient device error")
        return real(graphs, **kw)

    monkeypatch.setattr(broker_mod, "mcop_batch", flaky)
    hit = broker.submit("app", warm_env)              # resolvable from cache
    miss = broker.submit("app", Environment.symmetric(0.3, 3.0))
    with pytest.raises(RuntimeError, match="transient"):
        broker.tick()
    assert hit.done and not miss.done
    assert broker.pending == 1                        # only the miss requeued
    broker.tick()                                     # retry succeeds
    assert miss.done and broker.pending == 0
    # the retried request's counters are not double-counted: one miss for
    # each cold bin, one hit for the warm-bin re-request
    st = broker.tenant("app").cache.stats
    assert (st.hits, st.misses) == (1, 2)


def test_coalescing_respects_graph_size_within_a_bin():
    """A raw-graph tenant may mix graph sizes inside one env bin; a
    follower must never receive a wrong-length mask."""
    broker = _broker()
    broker.register("raw")
    env = Environment.symmetric(4.0, 3.0)
    g_small = random_wcg(6, rng=np.random.default_rng(0))
    g_large = random_wcg(13, rng=np.random.default_rng(1))
    f_small = broker.submit_graph("raw", g_small, env)
    f_large = broker.submit_graph("raw", g_large, env)
    report = broker.tick()
    assert report.solved == 2 and report.coalesced == 0
    assert f_small.result.result.local_mask.shape == (6,)
    assert f_large.result.result.local_mask.shape == (13,)


def test_observe_recovers_after_solver_failure():
    """A solver exception inside observe() must leave the controller able
    to retry, not permanently convinced it already repartitioned."""
    profile = _face_profile()
    ctl = AdaptiveController(
        profile, ResponseTimeModel(), threshold=0.15, min_interval=2,
        backend="definitely-not-a-backend",
    )
    env = Environment.symmetric(8.0, 3.0)
    with pytest.raises(ValueError):
        ctl.observe(env)
    ctl.backend = "reference"
    event = ctl.observe(env)
    assert event.repartitioned and ctl.placement is event.result


def test_broker_rejects_unknown_backend_eagerly():
    with pytest.raises(ValueError):
        OffloadBroker(backend="cuda")


def test_future_and_registration_error_paths():
    broker = _broker()
    broker.register("app", _face_profile(), ResponseTimeModel())
    with pytest.raises(ValueError):
        broker.register("app", _face_profile(), ResponseTimeModel())
    with pytest.raises(ValueError):
        broker.register("half", _face_profile())  # cost_model missing
    broker.register("raw")  # graph-only tenant
    with pytest.raises(ValueError):
        broker.submit("raw", Environment.symmetric(1.0, 2.0))
    fut = broker.submit("app", Environment.symmetric(1.0, 2.0))
    assert not fut.done
    with pytest.raises(RuntimeError):
        fut.result
    assert broker.pending == 1
    broker.tick()
    assert broker.pending == 0 and fut.done


def test_tick_latency_uses_injected_clock():
    t = [0.0]

    def clock():
        t[0] += 0.5
        return t[0]

    broker = OffloadBroker(backend="reference", clock=clock)
    broker.register("app", _face_profile(), ResponseTimeModel())
    broker.submit("app", Environment.symmetric(4.0, 3.0))
    report = broker.tick()
    assert report.latency_s == pytest.approx(0.5)
    assert broker.telemetry.mean_tick_latency_s == pytest.approx(0.5)


# ----------------------------------------------------------------------
# Broker ↔ serial parity (satellite: bit-identical placements and costs)
# ----------------------------------------------------------------------


def _serial_events(profile, traces, *, threshold, min_interval, n_users, steps):
    """Reference semantics: per-controller observe() loops over a shared
    cache, users visited in the same order the broker queue sees them."""
    cache = PlacementCache()
    ctls = [
        AdaptiveController(
            profile,
            ResponseTimeModel(),
            threshold=threshold,
            min_interval=min_interval,
            backend="reference",
            cache=cache,
        )
        for _ in range(n_users)
    ]
    for t in range(steps):
        for u, ctl in enumerate(ctls):
            ctl.observe(traces[u][t])
    return [ctl.history for ctl in ctls], cache


def _assert_event_parity(serial_events, broker_events):
    for ev_s, ev_b in zip(serial_events, broker_events):
        assert len(ev_s) == len(ev_b)
        for a, b in zip(ev_s, ev_b):
            assert a.step == b.step
            assert a.repartitioned == b.repartitioned
            assert a.cache_hit == b.cache_hit
            assert (a.result.local_mask == b.result.local_mask).all()
            assert b.partial_cost == pytest.approx(a.partial_cost, rel=1e-12)
            assert b.gain == pytest.approx(a.gain, rel=1e-9, abs=1e-12)


def test_broker_matches_serial_observe_loops():
    """N users through the broker ≡ N per-controller observe() loops."""
    profile = _face_profile()
    n_users, steps = 6, 10
    broker = _broker()
    broker.register("app", profile, ResponseTimeModel())
    report = run_workload(
        broker, "app", n_users=n_users, steps=steps,
        threshold=0.15, min_interval=2, seed=11,
    )
    serial, cache = _serial_events(
        profile, report.traces,
        threshold=0.15, min_interval=2, n_users=n_users, steps=steps,
    )
    _assert_event_parity(serial, report.events)
    tenant_cache = broker.tenant("app").cache
    assert (tenant_cache.stats.hits, tenant_cache.stats.misses) == (
        cache.stats.hits, cache.stats.misses,
    )
    # coalescing really happened (many users share few regime bins)
    assert broker.telemetry.solved < report.n_repartitions


def test_broker_parity_cooldown_and_drift_edge_cases():
    """Cooldown suppressing a due repartition, sub-threshold drift, and a
    drift landing exactly when the cooldown expires."""
    profile = _face_profile()
    base = [
        (8.0, 3.0),   # step 1: first observe always repartitions
        (1.0, 3.0),   # step 2: huge drift but min_interval=3 → suppressed
        (1.0, 3.0),   # step 3: still cooling down
        (1.0, 3.0),   # step 4: cooldown expired + drifted → repartition
        (1.02, 3.0),  # step 5: 2% drift < threshold → no repartition
        (8.0, 3.0),   # step 6: cooldown blocks again
        (8.0, 3.0),   # step 7: repartition, back to the cached wifi bin
    ]
    traces = [[Environment.symmetric(b, f) for b, f in base] for _ in range(3)]
    broker = _broker()
    broker.register("app", profile, ResponseTimeModel())
    report = run_workload(
        broker, "app", n_users=3, steps=len(base),
        threshold=0.15, min_interval=3, traces=traces,
    )
    serial, _ = _serial_events(
        profile, traces, threshold=0.15, min_interval=3,
        n_users=3, steps=len(base),
    )
    _assert_event_parity(serial, report.events)
    flags = [e.repartitioned for e in report.events[0]]
    assert flags == [True, False, False, True, False, False, True]
    # user 0 solves each bin once; users 1–2 ride entirely on coalescing
    assert all(e.cache_hit for evs in report.events[1:] for e in evs
               if e.repartitioned)


def test_sessions_can_queue_multiple_steps_before_a_tick():
    """drain() commits in observation order and stops at unresolved
    futures; a late tick releases the backlog with serial semantics."""
    profile = _face_profile()
    broker = _broker()
    broker.register("app", profile, ResponseTimeModel())
    session = BrokerSession(broker, "app", threshold=0.15, min_interval=1)
    envs = [Environment.symmetric(b, 3.0) for b in (8.0, 8.1, 1.0)]
    for env in envs:
        session.observe(env)
    assert session.drain() == [] and session.pending == 3
    broker.tick()
    events = session.drain()
    assert [e.repartitioned for e in events] == [True, False, True]
    # deferred commits carry the observation's own step, not the latest
    assert [e.step for e in events] == [1, 2, 3]
    assert session.pending == 0

    serial = AdaptiveController(
        profile, ResponseTimeModel(), threshold=0.15, min_interval=1,
        backend="reference", cache=PlacementCache(),
    )
    for env in envs:
        serial.observe(env)
    _assert_event_parity([serial.history], [events])


# ----------------------------------------------------------------------
# Cache persistence: snapshot → restart → warm start
# ----------------------------------------------------------------------


def test_snapshot_roundtrip_preserves_hit_behavior(tmp_path):
    cache = PlacementCache()
    envs = [Environment.symmetric(b, 3.0) for b in (8.0, 1.2, 0.3)]
    masks = [np.array([True, False, i % 2 == 0]) for i in range(3)]
    for env, mask in zip(envs, masks):
        cache.put(env, mask)
    path = tmp_path / "cache.json"
    cache.save(path, fingerprint="abc")

    warm = PlacementCache.from_snapshot(path, fingerprint="abc")
    assert len(warm) == 3
    for env, mask in zip(envs, masks):
        got = warm.get(env, expected_n=3)
        assert got is not None and (got == mask).all()
    assert warm.stats.hits == 3 and warm.stats.misses == 0


def test_snapshot_guards_fall_back_to_cold_cache(tmp_path):
    cache = PlacementCache()
    cache.put(Environment.symmetric(5.0, 3.0), np.array([True, False]))
    doc = cache.snapshot(fingerprint="fp-a")

    # fingerprint mismatch → ignored, no raise
    assert PlacementCache().load(doc, fingerprint="fp-b") == 0
    # unknown schema version → ignored
    assert PlacementCache().load({**doc, "version": SNAPSHOT_VERSION + 1}) == 0
    # quantizer step mismatch → bins not comparable → ignored
    other = PlacementCache(EnvQuantizer(rel_step=0.25))
    assert other.load(doc) == 0
    # corrupted file → cold cache, no raise
    bad = tmp_path / "corrupt.json"
    bad.write_text('{"version": 1, "entries": [truncated')
    assert PlacementCache().load(bad) == 0
    # missing file → cold cache
    assert PlacementCache().load(tmp_path / "nope.json") == 0
    # non-dict document → cold cache
    assert PlacementCache().load([1, 2, 3]) == 0
    # caller without a fingerprint requirement can still load
    assert PlacementCache().load(doc) == 1


def test_snapshot_load_skips_malformed_entries_and_evicts_to_capacity():
    """The version-1 reader, on a hand-built document of one
    ``{"key", "mask"}`` object per entry (what earlier releases wrote)."""
    q = EnvQuantizer()
    envs = [Environment.symmetric(bw, 3.0) for bw in (1.0, 2.0, 4.0, 8.0)]
    doc = {
        "version": 1,
        "fingerprint": "fp",
        "rel_step": q.rel_step,
        "entries": [
            {"key": list(q.key(env)), "mask": [1, int(i % 2 == 0)]}
            for i, env in enumerate(envs)
        ],
        "meta": {"journal_seq": 7, "tick": 3},
    }
    doc["entries"].insert(0, {"key": ["x"], "mask": [1]})      # bad key
    doc["entries"].insert(0, {"key": [1, 2], "mask": []})      # empty mask
    doc["entries"].insert(0, {"mask": [1]})                    # missing key

    small = PlacementCache(capacity=2)
    loaded, meta = small.load_with_meta(doc, fingerprint="fp")
    assert loaded == 4                   # good entries loaded (then evicted)
    assert meta == {"journal_seq": 7, "tick": 3}
    assert len(small) == 2               # evicted down to capacity...
    # ...keeping the newest entries (last written wins LRU)
    got = small.get(Environment.symmetric(8.0, 3.0), expected_n=2)
    assert got is not None and got.tolist() == [True, False]
    assert small.get(Environment.symmetric(1.0, 3.0)) is None
    assert small.stats.hits == 1 and small.stats.misses == 1
    # the guards hold for version 1 as for version 2
    assert PlacementCache().load(doc, fingerprint="other") == 0

    # wrong-length entries are skipped when the caller pins a profile size
    sized = PlacementCache()
    assert sized.load(doc, expected_n=3) == 0


def _random_cache(count, n, *, seed=0):
    rng = np.random.default_rng(seed)
    cache = PlacementCache(capacity=count)
    while len(cache) < count:
        key = tuple(int(x) for x in rng.integers(-2**31, 2**31, 6))
        cache.store(key, rng.random(n) < 0.5)
    return cache


def test_snapshot_v2_roundtrip_keeps_keys_masks_and_lru_order(tmp_path):
    cache = _random_cache(4096, 90)
    # touch a few old entries so the LRU order is not the insertion order
    for key in list(cache._entries)[:5]:
        assert cache.lookup(key) is not None
    path = tmp_path / "cache.json"
    nbytes = cache.save(path, fingerprint="fp", meta={"tick": 8})
    assert nbytes == path.stat().st_size
    doc = json.loads(path.read_text())
    assert doc["version"] == SNAPSHOT_VERSION == 2
    assert (doc["count"], doc["n"]) == (4096, 90)
    assert "entries" not in doc

    warm = PlacementCache(capacity=4096)
    assert warm.load_with_meta(path, fingerprint="fp") == (4096, {"tick": 8})
    assert list(warm._entries) == list(cache._entries)          # LRU order
    for key, mask in cache._entries.items():
        assert type(key[0]) is int
        np.testing.assert_array_equal(warm._entries[key], mask)
    # a smaller cache keeps the newest entries, as store() evicts
    small = PlacementCache(capacity=10)
    assert small.load(path) == 4096
    assert list(small._entries) == list(cache._entries)[-10:]
    # callers pinned to another graph size load nothing
    assert PlacementCache().load(path, expected_n=9) == 0


@pytest.mark.parametrize("field, value", [
    ("keys", "not*base64!"),                       # bad base64
    ("masks", "AAAA"),                             # masks too short
    ("count", 3),                                  # count disagrees with keys
    ("n", 17),                                     # n needs more mask bytes
    ("keys", None),                                # not a string
    ("count", "2"),                                # not an int
])
def test_snapshot_v2_with_bad_arrays_cold_starts(tmp_path, field, value):
    cache = _random_cache(2, 9)
    doc = cache.snapshot(fingerprint="fp", meta={"tick": 8})
    bad = {**doc, field: value}
    fresh = PlacementCache()
    assert fresh.load_with_meta(bad, fingerprint="fp") == (0, None)
    assert len(fresh) == 0
    path = tmp_path / "bad.json"
    path.write_text(json.dumps(bad))
    assert PlacementCache().load(path) == 0
    # the untouched document loads
    assert PlacementCache().load(doc, fingerprint="fp") == 2


def test_snapshot_with_mixed_mask_lengths_uses_the_v1_writer(tmp_path):
    cache = PlacementCache()
    masks = {(1, 2, 3, 4, 5, 6): np.array([True, False]),
             (1, 2, 3, 4, 5, 7): np.array([False, True, True]),
             (-(2**31), 0, 0, 0, 0, 0): np.array([True])}
    for key, mask in masks.items():
        cache.store(key, mask)
    path = tmp_path / "mixed.json"
    cache.save(path, fingerprint="fp")
    doc = json.loads(path.read_text())
    assert doc["version"] == 1 and len(doc["entries"]) == 3
    warm = PlacementCache()
    assert warm.load(path, fingerprint="fp") == 3
    assert list(warm._entries) == list(masks)
    for key, mask in masks.items():
        np.testing.assert_array_equal(warm._entries[key], mask)
    # a size-pinned caller takes only its own length
    assert PlacementCache().load(path, expected_n=3) == 1


def test_profile_fingerprint_distinguishes_profiles():
    a, b = _profile(8, seed=0), _profile(8, seed=1)
    assert profile_fingerprint(a) == profile_fingerprint(_profile(8, seed=0))
    assert profile_fingerprint(a) != profile_fingerprint(b)
    g = face_recognition_graph(speedup=1.0, bandwidth_mbps=1.0)
    assert profile_fingerprint(g) == profile_fingerprint(g)
    with pytest.raises(TypeError):
        profile_fingerprint(object())


def test_warm_started_broker_replays_trace_with_zero_dispatches(tmp_path):
    """Acceptance: serving restart + warm cache ⇒ no solver dispatches."""
    profile = _face_profile()
    broker = _broker()
    broker.register("app", profile, ResponseTimeModel())
    report = run_workload(broker, "app", n_users=4, steps=8, seed=5)
    assert broker.telemetry.dispatches > 0

    path = tmp_path / "app.json"
    broker.save_snapshot("app", path)

    warm = _broker()
    warm.register("app", profile, ResponseTimeModel(), warm_start=path)
    replay = run_workload(
        warm, "app", n_users=4, steps=8, traces=report.traces
    )
    assert warm.telemetry.dispatches == 0
    assert warm.telemetry.solved == 0
    assert all(e.cache_hit for evs in replay.events for e in evs
               if e.repartitioned)
    # placements/costs identical to the cold run (cache_hit flags differ
    # by design: the warm run never misses)
    for ev_cold, ev_warm in zip(report.events, replay.events):
        for a, b in zip(ev_cold, ev_warm):
            assert a.repartitioned == b.repartitioned
            assert (a.result.local_mask == b.result.local_mask).all()
            assert b.partial_cost == pytest.approx(a.partial_cost, rel=1e-12)

    # a different profile's snapshot must NOT warm this tenant
    cold = _broker()
    cold.register("app", _profile(profile.n, seed=99), ResponseTimeModel(),
                  warm_start=path)
    assert len(cold.tenant("app").cache) == 0

    # same profile but a different OBJECTIVE must not warm either: the
    # snapshot's masks minimize response time, not energy
    from repro.core import EnergyModel, WeightedModel

    cold2 = _broker()
    cold2.register("app", profile, EnergyModel(), warm_start=path)
    assert len(cold2.tenant("app").cache) == 0
    # parametric models fold their parameters into the guard
    assert WeightedModel(0.3).fingerprint != WeightedModel(0.7).fingerprint


# ----------------------------------------------------------------------
# Elastic events through the broker
# ----------------------------------------------------------------------


def test_elastic_submit_resize_matches_sync_resize(qwen_stages):
    from repro.core.placement import TPUV5E_TIER
    from repro.runtime import ElasticMeshManager

    tl = dataclasses.replace(TPUV5E_TIER, name="local", chips=128)
    tr = dataclasses.replace(TPUV5E_TIER, name="remote", chips=128)

    sync = ElasticMeshManager(list(qwen_stages), tl, tr)
    ev_sync = sync.resize(step=100, remote_chips=16, reason="failure")

    mgr = ElasticMeshManager(list(qwen_stages), tl, tr)
    broker = _broker()
    broker.register("fleet")   # raw-graph tenant
    pending = mgr.submit_resize(
        broker, "fleet", step=100, remote_chips=16, reason="failure"
    )
    assert not pending.done
    with pytest.raises(RuntimeError):
        pending.resolve()      # tick hasn't run yet
    broker.tick()
    ev = pending.resolve()
    assert (ev.plan.stage_tier == ev_sync.plan.stage_tier).all()
    assert ev.plan.mcop_cost == pytest.approx(ev_sync.plan.mcop_cost, rel=1e-9)
    assert ev.reason == "failure" and mgr.plan is ev.plan
    assert len(mgr.events) == 1

    # a flapping fleet revisits the same (bw, F) bin → served from cache
    p2 = mgr.submit_resize(broker, "fleet", step=200, remote_chips=16,
                           reason="flap")
    r = broker.tick()
    assert r.dispatches == 0 and r.cache_hits == 1
    assert (p2.resolve().plan.stage_tier == ev.plan.stage_tier).all()

    with pytest.raises(RuntimeError):
        mgr.submit_resize(broker, "fleet", step=300, remote_chips=0)
    # a rejected resize must not corrupt the tier state
    assert mgr.tier_remote.chips == 16

    # equal F but a bigger fleet is a DIFFERENT bin: compute times scale
    # with absolute FLOPs while transfer times don't, so the cached mask
    # must not be reused
    p3 = mgr.submit_resize(broker, "fleet", step=400,
                           local_chips=256, remote_chips=32, reason="grow")
    assert mgr.speedup == pytest.approx(16 / 128)  # same F as step 100
    r = broker.tick()
    assert r.cache_hits == 0 and r.solved == 1
    p3.resolve()


def test_overlapping_pending_resizes_resolve_safely(qwen_stages):
    """Out-of-order resolves must record the tiers each plan was solved
    on and never roll manager.plan back to a stale plan."""
    from repro.core.placement import TPUV5E_TIER
    from repro.runtime import ElasticMeshManager

    stages = qwen_stages
    tl = dataclasses.replace(TPUV5E_TIER, name="local", chips=128)
    tr = dataclasses.replace(TPUV5E_TIER, name="remote", chips=128)
    mgr = ElasticMeshManager(stages, tl, tr)
    broker = _broker()
    broker.register("fleet")
    p_old = mgr.submit_resize(broker, "fleet", step=1, remote_chips=16,
                              reason="brownout")
    p_new = mgr.submit_resize(broker, "fleet", step=2, remote_chips=512,
                              reason="scale_up")
    broker.tick()
    ev_new = p_new.resolve()
    ev_old = p_old.resolve()   # resolved late, after a newer plan landed
    assert ev_old.tier_remote.chips == 16      # tiers captured at submit
    assert ev_new.tier_remote.chips == 512
    assert mgr.plan is ev_new.plan             # stale plan did not clobber
    # each pending solved its own fleet state
    sync16 = ElasticMeshManager(stages, tl, tr).resize(step=1, remote_chips=16)
    assert (ev_old.plan.stage_tier == sync16.plan.stage_tier).all()


# ----------------------------------------------------------------------
# Telemetry
# ----------------------------------------------------------------------


def test_telemetry_aggregates_and_summary():
    broker = _broker()
    broker.register("app", _face_profile(), ResponseTimeModel())
    report = run_workload(broker, "app", n_users=5, steps=6, seed=2)
    tel = broker.telemetry
    assert tel.ticks == 6
    assert tel.requests == report.n_repartitions
    assert tel.cache_hits + tel.coalesced + tel.solved == tel.requests
    assert 0.0 <= tel.coalesce_ratio <= 1.0
    assert tel.max_queue_depth <= 5
    assert len(tel.reports) == 6
    s = tel.summary()
    assert s["requests"] == tel.requests
    assert s["dispatches"] == tel.dispatches
    # per-event hits = direct cache hits + same-tick coalesced followers
    assert report.n_cache_hits == tel.cache_hits + tel.coalesced
