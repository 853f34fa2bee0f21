"""Fault-tolerant broker: deterministic chaos, retries, degradation.

The PR-7 acceptance suite.  The injector is a pure function of
(seed, site, tick, index), so every chaos scenario here replays
bit-identically; clocks are injected (no real sleeps).  The headline
contracts:

* rate-0 / disabled injection ⇒ the resilient broker's replies, reports
  and telemetry are bit-identical (``==``, no tolerances) to today's
  broker, across the Fig.-2 topologies × three cost models;
* at a 10% fault rate every submitted future still resolves — solved,
  degraded, timed-out or rejected, never an exception out of ``tick()``
  — and cache counters record each served request exactly once;
* a failing (bin, bucket) flush quarantines only its own requests;
* batched sessions served fallbacks converge to the optimal placement
  once the fault storm ends.
"""

import dataclasses

import numpy as np
import pytest

from repro.core import (
    AppProfile,
    EnergyModel,
    Environment,
    NonFiniteWeightError,
    PlacementCache,
    ResponseTimeModel,
    SessionBatch,
    WeightedModel,
    linear_graph,
    loop_graph,
    mesh_graph,
    random_wcg,
    tick_sessions,
    tree_graph,
)
from repro.core.cost_models import EnvArrays, validate_env_finite
from repro.core.graph import WCGBatch
from repro.service import (
    CircuitBreaker,
    FaultInjector,
    InjectedClock,
    OffloadBroker,
    ResiliencePolicy,
    RetryPolicy,
    ScriptedFaultInjector,
    run_workload,
    user_traces,
)

from tests._hyp import given, settings, st

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


def _broker(**kw) -> OffloadBroker:
    kw.setdefault("backend", "reference")
    kw.setdefault("clock", InjectedClock())
    return OffloadBroker(**kw)


def _profile(n: int, seed: int) -> AppProfile:
    return AppProfile.from_wcg_times(random_wcg(n, rng=np.random.default_rng(seed)))


def _env(bw: float = 2.0, speedup: float = 4.0) -> Environment:
    return Environment.symmetric(bw, speedup)


def _policy(**kw) -> ResiliencePolicy:
    kw.setdefault("retry", RetryPolicy(max_retries=2))
    return ResiliencePolicy(**kw)


def _reply_tuple(reply):
    """Hashable bit-exact projection of a BrokerReply for == comparison."""
    r = reply.result
    return (
        None if r is None else (r.min_cut, r.local_mask.tobytes()),
        reply.cache_hit,
        reply.coalesced,
        reply.tick,
        reply.rejected,
        reply.degraded,
        reply.timed_out,
    )


# ----------------------------------------------------------------------
# Injector: determinism, frequency, validation
# ----------------------------------------------------------------------


def test_injector_is_deterministic_across_instances():
    a = FaultInjector(seed=7, rate=0.3)
    b = FaultInjector(seed=7, rate=0.3)
    grid = [
        (site, tick, index)
        for site in ("solve", "pricing", "cache_load", "cache_store")
        for tick in range(20)
        for index in range(5)
    ]
    assert [a.decide(*c) for c in grid] == [b.decide(*c) for c in grid]
    # a different seed produces a different schedule somewhere
    c = FaultInjector(seed=8, rate=0.3)
    assert [a.decide(*x).fires for x in grid] != [
        c.decide(*x).fires for x in grid
    ]


def test_injector_fire_frequency_tracks_rate():
    inj = FaultInjector(seed=0, rate=0.10)
    fired = sum(
        inj.decide("solve", t, i).fires for t in range(200) for i in range(10)
    )
    assert 120 < fired < 280  # 2000 draws @ 10%: generous deterministic band


def test_injector_rate_zero_and_disabled_never_fire():
    assert not FaultInjector(seed=1, rate=0.0).decide("solve", 3).fires
    inj = FaultInjector(seed=1, rate=1.0, enabled=False)
    assert not inj.decide("solve", 3).fires
    inj.enabled = True
    assert inj.decide("solve", 3).fires


def test_injector_per_site_rates_and_validation():
    inj = FaultInjector(seed=0, rate=0.0, rates={"solve": 1.0})
    assert inj.decide("solve", 1).fires
    assert not inj.decide("pricing", 1).fires
    with pytest.raises(ValueError):
        FaultInjector(rate=1.5)
    with pytest.raises(ValueError):
        FaultInjector(rates={"nope": 0.5})
    with pytest.raises(ValueError):
        FaultInjector(kinds=("error", "meteor"))
    with pytest.raises(ValueError):
        inj.decide("nope", 0)


def test_latency_faults_carry_deterministic_delay():
    inj = FaultInjector(seed=3, rate=1.0, kinds=("latency",), latency_s=0.01)
    d = inj.decide("solve", 5, 2)
    assert d.fires and d.kind == "latency"
    assert 0.005 <= d.delay_s <= 0.015
    assert d.delay_s == inj.decide("solve", 5, 2).delay_s


# -- property suite (hypothesis; opt-in via -m property) -----------------


@given(
    seed=st.integers(min_value=0, max_value=2**31),
    tick=st.integers(min_value=0, max_value=10**6),
    index=st.integers(min_value=0, max_value=10**4),
    rate=st.floats(min_value=0.01, max_value=1.0),
)
@settings(max_examples=200, deadline=None)
def test_property_injector_determinism(seed, tick, index, rate):
    """Two injectors with equal seeds agree on every coordinate — the
    schedule is a pure function, not process or call-order state."""
    a = FaultInjector(seed=seed, rate=rate)
    b = FaultInjector(seed=seed, rate=rate)
    for site in ("solve", "pricing", "cache_load", "cache_store"):
        assert a.decide(site, tick, index) == b.decide(site, tick, index)


@given(
    seed=st.integers(min_value=0, max_value=2**31),
    tick=st.integers(min_value=0, max_value=10**6),
    index=st.integers(min_value=0, max_value=10**4),
)
@settings(max_examples=200, deadline=None)
def test_property_sites_draw_independent_streams(seed, tick, index):
    """The underlying uniforms decorrelate across sites: a fault at one
    site never forces (or forbids) one at another coordinate."""
    inj = FaultInjector(seed=seed, rate=0.5)
    us = [
        inj._u(site, tick, index, "fire")
        for site in ("solve", "pricing", "cache_load", "cache_store")
    ]
    assert len(set(us)) == len(us)
    assert inj._u("solve", tick, index, "fire") != inj._u(
        "solve", tick + 1, index, "fire"
    )


# ----------------------------------------------------------------------
# Policies: retry backoff, circuit breaker
# ----------------------------------------------------------------------


def test_retry_policy_backoff_schedule():
    p = RetryPolicy(max_retries=3, base_backoff_s=0.001, multiplier=2.0,
                    max_backoff_s=0.003)
    assert p.attempts == 4
    assert [p.backoff(a) for a in range(4)] == [0.001, 0.002, 0.003, 0.003]
    with pytest.raises(ValueError):
        RetryPolicy(max_retries=-1)
    with pytest.raises(ValueError):
        RetryPolicy(multiplier=0.5)


def test_circuit_breaker_escalates_and_cools_down():
    br = CircuitBreaker(threshold=2, cooldown_ticks=3)
    assert br.backend("pallas", tick=1) == "pallas"
    br.record_failure("pallas", tick=1)
    assert not br.is_open("pallas", 1)
    assert br.record_failure("pallas", tick=1)  # second failure trips
    assert br.trips == 1
    assert br.is_open("pallas", 2)
    assert br.backend("pallas", tick=2) == "jax"
    # open jax too: escalate to the terminal reference backend
    br.record_failure("jax", tick=2)
    br.record_failure("jax", tick=2)
    assert br.backend("pallas", tick=3) == "reference"
    # reference is returned even if it somehow opens — nothing below it
    br.record_failure("reference", tick=3)
    br.record_failure("reference", tick=3)
    assert br.backend("pallas", tick=3) == "reference"
    # cooldown expiry re-admits pallas (opened at tick 1 for 3 ticks)
    assert br.backend("pallas", tick=5) == "pallas"
    # unknown backends pass through untouched
    assert br.backend("custom", tick=2) == "custom"
    with pytest.raises(ValueError):
        CircuitBreaker(threshold=0)


def test_resilience_policy_validation():
    with pytest.raises(ValueError):
        ResiliencePolicy(degrade="panic")
    with pytest.raises(ValueError):
        ResiliencePolicy(deadline_ticks=0)


# ----------------------------------------------------------------------
# Finite-weight validation (satellite b)
# ----------------------------------------------------------------------


def test_wcgbatch_pack_rejects_nonfinite_naming_row():
    g = linear_graph(5, rng=np.random.default_rng(0))
    batch = WCGBatch.from_wcgs([g, g, g], m=5)
    w = np.array(batch.w_local, copy=True)
    w[1, 2] = np.nan
    offloadable = ~batch.pinned
    with pytest.raises(NonFiniteWeightError, match=r"row\(s\) 1"):
        WCGBatch.pack(w, batch.w_cloud, batch.adj, offloadable, m=5)
    try:
        WCGBatch.pack(w, batch.w_cloud, batch.adj, offloadable, m=5)
    except NonFiniteWeightError as e:
        assert e.rows == (1,)


def test_env_validation_rejects_nonfinite_naming_row():
    envs = EnvArrays.from_envs([_env(), _env(), _env()])
    bad = envs._replace(
        bandwidth_up=np.array([2.0, np.inf, 2.0], dtype=np.float64)
    )
    with pytest.raises(NonFiniteWeightError, match="row 1"):
        validate_env_finite(bad)
    validate_env_finite(envs)  # clean input passes


def test_legacy_broker_raises_on_nonfinite_env_and_requeues():
    broker = _broker()
    broker.register("app", _profile(6, 0), ResponseTimeModel())
    fut = broker.submit("app", Environment.symmetric(float("nan"), 4.0))
    with pytest.raises(NonFiniteWeightError):
        broker.tick()
    assert not fut.done and broker.pending == 1  # re-queued, not stranded


def test_resilient_broker_rejects_nonfinite_env_inline():
    broker = _broker(resilience=_policy())
    broker.register("app", _profile(6, 0), ResponseTimeModel())
    bad = broker.submit("app", Environment.symmetric(float("nan"), 4.0))
    good = broker.submit("app", _env())
    report = broker.tick()
    assert bad.done and bad.result.rejected
    assert good.done and good.result.result is not None
    assert report.rejected == 1


# ----------------------------------------------------------------------
# Retry / degradation through the broker tick
# ----------------------------------------------------------------------


def test_retry_recovers_transient_solve_fault_bit_identically():
    """One injected transient error: the retry solves clean inputs and
    the reply equals the fault-free broker's reply bitwise."""
    clean = _broker()
    clean.register("app", _profile(8, 1), ResponseTimeModel())
    want = clean.submit("app", _env())
    clean.tick()

    for kind in ("error", "corrupt"):
        broker = _broker(
            resilience=_policy(),
            fault_injector=ScriptedFaultInjector({("solve", 1, 0): kind}),
        )
        broker.register("app", _profile(8, 1), ResponseTimeModel())
        fut = broker.submit("app", _env())
        report = broker.tick()
        assert _reply_tuple(fut.result) == _reply_tuple(want.result)
        assert report.retries == 1 and report.faults == 1
        assert report.degraded == 0


def test_exhausted_retries_degrade_to_no_offload_plan():
    faults = ScriptedFaultInjector(
        {("solve", 1, i): "error" for i in range(3)}  # all 3 attempts
    )
    broker = _broker(resilience=_policy(), fault_injector=faults)
    broker.register("app", _profile(8, 1), ResponseTimeModel())
    fut = broker.submit("app", _env())
    report = broker.tick()
    reply = fut.result
    assert reply.degraded and not reply.rejected
    # cold cache: the fallback is the §4.3 no-offload plan — always valid
    assert reply.result.local_mask.all()
    assert report.degraded == 1 and report.retries == 2
    assert report.solved == 0 and report.dispatches == 0
    assert broker.telemetry.degraded_replies == 1
    # the tick never raised and nothing is stranded
    assert broker.pending == 0


def test_degraded_reply_serves_stale_cached_mask():
    faults = ScriptedFaultInjector(
        dict(
            [(("cache_load", 2, 0), "error")]  # force the miss...
            + [(("solve", 2, i), "error") for i in range(3)]  # ...then fail
        )
    )
    broker = _broker(resilience=_policy(), fault_injector=faults)
    broker.register("app", _profile(8, 1), ResponseTimeModel())
    first = broker.submit("app", _env())
    broker.tick()  # tick 1: clean solve warms the cache
    stale = first.result.result.local_mask
    fut = broker.submit("app", _env())
    broker.tick()  # tick 2: load lost, flush exhausted → stale fallback
    reply = fut.result
    assert reply.degraded
    assert np.array_equal(reply.result.local_mask, stale)


def test_quarantine_requeue_mode_retries_next_tick():
    faults = ScriptedFaultInjector(
        {("solve", 1, i): "error" for i in range(3)}
    )
    broker = _broker(
        resilience=_policy(degrade="requeue"), fault_injector=faults
    )
    broker.register("app", _profile(8, 1), ResponseTimeModel())
    fut = broker.submit("app", _env())
    broker.tick()
    assert not fut.done and broker.pending == 1  # back in the queue
    broker.tick()  # tick 2 has no scheduled faults
    assert fut.done and not fut.result.degraded
    assert fut.result.result is not None


def test_failing_bucket_quarantines_only_its_own_requests():
    """Two tenants in different shape buckets; the small bucket's flush
    exhausts its retries while the big bucket commits normally — and the
    surviving reply is bit-identical to a fault-free run."""
    clean = _broker(buckets=(8, 16))
    clean.register("small", _profile(6, 2), ResponseTimeModel())
    clean.register("big", _profile(12, 3), ResponseTimeModel())
    clean_small = clean.submit("small", _env())
    clean_big = clean.submit("big", _env())
    clean.tick()

    # buckets dispatch in size order: bucket 8 burns solve indices 0..2,
    # bucket 16 dispatches clean at index 3
    faults = ScriptedFaultInjector(
        {("solve", 1, i): "error" for i in range(3)}
    )
    broker = _broker(
        buckets=(8, 16), resilience=_policy(), fault_injector=faults
    )
    broker.register("small", _profile(6, 2), ResponseTimeModel())
    broker.register("big", _profile(12, 3), ResponseTimeModel())
    small = broker.submit("small", _env())
    big = broker.submit("big", _env())
    report = broker.tick()
    assert small.result.degraded and small.result.result.local_mask.all()
    assert _reply_tuple(big.result) == _reply_tuple(clean_big.result)
    assert report.degraded == 1 and report.solved == 1
    assert report.buckets == (16,)
    # the healthy bucket's commit was not rolled back: its bin now hits
    rehit = broker.submit("big", _env())
    broker.tick()
    assert rehit.result.cache_hit


def test_breaker_escalates_failing_backend_mid_tick(monkeypatch):
    """A genuinely failing backend trips the breaker mid-retry and the
    next attempt runs on the escalated backend."""
    breaker = CircuitBreaker(threshold=2, cooldown_ticks=4)
    broker = _broker(
        backend="jax",  # escalation chain: jax → reference
        resilience=_policy(breaker=breaker),
    )
    broker.register("app", _profile(8, 1), ResponseTimeModel())

    backends_used = []
    from repro.service import broker as broker_mod

    real = broker_mod.mcop_batch

    def flaky(batch, *, backend, buckets, **kw):
        backends_used.append(backend)
        if backend == "jax":
            raise RuntimeError("device lost")
        return real(batch, backend=backend, buckets=buckets, **kw)

    monkeypatch.setattr(broker_mod, "mcop_batch", flaky)
    fut = broker.submit("app", _env())
    report = broker.tick()
    # attempts 0 and 1 fail on jax (the 2nd trips the breaker), attempt
    # 2 runs on the escalated terminal reference backend and succeeds
    assert backends_used == ["jax", "jax", "reference"]
    assert report.breaker_trips == 1 and breaker.trips == 1
    assert report.retries == 2
    assert fut.result.result is not None and not fut.result.degraded


def test_breaker_reaches_reference_when_the_lockstep_kernel_faults(monkeypatch):
    """Where the default path runs the lockstep kernel (a TPU, float32,
    bucket ≥ 64: forced here), "pallas" and "jax" both run it, so a
    kernel fault opens both circuits in turn and the flush is served by
    the numpy reference, exactly, not degraded."""
    import sys

    from repro.kernels import mcop_phase

    mcop_mod = sys.modules["repro.core.mcop"]  # ``repro.core.mcop`` is also a function
    monkeypatch.setattr(mcop_mod, "_solver_for", lambda platform, m, dtype: "lockstep")

    def kernel_fault(*args, **kw):
        raise RuntimeError("Mosaic kernel fault")

    monkeypatch.setattr(mcop_phase, "lockstep_phases", kernel_fault)
    breaker = CircuitBreaker(threshold=2, cooldown_ticks=4)
    broker = _broker(
        backend="pallas",  # escalation chain: pallas → jax → reference
        resilience=_policy(retry=RetryPolicy(max_retries=4), breaker=breaker),
    )
    profile = _profile(70, 5)  # bucket 128
    broker.register("app", profile, ResponseTimeModel())

    backends_used = []
    from repro.service import broker as broker_mod

    real = broker_mod.mcop_batch

    def spy(batch, *, backend, buckets, **kw):
        backends_used.append(backend)
        return real(batch, backend=backend, buckets=buckets, **kw)

    monkeypatch.setattr(broker_mod, "mcop_batch", spy)
    fut = broker.submit("app", _env())
    report = broker.tick()
    assert report.buckets == (128,)
    assert backends_used == ["pallas", "pallas", "jax", "jax", "reference"]
    assert report.breaker_trips == 2 and report.retries == 4
    assert fut.result.result is not None and not fut.result.degraded
    clean = _broker()
    clean.register("app", profile, ResponseTimeModel())
    want = clean.submit("app", _env())
    clean.tick()
    assert _reply_tuple(fut.result) == _reply_tuple(want.result)


def test_latency_faults_charge_injected_clock_only():
    clock = InjectedClock()
    faults = ScriptedFaultInjector(
        {("solve", 1, 0): "latency"}, latency_s=0.5
    )
    broker = _broker(
        clock=clock, resilience=_policy(), fault_injector=faults
    )
    broker.register("app", _profile(8, 1), ResponseTimeModel())
    fut = broker.submit("app", _env())
    report = broker.tick()
    assert fut.result.result is not None and not fut.result.degraded
    assert report.faults == 1 and report.retries == 0
    assert report.latency_s >= 0.5  # the spike shows up in telemetry


# ----------------------------------------------------------------------
# Deadlines and shutdown drain
# ----------------------------------------------------------------------


def test_deadline_resolves_overdue_request_as_timed_out():
    broker = _broker(resilience=_policy(deadline_ticks=1))
    broker.register("app", _profile(6, 0), ResponseTimeModel())
    fut = broker.submit("app", _env())
    broker.tick(budget=0)  # still within deadline, stays queued
    assert not fut.done
    report = broker.tick(budget=0)
    assert fut.done and fut.result.timed_out and fut.result.result is None
    assert report.timed_out == 1
    assert broker.telemetry.timed_out_requests == 1
    assert broker.pending == 0


def test_per_request_deadline_overrides_policy_default():
    broker = _broker(resilience=_policy(deadline_ticks=50))
    broker.register("app", _profile(6, 0), ResponseTimeModel())
    fut = broker.submit("app", _env(), deadline=1)
    broker.tick(budget=0)
    broker.tick(budget=0)
    assert fut.done and fut.result.timed_out
    with pytest.raises(ValueError):
        broker.submit("app", _env(), deadline=0)


def test_drain_resolves_abandoned_futures_as_rejected():
    broker = _broker()
    broker.register("app", _profile(6, 0), ResponseTimeModel())
    futs = [broker.submit("app", _env(2.0 + i)) for i in range(3)]
    assert broker.drain() == 3
    assert all(f.done and f.result.rejected for f in futs)
    assert broker.pending == 0
    assert broker.telemetry.rejected_requests == 3
    assert broker.drain() == 0  # idempotent


# ----------------------------------------------------------------------
# Injection-disabled bit-identity (tentpole acceptance)
# ----------------------------------------------------------------------


@pytest.mark.parametrize("topology", sorted(FIG2_TOPOLOGIES))
@pytest.mark.parametrize("model_name", sorted(MODELS))
def test_rate_zero_resilient_broker_is_bit_identical(topology, model_name):
    """A fully-armed resilient broker with a rate-0 injector produces a
    bit-identical event stream and telemetry to today's broker."""
    profile = AppProfile.from_wcg_times(FIG2_TOPOLOGIES[topology]())
    traces = user_traces(n_users=4, steps=6, seed=11)

    def run(**kw):
        broker = _broker(**kw)
        broker.register("app", profile, MODELS[model_name]())
        report = run_workload(
            broker, "app", n_users=4, steps=6,
            threshold=0.15, min_interval=2, traces=traces,
        )
        return report, broker

    plain_report, plain = run()
    armed_report, armed = run(
        resilience=_policy(breaker=CircuitBreaker()),
        fault_injector=FaultInjector(seed=123, rate=0.0),
    )
    for a, b in zip(plain_report.events, armed_report.events):
        for ea, eb in zip(a, b):
            assert ea.partial_cost == eb.partial_cost
            assert ea.gain == eb.gain
            assert ea.cache_hit == eb.cache_hit
            assert ea.repartitioned == eb.repartitioned
            assert np.array_equal(
                ea.result.local_mask, eb.result.local_mask
            )
    assert plain.telemetry.summary() == armed.telemetry.summary()
    for ra, rb in zip(plain.telemetry.reports, armed.telemetry.reports):
        assert dataclasses.asdict(ra) == dataclasses.asdict(rb)


def test_disabled_injector_session_tick_is_bit_identical():
    profile = AppProfile.from_wcg_times(FIG2_TOPOLOGIES["linear"]())
    traces = user_traces(n_users=6, steps=5, seed=21)

    def run(**kw):
        broker = _broker(**kw)
        broker.register("app", profile, ResponseTimeModel())
        group = broker.register_batch("app", 6, threshold=0.15, min_interval=2)
        for t in range(5):
            envs = EnvArrays.from_envs([traces[u][t] for u in range(6)])
            group.observe(envs, arrived=np.arange(6) if t == 0 else None)
            broker.tick()
        return group.drain(), broker

    plain_reports, plain = run()
    armed_reports, armed = run(
        resilience=_policy(),
        fault_injector=FaultInjector(seed=5, rate=1.0, enabled=False),
    )
    for ra, rb in zip(plain_reports, armed_reports):
        assert np.array_equal(ra.placements, rb.placements)
        assert np.array_equal(ra.partial_cost, rb.partial_cost)
        assert np.array_equal(ra.min_cut, rb.min_cut)
        assert np.array_equal(ra.cache_hit, rb.cache_hit)
        assert (ra.hits, ra.solved, ra.coalesced) == (
            rb.hits, rb.solved, rb.coalesced,
        )
        assert rb.retries == 0 and rb.faults == 0
    assert plain.telemetry.summary() == armed.telemetry.summary()


# ----------------------------------------------------------------------
# Chaos: every future resolves under randomized faults
# ----------------------------------------------------------------------


@pytest.mark.parametrize("degrade", ["fallback", "requeue"])
def test_chaos_every_future_resolves(degrade):
    faults = FaultInjector(seed=42, rate=0.10)
    broker = _broker(
        resilience=_policy(
            degrade=degrade,
            deadline_ticks=6,
            breaker=CircuitBreaker(threshold=3, cooldown_ticks=4),
        ),
        fault_injector=faults,
    )
    profile = _profile(9, 7)
    broker.register("app", profile, ResponseTimeModel())
    traces = user_traces(n_users=5, steps=8, seed=9)
    futures = []
    for t in range(8):
        for u in range(5):
            futures.append(broker.submit("app", traces[u][t]))
        broker.tick()
    ticks = 0
    while broker.pending and ticks < 40:
        broker.tick()
        ticks += 1
    assert broker.pending == 0
    assert all(f.done for f in futures)
    served = 0
    for f in futures:
        r = f.result
        # exactly one terminal state, never an unresolved/exception path
        if r.rejected or r.timed_out:
            assert r.result is None
        else:
            assert r.result is not None
            assert r.result.local_mask.shape == (9,)
            served += 1
    # each served request recorded exactly one cache-stat event — faults
    # never double-count (re-queues retry uncounted work)
    stats = broker.tenant("app").cache.stats
    assert stats.hits + stats.misses == served
    assert broker.telemetry.faults > 0  # the storm actually happened


def test_chaos_session_groups_never_raise_and_converge():
    faults = FaultInjector(seed=13, rates={"solve": 0.5, "pricing": 0.2})
    broker = _broker(resilience=_policy(), fault_injector=faults)
    profile = AppProfile.from_wcg_times(FIG2_TOPOLOGIES["tree"]())
    broker.register("app", profile, ResponseTimeModel())
    group = broker.register_batch("app", 4, threshold=0.15, min_interval=1)
    traces = user_traces(n_users=4, steps=6, seed=3)
    for t in range(6):
        envs = EnvArrays.from_envs([traces[u][t] for u in range(4)])
        if group.pending:  # a contained pricing failure kept the stage
            broker.tick()
        group.observe(envs, arrived=np.arange(4) if t == 0 else None)
        broker.tick()  # must never raise
    # end the storm, then force a drift no session can ignore: every
    # slot repartitions through a clean flush and lands on the true
    # optimum for the new environment
    faults.enabled = False
    if group.pending:
        broker.tick()
    extreme = EnvArrays.from_envs([_env(50.0, 50.0)] * 4)
    group.observe(extreme)
    broker.tick()
    reports = group.drain()
    assert reports, "group never completed a tick"
    final = reports[-1]
    assert final.faults == 0 and final.degraded is None
    assert final.repartitioned.all()

    # reference: a clean fresh batch observing the same environment
    clean = _broker()
    clean.register("app", profile, ResponseTimeModel())
    cgroup = clean.register_batch("app", 4, threshold=0.15, min_interval=1)
    cgroup.observe(extreme, arrived=np.arange(4))
    clean.tick()
    cfinal = cgroup.drain()[-1]
    assert np.array_equal(final.placements, cfinal.placements)
    assert np.array_equal(final.min_cut, cfinal.min_cut)


def test_session_flush_quarantine_degrades_and_recovers():
    """Direct tick_sessions: an exhausted flush serves fallbacks, rolls
    the drift anchors back, and the next clean tick solves for real."""
    profile = AppProfile.from_wcg_times(FIG2_TOPOLOGIES["linear"]())
    model = ResponseTimeModel()
    cache = PlacementCache()
    batch = SessionBatch.create(3, profile.n, threshold=0.15)
    batch.activate(np.arange(3))
    envs = EnvArrays.from_envs([_env(2.0 + u) for u in range(3)])
    faults = ScriptedFaultInjector(
        {("solve", 1, i): "error" for i in range(3)}
    )
    policy = _policy()
    r1 = tick_sessions(
        batch, envs, profile=profile, model=model, cache=cache,
        backend="reference", faults=faults, resilience=policy, tick=1,
    )
    assert r1.degraded is not None and r1.degraded.all()
    assert r1.retries == 2 and r1.solved == 0
    assert r1.placements.all()  # cold cache → §4.3 all-local fallbacks
    assert cache.stats.misses == 3 and cache.stats.hits == 0

    # no faults scheduled at tick 2: anchors were rolled back, so every
    # session re-partitions and lands on the real optimum
    r2 = tick_sessions(
        batch, envs, profile=profile, model=model, cache=cache,
        backend="reference", faults=faults, resilience=policy, tick=2,
    )
    assert r2.degraded is None and r2.repartitioned.all()

    clean_batch = SessionBatch.create(3, profile.n, threshold=0.15)
    clean_batch.activate(np.arange(3))
    r_clean = tick_sessions(
        clean_batch, envs, profile=profile, model=model,
        cache=PlacementCache(), backend="reference",
    )
    assert np.array_equal(r2.placements, r_clean.placements)
    assert np.array_equal(r2.min_cut, r_clean.min_cut)
