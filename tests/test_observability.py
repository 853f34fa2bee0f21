"""Observability plane: metrics, tracing, and the two hard contracts.

The PR-8 acceptance suite.  The headline contracts:

* a broker with no tracer/registry attached is **bit-identical** to the
  pre-observability code — replies, workload events, telemetry — across
  the Fig.-2 topologies × three cost models, with and without a fault
  storm (the instrumented call sites receive shared null objects and
  never read a clock);
* with instruments attached, the ``BrokerTelemetry`` fields and their
  mirrored registry counters can never disagree (seeded on bind), and
  every ``degraded`` event in an exported trace is attributable to a
  same-tick ``fault`` event — the ``tools/tracequery.py --audit`` CI
  gate, exercised here end to end through a scripted fault schedule.

What the enabled path costs in time is for the chip benchmark
(``bench/``) to measure, not here — wall-clock ratios don't belong in
tier-1.
"""

import dataclasses
import importlib.util
import json
import pathlib
import threading

import numpy as np
import pytest

from repro.core import (
    AppProfile,
    Environment,
    PlacementCache,
    ResponseTimeModel,
)
from repro.core.cost_models import EnvArrays
from repro.obs import MetricsRegistry, Tracer
from repro.obs.metrics import (
    NULL_COUNTER,
    NULL_GAUGE,
    NULL_HISTOGRAM,
    Histogram,
)
from repro.obs.trace import NULL_SPAN, profiler_annotation
from repro.service import (
    BrokerClient,
    CircuitBreaker,
    FaultInjector,
    InjectedClock,
    ScriptedFaultInjector,
    SolverServer,
    run_workload,
    unix_address,
    user_traces,
)
from tests.test_faults import (
    FIG2_TOPOLOGIES,
    MODELS,
    _broker,
    _env,
    _policy,
    _profile,
    _reply_tuple,
)

pytestmark = pytest.mark.service

_TOOLS = pathlib.Path(__file__).resolve().parent.parent / "tools"


def _load_tool(name: str):
    """Import a ``tools/`` script (not a package) by file path."""
    spec = importlib.util.spec_from_file_location(name, _TOOLS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ----------------------------------------------------------------------
# Metrics: instruments, quantiles, merge, disabled mode
# ----------------------------------------------------------------------


def test_counter_and_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("reqs")
    c.inc()
    c.inc(2)
    assert reg.value("reqs") == 3
    assert reg.counter("reqs") is c  # get-or-create
    assert reg.counter("reqs", tenant="a") is not c  # labels split series
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("depth", tenant="a")
    g.set(5)
    g.add(-2)
    assert reg.value("depth", tenant="a") == 3
    assert reg.value("absent", default=7.5) == 7.5


def test_histogram_quantiles_bracket_observations():
    h = Histogram("lat")
    values = [10e-6 * (1.3**i) for i in range(60)]  # ~10µs .. ~53s: in range
    h.observe_many(values)
    assert h.count == 60
    assert (h.min, h.max) == (values[0], values[-1])
    exact = sorted(values)
    for q in (0.5, 0.9, 0.99):
        est = h.quantile(q)
        true = exact[min(int(q * len(exact)), len(exact) - 1)]
        # growth-2 buckets: the estimate lands within one bucket (2x)
        assert true / 2 <= est <= true * 2
    # a single observation reports itself at every quantile (clamping)
    one = Histogram("one")
    one.observe(0.25)
    assert one.p50 == one.p90 == one.p99 == 0.25
    # out-of-range values land in under/overflow, quantiles stay clamped
    wide = Histogram("wide")
    wide.observe_many([1e-9, 1e9])
    assert wide.underflow == 1 and wide.overflow == 1
    assert 1e-9 <= wide.p50 <= 1e9


def test_histogram_merge_equals_union_and_rejects_geometry_mismatch():
    a, b, union = Histogram("x"), Histogram("x"), Histogram("x")
    va, vb = [1e-5, 3e-4, 0.02], [7e-3, 0.5, 4.0]
    a.observe_many(va)
    b.observe_many(vb)
    union.observe_many(va + vb)
    a.merge(b)
    assert a.counts == union.counts
    assert (a.count, a.sum, a.min, a.max) == (
        union.count, union.sum, union.min, union.max,
    )
    assert a.p50 == union.p50 and a.p99 == union.p99
    with pytest.raises(ValueError):
        a.merge(Histogram("x", growth=10.0, n_buckets=8))


def test_disabled_registry_hands_out_shared_nulls():
    reg = MetricsRegistry(enabled=False)
    assert reg.counter("c") is NULL_COUNTER
    assert reg.gauge("g") is NULL_GAUGE
    assert reg.histogram("h") is NULL_HISTOGRAM
    reg.counter("c").inc(5)
    reg.gauge("g").set(9)
    reg.histogram("h").observe(1.0)
    with reg.timer("t"):
        pass
    assert NULL_COUNTER.value == 0
    assert NULL_GAUGE.value == 0
    assert NULL_HISTOGRAM.count == 0
    assert reg.snapshot() == {"counters": [], "gauges": [], "histograms": []}


def test_timer_charges_injected_clock_delta():
    clock = InjectedClock()
    reg = MetricsRegistry(clock=clock)
    with reg.timer("dur", stage="solve"):
        clock.advance(0.125)
    h = reg.get_histogram("dur", stage="solve")
    assert h.count == 1 and h.sum == 0.125
    assert h.p50 == 0.125  # clamped to the single observation


def test_registry_merge_is_fleet_aggregation():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("reqs").inc(3)
    b.counter("reqs").inc(4)
    b.counter("only_b", tenant="t").inc(1)
    a.gauge("depth").set(2)
    b.gauge("depth").set(5)
    a.histogram("h").observe(1e-3)
    b.histogram("h").observe(1e-2)
    a.merge(b)
    assert a.value("reqs") == 7
    assert a.value("only_b", tenant="t") == 1
    assert a.value("depth") == 7  # cross-worker gauges add by convention
    assert a.get_histogram("h").count == 2
    # snapshot is JSON-serializable as-is (the worker wire format)
    json.dumps(a.snapshot())


# ----------------------------------------------------------------------
# Tracer: nesting, events, ring, exporters
# ----------------------------------------------------------------------


def test_span_nesting_parent_ids_and_innermost_events():
    clock = InjectedClock()
    tr = Tracer(clock=clock)
    with tr.span("broker.tick", tick=0) as root:
        clock.advance(1.0)
        with tr.span("stage.solve_flush", bucket=16) as child:
            clock.advance(0.5)
            tr.event("fault", site="solve", tick=0)
        root.set(requests=3)
    finished = tr.spans()
    assert [s.name for s in finished] == ["stage.solve_flush", "broker.tick"]
    child, root = finished
    assert root.parent_id is None and child.parent_id == root.span_id
    assert child.duration == 0.5 and root.duration == 1.5
    assert root.attrs["requests"] == 3
    # the event attached to the innermost open span, not the root
    assert root.events == []
    assert child.events[0]["name"] == "fault"
    assert child.events[0]["attrs"]["site"] == "solve"


def test_orphan_event_becomes_zero_duration_span():
    tr = Tracer(clock=InjectedClock())
    tr.event("degraded", tenant="app", tick=4)
    (s,) = tr.spans()
    assert s.duration == 0.0
    assert s.attrs["orphan_event"] is True and s.attrs["tenant"] == "app"


def test_ring_retains_only_newest_spans():
    tr = Tracer(clock=InjectedClock(), capacity=4)
    for i in range(10):
        with tr.span("s", i=i):
            pass
    assert len(tr) == 4
    assert [s.attrs["i"] for s in tr.spans()] == [6, 7, 8, 9]
    tr.clear()
    assert len(tr) == 0
    with pytest.raises(ValueError):
        Tracer(capacity=0)


def test_disabled_tracer_returns_null_span():
    tr = Tracer(enabled=False)
    assert tr.span("x") is NULL_SPAN
    tr.event("fault")
    assert len(tr) == 0
    # the null span is inert under every instrumented operation
    with NULL_SPAN as s:
        s.set(a=1)
        s.event("e")


def test_export_jsonl_and_chrome_roundtrip(tmp_path):
    clock = InjectedClock()
    tr = Tracer(clock=clock)
    with tr.span("broker.tick", tick=0):
        clock.advance(0.01)
        tr.event("fault", site="solve", kind="error", tick=0)
    out = tmp_path / "trace.jsonl"
    assert tr.export_jsonl(out) == 1
    (doc,) = [json.loads(line) for line in out.read_text().splitlines()]
    assert doc["type"] == "span" and doc["name"] == "broker.tick"
    assert doc["dur"] == 0.01
    assert doc["events"][0]["name"] == "fault"
    chrome = tmp_path / "trace.json"
    assert tr.export_chrome(chrome) == 2  # one "X" span + one "i" instant
    events = json.loads(chrome.read_text())["traceEvents"]
    assert sorted(e["ph"] for e in events) == ["X", "i"]
    (x,) = [e for e in events if e["ph"] == "X"]
    assert x["dur"] == pytest.approx(0.01 * 1e6)  # µs


# ----------------------------------------------------------------------
# PlacementCache: one stat funnel, eviction counts, registry binding
# ----------------------------------------------------------------------


def test_get_many_matches_scalar_path_through_one_funnel(monkeypatch):
    envs_list = [Environment.symmetric(0.5 * (1.6**i), 3.0) for i in range(6)]
    mask = np.random.default_rng(0).random(8) < 0.5

    def make() -> PlacementCache:
        c = PlacementCache(capacity=64)
        c.put(envs_list[0], mask)
        c.put(envs_list[3], ~mask)
        return c

    scalar_cache = make()
    scalar = [scalar_cache.get(e, expected_n=8) for e in envs_list]

    batch_cache = make()
    calls: list[dict] = []
    orig = PlacementCache.record_many

    def spy(self, **kw):
        calls.append(kw)
        return orig(self, **kw)

    monkeypatch.setattr(PlacementCache, "record_many", spy)
    got = batch_cache.get_many(EnvArrays.from_envs(envs_list), expected_n=8)

    # the whole batch funnels through ONE shared increment
    assert len(calls) == 1
    assert calls[0]["hits"] + calls[0]["misses"] == len(envs_list)
    # identical masks and identical accounting vs the scalar loop
    for ga, gb in zip(got, scalar):
        assert (ga is None) == (gb is None)
        if ga is not None:
            assert np.array_equal(ga, gb)
    assert batch_cache.stats == scalar_cache.stats


def test_cache_eviction_counts_and_registry_binding_seeds_history():
    cache = PlacementCache(capacity=2)
    e0, e1, e2 = (Environment.symmetric(bw, 3.0) for bw in (0.3, 2.0, 9.0))
    assert len({cache.key(e) for e in (e0, e1, e2)}) == 3  # distinct bins
    mask = np.ones(6, dtype=bool)
    cache.put(e0, mask)
    cache.get(e0, expected_n=6)  # hit
    cache.get(e1, expected_n=6)  # miss — both BEFORE binding
    reg = MetricsRegistry()
    cache.bind_metrics(reg, tenant="app")
    assert reg.value("cache_hits", tenant="app") == 1  # seeded
    assert reg.value("cache_misses", tenant="app") == 1
    cache.put(e1, mask)
    cache.put(e2, mask)  # capacity 2 → evicts e0's entry
    assert cache.stats.evictions == 1
    assert reg.value("cache_evictions", tenant="app") == 1
    assert reg.value("cache_size", tenant="app") == len(cache) == 2
    cache.get(e2, expected_n=6)
    assert reg.value("cache_hits", tenant="app") == cache.stats.hits == 2


# ----------------------------------------------------------------------
# Detached bit-identity (tentpole acceptance)
# ----------------------------------------------------------------------


@pytest.mark.parametrize("topology", sorted(FIG2_TOPOLOGIES))
@pytest.mark.parametrize("model_name", sorted(MODELS))
def test_attached_observability_is_bit_identical(topology, model_name):
    """Tracer + registry attached produce the same event stream, replies
    and telemetry as the detached broker — observing never perturbs."""
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
    traced_report, traced = run(
        tracer=Tracer(clock=InjectedClock(), capacity=8192),
        metrics=MetricsRegistry(clock=InjectedClock()),
    )
    for a, b in zip(plain_report.events, traced_report.events):
        for ea, eb in zip(a, b):
            assert ea.partial_cost == eb.partial_cost
            assert ea.gain == eb.gain
            assert ea.cache_hit == eb.cache_hit
            assert ea.repartitioned == eb.repartitioned
            assert np.array_equal(ea.result.local_mask, eb.result.local_mask)
    assert plain.telemetry.summary() == traced.telemetry.summary()
    for ra, rb in zip(plain.telemetry.reports, traced.telemetry.reports):
        assert dataclasses.asdict(ra) == dataclasses.asdict(rb)
    # ...and the attached run actually captured the tick structure
    assert traced.tracer.spans("broker.tick")
    assert traced.metrics.value("broker_ticks") == traced.telemetry.ticks


def test_chaos_replies_bit_identical_with_observability_attached():
    """Same contract under a live fault storm: the randomized injector
    fires identically whether or not instruments are attached."""
    profile = _profile(10, 3)

    def run(**kw):
        broker = _broker(
            resilience=_policy(
                degrade="fallback",
                deadline_ticks=6,
                breaker=CircuitBreaker(threshold=3, cooldown_ticks=4),
            ),
            fault_injector=FaultInjector(seed=2024, rate=0.2),
            **kw,
        )
        broker.register("app", profile, ResponseTimeModel())
        futs = []
        for t in range(6):
            for i in range(4):
                futs.append(
                    broker.submit("app", _env(0.5 + 0.7 * i + 0.1 * t))
                )
            broker.tick()
        guard = 0
        while broker.pending and guard < 24:
            broker.tick()
            guard += 1
        assert all(f.done for f in futs)
        return [_reply_tuple(f.result) for f in futs], broker

    plain, _ = run()
    traced, broker = run(
        tracer=Tracer(clock=InjectedClock(), capacity=8192),
        metrics=MetricsRegistry(clock=InjectedClock()),
    )
    assert plain == traced
    tel = broker.telemetry
    assert tel.faults > 0  # the storm actually fired
    assert broker.metrics.value("broker_faults") == tel.faults
    fault_events = [
        e
        for s in broker.tracer.spans()
        for e in s.events
        if e["name"] == "fault"
    ]
    assert len(fault_events) == tel.faults


def test_session_batch_tick_bit_identical_with_observability():
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

    plain_reports, _ = run()
    traced_reports, traced = run(
        tracer=Tracer(clock=InjectedClock(), capacity=8192),
        metrics=MetricsRegistry(clock=InjectedClock()),
    )
    for ra, rb in zip(plain_reports, traced_reports):
        assert ra.placements.tobytes() == rb.placements.tobytes()
        assert ra.min_cut.tobytes() == rb.min_cut.tobytes()
        assert np.array_equal(ra.cache_hit, rb.cache_hit)
        assert (ra.hits, ra.solved, ra.coalesced) == (
            rb.hits, rb.solved, rb.coalesced,
        )
    # the batched session path produced its own stage spans and counters
    names = {s.name for s in traced.tracer.spans()}
    assert {"stage.batch_group", "stage.drift"} <= names
    assert traced.metrics.value("broker_batch_sessions") > 0


# ----------------------------------------------------------------------
# Telemetry ↔ registry views can never disagree
# ----------------------------------------------------------------------


def test_telemetry_fields_mirror_registry_counters():
    metrics = MetricsRegistry(clock=InjectedClock())
    broker = _broker(metrics=metrics)
    profile = _profile(9, 5)
    broker.register("app", profile, ResponseTimeModel())
    traces = user_traces(n_users=4, steps=5, seed=13)
    run_workload(
        broker, "app", n_users=4, steps=5,
        threshold=0.15, min_interval=2, traces=traces,
    )
    tel = broker.telemetry
    assert tel.requests > 0
    views = {
        "broker_ticks": tel.ticks,
        "broker_requests": tel.requests,
        "broker_cache_hits": tel.cache_hits,
        "broker_coalesced": tel.coalesced,
        "broker_solved": tel.solved,
        "broker_dispatches": tel.dispatches,
        "broker_degraded_replies": tel.degraded_replies,
        "broker_rejected_requests": tel.rejected_requests,
    }
    for name, want in views.items():
        assert metrics.value(name) == want, name
    # per-tenant cache counters were bound by register()
    cache = broker._tenants["app"].cache
    assert metrics.value("cache_hits", tenant="app") == cache.stats.hits
    assert metrics.value("cache_misses", tenant="app") == cache.stats.misses
    # one tick-latency sample per tick; quantile view reads the histogram
    h = metrics.get_histogram("broker_tick_latency_s")
    assert h is not None and h.count == tel.ticks
    assert tel.tick_latency_quantiles() == (h.p50, h.p90, h.p99)
    # solver dispatches carried (backend, bucket, devices, solver) labels
    snap = metrics.snapshot()
    dispatch_rows = [
        c for c in snap["counters"] if c["name"] == "solve_envs_dispatches"
    ]
    assert tel.dispatches == 0 or dispatch_rows == [] or all(
        set(c["labels"]) == {"backend", "bucket", "devices", "solver"}
        for c in dispatch_rows
    )
    # queue gauges were published
    assert metrics.get_gauge("broker_queue_depth") is not None


def test_bind_metrics_after_history_seeds_counters():
    broker = _broker()
    broker.register("app", _profile(8, 2), ResponseTimeModel())
    for i in range(3):
        broker.submit("app", _env(1.0 + i))
        broker.tick()
    tel = broker.telemetry
    assert tel.metrics is None and tel.tick_latency_quantiles() == (0, 0, 0)
    reg = MetricsRegistry()
    tel.bind_metrics(reg)
    assert reg.value("broker_ticks") == tel.ticks
    assert reg.value("broker_requests") == tel.requests
    assert reg.value("broker_solved") == tel.solved
    # post-bind ticks keep the views equal
    broker.submit("app", _env(9.0))
    broker.tick()
    assert reg.value("broker_requests") == tel.requests


# ----------------------------------------------------------------------
# Degraded-reply provenance + tools/tracequery.py (the CI audit gate)
# ----------------------------------------------------------------------


def test_degraded_reply_trace_provenance_and_audit(tmp_path, capsys):
    tracequery = _load_tool("tracequery")
    clock = InjectedClock()
    tracer = Tracer(clock=clock)
    broker = _broker(
        clock=clock,
        resilience=_policy(),
        fault_injector=ScriptedFaultInjector(
            {("solve", 1, i): "error" for i in range(3)}  # all 3 attempts
        ),
        tracer=tracer,
        metrics=MetricsRegistry(clock=clock),
    )
    broker.register("app", _profile(8, 1), ResponseTimeModel())
    fut = broker.submit("app", _env())
    broker.tick()
    assert fut.result.degraded

    out = tmp_path / "trace.jsonl"
    assert tracer.export_jsonl(out) > 0
    spans = tracequery.load_spans(out)
    (row,) = tracequery.degraded_provenance(spans)
    assert row["tick"] == 1
    assert row["fault_events"], "degraded event must carry fault provenance"
    assert all(a["site"] == "solve" for a in row["fault_events"])
    assert row["retry_events"] == 2  # attempts 2 and 3
    assert tracequery.audit(spans) == []
    assert tracequery.main([str(out), "--audit"]) == 0
    assert "audit ok" in capsys.readouterr().out


def test_tracequery_audit_flags_unattributed_degraded(tmp_path):
    tracequery = _load_tool("tracequery")
    span = {
        "type": "span",
        "name": "broker.tick",
        "span_id": 1,
        "parent_id": None,
        "ts": 0.0,
        "dur": 0.01,
        "attrs": {"tick": 3},
        "events": [
            {
                "name": "degraded",
                "ts": 0.005,
                "attrs": {"tenant": "app", "tick": 3, "stale": False},
            }
        ],
    }
    bad = tmp_path / "bad.jsonl"
    bad.write_text(json.dumps(span) + "\nnot json, skipped with warning\n")
    (orphan,) = tracequery.audit(tracequery.load_spans(bad))
    assert orphan["tick"] == 3
    assert tracequery.main([str(bad), "--audit"]) == 1  # CI gate trips
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert tracequery.main([str(empty)]) == 2


def test_chaos_trace_tool_is_deterministic(tmp_path):
    """Two runs of the CI chaos-storm exporter with the same seed write
    byte-identical artifacts (shared InjectedClock: no real time)."""
    chaos_trace = _load_tool("chaos_trace")
    tracequery = _load_tool("tracequery")
    paths = []
    for tag in ("a", "b"):
        out = tmp_path / f"trace_{tag}.jsonl"
        rc = chaos_trace.main(
            ["--out", str(out), "--rate", "0.5", "--steps", "4",
             "--users", "4", "--seed", "7", "--retries", "1"]
        )
        assert rc == 0
        paths.append(out)
    assert paths[0].read_bytes() == paths[1].read_bytes()
    # and the artifact passes the same audit CI runs
    spans = tracequery.load_spans(paths[0])
    assert spans and tracequery.audit(spans) == []


# ----------------------------------------------------------------------
# Profiler annotations, server reactor spans, queue wait, solve wait
# ----------------------------------------------------------------------


class _Annotation:
    """Annotation factory that logs enters and exits in order."""

    log: list = []

    def __init__(self, name, **attrs):
        self.name, self.attrs = name, attrs

    def __enter__(self):
        self.log.append(("enter", self.name, self.attrs))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))
        return False


def test_tracer_opens_one_annotation_per_span_in_nesting_order():
    _Annotation.log = []
    tr = Tracer(clock=InjectedClock(), annotation=_Annotation)
    with tr.span("broker.tick", tick=3):
        with tr.span("stage.solve_flush", bucket=16):
            with tr.span("solve.wait"):
                pass
        with pytest.raises(ValueError):
            with tr.span("stage.commit"):
                raise ValueError("boom")
    assert _Annotation.log == [
        ("enter", "broker.tick", {"tick": 3}),
        ("enter", "stage.solve_flush", {"bucket": 16}),
        ("enter", "solve.wait", {}),
        ("exit", "solve.wait"),
        ("exit", "stage.solve_flush"),
        ("enter", "stage.commit", {}),
        ("exit", "stage.commit"),
        ("exit", "broker.tick"),
    ]
    assert len(tr) == 4
    # a disabled tracer opens none; the default is the profiler's own
    _Annotation.log = []
    assert Tracer(enabled=False, annotation=_Annotation).span("x") is NULL_SPAN
    assert _Annotation.log == []
    import jax

    assert isinstance(profiler_annotation("wire.read"), jax.profiler.TraceAnnotation)


def _serve(tmp_path, broker, tracer=None, **kwargs):
    server = SolverServer(
        broker, address=unix_address(tmp_path / "srv.sock"), tracer=tracer,
        **kwargs,
    )
    server.bind()
    thread = threading.Thread(
        target=server.serve_forever, kwargs={"poll_s": 0.01}, daemon=True
    )
    thread.start()
    return server, thread


def _submit_and_tick(server, profile, envs, ticks=1):
    client = BrokerClient(
        unix_address(server.address[1]),
        tenants={"app": (profile, ResponseTimeModel())},
        client="obs", timeout=10.0,
    ).connect()
    futures = [client.submit("app", env) for env in envs]
    for _ in range(ticks):
        client.tick()
    assert all(f.done for f in futures)
    client.close()
    return futures


def test_detached_broker_and_server_make_no_span_and_stamp_nothing(
    tmp_path, monkeypatch
):
    import repro.obs.trace as trace_mod
    import repro.service.broker as broker_mod

    made = []
    monkeypatch.setattr(
        trace_mod.Span, "__init__",
        lambda self, *a, **k: made.append(a) or None,
    )
    queued = []
    enqueue = broker_mod.OffloadBroker._enqueue
    monkeypatch.setattr(
        broker_mod.OffloadBroker, "_enqueue",
        lambda self, r: queued.append(r) or enqueue(self, r),
    )
    reads = []
    clock = InjectedClock()
    broker = _broker(clock=lambda: reads.append(1) or clock())
    profile = _profile(8, 1)
    broker.register("app", profile, ResponseTimeModel())
    server, thread = _serve(tmp_path, broker)
    try:
        _submit_and_tick(server, profile, [_env(0.5 + i) for i in range(3)], ticks=2)
    finally:
        server.stop()
        thread.join(timeout=10)
    assert made == []
    assert len(queued) == 3
    assert all(r.queued_at is None for r in queued)
    assert len(reads) == 2 * broker._tick == 4


def test_broker_tick_records_request_ids_and_queue_waits():
    clock = InjectedClock()
    tr = Tracer(clock=clock)
    broker = _broker(tracer=tr)
    broker.register("app", _profile(8, 2), ResponseTimeModel())
    broker.submit("app", _env(0.5), request_id="a")
    clock.advance(1.0)
    broker.submit("app", _env(3.0), request_id="b")
    broker.submit("app", _env(9.0))  # no id given: the broker numbers it
    clock.advance(0.5)
    broker.tick(budget=2)
    clock.advance(0.25)
    broker.tick()
    first, second = tr.spans("broker.tick")
    assert first.attrs["request_ids"] == ["a", "b"]
    assert first.attrs["queue_wait_s"] == [1.5, 0.5]
    assert second.attrs["request_ids"] == [1]
    assert second.attrs["queue_wait_s"] == [0.75]


def test_requeued_request_keeps_its_first_stamp(monkeypatch):
    import repro.service.broker as broker_mod

    clock = InjectedClock()
    tr = Tracer(clock=clock)
    broker = _broker(tracer=tr)
    broker.register("app", _profile(8, 3), ResponseTimeModel())
    fut = broker.submit("app", _env(2.0), request_id="r1")
    clock.advance(1.0)
    solve = broker_mod.mcop_batch

    def fail(*a, **k):
        raise RuntimeError("device lost")

    monkeypatch.setattr(broker_mod, "mcop_batch", fail)
    with pytest.raises(RuntimeError):
        broker.tick()
    assert broker.pending == 1 and not fut.done
    monkeypatch.setattr(broker_mod, "mcop_batch", solve)
    clock.advance(2.0)
    broker.tick()
    assert fut.done
    failed, served = tr.spans("broker.tick")
    assert failed.attrs["queue_wait_s"] == [1.0]
    assert served.attrs["request_ids"] == ["r1"]
    assert served.attrs["queue_wait_s"] == [3.0]


def _parent(spans, span):
    by_id = {s.span_id: s for s in spans}
    return by_id.get(span.parent_id)


def test_solve_wait_nests_under_the_solve_flush_on_both_paths():
    tr = Tracer()
    broker = _broker(backend="jax", tracer=tr)
    profile = AppProfile.from_wcg_times(FIG2_TOPOLOGIES["linear"]())
    broker.register("app", profile, ResponseTimeModel())
    for i in range(3):
        broker.submit("app", _env(0.5 + 2 * i))
    broker.tick()
    group = broker.register_batch("app", 4)
    group.observe(
        EnvArrays.from_envs([_env(0.3 + i) for i in range(4)]),
        arrived=np.arange(4),
    )
    broker.tick()
    spans = tr.spans()
    waits = tr.spans("solve.wait")
    assert len(waits) == 2  # one per flush: the request bucket, the group
    for w in waits:
        flush = _parent(spans, w)
        assert flush.name == "stage.solve_flush"
        assert flush.t0 <= w.t0 and w.t1 <= flush.t1
    request_flush, group_flush = (_parent(spans, w) for w in waits)
    assert _parent(spans, request_flush).name == "broker.tick"
    assert _parent(spans, group_flush).name == "stage.batch_group"


def test_server_spans_cover_waits_and_reads_outside_frames(tmp_path):
    tr = Tracer(capacity=100_000)
    broker = _broker(tracer=tr)
    profile = _profile(8, 4)
    broker.register("app", profile, ResponseTimeModel())
    server, thread = _serve(tmp_path, broker, tracer=tr)
    try:
        _submit_and_tick(server, profile, [_env(0.5 + i) for i in range(3)])
    finally:
        server.stop()
        thread.join(timeout=10)
    spans = tr.spans()
    names = {s.name for s in spans}
    assert {"server.wait", "wire.read", "wire.frame", "broker.tick"} <= names
    parents = {s.parent_id for s in spans}
    for s in spans:
        if s.name in ("server.wait", "wire.read"):
            assert s.parent_id is None  # top-level, never around a frame
            assert s.span_id not in parents  # holds no span at all
    frames = tr.spans("wire.frame")
    assert all(f.parent_id is None for f in frames)
    # one request's spans share its id: the submit frame and the tick
    submit_ids = [f.attrs["id"] for f in frames if f.attrs["type"] == "submit"]
    (tick,) = tr.spans("broker.tick")
    assert len(submit_ids) == 3
    assert tick.attrs["request_ids"] == submit_ids


def test_snapshot_pass_splits_into_one_save_per_tenant_then_compaction(tmp_path):
    tr = Tracer(capacity=100_000)
    broker = _broker(tracer=tr)
    profile = _profile(8, 4)
    broker.register("app", profile, ResponseTimeModel())
    broker.register("other", _profile(6, 5), ResponseTimeModel())
    server, thread = _serve(
        tmp_path, broker, tracer=tr, journal_path=tmp_path / "journal.jsonl",
        snapshot_dir=tmp_path / "snaps", snapshot_every_ticks=1,
    )
    try:
        _submit_and_tick(server, profile, [_env(0.5 + i) for i in range(3)])
    finally:
        server.stop()
        thread.join(timeout=10)
    spans = tr.spans()
    (snapshot,) = tr.spans("wire.snapshot")
    children = [s for s in spans if s.parent_id == snapshot.span_id]
    assert [s.name for s in children] == [
        "snapshot.save", "snapshot.save", "snapshot.compact"
    ]
    for save in children[:2]:
        tenant = save.attrs["tenant"]
        path = tmp_path / "snaps" / f"{tenant}.snapshot.json"
        assert save.attrs["entries"] == len(broker.tenant(tenant).cache)
        assert save.attrs["bytes"] == path.stat().st_size
    assert [s.attrs["tenant"] for s in children[:2]] == ["app", "other"]
    assert children[0].attrs["entries"] > 0
    assert all(snapshot.t0 <= s.t0 and s.t1 <= snapshot.t1 for s in children)
