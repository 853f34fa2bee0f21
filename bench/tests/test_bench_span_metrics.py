"""The readers of the server's reactor, read, queue-wait and solve-wait
spans, on synthetic spans, and the alignment of the tracer's clock with a
device trace recorded on a TPU v5e (one solve flush of two Fig. 12 graphs
at bucket 16)."""

import importlib.util
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402

RECORDED = pathlib.Path(__file__).resolve().parent / "data" / "fig12_flush.xplane.pb"


def reader(name):
    spec = importlib.util.spec_from_file_location(name, ROOT / "bench" / "metrics" / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def span(sid, parent, name, ts, dur, **attrs):
    return {"span_id": sid, "parent_id": parent, "name": name, "ts": ts, "dur": dur, "attrs": attrs}


# A 10 s window of one server: it waits, reads a submit, handles it, waits,
# reads a tick frame and runs a tick with one flush whose device wait is 2.5 s.
OLD = [
    span(2, None, "wire.frame", 1.0, 0.5, type="submit", id="a"),
    span(4, None, "wire.frame", 3.0, 5.0, type="tick"),
    span(5, 4, "broker.tick", 3.5, 4.0, tick=1),
    span(6, 5, "stage.materialize", 3.5, 0.5),
    span(7, 5, "stage.solve_flush", 4.0, 3.0, batch=1),
]
NEW = [
    span(1, None, "server.wait", 0.0, 0.8),
    span(11, None, "wire.read", 0.8, 0.2),
    span(3, None, "server.wait", 1.5, 1.25),
    span(12, None, "wire.read", 2.75, 0.25),
    span(8, 7, "solve.wait", 4.5, 2.5),
    span(9, None, "server.wait", 8.0, 2.0),
]


def ctx_of(spans, **extra):
    return {"window": (0.0, 10.0), "window_s": 10.0, "spans": spans, **extra}


def with_waits(spans, waits):
    out = [dict(s) for s in spans]
    for s in out:
        if s["name"] == "broker.tick":
            s["attrs"] = dict(s["attrs"], queue_wait_s=waits)
    return out


@pytest.mark.parametrize("name, value", [
    ("server_wait_share.req", 0.405), ("server_wait_share.sess", 0.405),
    ("wire_read_share.req", 0.045), ("wire_read_share.sess", 0.045),
    ("solve_host_ms.req", 500.0),
])
def test_span_readers_on_synthetic_spans(name, value):
    m = reader(name)
    assert m.read(ctx_of(OLD + NEW)) == pytest.approx(value)
    # a program without the span reports nothing, and does not raise
    assert m.read(ctx_of(OLD)) is None


def test_queue_wait_p95_reads_every_drained_request():
    m = reader("queue_wait_p95_ms.req")
    waits = [0.001 * i for i in range(1, 21)]  # 1..20 ms
    assert m.read(ctx_of(with_waits(OLD, waits))) == pytest.approx(19.0)
    assert m.read(ctx_of(OLD)) is None
    # a tick that starts outside the window is not read
    late = [dict(s, ts=11.0) if s["name"] == "broker.tick" else s for s in with_waits(OLD, waits)]
    assert m.read(ctx_of(late)) is None


def test_new_spans_leave_frame_and_broker_self_times_unchanged():
    before = trace.self_times(OLD, 0.0, 10.0)
    after = trace.self_times(OLD + NEW, 0.0, 10.0)
    for name in ("wire.frame", "broker.tick", "stage.materialize"):
        assert after[name] == pytest.approx(before[name])
    assert after["stage.solve_flush"] == pytest.approx(before["stage.solve_flush"] - 2.5)
    # the readers of stage.solve_flush read its duration, not its self time
    ctx = ctx_of(OLD + NEW)
    assert reader("solve_flush_ms.req").read(ctx) == reader("solve_flush_ms.req").read(ctx_of(OLD))
    assert reader("wire_self_share.req").read(ctx) == pytest.approx(reader("wire_self_share.req").read(ctx_of(OLD)))
    assert reader("broker_host_share.req").read(ctx) == pytest.approx(
        reader("broker_host_share.req").read(ctx_of(OLD)))


def flush_around(module, offset, t0):
    """Spans of one flush wrapped around ``module`` on a tracer clock that
    reads ``offset`` more than the device's."""
    start, end = module[1] * 1e-9 + offset, module[2] * 1e-9 + offset
    return [
        span(t0, None, "wire.frame", start - 0.004, end - start + 0.006, type="tick"),
        span(t0 + 1, t0, "stage.solve_flush", start - 0.003, end - start + 0.004, batch=2),
        span(t0 + 2, t0 + 1, "solve.wait", start - 0.001, end - start + 0.001),
    ]


def test_alignment_recovers_a_known_offset_on_the_recorded_trace():
    m = reader("idle_host_busy_share.req")
    dev = trace.device_trace(RECORDED)
    (module,) = dev["modules"]
    offset = 1234.5678
    spans = flush_around(module, offset, 1)
    got = m.align(spans, dev["modules"], (offset, offset + 1.0))
    assert got["offset_s"] == pytest.approx(offset, abs=1e-9)
    assert got["pairs"] == got["inside"] == 1
    assert got["residual_spread_s"] == 0.0
    # the server sat in the tick frame all through the module and waited
    # for the rest of the traced span: idle time splits between the two
    start, end = module[1] * 1e-9 + offset, module[2] * 1e-9 + offset
    t0, t1 = start - 0.05, end + 0.05
    spans += [span(10, None, "server.wait", t0, start - 0.004 - t0),
              span(11, None, "server.wait", end + 0.002, t1 - end - 0.002)]
    ctx = {"device": dev, "spans": spans, "trace_span": (t0, t1)}
    got = m.attribute(spans, dev, (t0, t1))
    idle = (t1 - t0) - trace.busy_seconds(dev["ops"])
    assert got["idle_s"] == pytest.approx(idle, rel=1e-9)
    assert sum(got["by_span"].values()) == pytest.approx(idle, rel=1e-9)
    assert got["by_span"]["server.wait"] == pytest.approx(0.094, abs=1e-9)
    assert got["by_span"]["wire.frame"] == pytest.approx(idle - 0.094, abs=1e-9)
    assert m.read(ctx) == pytest.approx((idle - 0.094) / idle)
    # no solve.wait (a program without it), or no solve program: nothing
    assert m.read(dict(ctx, spans=[s for s in spans if s["name"] != "solve.wait"])) is None
    assert m.read(dict(ctx, device=dict(dev, modules=[]))) is None


def test_alignment_slides_past_a_flush_the_trace_missed():
    m = reader("idle_host_busy_share.req")
    # three solve programs on the device; the first flush began
    # before the traced span, so only the last two flushes are in it
    modules = [("jit__mcop_batch_impl(1)", int(a * 1e6), int((a + 20) * 1e6)) for a in (10, 110, 230)]
    offset = 50.0
    spans = flush_around(modules[0], offset, 1) + flush_around(modules[1], offset, 11) \
        + flush_around(modules[2], offset, 21)
    got = m.align(spans, modules, (offset + 0.1, offset + 1.0))
    assert got["pairs"] == got["inside"] == 2
    assert got["offset_s"] == pytest.approx(offset, abs=1e-9)
