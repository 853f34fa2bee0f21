"""The reader of the server's snapshot-save spans, on synthetic spans: the
``snapshot.save`` children of a ``wire.snapshot`` pass inside the window."""

import importlib.util
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402


def reader(name):
    spec = importlib.util.spec_from_file_location(name, ROOT / "bench" / "metrics" / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def span(sid, parent, name, ts, dur, **attrs):
    return {"span_id": sid, "parent_id": parent, "name": name, "ts": ts, "dur": dur, "attrs": attrs}


def ctx_of(spans):
    return {"window": (0.0, 10.0), "window_s": 10.0, "spans": spans}


# A 10 s window of one server: a submit frame, then a tick frame whose tick
# ends in a snapshot pass; a second pass starts after the window.
FRAMES = [
    span(1, None, "server.wait", 0.0, 0.8),
    span(2, None, "wire.frame", 1.0, 0.5, type="submit", id="a"),
    span(4, None, "wire.frame", 3.0, 5.0, type="tick"),
    span(5, 4, "broker.tick", 3.5, 4.0, tick=1),
]
# two tenants' saves of 0.3 s and 0.1 s, then the compaction
PASSES = [
    span(20, 4, "wire.snapshot", 7.5, 0.45, transport="unix"),
    span(21, 20, "snapshot.save", 7.5, 0.3, tenant="a", entries=4096, bytes=327_799),
    span(22, 20, "snapshot.save", 7.8, 0.1, tenant="b", entries=400, bytes=5_000),
    span(23, 20, "snapshot.compact", 7.9, 0.05),
    span(30, None, "wire.snapshot", 10.5, 0.5, transport="unix"),
    span(31, 30, "snapshot.save", 10.5, 0.5, tenant="a", entries=4096, bytes=327_799),
]


def test_snapshot_save_ms_reads_the_saves_in_the_window():
    m = reader("snapshot_save_ms.req")
    assert m.read(ctx_of(FRAMES + PASSES)) == pytest.approx(200.0)
    # a program without the span reports nothing, and does not raise
    assert m.read(ctx_of(FRAMES + PASSES[:1])) is None
    assert m.read(ctx_of(FRAMES)) is None


def test_snapshot_children_split_the_pass_and_leave_the_frame_unchanged():
    whole = trace.self_times(FRAMES + PASSES[:1], 0.0, 10.0)
    split = trace.self_times(FRAMES + PASSES[:4], 0.0, 10.0)
    assert split["wire.frame"] == pytest.approx(whole["wire.frame"])
    assert split["broker.tick"] == pytest.approx(whole["broker.tick"])
    assert split["wire.snapshot"] == pytest.approx(0.0)
    assert split["snapshot.save"] == pytest.approx(0.4)
    assert split["snapshot.compact"] == pytest.approx(0.05)
