"""The reduction from spans and device traces to per-layer numbers, on
synthetic spans and on a small trace recorded on a TPU v5e (one solve
flush of two Fig. 12 graphs at bucket 16)."""

import importlib.util
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402

RECORDED = pathlib.Path(__file__).resolve().parent / "data" / "fig12_flush.xplane.pb"


def span(sid, parent, name, ts, dur, **attrs):
    return {"span_id": sid, "parent_id": parent, "name": name, "ts": ts, "dur": dur, "attrs": attrs}


def test_self_time_subtracts_children_and_clips_to_the_window():
    spans = [
        span(1, None, "wire.frame", 0.0, 10.0),
        span(2, 1, "broker.tick", 1.0, 6.0),
        span(3, 2, "stage.solve_flush", 2.0, 3.0),
        span(4, None, "wire.frame", 12.0, 4.0),
    ]
    own = trace.self_times(spans, 0.0, 14.0)
    assert own["wire.frame"] == pytest.approx(4.0 + 2.0)
    assert own["broker.tick"] == pytest.approx(3.0)
    assert own["stage.solve_flush"] == pytest.approx(3.0)


def test_busy_is_the_union_of_intervals():
    ops = [("a", 0, 10), ("b", 5, 15), ("c", 20, 30), ("d", 22, 25)]
    assert trace.busy_seconds(ops) == pytest.approx(25e-9)
    assert trace.idle_gaps(ops)[0] == ["after b", pytest.approx(5e-9)]


def test_solve_ops_and_bytes_by_hand():
    ops, nbytes = trace.solve_ops_bytes(3, pinned=1)
    # phases over a = 3 and a = 2 vertices: 2*3*3+2*3 + 1*3*2+2*2
    assert ops == 24 + 10
    assert nbytes == 4 * (9 + 6) + 3 + 4


def test_peaks_table_refuses_an_unknown_device():
    assert trace.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        trace.peaks("TPU v9 imaginary")


def test_recorded_trace():
    dev = trace.device_trace(RECORDED)
    assert dev["planes"] == 1
    assert [m[0].split("(")[0] for m in dev["modules"]] == ["jit__mcop_batch_impl"]
    busy = trace.busy_seconds(dev["ops"])
    module_s = (dev["modules"][0][2] - dev["modules"][0][1]) * 1e-9
    assert 0 < busy <= module_s
    assert len(trace.top_ops(dev["ops"])) == 10


def test_roofline_reader_on_the_recorded_trace():
    spec = importlib.util.spec_from_file_location("roofline", ROOT / "bench" / "metrics" / "solve_roofline.py")
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    dev = trace.device_trace(RECORDED)
    ctx = {"device": dev, "device_kind": "TPU v5 lite", "trace_span": (0.0, 1.0),
           "spans": [span(1, None, "stage.solve_flush", 0.5, 0.01, batch=2)],
           "profile_n": 9, "profile_pinned": 2}
    share = reader.read(ctx)
    assert 0 < share < 100
    assert reader.read(dict(ctx, spans=[])) is None
