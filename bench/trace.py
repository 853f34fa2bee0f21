"""Reduction from spans, compile events and the device trace to numbers.

Kept with the benchmark so that every PR computes each per-layer metric
the same way:

* ``self_times``: a span's self time is its duration minus what its child
  spans cover, clipped to the measured window;
* ``device_trace``: the events of the device planes of a JAX profiler
  trace (``*.xplane.pb``), read with ``jax.profiler.ProfileData``;
* ``busy_seconds``: the union of the device's op intervals;
* ``solve_ops_bytes``: the work one MCOP solve of an n-vertex graph needs
  (see its docstring), for a kernel's roofline share;
* ``peaks``: the chip's published peaks, keyed by ``device_kind``.
"""

from __future__ import annotations

import glob
import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def peaks(device_kind: str) -> dict:
    """Published peaks of ``device_kind``; a device not in the table is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} in {PEAKS.name}")
    return table[device_kind]


def self_times(spans: list[dict], t0: float, t1: float) -> dict[str, float]:
    """Self seconds per span name inside [t0, t1].

    ``spans`` are the tracer's dicts (``span_id``, ``parent_id``, ``ts``,
    ``dur``, ``name``); children are subtracted from their parent after
    both are clipped to the window.
    """

    def clip(s):
        return max(0.0, min(s["ts"] + s["dur"], t1) - max(s["ts"], t0))

    child = {}
    for s in spans:
        if s.get("parent_id") is not None:
            child[s["parent_id"]] = child.get(s["parent_id"], 0.0) + clip(s)
    out: dict[str, float] = {}
    for s in spans:
        own = clip(s) - child.get(s["span_id"], 0.0)
        out[s["name"]] = out.get(s["name"], 0.0) + max(own, 0.0)
    return out


def total_times(spans: list[dict], t0: float, t1: float, name: str) -> tuple[float, list[dict]]:
    """(seconds, spans) of the spans called ``name`` that start inside [t0, t1]."""
    inside = [s for s in spans if s["name"] == name and t0 <= s["ts"] < t1]
    return sum(s["dur"] for s in inside), inside


def xplane_file(trace_dir) -> str | None:
    files = sorted(glob.glob(str(pathlib.Path(trace_dir) / "**" / "*.xplane.pb"), recursive=True))
    return files[-1] if files else None


def device_trace(path) -> dict:
    """Device events of one trace: ``ops`` and ``modules`` as
    (name, start_ns, end_ns) lists over every device plane, and the
    number of device planes."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    ops, modules, planes = [], [], 0
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        planes += 1
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops.extend((e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events)
            elif line.name == MODULES_LINE:
                modules.extend((e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events)
    return {"ops": ops, "modules": modules, "planes": planes}


def busy_seconds(intervals: list[tuple]) -> float:
    """Length of the union of (name, start_ns, end_ns) intervals, in seconds."""
    busy = 0.0
    end = None
    for _, a, b in sorted(intervals, key=lambda x: x[1]):
        if end is None or a > end:
            busy += b - a
            end = b
        elif b > end:
            busy += b - end
            end = b
    return busy * 1e-9


def top_ops(ops: list[tuple], k: int = 10) -> list[list]:
    """The ``k`` device operations that took most time, by the name the
    trace prints up to its first ' = ' (the HLO instruction)."""
    total: dict[str, float] = {}
    for name, a, b in ops:
        key = name.split(" = ", 1)[0]
        total[key] = total.get(key, 0.0) + (b - a) * 1e-9
    return [[n, s] for n, s in sorted(total.items(), key=lambda x: -x[1])[:k]]


def idle_gaps(ops: list[tuple], k: int = 10) -> list[list]:
    """The ``k`` longest gaps between device operations, named by the
    operation that ended before each."""
    gaps = []
    end, last = None, None
    for name, a, b in sorted(ops, key=lambda x: x[1]):
        if end is not None and a > end:
            gaps.append([f"after {last.split(' = ', 1)[0]}", (a - end) * 1e-9])
        if end is None or b > end:
            end, last = b, name
    return sorted(gaps, key=lambda g: -g[1])[:k]


def solve_ops_bytes(n: int, pinned: int, dtype_bytes: int = 4) -> tuple[float, float]:
    """Operations and bytes one MCOP solve of an n-vertex graph needs.

    After the ``pinned`` vertices fold into one, a = N..2 vertices remain
    (N = n - pinned + 1, or n with none pinned) in the phases.  A phase
    over a vertices absorbs a-1 vertices; each absorption scores every
    vertex (subtract, compare: 2a) and adds the absorbed row (a), and the
    merge adds a row and a column (2a).  Bytes are the compulsory traffic:
    the adjacency and both weight vectors read once, the mask and the cut
    written once.  The same work is counted whatever implements it.
    """
    big_n = n - max(pinned, 1) + 1
    ops = sum((a - 1) * 3 * a + 2 * a for a in range(2, big_n + 1))
    nbytes = dtype_bytes * (n * n + 2 * n) + n + dtype_bytes
    return float(ops), float(nbytes)
