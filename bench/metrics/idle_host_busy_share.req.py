"""Share of the device's idle time in the traced span during which the
server was busy: inside one of its own top-level spans other than
``server.wait``.

The server's spans are on the tracer's clock; the device's events count ns
from the profile's start.  ``align`` matches the solve programs of the trace
(``solve_roofline.PROGRAMS``) in order to the ``stage.solve_flush`` spans of
the traced span, and takes the offset as the least (``solve.wait`` end -
program end) over the matched pairs: the wait ends only after its program
has.  ``attribute`` then splits the device's idle time by the top-level span
the server was in (``None``: in no span).  Both take the tracer's span dicts
and the device trace as ``bench.trace.device_trace`` reads them, so they can
be called on a saved run (``server.json`` and its trace) as well.
"""

import collections

from bench.metrics.solve_roofline import PROGRAMS


def align(spans: list[dict], modules: list[tuple], trace_span: tuple) -> dict | None:
    """The offset (s) that puts device ns on the tracer's clock,
    ``t = ns * 1e-9 + offset``, with its matched pairs, or ``None`` when the
    trace holds no solve program or the span no flush with a ``solve.wait``.

    Where the two counts differ (a flush begun before the trace, a program
    after its end), the solve programs are slid along the flushes, and the
    pairing that puts most programs inside their flush, then the one whose
    residuals spread least, is kept."""
    t0, t1 = trace_span
    wait_end: dict = {}
    for s in spans:
        if s["name"] == "solve.wait":
            wait_end[s["parent_id"]] = max(wait_end.get(s["parent_id"], s["ts"]), s["ts"] + s["dur"])
    flushes = sorted((s for s in spans if s["name"] == "stage.solve_flush" and t0 <= s["ts"] < t1
                      and s["span_id"] in wait_end), key=lambda s: s["ts"])
    programs = sorted((m for m in modules if m[0].startswith(PROGRAMS)), key=lambda m: m[1])
    if not flushes or not programs:
        return None
    best, best_key = None, None
    for shift in range(1 - len(flushes), len(programs)):
        pairs = [(programs[i + shift], f) for i, f in enumerate(flushes) if 0 <= i + shift < len(programs)]
        residuals = [wait_end[f["span_id"]] - m[2] * 1e-9 for m, f in pairs]
        offset = min(residuals)
        inside = [f["ts"] <= m[1] * 1e-9 + offset and m[2] * 1e-9 + offset <= f["ts"] + f["dur"]
                  for m, f in pairs]
        spread = max(residuals) - offset
        key = (sum(inside), len(pairs), -spread)
        if best_key is None or key > best_key:
            best_key = key
            best = {"offset_s": offset, "pairs": len(pairs), "inside": sum(inside),
                    "residual_spread_s": spread}
    return best


def _union(intervals: list[tuple]) -> list[list]:
    out: list[list] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def attribute(spans: list[dict], device: dict, trace_span: tuple) -> dict | None:
    """The device's idle seconds in ``trace_span`` by the server's top-level
    span name (``None``: in no span), with the alignment; ``None`` where
    ``align`` finds none."""
    aligned = align(spans, device["modules"], trace_span)
    if aligned is None:
        return None
    t0, t1 = trace_span
    off = aligned["offset_s"]
    busy = _union([(max(a * 1e-9 + off, t0), min(b * 1e-9 + off, t1)) for _, a, b in device["ops"]
                   if a * 1e-9 + off < t1 and b * 1e-9 + off > t0])
    idle, edge = [], t0
    for a, b in busy:
        if a > edge:
            idle.append((edge, a))
        edge = max(edge, b)
    if edge < t1:
        idle.append((edge, t1))
    top = sorted((s["ts"], s["ts"] + s["dur"], s["name"]) for s in spans
                 if s.get("parent_id") is None and s["dur"] > 0 and s["ts"] < t1 and s["ts"] + s["dur"] > t0)
    by_span: dict = collections.defaultdict(float)
    j = 0
    for a, b in idle:
        while j < len(top) and top[j][1] <= a:
            j += 1
        covered, k = 0.0, j
        while k < len(top) and top[k][0] < b:
            o = min(b, top[k][1]) - max(a, top[k][0])
            if o > 0:
                by_span[top[k][2]] += o
                covered += o
            k += 1
        by_span[None] += (b - a) - covered
    return {**aligned, "idle_s": sum(b - a for a, b in idle), "by_span": dict(by_span)}


def read(ctx):
    dev = ctx["device"]
    if dev is None or not dev["ops"]:
        return None
    got = attribute(ctx["spans"], dev, ctx["trace_span"])
    if got is None or not got["idle_s"]:
        return None
    busy = sum(s for name, s in got["by_span"].items() if name not in (None, "server.wait"))
    return busy / got["idle_s"]
