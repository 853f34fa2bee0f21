"""The four-chip granite cell on the host CPU, and its readers.

* The served fleet: ``bench/run.py`` drives ``granite34b.req_miss.4chip``
  (its mix cut to a test's size) with the server on four virtual CPU
  devices. Every reply passes the reference check, each flush is padded
  to a multiple of 4 and dealt to 4 shards, and the run exits 2 for want
  of a TPU.
* The readers of the fleet's spans and device trace, on synthetic spans
  and a synthetic four-plane trace; the one-chip device idle reader
  misreads that trace, which is recorded here and left as it is.
"""

import importlib.util
import json
import pathlib
import subprocess
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import _checkout  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402

CELL = "granite34b.req_miss.4chip"


def reader(name):
    spec = importlib.util.spec_from_file_location(name, ROOT / "bench" / "metrics" / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def span(sid, parent, name, ts, dur, **attrs):
    return {"span_id": sid, "parent_id": parent, "name": name, "ts": ts, "dur": dur, "attrs": attrs}


def test_the_fleet_config_is_granites_graph_and_guarantees_on_four_chips():
    """The four-chip deployment serves granite's graph under granite's guarantees: the
    two configuration files differ only in what names and places the deployment."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    configs = {c["name"]: c for c in bench["configs"]}
    one, four = (json.loads((ROOT / configs[name]["file"]).read_text())
                 for name in ("granite34b_layer_split", "granite34b_layer_split_4chip"))
    placed = {"name", "source", "deployment", "layout"}
    assert {k: v for k, v in one.items() if k not in placed} == {k: v for k, v in four.items() if k not in placed}
    assert four["name"] == "granite34b_layer_split_4chip" and four["layout"]["chips"] == 4
    assert configs["granite34b_layer_split_4chip"]["source"] != configs["granite34b_layer_split"]["source"]
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL]["traffic"] == cells["granite34b.req_miss"]["traffic"]


def test_the_fleet_cell_serves_on_four_cpu_devices_then_refuses(tmp_path):
    root = _checkout.make(tmp_path)
    mix = _checkout.tiny_mix(root, "req_miss", rate=20.0, check_sample=10_000)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("granite34b_layer_split_4chip", "req_miss", 4)
    cell["traffic"] = mix
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(_checkout.env(root), XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, str(root / "bench" / "run.py"), "--workload", CELL,
                           "--seed", str(2**33 + 9), "--seconds", "1", "--trace", "1"],
                          cwd=root, env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 2, proc.stderr[-3000:]
    assert proc.stdout.strip() == ""
    lines = proc.stderr.strip().splitlines()
    (served,) = [ln for ln in lines if ln.startswith("server ")]
    assert json.loads(served[len("server "):served.index("}") + 1])["count"] == 4
    checks = {ln.split()[1]: (float(ln.split()[2]), float(ln.split()[4]))
              for ln in lines if ln.startswith("check ")}
    assert list(checks) == ["placement_gap", "unexplained", "missing"]
    assert all(value <= limit for value, limit in checks.values()), checks

    server = json.loads((root / "bench_out" / CELL / "server.json").read_text())
    spans = server["spans"]
    flushes = {s["span_id"]: s for s in spans if s["name"] == "stage.solve_flush"}
    waits = {s["span_id"]: s["parent_id"] for s in spans if s["name"] == "solve.wait"}
    assert flushes and set(waits.values()) == set(flushes)
    shards: dict = {}
    for s in spans:
        if s["name"] == "solve.shard":
            shards.setdefault(waits[s["parent_id"]], []).append(s["attrs"]["shard"])
    packs = {s["parent_id"]: s["attrs"] for s in spans if s["name"] == "solve.shard_pack"}
    for sid, flush in flushes.items():
        assert sorted(shards[sid]) == [0, 1, 2, 3]
        pack = packs[sid]
        assert pack["k"] == flush["attrs"]["batch"] and pack["devices"] == 4
        assert (pack["k"] + pack["pad"]) % 4 == 0 and pack["bytes"] > 0
    ctx = {"window": (server["marks"]["start"], server["marks"]["end"]), "spans": spans}
    assert reader("shard_pack_ms.req").read(ctx) > 0
    assert 0 <= reader("shard_pad_share.req").read(ctx) < 1


# A 10 s window holding two flushes and one that starts after it.
PACKS = [
    span(1, None, "stage.solve_flush", 1.0, 0.5, batch=5),
    span(2, 1, "solve.shard_pack", 1.0, 0.002, k=5, pad=3, devices=4, bytes=8),
    span(3, None, "stage.solve_flush", 4.0, 0.5, batch=8),
    span(4, 3, "solve.shard_pack", 4.0, 0.004, k=8, pad=0, devices=4, bytes=8),
    span(5, None, "stage.solve_flush", 10.5, 0.5, batch=1),
    span(6, 5, "solve.shard_pack", 10.5, 0.1, k=1, pad=3, devices=4, bytes=8),
]


def test_pack_readers_count_the_spans_that_start_in_the_window():
    ctx = {"window": (0.0, 10.0), "window_s": 10.0, "spans": PACKS}
    assert reader("shard_pack_ms.req").read(ctx) == pytest.approx(3.0)
    assert reader("shard_pad_share.req").read(ctx) == pytest.approx(3 / 16)
    # a program without the span (one chip, or before the span existed)
    bare = dict(ctx, spans=[s for s in PACKS if s["name"] != "solve.shard_pack"])
    assert reader("shard_pack_ms.req").read(bare) is None
    assert reader("shard_pad_share.req").read(bare) is None


def four_planes(busy_ns, *, program="jit__mcop_fleet_solve(123)"):
    """A 1 s trace of four chips: plane p runs one program of ``busy_ns[p]``
    ns from 0.1 s, whose outer ``while`` op holds two body ops."""
    ops, modules = [], []
    for b in busy_ns:
        a = 100_000_000
        modules.append((program, a, a + b))
        ops += [("%while.1 = while()", a, a + b), ("%fusion.1 = fusion()", a, a + b // 2),
                ("%fusion.2 = fusion()", a + b // 2, a + b)]
    return {"ops": ops, "modules": modules, "planes": len(busy_ns)}


def test_fleet_idle_share_is_the_mean_of_each_planes_idle_share():
    busy = [500_000_000, 300_000_000, 200_000_000, 100_000_000]
    ctx = {"device": four_planes(busy), "trace_window_s": 1.0}
    per_plane = [1.0 - b * 1e-9 for b in busy]
    assert reader("fleet_idle_share.req").read(ctx) == pytest.approx(sum(per_plane) / 4)
    assert reader("fleet_idle_share.req").read(dict(ctx, device=None)) is None


def test_one_chip_idle_reader_misreads_four_planes():
    """``device_idle_share.req`` divides the union of every plane's ops by
    the plane count. Four chips busy together for 0.5 s of 1 s are each
    idle half the time, but it reads 1 - 0.5 / 4. Recorded, not mended:
    the cell does not list that reader."""
    ctx = {"device": four_planes([500_000_000] * 4), "trace_window_s": 1.0}
    assert reader("fleet_idle_share.req").read(ctx) == pytest.approx(0.5)
    assert reader("device_idle_share.req").read(ctx) == pytest.approx(0.875)


def test_fleet_roofline_counts_only_the_sharded_programs_and_true_rows():
    n, pinned = 90, 2
    peak = trace.peaks("TPU v5 lite")
    ops, nbytes = trace.solve_ops_bytes(n, pinned)
    least = max(ops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])  # one graph, one chip
    graphs = 5 + 8
    # at the peak: the 13 true graphs' least time, split over four chips
    per_chip = graphs * least / 4 * 1e9
    dev = four_planes([per_chip] * 4)
    one_chip = ("jit__mcop_batch_impl(9)", 0, 10**9)  # not a fleet program
    ctx = {"device": dict(dev, modules=dev["modules"] + [one_chip]), "device_kind": "TPU v5 lite",
           "trace_span": (0.0, 10.0), "spans": PACKS, "profile_n": n, "profile_pinned": pinned}
    roof = reader("fleet_solve_roofline")
    assert roof.read(ctx) == pytest.approx(100.0, rel=1e-6)
    assert roof.read(ctx) <= 100.0 + 1e-6
    # the padded rows take chip time and count as no work
    slower = four_planes([per_chip * 16 / 13] * 4)
    assert roof.read(dict(ctx, device=slower)) == pytest.approx(100.0 * 13 / 16, rel=1e-6)
    # a program whose sharded modules carry other names
    renamed = four_planes([per_chip] * 4, program="jit_solve(1)")
    assert roof.read(dict(ctx, device=renamed)) is None


def test_recorded_ops_nest_so_the_fleet_reads_modules():
    """On a TPU v5e trace the ``XLA Ops`` of one plane overlap: the solve's
    ``while`` ops span their bodies' ops, so summed op time overcounts. The
    program's module covers the union of its ops."""
    dev = trace.device_trace(pathlib.Path(__file__).resolve().parent / "data" / "fig12_flush.xplane.pb")
    summed = sum(b - a for _, a, b in dev["ops"]) * 1e-9
    union = trace.busy_seconds(dev["ops"])
    module = sum(b - a for _, a, b in dev["modules"]) * 1e-9
    assert summed > 2 * union
    assert union <= module <= 1.01 * union
