"""The harness end to end on the host CPU, in a throwaway checkout.

* No chip: ``bench/run.py`` drives the whole path (server, wire,
  generator, reference check), names the CPU device and the numbers it
  compared on standard error, prints no result and exits 2.
* Discovery: a configuration, a traffic mix, a cell's limits and a
  per-layer metric, each dropped in as a file of its own, are found by
  name with no edit to any file the benchmark has.
* Only the benchmark's files: with ``src`` missing the run fails with no
  result.
* A mix with a tick budget warms every batch size up to it, and may not
  set a warm-up ladder of its own.
"""

import json
import pathlib
import shutil
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import _checkout  # noqa: E402


def test_no_chip_runs_the_whole_path_then_refuses(tmp_path):
    root = _checkout.make(tmp_path)
    mix = _checkout.tiny_mix(root, "req_reuse", rate=60.0)
    _checkout.add_cell(root, "fig12.tiny", "fig12_face_recognition", mix, like="fig12.req_reuse")
    proc = _checkout.run_cli(root, "--workload", "fig12.tiny", "--seed", str(2**33 + 5),
                             "--seconds", "1", "--trace", "0")
    assert proc.returncode == 2, proc.stderr[-3000:]
    assert proc.stdout.strip() == ""
    lines = proc.stderr.strip().splitlines()
    assert '"platform": "cpu"' in lines[-1]
    checks = [ln for ln in lines if ln.startswith("check ")]
    assert [c.split()[1] for c in checks] == ["placement_gap", "unexplained", "missing"]
    assert float(checks[0].split()[2]) < float(checks[0].split()[4])


def test_new_files_are_found_by_name(tmp_path):
    root = _checkout.make(tmp_path)
    config = json.loads((root / "bench" / "configs" / "fig12_face_recognition.json").read_text())
    config["name"] = "fig12_copy"
    (root / "bench" / "configs" / "fig12_copy.json").write_text(json.dumps(config))
    mix = _checkout.tiny_mix(root, "req_reuse", rate=60.0)
    (root / "bench" / "metrics" / "ticks_seen.tmp.py").write_text(
        "def read(ctx):\n    return len(ctx['ticks'])\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "fig12_copy", "source": "test", "file": "bench/configs/fig12_copy.json",
                             "reduced": [], "why": "test"})
    bench["per_layer"].append({"name": "ticks_seen.tmp", "unit": "count", "better": "higher",
                               "source": "program_counter", "layer": "broker tick",
                               "moves": "placement_p50_ms", "workloads": []})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    _checkout.add_cell(root, "copy.tiny", "fig12_copy", mix, like="fig12.req_reuse")
    # listed by the new cell only: add_cell copied fig12.req_reuse's lists
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"][-1]["workloads"] = ["copy.tiny"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    result = _checkout.run_cell(root, "copy.tiny", 11, 1.0, trace=True)
    assert result["correct"], result["checks"]
    assert result["metrics"]["ticks_seen.tmp"]["value"] > 0
    assert "wire_self_share.req" in result["metrics"]
    assert result["device"]["platform"] == "cpu"
    assert "busy_s" not in result["device"]  # no device metric from a CPU run
    assert list(result)[-1] == "checks"


def test_only_the_benchmark_files_fail(tmp_path):
    root = _checkout.make(tmp_path)
    (root / "src").unlink()
    shutil.rmtree(root / "bench" / "configs")
    proc = _checkout.run_cli(root, "--workload", "fig12.req_reuse", "--seed", "1", "--seconds", "1",
                             timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_budgeted_mix_may_not_set_its_own_ladder(tmp_path):
    root = _checkout.make(tmp_path)
    mix = _checkout.tiny_mix(root, "req_miss", rate=20.0)
    path = root / "bench" / "traffic" / f"{mix}.json"
    spec = json.loads(path.read_text())
    spec["warm"]["batches"] = [1, 1]
    path.write_text(json.dumps(spec))
    _checkout.add_cell(root, "granite.ladder", "granite34b_layer_split", mix, like="granite34b.req_miss")
    proc = _checkout.run_cli(root, "--workload", "granite.ladder", "--seed", "1", "--seconds", "1",
                             timeout=120)
    assert proc.returncode == 1
    assert proc.stdout.strip() == ""
    assert "warms 1..budget" in proc.stderr
