"""A throwaway checkout for driving the benchmark on the host CPU.

It holds a copy of ``bench/`` and ``BENCHMARK.json``, the program's
``src`` linked in, and whatever extra cells, configurations, mixes,
metrics and limits a test drops in as files of their own.  Runs use a
compile cache inside it, and tiny mixes, so that a test run can hold them.
"""

from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parents[2]


def make(tmp: pathlib.Path) -> pathlib.Path:
    root = tmp / "checkout"
    shutil.copytree(REPO / "bench", root / "bench", ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    (root / "src").symlink_to(REPO / "src")
    return root


def tiny_mix(root: pathlib.Path, traffic: str, **over) -> str:
    """A copy of mix ``traffic`` cut to a test's size, as its own file."""
    mix = json.loads((root / "bench" / "traffic" / f"{traffic}.json").read_text())
    if mix["loop"] == "open":
        mix.update(warmup_s=0.5, drain_s=8.0, check_sample=50)
    else:
        mix.update(warmup_ticks=2, connections=2)
        mix["params"] = dict(mix["params"], capacity=300, initial=270, arrival_rate=14.0)
    if mix.get("budget"):
        mix["budget"] = 2  # the warm ladder follows the budget
    else:
        mix["warm"] = dict(mix["warm"], batches=[1, 2])
    mix.update(over)
    name = f"tiny_{traffic}"
    (root / "bench" / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    return name


def add_cell(root: pathlib.Path, name: str, config: str, traffic: str, like: str) -> None:
    """A new cell: an entry in BENCHMARK.json, listed wherever ``like`` is,
    with ``like``'s limits as its own file."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": name, "config": config, "traffic": traffic, "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if like in m.get("workloads", ()):
            m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    shutil.copy(root / "bench" / "limits" / f"{like}.json", root / "bench" / "limits" / f"{name}.json")


def env(root: pathlib.Path) -> dict:
    out = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(root / ".jax_cache"))
    out.pop("PYTHONPATH", None)
    return out


def run_cli(root: pathlib.Path, *args: str, timeout: float = 240) -> subprocess.CompletedProcess:
    return subprocess.run([sys.executable, str(root / "bench" / "run.py"), *args], cwd=root,
                          env=env(root), capture_output=True, text=True, timeout=timeout)


def run_cell(root: pathlib.Path, workload: str, seed: int, seconds: float, trace: bool = False,
             patch: str | None = None, timeout: float = 240) -> dict:
    """``bench.run.run_cell`` in a fresh process of the checkout: it
    drives the whole run on the CPU, with the timed path patched as given."""
    code = (
        "import json, sys, os; os.environ['JAX_PLATFORMS'] = 'cpu'; "
        f"sys.path[:0] = [{str(root / 'src')!r}, {str(root)!r}]; "
        "import bench.run as r; "
        f"print(json.dumps(r.run_cell({workload!r}, {seed}, {seconds}, {trace}, patch={patch!r}), default=str))"
    )
    proc = subprocess.run([sys.executable, "-c", code], cwd=root, env=env(root),
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])
