"""Benchmark orchestrator — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only complexity,gains,...]

Prints ``name,us_per_call,derived`` CSV.  Mapping to the paper:

    complexity      → Fig. 14 (runtime vs |V|, B&B comparator)
    gains           → Figs. 17–19 (schemes vs B and F; 3 cost models)
    optimality_gap  → beyond-paper: Theorem 1 gap quantification
    mcop_backends   → §3.1 real-time requirement (ref vs jit vs batched vs Pallas)
    pipeline        → fused env→placement pipeline vs the object path
    broker          → serving tier: multi-user tick throughput, warm restarts
    scale           → batched session engine: ticks/s and µs/user at
                      U ∈ {1k, 10k, 100k} vs the per-object baseline
                      (``REPRO_SCALE_U=1000`` for the CI smoke subset)
    faults          → fault-tolerance overhead: throughput/p99/degraded
                      fraction at injected fault rates {0%, 1%, 10%}
                      (``REPRO_FAULTS_STEPS=3`` for the CI smoke subset)
    ipc             → cross-process serving plane: req/s and p99 over a
                      unix-socket solver subprocess vs the in-process
                      broker (``REPRO_IPC_REQS=16`` for the CI smoke
                      subset)
    shard           → sharded solver fleet: µs/graph and tick throughput
                      at 1/2/4/8 simulated devices, plus compiled-vs-
                      interpret kernel rows (``REPRO_SHARD_K=64`` for the
                      CI smoke subset)
    roofline        → §Roofline table from the dry-run artifact

The mcop_backends rows are additionally appended to ``BENCH_mcop.json``,
the broker rows to ``BENCH_broker.json``, the pipeline rows to
``BENCH_pipeline.json``, the scale rows to ``BENCH_scale.json``, the
faults rows to ``BENCH_faults.json`` and the ipc rows to
``BENCH_ipc.json`` (bounded trajectories of runs), so
backend/batching/serving/resilience/transport numbers can be tracked
across commits; the broker, pipeline, scale, faults, shard and ipc
artifacts are smoke-checked after every append.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import pathlib
import re
import subprocess
import sys
import time

from repro.launch.compile_cache import use_compile_cache

from benchmarks import (
    broker,
    complexity,
    compression_ablation,
    faults,
    gains,
    ipc,
    mcop_backends,
    optimality_gap,
    pipeline,
    roofline,
    scale,
    shard,
)

MODULES = {
    "complexity": complexity,
    "gains": gains,
    "optimality_gap": optimality_gap,
    "mcop_backends": mcop_backends,
    "pipeline": pipeline,
    "broker": broker,
    "scale": scale,
    "faults": faults,
    "shard": shard,
    "ipc": ipc,
    "compression_ablation": compression_ablation,
    "roofline": roofline,
}


# anchored at the repo root so the trajectories accumulate in one place
# regardless of the invoking cwd
_REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
_TRAJECTORY_PATH = _REPO_ROOT / "BENCH_mcop.json"
_BROKER_TRAJECTORY_PATH = _REPO_ROOT / "BENCH_broker.json"
_PIPELINE_TRAJECTORY_PATH = _REPO_ROOT / "BENCH_pipeline.json"
_SCALE_TRAJECTORY_PATH = _REPO_ROOT / "BENCH_scale.json"
_FAULTS_TRAJECTORY_PATH = _REPO_ROOT / "BENCH_faults.json"
_SHARD_TRAJECTORY_PATH = _REPO_ROOT / "BENCH_shard.json"
_IPC_TRAJECTORY_PATH = _REPO_ROOT / "BENCH_ipc.json"
_TRAJECTORY_KEEP = 50  # bounded history of runs


@functools.lru_cache(maxsize=1)
def _env_metadata() -> dict:
    """Execution environment stamped onto every trajectory record.

    Makes cross-commit comparisons honest: a row timed on a different
    accelerator backend, under Pallas interpret mode, or on a different
    core count is not comparable, and the artifact now says so.
    """
    import jax  # deferred: keep artifact-only code paths import-light

    from repro.kernels.ops import default_interpret

    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=_REPO_ROOT,
            capture_output=True,
            text=True,
            timeout=10,
            check=False,
        ).stdout.strip() or None
    except (OSError, subprocess.SubprocessError):
        sha = None
    return {
        "jax_backend": jax.default_backend(),
        "pallas_interpret": bool(default_interpret()),
        "cpu_count": os.cpu_count(),
        "git_sha": sha,
    }


def _append_trajectory(
    rows: list[dict],
    path: pathlib.Path = _TRAJECTORY_PATH,
    benchmark: str = "mcop_backends",
    wall_s: float | None = None,
) -> None:
    """Append one run's rows to a bounded trajectory artifact."""
    doc = {"benchmark": benchmark, "runs": []}
    if path.exists():
        try:
            loaded = json.loads(path.read_text())
            # adopt only a well-formed doc for the SAME benchmark; a
            # foreign tag or non-dict payload starts a fresh trajectory
            # (isinstance guard also keeps JSON arrays on the corrupt path)
            if (
                isinstance(loaded, dict)
                and loaded.get("benchmark") == benchmark
                and isinstance(loaded.get("runs"), list)
            ):
                doc = loaded
        except (json.JSONDecodeError, OSError):
            pass  # corrupt artifact: start a fresh trajectory
    doc["runs"].append(
        {
            "unix_time": int(time.time()),
            "env": _env_metadata(),
            "wall_s": round(wall_s, 3) if wall_s is not None else None,
            "rows": [
                {
                    "name": r["name"],
                    "us_per_call": round(float(r["us_per_call"]), 2),
                    "derived": str(r["derived"]),
                }
                for r in rows
            ],
        }
    )
    doc["runs"] = doc["runs"][-_TRAJECTORY_KEEP:]
    path.write_text(json.dumps(doc, indent=2) + "\n")


def _smoke_check_trajectory(path: pathlib.Path, benchmark: str) -> None:
    """Fail loudly if the just-written artifact would not load warm.

    The broker trajectory is what dashboards (and the next session's
    diff) read; a malformed write must surface as a benchmark failure,
    not as a silently cold artifact later.
    """
    doc = json.loads(path.read_text())
    if doc.get("benchmark") != benchmark:
        raise RuntimeError(f"{path.name}: wrong benchmark tag {doc.get('benchmark')!r}")
    runs = doc.get("runs")
    if not isinstance(runs, list) or not runs:
        raise RuntimeError(f"{path.name}: no runs recorded")
    last = runs[-1]
    if not isinstance(last.get("rows"), list) or not last["rows"]:
        raise RuntimeError(f"{path.name}: last run has no rows")
    for row in last["rows"]:
        if not {"name", "us_per_call", "derived"} <= set(row):
            raise RuntimeError(f"{path.name}: malformed row {row!r}")
        float(row["us_per_call"])  # numeric or raise
    if benchmark == "pipeline":
        # the pricing fusion must keep reporting its series: a sweep's
        # telemetry speedup is an acceptance number, not a nice-to-have
        names = [row["name"] for row in last["rows"]]
        if not any(n.startswith("pipeline/pricing_fused") for n in names):
            raise RuntimeError(
                f"{path.name}: last run lacks a pipeline/pricing_fused_* row"
            )
    if benchmark == "scale":
        # the batched-session series is the PR-6 acceptance artifact:
        # every run must carry at least one batch row whose derived
        # column reports both throughput figures
        batch_rows = [
            row for row in last["rows"] if row["name"].startswith("scale/batch_u")
        ]
        if not batch_rows:
            raise RuntimeError(f"{path.name}: last run lacks a scale/batch_u* row")
        for row in batch_rows:
            if "ticks/s" not in row["derived"] or "us/user" not in row["derived"]:
                raise RuntimeError(
                    f"{path.name}: batch row missing throughput figures: {row!r}"
                )
    if benchmark == "faults":
        # PR-7 acceptance: all three rate rows present, and light chaos
        # (1% fault rate) holds throughput within 2x of the fault-free
        # pass — graceful degradation must not cost an order of magnitude
        by_name = {row["name"]: row for row in last["rows"]}
        req_s = {}
        for tag in ("rate0", "rate1pct", "rate10pct"):
            row = by_name.get(f"faults/{tag}")
            if row is None:
                raise RuntimeError(f"{path.name}: last run lacks a faults/{tag} row")
            m = re.search(r"req_s=(\d+(?:\.\d+)?)", row["derived"])
            if m is None:
                raise RuntimeError(
                    f"{path.name}: faults/{tag} derived lacks req_s=: {row!r}"
                )
            req_s[tag] = float(m.group(1))
        if req_s["rate1pct"] < 0.5 * req_s["rate0"]:
            raise RuntimeError(
                f"{path.name}: throughput at 1% faults "
                f"({req_s['rate1pct']:.0f} req/s) fell past 2x of fault-free "
                f"({req_s['rate0']:.0f} req/s)"
            )
    if benchmark == "shard":
        # PR-9 acceptance: the 8-device fleet must deliver ≥2x aggregate
        # solve throughput over 1 device for the 64-vertex bucket.  The
        # simulated fleet shares the host's physical cores, so the bar
        # scales with what the silicon can physically provide: ≥2x with
        # ≥4 cores, ≥1.3x with 2–3, and waived — loudly, in the artifact
        # — on single-core hosts (8 simulated devices on 1 core cannot
        # run in parallel at all).
        by_name = {row["name"]: row for row in last["rows"]}
        d_max = max(
            (int(m.group(1)) for n in by_name if (m := re.match(r"shard/solve_d(\d+)$", n))),
            default=0,
        )
        if "shard/solve_d1" not in by_name or d_max < 2:
            raise RuntimeError(
                f"{path.name}: last run lacks the shard/solve_d1 + "
                "shard/solve_dN sweep rows"
            )
        top = by_name[f"shard/solve_d{d_max}"]
        m = re.search(r"speedup_vs_1=(\d+(?:\.\d+)?)", top["derived"])
        if m is None:
            raise RuntimeError(
                f"{path.name}: shard/solve_d{d_max} derived lacks "
                f"speedup_vs_1=: {top!r}"
            )
        speedup = float(m.group(1))
        cores = (last.get("env") or {}).get("cpu_count") or os.cpu_count() or 1
        need = 2.0 if cores >= 4 else (1.3 if cores >= 2 else None)
        if need is None:
            if "gate=waived" not in top["derived"]:
                raise RuntimeError(
                    f"{path.name}: single-core run must carry an explicit "
                    f"gate=waived note: {top!r}"
                )
        elif speedup < need:
            raise RuntimeError(
                f"{path.name}: {speedup:.2f}x aggregate throughput at "
                f"{d_max} devices is below the {need:.1f}x bar "
                f"({cores} cores)"
            )
        on_tpu = (last.get("env") or {}).get("jax_backend") == "tpu"
        if on_tpu and "shard/kernel_compiled" not in by_name:
            raise RuntimeError(
                f"{path.name}: TPU run lacks the shard/kernel_compiled row"
            )
    if benchmark == "ipc":
        # ISSUE-10 acceptance: both passes present, and cross-process
        # throughput within 3x of in-process at the K=64 bucket (the
        # gate is re-checked from the artifact so a stale row can't
        # quietly pass CI)
        by_name = {row["name"]: row for row in last["rows"]}
        cross = next(
            (r for n, r in by_name.items() if n.startswith("ipc/cross_process_k")),
            None,
        )
        local = next(
            (r for n, r in by_name.items() if n.startswith("ipc/in_process_k")),
            None,
        )
        if cross is None or local is None:
            raise RuntimeError(
                f"{path.name}: last run lacks the ipc in/cross pass rows"
            )
        m = re.search(r"slowdown_vs_local=(\d+(?:\.\d+)?)x", cross["derived"])
        if m is None:
            raise RuntimeError(
                f"{path.name}: cross-process row lacks slowdown_vs_local=: "
                f"{cross!r}"
            )
        if cross["name"].endswith("_k64") and float(m.group(1)) > 3.0:
            raise RuntimeError(
                f"{path.name}: cross-process throughput fell past 3x of "
                f"in-process at K=64 ({m.group(1)}x)"
            )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", help="comma-separated subset of benchmarks")
    args = ap.parse_args(argv)
    use_compile_cache()
    names = args.only.split(",") if args.only else list(MODULES)

    print("name,us_per_call,derived")
    failures = 0
    for name in names:
        try:
            series_t0 = time.perf_counter()
            rows = list(MODULES[name].run())
            wall_s = time.perf_counter() - series_t0
            for row in rows:
                derived = str(row["derived"]).replace(",", ";")
                print(f"{row['name']},{row['us_per_call']:.2f},{derived}", flush=True)
            if name == "mcop_backends":
                _append_trajectory(rows, wall_s=wall_s)
            elif name == "broker":
                _append_trajectory(
                    rows, _BROKER_TRAJECTORY_PATH, "broker", wall_s=wall_s
                )
                _smoke_check_trajectory(_BROKER_TRAJECTORY_PATH, "broker")
                print("broker/smoke,0.00,BENCH_broker.json ok", flush=True)
            elif name == "pipeline":
                _append_trajectory(
                    rows, _PIPELINE_TRAJECTORY_PATH, "pipeline", wall_s=wall_s
                )
                _smoke_check_trajectory(_PIPELINE_TRAJECTORY_PATH, "pipeline")
                print("pipeline/smoke,0.00,BENCH_pipeline.json ok", flush=True)
            elif name == "scale":
                _append_trajectory(
                    rows, _SCALE_TRAJECTORY_PATH, "scale", wall_s=wall_s
                )
                _smoke_check_trajectory(_SCALE_TRAJECTORY_PATH, "scale")
                print("scale/smoke,0.00,BENCH_scale.json ok", flush=True)
            elif name == "faults":
                _append_trajectory(
                    rows, _FAULTS_TRAJECTORY_PATH, "faults", wall_s=wall_s
                )
                _smoke_check_trajectory(_FAULTS_TRAJECTORY_PATH, "faults")
                print("faults/smoke,0.00,BENCH_faults.json ok", flush=True)
            elif name == "shard":
                _append_trajectory(
                    rows, _SHARD_TRAJECTORY_PATH, "shard", wall_s=wall_s
                )
                _smoke_check_trajectory(_SHARD_TRAJECTORY_PATH, "shard")
                print("shard/smoke,0.00,BENCH_shard.json ok", flush=True)
            elif name == "ipc":
                _append_trajectory(
                    rows, _IPC_TRAJECTORY_PATH, "ipc", wall_s=wall_s
                )
                _smoke_check_trajectory(_IPC_TRAJECTORY_PATH, "ipc")
                print("ipc/smoke,0.00,BENCH_ipc.json ok", flush=True)
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{name}/ERROR,0.00,{e!r}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
