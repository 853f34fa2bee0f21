"""Sharded solver fleet: aggregate solve throughput vs device count.

The PR-9 tentpole number: the same K-graph, 64-vertex-bucket solve batch
is dispatched through :func:`repro.core.mcop.solve_envs` at simulated
fleet sizes D ∈ {1, 2, 4, 8}.  Each fleet size runs in a fresh
subprocess with ``XLA_FLAGS=--xla_force_host_platform_device_count=D``
exported *before* jax is imported (device count is frozen at first
import), so the parent process stays single-device and the child sees an
honest D-device mesh.  The sharded dispatcher is exercised through its
transparent path — the child passes ``mesh=None`` and the broker/solve
plane auto-detects the fleet — which is exactly what production code
does.

Per fleet size the child reports:

* ``shard/solve_dD``  — µs per graph for one ``solve_envs`` dispatch of
  the K-graph bucket (best of ``REPS`` steady-state calls), plus
  aggregate graphs/s;
* ``shard/tick_dD``   — broker tick throughput with a K-session batch
  group forced to re-solve every tick (threshold 0, churning traffic).

The d8 solve row carries ``speedup_vs_1=…`` — aggregate throughput at 8
devices over 1 — and a gate note.  ``benchmarks/run.py`` smoke-checks
it: ≥2× on hosts with ≥4 cores, ≥1.3× with ≥2 cores, and waived (with
an explicit note in the artifact) on single-core hosts where 8 simulated
devices share one physical core and no parallel speedup is physically
available.

Two kernel rows compare the compiled and interpret Pallas tiers on a
tiny batch: ``shard/kernel_interpret`` times the blocked
``mcop_stoer_wagner_kernel`` under ``interpret=True``;
``shard/kernel_compiled`` times ``interpret=False`` and is emitted only
when this process runs on a TPU, where a compiler refusal raises.  The
fleet children always run on virtual CPU devices (``JAX_PLATFORMS=cpu``
is exported for them), so they never contend with this process for a
chip.

``REPRO_SHARD_K`` shrinks the solve batch (CI smoke);
``REPRO_SHARD_DEVICES`` (comma-separated) restricts the fleet sweep.

Rows are appended to ``BENCH_shard.json`` by ``benchmarks/run.py`` and
smoke-checked after each append.
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import time

DEVICE_COUNTS = (1, 2, 4, 8)
DEFAULT_K = 512          # graphs per solve dispatch (the K=64-bucket batch)
N_VERTICES = 40          # pads to the 64-vertex bucket (DEFAULT_BUCKETS)
REPS = 3                 # steady-state solve repetitions (best-of)
TICK_WARMUP = 1
TICK_STEPS = 3
KERNEL_B = 4             # tiny batch for the interpret-tier kernel row
KERNEL_N = 16

_HERE = pathlib.Path(__file__).resolve()
_RESULT_TAG = "SHARD_RESULT "


def _shard_k() -> int:
    return max(8, int(os.environ.get("REPRO_SHARD_K", DEFAULT_K)))


def _device_counts() -> tuple[int, ...]:
    raw = os.environ.get("REPRO_SHARD_DEVICES")
    if not raw:
        return DEVICE_COUNTS
    return tuple(sorted({int(tok) for tok in raw.split(",") if tok.strip()}))


# ----------------------------------------------------------------------
# Child: one fleet size, measured behind a forced host device count
# ----------------------------------------------------------------------


def _worker(devices_requested: int) -> None:
    """Runs in a subprocess with XLA_FLAGS already exported."""
    import jax
    import numpy as np

    from repro.core import AppProfile, ResponseTimeModel, linear_graph
    from repro.core.cost_models import EnvArrays
    from repro.core.mcop import solve_envs
    from repro.service import OffloadBroker, TrafficGenerator

    assert jax.device_count() == devices_requested, (
        jax.device_count(),
        devices_requested,
    )
    k = _shard_k()
    rng = np.random.default_rng(11)
    profile = AppProfile.from_wcg_times(linear_graph(N_VERTICES, rng=rng))
    model = ResponseTimeModel()
    envs = EnvArrays(*(rng.uniform(0.5, 5.0, k) for _ in range(6)))

    # mesh=None everywhere: the transparent auto-detect path is the
    # production path, and it is what this benchmark certifies.
    solve_envs(profile, model, envs, backend="jax")  # compile + warm
    solve_s = min(
        _timed(lambda: solve_envs(profile, model, envs, backend="jax"))
        for _ in range(REPS)
    )

    broker = OffloadBroker(backend="jax")
    broker.register("app", profile, model)
    group = broker.register_batch("app", k, threshold=0.0, min_interval=1)
    gen = TrafficGenerator(
        k, seed=7, arrival_rate=max(1.0, 0.02 * k), churn=0.02, initial=k
    )
    ticks = [gen.step() for _ in range(TICK_WARMUP + TICK_STEPS)]
    for tk in ticks[:TICK_WARMUP]:
        group.observe(tk.envs, arrived=tk.arrived, departed=tk.departed)
        broker.tick()
    t0 = time.perf_counter()
    for tk in ticks[TICK_WARMUP:]:
        group.observe(tk.envs, arrived=tk.arrived, departed=tk.departed)
        broker.tick()
    tick_s = time.perf_counter() - t0

    print(
        _RESULT_TAG
        + json.dumps(
            {
                "devices": jax.device_count(),
                "k": k,
                "solve_s": solve_s,
                "tick_steps": TICK_STEPS,
                "tick_s": tick_s,
            }
        ),
        flush=True,
    )


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _run_child(devices: int) -> dict:
    # the simulated fleet is virtual CPU devices by design
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices} "
        + env.get("XLA_FLAGS", "")
    ).strip()
    src = str(_HERE.parent.parent / "src")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (src, env.get("PYTHONPATH", "")) if p
    )
    proc = subprocess.run(
        [sys.executable, str(_HERE), "--worker", str(devices)],
        env=env,
        capture_output=True,
        text=True,
        timeout=900,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"shard worker d{devices} failed "
            f"(rc={proc.returncode}): {proc.stderr.strip()[-800:]}"
        )
    for line in proc.stdout.splitlines():
        if line.startswith(_RESULT_TAG):
            return json.loads(line[len(_RESULT_TAG) :])
    raise RuntimeError(f"shard worker d{devices} emitted no result line")


# ----------------------------------------------------------------------
# Parent: the sweep + compiled-vs-interpret kernel rows
# ----------------------------------------------------------------------


def _speedup_gate_note(speedup: float) -> str:
    cores = os.cpu_count() or 1
    if cores >= 4:
        need = 2.0
    elif cores >= 2:
        need = 1.3
    else:
        return (
            f"gate=waived(single-core host: {cores} cpu for 8 simulated "
            "devices; no parallel speedup physically available)"
        )
    status = "met" if speedup >= need else "FAILED"
    return f"gate={status}(need {need:.1f}x at {cores} cores)"


def _fleet_rows() -> list[dict]:
    results = {d: _run_child(d) for d in _device_counts()}
    rows: list[dict] = []
    base = results.get(1)
    for d, r in sorted(results.items()):
        us_graph = r["solve_s"] / r["k"] * 1e6
        graphs_s = r["k"] / r["solve_s"]
        derived = f"graphs_s={graphs_s:.0f}; k={r['k']}; bucket=64"
        if base is not None and d == max(results):
            speedup = (base["solve_s"] / r["solve_s"]) if r["solve_s"] else 0.0
            derived += f"; speedup_vs_1={speedup:.2f}; {_speedup_gate_note(speedup)}"
        rows.append(
            {"name": f"shard/solve_d{d}", "us_per_call": us_graph, "derived": derived}
        )
        ticks_s = r["tick_steps"] / r["tick_s"] if r["tick_s"] else 0.0
        rows.append(
            {
                "name": f"shard/tick_d{d}",
                "us_per_call": r["tick_s"] / (r["tick_steps"] * r["k"]) * 1e6,
                "derived": f"{ticks_s:.2f} ticks/s; sessions={r['k']}",
            }
        )
    return rows


def _kernel_rows() -> list[dict]:
    import jax
    import numpy as np

    from repro.kernels.mcop_phase import (
        default_block_graphs,
        mcop_stoer_wagner_kernel,
    )

    b, n = KERNEL_B, KERNEL_N
    rng = np.random.default_rng(3)
    adj = rng.uniform(0.1, 1.0, (b, n, n)).astype(np.float32)
    adj = adj + adj.transpose(0, 2, 1)
    adj[:, np.arange(n), np.arange(n)] = 0.0
    wl = rng.uniform(1.0, 2.0, (b, n)).astype(np.float32)
    wc = rng.uniform(0.1, 1.0, (b, n)).astype(np.float32)
    pin = np.zeros((b, n), dtype=bool)
    pin[:, 0] = True

    rows = []
    cuts, _ = mcop_stoer_wagner_kernel(adj, wl, wc, pin, interpret=True)
    cuts.block_until_ready()  # compile + warm
    dt = _timed(
        lambda: mcop_stoer_wagner_kernel(adj, wl, wc, pin, interpret=True)[
            0
        ].block_until_ready()
    )
    rows.append(
        {
            "name": "shard/kernel_interpret",
            "us_per_call": dt / b * 1e6,
            "derived": f"interpret=True; b={b} n={n}; block_graphs=1",
        }
    )
    if jax.devices()[0].platform != "tpu":
        return rows  # only a TPU compiles the kernel
    g = default_block_graphs(n, False)
    cuts, _ = mcop_stoer_wagner_kernel(adj, wl, wc, pin, interpret=False)
    cuts.block_until_ready()
    dt = _timed(
        lambda: mcop_stoer_wagner_kernel(adj, wl, wc, pin, interpret=False)[
            0
        ].block_until_ready()
    )
    rows.append(
        {
            "name": "shard/kernel_compiled",
            "us_per_call": dt / b * 1e6,
            "derived": f"interpret=False; b={b} n={n}; block_graphs={g}",
        }
    )
    return rows


def run() -> list[dict]:
    return _fleet_rows() + _kernel_rows()


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--worker":
        _worker(int(sys.argv[2]))
    else:
        for row in run():
            print(f"{row['name']},{row['us_per_call']:.2f},{row['derived']}")
