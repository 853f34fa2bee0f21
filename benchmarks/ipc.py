"""Cross-process serving overhead: wire-protocol broker vs in-process.

Drives the SAME request stream twice — once against an in-process
``OffloadBroker`` and once through a ``BrokerClient`` talking to a real
solver subprocess (``examples/serve_broker.py``) over a unix socket —
and reports req/s and p99 per-request latency for each.  The delta is
what the serving plane *costs*: framing, journaling, the snapshot loop
and a socket round-trip per submit+tick.

The workload is solve-dominated on purpose: distinct environments over a
``REPRO_IPC_K``-vertex WCG (default 64, the shard benchmark's bucket),
so the wire overhead is amortised against real min-cut work rather than
measured against a no-op.  Both passes use the reference backend — no
jit compiles land inside either timed loop, and replies are asserted
bit-identical across the wire before any number is reported.

Rows are appended to ``BENCH_ipc.json`` by ``benchmarks/run.py`` and
smoke-checked: cross-process throughput must stay within 3x of
in-process at K=64.  ``REPRO_IPC_REQS`` trims the stream for the CI
smoke run.
"""

from __future__ import annotations

import os
import pathlib
import subprocess
import sys
import time

import numpy as np

from repro.core import AppProfile, ResponseTimeModel, random_wcg
from repro.service import BrokerClient, OffloadBroker, unix_address
from repro.service.workload import environment_trace

_REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
_SERVER = _REPO_ROOT / "examples" / "serve_broker.py"
_READY_TIMEOUT_S = 60.0

GATE_RATIO = 3.0  # cross-process must stay within 3x of in-process


def _profile(k: int) -> AppProfile:
    # mirrors examples/serve_broker.py demo_tenant: both processes build
    # the tenant independently from the same seed
    return AppProfile.from_wcg_times(
        random_wcg(k, rng=np.random.default_rng(0))
    )


def _start_server(tmp: pathlib.Path, k: int) -> subprocess.Popen:
    cmd = [
        sys.executable, str(_SERVER),
        "--socket", str(tmp / "solver.sock"),
        "--journal", str(tmp / "journal.jsonl"),
        "--snapshot-dir", str(tmp / "snaps"),
        "--nodes", str(k), "--seed", "0",
        "--backend", "reference",
    ]
    env = dict(os.environ, PYTHONPATH=str(_REPO_ROOT / "src"))
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True, env=env)
    deadline = time.monotonic() + _READY_TIMEOUT_S
    for line in proc.stdout:
        if line.startswith("READY"):
            return proc
        if time.monotonic() > deadline:
            break
    proc.kill()
    raise RuntimeError("solver subprocess never became READY")


def _sig(reply) -> tuple:
    res = reply.result
    return (
        None if res is None else (
            float(res.min_cut),
            np.asarray(res.local_mask, bool).tobytes(),
        ),
        reply.cache_hit,
        reply.tick,
    )


def _measure(submit, tick, envs) -> dict:
    """submit+tick per request; per-request wall latency and signatures."""
    lat_s: list[float] = []
    sigs: list[tuple] = []
    t0 = time.perf_counter()
    for env in envs:
        r0 = time.perf_counter()
        fut = submit("app", env)
        tick()
        assert fut.done, "request unresolved after its tick"
        lat_s.append(time.perf_counter() - r0)
        sigs.append(_sig(fut.result))
    elapsed = time.perf_counter() - t0
    return {
        "elapsed": elapsed,
        "req_s": len(envs) / max(elapsed, 1e-12),
        "p99_ms": float(np.percentile(lat_s, 99)) * 1e3,
        "sigs": sigs,
    }


def run() -> list[dict]:
    k = int(os.environ.get("REPRO_IPC_K", "64"))
    n_reqs = int(os.environ.get("REPRO_IPC_REQS", "48"))
    profile = _profile(k)
    envs = environment_trace(n_reqs, seed=13)

    # --- cross-process over a unix socket --------------------------------
    # The solver child runs first and alone: this process touches no
    # device until the child has exited (one process per chip).
    import tempfile

    with tempfile.TemporaryDirectory(prefix="bench_ipc_") as tmp_s:
        tmp = pathlib.Path(tmp_s)
        proc = _start_server(tmp, k)
        try:
            client = BrokerClient(
                unix_address(tmp / "solver.sock"),
                tenants={"app": (profile, ResponseTimeModel())},
                client="bench",
            )
            client.connect()
            remote = _measure(client.submit, client.tick, envs)
            stream = client._stream
            wire_bytes = (
                (stream.bytes_in + stream.bytes_out) if stream else 0
            )
            client.close()
        finally:
            proc.kill()
            proc.wait()

    # --- in-process baseline ---------------------------------------------
    broker = OffloadBroker(backend="reference", clock=lambda: 0.0)
    broker.register("app", profile, ResponseTimeModel())
    local = _measure(broker.submit, broker.tick, envs)

    # replies across the wire must be the in-process replies, bit for bit
    if remote["sigs"] != local["sigs"]:
        raise RuntimeError("cross-process replies diverged from in-process")

    ratio = local["req_s"] / max(remote["req_s"], 1e-12)
    rows = [
        {
            "name": f"ipc/in_process_k{k}",
            "us_per_call": local["elapsed"] / n_reqs * 1e6,
            "derived": (
                f"req_s={local['req_s']:.0f}; p99_ms={local['p99_ms']:.2f};"
                f" reqs={n_reqs}"
            ),
        },
        {
            "name": f"ipc/cross_process_k{k}",
            "us_per_call": remote["elapsed"] / n_reqs * 1e6,
            "derived": (
                f"req_s={remote['req_s']:.0f}; p99_ms={remote['p99_ms']:.2f};"
                f" reqs={n_reqs}; slowdown_vs_local={ratio:.2f}x;"
                f" wire_bytes={wire_bytes}"
            ),
        },
    ]

    # acceptance: the wire must not cost an order of magnitude at the
    # 64-vertex bucket (the gate benchmarks/run.py re-checks from the
    # artifact)
    if k == 64 and ratio > GATE_RATIO:
        raise RuntimeError(
            f"cross-process throughput fell past {GATE_RATIO:.0f}x of "
            f"in-process: {remote['req_s']:.0f} vs {local['req_s']:.0f} req/s"
        )
    return rows
