#!/usr/bin/env python
"""Cross-process smoke: one solver, N client processes, U batched users.

    PYTHONPATH=src python tools/ipc_smoke.py --users 1000 --clients 2 \
        --ticks 6 --dir /tmp/ipc_smoke

Boots ``examples/serve_broker.py`` on a unix socket, then spawns
``--clients`` REAL client processes (this script re-executed with
``--worker``), each registering a server-side
:class:`~repro.service.session.BatchSessionGroup` of ``U/N`` slots and
driving it with seeded :class:`~repro.service.workload.TrafficGenerator`
churn for ``--ticks`` ticks.  Every worker must see a ``batch_report``
for every tick it staged, and the solver must survive interleaved ticks
from concurrent clients.  On success the server is shut down gracefully
(SIGINT) so it exports its trace — the CI job feeds the JSONL to
``tools/tracequery.py --audit`` and uploads both trace files.

Exit status is the CI contract: 0 only if the server came up, every
worker resolved every staged tick, and the trace files exist.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import signal
import subprocess
import sys
import time

READY_TIMEOUT_S = 120.0


# ----------------------------------------------------------------------
# worker: one client process driving U/N batched users
# ----------------------------------------------------------------------

def worker(args) -> int:
    import numpy as np  # deferred: the coordinator stays stdlib-only

    from repro.core import AppProfile, ResponseTimeModel, random_wcg
    from repro.service import BrokerClient, unix_address
    from repro.service.workload import TrafficGenerator

    profile = AppProfile.from_wcg_times(
        random_wcg(args.nodes, rng=np.random.default_rng(args.seed))
    )
    client = BrokerClient(
        unix_address(args.socket),
        tenants={args.tenant: (profile, ResponseTimeModel())},
        client=args.name,
    )
    client.connect()
    group = client.register_batch(args.tenant, args.users)
    gen = TrafficGenerator(args.users, seed=args.traffic_seed)

    reports = []
    for _ in range(args.ticks):
        t = gen.step()
        group.observe(
            t.envs,
            arrived=np.nonzero(t.arrived)[0],
            departed=np.nonzero(t.departed)[0],
        )
        client.tick()
        reports.extend(group.drain())
    # a concurrent client's tick may resolve our stage before our own
    # tick frame lands, but every staged tick must report exactly once
    for _ in range(4):
        if len(reports) >= args.ticks:
            break
        client.tick()
        reports.extend(group.drain())
    client.close()

    if len(reports) != args.ticks:
        print(
            f"WORKER {args.name} FAIL: {len(reports)} reports for "
            f"{args.ticks} staged ticks",
            file=sys.stderr,
        )
        return 1
    solved = sum(r["solved"] for r in reports)
    active = reports[-1]["active"]
    print(
        f"WORKER {args.name} ok users={args.users} ticks={args.ticks} "
        f"solved={solved} active_last={active}",
        flush=True,
    )
    return 0


# ----------------------------------------------------------------------
# coordinator: server subprocess + N worker subprocesses
# ----------------------------------------------------------------------

def coordinator(args) -> int:
    repo = pathlib.Path(__file__).resolve().parent.parent
    out = pathlib.Path(args.dir)
    out.mkdir(parents=True, exist_ok=True)
    sock = out / "solver.sock"
    trace_chrome = out / "ipc_trace.json"
    trace_jsonl = out / "ipc_trace.jsonl"
    env = dict(os.environ, PYTHONPATH=str(repo / "src"))

    server = subprocess.Popen(
        [
            sys.executable, str(repo / "examples" / "serve_broker.py"),
            "--socket", str(sock),
            "--journal", str(out / "journal.jsonl"),
            "--snapshot-dir", str(out / "snaps"),
            "--nodes", str(args.nodes), "--seed", str(args.seed),
            "--tenant", args.tenant,
            "--trace", str(trace_chrome),
            "--trace-jsonl", str(trace_jsonl),
        ],
        stdout=subprocess.PIPE, text=True, env=env,
    )
    try:
        deadline = time.monotonic() + READY_TIMEOUT_S
        for line in server.stdout:
            print(line, end="", flush=True)
            if line.startswith("READY"):
                break
            if time.monotonic() > deadline:
                raise RuntimeError("server never became READY")
        else:
            raise RuntimeError("server exited before READY")

        per_client = args.users // args.clients
        # clients do host work only; the server is the one device owner
        worker_env = dict(env, JAX_PLATFORMS="cpu")
        workers = [
            subprocess.Popen(
                [
                    sys.executable, str(pathlib.Path(__file__).resolve()),
                    "--worker",
                    "--socket", str(sock),
                    "--users", str(per_client),
                    "--ticks", str(args.ticks),
                    "--nodes", str(args.nodes), "--seed", str(args.seed),
                    "--tenant", args.tenant,
                    "--name", f"smoke{i}",
                    "--traffic-seed", str(100 + i),
                ],
                env=worker_env,
            )
            for i in range(args.clients)
        ]
        codes = [w.wait(timeout=READY_TIMEOUT_S) for w in workers]
        if any(codes):
            print(f"SMOKE FAIL: worker exit codes {codes}", file=sys.stderr)
            return 1

        # graceful shutdown so the tracer exports
        server.send_signal(signal.SIGINT)
        server.wait(timeout=READY_TIMEOUT_S)
    finally:
        if server.poll() is None:
            server.kill()
            server.wait()

    for path in (trace_chrome, trace_jsonl):
        if not path.exists() or not path.stat().st_size:
            print(f"SMOKE FAIL: missing trace {path}", file=sys.stderr)
            return 1
    spans = sum(
        1 for line in trace_jsonl.read_text().splitlines()
        if line.strip() and json.loads(line).get("type") == "span"
    )
    print(
        f"SMOKE ok clients={args.clients} users={args.users} "
        f"ticks={args.ticks} trace_spans={spans}",
        flush=True,
    )
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--worker", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--socket", help="unix socket (worker mode)")
    ap.add_argument("--dir", default="ipc_smoke_out",
                    help="scratch/artifact directory (coordinator mode)")
    ap.add_argument("--users", type=int, default=1000,
                    help="total batched users across all clients")
    ap.add_argument("--clients", type=int, default=2)
    ap.add_argument("--ticks", type=int, default=6)
    ap.add_argument("--nodes", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tenant", default="app")
    ap.add_argument("--name", default="smoke")
    ap.add_argument("--traffic-seed", type=int, default=100)
    args = ap.parse_args(argv)
    if args.worker:
        if not args.socket:
            ap.error("--worker requires --socket")
        return worker(args)
    return coordinator(args)


if __name__ == "__main__":
    sys.exit(main())
