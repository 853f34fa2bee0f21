"""Run a solver process: the cross-process serving plane's server half.

Starts a :class:`~repro.service.server.SolverServer` owning an
:class:`~repro.service.broker.OffloadBroker` with one deterministic demo
tenant (a seeded random WCG — any client building the same
``--nodes``/``--seed`` profile gets bit-identical placements), a
write-ahead request journal, and a background snapshot loop.  On start
it warm-restarts from whatever journal/snapshots the directory already
holds, so SIGKILL + rerun resumes where the dead process stopped —
the crash-recovery integration test and the CI cross-process smoke both
drive exactly this entrypoint.

    PYTHONPATH=src python examples/serve_broker.py --socket /tmp/mcop.sock \
        --journal /tmp/mcop/journal.jsonl --snapshot-dir /tmp/mcop/snaps

then, from any number of other processes:

    from repro.service import BrokerClient, BrokerSession, unix_address
    client = BrokerClient(unix_address("/tmp/mcop.sock"),
                          tenants={"app": demo_tenant(12, 0)}).connect()
    session = BrokerSession(client, "app")   # the unmodified session class
    session.observe(env); client.tick(); print(session.drain())

``--kill-at-tick N`` is a crash-test hook: the process SIGKILLs *itself*
mid-tick — after the broker state mutates, before the journal tick
append — the exact torn write the warm-restart path must absorb.
"""

import argparse
import json
import os
import signal
import sys

import jax
import numpy as np

from repro.core import AppProfile, ResponseTimeModel, random_wcg
from repro.kernels.ops import default_interpret
from repro.launch.compile_cache import use_compile_cache
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import Tracer
from repro.service import OffloadBroker, SolverServer, tcp_address, unix_address


def demo_tenant(nodes: int, seed: int):
    """The (profile, cost_model) pair both sides build independently —
    seeded, so server and clients agree without shipping the graph."""
    profile = AppProfile.from_wcg_times(
        random_wcg(nodes, rng=np.random.default_rng(seed))
    )
    return profile, ResponseTimeModel()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--socket", help="unix socket path")
    ap.add_argument("--tcp", help="host:port (port 0 = ephemeral)")
    ap.add_argument("--journal", help="write-ahead journal path (JSONL)")
    ap.add_argument("--snapshot-dir", help="placement-cache snapshot dir")
    ap.add_argument("--snapshot-every", type=int, default=8,
                    help="snapshot cadence in ticks")
    ap.add_argument("--tenant", default="app")
    ap.add_argument("--nodes", type=int, default=12, help="demo WCG size")
    ap.add_argument("--seed", type=int, default=0, help="demo WCG seed")
    ap.add_argument("--backend", default="jax",
                    choices=("reference", "jax", "pallas"),
                    help="solver for every flush (reference = numpy oracle)")
    ap.add_argument("--batch-capacity", type=int, default=0,
                    help="also expose a batch session group of this size")
    ap.add_argument("--max-ticks", type=int, default=None,
                    help="exit after serving this many ticks")
    ap.add_argument("--trace", help="export a chrome trace here on exit")
    ap.add_argument("--trace-jsonl",
                    help="export a tracequery-readable JSONL trace on exit")
    ap.add_argument("--kill-at-tick", type=int, default=None,
                    help="crash hook: SIGKILL self mid-tick N")
    args = ap.parse_args(argv)
    use_compile_cache()

    if bool(args.socket) == bool(args.tcp):
        ap.error("exactly one of --socket / --tcp is required")
    if args.socket:
        address = unix_address(args.socket)
    else:
        host, _, port = args.tcp.partition(":")
        address = tcp_address(host or "127.0.0.1", int(port or 0))

    tracer = Tracer() if (args.trace or args.trace_jsonl) else None
    broker = OffloadBroker(backend=args.backend, clock=lambda: 0.0, tracer=tracer)
    profile, cost_model = demo_tenant(args.nodes, args.seed)
    broker.register(args.tenant, profile, cost_model)

    if args.kill_at_tick is not None:
        real_tick = broker.tick

        def tick_then_die(**kw):
            report = real_tick(**kw)
            if report.tick >= args.kill_at_tick:
                os.kill(os.getpid(), signal.SIGKILL)  # torn mid-tick crash
            return report

        broker.tick = tick_then_die

    server = SolverServer(
        broker,
        address=address,
        journal_path=args.journal,
        snapshot_dir=args.snapshot_dir,
        snapshot_every_ticks=args.snapshot_every,
        tracer=tracer,
        metrics=MetricsRegistry(),
    )
    recovered = server.recover()
    bound = server.bind()
    if args.batch_capacity > 0:
        broker.register_batch(args.tenant, args.batch_capacity)
    # READY is the startup barrier the tests/CI wait on; the address
    # matters for --tcp with an ephemeral port.  DEVICE names what the
    # solves run on, so a caller can refuse a host fallback.
    device = jax.devices()[0]
    facts = {
        "platform": device.platform,
        "kind": device.device_kind,
        "count": jax.device_count(),
        "interpret": default_interpret(),
    }
    print(f"RECOVERED {recovered}", flush=True)
    print(f"DEVICE {json.dumps(facts)}", flush=True)
    print(f"READY {' '.join(str(p) for p in bound)}", flush=True)
    try:
        server.serve_forever(max_ticks=args.max_ticks)
    except KeyboardInterrupt:
        server.close()
    if args.trace and tracer is not None:
        tracer.export_chrome(args.trace)
    if args.trace_jsonl and tracer is not None:
        tracer.export_jsonl(args.trace_jsonl)
    return 0


if __name__ == "__main__":
    sys.exit(main())
