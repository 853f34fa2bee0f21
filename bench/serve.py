"""The benchmark's solver process: the only process that touches the chip.

    python bench/serve.py <spec.json>

``bench/run.py`` writes the spec (configuration file, the warm-up ladder,
the output directory, whether to trace) and starts this launcher.  It
builds the configuration's tenant profile, an ``OffloadBroker`` with the
program's defaults (no backend, no mesh) and a ``SolverServer`` with the
configuration's guarantees (journal on, snapshot cadence, fsync), warms
every solve shape the cell's window can use through the program's own
entries on throwaway environments (the placement cache is untouched),
then prints ``DEVICE {...}`` and ``READY <port>`` and serves.

Commands on standard input, one per line: ``start`` and ``end`` mark the
measured window, ``trace_start`` and ``trace_end`` start and stop the
device trace when tracing, ``stop`` ends serving.  On exit it
writes ``server.json`` to the output directory: device facts, the peak
device memory, the window's timestamps on the tracer's clock, the
program preparations (compiles or compile-cache loads) with their times,
and, when tracing, the spans.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import sys
import threading
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src")]

LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def tenant_profile(config: dict):
    """The configuration's profile, built by ``bench/profiles/<kind>.py``."""
    from repro.core import AppProfile

    kind = config["profile"]["kind"]
    p = load_module(ROOT / "bench" / "profiles" / f"{kind}.py", f"bench_profile_{kind}").build(config["profile"])
    return AppProfile(p["t_local"], p["data_in"], p["data_out"], p["offloadable"], p["names"])


def cost_model(name: str):
    from repro.core import ResponseTimeModel

    return {"response_time": ResponseTimeModel}[name]()


def throwaway_envs(k: int, rng):
    from repro.core.cost_models import EnvArrays

    return EnvArrays(
        rng.uniform(0.5, 20.0, k), rng.uniform(0.5, 20.0, k), rng.uniform(1.5, 12.0, k),
        *(rng.uniform(0.1, 2.0, k) for _ in range(3)),
    )


def warm(broker, profile, model, warm_spec: dict) -> int:
    """Solve throwaway batches of every size the window can flush."""
    import numpy as np

    from repro.core.mcop import mcop_batch, solve_envs

    mesh = broker.mesh if broker.mesh is not None else False
    bucket = min(b for b in broker.buckets if b >= profile.n)
    rng = np.random.default_rng(0)
    lo, hi = warm_spec["batches"]
    for k in range(lo, hi + 1):
        envs = throwaway_envs(k, rng)
        if warm_spec["path"] == "request":
            mcop_batch(model.build_batch(profile, envs, m=bucket), backend=broker.backend,
                       buckets=broker.buckets, mesh=mesh)
        else:
            solve_envs(profile, model, envs, backend=broker.backend, buckets=broker.buckets, mesh=mesh)
    return hi - lo + 1


def patch(kind: str) -> None:
    """Break or replace the timed path underneath the server (tests and
    the precision control only; never in a benchmark run).

    ``control``: the plain reference in bfloat16 solves every flush.
    ``alter``: the first answer of every flush has its cut raised by 1%.
    ``half``: the second half of every flush is left unsolved: it gets the
    all-local plan at its all-local cost.
    """
    import numpy as np

    import repro.core.session_batch as session_batch
    import repro.service.broker as broker_mod
    from repro.core.mcop import MCOPResult

    sys.path.insert(0, str(ROOT))
    from bench import reference

    solve_batch, solve_envs = broker_mod.mcop_batch, session_batch.solve_envs

    def graphs_of(batch):
        gs = batch.to_wcgs()
        return (np.stack([g.w_local for g in gs]), np.stack([g.w_cloud for g in gs]),
                np.stack([g.adj for g in gs]), gs[0].offloadable)

    def bf16(wl, wc, adj, offloadable):
        cut, local = reference.mcop(wl, wc, adj, offloadable, reference.round_bf16)
        return [MCOPResult(min_cut=float(c), local_mask=m, phases=[]) for c, m in zip(cut, local)]

    def broken(results, graphs):
        if kind == "alter":
            r = results[0]
            results[0] = MCOPResult(min_cut=r.min_cut * 1.01, local_mask=r.local_mask, phases=[])
        elif kind == "half":
            all_local = graphs[0].sum(axis=1)
            for i in range(len(results) // 2, len(results)):
                n = len(results[i].local_mask)
                results[i] = MCOPResult(min_cut=float(all_local[i]), local_mask=np.ones(n, bool), phases=[])
        return results

    def flush(batch, **kw):
        if kind == "control":
            return bf16(*graphs_of(batch))
        return broken(solve_batch(batch, **kw), graphs_of(batch))

    def envs_flush(profile, model, envs, **kw):
        graphs = graphs_of(model.build_batch(profile, envs))
        if kind == "control":
            return bf16(*graphs)
        return broken(solve_envs(profile, model, envs, **kw), graphs)

    broker_mod.mcop_batch = flush
    session_batch.solve_envs = envs_flush


def main(argv: list[str]) -> int:
    spec = json.loads(pathlib.Path(argv[0]).read_text())
    out = pathlib.Path(spec["out"])
    import jax

    from repro.launch.compile_cache import use_compile_cache
    from repro.obs.trace import Tracer
    from repro.service import OffloadBroker, SolverServer, tcp_address

    pathlib.Path(use_compile_cache()).mkdir(parents=True, exist_ok=True)
    # every program, however fast it compiles, is found again by the next run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    lowerings: list[list] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, duration, **kw: event == LOWERING_EVENT
        and lowerings.append([time.perf_counter(), duration, kw.get("fun_name")])
    )

    config = json.loads(pathlib.Path(spec["config"]).read_text())
    guarantees = config["guarantees"]
    tracer = Tracer(capacity=4_000_000) if spec["trace"] else None
    broker = OffloadBroker(tracer=tracer)
    profile, model = tenant_profile(config), cost_model(config["cost_model"])
    broker.register(spec["tenant"], profile, model, cache_capacity=config["cache_capacity"])
    t0 = time.perf_counter()
    # the control replaces the device solver: it has no shapes to warm
    shapes = 0 if spec.get("patch") == "control" else warm(broker, profile, model, spec["warm"])
    warm_s = time.perf_counter() - t0
    if spec.get("patch"):
        patch(spec["patch"])
    server = SolverServer(
        broker,
        address=tcp_address("127.0.0.1", 0),
        journal_path=out / "journal.jsonl",
        snapshot_dir=out / "snapshots",
        snapshot_every_ticks=guarantees["snapshot_every_ticks"],
        fsync=guarantees["fsync"],
        tracer=tracer,
    )
    server.recover()
    port = server.bind()[-1]
    devices = jax.devices()
    facts = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    marks: dict[str, float] = {}
    stopping: list[threading.Thread] = []

    def control() -> None:
        for line in sys.stdin:
            cmd = line.strip()
            marks[cmd] = time.perf_counter()
            if cmd == "trace_start" and spec["trace"]:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 0
                jax.profiler.start_trace(str(out / "trace"), profiler_options=opts)
                marks["trace_start"] = time.perf_counter()
            elif cmd == "trace_end" and spec["trace"]:
                # writing the trace takes seconds: serving goes on meanwhile
                stopping.append(threading.Thread(target=jax.profiler.stop_trace))
                stopping[-1].start()
            elif cmd == "stop":
                server.stop()
                return

    watcher = threading.Thread(target=control, daemon=True)
    print(f"DEVICE {json.dumps(facts)}", flush=True)
    print(f"READY {port} warm_shapes={shapes} warm_s={warm_s:.3f}", flush=True)
    watcher.start()
    server.serve_forever()
    watcher.join(timeout=60)
    for t in stopping:
        t.join()
    peaks = [d.memory_stats().get("peak_bytes_in_use") for d in devices if d.memory_stats()]
    result = {
        "device": facts,
        "memory_peak_bytes": max(peaks) if peaks else None,
        "marks": marks,
        "warm_s": warm_s,
        "lowerings": lowerings,
        "spans": [s.to_dict() for s in tracer.spans()] if tracer is not None else [],
    }
    (out / "server.json").write_text(json.dumps(result, default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
