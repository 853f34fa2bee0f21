#!/usr/bin/env python
"""Bring-up smoke of the served MCOP path on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the solver fleet on a four-chip host

One chip: for each solver backend (``jax``, ``pallas``) and each default
shape bucket (12, 64 and 200 vertices fill buckets 16, 64 and 256) it
starts ``examples/serve_broker.py`` — the only process that touches the
chip — and drives it from client processes pinned to the host CPU:

* a request phase: 3 ticks, each submitting 256 environments that lie in
  distinct placement-cache bins (bandwidth up, bandwidth down and speedup
  varied jointly), so every flush is a device batch of 256 graphs;
* a batch-session phase: 10,000 sessions over 2 client processes, driven
  by ``TrafficGenerator`` for 4 ticks.

Every placement a client receives is checked against ``mcop_reference``
(clamped to the all-local plan as the service does): the cut within
``1e-4 * max(1, |cut_ref|)``, and the returned mask, priced in float64 by
``WCG.total_cost``, within the same bound.  A batch-session report carries
each session's cut but not its mask; a session whose cut changed in a tick
is checked against the reference placement of its cache bin, priced at the
session's own environment.

``--chips 4`` runs only the fleet: the request phase at 64 and 200
vertices on four chips (4 ``solve.shard`` spans per flush), then the same
batches through ``mcop_batch(mesh=None)`` and ``mcop_batch(mesh=False)``
in one process, which must agree bit for bit.

Earlier lines report each phase; the last line is one JSON object,
``{"ok": ..., "device": {"platform", "kind", "count"}}``.  ``ok`` is false,
and the exit status nonzero, unless the server ran on a TPU with compiled
kernels, no client saw an error frame, no tick reported a fault, retry,
breaker trip or degraded reply, and every placement matched.  Where the
server finds no TPU every phase still runs, at a reduced size, and the run
ends with ``ok`` false.  Journals, snapshots, traces and per-process logs
go to ``chiprun_out/chip_smoke/``.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import pathlib
import queue
import shutil
import signal
import subprocess
import sys
import threading
import time

REPO = pathlib.Path(__file__).resolve().parent
SERVER = REPO / "examples" / "serve_broker.py"
OUT = REPO / "chiprun_out" / "chip_smoke"
# the environment of the processes that hold the chip (the server, the
# fleet check); everything else is pinned to the host CPU
SERVER_ENV = dict(os.environ, PYTHONPATH=str(REPO / "src"))
HOST_ENV = dict(SERVER_ENV, JAX_PLATFORMS="cpu")
sys.path[:0] = [str(REPO / "src"), str(REPO / "examples")]

SEED = 0
TENANT = "app"
BACKENDS = ("jax", "pallas")
NODES = (12, 64, 200)          # fill buckets 16, 64 and 256
FLEET_NODES = (64, 200)
REQUEST_TICKS = 3
BATCH_TICKS = 4
BATCH_CLIENTS = 2
TRAFFIC_SEED = 100
TOL = 1e-4
READY_TIMEOUT_S = 300.0
CLIENT_TIMEOUT_S = 900.0
# (requests per tick, batch sessions): the chip's size, and the size of a
# rehearsal on a host whose server finds no TPU
CHIP_SIZE = (256, 10_000)
HOST_SIZE = (4, 200)
FAULT_FIELDS = ("faults", "retries", "breaker_trips", "degraded")


# ----------------------------------------------------------------------
# deterministic traffic, rebuilt identically by clients and checker
# ----------------------------------------------------------------------


def request_envs(ticks: int, per_tick: int) -> list[list[tuple]]:
    """``ticks`` lists of ``per_tick`` (up, down, speedup) triples, every
    one in its own quantizer bin: each value is a power of 1.1 (a bin
    centre at the cache's 10% step) and no exponent triple repeats."""
    import numpy as np

    grid = np.stack(
        np.meshgrid(
            np.arange(-8, 28), np.arange(-8, 28), np.arange(4, 24),
            indexing="ij",
        ),
        axis=-1,
    ).reshape(-1, 3)
    pick = grid[np.random.default_rng(SEED).permutation(len(grid))]
    pick = pick[: ticks * per_tick]
    return [
        [tuple(1.1 ** float(x) for x in row) for row in pick[t * per_tick:(t + 1) * per_tick]]
        for t in range(ticks)
    ]


@functools.lru_cache(maxsize=None)
def _tenant(nodes: int):
    """The server's demo tenant, built the same way from the same seed."""
    from serve_broker import demo_tenant

    return demo_tenant(nodes, SEED)


def traffic(users: int, client: int):
    from repro.service.workload import TrafficGenerator

    gen = TrafficGenerator(users // BATCH_CLIENTS, seed=TRAFFIC_SEED + client)
    return [gen.step() for _ in range(BATCH_TICKS)]


# ----------------------------------------------------------------------
# client processes (host CPU only)
# ----------------------------------------------------------------------


def _connect(args, name: str):
    from repro.service import BrokerClient, tcp_address

    client = BrokerClient(
        tcp_address("127.0.0.1", args.port),
        tenants={TENANT: _tenant(args.nodes)},
        client=name,
        timeout=CLIENT_TIMEOUT_S,
    )
    return client.connect()


def run_requests(args) -> dict:
    from repro.core import Environment

    client = _connect(args, "requests")
    ticks, replies = [], []
    for envs in request_envs(REQUEST_TICKS, args.per_tick):
        futs = [client.submit(TENANT, Environment(*e)) for e in envs]
        t0 = time.perf_counter()
        report = client.tick()
        ticks.append({"wall_s": time.perf_counter() - t0, "report": report})
        for fut in futs:
            reply = fut.result  # raises if the tick left it unresolved
            res = reply.result
            replies.append(
                {
                    "min_cut": None if res is None else float(res.min_cut),
                    "mask": None if res is None else [int(b) for b in res.local_mask],
                    "degraded": reply.degraded,
                    "rejected": reply.rejected,
                    "timed_out": reply.timed_out,
                }
            )
    client.close()
    return {"ticks": ticks, "replies": replies}


def run_batch(args) -> dict:
    import numpy as np

    client = _connect(args, f"batch{args.client}")
    group = client.register_batch(TENANT, args.users // BATCH_CLIENTS)
    ticks, reports = [], []
    for tk in traffic(args.users, args.client):
        group.observe(
            tk.envs,
            arrived=np.nonzero(tk.arrived)[0],
            departed=np.nonzero(tk.departed)[0],
        )
        t0 = time.perf_counter()
        report = client.tick()
        ticks.append({"wall_s": time.perf_counter() - t0, "report": report})
        reports.extend(group.drain())
    # a concurrent client's tick may resolve our stage before our own tick
    # frame lands, but every staged tick must report exactly once
    for _ in range(4):
        if len(reports) >= BATCH_TICKS:
            break
        ticks.append({"wall_s": None, "report": client.tick()})
        reports.extend(group.drain())
    client.close()
    if len(reports) != BATCH_TICKS:
        raise RuntimeError(f"{len(reports)} batch reports for {BATCH_TICKS} ticks")
    return {"ticks": ticks, "reports": reports}


def run_fleet(args) -> dict:
    """Same request batches through both fleet routings, in the one
    process that holds the chips: ``mesh=None`` shards over every device,
    ``mesh=False`` solves on one; they must agree bit for bit."""
    import jax
    import numpy as np

    from repro.core import Environment
    from repro.core.mcop import mcop_batch

    out = {"devices": jax.device_count(), "batches": 0, "unequal": 0}
    for nodes in FLEET_NODES:
        profile, model = _tenant(nodes)
        for envs in request_envs(REQUEST_TICKS, args.per_tick):
            graphs = [model.build(profile, Environment(*e)) for e in envs]
            fleet = mcop_batch(graphs, mesh=None)
            single = mcop_batch(graphs, mesh=False)
            same = all(
                a.min_cut == b.min_cut and np.array_equal(a.local_mask, b.local_mask)
                for a, b in zip(fleet, single)
            )
            out["batches"] += 1
            out["unequal"] += not same
    return out


ROLES = {"requests": run_requests, "batch": run_batch, "fleet": run_fleet}


# ----------------------------------------------------------------------
# reference checks (host CPU, in a process pool)
# ----------------------------------------------------------------------


def reference(task) -> tuple[float, list[int]]:
    """The placement the service must return for one environment:
    ``mcop_reference`` clamped to all-local where that is strictly cheaper
    (paper §4.3).  Returns (cut, mask)."""
    from repro.core import Environment, mcop_reference

    nodes, env = task
    profile, model = _tenant(nodes)
    g = model.build(profile, Environment(*env))
    res = mcop_reference(g)
    no_off = float(g.w_local.sum())
    if no_off < res.min_cut:
        return no_off, [1] * g.n
    return float(res.min_cut), [int(b) for b in res.local_mask]


def _off(got: float, want: float) -> bool:
    return not abs(got - want) <= TOL * max(1.0, abs(want))


def check_requests(nodes: int, replies: list[dict], refs: list) -> int:
    import numpy as np

    from repro.core import Environment

    profile, model = _tenant(nodes)
    envs = [e for tick in request_envs(REQUEST_TICKS, len(refs) // REQUEST_TICKS) for e in tick]
    bad = 0
    for env, reply, (cut, _) in zip(envs, replies, refs, strict=True):
        if reply["min_cut"] is None:
            bad += 1
            continue
        priced = model.build(profile, Environment(*env)).total_cost(
            np.asarray(reply["mask"], bool)
        )
        bad += _off(reply["min_cut"], cut) or _off(priced, cut)
    return bad


def changed_rows(users: int, client: int, reports: list[dict]):
    """Per tick: the active sessions whose reported cut changed (those
    the tick re-partitioned), with their environments and cuts."""
    import numpy as np

    prev = None
    for tk, rep in zip(traffic(users, client), reports, strict=True):
        cut = np.asarray(rep["min_cut"], np.float64)
        fresh = np.ones_like(cut, bool) if prev is None else ~(cut == prev)
        idx = np.nonzero(tk.active & fresh)[0]
        prev = cut
        yield idx, tk.envs.take(idx), cut[idx]


def env_arrays(envs: list[tuple]):
    from repro.core import Environment
    from repro.core.cost_models import EnvArrays

    return EnvArrays.from_envs([Environment(*e) for e in envs])


def bin_keys(envs) -> list[tuple]:
    from repro.core.placement_cache import EnvQuantizer

    return [tuple(int(v) for v in row) for row in EnvQuantizer().keys_batch(envs)]


def representatives(users: int, batches: list[list[dict]]) -> dict:
    """Per cache bin, the environments that can have solved it: for each
    client, the first re-partitioned session of the bin in slot order at
    the first tick the bin appears."""
    reps: dict[tuple, list[tuple]] = {}
    for client, reports in enumerate(batches):
        seen = set()
        for _, envs, _ in changed_rows(users, client, reports):
            for i, key in enumerate(bin_keys(envs)):
                if key not in seen:
                    seen.add(key)
                    reps.setdefault(key, []).append(tuple(float(f[i]) for f in envs))
    return reps


def _fits(cut: float, g, masks) -> bool:
    """Does ``cut`` price one of ``masks`` on ``g`` (clamped to all-local)?"""
    import numpy as np

    no_off = float(g.w_local.sum())
    return any(
        not _off(cut, min(g.total_cost(np.asarray(m, bool)), no_off)) for m in masks
    )


def check_batch(nodes, users, batches, masks_by_bin, solve) -> int:
    """Each changed session's cut must equal a reference placement of its
    bin priced at its own environment (clamped to all-local).  A session
    matching none gets its own reference, which joins the bin."""
    import numpy as np

    profile, model = _tenant(nodes)
    bad = 0
    for client, reports in enumerate(batches):
        for _, envs, cuts in changed_rows(users, client, reports):
            keys = bin_keys(envs)
            for lo in range(0, len(keys), 256):  # bounded host memory
                rows = np.arange(lo, min(lo + 256, len(keys)))
                wb = model.build_batch(profile, envs.take(rows))
                for j, i in enumerate(rows):
                    g, masks = wb.wcg(j), masks_by_bin.setdefault(keys[i], [])
                    if _fits(cuts[i], g, masks):
                        continue
                    masks.append(solve((nodes, tuple(float(f[i]) for f in envs)))[1])
                    bad += not _fits(cuts[i], g, masks)
    return bad


# ----------------------------------------------------------------------
# orchestration
# ----------------------------------------------------------------------


class Server:
    """One ``serve_broker.py`` process; the only one that touches JAX's
    accelerator."""

    def __init__(self, tag: str, backend: str, nodes: int):
        self.dir = OUT / tag
        self.dir.mkdir(parents=True)
        self.trace = self.dir / "trace.jsonl"
        cmd = [
            sys.executable, str(SERVER),
            "--tcp", "127.0.0.1:0",
            "--journal", str(self.dir / "journal.jsonl"),
            "--snapshot-dir", str(self.dir / "snaps"),
            "--tenant", TENANT,
            "--nodes", str(nodes), "--seed", str(SEED),
            "--backend", backend,
            "--trace-jsonl", str(self.trace),
        ]
        self.err = (self.dir / "server.err").open("w")
        self.proc = subprocess.Popen(
            cmd, stdout=subprocess.PIPE, stderr=self.err, text=True,
            env=SERVER_ENV, cwd=REPO,
        )
        lines: queue.Queue = queue.Queue()
        threading.Thread(
            target=lambda: [lines.put(x) for x in self.proc.stdout] + [lines.put(None)],
            daemon=True,
        ).start()
        self.device = None
        deadline = time.monotonic() + READY_TIMEOUT_S
        while True:
            try:
                line = lines.get(timeout=max(0.0, deadline - time.monotonic()))
            except queue.Empty:
                raise RuntimeError(f"{tag}: server not READY in {READY_TIMEOUT_S:.0f}s")
            if line is None:
                raise RuntimeError(f"{tag}: server exited before READY (see {self.err.name})")
            if line.startswith("DEVICE "):
                self.device = json.loads(line[len("DEVICE "):])
            if line.startswith("READY "):
                self.port = int(line.split()[-1])
                return

    def stop(self) -> int:
        """SIGINT: the server closes and exports its trace."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGINT)
        try:
            return self.proc.wait(timeout=120)
        finally:
            self.kill()

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self.err.close()


def start_role(role: str, out: pathlib.Path, env=HOST_ENV, **opts) -> subprocess.Popen:
    """This script in ``--role`` mode; it writes its result to ``out``."""
    cmd = [sys.executable, str(pathlib.Path(__file__).resolve()), "--role", role, "--result", str(out)]
    for k, v in opts.items():
        cmd += [f"--{k.replace('_', '-')}", str(v)]
    log = out.with_suffix(".log").open("w")
    return subprocess.Popen(cmd, stdout=log, stderr=subprocess.STDOUT, env=env, cwd=REPO)


def finish(procs: list[subprocess.Popen], outs: list[pathlib.Path]) -> list[dict]:
    try:
        codes = [p.wait(timeout=CLIENT_TIMEOUT_S) for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    for code, out in zip(codes, outs):
        if code:
            tail = out.with_suffix(".log").read_text()[-1500:]
            raise RuntimeError(f"{out.stem} exited {code}: {tail}")
    return [json.loads(o.read_text()) for o in outs]


def tick_faults(ticks: list[dict]) -> int:
    return sum(int(t["report"].get(f, 0)) for t in ticks for f in FAULT_FIELDS)


def tick_times(ticks: list[dict]) -> tuple[float, float | None]:
    walls = [t["wall_s"] for t in ticks if t["wall_s"] is not None]
    warm = sorted(walls[1:])
    return walls[0], (warm[len(warm) // 2] if warm else None)


def shard_spans(trace: pathlib.Path) -> list[int]:
    """``solve.shard`` span count under each ``stage.solve_flush`` span."""
    spans = [json.loads(x) for x in trace.read_text().splitlines() if x.strip()]
    flushes = {s["span_id"]: 0 for s in spans if s["name"] == "stage.solve_flush"}
    for s in spans:
        if s["name"] == "solve.shard" and s["parent_id"] in flushes:
            flushes[s["parent_id"]] += 1
    return list(flushes.values())


class Smoke:
    def __init__(self, chips: int, workers: int):
        import multiprocessing

        self.chips = chips
        self.pool = multiprocessing.get_context("spawn").Pool(workers)
        self.device = None
        self.problems: list[str] = []
        self.size = None

    def fail(self, msg: str) -> None:
        self.problems.append(msg)
        print(f"FAIL {msg}", flush=True)

    def solve(self, task):
        return self.pool.apply(reference, (task,))

    def serve(self, tag: str, backend: str, nodes: int) -> Server:
        server = Server(tag, backend, nodes)
        dev = server.device or {}
        if self.device is None:
            self.device = dev
            on_chip = dev.get("platform") == "tpu"
            self.size = CHIP_SIZE if on_chip else HOST_SIZE
            # references for every request the run will make, computed
            # while the servers work
            per_tick = self.size[0]
            envs = [e for t in request_envs(REQUEST_TICKS, per_tick) for e in t]
            nodes_all = FLEET_NODES if self.chips == 4 else NODES
            self.request_refs = {
                n: self.pool.map_async(reference, [(n, e) for e in envs])
                for n in nodes_all
            }
        print(f"server {tag} device {json.dumps(dev)}", flush=True)
        if dev.get("platform") != "tpu":
            self.fail(f"{tag}: platform is {dev.get('platform')!r}, not 'tpu'")
        if dev.get("interpret", True):
            self.fail(f"{tag}: Pallas runs in interpret mode")
        if dev.get("count") != self.chips:
            self.fail(f"{tag}: server sees {dev.get('count')} devices, expected {self.chips}")
        return server

    def phase(self, backend: str, nodes: int, kind: str, device_graphs: int,
              ticks: list[dict], mismatches: int) -> None:
        from repro.core.mcop import DEFAULT_BUCKETS, _bucket_size

        first, warm = tick_times(ticks)
        print(
            f"phase backend={backend} bucket={_bucket_size(nodes, DEFAULT_BUCKETS)} "
            f"nodes={nodes} {kind} device_graphs={device_graphs} "
            f"first_tick_s={first:.3f} "
            f"warm_tick_s={'n/a' if warm is None else f'{warm:.3f}'} "
            f"mismatches={mismatches}",
            flush=True,
        )
        if mismatches:
            self.fail(f"{backend}/{nodes}/{kind}: {mismatches} placements disagree with mcop_reference")
        faults = tick_faults(ticks)
        if faults:
            self.fail(f"{backend}/{nodes}/{kind}: ticks report {faults} faults/retries/trips/degraded")

    def requests(self, server: Server, nodes: int) -> dict:
        out = server.dir / "requests.json"
        (res,) = finish(
            [start_role("requests", out, port=server.port, nodes=nodes, per_tick=self.size[0])],
            [out],
        )
        return res

    def batch(self, server: Server, nodes: int) -> list[dict]:
        outs = [server.dir / f"batch{c}.json" for c in range(BATCH_CLIENTS)]
        procs = [
            start_role("batch", o, port=server.port, nodes=nodes, users=self.size[1], client=c)
            for c, o in enumerate(outs)
        ]
        return finish(procs, outs)

    def check_request_phase(self, backend, nodes, res):
        refs = self.request_refs[nodes].get()
        bad = check_requests(nodes, res["replies"], refs)
        bad += sum(r["degraded"] or r["rejected"] or r["timed_out"] for r in res["replies"])
        solved = sum(int(t["report"].get("solved", 0)) for t in res["ticks"])
        self.phase(backend, nodes, "requests", solved, res["ticks"], bad)
        per_tick = self.size[0]
        if solved != REQUEST_TICKS * per_tick:
            self.fail(f"{backend}/{nodes}: {solved} graphs solved, expected {REQUEST_TICKS * per_tick}")
        return refs

    def one_chip(self) -> None:
        for backend in BACKENDS:
            for nodes in NODES:
                tag = f"{backend}_n{nodes}"
                server = self.serve(tag, backend, nodes)
                try:
                    req = self.requests(server, nodes)
                    batches = self.batch(server, nodes)
                finally:
                    code = server.stop()
                if code:
                    self.fail(f"{tag}: server exited {code}")
                if any(shard_spans(server.trace)):
                    self.fail(f"{tag}: one-chip flushes were sharded")
                refs = self.check_request_phase(backend, nodes, req)
                self.check_batch_phase(backend, nodes, batches, refs)

    def check_batch_phase(self, backend, nodes, batches, request_refs):
        # batch sessions may hit the bins the request phase filled
        envs = [e for t in request_envs(REQUEST_TICKS, self.size[0]) for e in t]
        masks_by_bin: dict = {}
        for key, (_, mask) in zip(bin_keys(env_arrays(envs)), request_refs):
            masks_by_bin.setdefault(key, []).append(mask)
        reports = [b["reports"] for b in batches]
        tasks = [
            (key, (nodes, env))
            for key, envs_k in representatives(self.size[1], reports).items()
            for env in envs_k
        ]
        for (key, _), (_, mask) in zip(tasks, self.pool.map(reference, [t for _, t in tasks])):
            masks_by_bin.setdefault(key, []).append(mask)
        bad = check_batch(nodes, self.size[1], reports, masks_by_bin, self.solve)
        bad += sum(int(r["degraded"]) for rs in reports for r in rs)
        ticks = [t for b in batches for t in b["ticks"]]
        solved = sum(int(r["solved"]) for rs in reports for r in rs)
        self.phase(backend, nodes, f"batch_sessions={self.size[1]}", solved, ticks, bad)

    def four_chips(self) -> None:
        for nodes in FLEET_NODES:
            tag = f"fleet_n{nodes}"
            server = self.serve(tag, "jax", nodes)
            try:
                req = self.requests(server, nodes)
            finally:
                code = server.stop()
            if code:
                self.fail(f"{tag}: server exited {code}")
            self.check_request_phase("jax", nodes, req)
            spans = shard_spans(server.trace)
            print(f"fleet {tag} solve.shard spans per flush {spans}", flush=True)
            if not spans or any(s != 4 for s in spans):
                self.fail(f"{tag}: expected 4 solve.shard spans per flush, got {spans}")
        # after the servers exit: this process now holds the chips
        out = OUT / "fleet.json"
        (res,) = finish([start_role("fleet", out, env=SERVER_ENV, per_tick=self.size[0])], [out])
        print(
            f"fleet mesh=None vs mesh=False devices={res['devices']} "
            f"batches={res['batches']} unequal={res['unequal']}",
            flush=True,
        )
        if res["devices"] != 4 or res["unequal"] or not res["batches"]:
            self.fail(f"fleet parity: {res}")

    def close(self) -> None:
        self.pool.terminate()
        self.pool.join()


def coordinator(args) -> int:
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    shutil.rmtree(OUT, ignore_errors=True)
    OUT.mkdir(parents=True)
    t0 = time.perf_counter()
    smoke = Smoke(args.chips, max(1, min(10, (os.cpu_count() or 2) - 3)))
    try:
        (smoke.four_chips if args.chips == 4 else smoke.one_chip)()
    except Exception as err:  # noqa: BLE001 — a phase that raises fails the run
        smoke.fail(f"{type(err).__name__}: {err}")
    finally:
        smoke.close()
    dev = smoke.device or {}
    print(f"wall_s {time.perf_counter() - t0:.1f}", flush=True)
    ok = not smoke.problems
    print(json.dumps({
        "ok": ok,
        "device": {
            "platform": dev.get("platform"),
            "kind": dev.get("kind"),
            "count": dev.get("count"),
        },
    }))
    return 0 if ok else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: both backends x buckets 16/64/256; 4: the solver fleet")
    ap.add_argument("--role", choices=sorted(ROLES), help=argparse.SUPPRESS)
    ap.add_argument("--result", help=argparse.SUPPRESS)
    ap.add_argument("--port", type=int, help=argparse.SUPPRESS)
    ap.add_argument("--nodes", type=int, help=argparse.SUPPRESS)
    ap.add_argument("--per-tick", type=int, help=argparse.SUPPRESS)
    ap.add_argument("--users", type=int, help=argparse.SUPPRESS)
    ap.add_argument("--client", type=int, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.role != "fleet":
        os.environ["JAX_PLATFORMS"] = "cpu"  # before anything imports jax
    if args.role:
        result = ROLES[args.role](args)
        pathlib.Path(args.result).write_text(json.dumps(result))
        return 0
    try:
        return coordinator(args)
    except Exception as err:  # noqa: BLE001 — no repo, no result
        print(f"FAIL {type(err).__name__}: {err}", flush=True)
        print(json.dumps({"ok": False, "device": None}))
        return 1


if __name__ == "__main__":
    sys.exit(main())
