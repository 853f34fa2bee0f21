"""Run one cell of the benchmark and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name:
``BENCHMARK.json`` pairs ``bench/configs/<config>.json`` with
``bench/traffic/<mix>.json``; the mix names its generator
(``bench/generators/<kind>.py``), the configuration its profile builder
(``bench/profiles/<kind>.py``); each per-layer metric is read by
``bench/metrics/<name>.py`` and each cell's correctness limits are in
``bench/limits/<cell>.json``.

One run: start ``bench/serve.py`` (the only process on the chip; this
process and its client connections stay on the host CPU), wait for it to
warm every solve shape, play the mix's warm-up, then measure for
``--seconds``: open-loop requests through ``BrokerClient.submit`` with
tick frames on the mix's cadence, or closed-loop session groups through
``register_batch``/``observe``/``tick``.  After the window every reply is
compared with the plain reference (``bench/check.py``).  With
``--trace 1`` the server records its spans and a device trace, and the
line carries the per-layer metrics instead of the end-to-end ones.

The last line of standard output is the result.  A run whose server finds
no TPU, or fewer chips than the cell asks for, still drives the whole path
and checks it, then prints no result and exits with status 2.  Every number compared is printed with its limit as the
last lines of standard error, and under ``checks`` in the result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
TENANT = "app"
READY_TIMEOUT_S = 1000.0
CLIENT_TIMEOUT_S = 120.0


def load_module(path: pathlib.Path, name: str):
    if not path.exists():
        raise FileNotFoundError(f"no {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read_json(path: pathlib.Path) -> dict:
    if not path.exists():
        raise FileNotFoundError(f"no {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def cell_spec(workload: str) -> dict:
    """Everything BENCHMARK.json and the cell's files say about ``workload``."""
    bench = read_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    config_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    end_to_end = [m for m in bench["end_to_end"] if workload in m.get("workloads", [workload])]
    e2e_names = {m["name"] for m in end_to_end}
    per_layer = [
        m for m in bench["per_layer"]
        if (workload in m["workloads"] if "workloads" in m else m["moves"] in e2e_names)
    ]
    return {
        "cell": cell,
        "config_file": ROOT / config_entry["file"],
        "config": read_json(ROOT / config_entry["file"]),
        "mix": read_json(BENCH / "traffic" / f"{cell['traffic']}.json"),
        "limits": read_json(BENCH / "limits" / f"{workload}.json"),
        "end_to_end": end_to_end,
        "per_layer": per_layer,
    }


# ----------------------------------------------------------------------
# the server process
# ----------------------------------------------------------------------


class Server:
    """``bench/serve.py`` in a child process; commands go to its stdin."""

    def __init__(self, spec: dict, out: pathlib.Path, env: dict):
        launch = out / "launch.json"
        launch.write_text(json.dumps(spec))
        self.out = out
        self.err = (out / "server.err").open("w")
        self.proc = subprocess.Popen(
            [sys.executable, str(BENCH / "serve.py"), str(launch)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=self.err,
            text=True, env=env, cwd=ROOT,
        )
        lines: "collections.deque[str | None]" = collections.deque()
        ready = threading.Event()

        def pump():
            for line in self.proc.stdout:
                lines.append(line)
                if line.startswith("READY"):
                    ready.set()
            lines.append(None)
            ready.set()

        threading.Thread(target=pump, daemon=True).start()
        if not ready.wait(READY_TIMEOUT_S):
            self.kill()
            raise RuntimeError(f"server not READY in {READY_TIMEOUT_S:.0f}s")
        self.device = None
        self.port = None
        for line in list(lines):
            if line is None:
                break
            if line.startswith("DEVICE "):
                self.device = json.loads(line[len("DEVICE "):])
            if line.startswith("READY "):
                self.port = int(line.split()[1])
                self.ready_line = line.strip()
        if self.port is None:
            self.kill()
            raise RuntimeError(f"server exited before READY: {self.tail()}")

    def tail(self) -> str:
        self.err.flush()
        return (self.out / "server.err").read_text()[-1500:]

    def command(self, cmd: str) -> None:
        self.proc.stdin.write(cmd + "\n")
        self.proc.stdin.flush()

    def stop(self) -> dict:
        self.command("stop")
        try:
            code = self.proc.wait(timeout=240)
        finally:
            self.kill()
        if code:
            raise RuntimeError(f"server exited {code}: {self.tail()}")
        return json.loads((self.out / "server.json").read_text())

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self.err.close()


# ----------------------------------------------------------------------
# traffic loops (host CPU)
# ----------------------------------------------------------------------


class Window:
    """The measured window and the device trace, on this host's clock;
    tells the server when each starts and ends.

    ``trace`` (``None`` when not tracing) is the mix's ``trace``: the device
    trace starts ``from`` the window's start, or from the traffic's start
    (its warm-up first), and ends ``seconds`` after the window's start, or
    with the window when ``seconds`` is null."""

    def __init__(self, server: Server, seconds: float, trace: dict | None):
        self.server, self.seconds, self.trace = server, seconds, trace
        self.t0 = self.t1 = None
        self.tracing = False

    def _trace(self, cmd: str) -> None:
        self.server.command(cmd)
        self.tracing = cmd == "trace_start"

    def traffic(self) -> None:
        if self.trace and self.trace["from"] == "traffic":
            self._trace("trace_start")

    def open(self) -> None:
        self.server.command("start")
        self.t0 = time.perf_counter()
        self.t1 = self.t0 + self.seconds
        if self.trace and self.trace["from"] == "window":
            self._trace("trace_start")

    def poll(self, now: float) -> None:
        if self.tracing and self.trace["seconds"] is not None and now >= self.t0 + self.trace["seconds"]:
            self._trace("trace_end")

    def close(self) -> None:
        if self.tracing:
            self._trace("trace_end")
        self.server.command("end")


def drive_open(address, mix: dict, gen, seed: int, window: Window) -> dict:
    """Open-loop requests on one connection, as a front end sends them: a
    fixed number of arrivals, uniform over the warm-up, the window and the
    drain (a Poisson process given its count).  Every request that has
    arrived is submitted, then a tick frame goes out if requests are
    pending and the cadence has passed since the previous tick began; the
    replies a tick resolves arrive before its report.  Requests keep
    arriving after the window until every request due in it is answered."""
    import numpy as np

    from repro.core import Environment
    from repro.service import BrokerClient

    warm_s, drain_s = mix["warmup_s"], mix["drain_s"]
    span = warm_s + window.seconds + drain_s
    count = int(round(mix["rate"] * span))
    due = np.sort(np.random.default_rng([seed, 0]).uniform(0.0, span, count))
    _, envs = gen.requests(mix["params"], seed, count)
    env_objs = [Environment(*row) for row in envs.tolist()]
    budget, cadence = mix.get("budget"), mix["tick_interval_s"]
    client = BrokerClient(address, tenants={TENANT: (None, None)}, client="front",
                          timeout=CLIENT_TIMEOUT_S).connect()
    futs: list = [None] * count
    t_sub = np.full(count, np.nan)
    t_rep = np.full(count, np.nan)
    ticks: list[tuple] = []
    pending: list[int] = []
    i, last_tick = 0, -math.inf
    base = time.perf_counter()
    due_abs = base + due
    window_due = (due >= warm_s) & (due < warm_s + window.seconds)
    last_window = int(np.nonzero(window_due)[0].max()) if window_due.any() else -1
    opened = closed = False
    window.traffic()
    while True:
        now = time.perf_counter()
        if not opened and now >= base + warm_s:
            window.open()
            opened = True
        if opened:
            window.poll(now)
            if not closed and now >= window.t1:
                window.close()
                closed = True
        if closed and i > last_window and not any(p <= last_window for p in pending):
            break
        if now > base + span:
            break
        while i < count and due_abs[i] <= now:
            futs[i] = client.submit(TENANT, env_objs[i])
            t_sub[i] = time.perf_counter()
            pending.append(i)
            i += 1
        now = time.perf_counter()
        if pending and now - last_tick >= cadence:
            last_tick = now
            report = client.tick(budget=budget)
            t = time.perf_counter()
            ticks.append((last_tick, t, report))
            still = []
            for j in pending:
                if futs[j].done:
                    t_rep[j] = t
                else:
                    still.append(j)
            pending = still
            continue
        nxt = due_abs[i] if i < count else base + span
        if pending:
            nxt = min(nxt, last_tick + cadence)
        time.sleep(max(0.0, min(nxt - time.perf_counter(), 0.05)))
    if not closed:
        window.close()
    client.close()
    replies = []
    for j in range(i):
        if not futs[j].done:
            continue
        reply = futs[j].result
        res = reply.result
        replies.append({
            "index": j, "env": envs[j], "tick": reply.tick,
            "cut": None if res is None else float(res.min_cut),
            "mask": None if res is None else np.asarray(res.local_mask, bool),
            "cache_hit": reply.cache_hit, "coalesced": reply.coalesced,
            "failed": res is None or reply.rejected or reply.degraded or reply.timed_out,
        })
    return {"due_abs": due_abs, "window_due": window_due, "t_sub": t_sub, "t_rep": t_rep,
            "replies": replies, "ticks": ticks, "submitted": i}


def drive_sessions(address, mix: dict, gen, seed: int, window: Window) -> dict:
    """Closed loop: each connection holds one batch session group and
    observes, then ticks, back to back."""
    import numpy as np

    from repro.core.cost_models import EnvArrays
    from repro.service import BrokerClient

    conns = mix["connections"]
    barrier = threading.Barrier(conns + 1)
    stop = threading.Event()
    records: list[list[dict]] = [[] for _ in range(conns)]
    errors: list[BaseException] = []

    def loop(c: int) -> None:
        try:
            client = BrokerClient(address, tenants={TENANT: (None, None)}, client=f"group{c}",
                                  timeout=CLIENT_TIMEOUT_S).connect()
            pool = gen.SessionPool(mix["params"], seed, c)
            group = client.register_batch(TENANT, pool.capacity)

            def cycle() -> None:
                envs, _, arrived, departed = pool.step()
                t_obs = time.perf_counter()
                group.observe(EnvArrays(*envs.T), arrived=np.nonzero(arrived)[0],
                              departed=np.nonzero(departed)[0])
                client.tick()
                (report,) = group.drain()
                records[c].append({
                    "t_obs": t_obs, "t_rep": time.perf_counter(), "envs": envs,
                    "arrived": arrived, "departed": departed, "due": report["due"],
                    "active": report["active"], "degraded": report["degraded"],
                    "min_cut": np.asarray(report["min_cut"], np.float64),
                })

            for _ in range(mix["warmup_ticks"]):
                cycle()
            barrier.wait()
            while not stop.is_set():
                cycle()
            client.close()
        except BaseException as err:  # noqa: BLE001 — reported by the main thread
            errors.append(err)
            stop.set()
            barrier.abort()

    threads = [threading.Thread(target=loop, args=(c,), daemon=True) for c in range(conns)]
    window.traffic()
    for t in threads:
        t.start()
    try:
        barrier.wait()
    except threading.BrokenBarrierError:
        pass
    if not errors:
        window.open()
        while time.perf_counter() < window.t1 and not errors:
            window.poll(time.perf_counter())
            time.sleep(0.01)
        window.close()
    stop.set()
    for t in threads:
        t.join(CLIENT_TIMEOUT_S)
    if errors:
        raise errors[0]
    return {"records": records}


# ----------------------------------------------------------------------
# metrics
# ----------------------------------------------------------------------


def percentile(values, q: float) -> float:
    import numpy as np

    # an observed value, so that a failed request (inf) stays inf
    return float(np.percentile(np.asarray(values, np.float64), q, method="inverted_cdf"))


def open_loop_metrics(run: dict, window: Window) -> tuple[dict, int, int, dict]:
    import numpy as np

    wd = run["window_due"] & (np.arange(len(run["window_due"])) < run["submitted"])
    failed_idx = {r["index"] for r in run["replies"] if r["failed"]}
    lat = (run["t_rep"] - run["due_abs"]) * 1e3
    failed = np.zeros(len(lat), bool)
    failed[list(failed_idx)] = True
    lat_w = np.where(np.isnan(lat) | failed, np.inf, lat)[run["window_due"]]
    in_window = (run["t_rep"] >= window.t0) & (run["t_rep"] < window.t1)
    late = (run["t_sub"] - run["due_abs"])[wd] * 1e3
    in_ticks = [r for t0, _, r in run["ticks"] if window.t0 <= t0 < window.t1]
    solved = [t["solved"] for t in in_ticks if t["solved"]]
    metrics = {
        "placement_p50_ms": percentile(lat_w, 50) if lat_w.size else math.inf,
        "placement_p95_ms": percentile(lat_w, 95) if lat_w.size else math.inf,
        "replies_per_s": float(np.count_nonzero(in_window & ~failed)) / window.seconds,
    }
    attempted = int(run["window_due"].sum())
    n_failed = int(np.count_nonzero(~np.isfinite(lat_w)))
    diag = {
        "window_requests": attempted,
        "generator_late_p95_ms": percentile(late, 95) if late.size else None,
        "ticks_in_window": len(in_ticks),
        "solved_per_tick": [int(np.percentile(solved, q)) for q in (0, 50, 100)] if solved else None,
        "queue_depth_max": max((t["queue_depth"] for t in in_ticks), default=None),
    }
    return metrics, attempted, n_failed, diag


def session_metrics(run: dict, window: Window) -> tuple[dict, int, int, dict]:
    served = [r for rs in run["records"] for r in rs if window.t0 <= r["t_rep"] < window.t1]
    attempted = sum(r["active"] for r in served)
    failed = sum(r["degraded"] for r in served)
    metrics = {"session_obs_per_s": (attempted - failed) / window.seconds}
    return metrics, attempted, failed, {"cycles_in_window": len(served)}


def per_layer_metrics(spec: dict, profile: dict, server: dict, run: dict, window: Window,
                      out: pathlib.Path) -> tuple[dict, dict, dict]:
    """Per-layer metrics from the server's spans and compile events and the
    device trace; returns (metrics, device extras, breakdown)."""
    from bench import trace

    marks = server["marks"]
    s0, s1 = marks["start"], marks["end"]
    ticks = [r for t0, _, r in run.get("ticks", []) if window.t0 <= t0 < window.t1]
    xplane = trace.xplane_file(out / "trace")
    dev = trace.device_trace(xplane) if xplane else None
    trace_span = (marks.get("trace_start", s0), marks.get("trace_end", s1))
    ctx = {
        "window": (s0, s1), "window_s": s1 - s0,
        "trace_span": trace_span, "trace_window_s": trace_span[1] - trace_span[0],
        "spans": server["spans"],
        "ticks": ticks,
        "compiles": [e for e in server["lowerings"] if s0 <= e[0] < s1],
        "device": dev,
        "device_kind": server["device"]["kind"],
        "profile_n": len(profile["t_local"]),
        "profile_pinned": int((~profile["offloadable"]).sum()),
    }
    metrics = {}
    for m in spec["per_layer"]:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py", f"bench_metric_{m['name']}")
        value = reader.read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    extras, breakdown = {}, {}
    if dev is not None and dev["ops"]:
        extras = {"busy_s": trace.busy_seconds(dev["ops"]) / dev["planes"],
                  "window_s": ctx["trace_window_s"]}
        breakdown = {"device_ops": trace.top_ops(dev["ops"]), "idle_gaps": trace.idle_gaps(dev["ops"])}
    return metrics, extras, breakdown


# ----------------------------------------------------------------------
# one run
# ----------------------------------------------------------------------


def on_chip(device: dict, chips: int) -> bool:
    return device.get("platform") == "tpu" and device.get("count", 0) >= chips


def run_cell(workload: str, seed: int, seconds: float, traced: bool, *,
             patch: str | None = None) -> dict:
    """One run of ``workload``; returns the result dict (with ``checks``).

    It runs whatever device the server finds; ``main`` refuses a result
    from anything but the TPU chips the cell asks for."""
    import numpy as np

    from bench import check

    spec = cell_spec(workload)
    cell, mix, config = spec["cell"], spec["mix"], spec["config"]
    gen = load_module(BENCH / "generators" / f"{mix['generator']}.py", f"bench_gen_{mix['generator']}")
    out = ROOT / "bench_out" / workload
    shutil.rmtree(out, ignore_errors=True)
    out.mkdir(parents=True)
    warm = dict(mix["warm"])
    if mix.get("budget"):
        # a tick flushes at most ``budget`` graphs: warm every batch size up to it
        if "batches" in warm:
            raise ValueError(f"{cell['traffic']}: a mix with a budget warms 1..budget; give no batches")
        warm["batches"] = [1, mix["budget"]]
    env = dict(CHILD_ENV, PYTHONPATH=os.pathsep.join(filter(None, [str(ROOT / "src"), CHILD_ENV.get("PYTHONPATH")])))
    server = Server({
        "config": str(spec["config_file"]), "tenant": TENANT, "out": str(out),
        "trace": traced, "warm": warm, "patch": patch,
    }, out, env)
    try:
        dev = server.device or {}
        print(f"server {json.dumps(dev)} {server.ready_line}", file=sys.stderr, flush=True)
        window = Window(server, seconds, mix["trace"] if traced else None)
        address = ("tcp", "127.0.0.1", server.port)
        if mix["loop"] == "open":
            run = drive_open(address, mix, gen, seed, window)
        else:
            run = drive_sessions(address, mix, gen, seed, window)
        setup_s = window.t0 - T_START
        result_server = server.stop()
    except BaseException:
        server.kill()
        raise
    profile = load_module(BENCH / "profiles" / f"{config['profile']['kind']}.py", "bench_profile").build(
        config["profile"])
    if mix["loop"] == "open":
        e2e, attempted, failed, diag = open_loop_metrics(run, window)
        got = [r for r in run["replies"] if not r["failed"]]
        numbers = check.check_requests(profile, got, sample=mix["check_sample"], seed=seed)
        numbers["missing"] = int(np.count_nonzero(np.isnan(run["t_rep"][run["window_due"]])))
    else:
        e2e, attempted, failed, diag = session_metrics(run, window)
        numbers = check.check_sessions(profile, run["records"], threshold=mix["threshold"],
                                       min_interval=mix["min_interval"])
    e2e["setup_s"] = setup_s
    limits = spec["limits"]
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    device = {**result_server["device"], "memory_peak_bytes": result_server["memory_peak_bytes"]}
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    breakdown = None
    if traced:
        metrics, extras, breakdown = per_layer_metrics(spec, profile, result_server, run, window, out)
        device.update(extras)
    else:
        units = {m["name"]: m["unit"] for m in spec["end_to_end"]}
        metrics = {k: {"value": v, "unit": units[k]} for k, v in e2e.items() if k in units}
    result["metrics"] = metrics
    result["device"] = device
    if breakdown:
        result["breakdown"] = breakdown
    diag.update(checked=numbers.get("checked"), warm=server.ready_line,
                compiles_in_window=sum(result_server["marks"]["start"] <= e[0] < result_server["marks"]["end"]
                                       for e in result_server["lowerings"]))
    result["diagnostics"] = diag
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    except Exception as err:  # noqa: BLE001 — no result without a finished run
        print(f"FAIL {type(err).__name__}: {err}", file=sys.stderr, flush=True)
        return 1
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr, flush=True)
    chips = cell_spec(args.workload)["cell"]["chips"]
    if not on_chip(result["device"], chips):
        print(f"FAIL no result: the server ran on {json.dumps(result['device'])}, "
              f"the cell needs {chips} TPU chip(s)", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(result), flush=True)
    return 0


# the server gets this process's environment as it was given; this
# process and its client connections stay off the chip
CHILD_ENV = dict(os.environ)
if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    sys.exit(main())
