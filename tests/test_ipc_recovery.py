"""Cross-process serving plane: parity, SIGKILL warm restart, idempotency.

The headline contract (ISSUE 10): a solver process SIGKILLed mid-tick and
restarted against its placement-cache snapshot + journal tail must
reproduce the same replies BIT-identically (``==``, no tolerances) on the
reference backend, with cache stats never double-counted.  Everything
here drives the real ``examples/serve_broker.py`` entrypoint in real
subprocesses over real unix sockets; reads are timeout-bounded so a
protocol hang is a failure, not a CI deadlock.
"""

import json
import os
import pathlib
import signal
import socket
import struct
import subprocess
import sys
import time

import numpy as np
import pytest

from repro.core import AppProfile, Environment, ResponseTimeModel, random_wcg
from repro.core.placement_cache import SNAPSHOT_VERSION
from repro.service import (
    BrokerClient,
    BrokerSession,
    OffloadBroker,
    RetryPolicy,
    unix_address,
)
from repro.service.wire import FrameStream, PROTOCOL_VERSION, env_to_wire
from repro.service.workload import environment_trace

pytestmark = pytest.mark.service

REPO = pathlib.Path(__file__).resolve().parent.parent
SERVER = REPO / "examples" / "serve_broker.py"
TIMEOUT = 30.0
NODES, SEED = 12, 0


def _profile() -> AppProfile:
    # must mirror examples/serve_broker.py demo_tenant: both processes
    # build the tenant independently from the same seed
    return AppProfile.from_wcg_times(
        random_wcg(NODES, rng=np.random.default_rng(SEED))
    )


def _start_server(tmp: pathlib.Path, *, kill_at_tick=None,
                  snapshot_every=7) -> subprocess.Popen:
    """Launch the solver process and block until its READY barrier."""
    cmd = [
        sys.executable, str(SERVER),
        "--socket", str(tmp / "solver.sock"),
        "--journal", str(tmp / "journal.jsonl"),
        "--snapshot-dir", str(tmp / "snaps"),
        "--snapshot-every", str(snapshot_every),
        "--nodes", str(NODES), "--seed", str(SEED),
        "--backend", "reference",
    ]
    if kill_at_tick is not None:
        cmd += ["--kill-at-tick", str(kill_at_tick)]
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True, env=env)
    deadline = time.monotonic() + TIMEOUT
    for line in proc.stdout:
        if line.startswith("READY"):
            return proc
        if time.monotonic() > deadline:
            break
    proc.kill()
    raise RuntimeError("server never became READY")


def _client(tmp: pathlib.Path, name="drv") -> BrokerClient:
    return BrokerClient(
        unix_address(tmp / "solver.sock"),
        tenants={"app": (_profile(), ResponseTimeModel())},
        client=name,
        timeout=TIMEOUT,
        retry=RetryPolicy(max_retries=2, base_backoff_s=0.01,
                          max_backoff_s=0.05),
    )


def _sig(reply) -> tuple:
    """Bit-exact signature of a BrokerReply — ``==`` means identical."""
    res = reply.result
    return (
        None
        if res is None
        else (
            struct.pack("<d", res.min_cut),
            np.asarray(res.local_mask, bool).tobytes(),
        ),
        reply.cache_hit,
        reply.coalesced,
        reply.tick,
        reply.rejected,
        reply.degraded,
        reply.timed_out,
    )


def _drive(client, envs, sigs, start=0, until=None):
    """submit+tick loop; ``sigs[i]`` gets request i's reply signature."""
    for i, env in enumerate(envs[start:until], start):
        fut = client.submit("app", env)
        client.tick()
        assert fut.done, f"request {i} unresolved after its tick"
        sigs[i] = _sig(fut.result)


TRACE = environment_trace(24, seed=11)
KILL_I = 15            # the submit whose tick the solver dies inside
KILL_TICK = KILL_I + 1


def test_sigkill_warm_restart_replies_bit_identical(tmp_path):
    # --- run A: uninterrupted --------------------------------------------
    dir_a = tmp_path / "a"
    dir_a.mkdir()
    proc = _start_server(dir_a)
    try:
        client = _client(dir_a)
        client.connect()
        uninterrupted: dict[int, tuple] = {}
        _drive(client, TRACE, uninterrupted)
        client.close()
    finally:
        proc.kill()
        proc.wait()

    # --- run B: SIGKILL mid-tick, restart, warm-start, continue ----------
    dir_b = tmp_path / "b"
    dir_b.mkdir()
    proc = _start_server(dir_b, kill_at_tick=KILL_TICK)
    crashed: dict[int, tuple] = {}
    client = _client(dir_b)
    client.connect()
    _drive(client, TRACE, crashed, until=KILL_I)
    # the killing tick: the solver SIGKILLs itself after mutating broker
    # state, before the journal tick append — the torn write
    fut = client.submit("app", TRACE[KILL_I])
    with pytest.raises(ConnectionError):
        client.tick()
    proc.wait(timeout=TIMEOUT)
    assert proc.returncode == -signal.SIGKILL
    # the restart warm-starts from a packed (version 2) cache snapshot
    snap = json.loads((dir_b / "snaps" / "app.snapshot.json").read_text())
    assert snap["version"] == SNAPSHOT_VERSION == 2
    assert snap["count"] > 0 and snap["meta"]["tick"] == 14

    proc = _start_server(dir_b)  # warm restart against snapshot + journal
    try:
        # the retried tick: reconnect resubmits the unresolved window and
        # the exactly-once logic re-runs (or skips) the interrupted tick
        client.tick()
        assert fut.done, "unresolved future survived the warm restart"
        crashed[KILL_I] = _sig(fut.result)
        assert client.resubmitted >= 1  # the window really was replayed
        _drive(client, TRACE, crashed, start=KILL_I + 1)

        # THE acceptance criterion: every reply — pre-crash, the
        # interrupted tick's, and the continuation — bit-identical
        assert crashed == uninterrupted

        # --- cache stats never double-counted on resubmission ------------
        tel0 = client.telemetry()["caches"]["app"]
        raw = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        raw.settimeout(TIMEOUT)
        raw.connect(str(dir_b / "solver.sock"))
        stream = FrameStream(raw)
        stream.send({"type": "hello", "version": PROTOCOL_VERSION,
                     "encoding": "json", "client": "dup"})
        assert stream.recv(TIMEOUT)["type"] == "hello_ok"
        # resubmit the interrupted request's id: served from the reply
        # log — reply first, then a replayed ack
        stream.send({"type": "submit", "id": f"drv-{KILL_I + 1}",
                     "tenant": "app", "env": env_to_wire(TRACE[KILL_I]),
                     "lane": "user", "deadline": None})
        reply = stream.recv(TIMEOUT)
        assert reply["type"] == "reply" and reply["tick"] == KILL_TICK
        ack = stream.recv(TIMEOUT)
        assert ack["type"] == "submit_ok" and ack["replayed"] is True
        stream.send({"type": "bye"})
        stream.close()
        tel1 = client.telemetry()["caches"]["app"]
        assert tel1 == tel0, "resubmission touched cache stats"
        client.close()
    finally:
        proc.kill()
        proc.wait()


def test_cross_process_session_parity(tmp_path):
    """BrokerSession over a real subprocess solver == the same session
    against an in-process broker, event for event, bit for bit."""
    trace = environment_trace(20, seed=7)

    broker = OffloadBroker(backend="reference", clock=lambda: 0.0)
    broker.register("app", _profile(), ResponseTimeModel())
    local = BrokerSession(broker, "app")
    local_events = []
    for env in trace:
        local.observe(env)
        broker.tick()
        local_events.extend(local.drain())

    proc = _start_server(tmp_path)
    try:
        client = _client(tmp_path, name="sess")
        client.connect()
        remote = BrokerSession(client, "app")  # the unmodified class
        remote_events = []
        for env in trace:
            remote.observe(env)
            client.tick()
            remote_events.extend(remote.drain())
        client.close()
    finally:
        proc.kill()
        proc.wait()

    assert len(remote_events) == len(local_events) == len(trace)
    for r, l in zip(remote_events, local_events):
        assert r.env == l.env
        assert r.partial_cost == l.partial_cost          # ==, no tolerance
        assert r.gain == l.gain
        assert r.repartitioned == l.repartitioned
        assert r.cache_hit == l.cache_hit
        assert r.result.min_cut == l.result.min_cut
        assert np.array_equal(r.result.local_mask, l.result.local_mask)


def test_reconnect_against_live_server_is_idempotent(tmp_path):
    """Dropping the connection mid-window and reconnecting to the SAME
    server must not double-submit: the inflight dedup path."""
    proc = _start_server(tmp_path)
    try:
        client = _client(tmp_path, name="flaky")
        client.connect()
        futs = [client.submit("app", Environment.symmetric(bw, 3.0))
                for bw in (8.0, 1.2, 0.3)]
        # simulate a dropped transport (the socket dies, the server and
        # its queue survive)
        client._stream.close()
        client._stream = None
        client.connect()           # resubmits all three; server dedups
        assert client.resubmitted == 3
        client.drain(max_ticks=8)
        assert all(f.done for f in futs)
        tel = client.telemetry()
        assert tel["summary"]["requests"] == 3, (
            "resubmission re-queued an already-queued id"
        )
        client.close()
    finally:
        proc.kill()
        proc.wait()


def test_ipc_serves_llm_stage_profile(tmp_path, qwen_stages):
    """The serving plane is model-agnostic: an LLM stage-graph tenant
    (the shared qwen fixture) placed over the wire matches in-process,
    bit for bit."""
    import threading

    from repro.core.placement import TPUV5E_TIER, build_stage_wcg
    from repro.service import SolverServer

    profile = AppProfile.from_wcg_times(
        build_stage_wcg(qwen_stages, TPUV5E_TIER, TPUV5E_TIER)
    )
    cm = ResponseTimeModel()
    envs = [Environment.symmetric(bw, 2.0) for bw in (4.0, 0.5, 4.0)]

    def llm_broker():
        b = OffloadBroker(backend="reference", clock=lambda: 0.0)
        b.register("llm", profile, cm)
        return b

    local = llm_broker()
    want = []
    for env in envs:
        fut = local.submit("llm", env)
        local.tick()
        want.append(_sig(fut.result))

    server = SolverServer(
        llm_broker(),
        address=unix_address(tmp_path / "llm.sock"),
        journal_path=tmp_path / "llm.jsonl",
        snapshot_dir=tmp_path / "llm_snaps",
    )
    server.bind()
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        client = BrokerClient(
            unix_address(tmp_path / "llm.sock"),
            tenants={"llm": (profile, cm)},
            client="llm-drv", timeout=TIMEOUT,
        )
        client.connect()
        got = []
        for env in envs:
            fut = client.submit("llm", env)
            client.tick()
            got.append(_sig(fut.result))
        client.close()
    finally:
        server.stop()
        thread.join(timeout=TIMEOUT)

    assert got == want
    assert got[2][1] is True                 # the revisit is a cache hit
