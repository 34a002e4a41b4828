"""Seeded chaos soak: a 50-input map under 5% injected UNAVAILABLE on every
data-plane RPC plus one mid-run worker preemption must complete with zero
lost results (ISSUE 1 acceptance run; the standing robustness harness every
future PR can soak against).

Run explicitly: `pytest -m chaos` (or `-m slow`).
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import time

import pytest

SOAK_SEED = 42

# 5% UNAVAILABLE on the whole data plane: container pull/push, both map
# planes, single-call attempts, and the blob store's HTTP routes.
DATA_PLANE_RPCS = [
    "FunctionGetInputs",
    "FunctionPutOutputs",
    "FunctionPutInputs",
    "FunctionGetOutputs",
    "FunctionMap",
    "MapStartOrContinue",
    "MapAwait",
    "AttemptStart",
    "AttemptAwait",
    "BlobPut",
    "BlobGet",
]


def _soak_policy():
    from modal_tpu.chaos import ChaosEvent, ChaosPolicy

    return ChaosPolicy(
        seed=SOAK_SEED,
        error_rates={rpc: 0.05 for rpc in DATA_PLANE_RPCS},
        events=[
            # preempt worker 0 once the map is ~1/5 done (outputs are the
            # deterministic clock of a map run)
            ChaosEvent(kind="worker_preempt", after_outputs=10, worker_index=0, grace_s=5.0),
        ],
    )


@pytest.fixture
def chaotic_supervisor(tmp_path, monkeypatch):
    from modal_tpu._utils.async_utils import synchronizer
    from modal_tpu.client import _Client
    from modal_tpu.server.supervisor import LocalSupervisor

    monkeypatch.setenv("MODAL_TPU_STATE_DIR", str(tmp_path / "state"))
    sup = LocalSupervisor(
        num_workers=2,
        state_dir=str(tmp_path / "state"),
        worker_chips=8,
        worker_tpu_type="local-sim",
        chaos=_soak_policy(),
    )
    synchronizer.run(sup.start())
    monkeypatch.setenv("MODAL_TPU_SERVER_URL", f"grpc://127.0.0.1:{sup.port}")
    _Client.set_env_client(None)
    try:
        yield sup
    finally:
        env_client = _Client._client_from_env
        if env_client is not None and not env_client._closed:
            env_client._close()
        _Client.set_env_client(None)
        synchronizer.run(sup.stop())


@pytest.mark.slow
@pytest.mark.chaos
def test_soak_map_survives_faults_and_preemption(chaotic_supervisor):
    import modal_tpu

    sup = chaotic_supervisor
    app = modal_tpu.App("chaos-soak")

    def square(x):
        import time as _t

        _t.sleep(0.05)
        return x * x

    f = app.function(serialized=True)(square)
    t0 = time.monotonic()
    with app.run():
        results = sorted(f.map(range(50)))
    elapsed = time.monotonic() - t0
    assert results == [x * x for x in range(50)], "lost or corrupted results under chaos"
    # the chaos actually happened: faults were injected and the preemption
    # event fired (a quiet run would prove nothing)
    assert sum(sup.chaos.injected.values()) > 0, "no faults injected — soak was a no-op"
    assert all(ev.fired for ev in sup.chaos.events), "worker preemption never fired"
    print(
        f"soak: {elapsed:.1f}s, {sum(sup.chaos.call_counts.values())} RPCs, "
        f"{sum(sup.chaos.injected.values())} faults injected, "
        f"fault log head: {sup.chaos.fault_log[:8]}"
    )


def _count_journal_records(state_dir: str, record_type: str) -> int:
    import glob
    import json as _json

    n = 0
    for path in glob.glob(os.path.join(state_dir, "journal", "segment-*.jsonl")):
        try:
            with open(path) as f:
                for line in f:
                    try:
                        if _json.loads(line).get("t") == record_type:
                            n += 1
                    except _json.JSONDecodeError:
                        continue
        except OSError:
            continue
    return n


def _spawn_supervisor(port: int, state_dir: str, tmp_path) -> "subprocess.Popen":
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["MODAL_TPU_JAX_PLATFORM"] = "cpu"
    env["MODAL_TPU_AUTO_LOCAL_SERVER"] = "0"
    env["MODAL_TPU_STATE_DIR"] = state_dir
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    log = open(os.path.join(str(tmp_path), f"supervisor-{time.time_ns()}.log"), "wb")
    return subprocess.Popen(
        [
            sys.executable,
            "-m",
            "modal_tpu.server",
            "--port",
            str(port),
            "--workers",
            "2",
            "--state-dir",
            state_dir,
        ],
        env=env,
        stdout=log,
        stderr=log,
        start_new_session=True,
    )


def _wait_port(port: int, timeout_s: float = 60.0) -> None:
    import socket

    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            socket.create_connection(("127.0.0.1", port), timeout=0.5).close()
            return
        except OSError:
            time.sleep(0.2)
    raise TimeoutError(f"control plane on port {port} never came up")


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.recovery
def test_kill9_supervisor_mid_map_recovers_exactly_once(tmp_path, monkeypatch):
    """ISSUE 4 acceptance: a kill -9'd supervisor recovers from its journal —
    an in-flight 50-input map resumes after the restart (same port, same
    state dir) and delivers every output exactly once. The client is NOT
    restarted: its retry loops must ride the outage transparently (channel
    re-dial + call-resume by function_call_id)."""
    import threading

    import modal_tpu
    from modal_tpu._utils.grpc_utils import find_free_port
    from modal_tpu.client import _Client

    state_dir = str(tmp_path / "state")
    port = find_free_port()
    proc = _spawn_supervisor(port, state_dir, tmp_path)
    procs = [proc]
    try:
        _wait_port(port)
        monkeypatch.setenv("MODAL_TPU_SERVER_URL", f"grpc://127.0.0.1:{port}")
        _Client.set_env_client(None)

        app = modal_tpu.App("kill9-soak")

        def slow_square(x):
            import time as _t

            _t.sleep(0.15)
            return x * x

        f = app.function(serialized=True)(slow_square)
        results: list = []
        errors: list = []

        def run_map():
            try:
                with app.run():
                    results.extend(f.map(range(50)))
            except BaseException as exc:  # noqa: BLE001
                errors.append(exc)

        t = threading.Thread(target=run_map)
        t.start()
        # kill once the map is genuinely mid-flight: >= 8 outputs journaled
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            if _count_journal_records(state_dir, "output") >= 8:
                break
            if not t.is_alive():
                pytest.fail(f"map finished/died before the kill window (errors={errors})")
            time.sleep(0.25)
        else:
            pytest.fail("map never produced enough outputs to kill mid-flight")
        os.killpg(proc.pid, signal.SIGKILL)  # the whole process group: workers too
        proc.wait(timeout=30)
        # restart on the same port + state dir: recovery replays the journal
        proc2 = _spawn_supervisor(port, state_dir, tmp_path)
        procs.append(proc2)
        _wait_port(port)
        t.join(timeout=300)
        assert not t.is_alive(), "map never completed after supervisor restart"
        assert not errors, f"map failed across the kill -9: {errors}"
        assert len(results) == 50, f"expected 50 outputs exactly once, got {len(results)}"
        assert sorted(results) == [x * x for x in range(50)], "lost/duplicated/corrupted results"
    finally:
        env_client = _Client._client_from_env
        if env_client is not None and not env_client._closed:
            env_client._close()
        _Client.set_env_client(None)
        for p in procs:
            try:
                os.killpg(p.pid, signal.SIGKILL)
            except OSError:
                pass
            try:
                p.wait(timeout=10)
            except Exception:  # noqa: BLE001
                pass


@pytest.mark.slow
@pytest.mark.chaos
def test_soak_fault_sequence_is_seed_reproducible():
    """Same seed + same per-RPC call counts ⇒ byte-identical fault decisions.
    Replays the per-RPC call pattern of a soak policy against a fresh policy
    with the same seed and checks the injected sequence matches exactly."""
    a, b = _soak_policy(), _soak_policy()
    # synthetic but realistic call mix (counts differ per RPC on purpose)
    pattern = (
        [("FunctionGetInputs", 120), ("FunctionPutOutputs", 60), ("MapStartOrContinue", 9)]
        + [("MapAwait", 75), ("BlobPut", 12), ("BlobGet", 12), ("WorkerHeartbeat", 40)]
    )
    for policy in (a, b):
        for rpc, n in pattern:
            for _ in range(n):
                policy.decide(rpc)
    assert a.fault_log == b.fault_log and a.fault_log, "seeded chaos must be reproducible"
    assert a.injected == b.injected


# ---------------------------------------------------------------------------
# Sharded control plane (server/shards.py, ISSUE 16)
# ---------------------------------------------------------------------------


def _spawn_sharded_supervisor(port: int, state_dir: str, tmp_path) -> "subprocess.Popen":
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["MODAL_TPU_JAX_PLATFORM"] = "cpu"
    env["MODAL_TPU_AUTO_LOCAL_SERVER"] = "0"
    env["MODAL_TPU_STATE_DIR"] = state_dir
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    log = open(os.path.join(str(tmp_path), f"sharded-{time.time_ns()}.log"), "wb")
    return subprocess.Popen(
        [
            sys.executable,
            "-m",
            "modal_tpu.server",
            "--port",
            str(port),
            "--workers",
            "3",
            "--state-dir",
            state_dir,
            "--shards",
            "3",
            "--subprocess-shards",
        ],
        env=env,
        stdout=log,
        stderr=log,
        start_new_session=True,
    )


def _kill9_shard_soak(tmp_path, monkeypatch, delete_journal_dir: bool = False):
    """Shared soak body (ISSUE 16 / ISSUE 19): 3 OS-process shards behind the
    placement director; the shard owning the app's partition is kill -9'd
    (real SIGKILL, whole process group) mid-way through a 100k-input
    placement storm. With ``delete_journal_dir`` the victim's journal
    directory is deleted right after the kill — the disk is gone, not just
    the process — so recovery MUST come from the survivors' replica streams.
    Either way the director must fence the victim, a sibling must rehydrate
    its partition, and every input must land exactly once — the client's
    idempotent re-sends dedupe against the recovered state, and no placement
    may be lost. The client is never restarted: its retry loops ride
    UNAVAILABLE -> shard-map refresh -> redial."""
    import json as _json
    import shutil
    import threading
    import zlib

    import modal_tpu
    from modal_tpu._utils.async_utils import synchronizer
    from modal_tpu._utils.grpc_utils import find_free_port, retry_transient_errors
    from modal_tpu.client import _Client
    from modal_tpu.proto import api_pb2

    TOTAL_INPUTS = 100_000
    NUM_CALLS = 10
    BATCH = 250

    state_dir = str(tmp_path / "state")
    port = find_free_port()
    proc = _spawn_sharded_supervisor(port, state_dir, tmp_path)
    try:
        _wait_port(port, timeout_s=120.0)
        monkeypatch.setenv("MODAL_TPU_SERVER_URL", f"grpc://127.0.0.1:{port}")
        _Client.set_env_client(None)

        # an app name whose crc32 lands on partition 1 — shard 1 is the victim
        suffix = 0
        while zlib.crc32(f"shard-soak-{suffix}".encode()) % 3 != 1:
            suffix += 1
        app = modal_tpu.App(f"shard-soak-{suffix}")

        def noop(x):
            return 0

        f = app.function(serialized=True)(noop)
        with app.run():
            function_id = f.object_id
            client = _Client._client_from_env
            assert type(client._stub).__name__ == "ShardRouterStub", "router not engaged"

            placed = {"n": 0}
            payload = b"x" * 8
            per_call = TOTAL_INPUTS // NUM_CALLS

            async def _storm() -> list:
                call_ids = []
                for _ in range(NUM_CALLS):
                    call = await retry_transient_errors(
                        client.stub.FunctionMap,
                        api_pb2.FunctionMapRequest(
                            function_id=function_id,
                            function_call_type=api_pb2.FUNCTION_CALL_TYPE_MAP,
                        ),
                        max_retries=None,
                        total_timeout=180.0,
                    )
                    call_ids.append(call.function_call_id)
                    idx = 0
                    while idx < per_call:
                        chunk = min(BATCH, per_call - idx)
                        await retry_transient_errors(
                            client.stub.FunctionPutInputs,
                            api_pb2.FunctionPutInputsRequest(
                                function_id=function_id,
                                function_call_id=call.function_call_id,
                                inputs=[
                                    api_pb2.FunctionPutInputsItem(
                                        idx=idx + k, input=api_pb2.FunctionInput(args=payload)
                                    )
                                    for k in range(chunk)
                                ],
                            ),
                            # unlimited retries under a wall-clock budget: the
                            # outage window is the whole fence+replay takeover,
                            # far longer than a default backoff ladder
                            max_retries=None,
                            total_timeout=180.0,
                        )
                        idx += chunk
                        placed["n"] += chunk
                return call_ids

            storm_result: dict = {}
            storm_errors: list = []

            def run_storm():
                try:
                    storm_result["call_ids"] = synchronizer.run(_storm())
                except BaseException as exc:  # noqa: BLE001
                    storm_errors.append(exc)

            t = threading.Thread(target=run_storm)
            t.start()
            # kill the victim once the storm is genuinely mid-flight
            deadline = time.monotonic() + 300
            while time.monotonic() < deadline:
                if placed["n"] >= TOTAL_INPUTS // 3:
                    break
                if not t.is_alive():
                    pytest.fail(f"storm died before the kill window (errors={storm_errors})")
                time.sleep(0.1)
            else:
                pytest.fail("storm never reached the kill window")
            with open(os.path.join(state_dir, "shards.json")) as fh:
                victim = next(s for s in _json.load(fh)["shards"] if s["index"] == 1)
            assert victim["pid"] > 0, "subprocess shard pid not persisted"
            os.killpg(victim["pid"], signal.SIGKILL)
            if delete_journal_dir:
                # the disk dies with the process: nothing left to replay from
                deadline = time.monotonic() + 30
                while time.monotonic() < deadline:
                    try:
                        os.kill(victim["pid"], 0)
                    except OSError:
                        break  # corpse reaped — its file handles are gone
                    time.sleep(0.1)
                shutil.rmtree(
                    os.path.join(state_dir, "shard-1", "journal"), ignore_errors=True
                )
            t.join(timeout=600)
            assert not t.is_alive(), "placement storm never completed after the shard kill"
            assert not storm_errors, f"storm failed across the kill -9: {storm_errors}"
            assert placed["n"] == TOTAL_INPUTS

            # exactly-once: the successor's REPLAYED state counts every input
            # once — a lost placement or a dedupe miss both show up here
            listed = synchronizer.run(
                retry_transient_errors(
                    client.stub.FunctionCallList,
                    api_pb2.FunctionCallListRequest(function_id=function_id),
                    max_retries=8,
                )
            )
            by_id = {c.function_call_id: c.num_inputs for c in listed.calls}
            ours = [by_id.get(cid, 0) for cid in storm_result["call_ids"]]
            assert sum(ours) == TOTAL_INPUTS, f"placements lost/duplicated: {ours}"
            assert all(n == per_call for n in ours), f"per-call counts off: {ours}"

            # the takeover really happened, via the dead shard's journal
            with open(os.path.join(state_dir, "director.json")) as fh:
                topo = _json.load(fh)
            assert topo["epoch"] >= 2, "no epoch bump — takeover never ran"
            assert topo["assignments"][1] != 1, "partition 1 still on the dead shard"
            assert topo["takeovers"] and topo["takeovers"][-1]["report"]["records_applied"] > 0
            if delete_journal_dir:
                # the journal dir was deleted: only the quorum replica path
                # can explain a successful rehydration
                assert topo["takeovers"][-1]["mode"] == "replica", (
                    "takeover claims a journal replay from a deleted directory"
                )
    finally:
        env_client = _Client._client_from_env
        if env_client is not None and not env_client._closed:
            env_client._close()
        _Client.set_env_client(None)
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except OSError:
            pass
        try:
            proc.wait(timeout=10)
        except Exception:  # noqa: BLE001
            pass
        # shard subprocesses are their own sessions: reap via shards.json
        try:
            with open(os.path.join(state_dir, "shards.json")) as fh:
                for s in __import__("json").load(fh)["shards"]:
                    if s.get("pid"):
                        try:
                            os.killpg(s["pid"], signal.SIGKILL)
                        except OSError:
                            pass
        except OSError:
            pass


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.recovery
def test_kill9_shard_mid_100k_map_takeover_exactly_once(tmp_path, monkeypatch):
    """ISSUE 16 acceptance soak: process loss only — the corpse's disk
    survives, and either recovery path (replica stream or corpse journal)
    may serve the rehydration."""
    _kill9_shard_soak(tmp_path, monkeypatch, delete_journal_dir=False)


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.recovery
def test_kill9_and_delete_journal_dir_quorum_recovery(tmp_path, monkeypatch):
    """ISSUE 19 acceptance soak: kill -9 the home shard AND delete its
    journal directory mid-storm. Zero acked-record loss and exactly-once
    placement counts must come entirely from the surviving shards' quorum
    replica streams (takeover mode == "replica")."""
    _kill9_shard_soak(tmp_path, monkeypatch, delete_journal_dir=True)
