"""Horizontally-sharded control plane (server/shards.py, ISSUE 16).

Routing units, shard-map hello + client router engagement, journal-fed
takeover with exactly-once maps, epoch fencing of false deaths, director
restart mid-session, chaos knob parsing/off-toggles, the shard-aware journal
CLI, and the MODAL_TPU_SHARDS=1 monolith degradation."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- routing units (no server) -------------------------------------------------


def test_partition_embedded_ids_roundtrip():
    from modal_tpu.server import state as server_state

    for namespace in (0, 1, 2, 7):
        obj_id = server_state.make_id("fu", namespace=namespace)
        assert server_state.partition_of_id(obj_id) == namespace
    # partition 0 ids keep the pre-sharding shape (8-digit counter, no prefix
    # arithmetic visible) — a monolith journal replays into shard 0 unchanged
    assert server_state.partition_of_id("fu-00000012") == 0
    assert server_state.partition_of_id("not-an-id") is None
    assert server_state.partition_of_id("") is None


def test_partition_for_request_id_fields_win():
    from modal_tpu._utils.shard_routing import partition_for_name, partition_for_request
    from modal_tpu.proto import api_pb2
    from modal_tpu.server.state import PARTITION_STRIDE

    fn_id = f"fu-{2 * PARTITION_STRIDE + 7:08d}"
    req = api_pb2.FunctionPutInputsRequest(function_id=fn_id)
    assert partition_for_request(req, 3) == 2
    # names route by crc32 when no id field is set
    named = api_pb2.AppCreateRequest(description="route-me")
    assert partition_for_request(named, 3) == partition_for_name("route-me", 3)
    # ids beat names when both are present
    both = api_pb2.FunctionCreateRequest(app_id=f"ap-{1 * PARTITION_STRIDE + 3:08d}")
    both.function.function_name = "shadowed"
    assert partition_for_request(both, 3) == 1
    # an out-of-range embedded partition clamps instead of indexing off the map
    wide = api_pb2.FunctionPutInputsRequest(function_id=f"fu-{7 * PARTITION_STRIDE + 1:08d}")
    assert partition_for_request(wide, 3) == 7 % 3
    # nothing routable -> None (the caller sends it to the director)
    assert partition_for_request(api_pb2.ClientHelloRequest(), 3) is None
    # single-partition planes never consult the fields
    assert partition_for_request(req, 1) == 0


# -- chaos knob parsing (satellite 1: off-toggles + malformed tokens) ---------


def test_chaos_shard_knobs_parse(monkeypatch):
    from modal_tpu.chaos import ChaosPolicy

    monkeypatch.setenv("MODAL_TPU_CHAOS", "1")
    monkeypatch.setenv("MODAL_TPU_CHAOS_SHARD_KILL_AFTER", "1:50,2:200")
    monkeypatch.setenv("MODAL_TPU_CHAOS_SHARD_PARTITION", "2:100:5.5")
    policy = ChaosPolicy.from_env()
    assert policy is not None
    kills = [e for e in policy.events if e.kind == "shard_kill"]
    parts = [e for e in policy.events if e.kind == "shard_partition"]
    assert [(e.shard_index, e.after_outputs) for e in kills] == [(1, 50), (2, 200)]
    assert [(e.shard_index, e.after_outputs, e.duration_s) for e in parts] == [(2, 100, 5.5)]


def test_chaos_shard_knobs_off_by_default(monkeypatch):
    from modal_tpu.chaos import ChaosPolicy

    # chaos master switch off -> no policy at all, whatever the shard knobs say
    monkeypatch.delenv("MODAL_TPU_CHAOS", raising=False)
    monkeypatch.setenv("MODAL_TPU_CHAOS_SHARD_KILL_AFTER", "1:50")
    assert ChaosPolicy.from_env() is None
    # chaos on with the shard knobs unset/empty -> zero shard events
    monkeypatch.setenv("MODAL_TPU_CHAOS", "1")
    monkeypatch.delenv("MODAL_TPU_CHAOS_SHARD_KILL_AFTER", raising=False)
    monkeypatch.setenv("MODAL_TPU_CHAOS_SHARD_PARTITION", "")
    policy = ChaosPolicy.from_env()
    assert policy is not None
    assert [e for e in policy.events if e.kind.startswith("shard_")] == []


def test_chaos_shard_knobs_malformed_tokens_ignored(monkeypatch):
    from modal_tpu.chaos import ChaosPolicy

    monkeypatch.setenv("MODAL_TPU_CHAOS", "1")
    monkeypatch.setenv("MODAL_TPU_CHAOS_SHARD_KILL_AFTER", "nope:x,1:25")
    monkeypatch.setenv("MODAL_TPU_CHAOS_SHARD_PARTITION", ":::")
    policy = ChaosPolicy.from_env()  # must not raise: a typo'd knob can't kill boot
    assert policy is not None
    kills = [e for e in policy.events if e.kind == "shard_kill"]
    assert [(e.shard_index, e.after_outputs) for e in kills] == [(1, 25)]
    assert [e for e in policy.events if e.kind == "shard_partition"] == []
    # bare int targets shard 1 (shard 0 is the home partition)
    monkeypatch.setenv("MODAL_TPU_CHAOS_SHARD_KILL_AFTER", "40")
    monkeypatch.setenv("MODAL_TPU_CHAOS_SHARD_PARTITION", "")
    policy = ChaosPolicy.from_env()
    (ev,) = [e for e in policy.events if e.kind == "shard_kill"]
    assert (ev.shard_index, ev.after_outputs) == (1, 40)


# -- monolith degradation (satellite 5: MODAL_TPU_SHARDS=1 == today) ----------


def test_monolith_hello_has_no_shard_map(supervisor):
    """A LocalSupervisor (the shards=1 degradation) advertises no shard map,
    so the client keeps its plain fast-path stub — no router, no director."""
    from modal_tpu.client import _Client

    client = _Client.from_env()
    assert type(client._stub).__name__ != "ShardRouterStub"
    resp = client._stub  # fast-path or bare stub, never the router
    assert not isinstance(resp, dict)


# -- sharded plane end to end --------------------------------------------------


@pytest.fixture
def sharded(tmp_path, monkeypatch):
    """A 3-shard in-process control plane behind the placement director, one
    worker per shard, fast health loop so takeovers land within a test."""
    from modal_tpu._utils.async_utils import synchronizer
    from modal_tpu.client import _Client
    from modal_tpu.server.shards import ShardedSupervisor

    monkeypatch.setenv("MODAL_TPU_STATE_DIR", str(tmp_path / "state"))
    sup = ShardedSupervisor(
        num_shards=3,
        num_workers=3,
        state_dir=str(tmp_path / "state"),
        worker_chips=8,
        worker_tpu_type="local-sim",
        health_interval_s=0.2,
    )
    synchronizer.run(sup.start())
    monkeypatch.setenv("MODAL_TPU_SERVER_URL", sup.server_url)
    _Client.set_env_client(None)
    try:
        yield sup
    finally:
        env_client = _Client._client_from_env
        if env_client is not None and not env_client._closed:
            env_client._close()
        _Client.set_env_client(None)
        synchronizer.run(sup.stop())


def _wait_for(predicate, timeout_s: float = 15.0, what: str = "condition"):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(0.1)
    raise AssertionError(f"timed out waiting for {what}")


def test_sharded_map_kill_takeover_exactly_once(sharded):
    """The tentpole acceptance: maps route through the shard map, a kill -9
    of the app's home shard mid-session is fenced + journal-rehydrated by a
    sibling, and a subsequent map completes exactly-once on the successor."""
    import modal_tpu
    from modal_tpu._utils.async_utils import synchronizer
    from modal_tpu._utils.shard_routing import partition_for_name
    from modal_tpu.client import _Client

    app = modal_tpu.App("shard-e2e")

    def double(x):
        return x * 2

    f = app.function(serialized=True)(double)
    with app.run():
        results = sorted(f.map(range(24)))
        assert results == [x * 2 for x in range(24)], "pre-kill map lost/dup'd inputs"

    client = _Client._client_from_env
    assert type(client._stub).__name__ == "ShardRouterStub", "router not engaged at hello"
    assert len(client._stub.shard_urls) == 3

    home = partition_for_name("shard-e2e", 3)
    synchronizer.run(sharded.kill_shard(home))
    # the takeover RECORD, not just the remap: the director appends it only
    # after rehoming the workers, a few ms after assignments change
    _wait_for(
        lambda: any(e["dead_shard"] == home for e in sharded.takeover_log),
        what=f"takeover of partition {home}",
    )
    assert sharded.assignments[home] != home
    assert sharded.epoch >= 2
    (entry,) = [e for e in sharded.takeover_log if e["dead_shard"] == home]
    assert entry["report"]["records_applied"] > 0, "takeover did not replay the journal"
    # the fenced corpse can't serve its old partition at a stale epoch
    dead = sharded.shards[home]
    assert dead.fenced

    with app.run():
        results = sorted(f.map(range(10)))
        assert results == [x * 2 for x in range(10)], "post-takeover map lost/dup'd inputs"


def test_false_death_fences_before_adopt(sharded):
    """A live-but-partitioned shard (chaos shard_partition shape) is fenced
    BEFORE its journal is replayed elsewhere — the stale owner stops serving,
    so one partition never has two writers (split-brain)."""
    victim = 2
    sharded.partitioned_until[victim] = time.monotonic() + 60.0
    _wait_for(
        lambda: sharded.assignments[victim] != victim,
        what=f"false-death takeover of shard {victim}",
    )
    sup = sharded.shards[victim]
    assert sup.fenced, "survivor replayed the journal without fencing the live owner"
    assert sup.fenced_at_epoch == sharded.epoch
    # the fenced shard fails probes forever — it must NOT be re-adopted into
    # the map at its stale epoch when the partition heals
    sharded.partitioned_until[victim] = 0.0
    time.sleep(3 * sharded.health_interval_s)
    assert sharded.assignments[victim] != victim, "stale shard rejoined without fencing"


def test_director_restart_rides_client_redial(sharded):
    """Killing + restarting the director mid-session must be invisible to the
    app: unary traffic goes direct-to-shard, and the next ClientHello redial
    finds the director back on the same port."""
    import modal_tpu
    from modal_tpu._utils.async_utils import synchronizer

    app = modal_tpu.App("director-bounce")

    def inc(x):
        return x + 1

    f = app.function(serialized=True)(inc)
    with app.run():
        assert sorted(f.map(range(6))) == [x + 1 for x in range(6)]
    synchronizer.run(sharded.restart_director())
    with app.run():
        assert sorted(f.map(range(6))) == [x + 1 for x in range(6)]


def test_journal_cli_shard_aware(sharded, tmp_path):
    """`journal status` summarizes every shard journal under a sharded root;
    `journal compact` refuses while any shard is live (satellite 3)."""
    import click
    from click.testing import CliRunner

    from modal_tpu.cli.entry_point import journal_compact, journal_status

    root = str(tmp_path / "state")
    runner = CliRunner()
    res = runner.invoke(journal_status, ["--state-dir", root, "--json"])
    assert res.exit_code == 0, res.output
    payload = json.loads(res.output)
    assert len(payload["shards"]) == 3
    assert all(st["seq"] >= 0 for st in payload["shards"])
    human = runner.invoke(journal_status, ["--state-dir", root])
    assert human.exit_code == 0
    assert "3 shard journal(s)" in human.output
    # a live shard must refuse offline compaction (its open segment would race)
    res = runner.invoke(journal_compact, ["--state-dir", root])
    assert res.exit_code != 0
    assert "shard" in res.output


def test_shard_topology_persisted(sharded, tmp_path):
    """director.json / shards.json carry the routable topology (the chaos
    soak reads shard pids from here to aim its kill -9)."""
    root = str(tmp_path / "state")
    with open(os.path.join(root, "shards.json")) as fh:
        shards = json.load(fh)["shards"]
    assert len(shards) == 3
    assert all(s["url"].startswith("grpc://") and s["state_dir"] for s in shards)
    with open(os.path.join(root, "director.json")) as fh:
        director = json.load(fh)
    assert director["director"] == sharded.server_url
    assert director["epoch"] == sharded.epoch
    assert director["assignments"] == sharded.assignments


# -- scaled-down control bench (satellite 6: tier-1 budget variant) -----------


def test_control_bench_scaled_down(tmp_path):
    """tools/bench_control_plane.py at toy scale: boots its own 2-shard plane,
    drives routed placements, kills a shard mid-run, and must report a finite
    takeover-to-first-placement time + placement quantiles."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["MODAL_TPU_JAX_PLATFORM"] = "cpu"
    env["MODAL_TPU_AUTO_LOCAL_SERVER"] = "0"
    env["MODAL_TPU_STATE_DIR"] = str(tmp_path / "bench-state")
    out = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO_ROOT, "tools", "bench_control_plane.py"),
            "--inputs", "600",
            "--calls", "12",
            "--shards", "2",
            "--concurrency", "8",
        ],
        capture_output=True,
        text=True,
        timeout=240,
        env=env,
    )
    line = next(
        (l for l in out.stdout.splitlines() if l.startswith("CONTROL_BENCH_RESULT ")),
        None,
    )
    assert line is not None, f"no bench sentinel; rc={out.returncode}\n{out.stdout[-2000:]}\n{out.stderr[-2000:]}"
    result = json.loads(line.split(" ", 1)[1])
    assert result["control_placement_p99_s"] > 0
    assert result["control_takeover_s"] > 0
    assert result["control_calls_per_s"] > 0
    assert result["takeover_epoch"] >= 2 and result["takeover_log"], "shard kill did not fail over"
