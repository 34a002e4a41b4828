"""Quorum-replicated journals + lease-fenced takeover (ISSUE 19).

Unit layer: ReplicaStore epoch-fencing matrix, torn-tail repair, dup/gap
handling, chaos faults, seal-at-max-seq, materialize. Writer layer:
JournalReplicator commit-barrier ack ordering and fence propagation.
Fleet layer: an in-process 3-shard plane loses a shard AND its journal
directory (the disk, not just the process) and recovers from the
survivors' replica streams with a correct post-takeover map.
"""

from __future__ import annotations

import asyncio
import json
import os
import shutil
import time

import pytest

from modal_tpu.server.replication import (
    JournalReplicator,
    ReplicaStore,
    offline_stream_status,
    quorum_acks_needed,
    replicas_configured,
    stream_dir,
)


def _rec(seq: int, **extra) -> str:
    payload = {"seq": seq, "rpc": "TestOp", "req": {"n": seq}}
    payload.update(extra)
    return json.dumps(payload, separators=(",", ":"))


def _records_on_disk(state_dir: str, writer: int) -> list[dict]:
    path = os.path.join(stream_dir(state_dir, writer), "records.jsonl")
    out = []
    with open(path) as f:
        for line in f:
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                pass  # torn tail — excluded on purpose
    return out


# -- config ---------------------------------------------------------------------


def test_quorum_math():
    # majority of the write set (writer + followers), writer's own append free
    assert quorum_acks_needed(0) == 0
    assert quorum_acks_needed(1) == 1
    assert quorum_acks_needed(2) == 1
    assert quorum_acks_needed(3) == 2
    assert quorum_acks_needed(4) == 2


def test_replicas_env_knob(monkeypatch):
    monkeypatch.delenv("MODAL_TPU_JOURNAL_REPLICAS", raising=False)
    assert replicas_configured() == 2, "default replica count changed"
    # gate off-toggle: MODAL_TPU_JOURNAL_REPLICAS=0 disables replication entirely
    monkeypatch.setenv("MODAL_TPU_JOURNAL_REPLICAS", "0")
    assert replicas_configured() == 0
    monkeypatch.setenv("MODAL_TPU_JOURNAL_REPLICAS", "not-a-number")
    assert replicas_configured() == 2, "garbage knob must fall back, not crash boot"


# -- ReplicaStore: append/dup/gap ----------------------------------------------


def test_store_append_dedupes_resent_records(tmp_path):
    store = ReplicaStore(str(tmp_path))
    try:
        r = store.append(0, 1, [_rec(1), _rec(2), _rec(3)])
        assert r == {"ok": True, "last_seq": 3, "epoch": 1}
        # resend after a dropped ack: seqs <= last_seq are skipped, not duplicated
        r = store.append(0, 1, [_rec(2), _rec(3), _rec(4)])
        assert r["ok"] and r["last_seq"] == 4
    finally:
        store.close()
    recs = _records_on_disk(str(tmp_path), 0)
    assert [x["seq"] for x in recs] == [1, 2, 3, 4], "dup records leaked into the stream"


def test_store_refuses_gap(tmp_path):
    store = ReplicaStore(str(tmp_path))
    try:
        assert store.append(1, 1, [_rec(1)])["ok"]
        r = store.append(1, 1, [_rec(5)])
        assert r == {"ok": False, "error": "gap", "last_seq": 1, "epoch": 1}
        # the writer falls back to snapshot install, then the tail applies
        assert store.install_snapshot(1, 1, 4, [_rec(4, snapshot=True)])["ok"]
        assert store.append(1, 1, [_rec(5)])["last_seq"] == 5
    finally:
        store.close()


# -- ReplicaStore: epoch fencing matrix ----------------------------------------


def test_epoch_fencing_matrix(tmp_path):
    store = ReplicaStore(str(tmp_path))
    try:
        # writer at epoch 2 establishes the stream
        assert store.append(0, 2, [_rec(1), _rec(2)])["ok"]
        # stale epoch: structurally rejected (fencing token)
        r = store.append(0, 1, [_rec(3)])
        assert r == {"ok": False, "error": "stale_epoch", "last_seq": 2, "epoch": 2}
        # takeover seals at epoch 3: sealed_seq pins the replicated max-seq
        sealed = store.seal(0, 3)
        assert sealed["ok"] and sealed["sealed_seq"] == 2
        # the old writer cannot extend a sealed stream at ANY epoch <= the seal's
        for stale in (1, 2, 3):
            assert store.append(0, stale, [_rec(3)])["error"] == "stale_epoch"
        assert store.install_snapshot(0, 3, 9, [_rec(9)])["error"] == "stale_epoch"
        # a NEW incarnation of shard 0 (epoch 4 > seal) resets the stream
        r = store.append(0, 4, [_rec(1)])
        assert r == {"ok": True, "last_seq": 1, "epoch": 4}
        st = store.status(0)
        assert st["sealed_epoch"] == 0 and st["snapshot_seq"] == 0
    finally:
        store.close()


def test_seal_is_idempotent_and_fences_stale_sealers(tmp_path):
    store = ReplicaStore(str(tmp_path))
    try:
        assert store.append(2, 5, [_rec(1), _rec(2), _rec(3)])["ok"]
        first = store.seal(2, 6)
        again = store.seal(2, 6)
        assert first == again == {"ok": True, "last_seq": 3, "sealed_seq": 3, "epoch": 6}
        # a director retrying at an OLDER takeover epoch must not move the seal
        assert store.seal(2, 5)["error"] == "stale_epoch"
        # a later takeover may re-seal at a higher epoch
        assert store.seal(2, 7)["ok"]
    finally:
        store.close()


def test_fencing_survives_store_restart(tmp_path):
    store = ReplicaStore(str(tmp_path))
    store.append(0, 3, [_rec(1)])
    store.seal(0, 4)
    store.close()
    # meta.json is the durable fencing state — a restarted follower still rejects
    reopened = ReplicaStore(str(tmp_path))
    try:
        assert reopened.append(0, 4, [_rec(2)])["error"] == "stale_epoch"
        st = reopened.status(0)
        assert st["sealed_epoch"] == 4 and st["sealed_seq"] == 1
    finally:
        reopened.close()


def test_fence_rejection_callback_fires(tmp_path):
    seen: list[int] = []
    store = ReplicaStore(str(tmp_path), on_fence_rejection=seen.append)
    try:
        store.append(1, 5, [_rec(1)])
        store.append(1, 2, [_rec(2)])  # stale → rejected → callback
        store.append(1, 1, [_rec(2)])
    finally:
        store.close()
    assert seen == [1, 1]


# -- ReplicaStore: writer incarnation (crash-restart divergence guard) ---------


def test_new_incarnation_truncates_phantom_tail(tmp_path):
    """A kill -9 can lose the writer's buffered tail while followers keep it:
    the restarted writer replays to boot_seq and re-mints later seqs with
    DIFFERENT records. Deduping purely by seq would swallow them silently —
    the follower must truncate the phantom tail when it first sees the new
    incarnation."""
    store = ReplicaStore(str(tmp_path))
    try:
        assert store.append(0, 1, [_rec(i) for i in range(1, 6)], incarnation=1)["ok"]
        # writer crash-restarted having durably replayed only to seq 3:
        # seqs 4..5 on this follower are phantoms the writer lost
        r = store.append(0, 1, [_rec(4, reminted=True)], incarnation=2, boot_seq=3)
        assert r["ok"] and r["last_seq"] == 4
        assert store.status(0)["incarnation"] == 2
    finally:
        store.close()
    recs = _records_on_disk(str(tmp_path), 0)
    assert [x["seq"] for x in recs] == [1, 2, 3, 4]
    assert recs[3].get("reminted"), "re-minted seq 4 was seq-deduped against a phantom"


def test_incarnation_truncation_survives_follower_restart(tmp_path):
    store = ReplicaStore(str(tmp_path))
    store.append(1, 1, [_rec(i) for i in range(1, 4)], incarnation=1)
    store.append(1, 1, [_rec(2, reminted=True)], incarnation=2, boot_seq=1)
    store.close()
    reopened = ReplicaStore(str(tmp_path))
    try:
        st = reopened.status(1)
        assert st["incarnation"] == 2 and st["last_seq"] == 2
        # the repeat of the SAME incarnation must not truncate again
        assert reopened.append(1, 1, [_rec(3)], incarnation=2, boot_seq=1)["last_seq"] == 3
    finally:
        reopened.close()
    assert [x["seq"] for x in _records_on_disk(str(tmp_path), 1)] == [1, 2, 3]


def test_stale_incarnation_is_rejected(tmp_path):
    store = ReplicaStore(str(tmp_path))
    try:
        assert store.append(0, 1, [_rec(1)], incarnation=3, boot_seq=0)["ok"]
        r = store.append(0, 1, [_rec(2)], incarnation=2, boot_seq=0)
        assert r == {"ok": False, "error": "stale_incarnation", "last_seq": 1, "epoch": 1}
        # incarnation=0 (pre-incarnation peer / direct store use): no tracking
        assert store.append(0, 1, [_rec(2)])["ok"]
    finally:
        store.close()


def test_stale_epoch_never_triggers_truncation(tmp_path):
    """Fencing order matters: a partitioned undead writer that crash-restarts
    (bumping its incarnation) but still carries its pre-takeover epoch must be
    refused BEFORE the incarnation logic can touch the stream."""
    store = ReplicaStore(str(tmp_path))
    try:
        assert store.append(0, 5, [_rec(1), _rec(2)], incarnation=1)["ok"]
        r = store.append(0, 4, [_rec(1, undead=True)], incarnation=2, boot_seq=0)
        assert r["error"] == "stale_epoch"
        assert store.status(0)["last_seq"] == 2, "stale-epoch append truncated the stream"
        assert store.status(0)["incarnation"] == 1
    finally:
        store.close()
    assert [x["seq"] for x in _records_on_disk(str(tmp_path), 0)] == [1, 2]


# -- ReplicaStore: torn tail + chaos faults ------------------------------------


def test_torn_tail_written_then_repaired_on_resend(tmp_path):
    from modal_tpu.chaos import ChaosPolicy

    chaos = ChaosPolicy(seed=0)
    chaos.set_knob("repl_torn_tail", 1)
    store = ReplicaStore(str(tmp_path), chaos=chaos)
    try:
        r = store.append(0, 1, [_rec(1), _rec(2), _rec(3)])
        # the follower "crashed" mid-write: half of record 3 landed, no ack for it
        assert r["ok"] and r["last_seq"] == 2
    finally:
        store.close()
    raw = open(os.path.join(stream_dir(str(tmp_path), 0), "records.jsonl")).read()
    assert not raw.endswith("\n"), "chaos torn tail did not tear"
    # a fresh store (follower restart) detects the torn tail and the writer's
    # resend repairs it in place — no duplicate, no corruption
    store = ReplicaStore(str(tmp_path))
    try:
        assert store.status(0)["last_seq"] == 2
        assert store.append(0, 1, [_rec(3)]) == {"ok": True, "last_seq": 3, "epoch": 1}
    finally:
        store.close()
    assert [x["seq"] for x in _records_on_disk(str(tmp_path), 0)] == [1, 2, 3]


def test_chaos_disk_full_rejects_then_recovers(tmp_path):
    from modal_tpu.chaos import ChaosPolicy

    chaos = ChaosPolicy(seed=0)
    chaos.set_knob("repl_disk_full", 1)
    store = ReplicaStore(str(tmp_path), chaos=chaos)
    try:
        r = store.append(0, 1, [_rec(1)])
        assert r == {"ok": False, "error": "disk_full", "last_seq": 0, "epoch": 1}
        # budget consumed: the next append (operator freed space) succeeds
        assert store.append(0, 1, [_rec(1)])["ok"]
    finally:
        store.close()


def test_chaos_ack_drop_is_durable_but_nacked(tmp_path):
    from modal_tpu.chaos import ChaosPolicy

    chaos = ChaosPolicy(seed=0)
    chaos.set_knob("repl_ack_drop", 1)
    store = ReplicaStore(str(tmp_path), chaos=chaos)
    try:
        r = store.append(0, 1, [_rec(1), _rec(2)])
        # partition-during-commit: durable on the follower, ack lost in flight
        assert not r["ok"] and r["error"] == "ack_dropped" and r["last_seq"] == 2
        # the writer resends; seq-dedupe makes the retry harmless
        assert store.append(0, 1, [_rec(1), _rec(2)])["ok"]
    finally:
        store.close()
    assert [x["seq"] for x in _records_on_disk(str(tmp_path), 0)] == [1, 2]


def test_chaos_repl_knobs_parse_and_default_off(monkeypatch):
    from modal_tpu.chaos import ChaosPolicy

    for var in (
        "MODAL_TPU_CHAOS_REPL_TORN_TAIL",
        "MODAL_TPU_CHAOS_REPL_DISK_FULL",
        "MODAL_TPU_CHAOS_REPL_ACK_DROP",
        "MODAL_TPU_CHAOS_REPL_LAG_MS",
    ):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("MODAL_TPU_CHAOS", "1")
    policy = ChaosPolicy.from_env()
    for knob in ("repl_torn_tail", "repl_disk_full", "repl_ack_drop"):
        assert policy.get_knob(knob) == 0, f"{knob} not off by default"
    assert policy.repl_lag_ms == 0.0
    monkeypatch.setenv("MODAL_TPU_CHAOS_REPL_TORN_TAIL", "2")
    monkeypatch.setenv("MODAL_TPU_CHAOS_REPL_DISK_FULL", "1")
    monkeypatch.setenv("MODAL_TPU_CHAOS_REPL_ACK_DROP", "3")
    monkeypatch.setenv("MODAL_TPU_CHAOS_REPL_LAG_MS", "12.5")
    policy = ChaosPolicy.from_env()
    assert policy.get_knob("repl_torn_tail") == 2
    assert policy.get_knob("repl_disk_full") == 1
    assert policy.get_knob("repl_ack_drop") == 3
    assert policy.repl_lag_ms == 12.5
    monkeypatch.setenv("MODAL_TPU_CHAOS_REPL_LAG_MS", "banana")
    assert ChaosPolicy.from_env().repl_lag_ms == 0.0, "typo'd knob must not kill boot"


# -- ReplicaStore: snapshot + materialize --------------------------------------


def test_snapshot_install_prunes_covered_records(tmp_path):
    store = ReplicaStore(str(tmp_path))
    try:
        store.append(0, 1, [_rec(i) for i in range(1, 6)])
        assert store.install_snapshot(0, 1, 4, [_rec(4, compacted=True)])["ok"]
        st = store.status(0)
        assert st["snapshot_seq"] == 4 and st["last_seq"] == 5
        # only the uncovered tail remains as raw records
        assert [x["seq"] for x in _records_on_disk(str(tmp_path), 0)] == [5]
        # an older snapshot arriving late is a no-op, never a regression
        assert store.install_snapshot(0, 1, 2, [_rec(2)])["ok"]
        assert store.status(0)["snapshot_seq"] == 4
    finally:
        store.close()


def test_materialize_seals_at_replicated_max_seq(tmp_path):
    from modal_tpu.server.journal import JOURNAL_DIRNAME

    store = ReplicaStore(str(tmp_path))
    try:
        store.append(0, 1, [_rec(i) for i in range(1, 4)])
        store.install_snapshot(0, 1, 1, [_rec(1, compacted=True)])
        sealed = store.seal(0, 2)
        assert sealed["sealed_seq"] == 3
        root = store.materialize(0)
    finally:
        store.close()
    jdir = os.path.join(root, JOURNAL_DIRNAME)
    assert os.path.exists(os.path.join(jdir, "snapshot-1.jsonl"))
    seg = open(os.path.join(jdir, "segment-000001.jsonl")).read().splitlines()
    assert [json.loads(s)["seq"] for s in seg] == [2, 3], "materialized tail != seal range"


def test_offline_stream_status_reads_cold_disk(tmp_path):
    store = ReplicaStore(str(tmp_path))
    store.append(1, 2, [_rec(1), _rec(2)])
    store.append(2, 1, [_rec(1)])
    store.close()
    statuses = {s["writer"]: s for s in offline_stream_status(str(tmp_path))}
    assert statuses[1]["last_seq"] == 2 and statuses[1]["epoch"] == 2
    assert statuses[2]["last_seq"] == 1


# -- JournalReplicator: commit barrier + fencing -------------------------------


class _FakeJournal:
    def __init__(self, seq: int = 0):
        self.seq = seq

    def latest_snapshot(self):
        return None

    def tail_lines(self, since_seq: int):
        return []


def _replicator(tmp_path, peers, seq=5, replicas=2):
    journal = _FakeJournal(seq=seq)
    repl = JournalReplicator(
        journal, shard_index=0, state_dir=str(tmp_path), peers=lambda: peers, replicas=replicas
    )
    repl.timeout_s = 0.3  # unit tests never wait the production 5s
    return repl


async def test_commit_barrier_acks_quorum_in_any_order(tmp_path):
    repl = _replicator(tmp_path, [(1, "u1"), (2, "u2")], seq=5)
    repl._ack_event = asyncio.Event()
    # no acks yet → the barrier must NOT pass
    assert await repl.commit_barrier() is False
    # one stale ack (seq 3 < 5) is not enough
    repl.acked[1] = 3
    assert await repl.commit_barrier() is False
    # quorum for replicas=2 is ONE durable follower at >= journal.seq —
    # and it may be either follower (ack ordering is immaterial)
    repl.acked[2] = 5
    assert await repl.commit_barrier() is True
    repl.acked = {1: 7}
    assert await repl.commit_barrier() is True, "over-acked follower must also satisfy"


async def test_commit_barrier_fenced_writer_never_commits(tmp_path):
    repl = _replicator(tmp_path, [(1, "u1"), (2, "u2")], seq=1)
    repl._ack_event = asyncio.Event()
    repl.acked = {1: 99, 2: 99}
    repl.fenced = True
    assert await repl.commit_barrier() is False, "a fenced writer acked a mutation"


async def test_commit_barrier_degrades_without_followers(tmp_path):
    # zero live peers: local-only commit keeps the fleet serving (degradation
    # matrix row), rather than turning follower outages into a total outage
    repl = _replicator(tmp_path, [], seq=9)
    repl._ack_event = asyncio.Event()
    assert await repl.commit_barrier() is True
    # replicas=0 (MODAL_TPU_JOURNAL_REPLICAS=0): barrier is a no-op pass-through
    off = _replicator(tmp_path, [(1, "u1")], seq=9, replicas=0)
    assert off.active is False
    assert await off.commit_barrier() is True


async def test_stale_epoch_result_fences_writer(tmp_path):
    repl = _replicator(tmp_path, [(1, "u1")], seq=2)
    repl._ack_event = asyncio.Event()
    repl._handle_result(1, {"ok": False, "error": "stale_epoch", "epoch": 7})
    assert repl.fenced is True
    assert await repl.commit_barrier() is False


def test_ring_order_follower_selection(tmp_path):
    peers = [(1, "u1"), (2, "u2"), (3, "u3"), (4, "u4")]
    journal = _FakeJournal()
    repl = JournalReplicator(journal, shard_index=3, state_dir=str(tmp_path), peers=lambda: peers, replicas=2)
    # ring order after shard 3 in a 5-wide fleet: 4, then 0 (absent), then 1
    assert [idx for idx, _ in repl.current_followers()] == [4, 1]


async def test_observe_trims_buffer_to_slowest_follower(tmp_path):
    repl = _replicator(tmp_path, [(1, "u1"), (2, "u2")], seq=0)
    repl._ack_event = asyncio.Event()
    for seq in range(1, 6):
        repl.journal.seq = seq
        repl.observe({"seq": seq, "rpc": "TestOp"})
    assert len(repl._buffer) == 5
    repl._handle_result(1, {"ok": True, "last_seq": 5})
    assert len(repl._buffer) == 5, "trimmed past the slowest follower's ack"
    repl._handle_result(2, {"ok": True, "last_seq": 3})
    assert [seq for seq, _, _ in repl._buffer] == [4, 5]


async def test_buffer_is_capped_despite_unreachable_follower(tmp_path):
    """One unreachable-but-not-yet-dead follower pins the min-acked floor at
    0; the buffer must still be bounded — the slow follower is evicted to the
    disk catch-up path instead of growing writer memory without limit."""
    repl = _replicator(tmp_path, [(1, "u1"), (2, "u2")], seq=0)
    repl._ack_event = asyncio.Event()
    repl.buffer_max = 3
    for seq in range(1, 8):
        repl.journal.seq = seq
        repl.observe({"seq": seq, "rpc": "TestOp"})
    assert [seq for seq, _, _ in repl._buffer] == [5, 6, 7], "buffer grew past the cap"
    # follower 2 acks within the retained window; follower 1 never acks —
    # the ack-path trim must keep the cap too
    repl._handle_result(2, {"ok": True, "last_seq": 6})
    assert len(repl._buffer) <= 3
    # the evicted follower reads as behind the buffer floor → disk catch-up
    assert repl._buffer[0][0] > repl.acked.get(1, 0) + 1


# -- writer meta: incarnation + epoch survive a crash-restart -------------------


def test_writer_meta_bumps_incarnation_and_restores_epoch(tmp_path):
    repl = _replicator(tmp_path, [(1, "u1")], seq=5)
    assert repl.incarnation == 1 and repl.boot_seq == 5
    repl.note_epoch(7)
    # crash-restart: a new replicator on the same state dir is a NEW
    # incarnation and resumes at the adopted fleet epoch, not epoch 1 —
    # restarting at 1 would get every append stale_epoch-rejected (and the
    # shard permanently fenced) until the next director probe
    reborn = _replicator(tmp_path, [(1, "u1")], seq=3)
    assert reborn.incarnation == 2
    assert reborn.epoch == 7
    assert reborn.boot_seq == 3


def test_note_epoch_clears_fence_on_strictly_higher_epoch(tmp_path):
    repl = _replicator(tmp_path, [(1, "u1")], seq=1)
    repl._handle_result(1, {"ok": False, "error": "stale_epoch", "epoch": 9})
    assert repl.fenced is True
    repl.note_epoch(repl.epoch)  # same epoch: not an un-fence authority
    assert repl.fenced is True
    repl.note_epoch(repl.epoch + 1)  # the director re-adopted us
    assert repl.fenced is False


def test_writer_meta_skipped_when_replication_off(tmp_path):
    from modal_tpu.server.replication import WRITER_META_FILENAME

    _replicator(tmp_path, [(1, "u1")], replicas=0)
    assert not os.path.exists(os.path.join(str(tmp_path), WRITER_META_FILENAME)), (
        "replicas=0 must stay byte-identical: no writer meta file"
    )


# -- replicas=0 byte-identical degradation -------------------------------------


def test_replicas_zero_is_byte_identical_no_quorum_wrapper(tmp_path, monkeypatch):
    """MODAL_TPU_JOURNAL_REPLICAS=0 must degrade to the exact pre-ISSUE-19
    plane: no replica/ directory, no journal observer, and `_maybe_quorum`
    returning the raw impl object (identity, not an equivalent wrapper)."""
    from modal_tpu.proto.rpc import _maybe_quorum

    monkeypatch.setenv("MODAL_TPU_JOURNAL_REPLICAS", "0")

    class _Method:
        name = "FunctionCreate"  # a JOURNALED_RPCS member

    class _Servicer:
        replicator = object()  # even with a replicator attached, 0 gates it off

    async def impl(request, context):
        return "resp"

    assert _maybe_quorum(_Servicer(), _Method(), impl) is impl


async def test_replica_store_inherits_journal_fsync(tmp_path, monkeypatch):
    """MODAL_TPU_JOURNAL_FSYNC must govern BOTH sides of a quorum: a
    follower's "durably appended" ack is a lie if the writer fsyncs and the
    replica store only reaches the page cache."""
    from modal_tpu.server.supervisor import LocalSupervisor

    monkeypatch.delenv("MODAL_TPU_JOURNAL_REPLICAS", raising=False)
    monkeypatch.setenv("MODAL_TPU_JOURNAL_FSYNC", "1")
    sup = LocalSupervisor(
        num_workers=0,
        state_dir=str(tmp_path / "state"),
        worker_chips=8,
        worker_tpu_type="local-sim",
        replication_peers=lambda: [(1, "grpc://127.0.0.1:1")],
    )
    sup._attach_journal()
    journal = sup.state.journal
    try:
        assert journal.fsync is True
        assert sup.replica_store is not None and sup.replica_store.fsync is True
    finally:
        await sup._stop_replication()
        journal.close()


async def test_replicas_zero_supervisor_has_no_replication(tmp_path, monkeypatch):
    from modal_tpu.server.supervisor import LocalSupervisor

    monkeypatch.setenv("MODAL_TPU_JOURNAL_REPLICAS", "0")
    sup = LocalSupervisor(
        num_workers=0,
        state_dir=str(tmp_path / "state"),
        worker_chips=8,
        worker_tpu_type="local-sim",
        replication_peers=lambda: [(1, "grpc://127.0.0.1:1")],
    )
    sup._attach_journal()
    journal = sup.state.journal
    try:
        assert sup.replica_store is None
        assert sup.state.replicator is None
        assert journal is not None and journal.observer is None
        assert not os.path.isdir(os.path.join(str(tmp_path / "state"), "replica"))
    finally:
        journal.close()


# -- fleet: lose the shard AND its journal directory ---------------------------


@pytest.fixture
def sharded(tmp_path, monkeypatch):
    """3 in-process shards with journal replication on (default replicas=2),
    fast health loop — mirrors tests/test_shards.py's fixture."""
    from modal_tpu._utils.async_utils import synchronizer
    from modal_tpu.client import _Client
    from modal_tpu.server.shards import ShardedSupervisor

    monkeypatch.delenv("MODAL_TPU_JOURNAL_REPLICAS", raising=False)
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


def _wait_for(predicate, timeout_s: float = 20.0, what: str = "condition"):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(0.1)
    raise AssertionError(f"timed out waiting for {what}")


def test_kill_and_delete_journal_dir_replica_takeover(sharded, tmp_path):
    """The ISSUE 19 headline at tier-1 speed: the home shard dies AND its
    journal directory is deleted (disk loss, not process loss). The director
    seals the survivors' replica streams and adopts from them — mode
    "replica" — and a post-takeover map still computes exactly-once."""
    import modal_tpu
    from modal_tpu._utils.async_utils import synchronizer
    from modal_tpu._utils.shard_routing import partition_for_name

    app = modal_tpu.App("repl-e2e")

    def double(x):
        return x * 2

    f = app.function(serialized=True)(double)
    with app.run():
        results = sorted(f.map(range(24)))
        assert results == [x * 2 for x in range(24)], "pre-kill map lost/dup'd inputs"

    home = partition_for_name("repl-e2e", 3)
    # replication is live: some survivor holds a stream for the home shard
    _wait_for(
        lambda: any(
            sharded.shards[i] is not None
            and sharded.shards[i].replica_store is not None
            and sharded.shards[i].replica_store.status(home).get("last_seq", 0) > 0
            for i in range(3)
            if i != home
        ),
        what=f"a replica stream of shard {home} on a survivor",
    )

    synchronizer.run(sharded.kill_shard(home))
    # the disk is gone too: no corpse journal to replay from
    shutil.rmtree(os.path.join(str(tmp_path / "state"), f"shard-{home}", "journal"))

    # the takeover RECORD, not just the remap: the director appends it only
    # after rehoming the workers, a few ms after assignments change
    _wait_for(
        lambda: any(e["dead_shard"] == home for e in sharded.takeover_log),
        what=f"replica takeover of partition {home}",
    )
    assert sharded.assignments[home] != home
    (entry,) = [e for e in sharded.takeover_log if e["dead_shard"] == home]
    assert entry["mode"] == "replica", "takeover replayed a journal that no longer exists?"
    assert entry["report"]["records_applied"] > 0, "replica adoption replayed nothing"
    assert "seal" in entry["phases"], "replica takeover skipped the seal phase"

    # the seal lands on EVERY live shard — a survivor without a stream gets
    # an empty sealed one, so the undead writer can't rebuild a quorum from
    # shards the takeover never discovered as holders
    epoch = sharded.epoch
    for i in range(3):
        if i == home or sharded.shards[i] is None:
            continue
        store = sharded.shards[i].replica_store
        st = store.status(home)
        assert st["ok"], f"survivor {i} holds no sealed stream of dead writer {home}"
        assert st["sealed_epoch"] == epoch

    with app.run():
        results = sorted(f.map(range(10)))
        assert results == [x * 2 for x in range(10)], "post-takeover map lost/dup'd inputs"


def test_sharded_status_reports_replication(sharded):
    """Satellite: shard_status carries the writer-side replicator view and the
    follower-side replica streams for `modal_tpu journal status`."""
    import modal_tpu

    app = modal_tpu.App("repl-status")

    def inc(x):
        return x + 1

    f = app.function(serialized=True)(inc)
    with app.run():
        assert sorted(f.map(range(6))) == list(range(1, 7))

    saw_follower_ack = False
    for i in range(3):
        st = sharded.shards[i].shard_status()
        repl = st["replication"]
        assert repl is not None and repl["replicas"] == 2
        assert [f_["shard"] for f_ in repl["followers"]] == [(i + 1) % 3, (i + 2) % 3]
        saw_follower_ack = saw_follower_ack or any(
            f_["acked_seq"] > 0 for f_ in repl["followers"]
        )
        assert isinstance(st["replica_streams"], list)
    assert saw_follower_ack, "no shard replicated anything during a 6-input map"
