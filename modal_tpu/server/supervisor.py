"""LocalSupervisor: control plane + blob server + workers in one process.

The single-host orchestrator (SURVEY §7 step 3): an asyncio gRPC server with
the full servicer, an HTTP blob store, a scheduler, and N in-process worker
agents that spawn container subprocesses. Scales out later by running
`python -m modal_tpu.server` (control plane) and `python -m
modal_tpu.server.worker_main` (per host) separately — same code paths.
"""

from __future__ import annotations

import asyncio
import os
from typing import Any, Optional

import grpc

from ..chaos import ChaosPolicy, ChaosServicerProxy
from ..config import config, logger, tune_switch_interval
from ..observability import tracing
from ..observability.catalog import CHAOS_SEED
from ..proto.rpc import build_generic_handler
from .blob_server import BlobServer
from .input_plane import InputPlaneServer
from .journal import IdempotencyCache, Journal, recover_state
from .scheduler import Scheduler
from .services import ModalTPUServicer
from .state import ServerState
from .worker import WorkerAgent, chips_per_worker


def _journal_enabled() -> bool:
    return os.environ.get("MODAL_TPU_JOURNAL", "1") not in ("0", "false", "no")


class LocalSupervisor:
    def __init__(
        self,
        num_workers: int = 1,
        port: int = 0,
        state_dir: Optional[str] = None,
        worker_chips: Optional[int] = None,
        worker_tpu_type: Optional[str] = None,
        servicer_cls: type = ModalTPUServicer,  # tests inject fault-wrapping subclasses
        hosts_per_slice: int = 0,  # 0 = all workers share slice 0
        chaos: Optional[ChaosPolicy] = None,  # one policy object, every layer
        recover: Optional[bool] = None,  # None = auto: recover iff a journal exists
        shard_index: int = 0,  # home partition for minted ids (server/shards.py)
        blob_dir: Optional[str] = None,  # shared blob store across shards
        # quorum journal replication (ISSUE 19, server/replication.py):
        # peers = () -> [(shard_index, url)] of live siblings (in-process
        # sharding injects this); fleet_root = the sharded fleet's root dir
        # (subprocess shards discover peers from <fleet_root>/shards.json).
        # Neither set => a standalone monolith: no peers, no replication.
        replication_peers: Optional[Any] = None,
        fleet_root: Optional[str] = None,
    ):
        self.num_workers = num_workers
        self.port = port
        self.state_dir = state_dir or config["state_dir"]
        self.worker_chips = worker_chips
        self.worker_tpu_type = worker_tpu_type
        self.hosts_per_slice = hosts_per_slice
        self.recover = recover
        self.shard_index = shard_index
        self._blob_dir_override = blob_dir
        # epoch fencing (server/shards.py): a fenced shard has been replaced
        # by a takeover and must never serve or journal its partition again
        self.fenced = False
        self.fenced_at_epoch = 0
        self.recovery_report: Optional[dict] = None  # set when start() replayed a journal
        self.takeover_reports: list[dict] = []  # one per adopted partition
        self.replication_peers = replication_peers
        self.fleet_root = fleet_root
        self.replica_store = None  # follower side (ISSUE 19), set by _attach_journal
        self._fence_rejection_times: list[float] = []  # storm detector window
        self._fence_storm_dumped_at = 0.0
        self.state = ServerState(self.state_dir, shard_index=shard_index, blob_dir=blob_dir)
        # chaos: explicit policy, else env-driven (MODAL_TPU_CHAOS=1)
        self.chaos = chaos if chaos is not None else ChaosPolicy.from_env()
        self.servicer = servicer_cls(self.state)
        self.servicer.chaos = self.chaos
        self.servicer.supervisor = self  # ShardControl delegates here
        self.scheduler = Scheduler(self.state, self.servicer)
        self.servicer.scheduler = self.scheduler
        self.blob_server = BlobServer(self.state, chaos=self.chaos)
        self.input_plane = InputPlaneServer(self.state, self.servicer, chaos=self.chaos)
        self.workers: list[WorkerAgent] = []
        self.uds_path = ""  # control-plane Unix socket (set at bind time)
        self._grpc_server: Optional[grpc.aio.Server] = None
        self._sampler_task: Optional[asyncio.Task] = None  # ISSUE 11 time-series sampler
        self.flight_recorder = None  # ISSUE 17 crash-forensics ring
        self._chaos_task: Optional[asyncio.Task] = None
        self._chaos_subtasks: set[asyncio.Task] = set()  # strong refs (GC guard)
        # serializes crash_restart: two supervisor_crash chaos events due in
        # one tick must restart sequentially, not interleave teardown/rebuild
        self._crash_lock = asyncio.Lock()

    def _attach_journal(self) -> None:
        """Open the write-ahead journal (server/journal.py) and, when the
        state dir already holds one, replay it into this ServerState BEFORE
        any RPC is served: open calls resume, orphaned claimed inputs
        requeue, journaled workers await re-adoption by their next heartbeat."""
        if not _journal_enabled():
            return
        if self.recover is False:
            # explicit decline: archive any existing records — otherwise the
            # NEXT boot's auto-recovery would merge the abandoned state with
            # this run's, resurrecting ghost apps/calls/inputs
            from .journal import archive_existing

            archive_existing(self.state_dir)
        journal = Journal(self.state_dir)
        # the input-plane JWT secret must survive the restart, or every
        # already-minted client token turns UNAUTHENTICATED (not retried)
        secret_path = os.path.join(journal.dir, "auth.secret")
        try:
            if os.path.exists(secret_path):
                with open(secret_path, "rb") as f:
                    self.state.auth_secret = f.read()
            else:
                with open(secret_path, "wb") as f:
                    f.write(self.state.auth_secret)
                os.chmod(secret_path, 0o600)
        except OSError as exc:
            logger.warning(f"auth secret persistence failed: {exc}")
        should_recover = self.recover if self.recover is not None else journal.has_records()
        if should_recover and journal.has_records():
            self.state.idempotency = IdempotencyCache(journal=None)  # filled by replay
            self.recovery_report = recover_state(self.state, journal)
        # wire AFTER replay: replaying must not re-append its own records
        self.state.journal = journal
        if self.state.idempotency is None:
            self.state.idempotency = IdempotencyCache(journal=journal)
        else:
            self.state.idempotency.journal = journal
        self._attach_replication(journal)
        # data-plane port continuity: clients that survive a control-plane
        # restart hold the OLD input-plane/blob URLs (handed out at
        # ClientHello / BlobCreate) — rebinding the same ports makes their
        # retry loops land on the recovered plane instead of a dead socket.
        # Explicitly-requested ports are respected; fallback is ephemeral.
        ports_path = os.path.join(journal.dir, "ports.json")
        try:
            import json as _json

            with open(ports_path) as f:
                saved = _json.load(f)
            if not self.blob_server.port:
                self.blob_server.port = int(saved.get("blob", 0))
            if not self.input_plane.port:
                self.input_plane.port = int(saved.get("input_plane", 0))
        except (OSError, ValueError):
            pass

    def _attach_replication(self, journal: Journal) -> None:
        """Quorum journal replication (ISSUE 19, server/replication.py): wire
        the follower-side ReplicaStore and the writer-side JournalReplicator
        onto the freshly opened journal. Fleet-only: a standalone monolith
        (no peers callable, no fleet root) gets neither — and with
        MODAL_TPU_JOURNAL_REPLICAS=0 this is a structural no-op, so the
        single-writer path stays byte-identical."""
        from .replication import JournalReplicator, ReplicaStore, replicas_configured

        if (self.replication_peers is None and not self.fleet_root) or replicas_configured() == 0:
            return
        # follower durability must match the configured journal durability:
        # with MODAL_TPU_JOURNAL_FSYNC=1 a quorum "durably appended" ack has
        # to mean fsynced on the follower too, not just page-cached
        self.replica_store = ReplicaStore(
            self.state_dir,
            fsync=journal.fsync,
            chaos=self.chaos,
            on_fence_rejection=self._note_fence_rejection,
        )
        peers = self.replication_peers or self._peers_from_fleet_root
        replicator = JournalReplicator(
            journal, self.shard_index, self.state_dir, peers=peers, chaos=self.chaos
        )
        self.state.replicator = replicator
        # the hooks are what keeps replicas=0 byte-identical: without them the
        # journal doesn't know replication exists
        journal.observer = replicator.observe
        journal.on_snapshot = replicator.ship_snapshot

    def _peers_from_fleet_root(self) -> list[tuple[int, str]]:
        """Subprocess-shard peer discovery: the director persists
        <fleet_root>/shards.json (pids/ports) on every topology change; dead
        or unstarted siblings are excluded. Re-read per call so takeovers and
        respawns are picked up without a control channel."""
        import json as _json

        try:
            with open(os.path.join(self.fleet_root, "shards.json")) as f:
                doc = _json.load(f)
        except (OSError, ValueError):
            return []
        peers = []
        for entry in doc.get("shards", ()):
            try:
                idx = int(entry.get("index", -1))
            except (TypeError, ValueError):
                continue
            url = entry.get("url") or ""
            if idx < 0 or idx == self.shard_index or not url or entry.get("dead"):
                continue
            peers.append((idx, url))
        return peers

    def _note_fence_rejection(self, writer: int) -> None:
        """Fence-rejection storm detector (ISSUE 19 satellite): one stale
        append is routine during takeover; a sustained storm means an undead
        writer is actively hammering a sealed stream — freeze the flight
        recorder's last minute for the postmortem."""
        import time as _time

        now = _time.monotonic()
        window = [t for t in self._fence_rejection_times if now - t < 10.0]
        window.append(now)
        self._fence_rejection_times = window
        if len(window) >= 5 and now - self._fence_storm_dumped_at > 60.0:
            self._fence_storm_dumped_at = now
            if self.flight_recorder is not None:
                self.flight_recorder.dump(
                    "fence_rejections", extra={"writer": writer, "rejections_10s": len(window)}
                )

    def _save_ports(self) -> None:
        """Record the bound data-plane ports for the next (post-crash) boot."""
        if self.state.journal is None:
            return
        import json as _json

        try:
            with open(os.path.join(self.state.journal.dir, "ports.json"), "w") as f:
                _json.dump(
                    {"blob": self.blob_server.port, "input_plane": self.input_plane.port}, f
                )
        except OSError:
            pass

    @property
    def server_url(self) -> str:
        return f"grpc://127.0.0.1:{self.port}"

    async def start(self) -> None:
        os.makedirs(self.state_dir, exist_ok=True)
        tune_switch_interval()
        if config["trace"]:
            # span sink under the supervisor dir; exported to containers via
            # MODAL_TPU_TRACE_DIR (observability/tracing.py)
            trace_dir = config.get("trace_dir") or os.path.join(self.state_dir, "traces")
            # retention: prune dead-run span files before opening this run's
            # sink (size/age caps; `modal_tpu trace gc` does the same offline)
            tracing.gc_trace_dir(trace_dir)
            tracing.configure(trace_dir)
        # continuous profiling (observability/profiler.py): MODAL_TPU_PROFILE
        # starts the supervisor's sampler at boot; the ProfileControl RPC
        # toggles it (and every container's) at runtime
        from ..observability import profiler as obs_profiler

        obs_profiler.maybe_start_from_env(
            os.path.join(self.state_dir, "observability", "profiles"), tag="supervisor"
        )
        # journal + recovery BEFORE the gRPC server binds: the first client
        # retry after a restart must already see the replayed state (and the
        # dedupe wrapper captures state.idempotency at handler-build time)
        self._attach_journal()
        if self.chaos is not None:
            # /metrics echoes the active chaos seed so a soak failure is
            # attributable to the exact injected fault sequence
            CHAOS_SEED.set(float(self.chaos.seed))
        await self._start_control_plane(self.port)
        worker_chips = await chips_per_worker(self.num_workers, self.worker_chips)
        for i in range(self.num_workers):
            worker = WorkerAgent(
                self.server_url,
                num_chips=worker_chips,
                tpu_type=self.worker_tpu_type,
                state_dir=self.state_dir,
                slice_index=(i // self.hosts_per_slice) if self.hosts_per_slice else 0,
                chaos=self.chaos,
                # in-process workers are co-located by definition: hand them
                # the fast-path coordinates to use and to export to containers
                server_uds=self.uds_path,
                blob_local_dir=self.state.blob_dir,
                # fleet compile cache (ISSUE 20): the blob plane serves
                # /compile/<key>, so its base url IS the cache url
                compile_cache_url=self.state.blob_url_base,
            )
            await worker.start()
            self.workers.append(worker)
        if self.chaos is not None and self.chaos.events:
            self._chaos_task = asyncio.create_task(self._chaos_event_loop(), name="chaos-events")
        logger.debug(f"local supervisor up at {self.server_url} ({self.num_workers} workers)")

    async def _start_control_plane(self, grpc_port: int) -> None:
        """Bind + start the gRPC server, blob server, input plane, and
        scheduler — ONE code path for a fresh boot and the post-crash
        rebuild, so they can never drift."""
        from .._utils import local_transport

        self._grpc_server = grpc.aio.server(
            options=[
                ("grpc.max_receive_message_length", 128 * 1024 * 1024),
                ("grpc.max_send_message_length", 128 * 1024 * 1024),
            ]
        )
        # chaos attaches at the handler boundary so the servicer itself (and
        # every in-process caller: scheduler, tests) stays clean
        handler_target = (
            ChaosServicerProxy(self.servicer, self.chaos) if self.chaos is not None else self.servicer
        )
        self._grpc_server.add_generic_rpc_handlers((build_generic_handler(handler_target),))
        self.port = self._grpc_server.add_insecure_port(f"127.0.0.1:{grpc_port}")
        # local fast-path transport (ISSUE 8, docs/DISPATCH.md): a Unix
        # socket next to the TCP port for co-located cross-process peers
        # (containers), advertised on ClientHello; stable across crash
        # restarts because it lives in the state dir
        self.uds_path = ""
        uds = os.path.join(self.state_dir, "control.sock")
        if local_transport.uds_enabled() and local_transport.usable_uds_path(uds):
            try:
                os.unlink(uds)
            except FileNotFoundError:
                pass
            try:
                self._grpc_server.add_insecure_port(f"unix:{uds}")
                self.uds_path = uds
            except Exception as exc:  # noqa: BLE001 — UDS is an optimization
                logger.warning(f"control-plane UDS bind failed ({exc}); TCP only")
        self.state.uds_path = self.uds_path
        self.state.blob_local_dir = self.state.blob_dir
        await self._grpc_server.start()
        await self.blob_server.start()
        await self.input_plane.start()
        # in-process rung: same-process clients (the default zero-config
        # local mode) skip the socket entirely — registered AFTER the servers
        # are live so a resolvable entry always means a serving control plane
        local_transport.register_local_server(self.server_url, handler_target)
        self._save_ports()
        self.scheduler.start()
        # fleet SLO observability (ISSUE 11): the supervisor-resident
        # time-series store samples the merged registry on cadence and the
        # burn-rate evaluator rides the same tick. Built here (not start())
        # so a crash_restart rebuilds both against the NEW state — the
        # evaluator adopts state.alerts, which journal replay just refilled,
        # so a firing alert survives the restart and can only resolve on
        # real post-restart samples.
        from ..observability import timeseries as ts
        from ..observability.slo import SLOEvaluator

        if ts.sampling_enabled():
            self.state.timeseries = ts.TimeSeriesStore()
            self.state.slo = SLOEvaluator(
                self.state.timeseries, alerts=self.state.alerts, journal=self.state.journal
            )
            self._sampler_task = asyncio.create_task(self._sampler_loop(), name="ts-sampler")
        # crash-forensics flight recorder (ISSUE 17): bounded in-memory ring
        # of raw samples + span/journal/chaos tails, frozen and dumped as
        # postmortem-<event>.json on crash_restart / fence / takeover / alert
        # firing. Rebuilt here (like the store) so it taps the NEW journal.
        from ..observability import flight_recorder as obs_fr

        if obs_fr.enabled():
            self.flight_recorder = obs_fr.FlightRecorder(
                self.state_dir,
                journal=self.state.journal,
                chaos=self.chaos,
                shard_index=self.shard_index,
            )
            self.flight_recorder.start()
        else:
            self.flight_recorder = None
        # quorum replication sender tasks (ISSUE 19): started here — not in
        # _attach_journal — because they need the running loop, and the
        # crash_restart rebuild must respawn them against the NEW journal
        if self.state.replicator is not None:
            self.state.replicator.start()

    async def _sampler_loop(self) -> None:
        """Sample the registry into the store + evaluate SLO rules, forever.
        One loop owns both so alert windows and history always agree."""
        import time as _time

        from ..observability.catalog import (
            TIMESERIES_POINTS,
            TIMESERIES_SAMPLE_SECONDS,
            TIMESERIES_SAMPLES,
        )

        store, evaluator = self.state.timeseries, self.state.slo
        while True:
            try:
                t0 = _time.perf_counter()
                store.sample()
                TIMESERIES_SAMPLES.inc()
                TIMESERIES_SAMPLE_SECONDS.observe(_time.perf_counter() - t0)
                for tier, n in store.point_counts().items():
                    TIMESERIES_POINTS.set(float(n), tier=tier)
                transitions = evaluator.evaluate()
                recorder = self.flight_recorder
                if recorder is not None:
                    for tr in transitions:
                        if tr.get("state") == "firing":
                            recorder.dump("alert", extra={"alert": tr})
            except asyncio.CancelledError:
                raise
            except Exception:
                logger.exception("time-series sampler iteration failed")
            await asyncio.sleep(store.interval_s)

    async def _stop_sampler(self) -> None:
        if self._sampler_task is not None:
            self._sampler_task.cancel()
            try:
                await self._sampler_task
            except asyncio.CancelledError:
                pass
            self._sampler_task = None
        if self.flight_recorder is not None:
            self.flight_recorder.stop()
            self.flight_recorder = None

    async def _chaos_event_loop(self) -> None:
        """Fire scheduled chaos events (worker kill / preempt / heartbeat
        blackhole) once their output-count threshold passes."""
        while True:
            try:
                for ev in self.chaos.pop_due_events():
                    if ev.kind == "supervisor_crash":
                        # control-plane crash-and-recover: worker-agnostic
                        logger.warning("chaos: crashing + recovering the control plane")
                        t = asyncio.create_task(self.crash_restart())
                        self._chaos_subtasks.add(t)
                        t.add_done_callback(self._chaos_subtasks.discard)
                        continue
                    idx = min(ev.worker_index, len(self.workers) - 1)
                    if idx < 0:
                        continue
                    if ev.kind == "worker_preempt":
                        logger.warning(f"chaos: preempting worker {idx} (grace {ev.grace_s}s)")
                        t = asyncio.create_task(self.workers[idx].preempt(ev.grace_s))
                        self._chaos_subtasks.add(t)
                        t.add_done_callback(self._chaos_subtasks.discard)
                    elif ev.kind == "worker_kill":
                        logger.warning(f"chaos: killing worker {idx} containers")
                        self.workers[idx].kill_containers()
                    elif ev.kind == "heartbeat_blackhole":
                        self.chaos.start_heartbeat_blackhole(ev.duration_s)
            except asyncio.CancelledError:
                return
            except Exception:
                logger.exception("chaos event loop iteration failed")
            await asyncio.sleep(0.1)

    async def preempt_worker(self, index: int = 0, grace_s: float = 10.0) -> None:
        """Simulate a TPU-slice preemption notice for one worker: drain +
        graceful container stop + checkpoint flush + input requeue."""
        await self.workers[index].preempt(grace_s)

    async def crash_restart(self) -> Optional[dict]:
        """Simulated control-plane crash + journal recovery, in one process
        (chaos `supervisor_crash` event; the subprocess analogue is kill -9 +
        re-exec, tests/test_chaos_soak.py). The old ServerState is ABANDONED
        — nothing is drained or flushed beyond what the journal already holds
        — then a fresh state is rebuilt by replay and served on the same
        ports. Worker agents are left running: their next heartbeat gets
        `reannounce` or re-adopts the journal-recovered record."""
        if not _journal_enabled():
            logger.warning("supervisor_crash chaos event ignored: journaling is off")
            return None
        # serialization IS the point: overlapping crash_restarts would tear
        # down the same servers twice
        async with self._crash_lock:  # lint: disable=lock-across-await
            return await self._crash_restart_locked()

    async def crash_abandon(self) -> tuple[int, int, int]:
        """The teardown half of a simulated crash: kill container
        subprocesses, drop every serving surface with no drain and no state
        flush, abandon the ServerState. The journal handle is closed but its
        segments STAY on disk — they are the substrate a same-dir restart
        recovers (crash_restart) or a sibling shard's takeover replays
        (chaos shard_kill, server/shards.py). Returns the (grpc, blob,
        input-plane) ports for a same-port rebuild."""
        old_journal = self.state.journal
        ports = (
            self.port,
            self.blob_server.port,
            getattr(self.input_plane, "port", 0),
        )
        # this supervisor's workers are IN-PROCESS: a real crash of this
        # process takes their container subprocesses with it — kill them so
        # the simulation matches (the worker AGENTS survive and re-adopt;
        # remote-worker orphan semantics are covered by the dedupe tests)
        for worker in self.workers:
            worker.kill_containers()
        # abrupt teardown: no graceful drain, no state flush — in-flight RPCs
        # see UNAVAILABLE and retry against the recovered plane. The
        # in-process fast-path rung dies WITH the plane (a ghost registration
        # would serve the abandoned state) and re-registers on rebuild.
        from .._utils import local_transport

        local_transport.unregister_local_server(self.server_url)
        local_transport.unregister_local_server(self.state.input_plane_url)
        if self._grpc_server is not None:
            await self._grpc_server.stop(grace=None)
            self._grpc_server = None
        await self.scheduler.stop()
        await self._stop_sampler()  # references the abandoned state
        await self.input_plane.stop()
        await self.blob_server.stop()
        await self._stop_replication()
        if old_journal is not None:
            old_journal.close()
        return ports

    async def _crash_restart_locked(self) -> Optional[dict]:
        import time as _time

        t0 = _time.time()
        if self.flight_recorder is not None:
            # black-box dump BEFORE teardown: the ring still holds the 60 s
            # leading up to the crash (the rebuilt plane gets a fresh ring)
            self.flight_recorder.dump("crash_restart")
        grpc_port, blob_port, input_port = await self.crash_abandon()
        # rebuild the whole control plane from the journal
        self.state = ServerState(
            self.state_dir, shard_index=self.shard_index, blob_dir=self._blob_dir_override
        )
        self.servicer = type(self.servicer)(self.state)
        self.servicer.chaos = self.chaos
        self.servicer.supervisor = self
        self.scheduler = Scheduler(self.state, self.servicer)
        self.servicer.scheduler = self.scheduler
        self.blob_server = BlobServer(self.state, port=blob_port, chaos=self.chaos)
        self.input_plane = InputPlaneServer(
            self.state, self.servicer, port=input_port, chaos=self.chaos
        )
        self.recover = True
        self._attach_journal()
        await self._start_control_plane(grpc_port)
        tracing.record_span(
            "recovery.crash_restart",
            start=t0,
            end=_time.time(),
            attrs=dict(self.recovery_report or {}),
        )
        logger.warning(
            f"control plane crash-restarted in {_time.time() - t0:.2f}s: {self.recovery_report}"
        )
        return self.recovery_report

    async def adopt_partition(self, source_state_dir: str, partition: int = -1) -> dict:
        """Leader takeover (server/shards.py, docs/CONTROL_PLANE.md): rehydrate
        a DEAD sibling shard's partition from that shard's journal into THIS
        shard's live state. The PR 5 typed records are the replication
        substrate — takeover is recover_state pointed at someone else's
        segments. Post-replay, the adopted state is compacted into OUR journal
        (making it the single durable record of the merged partitions) and the
        source segments are archived so a respawned stale shard can never
        replay them (split-brain fence, half one: the director's epoch bump is
        half two)."""
        import time as _time

        from ..observability.catalog import SHARD_TAKEOVER_SECONDS
        from .journal import archive_existing, synthesize_records

        t0 = _time.time()
        source = Journal(source_state_dir)
        try:
            report = recover_state(self.state, source, preserve_live_workers=True)
        finally:
            source.close()
        archive_existing(source_state_dir)
        if self.state.journal is not None:
            await self.state.journal.compact_async(synthesize_records(self.state))
        # requeued inputs of the adopted partition want placement immediately
        self.state.schedule_event.set()
        took = _time.time() - t0
        report = dict(
            report, partition=partition, source=source_state_dir, seconds=round(took, 4)
        )
        self.takeover_reports.append(report)
        SHARD_TAKEOVER_SECONDS.set(took, partition=str(partition))
        if self.flight_recorder is not None:
            self.flight_recorder.dump("takeover", extra={"report": report})
        tracing.record_span("control.takeover", start=t0, end=_time.time(), attrs=report)
        logger.warning(f"shard {self.shard_index} adopted partition {partition}: {report}")
        return report

    async def fence(self, epoch: int) -> None:
        """Epoch fencing (the split-brain test's subject): this shard's
        partition was either taken over while it was presumed dead (stale
        rejoiner) or is ABOUT to be (false death: the director lost contact
        but the shard still lives). Either way it must stop serving — clients
        get UNAVAILABLE, re-hello the director, and land on the successor.
        The journal is closed but NOT archived: in the false-death case the
        successor replays these very segments next (adopt_partition is the
        single archive point, stamping the tombstone AFTER a successful
        replay)."""
        if self.fenced:
            return
        self.fenced = True
        self.fenced_at_epoch = epoch
        if self.flight_recorder is not None:
            self.flight_recorder.dump("fence", extra={"epoch": epoch})
        from .._utils import local_transport

        local_transport.unregister_local_server(self.server_url)
        local_transport.unregister_local_server(self.state.input_plane_url)
        for worker in self.workers:
            worker.kill_containers()
        if self._grpc_server is not None:
            await self._grpc_server.stop(grace=None)
            self._grpc_server = None
        await self.scheduler.stop()
        await self._stop_sampler()
        await self.input_plane.stop()
        await self.blob_server.stop()
        await self._stop_replication()
        if self.state.journal is not None:
            self.state.journal.close()
            self.state.journal = None
        logger.warning(f"shard {self.shard_index} fenced at epoch {epoch}")

    async def _stop_replication(self) -> None:
        """Tear down the quorum-replication surfaces (ISSUE 19): cancel the
        writer's sender tasks and close the follower store's file handles.
        Replica streams STAY on disk — they are what a takeover seals and
        materializes after this shard (or its whole disk) is gone."""
        replicator = self.state.replicator
        if replicator is not None:
            await replicator.stop()
            self.state.replicator = None
        if self.replica_store is not None:
            self.replica_store.close()
            self.replica_store = None

    def note_fleet_epoch(self, epoch: int) -> None:
        """Adopt the director's fleet epoch (piggybacked on health probes and
        takeover adopts): the replicator stamps subsequent appends with it so
        followers can fence any incarnation of us that missed a takeover."""
        replicator = self.state.replicator
        if replicator is not None:
            replicator.note_epoch(epoch)

    async def adopt_from_replica(self, writer: int, partition: int, epoch: int) -> dict:
        """Quorum takeover (ISSUE 19, server/shards.py): adopt a dead
        writer's partition from OUR replica stream of its journal — the path
        the director takes when the writer's own journal directory is gone
        (lost disk). Seal first (idempotent; the director also seals every
        other surviving holder at the same epoch, so the old writer's quorum
        is structurally dead), then materialize the sealed stream into a
        journal-shaped directory and ride the existing adopt_partition
        replay."""
        import time as _time

        if self.replica_store is None:
            raise RuntimeError(
                f"shard {self.shard_index} holds no replica streams (replication off?)"
            )
        t0 = _time.time()
        sealed = self.replica_store.seal(writer, epoch)
        if not sealed.get("ok"):
            raise RuntimeError(f"seal of writer {writer} at epoch {epoch} refused: {sealed}")
        source = self.replica_store.materialize(writer)
        tracing.record_span(
            "control.seal",
            start=t0,
            end=_time.time(),
            attrs={
                "writer": writer,
                "partition": partition,
                "epoch": epoch,
                "sealed_seq": sealed.get("sealed_seq", 0),
            },
        )
        self.note_fleet_epoch(epoch)
        report = await self.adopt_partition(source, partition=partition)
        report["mode"] = "replica"
        report["writer"] = writer
        report["sealed_seq"] = sealed.get("sealed_seq", 0)
        return report

    def shard_status(self) -> dict:
        """Health/topology snapshot for the director's probe loop and the
        shard-aware `modal_tpu journal status`."""
        j = self.state.journal
        return {
            "shard_index": self.shard_index,
            "state_dir": self.state_dir,
            "url": self.server_url,
            "fenced": self.fenced,
            "fenced_at_epoch": self.fenced_at_epoch,
            "workers": len(self.state.workers),
            "open_calls": sum(
                1 for c in self.state.function_calls.values() if c.num_done < c.num_inputs
            ),
            "journal_seq": j.seq if j is not None else 0,
            "takeovers": len(self.takeover_reports),
            # quorum replication (ISSUE 19): writer-side follower lag/epoch
            # and the replica streams this shard holds for peer writers
            "replication": (
                self.state.replicator.status() if self.state.replicator is not None else None
            ),
            "replica_streams": (
                self.replica_store.status_all() if self.replica_store is not None else []
            ),
            # the director's shared chaos clock (subprocess shards report
            # their output count through the health probe)
            "chaos_outputs_seen": self.chaos.outputs_seen if self.chaos is not None else 0,
        }

    async def stop(self) -> None:
        # bounded: a supervisor that cannot shut down must not hang its host
        # forever — on timeout, log every still-pending task (with its await
        # site) and abandon the stragglers
        try:
            await asyncio.wait_for(asyncio.shield(self._stop_inner()), timeout=30.0)
        except asyncio.TimeoutError:
            pending = [t for t in asyncio.all_tasks() if t is not asyncio.current_task()]
            detail = "\n".join(f"  {t!r}" for t in pending if not t.done())
            logger.error(f"supervisor stop timed out after 30s; pending tasks:\n{detail}")

    async def _stop_inner(self) -> None:
        from .._utils import local_transport

        local_transport.unregister_local_server(self.server_url)
        local_transport.unregister_local_server(self.state.input_plane_url)
        if self.uds_path:
            try:
                os.unlink(self.uds_path)
            except OSError:
                pass
        if self._chaos_task is not None:
            self._chaos_task.cancel()
            try:
                await self._chaos_task
            except asyncio.CancelledError:
                pass
        for t in list(self._chaos_subtasks):
            t.cancel()
        if self._chaos_subtasks:
            await asyncio.gather(*self._chaos_subtasks, return_exceptions=True)
        for worker in self.workers:
            await worker.stop()
        if self.fenced:
            return  # fence() already tore down the serving surfaces + journal
        await self.scheduler.stop()
        await self._stop_sampler()
        await self.input_plane.stop()
        await self.blob_server.stop()
        await self._stop_replication()
        if self._grpc_server is not None:
            await self._grpc_server.stop(grace=0.5)
        if self.state.journal is not None:
            self.state.journal.close()


async def serve_forever(
    port: int = 9900,
    num_workers: int = 1,
    state_dir: Optional[str] = None,
    shards: int = 1,
    subprocess_shards: bool = False,
    shard_index: int = 0,
    blob_dir: Optional[str] = None,
    fleet_root: Optional[str] = None,
) -> None:
    if shards > 1:
        # sharded control plane (server/shards.py): shards==1 stays on this
        # code path untouched — the degradation contract docs/CONTROL_PLANE.md
        # leans on (the director is never even constructed)
        from .shards import ShardedSupervisor

        sup: Any = ShardedSupervisor(
            num_shards=shards,
            num_workers=num_workers,
            port=port,
            state_dir=state_dir,
            subprocess_shards=subprocess_shards,
        )
    else:
        sup = LocalSupervisor(
            num_workers=num_workers,
            port=port,
            state_dir=state_dir,
            shard_index=shard_index,
            blob_dir=blob_dir,
            fleet_root=fleet_root,
        )
    await sup.start()
    print(f"modal_tpu control plane listening on {sup.server_url}", flush=True)
    try:
        await asyncio.Event().wait()
    finally:
        await sup.stop()
