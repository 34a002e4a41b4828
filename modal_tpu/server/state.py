"""Control-plane state: the in-memory data model.

Shaped after the reference's test servicer state (reference:
py/test/conftest.py:701-820 MockClientServicer — apps, functions, input/output
queues, volumes, secrets) but built as a real backend: long-poll conditions,
task/worker scheduling state, gang (pod-slice) allocation, and an on-disk blob
+ volume-block store.
"""

from __future__ import annotations

import asyncio
import itertools
import os
import time
from dataclasses import dataclass, field
from typing import Any, Optional

from ..proto import api_pb2

# Sharded control plane (server/shards.py): every object id embeds its home
# partition so any id-carrying RPC is routable statelessly — the numeric part
# is `partition * PARTITION_STRIDE + local_counter`. Partition 0 stays inside
# the stride, so single-shard deployments (MODAL_TPU_SHARDS=1, the historical
# monolith) mint byte-identical 8-digit ids to every release before sharding.
PARTITION_STRIDE = 10**8

_id_counters: dict[tuple[int, str], itertools.count] = {}


def make_id(prefix: str, namespace: int = 0) -> str:
    counter = _id_counters.setdefault((namespace, prefix), itertools.count(1))
    return f"{prefix}-{namespace * PARTITION_STRIDE + next(counter):08d}"


def partition_of_id(object_id: str) -> Optional[int]:
    """Home partition embedded in an object id, or None when the id doesn't
    follow the `prefix-NNNNNNNN` scheme (content-hashed blob ids, external
    names). Routing falls back to the placement director for those."""
    _, _, num = object_id.rpartition("-")
    if not num.isdigit():
        return None
    return int(num) // PARTITION_STRIDE


def bump_id_counter(existing_id: str) -> None:
    """Advance the prefix counter past an id recovered from the journal so a
    fresh make_id can never re-issue it (server/journal.py recover_state).
    Counters only ever move forward — safe with several supervisors sharing
    one process (tests, in-process shards). Namespace-aware: replaying a dead
    shard's journal during takeover bumps the DEAD partition's counters, so a
    respawned shard fenced back in can never re-mint a migrated id either."""
    prefix, _, num = existing_id.rpartition("-")
    if not prefix or not num.isdigit():
        return
    namespace, floor = int(num) // PARTITION_STRIDE, int(num) % PARTITION_STRIDE + 1
    counter = _id_counters.setdefault((namespace, prefix), itertools.count(1))
    # itertools.count has no peek: draw once to learn the position, then
    # replace with whichever is further along
    current = next(counter)
    _id_counters[(namespace, prefix)] = itertools.count(max(current, floor))


@dataclass
class AppState:
    app_id: str
    description: str = ""
    name: str = ""
    state: int = api_pb2.APP_STATE_INITIALIZING
    environment_name: str = ""
    created_at: float = field(default_factory=time.time)
    stopped_at: float = 0.0
    last_heartbeat: float = field(default_factory=time.time)
    function_ids: dict[str, str] = field(default_factory=dict)
    class_ids: dict[str, str] = field(default_factory=dict)
    deployment_history: list[api_pb2.AppDeploymentHistory] = field(default_factory=list)
    version: int = 0
    log_entries: list[api_pb2.TaskLogs] = field(default_factory=list)
    log_condition: asyncio.Condition = field(default_factory=asyncio.Condition)
    done: bool = False


@dataclass
class InputState:
    input_id: str
    function_call_id: str
    idx: int
    input: api_pb2.FunctionInput
    status: str = "pending"  # pending | claimed | done | cancelled
    retry_count: int = 0
    claimed_by: str = ""  # task_id
    claimed_at: float = 0.0
    created_at: float = field(default_factory=time.time)
    # gang broadcast: which gang members have received this input
    delivered_to: set = field(default_factory=set)
    # checkpoint recorded by a preempted attempt (ContainerCheckpoint):
    # redelivered with the input so the retry resumes instead of restarting
    resume_token: str = ""
    # distributed tracing: "trace_id:span_id" captured at enqueue from the
    # submitting RPC's metadata; redelivered with the input so container
    # spans stitch into the caller's trace (observability/tracing.py)
    trace_context: str = ""


@dataclass
class FunctionCallState:
    function_call_id: str
    function_id: str
    call_type: int = api_pb2.FUNCTION_CALL_TYPE_UNARY
    invocation_type: int = api_pb2.FUNCTION_CALL_INVOCATION_TYPE_SYNC
    created_at: float = field(default_factory=time.time)
    input_ids: list[str] = field(default_factory=list)
    outputs: list[api_pb2.FunctionGetOutputsItem] = field(default_factory=list)
    outputs_consumed: int = 0
    output_condition: asyncio.Condition = field(default_factory=asyncio.Condition)
    data_chunks: list[api_pb2.DataChunk] = field(default_factory=list)
    data_condition: asyncio.Condition = field(default_factory=asyncio.Condition)
    num_inputs: int = 0
    num_done: int = 0
    cancelled: bool = False
    return_exceptions: bool = False
    first_output_at: float = 0.0
    server_originated: bool = False  # scheduled fire: GC after completion
    # exactly-once outputs (server/journal.py): dedupe keys
    # ("input_id:retry_count") of every delivered output — a requeued input
    # whose dead attempt already reported cannot double-deliver
    output_keys: set = field(default_factory=set)


@dataclass
class FunctionState:
    function_id: str
    app_id: str
    tag: str
    definition: api_pb2.Function
    created_at: float = field(default_factory=time.time)
    # queue of pending input_ids awaiting a container
    pending: list[str] = field(default_factory=list)
    input_condition: asyncio.Condition = field(default_factory=asyncio.Condition)
    # autoscaler bookkeeping
    task_ids: set[str] = field(default_factory=set)
    web_url: str = ""
    next_fire_at: float = 0.0  # schedule evaluation (server/cron.py)
    init_failures: int = 0  # consecutive container INIT_FAILUREs
    placement_unsat_since: float = 0.0  # when placement first looked unsatisfiable
    bound_parent: Optional[str] = None  # parametrized variant parent id
    serialized_params: bytes = b""
    autoscaler_override: Optional[api_pb2.AutoscalerSettings] = None
    # EWMA of per-call wall time, as reported by containers on
    # FunctionGetInputs (io_manager.note_call_time) — shapes the autoscaler's
    # drain-time estimate (reference autoscaler surface app.py:778)
    reported_call_time: float = 0.0
    # SLO autoscaling cooldown stamp (scheduler._slo_desired): serving
    # replica counts move at most one step per window, so a TTFT spike can't
    # slam min→max in one tick
    slo_last_scale_at: float = 0.0

    @property
    def autoscaler(self) -> api_pb2.AutoscalerSettings:
        return self.autoscaler_override or self.definition.autoscaler_settings


@dataclass
class TaskState_:
    task_id: str
    function_id: str
    app_id: str
    state: int = api_pb2.TASK_STATE_QUEUED
    worker_id: str = ""
    rank: int = 0
    cluster_id: str = ""
    created_at: float = field(default_factory=time.time)
    started_at: float = 0.0
    first_input_at: float = 0.0
    first_output_at: float = 0.0
    finished_at: float = 0.0
    last_heartbeat: float = 0.0
    cancelled_input_ids: list[str] = field(default_factory=list)
    terminate: bool = False
    preempted: bool = False  # torn down because a gang peer died
    result: Optional[api_pb2.GenericResult] = None
    tpu_chip_ids: list[int] = field(default_factory=list)
    container_address: str = ""
    # this replica's own web endpoint (FunctionSetWebUrl); the function-level
    # web_url is whichever replica registered last
    web_url: str = ""
    router_token: str = ""  # bearer token for the worker's command router
    # trace context of the input whose backlog caused this launch: the
    # container's boot/import spans parent here (cold-start attribution)
    trace_context: str = ""
    # served by a pre-forked warm-pool interpreter (ContainerHello stamp;
    # surfaced on TaskGetTimeline so bench.py can prove the warm path)
    warm_pool_hit: bool = False
    # the container's previous telemetry push (raw JSON) — counter/histogram
    # merges are delta'd against it (observability/device_telemetry.py)
    telemetry_prev_json: str = ""


@dataclass
class ClusterState:
    """A gang: N tasks co-scheduled on one pod slice (TPU-native analogue of
    the reference's i6pn cluster, _clustered_functions.py)."""

    cluster_id: str
    function_id: str
    size: int
    task_ids: list[str] = field(default_factory=list)  # rank order
    reported: dict[str, str] = field(default_factory=dict)  # task_id -> container addr
    coordinator_port: int = 0
    condition: asyncio.Condition = field(default_factory=asyncio.Condition)
    slice_info: Optional[api_pb2.TPUSliceInfo] = None


@dataclass
class WorkerState:
    worker_id: str
    hostname: str = ""
    tpu_type: str = ""
    num_chips: int = 0
    topology: str = ""
    milli_cpu: int = 0
    memory_mb: int = 0
    container_address: str = ""
    router_address: str = ""  # worker's TaskCommandRouter data plane
    slice_index: int = 0
    region: str = ""  # placement labels (SchedulerPlacement matching)
    zone: str = ""
    spot: bool = False
    instance_type: str = ""
    last_heartbeat: float = field(default_factory=time.time)
    # assignment channel consumed by the worker's WorkerPoll stream
    events: asyncio.Queue = field(default_factory=asyncio.Queue)
    active_tasks: set[str] = field(default_factory=set)
    chips_in_use: dict[int, str] = field(default_factory=dict)  # chip_id -> task_id
    # preemption drain: no NEW placements land here; tasks still running past
    # drain_deadline are force-reaped (their inputs requeue for free)
    draining: bool = False
    drain_deadline: float = 0.0
    # journal recovery (server/journal.py): a worker rebuilt from the journal
    # takes no placements until its next heartbeat re-adopts it; never
    # re-adopted within the grace window ⇒ deregistered by the reaper
    adoption_pending: bool = False
    recovered_at: float = 0.0
    # parked warm-pool interpreters this host reported on its last heartbeat
    # (scheduler prefers warm hosts on placement ties)
    warm_pool_ready: int = 0
    # image_id -> target last directed to this worker (scheduler
    # _sync_pool_directives; diffed so directives are sent on change only)
    pool_directives: dict[str, int] = field(default_factory=dict)

    def free_chips(self) -> list[int]:
        return [c for c in range(self.num_chips) if c not in self.chips_in_use]


@dataclass
class VolumeState:
    volume_id: str
    name: str = ""
    version: int = api_pb2.VOLUME_FS_VERSION_V2
    created_at: float = field(default_factory=time.time)
    files: dict[str, api_pb2.VolumeFile] = field(default_factory=dict)
    committed_version: int = 0
    # ephemeral objects are reaped when their client's heartbeat goes stale
    # (reference _object.py:21); 0.0 heartbeat = not ephemeral
    ephemeral: bool = False
    last_heartbeat: float = 0.0


@dataclass
class ProxyState:
    """Static-egress proxy (reference proxy.py:1): a named, stable outbound
    IP that functions can bind to via `proxy=`."""

    proxy_id: str
    name: str = ""
    proxy_ip: str = ""
    environment_name: str = ""
    created_at: float = field(default_factory=time.time)


@dataclass
class SecretState:
    secret_id: str
    name: str = ""
    env_dict: dict[str, str] = field(default_factory=dict)
    created_at: float = field(default_factory=time.time)
    last_used_at: float = 0.0


@dataclass
class DictState:
    dict_id: str
    name: str = ""
    data: dict[bytes, bytes] = field(default_factory=dict)
    created_at: float = field(default_factory=time.time)
    ephemeral: bool = False
    last_heartbeat: float = 0.0


@dataclass
class QueuePartition:
    items: list[tuple[str, bytes]] = field(default_factory=list)  # (entry_id, value)
    condition: asyncio.Condition = field(default_factory=asyncio.Condition)
    next_entry: int = 0


@dataclass
class QueueState:
    queue_id: str
    name: str = ""
    partitions: dict[str, QueuePartition] = field(default_factory=dict)
    created_at: float = field(default_factory=time.time)
    ephemeral: bool = False
    last_heartbeat: float = 0.0

    def partition(self, key: str) -> QueuePartition:
        return self.partitions.setdefault(key, QueuePartition())


@dataclass
class ImageState:
    image_id: str
    definition: api_pb2.Image
    metadata: api_pb2.ImageMetadata = field(default_factory=api_pb2.ImageMetadata)
    built: bool = False
    build_logs: list[api_pb2.TaskLogs] = field(default_factory=list)
    created_at: float = field(default_factory=time.time)


@dataclass
class SandboxState_:
    sandbox_id: str
    app_id: str
    definition: api_pb2.Sandbox
    state: int = api_pb2.SANDBOX_STATE_PENDING
    task_id: str = ""
    created_at: float = field(default_factory=time.time)
    result: Optional[api_pb2.GenericResult] = None
    condition: asyncio.Condition = field(default_factory=asyncio.Condition)
    stdin_chunks: list[bytes] = field(default_factory=list)
    stdin_eof: bool = False
    stdin_last_index: int = 0  # dedups retried SandboxStdinWrite calls
    name: str = ""
    tunnels: list = field(default_factory=list)  # TunnelData, worker-reported
    tunnels_reported: bool = False
    ready: bool = False  # readiness probe passed (or no probe configured)
    workdir: str = ""  # worker-reported ACTUAL cwd (fs snapshots tar this)
    # name -> SandboxSidecar proto (reference sandbox.py:2157 sidecars):
    # running/returncode updated by SandboxSidecarExit from the worker
    sidecars: dict[str, api_pb2.SandboxSidecar] = field(default_factory=dict)


@dataclass
class SandboxSnapshotState:
    """A full sandbox snapshot: definition + filesystem tarball
    (reference snapshot.py:17 _SandboxSnapshot)."""

    snapshot_id: str
    definition: api_pb2.Sandbox
    fs_blob_id: str  # empty if the sandbox had no workdir content
    created_at: float = field(default_factory=time.time)


class ServerState:
    """All control-plane state + the on-disk stores."""

    def __init__(self, state_dir: str, shard_index: int = 0, blob_dir: Optional[str] = None):
        self.state_dir = state_dir
        # Which control-plane partition this state natively mints ids into
        # (server/shards.py). 0 for the monolith — ids and journals are then
        # identical to the pre-sharding layout.
        self.shard_index = shard_index
        # Shards share one blob/block store (blob ids are content-addressed or
        # presigned-URL-only, so any shard can serve any blob) — the sharded
        # supervisor passes a common data dir here; the monolith keeps the
        # per-state-dir default.
        self.blob_dir = blob_dir or os.path.join(state_dir, "blobs")
        self.block_dir = os.path.join(os.path.dirname(self.blob_dir), "volume_blocks")
        os.makedirs(self.blob_dir, exist_ok=True)
        os.makedirs(self.block_dir, exist_ok=True)
        # fleet compile cache (ISSUE 20, server/compile_cache.py): shared like
        # the blob store — entries are content-keyed, any shard serves any key
        from .compile_cache import CompileCacheStore

        self.compile_cache = CompileCacheStore(
            os.path.join(os.path.dirname(self.blob_dir), "compile_cache")
        )

        self.apps: dict[str, AppState] = {}
        self.deployed_apps: dict[tuple[str, str], str] = {}  # (env, name) -> app_id
        self.functions: dict[str, FunctionState] = {}
        self.deployed_functions: dict[tuple[str, str, str], str] = {}  # (env, app_name, tag) -> fn_id
        self.inputs: dict[str, InputState] = {}
        self.function_calls: dict[str, FunctionCallState] = {}
        self.tasks: dict[str, TaskState_] = {}
        self.clusters: dict[str, ClusterState] = {}
        self.workers: dict[str, WorkerState] = {}
        self.volumes: dict[str, VolumeState] = {}
        self.deployed_volumes: dict[tuple[str, str], str] = {}
        self.secrets: dict[str, SecretState] = {}
        self.deployed_secrets: dict[tuple[str, str], str] = {}
        self.dicts: dict[str, DictState] = {}
        self.deployed_dicts: dict[tuple[str, str], str] = {}
        self.queues: dict[str, QueueState] = {}
        self.deployed_queues: dict[tuple[str, str], str] = {}
        self.proxies: dict[str, "ProxyState"] = {}
        self.deployed_proxies: dict[tuple[str, str], str] = {}
        self.images: dict[str, ImageState] = {}
        self.images_by_hash: dict[str, str] = {}
        self.sandboxes: dict[str, SandboxState_] = {}
        self.sandbox_snapshots: dict[str, SandboxSnapshotState] = {}
        # (task_id, port) -> (server, proxy_port), or an asyncio.Future while
        # a TunnelStart is mid-flight (the reservation protocol in TunnelStart)
        self.tunnels: dict[tuple[str, int], object] = {}
        self.environments: dict[str, str] = {"main": ""}  # name -> web suffix
        self.tokens: dict[str, str] = {}  # token_id -> token_secret
        # token_id -> grant timestamp: the local workspace's "members" are
        # its issued tokens, oldest = owner (services.py WorkspaceMemberList)
        self.token_granted_at: dict[str, float] = {}
        # workspace-wide settings (reference _WorkspaceSettingsManager,
        # _workspace.py:387): validated in WorkspaceSettingsSet
        self.workspace_settings: dict[str, str] = {}
        # flow_id -> {token_id, token_secret, code, approved: asyncio.Event,
        # localhost_port} — browser-completed token issuance (services.py
        # TokenFlowCreate + blob_server auth route)
        self.pending_token_flows: dict[str, dict] = {}
        self.blob_url_base: str = ""  # set by supervisor once blob server is up
        # active profiling command ("start:<hz>" | "stop" | ""): repeated on
        # every container heartbeat while set (ProfileControl, profiler.py).
        # "stop" expires after PROFILE_STOP_TTL_S — it only needs to reach
        # containers live at stop time; broadcast forever it would also kill
        # every FUTURE container's env-enabled (MODAL_TPU_PROFILE) profiler
        self.profile_command: str = ""
        self.profile_command_set_at: float = 0.0
        # input plane (region-local data plane): url advertised in
        # ClientHello; HS256 secret shared between AuthTokenGet (control
        # plane) and the input-plane servicer's verifier; attempt_token ->
        # (function_call_id, input_id)
        self.input_plane_url: str = ""
        # local fast-path coordinates advertised on ClientHello (ISSUE 8,
        # docs/DISPATCH.md): the control/input-plane Unix sockets and the
        # on-disk blob store a co-located client can touch directly
        self.uds_path: str = ""
        self.input_plane_uds: str = ""
        self.blob_local_dir: str = ""
        self.auth_secret: bytes = os.urandom(32)
        self.attempts: dict[str, tuple[str, str, float]] = {}  # token -> (call_id, input_id, minted_at)

        # scheduling wakeup
        self.schedule_event = asyncio.Event()

        # durable control plane (server/journal.py): wired by the supervisor
        # when journaling is enabled. journal = write-ahead record sink;
        # idempotency = journal-backed seen-set for mutating RPC dedupe.
        self.journal = None  # Optional[journal.Journal]
        self.idempotency = None  # Optional[journal.IdempotencyCache]
        # quorum journal replication (ISSUE 19, server/replication.py):
        # wired by the supervisor when MODAL_TPU_JOURNAL_REPLICAS > 0; the
        # RPC layer's _maybe_quorum reads it at handler-build time
        self.replicator = None  # Optional[replication.JournalReplicator]

        # fleet SLO observability (ISSUE 11): the supervisor-resident
        # time-series store + burn-rate evaluator (wired by the supervisor's
        # sampler loop; None on bare states, e.g. scheduler unit tests).
        # `alerts` is the journal-backed projection of SLO alert state —
        # rule name -> last transition dict — rebuilt by replay ("alert"
        # records) so firing alerts survive crash_restart.
        self.timeseries = None  # Optional[timeseries.TimeSeriesStore]
        self.slo = None  # Optional[slo.SLOEvaluator]
        self.alerts: dict[str, dict] = {}

    def make_id(self, prefix: str) -> str:
        """Mint an id in this shard's home partition (module-level make_id
        namespaced by shard_index). All servicer/scheduler/input-plane id
        minting goes through here so migrated partitions keep routing to
        their journaled home while new objects land on the live shard."""
        return make_id(prefix, self.shard_index)

    # -- blob store ---------------------------------------------------------

    def blob_path(self, blob_id: str) -> str:
        return os.path.join(self.blob_dir, blob_id)

    def block_path(self, sha256_hex: str) -> str:
        return os.path.join(self.block_dir, sha256_hex)

    def put_block(self, sha256_hex: str, data: bytes) -> None:
        path = self.block_path(sha256_hex)
        if not os.path.exists(path):
            tmp = path + ".tmp"
            with open(tmp, "wb") as f:
                f.write(data)
            os.replace(tmp, path)

    def has_block(self, sha256_hex: str) -> bool:
        return os.path.exists(self.block_path(sha256_hex))

    def get_block(self, sha256_hex: str, offset: int = 0, length: int = 0) -> bytes:
        with open(self.block_path(sha256_hex), "rb") as f:
            f.seek(offset)
            return f.read(length) if length else f.read()

    # -- helpers ------------------------------------------------------------

    def app_log(self, app_id: str, data: str, task_id: str = "", fd: int = 1, function_call_id: str = "") -> None:
        app = self.apps.get(app_id)
        if app is None:
            return
        app.log_entries.append(
            api_pb2.TaskLogs(
                data=data, task_id=task_id, file_descriptor=fd, timestamp=time.time(), function_call_id=function_call_id
            )
        )

    async def notify_logs(self, app_id: str) -> None:
        app = self.apps.get(app_id)
        if app is not None:
            async with app.log_condition:
                app.log_condition.notify_all()
