"""Control-plane RPC implementations.

The real backend the reference doesn't ship (its control plane is closed
source; SURVEY §7 step 3 "the mock made real"). Handlers follow the contract
encoded in the reference's client call sites: FunctionMap/GetOutputs long-poll
semantics (_functions.py:140-262), FunctionGetInputs/PutOutputs container
loops (container_io_manager.py:788-886), TaskClusterHello gang rendezvous
(_clustered_functions.py:70-83).
"""

from __future__ import annotations

import asyncio
import hashlib
import json
import os
import time
from typing import Any, Optional

import grpc

from ..config import logger
from ..observability import tracing
from ..observability.catalog import (
    INPUT_QUEUE_WAIT,
    TASK_RESULTS,
    WORKER_HEARTBEATS,
    WORKERS_READOPTED,
)
from ..proto import api_pb2
from .journal import _b64 as _jb64
from .scheduler import PLACEMENT_UNSAT_GRACE_S
from .state import (
    AppState,
    ClusterState,
    DictState,
    FunctionCallState,
    FunctionState,
    ImageState,
    InputState,
    ProxyState,
    QueueState,
    SecretState,
    ServerState,
    TaskState_,
    VolumeState,
    WorkerState,
)

# how long a ProfileControl "stop" keeps broadcasting on heartbeats before
# expiring (long enough for every live container's next few beats; short
# enough that future env-enabled profilers aren't killed at boot)
PROFILE_STOP_TTL_S = 60.0

CREATE_IF_MISSING = api_pb2.OBJECT_CREATION_TYPE_CREATE_IF_MISSING
FAIL_IF_EXISTS = api_pb2.OBJECT_CREATION_TYPE_CREATE_FAIL_IF_EXISTS
EPHEMERAL = api_pb2.OBJECT_CREATION_TYPE_EPHEMERAL
ANONYMOUS = api_pb2.OBJECT_CREATION_TYPE_ANONYMOUS_OWNED_BY_APP


class ModalTPUServicer:
    """All RPC handlers. One instance per control plane."""

    def __init__(self, state: ServerState):
        self.s = state
        self.scheduler = None  # wired by the supervisor (sandbox placement)
        self.chaos = None  # ChaosPolicy, wired by the supervisor when attached
        self.supervisor = None  # LocalSupervisor backref (ShardControl admin)
        # real throttling control surfaced to containers on every GetInputs
        # response (reference rate_limit_sleep_duration)
        self.rate_limit_sleep_duration = 0.0

    # ------------------------------------------------------------------
    # Durable control plane (server/journal.py)
    # ------------------------------------------------------------------

    @property
    def idempotency(self):
        """Journal-backed idempotency seen-set, consumed by the dedupe
        wrapper in proto/rpc.py. None when journaling is off."""
        return self.s.idempotency

    @property
    def replicator(self):
        """Quorum journal replicator (ISSUE 19, server/replication.py),
        consumed by the quorum-commit wrapper in proto/rpc.py. None when
        journaling or replication is off."""
        return self.s.replicator

    def _j(self, t: str, **payload) -> None:
        """Append one typed record to the write-ahead journal (no-op when
        journaling is off). Every mutating handler below calls this with the
        EFFECT it just applied — replay is services-agnostic."""
        j = self.s.journal
        if j is not None:
            j.append(t, **payload)

    def _journal_group(self):
        """Group-commit scope for coalesced handlers (journal.group()): N
        records, one flush, committed before the RPC returns — batched
        appends group-commit but never skip (docs/RECOVERY.md)."""
        import contextlib

        j = self.s.journal
        return j.group() if j is not None else contextlib.nullcontext()

    def _append_output(self, call: FunctionCallState, item: api_pb2.FunctionGetOutputsItem) -> bool:
        """The one funnel every delivered output goes through: dedupe by
        (input_id, retry_count) so a requeued input whose dead attempt
        already reported cannot double-deliver, then append + journal.
        Returns False when the output was a duplicate."""
        key = f"{item.input_id}:{item.retry_count}"
        if item.input_id and key in call.output_keys:
            return False
        if item.input_id:
            call.output_keys.add(key)
        call.outputs.append(item)
        call.num_done += 1
        call.first_output_at = call.first_output_at or time.time()
        if self.s.journal is not None:  # don't pay serialize+b64 when journaling is off
            self._j(
                "output",
                function_call_id=call.function_call_id,
                item=_jb64(item.SerializeToString()),
            )
        return True

    async def maybe_compact(self) -> None:
        """Periodic journal compaction (scheduler reap tick): snapshot the
        current state and prune covered segments once enough records pile up.
        Synthesis happens on the loop (consistent view); the bulk write/fsync
        runs in a thread so RPC handling never stalls on snapshot I/O."""
        from .journal import COMPACT_EVERY_RECORDS, synthesize_records

        j = self.s.journal
        if j is not None and j.records_since_snapshot() >= COMPACT_EVERY_RECORDS:
            await j.compact_async(synthesize_records(self.s))
            logger.info(f"journal compacted at seq {j.seq}")

    # ------------------------------------------------------------------
    # Misc
    # ------------------------------------------------------------------

    async def ClientHello(self, request: api_pb2.ClientHelloRequest, context) -> api_pb2.ClientHelloResponse:
        return api_pb2.ClientHelloResponse(
            server_version="0.1.0",
            # workspace-wide override (WorkspaceSettingsSet) wins over the
            # build default — clients pick this up at handshake
            image_builder_version=self.s.workspace_settings.get("image_builder_version", "2026.07"),
            input_plane_url=self.s.input_plane_url,
            # local fast-path coordinates (docs/DISPATCH.md): a client that
            # can stat these paths is co-located and upgrades its transport;
            # anyone else ignores them
            uds_path=self.s.uds_path,
            input_plane_uds_path=self.s.input_plane_uds,
            blob_local_dir=self.s.blob_local_dir,
        )

    def _resolve_environment(self, name: str) -> str:
        """Empty environment name resolves to the workspace's configured
        default (WorkspaceSettingsSet default_environment), falling back to
        "" (the implicit main) — the reference's per-workspace default
        environment behavior (_workspace.py:420)."""
        return name or self.s.workspace_settings.get("default_environment", "")

    async def AuthTokenGet(self, request: api_pb2.AuthTokenGetRequest, context) -> api_pb2.AuthTokenGetResponse:
        """Issue an input-plane JWT (reference: AuthTokenGet consumed by
        _AuthTokenManager, auth_token_manager.py:28). TTL overridable for
        expiry tests via MODAL_TPU_AUTH_TOKEN_TTL."""
        from .._utils.jwt_utils import encode_jwt

        ttl = float(os.environ.get("MODAL_TPU_AUTH_TOKEN_TTL", "1200"))
        token = encode_jwt({"sub": "input-plane"}, self.s.auth_secret, ttl_s=ttl)
        return api_pb2.AuthTokenGetResponse(token=token)

    async def EnvironmentList(self, request, context):
        names = set(self.s.environments) | {env for env, _ in self.s.deployed_apps.keys() if env}
        return api_pb2.EnvironmentListResponse(
            items=[api_pb2.EnvironmentListItem(name=n) for n in sorted(names)]
        )

    async def EnvironmentCreate(self, request, context):
        name = request.name
        if not name:
            await context.abort(grpc.StatusCode.INVALID_ARGUMENT, "environment needs a name")
        self.s.environments.setdefault(name, "")
        self._j("environment", name=name, web_suffix=self.s.environments[name])
        return api_pb2.EnvironmentCreateResponse()

    async def EnvironmentDelete(self, request, context):
        name = request.name
        if any(env == name for env, _ in self.s.deployed_apps.keys()):
            await context.abort(
                grpc.StatusCode.FAILED_PRECONDITION, f"environment {name!r} still has deployed apps"
            )
        self.s.environments.pop(name, None)
        self._j("environment_del", name=name)
        return api_pb2.EnvironmentDeleteResponse()

    async def EnvironmentUpdate(self, request, context):
        current = request.current_name
        if current not in self.s.environments:
            await context.abort(grpc.StatusCode.NOT_FOUND, f"environment {current!r} not found")
        if request.HasField("web_suffix"):
            self.s.environments[current] = request.web_suffix
        if request.HasField("name") and request.name and request.name != current:
            if request.name in self.s.environments:
                await context.abort(
                    grpc.StatusCode.ALREADY_EXISTS, f"environment {request.name!r} already exists"
                )
            self.s.environments[request.name] = self.s.environments.pop(current)
            # re-key deployments under the new name
            for (env, app_name), app_id in list(self.s.deployed_apps.items()):
                if env == current:
                    del self.s.deployed_apps[(env, app_name)]
                    self.s.deployed_apps[(request.name, app_name)] = app_id
        rec: dict = {"current": current}
        if request.HasField("web_suffix"):
            rec["web_suffix"] = request.web_suffix
        if request.HasField("name") and request.name:
            rec["name"] = request.name
        self._j("environment_update", **rec)
        return api_pb2.EnvironmentUpdateResponse()

    async def TokenFlowCreate(self, request, context):
        """Browser-completed token issuance (reference token_flow.py:1): the
        flow's web_url is an HTTP page served by this control plane's blob
        server; visiting it with the verification code approves the flow and
        unblocks TokenFlowWait. Headless callers pass timeout=0 to Wait for
        an immediate local grant."""
        import secrets as _secrets

        flow_id = self.s.make_id("tf")
        self.s.pending_token_flows[flow_id] = {
            "token_id": "tk-" + _secrets.token_hex(8),
            "token_secret": "ts-" + _secrets.token_hex(16),
            "code": _secrets.token_hex(3),
            "approved": asyncio.Event(),
            "localhost_port": request.localhost_port,
        }
        flow = self.s.pending_token_flows[flow_id]
        base = self.s.blob_url_base or ""
        web_url = (
            f"{base}/auth/token-flow/{flow_id}?code={flow['code']}"
            if base
            else "local://token-granted"
        )
        return api_pb2.TokenFlowCreateResponse(
            token_flow_id=flow_id, web_url=web_url, code=flow["code"]
        )

    async def TokenFlowWait(self, request, context):
        flow = self.s.pending_token_flows.get(request.token_flow_id)
        if flow is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "unknown token flow")
        if request.timeout > 0:
            # browser flow: block until the web page approves (or time out —
            # the CLI polls, reference token_flow.py finish loop)
            try:
                await asyncio.wait_for(flow["approved"].wait(), request.timeout)
            except asyncio.TimeoutError:
                return api_pb2.TokenFlowWaitResponse(timeout=True)
        # timeout == 0: headless local grant, no browser leg.
        # pop-not-del: a retried Wait (dropped response) may race another
        # waiter for the same flow — the grant is idempotent, both get the
        # same credentials.
        self.s.tokens[flow["token_id"]] = flow["token_secret"]
        self.s.token_granted_at.setdefault(flow["token_id"], time.time())
        self._j(
            "token",
            token_id=flow["token_id"],
            token_secret=flow["token_secret"],
            granted_at=self.s.token_granted_at[flow["token_id"]],
        )
        self.s.pending_token_flows.pop(request.token_flow_id, None)
        return api_pb2.TokenFlowWaitResponse(
            token_id=flow["token_id"], token_secret=flow["token_secret"], workspace_name="local"
        )

    # ------------------------------------------------------------------
    # Workspace (reference _workspace.py:70; billing RPCs are NG)
    # ------------------------------------------------------------------

    # settings the local control plane understands; Set validates against
    # this so a typo'd name fails loudly (reference settings manager has a
    # curated set too, _workspace.py:387)
    _WORKSPACE_SETTINGS = ("image_builder_version", "default_environment")

    async def WorkspaceNameLookup(
        self, request: api_pb2.WorkspaceNameLookupRequest, context
    ) -> api_pb2.WorkspaceNameLookupResponse:
        return api_pb2.WorkspaceNameLookupResponse(workspace_name="local", username="local")

    async def WorkspaceMemberList(
        self, request: api_pb2.WorkspaceMemberListRequest, context
    ) -> api_pb2.WorkspaceMemberListResponse:
        members = []
        ordered = sorted(self.s.tokens, key=lambda t: self.s.token_granted_at.get(t, 0.0))
        for i, token_id in enumerate(ordered):
            members.append(
                api_pb2.WorkspaceMemberInfo(
                    username=token_id,
                    role="owner" if i == 0 else "member",
                    created_at=self.s.token_granted_at.get(token_id, 0.0),
                )
            )
        return api_pb2.WorkspaceMemberListResponse(members=members)

    async def WorkspaceSettingsList(
        self, request: api_pb2.WorkspaceSettingsListRequest, context
    ) -> api_pb2.WorkspaceSettingsListResponse:
        return api_pb2.WorkspaceSettingsListResponse(
            settings=[
                api_pb2.WorkspaceSetting(name=k, value=v)
                for k, v in sorted(self.s.workspace_settings.items())
            ]
        )

    async def WorkspaceSettingsSet(
        self, request: api_pb2.WorkspaceSettingsSetRequest, context
    ) -> api_pb2.WorkspaceSettingsSetResponse:
        if request.name not in self._WORKSPACE_SETTINGS:
            await context.abort(
                grpc.StatusCode.INVALID_ARGUMENT,
                f"unknown workspace setting {request.name!r} (known: {', '.join(self._WORKSPACE_SETTINGS)})",
            )
        if not request.value:
            # empty value = unset (there is no separate delete RPC)
            self.s.workspace_settings.pop(request.name, None)
            self._j("ws_setting", name=request.name, value="")
            return api_pb2.WorkspaceSettingsSetResponse()
        if request.name == "image_builder_version":
            from ..builder import known_versions

            known = known_versions()
            if known and request.value not in known:
                await context.abort(
                    grpc.StatusCode.INVALID_ARGUMENT,
                    f"unknown image builder version {request.value!r} (known: {', '.join(known)})",
                )
        if request.name == "default_environment" and request.value not in self.s.environments:
            await context.abort(
                grpc.StatusCode.NOT_FOUND, f"environment {request.value!r} does not exist"
            )
        self.s.workspace_settings[request.name] = request.value
        self._j("ws_setting", name=request.name, value=request.value)
        return api_pb2.WorkspaceSettingsSetResponse()

    # ------------------------------------------------------------------
    # Apps
    # ------------------------------------------------------------------

    async def AppCreate(self, request: api_pb2.AppCreateRequest, context) -> api_pb2.AppCreateResponse:
        app_id = self.s.make_id("ap")
        app = AppState(
            app_id=app_id,
            description=request.description,
            state=request.app_state or api_pb2.APP_STATE_INITIALIZING,
            environment_name=self._resolve_environment(request.environment_name),
        )
        self.s.apps[app_id] = app
        self._j(
            "app",
            app_id=app_id,
            description=app.description,
            state=app.state,
            environment_name=app.environment_name,
        )
        return api_pb2.AppCreateResponse(app_id=app_id, app_page_url=f"http://local/apps/{app_id}")

    async def AppGetOrCreate(self, request: api_pb2.AppGetOrCreateRequest, context) -> api_pb2.AppGetOrCreateResponse:
        key = (self._resolve_environment(request.environment_name), request.app_name)
        app_id = self.s.deployed_apps.get(key)
        if app_id is None:
            if request.object_creation_type not in (CREATE_IF_MISSING, FAIL_IF_EXISTS):
                await context.abort(grpc.StatusCode.NOT_FOUND, f"app {request.app_name!r} not found")
            app_id = self.s.make_id("ap")
            self.s.apps[app_id] = AppState(
                app_id=app_id,
                name=request.app_name,
                description=request.app_name,
                state=api_pb2.APP_STATE_DEPLOYED,
                environment_name=key[0],
            )
            self.s.deployed_apps[key] = app_id
            self._j(
                "app",
                app_id=app_id,
                name=request.app_name,
                description=request.app_name,
                state=api_pb2.APP_STATE_DEPLOYED,
                environment_name=key[0],
                deploy_name=request.app_name,
            )
        elif request.object_creation_type == FAIL_IF_EXISTS:
            await context.abort(grpc.StatusCode.ALREADY_EXISTS, f"app {request.app_name!r} exists")
        return api_pb2.AppGetOrCreateResponse(app_id=app_id)

    async def AppHeartbeat(self, request, context) -> api_pb2.AppHeartbeatResponse:
        app = self.s.apps.get(request.app_id)
        if app is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, f"app {request.app_id} not found")
        app.last_heartbeat = time.time()
        return api_pb2.AppHeartbeatResponse()

    async def AppPublish(self, request: api_pb2.AppPublishRequest, context) -> api_pb2.AppPublishResponse:
        app = self.s.apps.get(request.app_id)
        if app is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "app not found")
        app.state = request.app_state
        app.function_ids.update(request.function_ids)
        app.class_ids.update(request.class_ids)
        if request.name:
            app.name = request.name
            self.s.deployed_apps[(app.environment_name, request.name)] = app.app_id
            for (env, app_name, tag) in list(self.s.deployed_functions.keys()):
                if env == app.environment_name and app_name == request.name:
                    del self.s.deployed_functions[(env, app_name, tag)]
            for tag, fn_id in request.function_ids.items():
                self.s.deployed_functions[(app.environment_name, request.name, tag)] = fn_id
            app.version += 1
            app.deployment_history.append(
                api_pb2.AppDeploymentHistory(
                    app_id=app.app_id,
                    version=app.version,
                    deployed_at=time.time(),
                    deployment_tag=request.deployment_tag,
                    commit_info=request.commit_info,
                )
            )
        self._j(
            "app_state",
            app_id=app.app_id,
            state=app.state,
            function_ids=dict(request.function_ids),
            class_ids=dict(request.class_ids),
            name=request.name or "",
            publish=True,  # replay re-keys deployed_functions (AppDeploy doesn't)
        )
        self.s.schedule_event.set()  # min_containers may need warm pools
        return api_pb2.AppPublishResponse(url=f"http://local/apps/{app.app_id}")

    async def AppClientDisconnect(self, request, context) -> api_pb2.AppClientDisconnectResponse:
        app = self.s.apps.get(request.app_id)
        if app is not None and app.state in (api_pb2.APP_STATE_EPHEMERAL, api_pb2.APP_STATE_INITIALIZING):
            await self._stop_app(app)
        return api_pb2.AppClientDisconnectResponse()

    async def AppStop(self, request, context) -> api_pb2.AppStopResponse:
        app = self.s.apps.get(request.app_id)
        if app is not None:
            await self._stop_app(app)
        return api_pb2.AppStopResponse()

    async def _stop_app(self, app: AppState) -> None:
        app.state = api_pb2.APP_STATE_STOPPED
        app.stopped_at = time.time()
        app.done = True
        self._j(
            "app_state", app_id=app.app_id, state=app.state, done=True, stopped_at=app.stopped_at
        )
        # stop tasks belonging to the app
        for task in list(self.s.tasks.values()):
            if task.app_id == app.app_id and task.state not in (
                api_pb2.TASK_STATE_COMPLETED,
                api_pb2.TASK_STATE_FAILED,
                api_pb2.TASK_STATE_TERMINATED,
            ):
                task.terminate = True
                worker = self.s.workers.get(task.worker_id)
                if worker is not None:
                    await worker.events.put(
                        api_pb2.WorkerPollResponse(stop=api_pb2.TaskStopEvent(task_id=task.task_id))
                    )
        # wake any input long-polls so containers see kill switches
        for fn_id in app.function_ids.values():
            fn = self.s.functions.get(fn_id)
            if fn is not None:
                async with fn.input_condition:
                    fn.input_condition.notify_all()
        await self.s.notify_logs(app.app_id)
        async with app.log_condition:
            app.log_condition.notify_all()

    async def AppGetLayout(self, request, context) -> api_pb2.AppGetLayoutResponse:
        app = self.s.apps.get(request.app_id)
        if app is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "app not found")
        layout = api_pb2.AppLayout()
        for tag, fn_id in app.function_ids.items():
            layout.objects[tag] = fn_id
            fn = self.s.functions.get(fn_id)
            if fn is not None:
                layout.function_metadata[tag].CopyFrom(self._function_metadata(fn))
        for tag, cls_id in app.class_ids.items():
            layout.objects[tag] = cls_id
        return api_pb2.AppGetLayoutResponse(app_layout=layout)

    async def AppList(self, request, context) -> api_pb2.AppListResponse:
        items = []
        for app in self.s.apps.values():
            if request.environment_name and app.environment_name != request.environment_name:
                continue
            n_running = sum(
                1
                for t in self.s.tasks.values()
                if t.app_id == app.app_id and t.state == api_pb2.TASK_STATE_ACTIVE
            )
            items.append(
                api_pb2.AppListItem(
                    app_id=app.app_id,
                    description=app.description,
                    state=app.state,
                    created_at=app.created_at,
                    stopped_at=app.stopped_at,
                    name=app.name,
                    n_running_tasks=n_running,
                )
            )
        return api_pb2.AppListResponse(apps=sorted(items, key=lambda a: a.created_at, reverse=True))

    async def AppDeploy(self, request, context) -> api_pb2.AppDeployResponse:
        app = self.s.apps.get(request.app_id)
        if app is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "app not found")
        app.state = api_pb2.APP_STATE_DEPLOYED
        self.s.deployed_apps[(app.environment_name, request.name)] = app.app_id
        self._j("app_state", app_id=app.app_id, state=app.state, name=request.name)
        return api_pb2.AppDeployResponse(url=f"http://local/apps/{app.app_id}")

    async def AppGetByDeploymentName(self, request, context) -> api_pb2.AppGetByDeploymentNameResponse:
        app_id = self.s.deployed_apps.get((self._resolve_environment(request.environment_name), request.name))
        if app_id is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, f"deployed app {request.name!r} not found")
        return api_pb2.AppGetByDeploymentNameResponse(app_id=app_id)

    async def AppDeploymentHistory(self, request, context) -> api_pb2.AppDeploymentHistoryResponse:
        app = self.s.apps.get(request.app_id)
        if app is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "app not found")
        return api_pb2.AppDeploymentHistoryResponse(history=app.deployment_history)

    async def AppGetLogs(self, request: api_pb2.AppGetLogsRequest, context):
        """Server-streaming log tail with long-poll (reference AppGetLogs)."""
        app = self.s.apps.get(request.app_id)
        if app is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "app not found")
        pos = int(request.last_entry_id) if request.last_entry_id else 0
        deadline = time.monotonic() + (request.timeout or 55.0)
        while time.monotonic() < deadline:
            entries = app.log_entries[pos:]
            if entries:
                for i, entry in enumerate(entries):
                    if request.task_id and entry.task_id != request.task_id:
                        continue  # filtered entries still advance the cursor
                    batch = api_pb2.TaskLogsBatch(entry_id=str(pos + i + 1))
                    batch.items.append(entry)
                    yield batch
                pos += len(entries)
            if app.done:
                yield api_pb2.TaskLogsBatch(app_done=True, entry_id=str(pos))
                return
            async with app.log_condition:
                try:
                    await asyncio.wait_for(app.log_condition.wait(), timeout=1.0)
                except asyncio.TimeoutError:
                    pass

    # ------------------------------------------------------------------
    # Blobs
    # ------------------------------------------------------------------

    async def BlobCreate(self, request: api_pb2.BlobCreateRequest, context) -> api_pb2.BlobCreateResponse:
        blob_id = "bl-" + hashlib.sha256(
            (request.content_sha256_base64 + str(time.time_ns())).encode()
        ).hexdigest()[:16]
        # Multipart above the reference threshold (blob_utils.py:54: 1 GiB;
        # env-overridable so tests exercise the path without GiB payloads).
        # Part length balances part count (S3-style 10k cap) against memory.
        from .._utils.blob_utils import MULTIPART_THRESHOLD

        threshold = int(os.environ.get("MODAL_TPU_MULTIPART_THRESHOLD", str(MULTIPART_THRESHOLD)))
        if request.content_length >= threshold:
            part_length = int(
                os.environ.get("MODAL_TPU_MULTIPART_PART_LEN", str(64 * 1024 * 1024))
            )
            part_length = max(part_length, (request.content_length + 9_999) // 10_000)
            n_parts = (request.content_length + part_length - 1) // part_length
            mp = api_pb2.MultiPartUpload(
                part_length=part_length,
                upload_urls=[
                    f"{self.s.blob_url_base}/blob/{blob_id}/part/{i}" for i in range(n_parts)
                ],
                completion_url=f"{self.s.blob_url_base}/blob/{blob_id}/complete/{n_parts}",
            )
            return api_pb2.BlobCreateResponse(blob_id=blob_id, multipart=mp)
        return api_pb2.BlobCreateResponse(
            blob_id=blob_id, upload_url=f"{self.s.blob_url_base}/blob/{blob_id}"
        )

    async def BlobGet(self, request, context) -> api_pb2.BlobGetResponse:
        return api_pb2.BlobGetResponse(download_url=f"{self.s.blob_url_base}/blob/{request.blob_id}")

    # ------------------------------------------------------------------
    # Functions — definition
    # ------------------------------------------------------------------

    def _function_metadata(self, fn: FunctionState) -> api_pb2.FunctionHandleMetadata:
        d = fn.definition
        return api_pb2.FunctionHandleMetadata(
            function_name=d.function_name,
            function_type=d.function_type,
            web_url=fn.web_url,
            is_generator=d.function_type == api_pb2.FUNCTION_TYPE_GENERATOR,
            definition_id=fn.function_id,
            input_concurrency=d.max_concurrent_inputs,
            batch_max_size=d.batch_max_size,
            batch_wait_ms=d.batch_linger_ms,
            schema=d.function_schema,
        )

    async def FunctionCreate(self, request: api_pb2.FunctionCreateRequest, context) -> api_pb2.FunctionCreateResponse:
        if request.app_id and request.app_id not in self.s.apps:
            await context.abort(grpc.StatusCode.NOT_FOUND, f"app {request.app_id} not found")
        function_id = request.existing_function_id or self.s.make_id("fu")
        definition = request.function
        if definition.webhook_type != api_pb2.WEB_ENDPOINT_TYPE_UNSPECIFIED:
            # web functions serve HTTP, not a queue: at least one warm
            # container must exist for the endpoint to answer
            definition.autoscaler_settings.min_containers = max(
                1, definition.autoscaler_settings.min_containers
            )
        fn = FunctionState(
            function_id=function_id,
            app_id=request.app_id,
            tag=request.tag or request.function.function_name,
            definition=definition,
        )
        self.s.functions[function_id] = fn
        self._j(
            "function",
            function_id=function_id,
            app_id=request.app_id,
            tag=fn.tag,
            definition=_jb64(definition.SerializeToString()),
        )
        app = self.s.apps.get(request.app_id)
        if app is not None:
            app.function_ids[fn.tag] = function_id
            self._j(
                "app_state", app_id=app.app_id, state=app.state, function_ids={fn.tag: function_id}
            )
        self.s.schedule_event.set()
        return api_pb2.FunctionCreateResponse(
            function_id=function_id, handle_metadata=self._function_metadata(fn)
        )

    async def FunctionGet(self, request: api_pb2.FunctionGetRequest, context) -> api_pb2.FunctionGetResponse:
        key = (self._resolve_environment(request.environment_name), request.app_name, request.object_tag)
        fn_id = self.s.deployed_functions.get(key)
        if fn_id is None:
            await context.abort(
                grpc.StatusCode.NOT_FOUND, f"function {request.app_name}/{request.object_tag} not found"
            )
        fn = self.s.functions[fn_id]
        return api_pb2.FunctionGetResponse(function_id=fn_id, handle_metadata=self._function_metadata(fn))

    async def FunctionBindParams(self, request, context) -> api_pb2.FunctionBindParamsResponse:
        parent = self.s.functions.get(request.function_id)
        if parent is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "function not found")
        bound_id = self.s.make_id("fu")
        bound_def = api_pb2.Function()
        bound_def.CopyFrom(parent.definition)
        # with_options variant: MERGE rebind-time overrides — only fields the
        # caller passed change; everything else keeps the parent's values
        # (reference _function_variants.py semantics)
        opts = request.options
        if opts.HasField("min_containers"):
            bound_def.autoscaler_settings.min_containers = opts.min_containers
        if opts.HasField("max_containers"):
            bound_def.autoscaler_settings.max_containers = opts.max_containers
        if opts.HasField("buffer_containers"):
            bound_def.autoscaler_settings.buffer_containers = opts.buffer_containers
        if opts.HasField("scaledown_window"):
            bound_def.autoscaler_settings.scaledown_window = opts.scaledown_window
        if opts.HasField("timeout_secs"):
            bound_def.timeout_secs = opts.timeout_secs
        if opts.has_tpu:
            bound_def.resources.tpu_config.CopyFrom(opts.tpu_config)  # tpu ONLY
        if opts.has_retry_policy:
            bound_def.retry_policy.CopyFrom(opts.retry_policy)
        if opts.HasField("max_concurrent_inputs"):
            bound_def.max_concurrent_inputs = opts.max_concurrent_inputs
        if opts.replace_secrets:
            del bound_def.secret_ids[:]
            bound_def.secret_ids.extend(opts.secret_ids)
        bound = FunctionState(
            function_id=bound_id,
            app_id=parent.app_id,
            tag=parent.tag,
            definition=bound_def,
            bound_parent=parent.function_id,
            serialized_params=request.serialized_params,
        )
        self.s.functions[bound_id] = bound
        self._j(
            "function",
            function_id=bound_id,
            app_id=parent.app_id,
            tag=parent.tag,
            definition=_jb64(bound_def.SerializeToString()),
            bound_parent=parent.function_id,
            serialized_params=_jb64(request.serialized_params),
        )
        return api_pb2.FunctionBindParamsResponse(
            bound_function_id=bound_id, handle_metadata=self._function_metadata(bound)
        )

    async def FunctionSetWebUrl(self, request: api_pb2.FunctionSetWebUrlRequest, context) -> api_pb2.FunctionSetWebUrlResponse:
        fn = self.s.functions.get(request.function_id)
        if fn is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "function not found")
        fn.web_url = request.web_url
        task = self.s.tasks.get(request.task_id)
        if task is not None:
            task.web_url = request.web_url
        async with fn.input_condition:
            fn.input_condition.notify_all()
        return api_pb2.FunctionSetWebUrlResponse()

    async def FunctionGetWebUrl(self, request: api_pb2.FunctionGetWebUrlRequest, context) -> api_pb2.FunctionGetWebUrlResponse:
        fn = self.s.functions.get(request.function_id)
        if fn is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "function not found")
        if fn.definition.webhook_type == api_pb2.WEB_ENDPOINT_TYPE_UNSPECIFIED:
            # fast-fail: a non-web function can never grow a URL — don't
            # make the client wait out the long-poll window
            await context.abort(
                grpc.StatusCode.INVALID_ARGUMENT, "function has no web endpoint (webhook_type unset)"
            )
        deadline = time.monotonic() + min(max(request.timeout, 0.0), 60.0)
        while time.monotonic() < deadline:
            async with fn.input_condition:
                # re-check UNDER the lock: a SetWebUrl notify between an
                # unlocked check and wait() would otherwise be lost
                if fn.web_url:
                    break
                try:
                    await asyncio.wait_for(
                        fn.input_condition.wait(), timeout=max(0.05, deadline - time.monotonic())
                    )
                except asyncio.TimeoutError:
                    break
        return api_pb2.FunctionGetWebUrlResponse(web_url=fn.web_url)

    async def FunctionUpdateSchedulingParams(self, request, context):
        fn = self.s.functions.get(request.function_id)
        if fn is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "function not found")
        fn.autoscaler_override = request.settings
        self._j(
            "fn_sched",
            function_id=request.function_id,
            settings=_jb64(request.settings.SerializeToString()),
        )
        self.s.schedule_event.set()
        return api_pb2.FunctionUpdateSchedulingParamsResponse()

    async def FunctionGetCurrentStats(self, request, context) -> api_pb2.FunctionStats:
        fn = self.s.functions.get(request.function_id)
        if fn is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "function not found")
        active = sum(
            1 for tid in fn.task_ids if self.s.tasks[tid].state == api_pb2.TASK_STATE_ACTIVE
        )
        return api_pb2.FunctionStats(
            backlog=len(fn.pending), num_total_tasks=len(fn.task_ids), num_active_tasks=active
        )

    # ------------------------------------------------------------------
    # Functions — invocation data plane
    # ------------------------------------------------------------------

    def _enqueue_input(self, fn: FunctionState, call: FunctionCallState, item: api_pb2.FunctionPutInputsItem) -> InputState:
        input_id = self.s.make_id("in")
        inp = InputState(
            input_id=input_id,
            function_call_id=call.function_call_id,
            idx=item.idx,
            input=item.input,
            # the submitting RPC's trace context (the server-side handler span
            # set by proto/rpc.py) rides the input to the container
            trace_context=tracing.format_context(tracing.current_context()),
        )
        self.s.inputs[input_id] = inp
        call.input_ids.append(input_id)
        call.num_inputs += 1
        fn.pending.append(input_id)
        if self.s.journal is not None:  # don't pay serialize+b64 when journaling is off
            self._j(
                "input",
                input_id=input_id,
                function_call_id=call.function_call_id,
                function_id=fn.function_id,
                idx=item.idx,
                input=_jb64(item.input.SerializeToString()),
                retry_count=0,
            )
        return inp

    async def FunctionMap(self, request: api_pb2.FunctionMapRequest, context) -> api_pb2.FunctionMapResponse:
        fn = self.s.functions.get(request.function_id)
        if fn is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, f"function {request.function_id} not found")
        call_id = self.s.make_id("fc")
        call = FunctionCallState(
            function_call_id=call_id,
            function_id=request.function_id,
            call_type=request.function_call_type,
            invocation_type=request.invocation_type,
            return_exceptions=request.return_exceptions,
        )
        self.s.function_calls[call_id] = call
        self._j(
            "call",
            function_call_id=call_id,
            function_id=request.function_id,
            call_type=call.call_type,
            invocation_type=call.invocation_type,
            return_exceptions=call.return_exceptions,
        )
        resp = api_pb2.FunctionMapResponse(
            function_call_id=call_id,
            function_call_jwt=call_id,
            max_inputs_outstanding=1000,
        )
        for item in request.pipelined_inputs:
            inp = self._enqueue_input(fn, call, item)
            resp.pipelined_inputs.append(
                api_pb2.FunctionPutInputsResponseItem(idx=item.idx, input_id=inp.input_id)
            )
        async with fn.input_condition:
            fn.input_condition.notify_all()
        self.s.schedule_event.set()
        return resp

    async def FunctionMapBatch(self, request: api_pb2.FunctionMapBatchRequest, context) -> api_pb2.FunctionMapBatchResponse:
        """Coalesced dispatch (ISSUE 8, _utils/coalescer.py): N unary
        `.remote()`s submitted within one client-side window arrive as one
        RPC. Each sub-request runs the exact FunctionMap path (own call id,
        own journal records); the journal group-commits the batch — one
        flush, no skipped records."""
        # validate EVERY sub-request before executing ANY: an abort must mean
        # "nothing happened", or the client's per-item fallback would re-run
        # the successful prefix (double dispatch)
        for sub in request.requests:
            if sub.function_id not in self.s.functions:
                await context.abort(
                    grpc.StatusCode.NOT_FOUND, f"function {sub.function_id} not found"
                )
        resp = api_pb2.FunctionMapBatchResponse()
        # group-commit across the sub-handler awaits is the DESIGN: N records,
        # one flush, committed before this RPC returns; journal.group() is
        # task-scoped, so interleaved handlers keep their per-record flush
        with self._journal_group():  # lint: disable=lock-across-await
            for sub in request.requests:
                if sub.function_id not in self.s.functions:
                    # vanished BETWEEN validation and execution (app-stop
                    # racing one of the loop's awaits): an abort here would
                    # leave a dispatched prefix — answer THIS item with an
                    # empty response (no call id = not found) instead, so the
                    # batch never aborts after partial execution
                    resp.responses.append(api_pb2.FunctionMapResponse())
                    continue
                resp.responses.append(await self.FunctionMap(sub, context))
        return resp

    async def FunctionPutInputs(self, request, context) -> api_pb2.FunctionPutInputsResponse:
        fn = self.s.functions.get(request.function_id)
        call = self.s.function_calls.get(request.function_call_id)
        if fn is None or call is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "function or call not found")
        resp = api_pb2.FunctionPutInputsResponse()
        with self._journal_group():
            for item in request.inputs:
                inp = self._enqueue_input(fn, call, item)
                resp.inputs.append(api_pb2.FunctionPutInputsResponseItem(idx=item.idx, input_id=inp.input_id))
        async with fn.input_condition:
            fn.input_condition.notify_all()
        self.s.schedule_event.set()
        return resp

    async def FunctionRetryInputs(self, request, context) -> api_pb2.FunctionRetryInputsResponse:
        call = self.s.function_calls.get(request.function_call_jwt)
        if call is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "call not found")
        fn = self.s.functions[call.function_id]
        jwts = []
        for item in request.inputs:
            old = self.s.inputs.get(item.input_id)
            if old is None:
                continue
            old.status = "pending"
            old.retry_count = item.retry_count
            if item.input.WhichOneof("args_oneof"):  # payload resend optional
                old.input.CopyFrom(item.input)
                # re-journal the payload so a post-crash replay retries the
                # NEW bytes, not the original enqueue's (resume_token carried
                # over: the replacing record must not drop the checkpoint)
                if self.s.journal is not None:
                    self._j(
                        "input",
                        input_id=old.input_id,
                        function_call_id=old.function_call_id,
                        function_id=call.function_id,
                        idx=old.idx,
                        input=_jb64(old.input.SerializeToString()),
                        retry_count=old.retry_count,
                        resume_token=old.resume_token,
                    )
            else:
                self._j("input_retry", input_id=old.input_id, retry_count=old.retry_count)
            old.delivered_to.clear()
            old.claimed_by = ""
            old.claimed_at = 0.0
            if old.input_id not in fn.pending:
                fn.pending.append(old.input_id)
            jwts.append(old.input_id)
        async with fn.input_condition:
            fn.input_condition.notify_all()
        self.s.schedule_event.set()
        return api_pb2.FunctionRetryInputsResponse(input_jwts=jwts)

    async def MapCheckInputs(self, request: api_pb2.MapCheckInputsRequest, context) -> api_pb2.MapCheckInputsResponse:
        """Which of the caller's unfinished idxs does the server no longer
        track? (reference MapCheckInputs, parallel_map.py:793 — the client
        re-submits lost inputs)."""
        call = self.s.function_calls.get(request.function_call_id)
        if call is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "call not found")
        known_idxs = set()
        for iid in call.input_ids:
            inp = self.s.inputs.get(iid)
            if inp is not None:
                known_idxs.add(inp.idx)
        lost = [idx for idx in request.idxs if idx not in known_idxs]
        return api_pb2.MapCheckInputsResponse(lost_idxs=lost)

    async def FunctionGetOutputs(self, request: api_pb2.FunctionGetOutputsRequest, context) -> api_pb2.FunctionGetOutputsResponse:
        call = self.s.function_calls.get(request.function_call_id)
        if call is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, f"call {request.function_call_id} not found")
        deadline = time.monotonic() + min(max(request.timeout, 0.0), 60.0)
        while True:
            start = call.outputs_consumed if request.clear_on_success else int(request.last_entry_id or 0)
            available = call.outputs[start:]
            if available:
                n = len(available) if request.max_values <= 0 else min(len(available), request.max_values)
                taken = available[:n]
                if request.clear_on_success:
                    call.outputs_consumed += n
                    # the consumption pointer survives a restart: a recovered
                    # call must not re-deliver outputs this client already took
                    self._j(
                        "consumed", function_call_id=call.function_call_id, n=call.outputs_consumed
                    )
                return api_pb2.FunctionGetOutputsResponse(
                    outputs=taken,
                    last_entry_id=str(start + n),
                    num_unfinished_inputs=call.num_inputs - call.num_done,
                )
            if time.monotonic() >= deadline:
                return api_pb2.FunctionGetOutputsResponse(
                    outputs=[],
                    last_entry_id=str(start),
                    num_unfinished_inputs=call.num_inputs - call.num_done,
                )
            async with call.output_condition:
                try:
                    await asyncio.wait_for(
                        call.output_condition.wait(), timeout=max(0.05, deadline - time.monotonic())
                    )
                except asyncio.TimeoutError:
                    pass

    async def FunctionStreamOutputs(self, request: api_pb2.FunctionGetOutputsRequest, context):
        """Push-streamed output delivery (ISSUE 8, docs/DISPATCH.md): the
        keep-alive server-streaming twin of FunctionGetOutputs. A batch is
        pushed the instant ``_append_output`` fires (same cursor semantics,
        same journaled consumption for clear_on_success takes); empty
        keep-alive responses every few seconds let the client distinguish a
        quiet call from a dead stream. The poll RPC stays as the fallback
        rung — chaos `stream_reset` charges abort the stream mid-flight to
        prove the client degrades to it."""
        call = self.s.function_calls.get(request.function_call_id)
        if call is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, f"call {request.function_call_id} not found")
        keepalive_s = 5.0
        cursor = int(request.last_entry_id or 0)
        while True:
            if self.chaos is not None and self.chaos.consume_knob("stream_reset"):
                await context.abort(grpc.StatusCode.UNAVAILABLE, "chaos: output stream reset")
            start = call.outputs_consumed if request.clear_on_success else cursor
            available = call.outputs[start:]
            if available:
                n = len(available) if request.max_values <= 0 else min(len(available), request.max_values)
                taken = available[:n]
                if request.clear_on_success:
                    call.outputs_consumed += n
                    # same durability contract as the poll path: the client's
                    # consumption survives a supervisor restart
                    self._j(
                        "consumed", function_call_id=call.function_call_id, n=call.outputs_consumed
                    )
                cursor = start + n
                yield api_pb2.FunctionGetOutputsResponse(
                    outputs=taken,
                    last_entry_id=str(cursor),
                    num_unfinished_inputs=call.num_inputs - call.num_done,
                )
                continue
            timed_out = False
            async with call.output_condition:
                try:
                    await asyncio.wait_for(call.output_condition.wait(), timeout=keepalive_s)
                except asyncio.TimeoutError:
                    timed_out = True
            if timed_out:
                # keep-alive OUTSIDE the condition lock: the yield suspends
                # for the whole gRPC write (flow control included) — holding
                # the lock there would let one stalled consumer block every
                # producer's notify_all for this call
                yield api_pb2.FunctionGetOutputsResponse(
                    outputs=[],
                    last_entry_id=str(start),
                    num_unfinished_inputs=call.num_inputs - call.num_done,
                )

    async def FunctionCallGetData(self, request: api_pb2.FunctionCallGetDataRequest, context):
        call = self.s.function_calls.get(request.function_call_id)
        if call is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "call not found")
        idx = int(request.last_index)
        deadline = time.monotonic() + 55.0
        while time.monotonic() < deadline:
            chunks = call.data_chunks[idx:]
            if chunks:
                for c in chunks:
                    yield c
                idx += len(chunks)
                if chunks[-1].data_format == api_pb2.DATA_FORMAT_GENERATOR_DONE:
                    return
                deadline = time.monotonic() + 55.0
                continue
            if call.num_done >= call.num_inputs and call.num_inputs > 0:
                # the call FINISHED without a GENERATOR_DONE chunk (generator
                # raised mid-stream): end the stream now so the client's
                # unary-channel check sees the failure immediately instead of
                # after this long-poll's full 55s window
                return
            async with call.data_condition:
                try:
                    await asyncio.wait_for(call.data_condition.wait(), timeout=1.0)
                except asyncio.TimeoutError:
                    pass

    async def FunctionCallPutData(self, request: api_pb2.FunctionCallPutDataRequest, context):
        call = self.s.function_calls.get(request.function_call_id)
        if call is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "call not found")
        for chunk in request.data_chunks:
            new = api_pb2.DataChunk()
            new.CopyFrom(chunk)
            new.index = len(call.data_chunks) + 1
            call.data_chunks.append(new)
        async with call.data_condition:
            call.data_condition.notify_all()
        return api_pb2.FunctionCallPutDataResponse()

    async def FunctionCallList(self, request, context) -> api_pb2.FunctionCallListResponse:
        calls = [
            api_pb2.FunctionCallInfo(
                function_call_id=c.function_call_id,
                created_at=c.created_at,
                type=c.call_type,
                num_inputs=c.num_inputs,
                num_outputs=len(c.outputs),
            )
            for c in self.s.function_calls.values()
            if c.function_id == request.function_id
        ]
        return api_pb2.FunctionCallListResponse(calls=calls)

    async def FunctionCallCancel(self, request, context) -> api_pb2.FunctionCallCancelResponse:
        call = self.s.function_calls.get(request.function_call_id)
        if call is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "call not found")
        call.cancelled = True
        self._j("call_cancel", function_call_id=call.function_call_id)
        fn = self.s.functions[call.function_id]
        # drop pending inputs; notify running tasks via heartbeat channel
        for input_id in call.input_ids:
            inp = self.s.inputs.get(input_id)
            if inp is None:
                continue
            if inp.status == "pending":
                inp.status = "cancelled"
                if input_id in fn.pending:
                    fn.pending.remove(input_id)
            elif inp.status == "claimed":
                task = self.s.tasks.get(inp.claimed_by)
                if task is not None:
                    task.cancelled_input_ids.append(input_id)
                    if request.terminate_containers:
                        task.terminate = True
        async with call.output_condition:
            call.output_condition.notify_all()
        return api_pb2.FunctionCallCancelResponse()

    async def FunctionCallGetInfo(self, request, context) -> api_pb2.FunctionCallGetInfoResponse:
        call = self.s.function_calls.get(request.function_call_id)
        if call is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "call not found")
        return api_pb2.FunctionCallGetInfoResponse(
            info=api_pb2.FunctionCallInfo(
                function_call_id=call.function_call_id,
                created_at=call.created_at,
                type=call.call_type,
                num_inputs=call.num_inputs,
                num_outputs=len(call.outputs),
            ),
            function_id=call.function_id,
        )

    # ------------------------------------------------------------------
    # Container data plane
    # ------------------------------------------------------------------

    async def AppListProfiles(
        self, request: api_pb2.AppListProfilesRequest, context
    ) -> api_pb2.AppListProfilesResponse:
        """Enumerate jax profiler dumps recorded by runtime_debug tasks of
        this app (the dirs the container entrypoint's _maybe_profile wrote)."""
        out = []
        for task in self.s.tasks.values():
            if request.app_id and task.app_id != request.app_id:
                continue
            profile_dir = os.path.join(self.s.state_dir, "tasks", task.task_id, "profile")
            if not os.path.isdir(profile_dir):
                continue
            size = 0
            traces = 0
            for root, _dirs, files in os.walk(profile_dir):
                for f in files:
                    try:
                        size += os.path.getsize(os.path.join(root, f))
                    except OSError:
                        pass
                    if f.endswith(".xplane.pb"):
                        traces += 1
            out.append(
                api_pb2.ProfileEntry(
                    task_id=task.task_id, path=profile_dir, size_bytes=size, num_traces=traces
                )
            )
        return api_pb2.AppListProfilesResponse(profiles=out)

    async def ContainerHello(self, request, context) -> api_pb2.ContainerHelloResponse:
        task = self.s.tasks.get(request.task_id)
        if task is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, f"task {request.task_id} not found")
        task.state = api_pb2.TASK_STATE_ACTIVE
        task.started_at = task.started_at or time.time()
        task.last_heartbeat = time.time()
        if request.warm_pool_hit:
            # placement served by a pre-forked warm-pool interpreter
            # (handoff, no re-exec) — surfaced on TaskGetTimeline
            task.warm_pool_hit = True
        fn = self.s.functions.get(task.function_id)
        if fn is not None:
            fn.init_failures = 0  # a container came up: init is healthy
        if request.sandbox_workdir:
            # the worker's ACTUAL choice of sandbox cwd (may come from the
            # image's WORKDIR) — fs snapshots must tar this, not a guess
            for sb in self.s.sandboxes.values():
                if sb.task_id == request.task_id:
                    sb.workdir = request.sandbox_workdir
                    break
        return api_pb2.ContainerHelloResponse()

    async def ContainerHeartbeat(self, request, context) -> api_pb2.ContainerHeartbeatResponse:
        task = self.s.tasks.get(request.task_id)
        if task is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "task not found")
        task.last_heartbeat = time.time()
        if request.telemetry_json:
            # device/compile telemetry push (observability/device_telemetry.py):
            # merge the container's whitelisted metric families into this
            # process's registry so GET /metrics shows live HBM + compile
            # activity; deltas are computed against the task's previous push
            from ..observability.device_telemetry import merge_container_report

            task.telemetry_prev_json = merge_container_report(
                request.telemetry_json,
                getattr(task, "telemetry_prev_json", ""),
                task_id=task.task_id,
            )
        resp = api_pb2.ContainerHeartbeatResponse()
        if (
            self.s.profile_command == "stop"
            and time.time() - self.s.profile_command_set_at > PROFILE_STOP_TTL_S
        ):
            # expire a stale stop: every container live at stop time has had
            # many heartbeats to apply it; a permanent broadcast would also
            # kill future containers' env-enabled profilers at first beat
            self.s.profile_command = ""
        if self.s.profile_command:
            # repeat the active profiling command every heartbeat; containers
            # apply it idempotently (observability/profiler.py)
            resp.profile_command = self.s.profile_command
        if task.cancelled_input_ids:
            resp.cancel_input_event.input_ids.extend(task.cancelled_input_ids)
            task.cancelled_input_ids = []
        if task.terminate:
            resp.cancel_input_event.terminate_containers = True
        return resp

    async def ProfileControl(self, request, context) -> api_pb2.ProfileControlResponse:
        """Runtime toggle for the sampling profiler (observability/profiler.py):
        applies to the supervisor process immediately and fans out to live
        containers via the heartbeat's profile_command."""
        from ..observability import profiler

        profiles_dir = os.path.join(self.s.state_dir, "observability", "profiles")
        action = request.action or "status"
        if action == "start":
            hz = request.hz or profiler.DEFAULT_HZ
            self.s.profile_command = f"start:{hz:g}"
            self.s.profile_command_set_at = time.time()
            profiler.start(profiles_dir, tag="supervisor", hz=hz)
        elif action == "stop":
            self.s.profile_command = "stop"
            self.s.profile_command_set_at = time.time()
            profiler.stop()
        elif action != "status":
            await context.abort(
                grpc.StatusCode.INVALID_ARGUMENT, f"unknown profile action {action!r}"
            )
        prof = profiler.current()
        return api_pb2.ProfileControlResponse(
            running=profiler.running(),
            supervisor_profile_path=prof.path if prof is not None else "",
            n_samples=prof.n_samples if prof is not None else 0,
            profile_paths=profiler.list_profiles(profiles_dir),
        )

    async def MetricsHistory(self, request, context) -> api_pb2.MetricsHistoryResponse:
        """Windowed history / burn-rate alert queries against the
        supervisor-resident time-series store (ISSUE 11; server/history.py
        answers the same queries on GET /metrics/history)."""
        from .history import history_payload

        payload = history_payload(
            self.s,
            query=request.query,
            family=request.family,
            window_s=request.window_s,
            q=request.q,
        )
        return api_pb2.MetricsHistoryResponse(payload_json=json.dumps(payload))

    async def ShardControl(self, request, context) -> api_pb2.ShardControlResponse:
        """Sharded control plane administration (ISSUE 16, server/shards.py):
        the placement director drives shard health probes, journal-fed
        partition takeover, and epoch fencing through this RPC so subprocess
        shards are orchestrated identically to in-process ones. Journal-EXEMPT
        (topology is runtime state; the takeover it triggers replays+compacts
        journals, which is the durable part)."""
        sup = self.supervisor
        if sup is None:
            await context.abort(
                grpc.StatusCode.FAILED_PRECONDITION,
                "shard administration requires a supervisor-attached servicer",
            )
        if request.epoch and hasattr(sup, "note_fleet_epoch"):
            # director probes piggyback the fleet epoch (ISSUE 19): the
            # local replicator stamps subsequent appends with it, so
            # followers can fence a writer that missed a takeover
            sup.note_fleet_epoch(request.epoch)
        if request.action == "status":
            return api_pb2.ShardControlResponse(payload_json=json.dumps(sup.shard_status()))
        if request.action == "adopt":
            report = await sup.adopt_partition(request.journal_dir, request.partition)
            return api_pb2.ShardControlResponse(payload_json=json.dumps(report))
        if request.action == "adopt_replica":
            # quorum takeover (ISSUE 19): adopt a partition from OUR replica
            # stream of the dead writer — used when the writer's own journal
            # directory is gone (lost disk), not just its process
            report = await sup.adopt_from_replica(
                request.shard_index, request.partition, request.epoch
            )
            return api_pb2.ShardControlResponse(payload_json=json.dumps(report))
        if request.action == "fence":
            # fencing stops the very gRPC server carrying this call: run it as
            # a task so the response gets out before the listener dies
            t = asyncio.create_task(sup.fence(request.epoch))
            sup._chaos_subtasks.add(t)
            t.add_done_callback(sup._chaos_subtasks.discard)
            return api_pb2.ShardControlResponse(
                payload_json=json.dumps({"fencing": True, "epoch": request.epoch})
            )
        await context.abort(
            grpc.StatusCode.INVALID_ARGUMENT, f"unknown shard action {request.action!r}"
        )

    async def JournalReplicate(self, request, context) -> api_pb2.JournalReplicateResponse:
        """Follower side of quorum journal replication (ISSUE 19,
        server/replication.py): a peer writer streams its journal appends /
        compacted snapshots / seal requests here; we persist them into our
        per-writer ReplicaStore stream. Every message carries the writer's
        fleet epoch — a stale epoch is rejected (fencing token), which is
        what makes a partitioned old writer structurally unable to commit
        past a takeover. Journal-EXEMPT: the payload IS journal records."""
        sup = self.supervisor
        store = getattr(sup, "replica_store", None) if sup is not None else None
        if store is None:
            await context.abort(
                grpc.StatusCode.FAILED_PRECONDITION,
                "journal replication requires a replica store (journaling + replicas > 0)",
            )
        kind = request.kind
        # payload is newline-joined record lines, not a JSON array: the hot
        # append path must not re-encode/re-parse what is already JSONL
        lines = request.payload_json.split("\n") if request.payload_json else []
        if kind == "append":
            result = store.append(
                request.writer_shard,
                request.epoch,
                lines,
                incarnation=request.incarnation,
                boot_seq=request.boot_seq,
            )
        elif kind == "snapshot":
            result = store.install_snapshot(
                request.writer_shard,
                request.epoch,
                request.base_seq,
                lines,
                incarnation=request.incarnation,
                boot_seq=request.boot_seq,
            )
        elif kind == "seal":
            result = store.seal(request.writer_shard, request.epoch)
        elif kind == "status":
            result = store.status(request.writer_shard)
        else:
            await context.abort(
                grpc.StatusCode.INVALID_ARGUMENT, f"unknown replicate kind {request.kind!r}"
            )
        return api_pb2.JournalReplicateResponse(payload_json=json.dumps(result))

    def _scaledown_blocked(self, fn, task) -> bool:
        """Is this container one of the `min_containers` oldest live ones for
        its function? Those must stay warm through idle (VERDICT r4 weak #4:
        containers scaled below min_containers and paid a fresh cold start on
        the next input). Oldest-first is deterministic, so exactly
        min_containers containers self-select to stay — no reservation
        protocol or races between concurrently-draining containers."""
        min_containers = fn.autoscaler.min_containers
        if min_containers <= 0:
            return False
        live = sorted(
            (
                tid
                for tid in fn.task_ids
                if self.s.tasks[tid].state
                in (api_pb2.TASK_STATE_CREATED, api_pb2.TASK_STATE_ACTIVE, api_pb2.TASK_STATE_IDLE)
            ),
            key=lambda tid: self.s.tasks[tid].created_at,
        )
        return task.task_id in live[:min_containers]

    def _note_input_claimed(self, fn: FunctionState, inp: InputState) -> None:
        """Queue-segment attribution at the claim transition: the enqueue→
        claim wait becomes a histogram sample and (for traced inputs) a
        retroactive `scheduler.queue_wait` span in the caller's trace."""
        now = time.time()
        INPUT_QUEUE_WAIT.observe(max(0.0, now - inp.created_at))
        ctx = tracing.parse_context(inp.trace_context)
        if ctx is not None:
            tracing.record_span(
                "scheduler.queue_wait",
                start=inp.created_at,
                end=now,
                parent=ctx,
                attrs={
                    "input_id": inp.input_id,
                    "function_call_id": inp.function_call_id,
                    "app_id": fn.app_id,
                    "function_id": fn.function_id,
                },
            )

    async def FunctionGetInputs(self, request: api_pb2.FunctionGetInputsRequest, context) -> api_pb2.FunctionGetInputsResponse:
        fn = self.s.functions.get(request.function_id)
        task = self.s.tasks.get(request.task_id)
        if fn is None or task is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "function or task not found")
        if request.average_call_time > 0:
            # container-reported call-time EWMA feeds the autoscaler's
            # drain-time shaping (scheduler._schedule_once)
            fn.reported_call_time = request.average_call_time
        # Long-poll for inputs; kill_switch when the app stops or the task is
        # being drained (reference container_io_manager.py:820).
        deadline = time.monotonic() + 10.0
        while True:
            app = self.s.apps.get(fn.app_id)
            if task.terminate or (app is not None and app.done):
                return api_pb2.FunctionGetInputsResponse(
                    inputs=[api_pb2.FunctionGetInputsItem(kill_switch=True)]
                )
            batch_size = max(1, request.max_values or 1)
            items = []
            cluster = self.s.clusters.get(task.cluster_id) if task.cluster_id else None
            broadcast = cluster is not None and fn.definition.broadcast_inputs
            if broadcast:
                # Gang broadcast: every gang member receives a copy of each
                # input (reference broadcast semantics,
                # _partial_function.py:780 `broadcast`); the input leaves the
                # queue once all ranks have it. FunctionPutOutputs keeps
                # rank 0's SUCCESS as canonical and accepts FAILURE from any
                # rank (fail fast).
                for input_id in list(fn.pending):
                    if len(items) >= batch_size:
                        break
                    inp = self.s.inputs[input_id]
                    if inp.status != "pending" or task.task_id in inp.delivered_to:
                        continue
                    if inp.claimed_by:
                        # with concurrent gangs, an input broadcast to one
                        # cluster must not also fan out to another: the first
                        # claiming rank's cluster owns it
                        claimer = self.s.tasks.get(inp.claimed_by)
                        if claimer is not None and claimer.cluster_id != task.cluster_id:
                            continue
                    inp.delivered_to.add(task.task_id)
                    inp.claimed_by = inp.claimed_by or task.task_id
                    inp.claimed_at = inp.claimed_at or time.time()
                    if len(inp.delivered_to) >= cluster.size:
                        inp.status = "claimed"
                        fn.pending.remove(input_id)
                        self._note_input_claimed(fn, inp)
                    task.first_input_at = task.first_input_at or time.time()
                    items.append(
                        api_pb2.FunctionGetInputsItem(
                            input_id=inp.input_id,
                            input=inp.input,
                            function_call_id=inp.function_call_id,
                            idx=inp.idx,
                            retry_count=inp.retry_count,
                            resume_token=inp.resume_token,
                            trace_context=inp.trace_context,
                            claimed_at=inp.claimed_at,
                        )
                    )
            else:
                # Batching linger: once the first input of a batch is seen,
                # wait up to batch_linger_ms for the batch to fill (reference
                # @batched wait_ms semantics).
                linger_deadline = None
                while True:
                    while fn.pending and len(items) < batch_size:
                        input_id = fn.pending.pop(0)
                        inp = self.s.inputs[input_id]
                        if inp.status != "pending":
                            continue
                        inp.status = "claimed"
                        inp.claimed_by = task.task_id
                        inp.claimed_at = time.time()
                        self._note_input_claimed(fn, inp)
                        task.first_input_at = task.first_input_at or time.time()
                        items.append(
                            api_pb2.FunctionGetInputsItem(
                                input_id=inp.input_id,
                                input=inp.input,
                                function_call_id=inp.function_call_id,
                                idx=inp.idx,
                                retry_count=inp.retry_count,
                                resume_token=inp.resume_token,
                                trace_context=inp.trace_context,
                                claimed_at=inp.claimed_at,
                            )
                        )
                    if not items or len(items) >= batch_size or not request.batch_linger_ms:
                        break
                    if linger_deadline is None:
                        linger_deadline = time.monotonic() + request.batch_linger_ms / 1000.0
                    remaining = linger_deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    async with fn.input_condition:
                        try:
                            await asyncio.wait_for(fn.input_condition.wait(), timeout=remaining)
                        except asyncio.TimeoutError:
                            break
            if items:
                return api_pb2.FunctionGetInputsResponse(
                    inputs=items, rate_limit_sleep_duration=self.rate_limit_sleep_duration
                )
            if time.monotonic() >= deadline:
                return api_pb2.FunctionGetInputsResponse(
                    inputs=[],
                    rate_limit_sleep_duration=self.rate_limit_sleep_duration,
                    scaledown_blocked=self._scaledown_blocked(fn, task),
                )
            async with fn.input_condition:
                try:
                    await asyncio.wait_for(
                        fn.input_condition.wait(), timeout=max(0.05, deadline - time.monotonic())
                    )
                except asyncio.TimeoutError:
                    pass

    async def FunctionPutOutputs(self, request: api_pb2.FunctionPutOutputsRequest, context) -> api_pb2.FunctionPutOutputsResponse:
        # task-scoped group-commit (see FunctionMapBatch): intentional hold
        with self._journal_group():  # lint: disable=lock-across-await
            return await self._put_outputs(request)

    async def FunctionExchange(self, request: api_pb2.FunctionExchangeRequest, context) -> api_pb2.FunctionGetInputsResponse:
        """One container turnaround in one RPC (docs/DISPATCH.md): apply the
        finished inputs' outputs (same journal group-commit + (input_id,
        retry_count) dedupe as FunctionPutOutputs — a retried exchange cannot
        double-deliver), then run the FunctionGetInputs long-poll. Outputs
        land and notify waiters BEFORE the poll blocks, so caller-visible
        delivery never waits out the claim window."""
        from ..observability.catalog import DISPATCH_EXCHANGES

        if request.HasField("put") and request.put.outputs:
            DISPATCH_EXCHANGES.inc(carried="with_outputs")
            # task-scoped group-commit (see FunctionMapBatch): intentional hold
            with self._journal_group():  # lint: disable=lock-across-await
                await self._put_outputs(request.put)
        else:
            DISPATCH_EXCHANGES.inc(carried="claim_only")
        return await self.FunctionGetInputs(request.get, context)

    async def _put_outputs(self, request: api_pb2.FunctionPutOutputsRequest) -> api_pb2.FunctionPutOutputsResponse:
        # coalesced publication (io_manager's output MicroBatcher) delivers
        # many inputs' outputs in one RPC; the journal group above commits
        # their records with one flush — group-committed, never skipped
        touched: set[str] = set()
        pushing_task = self.s.tasks.get(request.task_id) if request.task_id else None
        for item in request.outputs:
            call = self.s.function_calls.get(item.function_call_id)
            if call is None:
                continue
            if pushing_task is not None and pushing_task.preempted:
                # a preempted task pushes void results: its inputs are (being)
                # re-queued — a stale SUCCESS would complete the call with
                # partial work, and a TERMINATED from the drain cancellation
                # would surface as a client error instead of the free retry.
                # (Only .preempted — plain terminate also covers app drain,
                # where concurrent calls' outputs are still valid. Gang
                # fail-fast is preserved: the CRASHING rank is never marked
                # preempted, only its torn-down peers are.)
                continue
            if pushing_task is not None:
                # stamp before dedup: every rank's first push counts as its
                # first output (cold-start attribution for gang members)
                pushing_task.first_output_at = pushing_task.first_output_at or time.time()
            inp = self.s.inputs.get(item.input_id)
            if inp is not None:
                if inp.status == "done":
                    continue  # duplicate (e.g. gang peer)
                # Broadcast gangs: every rank computes; rank 0's SUCCESS is
                # the canonical output. FAILURE from any rank is accepted
                # immediately (fail fast — a crashed peer would otherwise
                # stall rank 0 in a collective until heartbeat timeout).
                if (
                    pushing_task is not None
                    and pushing_task.cluster_id
                    and pushing_task.rank != 0
                    and inp.delivered_to
                    and item.result.status == api_pb2.GENERIC_STATUS_SUCCESS
                ):
                    continue
                inp.status = "done"
            appended = self._append_output(
                call,
                api_pb2.FunctionGetOutputsItem(
                    result=item.result,
                    idx=item.idx,
                    input_id=item.input_id,
                    data_format=item.data_format,
                    retry_count=item.retry_count,
                ),
            )
            if appended:
                touched.add(call.function_call_id)
        for call_id in touched:
            call = self.s.function_calls[call_id]
            async with call.output_condition:
                call.output_condition.notify_all()
        return api_pb2.FunctionPutOutputsResponse()

    async def ContainerCheckpoint(self, request, context):
        # preemption flush (runtime/preemption.py): the container recorded a
        # checkpoint for a claimed input — stash the resume token on the
        # input so the requeued attempt is redelivered with it and restarts
        # from the checkpoint instead of from scratch
        if request.input_id and request.resume_token:
            inp = self.s.inputs.get(request.input_id)
            # stale-flush guard: a dead attempt's delayed flush must not
            # clobber the token a NEWER attempt recorded after the requeue —
            # accept only from the attempt that currently holds the input,
            # or a first-ever token for an input nobody holds
            if inp is not None and (
                inp.claimed_by == request.task_id
                or request.task_id in inp.delivered_to
                or (not inp.claimed_by and not inp.resume_token)
            ):
                inp.resume_token = request.resume_token
                # the checkpoint must survive a control-plane crash too — a
                # recovered (requeued) input is redelivered with its token
                self._j("input_token", input_id=request.input_id, resume_token=request.resume_token)
                logger.debug(
                    f"resume token recorded for {request.input_id}: {request.resume_token!r}"
                )
        return api_pb2.ContainerCheckpointResponse()

    async def ContainerStop(self, request, context):
        task = self.s.tasks.get(request.task_id)
        if task is not None:
            task.terminate = True
            # push the stop to the worker immediately (same channel as
            # _stop_app) — the terminate flag alone only takes effect at the
            # container's next poll
            worker = self.s.workers.get(task.worker_id)
            if worker is not None:
                await worker.events.put(
                    api_pb2.WorkerPollResponse(stop=api_pb2.TaskStopEvent(task_id=task.task_id))
                )
        return api_pb2.ContainerStopResponse()

    async def TaskList(self, request: api_pb2.TaskListRequest, context) -> api_pb2.TaskListResponse:
        """Running (and optionally finished) containers across apps
        (reference `modal container list`, cli/container.py)."""
        out = []
        finished_states = (
            api_pb2.TASK_STATE_COMPLETED,
            api_pb2.TASK_STATE_FAILED,
            api_pb2.TASK_STATE_TERMINATED,
            api_pb2.TASK_STATE_PREEMPTED,
        )
        for task in self.s.tasks.values():
            if not request.include_finished and task.state in finished_states:
                continue
            app = self.s.apps.get(task.app_id)
            if request.environment_name and (
                app is None or app.environment_name != request.environment_name
            ):
                continue
            fn = self.s.functions.get(task.function_id)
            out.append(
                api_pb2.TaskInfo(
                    task_id=task.task_id,
                    app_id=task.app_id,
                    app_description=app.description if app else "",
                    function_tag=fn.tag if fn else "",
                    state=task.state,
                    worker_id=task.worker_id,
                    created_at=task.created_at,
                    started_at=task.started_at,
                    finished_at=task.finished_at,
                    cluster_id=task.cluster_id,
                    rank=task.rank,
                    tpu_chip_ids=list(task.tpu_chip_ids),
                )
            )
        return api_pb2.TaskListResponse(tasks=out)

    async def ClusterList(self, request, context) -> api_pb2.ClusterListResponse:
        """Live gangs (reference `modal cluster list`, cli/cluster.py)."""
        out = []
        for cluster in self.s.clusters.values():
            fn = self.s.functions.get(cluster.function_id)
            out.append(
                api_pb2.ClusterInfo(
                    cluster_id=cluster.cluster_id,
                    function_tag=fn.tag if fn else "",
                    size=cluster.size,
                    task_ids=list(cluster.task_ids),
                    topology=(
                        cluster.slice_info.topology if cluster.slice_info is not None else ""
                    ),
                    ranks_reported=len(cluster.reported),
                )
            )
        return api_pb2.ClusterListResponse(clusters=out)

    def _image_refs(self) -> dict[str, int]:
        """Pin counts for `image prune`: an image is pinned while ANY
        function or sandbox of a non-stopped app references it (scale-to-zero
        deployments included — their autoscaler can start a task later), and
        FROM-chain base images are pinned by their pinned children."""
        refs: dict[str, int] = {}

        def add_with_parents(image_id: str) -> None:
            for _ in range(32):  # FROM chains are short; bound anyway
                if not image_id:
                    return
                refs[image_id] = refs.get(image_id, 0) + 1
                img = self.s.images.get(image_id)
                if img is None:
                    return
                image_id = next(
                    (
                        c.strip()[5:].strip()
                        for c in img.definition.dockerfile_commands
                        if c.strip().startswith("FROM im-")
                    ),
                    "",
                )

        def app_alive(app_id: str) -> bool:
            app = self.s.apps.get(app_id)
            return app is not None and not app.done

        for fn in self.s.functions.values():
            if fn.definition.image_id and app_alive(fn.app_id):
                add_with_parents(fn.definition.image_id)
        for sb in self.s.sandboxes.values():
            if sb.definition.image_id and sb.state != api_pb2.SANDBOX_STATE_TERMINATED:
                add_with_parents(sb.definition.image_id)
        return refs

    async def ImageList(self, request, context) -> api_pb2.ImageListResponse:
        refs = self._image_refs()
        out = []
        for image in self.s.images.values():
            out.append(
                api_pb2.ImageInfo(
                    image_id=image.image_id,
                    built=image.built,
                    builder_version=image.metadata.image_builder_version,
                    python_version=image.metadata.python_version,
                    created_at=image.created_at,
                    ref_count=refs.get(image.image_id, 0),
                )
            )
        return api_pb2.ImageListResponse(images=out)

    async def ImageDelete(self, request: api_pb2.ImageDeleteRequest, context) -> api_pb2.ImageDeleteResponse:
        """`image prune` building block: delete an image RECORD. Refuses
        pinned images — a record has no rebuild path from its id, so deleting
        a referenced one would NOT_FOUND every later cold start. The
        content-addressed venv on disk is shared and untouched."""
        if request.image_id in self._image_refs():
            await context.abort(
                grpc.StatusCode.FAILED_PRECONDITION,
                f"image {request.image_id} is referenced by a live app/sandbox",
            )
        self.s.images.pop(request.image_id, None)
        # keep the content-hash index consistent: a later ImageGetOrCreate of
        # the same definition must mint a fresh record, not a dangling id
        for key, image_id in list(self.s.images_by_hash.items()):
            if image_id == request.image_id:
                del self.s.images_by_hash[key]
        self._j("image_del", image_id=request.image_id)
        return api_pb2.ImageDeleteResponse()

    async def ContainerLog(self, request: api_pb2.ContainerLogRequest, context):
        task = self.s.tasks.get(request.task_id)
        if task is not None:
            app = self.s.apps.get(task.app_id)
            if app is not None:
                for entry in request.logs:
                    e = api_pb2.TaskLogs()
                    e.CopyFrom(entry)
                    e.task_id = task.task_id
                    app.log_entries.append(e)
                async with app.log_condition:
                    app.log_condition.notify_all()
        return api_pb2.ContainerLogResponse()

    async def AppCountLogs(self, request: api_pb2.AppCountLogsRequest, context) -> api_pb2.AppCountLogsResponse:
        """Histogram of stored log entries over [min_timestamp, max_timestamp)
        (reference _logs.py:114-310: the client refines dense buckets into
        fetch intervals instead of paging the whole history)."""
        app = self.s.apps.get(request.app_id)
        if app is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "app not found")
        lo = request.min_timestamp or (app.log_entries[0].timestamp if app.log_entries else time.time())
        hi = request.max_timestamp or time.time()
        n = min(max(request.n_buckets or 16, 1), 256)
        if hi <= lo:
            hi = lo + 1e-6
        width = (hi - lo) / n
        counts = [0] * n
        first_index = [0] * n  # offset of each bucket's first entry
        for i, entry in enumerate(app.log_entries):
            if entry.timestamp < lo or entry.timestamp >= hi:
                continue
            if request.task_id and entry.task_id != request.task_id:
                continue
            b = min(int((entry.timestamp - lo) / width), n - 1)
            if counts[b] == 0:
                first_index[b] = i
            counts[b] += 1
        return api_pb2.AppCountLogsResponse(
            buckets=[
                api_pb2.LogBucket(
                    start=lo + i * width, end=lo + (i + 1) * width, count=c, start_index=first_index[i]
                )
                for i, c in enumerate(counts)
            ]
        )

    async def AppFetchLogs(self, request: api_pb2.AppFetchLogsRequest, context) -> api_pb2.AppFetchLogsResponse:
        """Historical log backfill: offset-paged over the app's stored
        entries with time/task filters (reference _logs.py:114-310)."""
        app = self.s.apps.get(request.app_id)
        if app is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "app not found")
        page = request.max_entries or 500
        resp = api_pb2.AppFetchLogsResponse(total=len(app.log_entries))
        i = request.start_index
        while i < len(app.log_entries) and len(resp.entries) < page:
            entry = app.log_entries[i]
            i += 1
            if request.min_timestamp and entry.timestamp < request.min_timestamp:
                continue
            if request.max_timestamp and entry.timestamp >= request.max_timestamp:
                # Entries are stamped worker-side and appended at RPC arrival,
                # so the store is only approximately time-ordered: a windowed
                # fetch may still find in-window entries after this one. Keep
                # scanning until entries are past the window by more than any
                # plausible worker->server delivery skew.
                if entry.timestamp >= request.max_timestamp + 30.0:
                    i = len(app.log_entries)
                    break
                continue
            if request.task_id and entry.task_id != request.task_id:
                continue
            resp.entries.append(entry)
        resp.next_index = i
        return resp

    async def TaskResult(self, request: api_pb2.TaskResultRequest, context) -> api_pb2.TaskResultResponse:
        task = self.s.tasks.get(request.task_id)
        if task is not None:
            TASK_RESULTS.inc(status=api_pb2.GenericResultStatus.Name(request.result.status))
            if task.result is not None:
                # first report wins: the container's own result (e.g.
                # TERMINATED from a graceful drain) must not be overwritten
                # by the worker's rc-based backstop report
                return api_pb2.TaskResultResponse()
            task.result = request.result
            if request.result.status == api_pb2.GENERIC_STATUS_SUCCESS:
                task.state = api_pb2.TASK_STATE_COMPLETED
                if task.preempted:
                    # drain race: outputs pushed after the preempt flag was
                    # set were dropped by FunctionPutOutputs, yet the
                    # container drained cleanly and reports SUCCESS — those
                    # inputs are still claimed and must requeue or the
                    # client hangs (inputs whose outputs landed before the
                    # flag are completed and untouched by the requeue)
                    await self._requeue_claimed_inputs(task)
            elif task.preempted:
                # preemption drain: claimed inputs go back to pending WITHOUT
                # consuming the user retry budget — system-initiated worker
                # loss is not the input's fault
                task.state = api_pb2.TASK_STATE_PREEMPTED
                await self._requeue_claimed_inputs(task)
            else:
                task.state = api_pb2.TASK_STATE_FAILED
                await self._fail_claimed_inputs(task, request.result)
                if request.result.status == api_pb2.GENERIC_STATUS_INIT_FAILURE:
                    # containers that die before serving (image build failed,
                    # spawn failed) never claim inputs — repeated init
                    # failures must fail the backlog or clients hang forever
                    fn = self.s.functions.get(task.function_id)
                    if fn is not None:
                        fn.init_failures += 1
                        if fn.init_failures >= 2:
                            await self._fail_pending_inputs(fn, request.result)
            task.finished_at = time.time()
            self._release_task(task)
        return api_pb2.TaskResultResponse()

    async def _fail_pending_inputs(self, fn: FunctionState, result: api_pb2.GenericResult) -> None:
        for input_id in list(fn.pending):
            inp = self.s.inputs.get(input_id)
            if inp is None or inp.status != "pending":
                continue
            inp.status = "done"
            fn.pending.remove(input_id)
            call = self.s.function_calls.get(inp.function_call_id)
            if call is None:
                continue
            self._append_output(
                call,
                api_pb2.FunctionGetOutputsItem(
                    result=result, idx=inp.idx, input_id=inp.input_id, retry_count=inp.retry_count
                ),
            )
            async with call.output_condition:
                call.output_condition.notify_all()

    async def _fail_claimed_inputs(self, task: TaskState_, result: api_pb2.GenericResult) -> None:
        """Inputs claimed by a dead container either retry or fail
        (reference: server-driven FunctionRetryInputs semantics).

        Gangs fail as a unit: a dead member fails every input delivered to
        the gang (claimed_by may be any rank for broadcast inputs) and tears
        down the surviving peers."""
        gang_tasks: set[str] = set()
        if task.cluster_id and task.cluster_id in self.s.clusters:
            cluster = self.s.clusters[task.cluster_id]
            gang_tasks = set(cluster.task_ids)
            for peer_id in cluster.task_ids:
                peer = self.s.tasks.get(peer_id)
                if peer is not None and peer_id != task.task_id and not peer.terminate:
                    peer.terminate = True
                    peer.preempted = True  # surfaced as TASK_STATE_PREEMPTED
                    worker = self.s.workers.get(peer.worker_id)
                    if worker is not None:
                        await worker.events.put(
                            api_pb2.WorkerPollResponse(stop=api_pb2.TaskStopEvent(task_id=peer_id))
                        )
        dead_ids = gang_tasks | {task.task_id}
        for inp in self.s.inputs.values():
            # A partially-delivered broadcast input (status stays "pending"
            # until every rank fetches it) counts as touched by the dead gang
            # the same as a fully-claimed one: both consume a retry, so a
            # crash-inducing input can't loop forever through redelivery.
            touched_pending = inp.status == "pending" and bool(
                inp.delivered_to & dead_ids or (inp.claimed_by and inp.claimed_by in dead_ids)
            )
            claimed_by_gang = inp.status == "claimed" and (
                inp.claimed_by == task.task_id
                or bool(gang_tasks and (inp.claimed_by in gang_tasks or task.task_id in inp.delivered_to))
            )
            if not (touched_pending or claimed_by_gang):
                continue
            call = self.s.function_calls.get(inp.function_call_id)
            fn = self.s.functions.get(task.function_id)
            if call is None or fn is None:
                continue
            retries = fn.definition.retry_policy.retries
            if inp.retry_count < retries:
                inp.retry_count += 1
                inp.status = "pending"
                self._j("input_retry", input_id=inp.input_id, retry_count=inp.retry_count)
                # Clear delivery bookkeeping from the dead gang: a stale
                # delivered_to set would otherwise mark the input claimed
                # after reaching only one rank of the replacement gang.
                inp.delivered_to -= dead_ids
                inp.claimed_by = ""
                inp.claimed_at = 0.0
                if inp.input_id not in fn.pending:
                    fn.pending.append(inp.input_id)
                async with fn.input_condition:
                    fn.input_condition.notify_all()
                self.s.schedule_event.set()
            else:
                inp.status = "done"
                # partially-delivered broadcast inputs are still queued;
                # drop them so backlog/delivery scans don't see phantom work
                if inp.input_id in fn.pending:
                    fn.pending.remove(inp.input_id)
                self._append_output(
                    call,
                    api_pb2.FunctionGetOutputsItem(
                        result=result, idx=inp.idx, input_id=inp.input_id, retry_count=inp.retry_count
                    ),
                )
                async with call.output_condition:
                    call.output_condition.notify_all()

    async def _requeue_claimed_inputs(self, task: TaskState_) -> None:
        """Preemption path: inputs touched by a preempted task return to
        pending WITHOUT consuming the retry budget (contrast
        `_fail_claimed_inputs`, the crash path). The recorded resume_token
        (ContainerCheckpoint) survives the requeue, so the next attempt is
        redelivered with it and resumes from the checkpoint. Idempotent: gang
        peers reporting one after another requeue each input once."""
        gang_tasks: set[str] = set()
        if task.cluster_id and task.cluster_id in self.s.clusters:
            gang_tasks = set(self.s.clusters[task.cluster_id].task_ids)
        dead_ids = gang_tasks | {task.task_id}
        fn = self.s.functions.get(task.function_id)
        if fn is None:
            return
        requeued = 0
        for inp in self.s.inputs.values():
            touched = bool(
                inp.delivered_to & dead_ids or (inp.claimed_by and inp.claimed_by in dead_ids)
            )
            if not touched or inp.status not in ("pending", "claimed"):
                continue
            inp.status = "pending"
            inp.delivered_to -= dead_ids
            inp.claimed_by = ""
            inp.claimed_at = 0.0
            if inp.input_id not in fn.pending:
                fn.pending.append(inp.input_id)
            # free requeue (no budget consumed) — journaled so a crash after
            # the preemption replays the input as pending, not claimed
            self._j("input_retry", input_id=inp.input_id, retry_count=inp.retry_count)
            requeued += 1
        if requeued:
            logger.warning(
                f"requeued {requeued} input(s) from preempted task {task.task_id} (no retry consumed)"
            )
            async with fn.input_condition:
                fn.input_condition.notify_all()
            self.s.schedule_event.set()

    def _release_task(self, task: TaskState_) -> None:
        worker = self.s.workers.get(task.worker_id)
        if worker is not None:
            worker.active_tasks.discard(task.task_id)
            for chip, tid in list(worker.chips_in_use.items()):
                if tid == task.task_id:
                    del worker.chips_in_use[chip]
        # drop the task's pushed device-memory gauge series: stale HBM values
        # must not render forever, and per-task keys would otherwise leak the
        # family into __overflow__ (observability/device_telemetry.py)
        from ..observability.device_telemetry import drop_task_device_series

        drop_task_device_series(task.task_id)
        fn = self.s.functions.get(task.function_id)
        if fn is not None:
            fn.task_ids.discard(task.task_id)
        # close any forward() tunnels the container left open (crash, or a
        # swallowed TunnelStop) — otherwise the proxy listener leaks for the
        # control plane's lifetime
        for key in [k for k in self.s.tunnels if k[0] == task.task_id]:
            entry = self.s.tunnels.pop(key)
            if isinstance(entry, asyncio.Future):
                if not entry.done():
                    entry.set_result(None)  # wake waiters now, not at their 15s timeout
            elif entry[0] is not None:
                entry[0].close()
        self.s.schedule_event.set()

    async def TaskGetTimeline(self, request: api_pb2.TaskGetTimelineRequest, context) -> api_pb2.TaskGetTimelineResponse:
        """Boot/serve timestamps for cold-start attribution (stamped by the
        control plane at assignment / ContainerHello / first input / first
        output — see bench.py's cold_start_to_first_step)."""
        resp = api_pb2.TaskGetTimelineResponse()
        task_ids: list[str] = []
        if request.task_id:
            if request.task_id not in self.s.tasks:
                await context.abort(grpc.StatusCode.NOT_FOUND, "task not found")
            task_ids = [request.task_id]
        elif request.function_call_id:
            call = self.s.function_calls.get(request.function_call_id)
            if call is None:
                await context.abort(grpc.StatusCode.NOT_FOUND, "call not found")
            resp.call_created_at = call.created_at
            resp.call_first_output_at = call.first_output_at
            seen: set[str] = set()
            for iid in call.input_ids:
                inp = self.s.inputs.get(iid)
                if inp is None:
                    continue
                for tid in [inp.claimed_by, *inp.delivered_to]:
                    if tid and tid not in seen:
                        seen.add(tid)
                        task_ids.append(tid)
        for tid in task_ids:
            task = self.s.tasks.get(tid)
            if task is None:
                continue
            resp.tasks.append(
                api_pb2.TaskTimeline(
                    task_id=task.task_id,
                    created_at=task.created_at,
                    started_at=task.started_at,
                    first_input_at=task.first_input_at,
                    first_output_at=task.first_output_at,
                    finished_at=task.finished_at,
                    warm_pool_hit=task.warm_pool_hit,
                )
            )
        return resp

    async def TaskClusterHello(self, request: api_pb2.TaskClusterHelloRequest, context) -> api_pb2.TaskClusterHelloResponse:
        """Gang rendezvous: block until all ranks report, then return rank +
        coordinator + slice topology (reference api.proto:3935-3953; feeds
        jax.distributed.initialize in the entrypoint)."""
        task = self.s.tasks.get(request.task_id)
        if task is None or not task.cluster_id:
            await context.abort(grpc.StatusCode.NOT_FOUND, "task has no cluster")
        cluster = self.s.clusters.get(task.cluster_id)
        if cluster is None:  # e.g. gang rolled back while this container booted
            await context.abort(grpc.StatusCode.NOT_FOUND, "cluster torn down")
        task.container_address = request.container_address
        async with cluster.condition:
            cluster.reported[request.task_id] = request.container_address
            cluster.condition.notify_all()
            deadline = time.monotonic() + 120.0
            while len(cluster.reported) < cluster.size:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                try:
                    await asyncio.wait_for(cluster.condition.wait(), timeout=remaining)
                except asyncio.TimeoutError:
                    pass
        if len(cluster.reported) < cluster.size:
            # abort OUTSIDE the condition lock: the status write suspends for
            # the full gRPC send, and holding the lock there would stall every
            # other gang member's rendezvous report (lock-across-await)
            await context.abort(grpc.StatusCode.DEADLINE_EXCEEDED, "gang rendezvous timeout")
        rank = cluster.task_ids.index(request.task_id)
        rank0_addr = cluster.reported[cluster.task_ids[0]]
        coordinator_host = rank0_addr.rsplit(":", 1)[0] if ":" in rank0_addr else rank0_addr
        def _slice_of(tid: str) -> int:
            worker = self.s.workers.get(self.s.tasks[tid].worker_id)
            return worker.slice_index if worker is not None else 0

        resp = api_pb2.TaskClusterHelloResponse(
            rank=rank,
            world_size=cluster.size,
            coordinator_address=f"{coordinator_host}:{cluster.coordinator_port}",
            peer_addresses=[cluster.reported[tid] for tid in cluster.task_ids],
            cluster_id=cluster.cluster_id,
            peer_slice_indices=[_slice_of(tid) for tid in cluster.task_ids],
            slice_index=_slice_of(request.task_id),
        )
        if cluster.slice_info is not None:
            resp.slice_info.CopyFrom(cluster.slice_info)
        return resp

    # ------------------------------------------------------------------
    # Sandboxes (reference sandbox.py:322 — on-demand containers; local
    # backend runs the command as a supervised worker subprocess)
    # ------------------------------------------------------------------

    async def SandboxCreate(self, request: api_pb2.SandboxCreateRequest, context) -> api_pb2.SandboxCreateResponse:
        from .state import SandboxState_

        if self.scheduler is None:
            await context.abort(grpc.StatusCode.UNIMPLEMENTED, "no scheduler attached")
        app_id = request.app_id
        if not app_id:
            # sandboxes may be app-less: create an implicit app
            resp = await self.AppCreate(
                api_pb2.AppCreateRequest(description="sandbox", app_state=api_pb2.APP_STATE_EPHEMERAL), context
            )
            app_id = resp.app_id
        sandbox_id = self.s.make_id("sb")
        sb = SandboxState_(
            sandbox_id=sandbox_id,
            app_id=app_id,
            definition=request.definition,
            name=request.definition.name,
        )
        task = await self.scheduler.launch_sandbox(sb)
        unsat = None
        if task is None:
            # A placement no worker could EVER match must fail loudly (same
            # rule as the function-backlog path) — but only after a bounded
            # grace wait: a matching worker may simply not have (re-)registered
            # yet (boot, restart-with-retries).
            unsat = self.scheduler.placement_unsatisfiable_reason(
                request.definition.scheduler_placement
            )
            if unsat is not None:
                deadline = time.time() + PLACEMENT_UNSAT_GRACE_S
                while time.time() < deadline:
                    await asyncio.sleep(0.25)
                    unsat = self.scheduler.placement_unsatisfiable_reason(
                        request.definition.scheduler_placement
                    )
                    if unsat is None:
                        task = await self.scheduler.launch_sandbox(sb)
                        break
        if task is None:
            # don't leave ghost state behind: neither the sandbox nor an
            # implicitly created ephemeral app
            if not request.app_id:
                implicit_app = self.s.apps.get(app_id)
                if implicit_app is not None:
                    await self._stop_app(implicit_app)
                    del self.s.apps[app_id]
            if unsat is not None:
                await context.abort(grpc.StatusCode.INVALID_ARGUMENT, f"sandbox {unsat}")
            await context.abort(grpc.StatusCode.RESOURCE_EXHAUSTED, "no worker capacity for sandbox")
        self.s.sandboxes[sandbox_id] = sb
        sb.state = api_pb2.SANDBOX_STATE_RUNNING
        return api_pb2.SandboxCreateResponse(sandbox_id=sandbox_id)

    async def SandboxGetTaskId(self, request, context) -> api_pb2.SandboxGetTaskIdResponse:
        sb = self.s.sandboxes.get(request.sandbox_id)
        if sb is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "sandbox not found")
        if request.wait_until_ready:
            # block until the readiness probe passes (or the sandbox exits
            # first — then surface its result so the client raises)
            deadline = time.monotonic() + min(max(request.timeout, 0.0) or 55.0, 60.0)
            while not sb.ready and sb.result is None and time.monotonic() < deadline:
                task = self.s.tasks.get(sb.task_id)
                if task is not None and task.result is not None:
                    sb.result = task.result
                    break
                await asyncio.sleep(0.05)
            if not sb.ready and sb.result is not None:
                return api_pb2.SandboxGetTaskIdResponse(
                    task_id=sb.task_id,
                    task_result_json=json.dumps(
                        {"status": int(sb.result.status), "exception": sb.result.exception}
                    ),
                )
            return api_pb2.SandboxGetTaskIdResponse(task_id=sb.task_id, ready=sb.ready)
        return api_pb2.SandboxGetTaskIdResponse(task_id=sb.task_id, ready=sb.ready)

    async def SandboxWait(self, request: api_pb2.SandboxWaitRequest, context) -> api_pb2.SandboxWaitResponse:
        sb = self.s.sandboxes.get(request.sandbox_id)
        if sb is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "sandbox not found")
        # timeout=0 means poll-once (falsy-zero must NOT default to long-poll)
        deadline = time.monotonic() + min(max(request.timeout, 0.0), 60.0)
        while True:
            task = self.s.tasks.get(sb.task_id)
            if task is not None and task.result is not None:
                sb.result = task.result
                sb.state = (
                    api_pb2.SANDBOX_STATE_TIMEOUT
                    if task.result.status == api_pb2.GENERIC_STATUS_TIMEOUT
                    else api_pb2.SANDBOX_STATE_TERMINATED
                )
                return api_pb2.SandboxWaitResponse(result=task.result)
            if time.monotonic() >= deadline:
                return api_pb2.SandboxWaitResponse()
            await asyncio.sleep(0.1)

    async def SandboxTerminate(self, request, context) -> api_pb2.SandboxTerminateResponse:
        sb = self.s.sandboxes.get(request.sandbox_id)
        if sb is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "sandbox not found")
        task = self.s.tasks.get(sb.task_id)
        if task is not None and task.result is None:
            task.terminate = True
            worker = self.s.workers.get(task.worker_id)
            if worker is not None:
                await worker.events.put(
                    api_pb2.WorkerPollResponse(stop=api_pb2.TaskStopEvent(task_id=task.task_id))
                )
        sb.state = api_pb2.SANDBOX_STATE_TERMINATED
        return api_pb2.SandboxTerminateResponse()

    # -- sidecars (reference sandbox.py:2157 _experimental_sidecars) --------

    async def SandboxSidecarCreate(
        self, request: api_pb2.SandboxSidecarCreateRequest, context
    ) -> api_pb2.SandboxSidecarCreateResponse:
        sb = self.s.sandboxes.get(request.sandbox_id)
        if sb is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "sandbox not found")
        sc = request.sidecar
        if not sc.name or sc.name == "main":
            await context.abort(
                grpc.StatusCode.INVALID_ARGUMENT, "sidecar name required ('main' is reserved)"
            )
        if sc.name in sb.sidecars and sb.sidecars[sc.name].running:
            await context.abort(grpc.StatusCode.ALREADY_EXISTS, f"sidecar {sc.name!r} is running")
        if not sc.entrypoint_args:
            await context.abort(grpc.StatusCode.INVALID_ARGUMENT, "sidecar command required")
        task = self.s.tasks.get(sb.task_id)
        worker = self.s.workers.get(task.worker_id) if task is not None else None
        if task is None or task.result is not None or worker is None:
            await context.abort(
                grpc.StatusCode.FAILED_PRECONDITION, "sandbox is not running — cannot attach a sidecar"
            )
        rec = api_pb2.SandboxSidecar()
        rec.CopyFrom(sc)
        rec.running = True
        sb.sidecars[sc.name] = rec
        await worker.events.put(
            api_pb2.WorkerPollResponse(
                sidecar=api_pb2.SidecarLaunchEvent(
                    task_id=task.task_id, sandbox_id=sb.sandbox_id, sidecar=rec
                )
            )
        )
        return api_pb2.SandboxSidecarCreateResponse(name=sc.name)

    async def SandboxSidecarList(
        self, request: api_pb2.SandboxSidecarListRequest, context
    ) -> api_pb2.SandboxSidecarListResponse:
        sb = self.s.sandboxes.get(request.sandbox_id)
        if sb is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "sandbox not found")
        return api_pb2.SandboxSidecarListResponse(sidecars=list(sb.sidecars.values()))

    async def SandboxSidecarStop(
        self, request: api_pb2.SandboxSidecarStopRequest, context
    ) -> api_pb2.SandboxSidecarStopResponse:
        sb = self.s.sandboxes.get(request.sandbox_id)
        if sb is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "sandbox not found")
        if request.name not in sb.sidecars:
            await context.abort(grpc.StatusCode.NOT_FOUND, f"no sidecar {request.name!r}")
        task = self.s.tasks.get(sb.task_id)
        worker = self.s.workers.get(task.worker_id) if task is not None else None
        if worker is not None:
            await worker.events.put(
                api_pb2.WorkerPollResponse(
                    stop=api_pb2.TaskStopEvent(
                        task_id=sb.task_id, force=True, sidecar_name=request.name
                    )
                )
            )
        return api_pb2.SandboxSidecarStopResponse()

    async def SandboxSidecarExit(
        self, request: api_pb2.SandboxSidecarExitRequest, context
    ) -> api_pb2.SandboxSidecarExitResponse:
        for sb in self.s.sandboxes.values():
            if sb.task_id == request.task_id and request.name in sb.sidecars:
                sb.sidecars[request.name].running = False
                sb.sidecars[request.name].returncode = request.returncode
                break
        return api_pb2.SandboxSidecarExitResponse()

    async def SandboxList(self, request, context) -> api_pb2.SandboxListResponse:
        out = []
        for sb in self.s.sandboxes.values():
            if request.app_id and sb.app_id != request.app_id:
                continue
            info = api_pb2.SandboxInfo(
                sandbox_id=sb.sandbox_id, created_at=sb.created_at, state=sb.state, name=sb.name
            )
            if sb.result is not None:
                info.result.CopyFrom(sb.result)
            out.append(info)
        return api_pb2.SandboxListResponse(sandboxes=out)

    async def SandboxGetFromName(self, request, context) -> api_pb2.SandboxGetFromNameResponse:
        for sb in self.s.sandboxes.values():
            if sb.name == request.name:
                return api_pb2.SandboxGetFromNameResponse(sandbox_id=sb.sandbox_id)
        await context.abort(grpc.StatusCode.NOT_FOUND, f"sandbox {request.name!r} not found")

    async def SandboxStdinWrite(self, request: api_pb2.SandboxStdinWriteRequest, context) -> api_pb2.SandboxStdinWriteResponse:
        sb = self.s.sandboxes.get(request.sandbox_id)
        if sb is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "sandbox not found")
        # idempotent on the client's monotonically increasing index: a retried
        # write (response lost) must not duplicate stdin bytes
        if request.index and request.index <= sb.stdin_last_index:
            return api_pb2.SandboxStdinWriteResponse()
        if request.index:
            sb.stdin_last_index = request.index
        if request.input:
            sb.stdin_chunks.append(bytes(request.input))
        if request.eof:
            sb.stdin_eof = True
        async with sb.condition:
            sb.condition.notify_all()
        return api_pb2.SandboxStdinWriteResponse()

    async def SandboxGetStdin(self, request: api_pb2.SandboxGetStdinRequest, context) -> api_pb2.SandboxGetStdinResponse:
        sb = self.s.sandboxes.get(request.sandbox_id)
        if sb is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "sandbox not found")
        deadline = time.monotonic() + min(request.timeout or 5.0, 30.0)
        # predicate re-checked under the condition lock so a notify between
        # check and wait can't be lost
        async with sb.condition:
            while True:
                chunks = sb.stdin_chunks[request.offset :]
                if chunks or sb.stdin_eof:
                    return api_pb2.SandboxGetStdinResponse(
                        chunks=chunks, eof=sb.stdin_eof, next_offset=len(sb.stdin_chunks)
                    )
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return api_pb2.SandboxGetStdinResponse(next_offset=request.offset)
                try:
                    await asyncio.wait_for(sb.condition.wait(), timeout=remaining)
                except asyncio.TimeoutError:
                    pass

    async def SandboxGetLogs(self, request: api_pb2.SandboxGetLogsRequest, context):
        sb = self.s.sandboxes.get(request.sandbox_id)
        if sb is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "sandbox not found")
        app = self.s.apps.get(sb.app_id)
        if app is None:
            return
        pos = int(request.last_entry_id) if request.last_entry_id else 0
        deadline = time.monotonic() + (request.timeout or 30.0)
        while time.monotonic() < deadline:
            entries = [
                e
                for e in app.log_entries[pos:]
                if e.task_id == sb.task_id
                and (not request.file_descriptor or e.file_descriptor == request.file_descriptor)
            ]
            new_pos = len(app.log_entries)
            if entries:
                batch = api_pb2.TaskLogsBatch(entry_id=str(new_pos))
                batch.items.extend(entries)
                yield batch
            pos = new_pos
            task = self.s.tasks.get(sb.task_id)
            if task is not None and task.result is not None:
                yield api_pb2.TaskLogsBatch(entry_id=str(pos), eof_task_id=sb.task_id)
                return
            async with app.log_condition:
                try:
                    await asyncio.wait_for(app.log_condition.wait(), timeout=0.5)
                except asyncio.TimeoutError:
                    pass

    # ------------------------------------------------------------------
    # Workers
    # ------------------------------------------------------------------

    async def WorkerRegister(self, request: api_pb2.WorkerRegisterRequest, context) -> api_pb2.WorkerRegisterResponse:
        worker_id = request.worker_id or self.s.make_id("wk")
        stale = self.s.workers.get(worker_id)
        if stale is not None:
            # re-registration under an existing id (worker survived a
            # control-plane restart, or re-announced after deregistration):
            # the stale record must not leak chips/tasks into the new one
            self._release_worker_tasks(stale)
        self.s.workers[worker_id] = WorkerState(
            worker_id=worker_id,
            hostname=request.hostname,
            tpu_type=request.tpu_type,
            num_chips=request.num_chips,
            topology=request.topology,
            milli_cpu=request.milli_cpu,
            memory_mb=request.memory_mb,
            container_address=request.container_address,
            slice_index=request.slice_index,
            router_address=request.router_address,
            region=request.region,
            zone=request.zone,
            spot=request.spot,
            instance_type=request.instance_type,
        )
        self._j(
            "worker",
            worker_id=worker_id,
            hostname=request.hostname,
            tpu_type=request.tpu_type,
            num_chips=request.num_chips,
            topology=request.topology,
            milli_cpu=request.milli_cpu,
            memory_mb=request.memory_mb,
            container_address=request.container_address,
            router_address=request.router_address,
            slice_index=request.slice_index,
            region=request.region,
            zone=request.zone,
            spot=request.spot,
            instance_type=request.instance_type,
        )
        self.s.schedule_event.set()
        return api_pb2.WorkerRegisterResponse(worker_id=worker_id)

    def _release_worker_tasks(self, worker: WorkerState) -> None:
        """Detach a stale WorkerState's bookkeeping before it is replaced:
        tasks it supposedly ran are marked lost (their inputs retry/fail via
        the reaper) rather than KeyError-ing later scans."""
        for task_id in list(worker.active_tasks):
            task = self.s.tasks.get(task_id)
            if task is not None and not task.finished_at:
                task.terminate = True
        worker.active_tasks.clear()
        worker.chips_in_use.clear()

    async def SandboxGetCommandRouterAccess(
        self, request: api_pb2.SandboxGetCommandRouterAccessRequest, context
    ) -> api_pb2.SandboxGetCommandRouterAccessResponse:
        """Hand the client the worker's direct data plane address (reference
        SandboxGetCommandRouterAccess → task_command_router_client.py:42)."""
        sandbox = self.s.sandboxes.get(request.sandbox_id)
        if sandbox is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "sandbox not found")
        # the task may still be scheduling: surface UNAVAILABLE so the
        # client's bounded connect-retry loop keeps polling
        task = self.s.tasks.get(sandbox.task_id) if sandbox.task_id else None
        if task is None:
            await context.abort(grpc.StatusCode.UNAVAILABLE, "sandbox not yet scheduled")
        worker = self.s.workers.get(task.worker_id)
        if worker is None or not worker.router_address:
            await context.abort(grpc.StatusCode.UNAVAILABLE, "worker router unavailable")
        return api_pb2.SandboxGetCommandRouterAccessResponse(
            router_address=worker.router_address,
            task_id=task.task_id,
            router_token=task.router_token,
        )

    # -- sandbox snapshots + tunnels + readiness ----------------------------

    def _sandbox_workdir(self, sb) -> str:
        from .fs_snapshot import sandbox_workdir

        # prefer the cwd the worker REPORTED at ContainerHello (it may come
        # from the image's WORKDIR, which the control plane can't derive)
        return sb.workdir or sandbox_workdir(self.s.state_dir, sb.task_id, sb.definition.workdir)

    async def _snapshot_sandbox_fs(self, sb) -> str:
        """Tar the sandbox's workdir into the blob store; returns blob_id."""
        from .fs_snapshot import tar_dir

        workdir = self._sandbox_workdir(sb)
        if not os.path.isdir(workdir):
            raise FileNotFoundError(f"sandbox workdir {workdir} not found on this host")
        data = await tar_dir(workdir)
        blob_id = self.s.make_id("bl")
        path = self.s.blob_path(blob_id)
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(data)
        os.replace(tmp, path)
        return blob_id

    async def SandboxSnapshotFs(
        self, request: api_pb2.SandboxSnapshotFsRequest, context
    ) -> api_pb2.SandboxSnapshotFsRequestResponse:
        """Filesystem snapshot → a snapshot-image usable by new sandboxes
        (reference sandbox.py:1480 returns an Image the same way)."""
        from .state import ImageState

        sb = self.s.sandboxes.get(request.sandbox_id)
        if sb is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "sandbox not found")
        try:
            blob_id = await self._snapshot_sandbox_fs(sb)
        except Exception as exc:  # noqa: BLE001 — surface as result, like ref
            return api_pb2.SandboxSnapshotFsRequestResponse(
                result=api_pb2.GenericResult(
                    status=api_pb2.GENERIC_STATUS_FAILURE, exception=f"fs snapshot failed: {exc}"
                )
            )
        image_id = self.s.make_id("im")
        definition = api_pb2.Image(fs_snapshot_blob_id=blob_id)
        self.s.images[image_id] = ImageState(image_id=image_id, definition=definition, built=True)
        return api_pb2.SandboxSnapshotFsRequestResponse(
            image_id=image_id,
            result=api_pb2.GenericResult(status=api_pb2.GENERIC_STATUS_SUCCESS),
        )

    async def SandboxSnapshot(
        self, request: api_pb2.SandboxSnapshotRequest, context
    ) -> api_pb2.SandboxSnapshotResponse:
        from .state import SandboxSnapshotState

        sb = self.s.sandboxes.get(request.sandbox_id)
        if sb is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "sandbox not found")
        try:
            blob_id = await self._snapshot_sandbox_fs(sb)
        except (OSError, ValueError) as exc:
            await context.abort(grpc.StatusCode.FAILED_PRECONDITION, f"snapshot failed: {exc}")
        snapshot_id = self.s.make_id("sn")
        definition = api_pb2.Sandbox()
        definition.CopyFrom(sb.definition)
        self.s.sandbox_snapshots[snapshot_id] = SandboxSnapshotState(
            snapshot_id=snapshot_id, definition=definition, fs_blob_id=blob_id
        )
        return api_pb2.SandboxSnapshotResponse(snapshot_id=snapshot_id)

    async def SandboxSnapshotGet(
        self, request: api_pb2.SandboxSnapshotGetRequest, context
    ) -> api_pb2.SandboxSnapshotGetResponse:
        snap = self.s.sandbox_snapshots.get(request.snapshot_id)
        if snap is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "snapshot not found")
        return api_pb2.SandboxSnapshotGetResponse(
            snapshot_id=snap.snapshot_id, created_at=snap.created_at
        )

    async def SandboxRestore(
        self, request: api_pb2.SandboxRestoreRequest, context
    ) -> api_pb2.SandboxRestoreResponse:
        """Recreate a sandbox from a snapshot: same definition, workdir seeded
        from the snapshot's filesystem tarball (via a snapshot-image)."""
        from .state import ImageState

        snap = self.s.sandbox_snapshots.get(request.snapshot_id)
        if snap is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "snapshot not found")
        definition = api_pb2.Sandbox()
        definition.CopyFrom(snap.definition)
        if snap.fs_blob_id:
            image_id = self.s.make_id("im")
            self.s.images[image_id] = ImageState(
                image_id=image_id,
                definition=api_pb2.Image(fs_snapshot_blob_id=snap.fs_blob_id),
                built=True,
            )
            definition.image_id = image_id
            definition.workdir = ""  # seeded copy, not the old sandbox's dir
        if request.name:
            definition.name = request.name
        resp = await self.SandboxCreate(
            api_pb2.SandboxCreateRequest(definition=definition), context
        )
        return api_pb2.SandboxRestoreResponse(sandbox_id=resp.sandbox_id)

    async def SandboxGetTunnels(
        self, request: api_pb2.SandboxGetTunnelsRequest, context
    ) -> api_pb2.SandboxGetTunnelsResponse:
        sb = self.s.sandboxes.get(request.sandbox_id)
        if sb is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "sandbox not found")
        if not sb.definition.open_ports:
            return api_pb2.SandboxGetTunnelsResponse(
                result=api_pb2.GenericResult(
                    status=api_pb2.GENERIC_STATUS_FAILURE,
                    exception="sandbox has no open ports — pass unencrypted_ports/encrypted_ports to create()",
                )
            )
        deadline = time.monotonic() + min(max(request.timeout, 0.0), 60.0)
        while not sb.tunnels_reported and time.monotonic() < deadline:
            if sb.result is not None:  # sandbox already exited
                break
            await asyncio.sleep(0.05)
        if not sb.tunnels_reported:
            # an empty list must NOT read as success — callers index by port
            reason = (
                f"sandbox exited before tunnels came up: {sb.result.exception or 'exit'}"
                if sb.result is not None
                else f"tunnels not reported within {request.timeout:.0f}s"
            )
            return api_pb2.SandboxGetTunnelsResponse(
                result=api_pb2.GenericResult(
                    status=api_pb2.GENERIC_STATUS_FAILURE, exception=reason
                )
            )
        return api_pb2.SandboxGetTunnelsResponse(
            tunnels=list(sb.tunnels),
            result=api_pb2.GenericResult(status=api_pb2.GENERIC_STATUS_SUCCESS),
        )

    async def TunnelStart(self, request: api_pb2.TunnelStartRequest, context) -> api_pb2.TunnelStartResponse:
        """In-container `modal_tpu.forward(port)` (reference _tunnel.py): the
        control plane serves a TCP proxy to the container's port (same host
        in the local backend; production would front this with TLS + a
        public hostname)."""
        task = self.s.tasks.get(request.task_id)
        if task is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "task not found")
        key = (request.task_id, request.port)
        # Reservation protocol: a mid-flight start stores a Future under the
        # key; late arrivals await THAT future instead of creating a second
        # listener (two listeners for one key meant one asyncio server leaked
        # for the control plane's lifetime).
        for _ in range(3):
            existing = self.s.tunnels.get(key)
            if existing is None:
                break
            if isinstance(existing, asyncio.Future):
                try:
                    await asyncio.wait_for(asyncio.shield(existing), timeout=15.0)
                except asyncio.TimeoutError:
                    pass
                continue  # re-read: resolved to (server, port) or was stopped
            scheme = "tcp" if request.unencrypted else "tls"
            return api_pb2.TunnelStartResponse(
                host="127.0.0.1", port=existing[1], url=f"{scheme}://127.0.0.1:{existing[1]}"
            )
        else:
            await context.abort(grpc.StatusCode.UNAVAILABLE, "tunnel start contended; retry")
        # Re-validate task liveness AFTER the wait: the task may have finished
        # while we awaited, and _release_task (which closes this task's
        # tunnels) has already run — a listener installed now would leak for
        # the control plane's lifetime.
        task = self.s.tasks.get(request.task_id)
        if task is None or task.finished_at:
            await context.abort(grpc.StatusCode.FAILED_PRECONDITION, "task finished")
        fut: asyncio.Future = asyncio.get_running_loop().create_future()
        self.s.tunnels[key] = fut  # reservation
        target_port = request.port

        async def handle(reader, writer):
            try:
                up_r, up_w = await asyncio.open_connection("127.0.0.1", target_port)
            except OSError:
                writer.close()
                return

            async def pipe(src, dst):
                try:
                    while True:
                        data = await src.read(64 * 1024)
                        if not data:
                            break
                        dst.write(data)
                        await dst.drain()
                except Exception:  # noqa: BLE001 — peer reset
                    pass
                finally:
                    try:
                        dst.close()
                    except Exception:  # noqa: BLE001
                        pass

            await asyncio.gather(pipe(reader, up_w), pipe(up_r, writer))

        server = None
        try:
            server = await asyncio.start_server(handle, "127.0.0.1", 0)
            port = server.sockets[0].getsockname()[1]
            if self.s.tunnels.get(key) is fut:
                self.s.tunnels[key] = (server, port)
            else:
                # TunnelStop raced the start: don't leak the listener, and
                # don't hand the client a port whose listener is closed
                server.close()
                await context.abort(grpc.StatusCode.UNAVAILABLE, "tunnel stopped during start")
        finally:
            # ANY exit (OSError, RPC cancellation, abort) must release a
            # still-held reservation and wake waiters, or the key is bricked
            # for the control plane's lifetime
            if self.s.tunnels.get(key) is fut:
                del self.s.tunnels[key]
            if not fut.done():
                fut.set_result(None)  # waiters re-read the key and retry
        scheme = "tcp" if request.unencrypted else "tls"
        return api_pb2.TunnelStartResponse(host="127.0.0.1", port=port, url=f"{scheme}://127.0.0.1:{port}")

    async def TunnelStop(self, request: api_pb2.TunnelStopRequest, context) -> api_pb2.TunnelStopResponse:
        entry = self.s.tunnels.pop((request.task_id, request.port), None)
        if entry is None:
            return api_pb2.TunnelStopResponse(exists=False)
        # a Future entry is a mid-flight start: the starter sees its
        # reservation is gone and closes the listener itself; resolve it so
        # waiters wake immediately instead of riding their 15s timeout
        if isinstance(entry, asyncio.Future):
            if not entry.done():
                entry.set_result(None)
        elif entry[0] is not None:
            entry[0].close()
        return api_pb2.TunnelStopResponse(exists=True)

    async def TaskTunnelsUpdate(
        self, request: api_pb2.TaskTunnelsUpdateRequest, context
    ) -> api_pb2.TaskTunnelsUpdateResponse:
        for sb in self.s.sandboxes.values():
            if sb.task_id == request.task_id:
                sb.tunnels = list(request.tunnels)
                sb.tunnels_reported = True
                break
        return api_pb2.TaskTunnelsUpdateResponse()

    async def TaskReady(self, request: api_pb2.TaskReadyRequest, context) -> api_pb2.TaskReadyResponse:
        for sb in self.s.sandboxes.values():
            if sb.task_id == request.task_id:
                sb.ready = True
                break
        return api_pb2.TaskReadyResponse()

    async def WorkerPoll(self, request: api_pb2.WorkerPollRequest, context):
        worker = self.s.workers.get(request.worker_id)
        if worker is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "worker not registered")
        while True:
            try:
                event = await asyncio.wait_for(worker.events.get(), timeout=5.0)
            except asyncio.TimeoutError:
                # re-registration (reannounce / poll-NOT_FOUND re-announce)
                # replaces the WorkerState — and with it the events queue the
                # scheduler targets. A stream still draining the ABANDONED
                # queue would starve the worker of placements forever: end
                # the stream so the agent reconnects and binds the live one.
                if self.s.workers.get(request.worker_id) is not worker:
                    return
                continue
            yield event

    async def WorkerHeartbeat(self, request, context) -> api_pb2.WorkerHeartbeatResponse:
        worker = self.s.workers.get(request.worker_id)
        if worker is None:
            # unknown id — e.g. this control plane restarted without (or
            # before) the worker's journal record, or the worker was
            # deregistered. Never KeyError, never silently ignore: instruct
            # the worker to re-announce under its old id.
            return api_pb2.WorkerHeartbeatResponse(reannounce=True)
        if worker.adoption_pending:
            # journal-recovered worker proved it survived the control-plane
            # crash: re-adopt — placements may land here again
            worker.adoption_pending = False
            worker.recovered_at = 0.0
            WORKERS_READOPTED.inc()
            logger.info(f"worker {request.worker_id} re-adopted after recovery")
            self.s.schedule_event.set()
        WORKER_HEARTBEATS.inc()
        worker.last_heartbeat = time.time()
        worker.warm_pool_ready = request.warm_pool_ready
        if request.draining and not worker.draining and self.scheduler is not None:
            # worker announces an impending preemption (SIGTERM from the
            # cloud): enter drain state. The worker SIGTERMs its own
            # containers, so don't double-signal them from here. Honor
            # the grace the worker promised its containers — reaping on
            # the env default would SIGKILL them mid-checkpoint-flush.
            grace = request.drain_grace_s or float(
                os.environ.get("MODAL_TPU_PREEMPT_GRACE", "10")
            )
            await self.scheduler.drain_worker(
                request.worker_id, grace_s=grace, notify_worker=False
            )
        return api_pb2.WorkerHeartbeatResponse()

    # ------------------------------------------------------------------
    # Images
    # ------------------------------------------------------------------

    async def ImageGetOrCreate(self, request: api_pb2.ImageGetOrCreateRequest, context) -> api_pb2.ImageGetOrCreateResponse:
        key = hashlib.sha256(request.image.SerializeToString(deterministic=True)).hexdigest()[:16]
        image_id = self.s.images_by_hash.get(key)
        if image_id is None:
            image_id = self.s.make_id("im")
            metadata = api_pb2.ImageMetadata(
                image_builder_version=request.builder_version or "2026.07",
                python_version="local",
            )
            self.s.images[image_id] = ImageState(
                image_id=image_id, definition=request.image, metadata=metadata, built=True
            )
            self.s.images_by_hash[key] = image_id
            self._j(
                "image",
                image_id=image_id,
                definition=_jb64(request.image.SerializeToString()),
                metadata=_jb64(metadata.SerializeToString()),
                built=True,
                hash_key=key,
            )
        return api_pb2.ImageGetOrCreateResponse(image_id=image_id, metadata=self.s.images[image_id].metadata)

    async def ImageJoinStreaming(self, request, context) -> api_pb2.ImageJoinStreamingResponse:
        image = self.s.images.get(request.image_id)
        if image is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "image not found")
        return api_pb2.ImageJoinStreamingResponse(
            result=api_pb2.GenericResult(status=api_pb2.GENERIC_STATUS_SUCCESS),
            eof=True,
            metadata=image.metadata,
        )

    async def ImageFromId(self, request, context) -> api_pb2.ImageFromIdResponse:
        image = self.s.images.get(request.image_id)
        if image is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "image not found")
        return api_pb2.ImageFromIdResponse(
            image_id=request.image_id, metadata=image.metadata, definition=image.definition
        )

    # ------------------------------------------------------------------
    # Mounts
    # ------------------------------------------------------------------

    async def MountPutFile(self, request: api_pb2.MountPutFileRequest, context) -> api_pb2.MountPutFileResponse:
        if request.WhichOneof("data_oneof") is None:
            return api_pb2.MountPutFileResponse(exists=self.s.has_block(request.sha256_hex))
        data = request.data
        if request.data_blob_id:
            with open(self.s.blob_path(request.data_blob_id), "rb") as f:
                data = f.read()
        self.s.put_block(request.sha256_hex, data)
        return api_pb2.MountPutFileResponse(exists=True)

    async def MountGetOrCreate(self, request: api_pb2.MountGetOrCreateRequest, context) -> api_pb2.MountGetOrCreateResponse:
        missing = [f.sha256_hex for f in request.files if not self.s.has_block(f.sha256_hex)]
        if missing:
            await context.abort(
                grpc.StatusCode.FAILED_PRECONDITION, f"missing file content: {missing[:3]}"
            )
        mount_id = self.s.make_id("mo")
        # store manifest as a block so workers can materialize it
        manifest = json.dumps(
            [
                {"filename": f.filename, "sha256_hex": f.sha256_hex, "mode": f.mode, "size": f.size}
                for f in request.files
            ]
        ).encode()
        self.s.put_block("mount-" + mount_id, manifest)
        digest = hashlib.sha256(manifest).hexdigest()
        return api_pb2.MountGetOrCreateResponse(
            mount_id=mount_id,
            handle_metadata=api_pb2.MountHandleMetadata(content_checksum_sha256_hex=digest),
        )

    # ------------------------------------------------------------------
    # Volumes
    # ------------------------------------------------------------------

    async def VolumeGetOrCreate(self, request: api_pb2.VolumeGetOrCreateRequest, context) -> api_pb2.VolumeGetOrCreateResponse:
        if request.object_creation_type == EPHEMERAL or not request.deployment_name:
            volume_id = self.s.make_id("vo")
            self.s.volumes[volume_id] = VolumeState(
                volume_id=volume_id,
                version=request.version,
                ephemeral=request.object_creation_type == EPHEMERAL,
                last_heartbeat=time.time(),
            )
            self._j(
                "volume",
                volume_id=volume_id,
                version=request.version,
                ephemeral=request.object_creation_type == EPHEMERAL,
            )
            return api_pb2.VolumeGetOrCreateResponse(
                volume_id=volume_id, metadata=api_pb2.VolumeMetadata(version=request.version)
            )
        key = (self._resolve_environment(request.environment_name), request.deployment_name)
        volume_id = self.s.deployed_volumes.get(key)
        if volume_id is None:
            if request.object_creation_type not in (CREATE_IF_MISSING, FAIL_IF_EXISTS):
                await context.abort(grpc.StatusCode.NOT_FOUND, f"volume {request.deployment_name!r} not found")
            volume_id = self.s.make_id("vo")
            self.s.volumes[volume_id] = VolumeState(
                volume_id=volume_id, name=request.deployment_name, version=request.version
            )
            self.s.deployed_volumes[key] = volume_id
            self._j(
                "volume",
                volume_id=volume_id,
                name=request.deployment_name,
                version=request.version,
                deploy_key=list(key),
            )
        vol = self.s.volumes[volume_id]
        return api_pb2.VolumeGetOrCreateResponse(
            volume_id=volume_id, metadata=api_pb2.VolumeMetadata(version=vol.version, name=vol.name)
        )

    async def VolumePutFiles2(self, request: api_pb2.VolumePutFiles2Request, context) -> api_pb2.VolumePutFiles2Response:
        vol = self.s.volumes.get(request.volume_id)
        if vol is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "volume not found")
        missing = sorted(
            {sha for f in request.files for sha in f.block_sha256_hex if not self.s.has_block(sha)}
        )
        if missing:
            return api_pb2.VolumePutFiles2Response(missing_blocks=missing)
        stored = []
        for f in request.files:
            path = f.path.lstrip("/")
            if request.disallow_overwrite_existing_files and path in vol.files:
                await context.abort(grpc.StatusCode.ALREADY_EXISTS, f"file {path!r} already exists")
            new = api_pb2.VolumeFile()
            new.CopyFrom(f)
            new.path = path
            new.mtime = time.time()
            vol.files[path] = new
            stored.append(new)
        if stored:
            self._j(
                "volume_files",
                volume_id=request.volume_id,
                files=[_jb64(f.SerializeToString()) for f in stored],
            )
        return api_pb2.VolumePutFiles2Response()

    async def VolumeBlockPut(self, request, context) -> api_pb2.VolumeBlockPutResponse:
        import hashlib as _h

        actual = _h.sha256(request.data).hexdigest()
        if actual != request.sha256_hex:
            await context.abort(grpc.StatusCode.INVALID_ARGUMENT, "block hash mismatch")
        self.s.put_block(request.sha256_hex, request.data)
        return api_pb2.VolumeBlockPutResponse()

    async def VolumeBlockGet(self, request, context) -> api_pb2.VolumeBlockGetResponse:
        if not self.s.has_block(request.sha256_hex):
            await context.abort(grpc.StatusCode.NOT_FOUND, "block not found")
        return api_pb2.VolumeBlockGetResponse(
            data=self.s.get_block(request.sha256_hex, request.offset, request.length)
        )

    async def VolumeGetFile2(self, request, context) -> api_pb2.VolumeGetFile2Response:
        vol = self.s.volumes.get(request.volume_id)
        if vol is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "volume not found")
        f = vol.files.get(request.path.lstrip("/"))
        if f is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, f"file {request.path!r} not found")
        from .._utils.hash_utils import BLOCK_SIZE

        # advertise the HTTP block plane (Range-capable GET /block/{sha}) and
        # the local block dir: co-located clients pread from page cache,
        # remote ones stream HTTP without the per-block gRPC proto copy
        return api_pb2.VolumeGetFile2Response(
            file=f,
            block_size=BLOCK_SIZE,
            block_url_base=self.s.blob_url_base or "",
            block_local_dir=self.s.block_dir,
        )

    async def VolumeListFiles(self, request, context) -> api_pb2.VolumeListFilesResponse:
        vol = self.s.volumes.get(request.volume_id)
        if vol is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "volume not found")
        prefix = request.path.lstrip("/").rstrip("/")
        files = []
        for path, f in sorted(vol.files.items()):
            if prefix and not (path == prefix or path.startswith(prefix + "/")):
                continue
            if not request.recursive and prefix:
                rel = path[len(prefix) :].lstrip("/")
                if "/" in rel:
                    continue
            elif not request.recursive and "/" in path:
                continue
            files.append(f)
        return api_pb2.VolumeListFilesResponse(files=files)

    async def VolumeRemoveFile(self, request, context) -> api_pb2.VolumeRemoveFileResponse:
        vol = self.s.volumes.get(request.volume_id)
        if vol is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "volume not found")
        path = request.path.lstrip("/")
        if request.recursive:
            for p in list(vol.files):
                if p == path or p.startswith(path + "/"):
                    del vol.files[p]
        elif path in vol.files:
            del vol.files[path]
        else:
            await context.abort(grpc.StatusCode.NOT_FOUND, f"file {path!r} not found")
        self._j("volume_rm", volume_id=request.volume_id, path=path, recursive=request.recursive)
        return api_pb2.VolumeRemoveFileResponse()

    async def VolumeCopyFiles(self, request, context) -> api_pb2.VolumeCopyFilesResponse:
        vol = self.s.volumes.get(request.volume_id)
        if vol is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "volume not found")
        dst = request.dst_path.lstrip("/")
        copied = []
        for src in request.src_paths:
            src = src.lstrip("/")
            f = vol.files.get(src)
            if f is None:
                await context.abort(grpc.StatusCode.NOT_FOUND, f"file {src!r} not found")
            new = api_pb2.VolumeFile()
            new.CopyFrom(f)
            new.path = (dst.rstrip("/") + "/" + src.rsplit("/", 1)[-1]) if dst.endswith("/") or len(request.src_paths) > 1 else dst
            vol.files[new.path] = new
            copied.append(new)
        if copied:
            self._j(
                "volume_files",
                volume_id=request.volume_id,
                files=[_jb64(f.SerializeToString()) for f in copied],
            )
        return api_pb2.VolumeCopyFilesResponse()

    async def VolumeCommit(self, request, context) -> api_pb2.VolumeCommitResponse:
        vol = self.s.volumes.get(request.volume_id)
        if vol is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "volume not found")
        vol.committed_version += 1
        self._j("volume_meta", volume_id=request.volume_id, committed_version=vol.committed_version)
        return api_pb2.VolumeCommitResponse(skip_reload=False)

    async def VolumeReload(self, request, context) -> api_pb2.VolumeReloadResponse:
        if request.volume_id not in self.s.volumes:
            await context.abort(grpc.StatusCode.NOT_FOUND, "volume not found")
        return api_pb2.VolumeReloadResponse()

    async def VolumeRename(self, request, context) -> api_pb2.VolumeRenameResponse:
        vol = self.s.volumes.get(request.volume_id)
        if vol is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "volume not found")
        for key, vid in list(self.s.deployed_volumes.items()):
            if vid == vol.volume_id:
                del self.s.deployed_volumes[key]
                self.s.deployed_volumes[(key[0], request.name)] = vid
        vol.name = request.name
        self._j("volume_meta", volume_id=request.volume_id, name=request.name)
        return api_pb2.VolumeRenameResponse()

    async def VolumeDelete(self, request, context) -> api_pb2.VolumeDeleteResponse:
        vol = self.s.volumes.pop(request.volume_id, None)
        if vol is not None:
            for key, vid in list(self.s.deployed_volumes.items()):
                if vid == request.volume_id:
                    del self.s.deployed_volumes[key]
            self._j("volume_del", volume_id=request.volume_id)
        return api_pb2.VolumeDeleteResponse()

    async def VolumeList(self, request, context) -> api_pb2.VolumeListResponse:
        items = [
            api_pb2.VolumeListItem(volume_id=v.volume_id, name=v.name, created_at=v.created_at, version=v.version)
            for v in self.s.volumes.values()
            if v.name
        ]
        return api_pb2.VolumeListResponse(items=items)

    # ------------------------------------------------------------------
    # Secrets
    # ------------------------------------------------------------------

    async def SecretGetOrCreate(self, request: api_pb2.SecretGetOrCreateRequest, context) -> api_pb2.SecretGetOrCreateResponse:
        if request.object_creation_type in (ANONYMOUS, EPHEMERAL) or (
            not request.deployment_name and request.env_dict
        ):
            secret_id = self.s.make_id("st")
            self.s.secrets[secret_id] = SecretState(secret_id=secret_id, env_dict=dict(request.env_dict))
            self._j("secret", secret_id=secret_id, env=dict(request.env_dict))
            return api_pb2.SecretGetOrCreateResponse(secret_id=secret_id)
        key = (self._resolve_environment(request.environment_name), request.deployment_name)
        secret_id = self.s.deployed_secrets.get(key)
        if secret_id is None:
            if request.object_creation_type not in (CREATE_IF_MISSING, FAIL_IF_EXISTS) and not request.env_dict:
                await context.abort(grpc.StatusCode.NOT_FOUND, f"secret {request.deployment_name!r} not found")
            secret_id = self.s.make_id("st")
            self.s.secrets[secret_id] = SecretState(
                secret_id=secret_id, name=request.deployment_name, env_dict=dict(request.env_dict)
            )
            self.s.deployed_secrets[key] = secret_id
            self._j(
                "secret",
                secret_id=secret_id,
                name=request.deployment_name,
                env=dict(request.env_dict),
                deploy_key=list(key),
            )
        elif request.object_creation_type == FAIL_IF_EXISTS:
            await context.abort(grpc.StatusCode.ALREADY_EXISTS, "secret exists")
        elif request.env_dict:
            self.s.secrets[secret_id].env_dict = dict(request.env_dict)
            self._j(
                "secret",
                secret_id=secret_id,
                name=self.s.secrets[secret_id].name,
                env=dict(request.env_dict),
                deploy_key=list(key),
            )
        self.s.secrets[secret_id].last_used_at = time.time()
        return api_pb2.SecretGetOrCreateResponse(secret_id=secret_id)

    async def SecretList(self, request, context) -> api_pb2.SecretListResponse:
        items = [
            api_pb2.SecretListItem(
                label=s.name, created_at=s.created_at, last_used_at=s.last_used_at, secret_id=s.secret_id
            )
            for s in self.s.secrets.values()
            if s.name
        ]
        return api_pb2.SecretListResponse(items=items)

    async def SecretDelete(self, request, context) -> api_pb2.SecretDeleteResponse:
        secret = self.s.secrets.pop(request.secret_id, None)
        if secret is not None:
            for key, sid in list(self.s.deployed_secrets.items()):
                if sid == request.secret_id:
                    del self.s.deployed_secrets[key]
            self._j("secret_del", secret_id=request.secret_id)
        return api_pb2.SecretDeleteResponse()

    # ------------------------------------------------------------------
    # Dicts
    # ------------------------------------------------------------------

    # -- proxies (static egress; reference proxy.py:1) ----------------------

    async def ProxyCreate(self, request: api_pb2.ProxyCreateRequest, context) -> api_pb2.ProxyCreateResponse:
        if not request.name:
            await context.abort(grpc.StatusCode.INVALID_ARGUMENT, "proxy name required")
        key = (self._resolve_environment(request.environment_name), request.name)
        if key in self.s.deployed_proxies:
            await context.abort(grpc.StatusCode.ALREADY_EXISTS, f"proxy {request.name!r} exists")
        proxy_id = self.s.make_id("pr")
        # static IP from a private range, never reusing one a live proxy
        # holds (a count-derived octet would collide after deletes) — the
        # worker exports it to containers as their egress address (locally:
        # env only; a production deployment binds SNAT to it)
        in_use = {p.proxy_ip for p in self.s.proxies.values()}
        ip = next(
            (
                f"10.250.{block}.{octet}"
                for block in range(256)
                for octet in range(2, 252)
                if f"10.250.{block}.{octet}" not in in_use
            ),
            None,
        )
        if ip is None:
            await context.abort(grpc.StatusCode.RESOURCE_EXHAUSTED, "proxy IP range exhausted")
        proxy = ProxyState(
            proxy_id=proxy_id,
            name=request.name,
            proxy_ip=ip,
            # resolved, so ProxyDelete's (environment, name) un-keying
            # matches the deployed_proxies key written below
            environment_name=key[0],
        )
        self.s.proxies[proxy_id] = proxy
        self.s.deployed_proxies[key] = proxy_id
        self._j(
            "proxy",
            proxy_id=proxy_id,
            name=proxy.name,
            proxy_ip=proxy.proxy_ip,
            environment_name=proxy.environment_name,
        )
        return api_pb2.ProxyCreateResponse(
            proxy=api_pb2.Proxy(proxy_id=proxy_id, name=proxy.name, proxy_ip=proxy.proxy_ip)
        )

    async def ProxyGet(self, request: api_pb2.ProxyGetRequest, context) -> api_pb2.ProxyGetResponse:
        proxy_id = self.s.deployed_proxies.get((self._resolve_environment(request.environment_name), request.name))
        if proxy_id is None:
            await context.abort(
                grpc.StatusCode.NOT_FOUND,
                f"proxy {request.name!r} not found — provision it with `modal-tpu proxy create`",
            )
        proxy = self.s.proxies[proxy_id]
        return api_pb2.ProxyGetResponse(
            proxy=api_pb2.Proxy(proxy_id=proxy_id, name=proxy.name, proxy_ip=proxy.proxy_ip)
        )

    async def ProxyList(self, request: api_pb2.ProxyListRequest, context) -> api_pb2.ProxyListResponse:
        return api_pb2.ProxyListResponse(
            proxies=[
                api_pb2.Proxy(proxy_id=p.proxy_id, name=p.name, proxy_ip=p.proxy_ip)
                for p in self.s.proxies.values()
                if not request.environment_name or p.environment_name == request.environment_name
            ]
        )

    async def ProxyDelete(self, request: api_pb2.ProxyDeleteRequest, context) -> api_pb2.ProxyDeleteResponse:
        proxy = self.s.proxies.pop(request.proxy_id, None)
        if proxy is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "proxy not found")
        self.s.deployed_proxies.pop((proxy.environment_name, proxy.name), None)
        self._j("proxy_del", proxy_id=request.proxy_id)
        return api_pb2.ProxyDeleteResponse()

    # -- ephemeral-object liveness (reference _object.py:21) ----------------

    async def EphemeralObjectHeartbeat(
        self, request: api_pb2.EphemeralObjectHeartbeatRequest, context
    ) -> api_pb2.EphemeralObjectHeartbeatResponse:
        pools = {"di": self.s.dicts, "qu": self.s.queues, "vo": self.s.volumes}
        pool = pools.get(request.object_id[:2])
        obj = pool.get(request.object_id) if pool is not None else None
        if obj is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, f"no such object {request.object_id}")
        obj.last_heartbeat = time.time()
        return api_pb2.EphemeralObjectHeartbeatResponse(ttl_seconds=self.ephemeral_ttl_seconds())

    @staticmethod
    def ephemeral_ttl_seconds() -> float:
        """How long an ephemeral object outlives its last heartbeat. The
        client heartbeats at a third of this (object.py), mirroring the
        reference's 300s heartbeat sleep."""
        return float(os.environ.get("MODAL_TPU_EPHEMERAL_TTL", "900"))

    def reap_stale_ephemerals(self) -> int:
        """Delete ephemeral dicts/queues/volumes whose client stopped
        heartbeating (called from the scheduler's reap tick). Returns the
        number reaped."""
        ttl = self.ephemeral_ttl_seconds()
        cutoff = time.time() - ttl
        reaped = 0
        for pool in (self.s.dicts, self.s.queues, self.s.volumes):
            for obj_id in [
                oid
                for oid, obj in pool.items()
                if obj.ephemeral and obj.last_heartbeat and obj.last_heartbeat < cutoff
            ]:
                logger.debug(f"reaping stale ephemeral object {obj_id}")
                del pool[obj_id]
                reaped += 1
        return reaped

    async def DictGetOrCreate(self, request: api_pb2.DictGetOrCreateRequest, context) -> api_pb2.DictGetOrCreateResponse:
        if request.object_creation_type == EPHEMERAL or not request.deployment_name:
            dict_id = self.s.make_id("di")
            self.s.dicts[dict_id] = DictState(
                dict_id=dict_id,
                ephemeral=request.object_creation_type == EPHEMERAL,
                last_heartbeat=time.time(),
            )
            self._j(
                "dictq",
                pool="dicts",
                id=dict_id,
                ephemeral=request.object_creation_type == EPHEMERAL,
            )
            return api_pb2.DictGetOrCreateResponse(dict_id=dict_id)
        key = (self._resolve_environment(request.environment_name), request.deployment_name)
        dict_id = self.s.deployed_dicts.get(key)
        if dict_id is None:
            if request.object_creation_type not in (CREATE_IF_MISSING, FAIL_IF_EXISTS):
                await context.abort(grpc.StatusCode.NOT_FOUND, f"dict {request.deployment_name!r} not found")
            dict_id = self.s.make_id("di")
            self.s.dicts[dict_id] = DictState(dict_id=dict_id, name=request.deployment_name)
            self.s.deployed_dicts[key] = dict_id
            self._j(
                "dictq", pool="dicts", id=dict_id, name=request.deployment_name, deploy_key=list(key)
            )
        return api_pb2.DictGetOrCreateResponse(dict_id=dict_id)

    async def DictUpdate(self, request, context) -> api_pb2.DictUpdateResponse:
        d = self.s.dicts.get(request.dict_id)
        if d is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "dict not found")
        if request.if_not_exists and len(request.updates) == 1:
            entry = request.updates[0]
            if bytes(entry.key) in d.data:
                return api_pb2.DictUpdateResponse(created=False)
            d.data[bytes(entry.key)] = bytes(entry.value)
            return api_pb2.DictUpdateResponse(created=True)
        for entry in request.updates:
            d.data[bytes(entry.key)] = bytes(entry.value)
        return api_pb2.DictUpdateResponse(created=True)

    async def DictGet(self, request, context) -> api_pb2.DictGetResponse:
        d = self.s.dicts.get(request.dict_id)
        if d is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "dict not found")
        value = d.data.get(bytes(request.key))
        return api_pb2.DictGetResponse(found=value is not None, value=value or b"")

    async def DictPop(self, request, context) -> api_pb2.DictPopResponse:
        d = self.s.dicts.get(request.dict_id)
        if d is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "dict not found")
        value = d.data.pop(bytes(request.key), None)
        return api_pb2.DictPopResponse(found=value is not None, value=value or b"")

    async def DictContains(self, request, context) -> api_pb2.DictContainsResponse:
        d = self.s.dicts.get(request.dict_id)
        if d is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "dict not found")
        return api_pb2.DictContainsResponse(found=bytes(request.key) in d.data)

    async def DictLen(self, request, context) -> api_pb2.DictLenResponse:
        d = self.s.dicts.get(request.dict_id)
        if d is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "dict not found")
        return api_pb2.DictLenResponse(len=len(d.data))

    async def DictContents(self, request, context) -> api_pb2.DictContentsResponse:
        d = self.s.dicts.get(request.dict_id)
        if d is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "dict not found")
        return api_pb2.DictContentsResponse(
            items=[api_pb2.DictEntry(key=k, value=v) for k, v in d.data.items()]
        )

    async def DictClear(self, request, context) -> api_pb2.DictClearResponse:
        d = self.s.dicts.get(request.dict_id)
        if d is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "dict not found")
        d.data.clear()
        return api_pb2.DictClearResponse()

    async def DictDelete(self, request, context) -> api_pb2.DictDeleteResponse:
        d = self.s.dicts.pop(request.dict_id, None)
        if d is not None:
            for key, did in list(self.s.deployed_dicts.items()):
                if did == request.dict_id:
                    del self.s.deployed_dicts[key]
            self._j("dictq_del", pool="dicts", id=request.dict_id)
        return api_pb2.DictDeleteResponse()

    async def DictList(self, request, context) -> api_pb2.DictListResponse:
        items = [
            api_pb2.DictListItem(name=d.name, created_at=d.created_at, dict_id=d.dict_id)
            for d in self.s.dicts.values()
            if d.name
        ]
        return api_pb2.DictListResponse(items=items)

    # ------------------------------------------------------------------
    # Queues
    # ------------------------------------------------------------------

    async def QueueGetOrCreate(self, request: api_pb2.QueueGetOrCreateRequest, context) -> api_pb2.QueueGetOrCreateResponse:
        if request.object_creation_type == EPHEMERAL or not request.deployment_name:
            queue_id = self.s.make_id("qu")
            self.s.queues[queue_id] = QueueState(
                queue_id=queue_id,
                ephemeral=request.object_creation_type == EPHEMERAL,
                last_heartbeat=time.time(),
            )
            self._j(
                "dictq",
                pool="queues",
                id=queue_id,
                ephemeral=request.object_creation_type == EPHEMERAL,
            )
            return api_pb2.QueueGetOrCreateResponse(queue_id=queue_id)
        key = (self._resolve_environment(request.environment_name), request.deployment_name)
        queue_id = self.s.deployed_queues.get(key)
        if queue_id is None:
            if request.object_creation_type not in (CREATE_IF_MISSING, FAIL_IF_EXISTS):
                await context.abort(grpc.StatusCode.NOT_FOUND, f"queue {request.deployment_name!r} not found")
            queue_id = self.s.make_id("qu")
            self.s.queues[queue_id] = QueueState(queue_id=queue_id, name=request.deployment_name)
            self.s.deployed_queues[key] = queue_id
            self._j(
                "dictq", pool="queues", id=queue_id, name=request.deployment_name, deploy_key=list(key)
            )
        return api_pb2.QueueGetOrCreateResponse(queue_id=queue_id)

    async def QueuePut(self, request: api_pb2.QueuePutRequest, context) -> api_pb2.QueuePutResponse:
        q = self.s.queues.get(request.queue_id)
        if q is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "queue not found")
        part = q.partition(request.partition_key)
        for v in request.values:
            part.next_entry += 1
            part.items.append((str(part.next_entry), bytes(v)))
        async with part.condition:
            part.condition.notify_all()
        return api_pb2.QueuePutResponse()

    async def QueueGet(self, request: api_pb2.QueueGetRequest, context) -> api_pb2.QueueGetResponse:
        q = self.s.queues.get(request.queue_id)
        if q is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "queue not found")
        part = q.partition(request.partition_key)
        n = max(1, request.n_values)
        deadline = time.monotonic() + (request.timeout or 0.0)
        while True:
            if part.items:
                taken = part.items[:n]
                del part.items[:n]
                return api_pb2.QueueGetResponse(values=[v for _, v in taken])
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return api_pb2.QueueGetResponse(values=[])
            async with part.condition:
                try:
                    await asyncio.wait_for(part.condition.wait(), timeout=remaining)
                except asyncio.TimeoutError:
                    pass

    async def QueueNextItems(self, request: api_pb2.QueueNextItemsRequest, context) -> api_pb2.QueueNextItemsResponse:
        q = self.s.queues.get(request.queue_id)
        if q is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "queue not found")
        part = q.partition(request.partition_key)
        last = int(request.last_entry_id) if request.last_entry_id else 0
        deadline = time.monotonic() + (request.item_poll_timeout or 0.0)
        while True:
            items = [
                api_pb2.QueueItem(value=v, entry_id=eid) for eid, v in part.items if int(eid) > last
            ]
            if items:
                return api_pb2.QueueNextItemsResponse(items=items)
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return api_pb2.QueueNextItemsResponse(items=[])
            async with part.condition:
                try:
                    await asyncio.wait_for(part.condition.wait(), timeout=remaining)
                except asyncio.TimeoutError:
                    pass

    async def QueueLen(self, request, context) -> api_pb2.QueueLenResponse:
        q = self.s.queues.get(request.queue_id)
        if q is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "queue not found")
        if request.total:
            return api_pb2.QueueLenResponse(len=sum(len(p.items) for p in q.partitions.values()))
        return api_pb2.QueueLenResponse(len=len(q.partition(request.partition_key).items))

    async def QueueClear(self, request, context) -> api_pb2.QueueClearResponse:
        q = self.s.queues.get(request.queue_id)
        if q is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "queue not found")
        if request.all_partitions:
            q.partitions.clear()
        else:
            q.partition(request.partition_key).items.clear()
        return api_pb2.QueueClearResponse()

    async def QueueDelete(self, request, context) -> api_pb2.QueueDeleteResponse:
        q = self.s.queues.pop(request.queue_id, None)
        if q is not None:
            for key, qid in list(self.s.deployed_queues.items()):
                if qid == request.queue_id:
                    del self.s.deployed_queues[key]
            self._j("dictq_del", pool="queues", id=request.queue_id)
        return api_pb2.QueueDeleteResponse()

    async def QueueList(self, request, context) -> api_pb2.QueueListResponse:
        items = [
            api_pb2.QueueListItem(
                name=q.name,
                created_at=q.created_at,
                num_partitions=len(q.partitions),
                total_size=sum(len(p.items) for p in q.partitions.values()),
                queue_id=q.queue_id,
            )
            for q in self.s.queues.values()
            if q.name
        ]
        return api_pb2.QueueListResponse(items=items)
