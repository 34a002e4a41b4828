"""The client: owns the channel + stub to the control plane.

Reference: py/modal/client.py `_Client` (client.py:77) — `from_env`
(client.py:207), `from_credentials` (client.py:256), per-URL stub cache
(client.py:135), fork-safety PID reset (client.py:347). The TPU build keeps
the same shape; the stub is the hand-written `ModalTPUStub` spine.
"""

from __future__ import annotations

import asyncio
import os
from typing import Any, ClassVar, Optional

import grpc

from ._utils.async_utils import synchronize_api
from ._utils.grpc_utils import create_channel, retry_transient_errors
from .config import config, logger
from .exception import AuthError, ClientClosed
from .proto import api_pb2
from .proto.rpc import ModalTPUStub

HEARTBEAT_INTERVAL: float = config.get("heartbeat_interval")
CLIENT_VERSION = "0.1.0"


class _Client:
    _client_from_env: ClassVar[Optional["_Client"]] = None
    _client_from_env_lock: ClassVar[Optional[asyncio.Lock]] = None
    _cancellation_context: Any

    def __init__(
        self,
        server_url: str,
        client_type: int = api_pb2.CLIENT_TYPE_CLIENT,
        credentials: Optional[tuple[str, str]] = None,
    ):
        self.server_url = server_url
        self.client_type = client_type
        self._credentials = credentials
        self._channel: Optional[grpc.aio.Channel] = None
        self._stub: Optional[ModalTPUStub] = None
        self._stub_cache: dict[str, ModalTPUStub] = {}
        self._channel_cache: dict[str, grpc.aio.Channel] = {}
        self._closed = False
        self._owner_pid = os.getpid()
        self.image_builder_version: Optional[str] = None
        self.input_plane_url: Optional[str] = None
        self._auth_token_manager: Optional[Any] = None
        # local fast-path coordinates by server URL (learned at hello();
        # env-provided for containers) — consumed by _wrap_fastpath
        self._uds_by_url: dict[str, str] = {}
        self._stub_tcp: Optional[ModalTPUStub] = None
        # coalesced dispatch (_utils/coalescer.py): per-plane micro-batchers
        # for FunctionMap / AttemptStart submissions
        from ._utils.coalescer import BatcherRegistry

        self._batchers = BatcherRegistry()
        self._map_batch_unsupported = False
        self._attempt_batch_unsupported = False
        self._stream_outputs_unsupported = False

    def _metadata(self) -> dict[str, str]:
        md = {
            "x-modal-tpu-client-version": CLIENT_VERSION,
            "x-modal-tpu-client-type": str(self.client_type),
        }
        if self._credentials:
            token_id, token_secret = self._credentials
            md["x-modal-tpu-token-id"] = token_id
            md["x-modal-tpu-token-secret"] = token_secret
        if config.get("task_id"):
            md["x-modal-tpu-task-id"] = config.get("task_id")
        return md

    def _wrap_fastpath(
        self, server_url: str, tcp_stub: ModalTPUStub, uds_path: str = "", blob_local_dir: str = ""
    ) -> Any:
        """Upgrade a TCP stub to the local fast-path ladder (inproc → UDS →
        TCP, _utils/local_transport.py) when any local rung is usable. The
        co-location check is a stat: a path the server advertised that this
        process can actually see. Anything non-local returns the TCP stub
        unchanged."""
        from ._utils import local_transport

        if not local_transport.fastpath_enabled():
            return tcp_stub
        uds_ok = (
            local_transport.uds_enabled()
            and local_transport.usable_uds_path(uds_path)
            and os.path.exists(uds_path)
        )
        blob_ok = bool(blob_local_dir) and os.path.isdir(blob_local_dir)
        inproc_ok = local_transport.resolve_local_server(server_url) is not None
        if not (uds_ok or blob_ok or inproc_ok):
            return tcp_stub
        uds_stub = None
        if uds_ok:
            uds_url = f"unix://{uds_path}"
            if uds_url not in self._channel_cache:
                self._channel_cache[uds_url] = create_channel(uds_url, metadata=self._metadata())
            uds_stub = ModalTPUStub(self._channel_cache[uds_url])
        return local_transport.FastPathStub(
            server_url,
            tcp_stub,
            uds_path=uds_path if uds_ok else "",
            uds_stub=uds_stub,
            base_metadata=self._metadata(),
            blob_local_dir=blob_local_dir if blob_ok else "",
        )

    async def _open(self) -> None:
        self._channel = create_channel(self.server_url, metadata=self._metadata())
        # containers learn their local coordinates from the worker's env
        # (they never call hello()); plain clients upgrade at hello() time
        self._stub_tcp = ModalTPUStub(self._channel)
        self._stub = self._wrap_fastpath(
            self.server_url,
            self._stub_tcp,
            uds_path=os.environ.get("MODAL_TPU_SERVER_UDS", ""),
            blob_local_dir=os.environ.get("MODAL_TPU_BLOB_LOCAL_DIR", ""),
        )

    async def _close(self) -> None:
        self._closed = True
        for channel in [self._channel, *self._channel_cache.values()]:
            if channel is not None:
                await channel.close()
        self._channel = None
        self._stub = None
        self._channel_cache.clear()
        self._stub_cache.clear()

    @property
    def stub(self) -> ModalTPUStub:
        if self._stub is None:
            raise ClientClosed("client is not connected")
        return self._stub

    async def get_stub(self, server_url: str) -> ModalTPUStub:
        """Stub for an alternate server URL (input plane / worker data plane),
        cached per URL (reference client.py:135). Fast-path-upgraded when the
        URL has known local coordinates (ClientHello advertisement / env)."""
        if server_url not in self._stub_cache:
            channel = create_channel(server_url, metadata=self._metadata())
            self._channel_cache[server_url] = channel
            self._stub_cache[server_url] = self._wrap_fastpath(
                server_url,
                ModalTPUStub(channel),
                uds_path=self._uds_by_url.get(server_url, ""),
            )
        return self._stub_cache[server_url]

    async def get_input_plane_metadata(self) -> list[tuple[str, str]]:
        """Per-call metadata for input-plane RPCs: the refreshing JWT
        (reference client.py:301 get_input_plane_metadata)."""
        if self._auth_token_manager is None:
            from ._utils.auth_token_manager import AuthTokenManager

            self._auth_token_manager = AuthTokenManager(self.stub)
        token = await self._auth_token_manager.get_token()
        return [("x-modal-tpu-auth-token", token)]

    async def hello(self) -> None:
        resp = await retry_transient_errors(
            self.stub.ClientHello,
            api_pb2.ClientHelloRequest(client_version=CLIENT_VERSION, client_type=self.client_type),
        )
        if resp.warning:
            logger.warning(resp.warning)
        self.image_builder_version = resp.image_builder_version or None
        self.input_plane_url = resp.input_plane_url or None
        # transport upgrade (docs/DISPATCH.md): the server just told us its
        # local coordinates — a stat-able socket/blob dir means co-location,
        # so re-point the stub at the fast-path ladder. Unverifiable paths
        # leave the TCP stub untouched (the false-negative case degrades to
        # today's behavior by construction).
        if resp.input_plane_url and resp.input_plane_uds_path:
            self._uds_by_url[resp.input_plane_url] = resp.input_plane_uds_path
        if self._stub_tcp is not None and (resp.uds_path or resp.blob_local_dir):
            self._stub = self._wrap_fastpath(
                self.server_url,
                self._stub_tcp,
                uds_path=resp.uds_path,
                blob_local_dir=resp.blob_local_dir,
            )
        # sharded control plane (server/shards.py): a shard map with more
        # than one owner upgrades the stub to direct-to-shard routing — the
        # director stays out of the unary data path entirely
        if resp.shard_map_json:
            import json as _json

            from ._utils.shard_router import ShardRouterStub

            shard_map = _json.loads(resp.shard_map_json)
            if isinstance(self._stub, ShardRouterStub):
                self._stub.update_map(shard_map)
            elif len(shard_map.get("urls") or []) > 1:
                self._stub = ShardRouterStub(self, self._stub, shard_map)

    async def __aenter__(self) -> "_Client":
        await self._open()
        return self

    async def __aexit__(self, *exc: Any) -> None:
        await self._close()

    _local_supervisor: ClassVar[Optional[Any]] = None

    @classmethod
    async def _maybe_boot_local_server(cls, server_url: str) -> str:
        """Zero-config local mode: when the configured server is the default
        localhost URL and nothing is listening, boot an in-process
        LocalSupervisor (control plane + worker + blob server) and use it.
        The reference SDK always has a cloud to talk to; this is our
        equivalent of that always-reachable default. Containers
        (task_id set) never auto-boot — a refused connection there is real."""
        if cls._local_supervisor is not None:
            return cls._local_supervisor.server_url
        if config.get("task_id") or not config.get("auto_local_server"):
            return server_url
        from .config import _SETTINGS

        if server_url != _SETTINGS["server_url"].default:
            # an explicitly configured URL means the user runs their own
            # server — a refused connection there must surface, not be
            # papered over by a fresh empty supervisor
            return server_url
        import socket

        host, port_s = server_url.removeprefix("grpc://").rsplit(":", 1)
        try:
            # one 250 ms-bounded probe, before any RPC traffic exists on this
            # loop — nothing else is in flight to stall
            probe = socket.create_connection((host, int(port_s)), timeout=0.25)  # lint: disable=blocking-in-async
            probe.close()
            return server_url  # a real server is listening
        except OSError:
            pass
        from .server.supervisor import LocalSupervisor

        # MODAL_TPU_SHARDS>1 auto-boots the sharded control plane instead
        # (server/shards.py); 1 is the monolith degradation contract
        try:
            num_shards = int(os.environ.get("MODAL_TPU_SHARDS", "1") or 1)
        except ValueError:
            num_shards = 1
        if num_shards > 1:
            from .server.shards import ShardedSupervisor

            sup: Any = ShardedSupervisor(num_shards=num_shards, num_workers=1, port=int(port_s))
        else:
            sup = LocalSupervisor(num_workers=1, port=int(port_s))
        from .server.worker import TpuProbeError

        try:
            await sup.start()
        except Exception as exc:  # noqa: BLE001 — e.g. lost a port race
            logger.debug(f"local supervisor auto-boot failed: {exc}")
            try:
                await sup.stop()  # release anything that did bind (port!)
            except Exception:  # noqa: BLE001
                pass
            if isinstance(exc, TpuProbeError):
                # not a race to paper over: this host's chips cannot be
                # inventoried, and a worker reporting zero would leave every
                # tpu= function queued for ever
                raise
            return server_url
        cls._local_supervisor = sup
        loop = asyncio.get_running_loop()

        def _shutdown() -> None:
            try:
                if loop.is_closed():
                    return
                asyncio.run_coroutine_threadsafe(sup.stop(), loop).result(timeout=5.0)
            except Exception:  # noqa: BLE001 — loop already gone at exit
                pass

        import atexit

        atexit.register(_shutdown)
        logger.info(f"auto-booted local supervisor at {sup.server_url}")
        return sup.server_url

    @classmethod
    async def from_env(cls) -> "_Client":
        """Singleton client from config/env; re-created on fork (reference
        client.py:207,347)."""
        if cls._client_from_env is not None and cls._client_from_env._owner_pid != os.getpid():
            cls._client_from_env = None
            cls._client_from_env_lock = None
        if cls._client_from_env_lock is None:
            cls._client_from_env_lock = asyncio.Lock()
        # single-flight by design: concurrent from_env callers must wait for
        # ONE handshake instead of racing dials
        async with cls._client_from_env_lock:  # lint: disable=lock-across-await
            if cls._client_from_env is None or cls._client_from_env._closed:
                server_url = await cls._maybe_boot_local_server(config["server_url"])
                token_id = config.get("token_id")
                token_secret = config.get("token_secret")
                credentials = (token_id, token_secret) if token_id else None
                client_type = (
                    api_pb2.CLIENT_TYPE_CONTAINER if config.get("task_id") else api_pb2.CLIENT_TYPE_CLIENT
                )
                client = cls(server_url, client_type, credentials)
                await client._open()
                try:
                    # learn server capabilities (input_plane_url, builder
                    # version); a failure here surfaces on the first real
                    # RPC anyway — don't block client creation
                    await client.hello()
                except Exception as exc:  # noqa: BLE001
                    logger.debug(f"client hello failed: {exc}")
                cls._client_from_env = client
            return cls._client_from_env

    @classmethod
    async def from_credentials(cls, token_id: str, token_secret: str) -> "_Client":
        client = cls(config["server_url"], api_pb2.CLIENT_TYPE_CLIENT, (token_id, token_secret))
        await client._open()
        return client

    @classmethod
    async def anonymous(cls, server_url: str) -> "_Client":
        client = cls(server_url, api_pb2.CLIENT_TYPE_CLIENT, None)
        await client._open()
        return client

    @classmethod
    def set_env_client(cls, client: Optional["_Client"]) -> None:
        cls._client_from_env = client

    @classmethod
    async def verify(cls, server_url: str, credentials: tuple[str, str]) -> None:
        async with cls(server_url, api_pb2.CLIENT_TYPE_CLIENT, credentials) as client:
            await client.hello()


Client = synchronize_api(_Client)
