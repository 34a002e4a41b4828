"""Worker agent: the host daemon that runs containers.

Net-new relative to the reference (its worker fleet is closed; the contract it
must satisfy is visible in the container entrypoint it boots — reference
_container_entrypoint.py:475-490: write ContainerArguments to a file, point
the env at it, exec the entrypoint).

The local worker runs containers as subprocesses of this host (the "container
image" is the worker's own venv in v0). Which device a container may use is
decided in one place, `device_env`: assigned chips are pinned and required,
a container with none is held to the CPU.
"""

from __future__ import annotations

import asyncio
import os
import shutil
import signal
import sys
import tempfile
import time
from typing import Optional

from ..config import compile_cache_dir, config, logger
from ..observability import tracing
from ..observability.catalog import IMAGE_BUILD_SECONDS
from ..proto import api_pb2
from .._utils.grpc_utils import create_channel, retry_transient_errors
from ..proto.rpc import ModalTPUStub


class TpuProbeError(RuntimeError):
    """The chip probe failed on a host that was not forced to the CPU."""


_PROBE_CODE = "import jax; d = jax.devices(); print('PROBE', len(d), d[0].platform, d[0].device_kind, sep='|')"


def probe_jax_devices(timeout_s: float = 120.0) -> tuple[int, str, str]:
    """(count, platform, device_kind) of `jax.devices()`, asked of a child
    process so the caller never holds a chip: a process that has initialized
    the TPU backend owns every chip it can see until it exits, and the child
    has exited when this returns. Raises TpuProbeError with the child's own
    error (for a chip in use, libtpu names the pid that holds it)."""
    import subprocess

    try:
        out = subprocess.run(
            [sys.executable, "-c", _PROBE_CODE], capture_output=True, timeout=timeout_s, text=True
        )
    except subprocess.TimeoutExpired as exc:
        raise TpuProbeError(
            f"device probe (`import jax; jax.devices()` in a child process) did not finish in {timeout_s:.0f} s"
        ) from exc
    line = next((ln for ln in out.stdout.splitlines() if ln.startswith("PROBE|")), None)
    if out.returncode != 0 or line is None:
        raise TpuProbeError(
            f"device probe failed (exit {out.returncode}). Probe stderr ends:\n{out.stderr.strip()[-1500:]}"
        )
    _tag, count, platform, kind = line.split("|")
    return int(count), platform, kind


def detect_tpu_inventory() -> tuple[str, int, str]:
    """(tpu_type, num_chips, topology) for this host. Env overrides let tests
    simulate multi-chip hosts. Blocks for as long as a JAX start-up takes:
    call it off the event loop.

    A host forced to the CPU, or one where JAX finds only the CPU, honestly
    has no chips. A probe that fails or hangs is different: reporting zero
    chips there would leave every `tpu=` function queued for ever, so the
    probe's error propagates (set JAX_PLATFORMS=cpu for a host without
    chips)."""
    env_type = os.environ.get("MODAL_TPU_WORKER_TPU_TYPE")
    if env_type is not None:
        return env_type, int(os.environ.get("MODAL_TPU_WORKER_NUM_CHIPS", "0")), os.environ.get(
            "MODAL_TPU_WORKER_TOPOLOGY", ""
        )
    if os.environ.get("MODAL_TPU_JAX_PLATFORM") == "cpu" or os.environ.get("JAX_PLATFORMS") == "cpu":
        return "", 0, ""
    count, platform, kind = probe_jax_devices()
    if platform != "tpu":
        return "", 0, ""
    return kind, count, os.environ.get("TPU_TOPOLOGY", "")


async def chips_per_worker(num_workers: int, worker_chips: Optional[int]) -> Optional[int]:
    """The chip count to start each of `num_workers` same-host workers with.
    One worker, or an explicit count (argument or the simulation env
    override), passes through (None = the worker probes for itself). Several
    workers left to probe would each find, and each claim, every chip of the
    host: so probe once here — no chips means 0 for all of them, chips mean
    an error."""
    explicit = worker_chips is not None or "MODAL_TPU_WORKER_TPU_TYPE" in os.environ
    if explicit or num_workers <= 1:
        return worker_chips
    _kind, found, _topology = await asyncio.to_thread(detect_tpu_inventory)
    if found:
        raise TpuProbeError(
            f"{num_workers} workers on one host would each claim all {found} of its chips; "
            "start one worker per host, or pass worker_chips to divide them"
        )
    return 0


# x,y,z box of chips one process owns, for TPU_CHIPS_PER_PROCESS_BOUNDS. On a
# v5e 2x2 host chip ids run x-first: 0=(0,0) 1=(1,0) 2=(0,1) 3=(1,1). One chip
# and all four were run (my chip run, PR 21); two chips (ids 0,1 or 2,3, as
# the scheduler hands them out) follow from those coordinates but were not.
_CHIP_BOUNDS = {1: "1,1,1", 2: "2,1,1", 4: "2,2,1"}


def chip_bounds(n_chips: int) -> str:
    try:
        return _CHIP_BOUNDS[n_chips]
    except KeyError:
        raise ValueError(f"no known per-process chip layout for {n_chips} chips of one host") from None


def device_env(env: dict, chip_ids: list, tpu_type: str, world_size: int, jax_platform: str) -> bool:
    """Write into `env` which device a container may use — the one place that
    decides it — and return whether it was pinned to real chips. The rule: a
    container given chips runs on exactly those chips or fails; a container
    given none cannot take one.

    - `jax_platform == "cpu"` (tests, `MODAL_TPU_JAX_PLATFORM=cpu`): CPU, with
      the slice's chips simulated as host devices when the function asked
      for a TPU.
    - chips assigned: `JAX_PLATFORMS=tpu` alone, so a chip another process
      holds is a start-up error naming that pid, never a quiet fall-back to
      the CPU; the visible devices and the per-process bounds libtpu needs
      for one process on a subset of a host's chips.
    - no chips: `JAX_PLATFORMS=cpu` — inheriting the host's environment would
      let any function that imports JAX take every chip, unaccounted.
    """
    if jax_platform == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
        if tpu_type:
            from ..tpu_config import parse_tpu_config

            spec = parse_tpu_config(tpu_type)
            chips = spec.chips_per_host if world_size > 1 else spec.chips
            # replace (not append) any inherited device-count flag — XLA
            # honors the last occurrence
            inherited = [
                f
                for f in env.get("XLA_FLAGS", "").split()
                if not f.startswith("--xla_force_host_platform_device_count")
            ]
            env["XLA_FLAGS"] = " ".join(
                inherited + [f"--xla_force_host_platform_device_count={max(1, chips)}"]
            )
    elif chip_ids:
        env["JAX_PLATFORMS"] = jax_platform or "tpu"
        env["TPU_VISIBLE_DEVICES"] = ",".join(str(c) for c in chip_ids)
        env["TPU_PROCESS_BOUNDS"] = "1,1,1"
        env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = chip_bounds(len(chip_ids))
        return True
    else:
        env["JAX_PLATFORMS"] = "cpu"
    return False


class WorkerAgent:
    """Registers with the control plane, polls for assignments, runs
    container subprocesses, reports exits."""

    def __init__(
        self,
        server_url: str,
        worker_id: Optional[str] = None,
        num_chips: Optional[int] = None,
        tpu_type: Optional[str] = None,
        state_dir: Optional[str] = None,
        region: Optional[str] = None,
        zone: Optional[str] = None,
        spot: Optional[bool] = None,
        instance_type: Optional[str] = None,
        slice_index: int = 0,
        chaos=None,  # ChaosPolicy: lifecycle faults + heartbeat blackhole
        server_uds: str = "",  # co-located control-plane Unix socket
        blob_local_dir: str = "",  # co-located blob store (path handoff)
        compile_cache_url: str = "",  # fleet compile store, HTTP leg (ISSUE 20)
    ):
        self.server_url = server_url
        # local fast-path coordinates (docs/DISPATCH.md): explicit from an
        # in-process supervisor, else env for a standalone co-located worker
        self.server_uds = server_uds or os.environ.get("MODAL_TPU_SERVER_UDS", "")
        self.blob_local_dir = blob_local_dir or os.environ.get("MODAL_TPU_BLOB_LOCAL_DIR", "")
        self.compile_cache_url = compile_cache_url or os.environ.get(
            "MODAL_TPU_COMPILE_CACHE_URL", ""
        )
        self.worker_id = worker_id or ""
        self._override_chips = num_chips
        self._override_type = tpu_type
        # placement labels: explicit args, else env (MODAL_TPU_WORKER_REGION
        # / _ZONE / _SPOT — how a fleet operator tags hosts)
        self.region = region if region is not None else config.get("worker_region")
        self.zone = zone if zone is not None else config.get("worker_zone")
        self.spot = spot if spot is not None else bool(config.get("worker_spot"))
        # which ICI domain (pod slice) this host belongs to: gangs with
        # require_single_slice are placed within one slice_index
        self.slice_index = slice_index
        self.instance_type = (
            instance_type if instance_type is not None else config.get("worker_instance_type")
        )
        self.state_dir = state_dir or config["state_dir"]
        self._procs: dict[str, asyncio.subprocess.Process] = {}
        # chip id -> the last container process pinned to it. The control
        # plane frees a task's chips on the container's OWN TaskResult, sent
        # just before the process exits, but libtpu lets go of a chip only at
        # exit: the next container for that chip waits for this process
        # (_await_chips_released), so accounting and device agree.
        self._chip_procs: dict[int, asyncio.subprocess.Process] = {}
        # task_id -> warm-pool entry serving it: stop events for these tasks
        # drain in-band (kill switch) instead of SIGTERM — the signal would
        # evict a reusable interpreter, and the stop escalation would SIGKILL
        # it AFTER it re-parked (pool procs outlive their tasks by design)
        self._pool_tasks: dict[str, object] = {}
        # task_id -> (cwd, env) of a running sandbox: sidecars launch into the
        # same filesystem/env (the local analogue of sharing the pod)
        self._sandbox_runtime: dict[str, tuple[str, dict]] = {}
        self._image_builder = None  # lazy ImageBuilder (created on first use)
        # stop events that raced ahead of their assignment (e.g. gang
        # rollback): the task is killed at/before registration instead of
        # booting on chips the scheduler already released. Bounded: stops for
        # long-gone tasks (reaper duplicates) would otherwise accumulate.
        self._early_stops: dict[str, None] = {}  # insertion-ordered set
        self._early_stops_max = 1024
        self._channel = None
        self._stub: Optional[ModalTPUStub] = None
        self.pool = None  # WarmPool, created in start() once the router is up
        self._tasks: list[asyncio.Task] = []
        self._escalations: set[asyncio.Task] = set()
        self._stopped = False
        self.chaos = chaos
        # preemption drain: announced to the control plane on the next
        # heartbeat; assignments that race the notice are preempt-signaled
        # as soon as they spawn (_run_task) instead of running unaware
        self.draining = False
        self._drain_grace_s = 10.0

    async def start(self) -> None:
        os.makedirs(os.path.join(self.state_dir, "tasks"), exist_ok=True)
        self._channel = create_channel(self.server_url)
        self._stub = ModalTPUStub(self._channel)
        # fast-path upgrade: an in-process supervisor (LocalSupervisor) is
        # reached directly; a co-located one over its Unix socket
        from .._utils import local_transport

        if local_transport.fastpath_enabled():
            uds_ok = (
                local_transport.uds_enabled()
                and local_transport.usable_uds_path(self.server_uds)
                and os.path.exists(self.server_uds)
            )
            if uds_ok or local_transport.resolve_local_server(self.server_url) is not None:
                uds_stub = None
                if uds_ok:
                    self._uds_channel = create_channel(f"unix://{self.server_uds}")
                    uds_stub = ModalTPUStub(self._uds_channel)
                self._stub = local_transport.FastPathStub(
                    self.server_url,
                    self._stub,
                    uds_path=self.server_uds if uds_ok else "",
                    uds_stub=uds_stub,
                )
        if self._override_chips is not None:
            tpu_type, num_chips, topology = "", self._override_chips, ""
        else:
            # off the loop: the probe is a whole JAX start-up in a child
            tpu_type, num_chips, topology = await asyncio.to_thread(detect_tpu_inventory)
        if self._override_type is not None:
            tpu_type = self._override_type
        self._inventory = (tpu_type, num_chips, topology)
        # second data plane: the task command router clients dial directly
        # (reference task_command_router.proto — exec/stdio/FS on the worker)
        import grpc as _grpc

        from ..proto.rpc import build_router_handler
        from .task_router import TaskRouterServicer

        self.router = TaskRouterServicer()
        self._router_server = _grpc.aio.server()
        self._router_server.add_generic_rpc_handlers((build_router_handler(self.router),))
        router_port = self._router_server.add_insecure_port("127.0.0.1:0")
        await self._router_server.start()
        self.router_address = f"127.0.0.1:{router_port}"
        # warm pool: pre-forked parked interpreters served handoffs over the
        # router plane above (server/warm_pool.py, docs/COLDSTART.md)
        from .warm_pool import WarmPool

        self.pool = WarmPool(self)
        self.router.pool = self.pool
        await self.pool.start()
        await self._register()
        self._tasks.append(asyncio.create_task(self._poll_loop(), name=f"worker-poll-{self.worker_id}"))
        self._tasks.append(asyncio.create_task(self._heartbeat_loop(), name=f"worker-hb-{self.worker_id}"))
        logger.debug(f"worker {self.worker_id} registered ({num_chips} chips, type={tpu_type!r})")

    async def _register(self) -> None:
        """(Re-)announce this host to the control plane. Reused verbatim when
        a restarted control plane answers a heartbeat with `reannounce` or a
        poll with NOT_FOUND: the SAME worker_id is presented, so a journal-
        recovered WorkerState is replaced in place instead of colliding."""
        tpu_type, num_chips, topology = self._inventory
        resp = await retry_transient_errors(
            self._stub.WorkerRegister,
            api_pb2.WorkerRegisterRequest(
                worker_id=self.worker_id,
                hostname=os.uname().nodename,
                tpu_type=tpu_type,
                num_chips=num_chips,
                topology=topology,
                milli_cpu=(os.cpu_count() or 1) * 1000,
                memory_mb=16384,
                container_address="127.0.0.1",
                router_address=self.router_address,
                slice_index=self.slice_index,
                region=self.region or "",
                zone=self.zone or "",
                spot=self.spot,
                instance_type=self.instance_type or "",
            ),
            max_retries=10,
            max_delay=2.0,
        )
        self.worker_id = resp.worker_id

    async def rehome(self, server_url: str, server_uds: str = "") -> None:
        """Point this agent at a NEW control plane (shard takeover,
        server/shards.py): the shard that owned this worker died and a
        surviving shard adopted its partition from the journal. Rebuild the
        channel/stub exactly like start() and re-announce under the SAME
        worker_id — the successor's journal-replayed WorkerState sits in
        adoption_pending, so the re-registration adopts it in place and
        in-flight maps resume on this worker without a fresh identity."""
        from .._utils import local_transport

        old_channels = [self._channel, getattr(self, "_uds_channel", None)]
        self.server_url = server_url
        self.server_uds = server_uds
        self._uds_channel = None
        self._channel = create_channel(self.server_url)
        self._stub = ModalTPUStub(self._channel)
        if local_transport.fastpath_enabled():
            uds_ok = (
                local_transport.uds_enabled()
                and local_transport.usable_uds_path(self.server_uds)
                and os.path.exists(self.server_uds)
            )
            if uds_ok or local_transport.resolve_local_server(self.server_url) is not None:
                uds_stub = None
                if uds_ok:
                    self._uds_channel = create_channel(f"unix://{self.server_uds}")
                    uds_stub = ModalTPUStub(self._uds_channel)
                self._stub = local_transport.FastPathStub(
                    self.server_url,
                    self._stub,
                    uds_path=self.server_uds if uds_ok else "",
                    uds_stub=uds_stub,
                )
        for ch in old_channels:
            if ch is not None:
                try:
                    await ch.close()
                except Exception:  # noqa: BLE001 — the old plane is dead anyway
                    pass
        await self._register()
        logger.warning(f"worker {self.worker_id} rehomed to {server_url}")

    async def stop(self) -> None:
        self._stopped = True
        for task in self._tasks:
            task.cancel()
        await asyncio.gather(*self._tasks, return_exceptions=True)
        if getattr(self, "pool", None) is not None:
            await self.pool.stop()
        for task_id, proc in list(self._procs.items()):
            await self._kill_proc(proc)
        if getattr(self, "router", None) is not None:
            await self.router.shutdown()
        if getattr(self, "_router_server", None) is not None:
            await self._router_server.stop(grace=0.2)
        if self._channel is not None:
            await self._channel.close()
        if getattr(self, "_uds_channel", None) is not None:
            await self._uds_channel.close()

    async def _kill_proc(self, proc: asyncio.subprocess.Process) -> None:
        if proc.returncode is None:
            try:
                proc.terminate()
                try:
                    await asyncio.wait_for(proc.wait(), timeout=5.0)
                except asyncio.TimeoutError:
                    proc.kill()
                    await proc.wait()
            except ProcessLookupError:
                pass

    async def _heartbeat_loop(self) -> None:
        while not self._stopped:
            try:
                resp = await retry_transient_errors(
                    self._stub.WorkerHeartbeat,
                    api_pb2.WorkerHeartbeatRequest(
                        worker_id=self.worker_id,
                        active_task_ids=list(self._procs.keys()),
                        draining=self.draining,
                        drain_grace_s=self._drain_grace_s if self.draining else 0.0,
                        warm_pool_ready=self.pool.ready_count() if self.pool is not None else 0,
                    ),
                    max_retries=2,
                )
                if resp.reannounce:
                    # the control plane restarted without our registration
                    # (e.g. journal disabled or record compacted away):
                    # re-register under the same id immediately
                    logger.warning(f"worker {self.worker_id} unknown to control plane; re-announcing")
                    await self._register()
            except Exception as exc:
                logger.warning(f"worker heartbeat failed: {exc}")
            await asyncio.sleep(5.0)

    # ------------------------------------------------------------------
    # Preemption lifecycle (TPU slices get preempted: the cloud sends the
    # host a termination notice with a grace window)
    # ------------------------------------------------------------------

    async def preempt(self, grace_s: float = 10.0) -> None:
        """Simulate/handle a preemption notice for this host.

        Order matters: the control plane must mark this worker's tasks
        preempted BEFORE any container exits — else an early TaskResult
        lands while `task.preempted` is False and the inputs burn retry
        budget instead of requeueing for free. So: (1) announce draining
        via an immediate heartbeat (the servicer enters scheduler drain
        state synchronously in the handler), (2) send each container the
        preempt signal (SIGUSR2 → checkpoint flush, then graceful exit),
        (3) escalate to SIGTERM/SIGKILL after the grace window."""
        if self.draining:
            return
        self.draining = True
        self._drain_grace_s = grace_s
        logger.warning(f"worker {self.worker_id} preempted (grace {grace_s}s); draining")
        if self.pool is not None:
            # parked interpreters hold no work: evict them immediately so the
            # host can terminate inside its grace window
            self.pool.drain()
        try:
            await retry_transient_errors(
                self._stub.WorkerHeartbeat,
                api_pb2.WorkerHeartbeatRequest(
                    worker_id=self.worker_id,
                    active_task_ids=list(self._procs.keys()),
                    draining=True,
                    drain_grace_s=grace_s,
                ),
                max_retries=3,
                max_delay=1.0,
            )
        except Exception as exc:
            logger.warning(f"preemption drain announce failed: {exc}")
        for task_id, proc in list(self._procs.items()):
            self._signal_preempt(task_id, proc, grace_s)

    def _signal_preempt(self, task_id: str, proc: asyncio.subprocess.Process, grace_s: float) -> None:
        """SIGUSR2 = preempt notice (the entrypoint's preempt hook flushes a
        checkpoint + resume token, then exits gracefully); SIGTERM at the
        grace deadline; SIGKILL 5s later for containers stuck in user code."""
        if proc.returncode is not None:
            return
        try:
            proc.send_signal(signal.SIGUSR2)
        except ProcessLookupError:
            return

        async def _escalate(p=proc, tid=task_id) -> None:
            try:
                await asyncio.wait_for(p.wait(), timeout=grace_s)
                return
            except asyncio.TimeoutError:
                logger.warning(f"task {tid} still running at preemption deadline; terminating")
            await self._kill_proc(p)

        esc = asyncio.create_task(_escalate())
        self._escalations.add(esc)
        esc.add_done_callback(self._escalations.discard)

    def kill_containers(self) -> None:
        """Chaos worker_kill event: SIGKILL every container on this host, no
        grace — models abrupt host loss (vs. preempt's graceful drain)."""
        for task_id, proc in list(self._procs.items()):
            if proc.returncode is None:
                try:
                    proc.kill()
                except ProcessLookupError:
                    pass
        if self.pool is not None:
            self.pool.kill_parked()

    async def _poll_loop(self) -> None:
        while not self._stopped:
            try:
                async for event in self._stub.WorkerPoll(
                    api_pb2.WorkerPollRequest(worker_id=self.worker_id)
                ):
                    which = event.WhichOneof("event_oneof")
                    if which == "assignment":
                        if event.assignment.sandbox_id:
                            asyncio.create_task(self._run_sandbox(event.assignment))
                        else:
                            asyncio.create_task(self._run_task(event.assignment))
                    elif which == "stop":
                        await self._stop_task(event.stop)
                    elif which == "sidecar":
                        asyncio.create_task(self._run_sidecar(event.sidecar))
                    elif event.HasField("pool_directive") and self.pool is not None:
                        # scheduler-driven warm-pool sizing (outside the
                        # event oneof — see api.proto PoolDirective)
                        self.pool.set_directive(
                            event.pool_directive.image_id, event.pool_directive.target
                        )
            except asyncio.CancelledError:
                return
            except Exception as exc:
                if self._stopped:
                    return
                import grpc as _grpc

                if (
                    isinstance(exc, _grpc.aio.AioRpcError)
                    and exc.code() == _grpc.StatusCode.NOT_FOUND
                ):
                    # restarted control plane doesn't know this worker id:
                    # re-announce (same id), then resume polling
                    try:
                        logger.warning(
                            f"worker {self.worker_id} poll NOT_FOUND; re-announcing to control plane"
                        )
                        await self._register()
                        continue
                    except Exception as reg_exc:  # noqa: BLE001
                        logger.warning(f"worker re-announce failed: {reg_exc}")
                logger.warning(f"worker poll stream broke ({exc}); reconnecting")
                await asyncio.sleep(0.5)

    async def _stop_task(self, stop: api_pb2.TaskStopEvent) -> None:
        if stop.sidecar_name:
            # sidecar stop: kill only the named auxiliary process. A stop
            # racing ahead of the spawn is recorded like main-task early
            # stops — _run_sidecar consumes it at/after registration.
            key = f"{stop.task_id}/sc/{stop.sidecar_name}"
            proc = self._procs.get(key)
            if proc is None:
                self._early_stops[key] = None
                while len(self._early_stops) > self._early_stops_max:
                    self._early_stops.pop(next(iter(self._early_stops)))
                return
            try:
                proc.kill()
            except ProcessLookupError:
                pass
            return
        proc = self._procs.get(stop.task_id)
        if proc is None:
            self._early_stops[stop.task_id] = None
            while len(self._early_stops) > self._early_stops_max:
                self._early_stops.pop(next(iter(self._early_stops)))
            return
        logger.debug(f"stopping task {stop.task_id}")
        pool_entry = self._pool_tasks.get(stop.task_id)
        if pool_entry is not None and not stop.force and not stop.preempt:
            # pooled placement: the control plane's task.terminate already
            # surfaces as a kill switch on the next FunctionGetInputs (the
            # input condition is notified), so the input loop drains and the
            # interpreter RE-PARKS. Escalate to SIGKILL only if the placement
            # doesn't end inside the grace window.
            grace = float(os.environ.get("MODAL_TPU_STOP_GRACE", "10"))

            async def _escalate_pool(e=pool_entry, p=proc, task_id=stop.task_id) -> None:
                try:
                    if e.task_done is not None:
                        await asyncio.wait_for(asyncio.shield(e.task_done), timeout=grace)
                except (asyncio.TimeoutError, asyncio.CancelledError):
                    logger.warning(f"pooled task {task_id} ignored kill switch for {grace}s; killing")
                    try:
                        p.kill()
                    except ProcessLookupError:
                        pass

            esc = asyncio.create_task(_escalate_pool())
            self._escalations.add(esc)
            esc.add_done_callback(self._escalations.discard)
            return
        if stop.preempt and not stop.force:
            # scheduler-initiated preemption (e.g. a gang peer's host is
            # draining): give the container its checkpoint-flush window
            self._signal_preempt(stop.task_id, proc, stop.grace_s or 10.0)
            return
        if stop.force:
            proc.kill()
        else:
            try:
                proc.terminate()
            except ProcessLookupError:
                return
            # escalate: a container stuck in user code (native collective,
            # non-cancellable thread) must still die so e.g. a replacement
            # gang can schedule — SIGKILL after the grace window
            grace = float(os.environ.get("MODAL_TPU_STOP_GRACE", "10"))

            async def _escalate(p=proc, task_id=stop.task_id) -> None:
                try:
                    await asyncio.wait_for(p.wait(), timeout=grace)
                except asyncio.TimeoutError:
                    logger.warning(f"task {task_id} ignored SIGTERM for {grace}s; killing")
                    try:
                        p.kill()
                    except ProcessLookupError:
                        pass

            # strong reference: a bare create_task could be GC'd mid-grace
            # and the SIGKILL would never fire
            esc = asyncio.create_task(_escalate())
            self._escalations.add(esc)
            esc.add_done_callback(self._escalations.discard)

    async def _materialize_image(self, image_id: str):
        """Build (or reuse) the task's image; returns BuiltImage or None for
        trivial chains (host venv). Raises ImageBuildError on failure."""
        from .image_builder import get_image_builder

        if self._image_builder is None:
            self._image_builder = get_image_builder(self.state_dir)
        return await self._image_builder.materialize(self._stub, image_id)

    async def _prepare_image(self, task_id: str, image_id: str, env: dict, trace_context: str = ""):
        """Materialize the image and fold its env/PATH/rootfs into `env`.
        Returns (ok, built): on build failure reports INIT_FAILURE and
        returns (False, None) — shared by the function and sandbox paths."""
        if not image_id:
            return True, None
        t_build0 = time.time()
        try:
            built = await self._materialize_image(image_id)
            IMAGE_BUILD_SECONDS.observe(time.time() - t_build0)
            tracing.record_span(
                "image.build",
                start=t_build0,
                end=time.time(),
                parent=tracing.parse_context(trace_context),
                attrs={"task_id": task_id, "image_id": image_id},
            )
        except Exception as exc:
            logger.warning(f"image build failed for task {task_id}: {exc}")
            try:
                await retry_transient_errors(
                    self._stub.TaskResult,
                    api_pb2.TaskResultRequest(
                        task_id=task_id,
                        result=api_pb2.GenericResult(
                            status=api_pb2.GENERIC_STATUS_INIT_FAILURE,
                            exception=f"image build failed: {exc}",
                        ),
                    ),
                    max_retries=2,
                )
            except Exception as report_exc:
                logger.warning(f"failed reporting image build failure: {report_exc}")
            return False, None
        if built is not None:
            env.update(built.env)
            env["MODAL_TPU_IMAGE_ROOT"] = built.rootfs
            env["PATH"] = os.path.dirname(built.python_bin) + os.pathsep + env.get("PATH", "")
        return True, built

    def _compile_cache_env(self) -> dict[str, str]:
        """Fleet compile-cache coordinates a container (or parked pool
        interpreter) should inherit (ISSUE 20, docs/COLDSTART.md): the
        co-located store dir — a sibling of the blob store under the
        supervisor state dir, stat-verified container-side like the blob
        fast path — plus the HTTP url for fetch-on-miss/evict. Empty dict
        when nothing is configured (remote worker with no coordinates)."""
        out: dict[str, str] = {}
        # Key normalization must be env-level and unconditional: the prewarm
        # bake clears the GPU autotune-dir debug option (it hashes an absolute
        # local path into every cache key), and a container that compiles
        # before install_fleet_cache() runs would otherwise mint divergent
        # keys and miss every baked entry. Applied via setdefault — an
        # explicit user value wins (see compile_client.normalize_cache_keys).
        out["JAX_PERSISTENT_CACHE_ENABLE_XLA_CACHES"] = ""
        if self.blob_local_dir:
            cache_dir = os.path.join(
                os.path.dirname(os.path.abspath(self.blob_local_dir)), "compile_cache"
            )
            if os.path.isdir(cache_dir):
                out["MODAL_TPU_COMPILE_CACHE_DIR"] = cache_dir
        if self.compile_cache_url:
            out["MODAL_TPU_COMPILE_CACHE_URL"] = self.compile_cache_url
            # same blob plane carries KV-page shipments for serving engines
            # with no shared fs (serving/api.py handle_prefill)
            out["MODAL_TPU_KV_SHIP_URL"] = self.compile_cache_url
        return out

    async def _await_chips_released(self, chip_ids: list, grace_s: float = 30.0) -> None:
        """Before a container is pinned to `chip_ids`: wait for the previous
        process on each to be gone. One that outlives the grace has been
        reported finished (that is why its chips were reassigned) and is
        killed — it holds a chip the scheduler gave to someone else."""
        for chip in chip_ids:
            prev = self._chip_procs.get(chip)
            if prev is None or prev.returncode is not None:
                continue
            try:
                await asyncio.wait_for(prev.wait(), timeout=grace_s)
            except asyncio.TimeoutError:
                logger.warning(f"pid {prev.pid} still holds reassigned chip {chip}; killing it")
                try:
                    prev.kill()
                except ProcessLookupError:
                    pass
                await prev.wait()

    def _consume_early_stop(self, task_id: str) -> bool:
        """True if a stop for this task arrived before it was registered."""
        if task_id in self._early_stops:
            self._early_stops.pop(task_id)
            return True
        return False

    async def _report_never_started(self, task_id: str) -> None:
        """TaskResult for a task stopped before launch — the server's result
        handler releases its chips/bookkeeping (nothing else will: the
        container never boots, never heartbeats, so the reaper won't see it)."""
        try:
            await retry_transient_errors(
                self._stub.TaskResult,
                api_pb2.TaskResultRequest(
                    task_id=task_id,
                    result=api_pb2.GenericResult(
                        status=api_pb2.GENERIC_STATUS_TERMINATED,
                        exception="stopped before container start",
                    ),
                ),
                max_retries=2,
            )
        except Exception as exc:
            logger.warning(f"failed reporting never-started task {task_id}: {exc}")

    async def _run_sidecar(self, event: api_pb2.SidecarLaunchEvent) -> None:
        """Launch a sandbox sidecar (reference sandbox.py:2157): an auxiliary
        process sharing the sandbox's working directory and base env, with its
        own command/env/image. Its stdout/stderr stream into the sandbox's
        logs tagged by fd, and its exit is reported via SandboxSidecarExit."""
        task_id = event.task_id
        sc = event.sidecar
        # the launch event can race the sandbox's own boot — including image
        # materialization, which can take minutes — so the wait window must
        # cover a full image build, not just process spawn
        key = f"{task_id}/sc/{sc.name}"
        runtime = None
        boot_deadline = time.monotonic() + float(
            os.environ.get("MODAL_TPU_SIDECAR_BOOT_WAIT", "600")
        )
        while time.monotonic() < boot_deadline:
            if self._consume_early_stop(key):
                await retry_transient_errors(
                    self._stub.SandboxSidecarExit,
                    api_pb2.SandboxSidecarExitRequest(task_id=task_id, name=sc.name, returncode=-1),
                    max_retries=2,
                )
                return
            runtime = self._sandbox_runtime.get(task_id)
            if runtime is not None:
                break
            await asyncio.sleep(0.2)
        if runtime is None:
            await retry_transient_errors(
                self._stub.SandboxSidecarExit,
                api_pb2.SandboxSidecarExitRequest(task_id=task_id, name=sc.name, returncode=-1),
                max_retries=2,
            )
            return
        cwd, base_env = runtime
        env = dict(base_env)
        if sc.image_id:
            # NOT _prepare_image: its failure path reports TaskResult
            # INIT_FAILURE for the whole task, which would kill the main
            # sandbox over a sidecar-only image problem
            try:
                built = await self._materialize_image(sc.image_id)
                if built is not None:
                    env.update(built.env)
                    env["MODAL_TPU_IMAGE_ROOT"] = built.rootfs
                    env["PATH"] = os.path.dirname(built.python_bin) + os.pathsep + env.get("PATH", "")
            except Exception as exc:  # noqa: BLE001
                logger.warning(f"sidecar {sc.name!r} image build failed: {exc}")
                await retry_transient_errors(
                    self._stub.SandboxSidecarExit,
                    api_pb2.SandboxSidecarExitRequest(task_id=task_id, name=sc.name, returncode=-1),
                    max_retries=2,
                )
                return
        env.update(dict(sc.env))
        env["JAX_COMPILATION_CACHE_DIR"] = compile_cache_dir()
        try:
            proc = await asyncio.create_subprocess_exec(
                *sc.entrypoint_args,
                stdout=asyncio.subprocess.PIPE,
                stderr=asyncio.subprocess.PIPE,
                cwd=cwd,
                env=env,
            )
        except Exception as exc:  # noqa: BLE001 — reported as exit -1
            logger.warning(f"sidecar {sc.name!r} failed to spawn: {exc}")
            await retry_transient_errors(
                self._stub.SandboxSidecarExit,
                api_pb2.SandboxSidecarExitRequest(task_id=task_id, name=sc.name, returncode=-1),
                max_retries=2,
            )
            return
        self._procs[key] = proc
        if self._consume_early_stop(key):  # stop raced in during spawn
            proc.kill()

        async def _pump(stream, fd: int) -> None:
            while True:
                data = await stream.read(64 * 1024)
                if not data:
                    return
                try:
                    await self._stub.ContainerLog(
                        api_pb2.ContainerLogRequest(
                            task_id=task_id,
                            logs=[
                                api_pb2.TaskLogs(
                                    data=f"[{sc.name}] " + data.decode("utf-8", "replace"),
                                    task_id=task_id,
                                    file_descriptor=fd,
                                    timestamp=time.time(),
                                )
                            ],
                        ),
                        timeout=10.0,
                    )
                except Exception:
                    pass

        pumps = [
            asyncio.create_task(_pump(proc.stdout, 1)),
            asyncio.create_task(_pump(proc.stderr, 2)),
        ]
        try:
            returncode = await proc.wait()
        finally:
            self._procs.pop(key, None)
            for p in pumps:
                p.cancel()
        try:
            await retry_transient_errors(
                self._stub.SandboxSidecarExit,
                api_pb2.SandboxSidecarExitRequest(
                    task_id=task_id, name=sc.name, returncode=returncode
                ),
                max_retries=2,
            )
        except Exception:
            pass

    async def _run_sandbox(self, assignment: api_pb2.TaskAssignment) -> None:
        """Run a sandbox command as a supervised subprocess: stdin drained
        from the control plane, stdout/stderr streamed back as logs."""
        task_id = assignment.task_id
        if self._consume_early_stop(task_id):
            await self._report_never_started(task_id)
            return
        sandbox_id = assignment.sandbox_id
        d = assignment.sandbox_def
        env = dict(os.environ)
        ok, built_image = await self._prepare_image(task_id, d.image_id, env)
        if not ok:
            return
        # Dedicated per-task workdir (unless explicit): makes fs snapshots
        # capture exactly this sandbox's files, and gives snapshot-images a
        # place to seed their content into
        from .fs_snapshot import sandbox_workdir

        sandbox_cwd = d.workdir or (built_image.workdir if built_image else "") or ""
        if not sandbox_cwd:
            sandbox_cwd = sandbox_workdir(self.state_dir, task_id, "")
            os.makedirs(sandbox_cwd, exist_ok=True)
        if built_image is not None and built_image.fs_seed_dir:
            # snapshot-image: the sandbox starts on a COPY of the snapshot
            # content (each restored sandbox gets its own mutable tree)
            try:
                await asyncio.to_thread(
                    shutil.copytree,
                    built_image.fs_seed_dir,
                    sandbox_cwd,
                    dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns(".complete"),
                )
            except Exception as exc:
                await retry_transient_errors(
                    self._stub.TaskResult,
                    api_pb2.TaskResultRequest(
                        task_id=task_id,
                        result=api_pb2.GenericResult(
                            status=api_pb2.GENERIC_STATUS_INIT_FAILURE,
                            exception=f"snapshot restore failed: {exc}",
                        ),
                    ),
                    max_retries=2,
                )
                return
        # secrets are resolved control-plane-side into the assignment env
        env.update(dict(assignment.container_arguments.env))
        device_env(
            env,
            list(assignment.tpu_chip_ids),
            d.resources.tpu_config.tpu_type,
            1,
            config["jax_platform"],
        )
        env["JAX_COMPILATION_CACHE_DIR"] = compile_cache_dir()
        try:
            await retry_transient_errors(
                self._stub.ContainerHello,
                api_pb2.ContainerHelloRequest(task_id=task_id, sandbox_workdir=sandbox_cwd),
                max_retries=3,
            )
            proc = await asyncio.create_subprocess_exec(
                *d.entrypoint_args,
                stdin=asyncio.subprocess.PIPE,
                stdout=asyncio.subprocess.PIPE,
                stderr=asyncio.subprocess.PIPE,
                cwd=sandbox_cwd,
                env=env,
            )
        except Exception as exc:
            await retry_transient_errors(
                self._stub.TaskResult,
                api_pb2.TaskResultRequest(
                    task_id=task_id,
                    result=api_pb2.GenericResult(
                        status=api_pb2.GENERIC_STATUS_INIT_FAILURE, exception=repr(exc)
                    ),
                ),
                max_retries=2,
            )
            return
        self._procs[task_id] = proc
        if self._consume_early_stop(task_id):  # stop raced in during spawn
            proc.kill()
        self._sandbox_runtime[task_id] = (sandbox_cwd or os.getcwd(), env)
        self.router.register_task(task_id, env, sandbox_cwd or os.getcwd(), token=assignment.router_token)

        async def _heartbeat() -> None:
            # sandboxes heartbeat like function containers so the reaper
            # doesn't kill long-running commands
            while proc.returncode is None:
                try:
                    await retry_transient_errors(
                        self._stub.ContainerHeartbeat,
                        api_pb2.ContainerHeartbeatRequest(task_id=task_id),
                        max_retries=1,
                        attempt_timeout=10.0,
                    )
                except Exception:
                    pass
                await asyncio.sleep(10.0)

        async def _pump_stdin() -> None:
            offset = 0
            try:
                while proc.returncode is None:
                    resp = await retry_transient_errors(
                        self._stub.SandboxGetStdin,
                        api_pb2.SandboxGetStdinRequest(sandbox_id=sandbox_id, offset=offset, timeout=5.0),
                        attempt_timeout=15.0,
                        max_retries=8,
                    )
                    for chunk in resp.chunks:
                        proc.stdin.write(chunk)
                        await proc.stdin.drain()
                    offset = resp.next_offset
                    if resp.eof:
                        proc.stdin.close()
                        return
            except asyncio.CancelledError:
                raise
            except Exception as exc:
                # stdin channel lost: close the pipe so readers see EOF
                # instead of blocking to the sandbox timeout
                logger.warning(f"sandbox {sandbox_id} stdin pump failed: {exc}")
                try:
                    proc.stdin.close()
                except Exception:
                    pass

        async def _pump_out(stream, fd: int) -> None:
            import codecs

            # incremental decoder: a multi-byte UTF-8 char split across 64KB
            # reads must not become U+FFFD
            decoder = codecs.getincrementaldecoder("utf-8")(errors="replace")
            while True:
                data = await stream.read(64 * 1024)
                text = decoder.decode(data, final=not data)
                if not data and not text:
                    return
                if not text:
                    continue
                try:
                    await self._stub.ContainerLog(
                        api_pb2.ContainerLogRequest(
                            task_id=task_id,
                            logs=[
                                api_pb2.TaskLogs(
                                    data=text,
                                    task_id=task_id,
                                    file_descriptor=fd,
                                    timestamp=time.time(),
                                )
                            ],
                        ),
                        timeout=10.0,
                    )
                except Exception:
                    pass
                if not data:
                    return

        tunnel_servers: list[asyncio.AbstractServer] = []

        async def _open_tunnels() -> None:
            """One TCP proxy listener per open port: client connects to the
            tunnel port, bytes are piped to the sandbox's own port. This IS
            the data plane (not a stub) — production would front the same
            proxy with TLS (reference _tunnel.py / sandbox.py:1930)."""
            tunnels = []
            for spec in d.open_ports:
                target_port = spec.port

                def make_handler(tp):
                    async def handle(reader, writer):
                        try:
                            up_r, up_w = await asyncio.open_connection("127.0.0.1", tp)
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

                    return handle

                server = await asyncio.start_server(make_handler(target_port), "127.0.0.1", 0)
                tunnel_servers.append(server)
                port = server.sockets[0].getsockname()[1]
                tunnels.append(
                    api_pb2.TunnelData(
                        container_port=target_port,
                        host="127.0.0.1",
                        port=port,
                        unencrypted=spec.unencrypted,
                    )
                )
            await retry_transient_errors(
                self._stub.TaskTunnelsUpdate,
                api_pb2.TaskTunnelsUpdateRequest(task_id=task_id, tunnels=tunnels),
                max_retries=3,
            )

        async def _readiness_probe() -> None:
            probe = d.readiness_probe
            if not probe.exec_command:
                return
            period = probe.period_secs or 1.0
            deadline = time.monotonic() + (probe.timeout_secs or d.timeout_secs or 600)
            while proc.returncode is None and time.monotonic() < deadline:
                try:
                    p = await asyncio.create_subprocess_exec(
                        *probe.exec_command,
                        cwd=sandbox_cwd,
                        env=env,
                        stdout=asyncio.subprocess.DEVNULL,
                        stderr=asyncio.subprocess.DEVNULL,
                    )
                    rc = await asyncio.wait_for(p.wait(), timeout=max(period * 5, 10.0))
                except (asyncio.TimeoutError, OSError):
                    rc = -1
                if rc == 0:
                    await retry_transient_errors(
                        self._stub.TaskReady, api_pb2.TaskReadyRequest(task_id=task_id), max_retries=3
                    )
                    return
                await asyncio.sleep(period)

        stdin_task = asyncio.create_task(_pump_stdin())
        hb_task = asyncio.create_task(_heartbeat())
        out_task = asyncio.create_task(_pump_out(proc.stdout, 1))
        err_task = asyncio.create_task(_pump_out(proc.stderr, 2))
        aux_tasks = []
        if d.open_ports:
            aux_tasks.append(asyncio.create_task(_open_tunnels()))
        if d.readiness_probe.exec_command:
            aux_tasks.append(asyncio.create_task(_readiness_probe()))
        else:
            # no probe configured: the sandbox is "ready" once running
            aux_tasks.append(
                asyncio.create_task(
                    retry_transient_errors(
                        self._stub.TaskReady, api_pb2.TaskReadyRequest(task_id=task_id), max_retries=3
                    )
                )
            )
        timeout_s = d.timeout_secs or 600
        try:
            returncode = await asyncio.wait_for(proc.wait(), timeout=timeout_s)
            if returncode == 0:
                status = api_pb2.GENERIC_STATUS_SUCCESS
                exception = ""
            elif returncode < 0:
                # killed by signal (terminate/stop event): TERMINATED, so the
                # client's SandboxTerminatedError contract holds
                status = api_pb2.GENERIC_STATUS_TERMINATED
                exception = f"terminated by signal {-returncode}"
            else:
                status = api_pb2.GENERIC_STATUS_FAILURE
                exception = f"exit code {returncode}"
        except asyncio.TimeoutError:
            proc.kill()
            await proc.wait()
            returncode = -1
            status = api_pb2.GENERIC_STATUS_TIMEOUT
            exception = f"sandbox exceeded timeout of {timeout_s}s"
        finally:
            self._procs.pop(task_id, None)
            self._sandbox_runtime.pop(task_id, None)
            # sidecars share the sandbox's lifecycle: main container exit
            # tears them down too (reference sidecar semantics)
            for key, sc_proc in list(self._procs.items()):
                if key.startswith(f"{task_id}/sc/"):
                    try:
                        sc_proc.kill()
                    except ProcessLookupError:
                        pass
            self.router.unregister_task(task_id)
            stdin_task.cancel()
            hb_task.cancel()
            for t in aux_tasks:
                t.cancel()
            for server in tunnel_servers:
                server.close()
            await asyncio.gather(stdin_task, hb_task, *aux_tasks, return_exceptions=True)
            await asyncio.gather(out_task, err_task, return_exceptions=True)
        result = api_pb2.GenericResult(status=status, exception=exception)
        result.data = str(returncode).encode()
        try:
            await retry_transient_errors(
                self._stub.TaskResult,
                api_pb2.TaskResultRequest(task_id=task_id, result=result),
                max_retries=3,
            )
        except Exception as exc:
            logger.warning(f"sandbox result report failed: {exc}")

    async def _run_task(self, assignment: api_pb2.TaskAssignment) -> None:
        task_id = assignment.task_id
        t_launch0 = time.time()
        if self._consume_early_stop(task_id):
            logger.debug(f"task {task_id} stopped before start; not launching")
            await self._report_never_started(task_id)
            return
        args = assignment.container_arguments
        args.server_url = self.server_url
        task_dir = os.path.join(self.state_dir, "tasks", task_id)
        os.makedirs(task_dir, exist_ok=True)
        args_path = os.path.join(task_dir, "container_arguments.pb")
        with open(args_path, "wb") as f:
            f.write(args.SerializeToString())

        # materialize the function's image (content-addressed venv; cached).
        # Failures are loud: the task reports INIT_FAILURE with the build log
        # tail instead of silently running the host venv (round-1 behavior).
        env = dict(os.environ)
        task_trace_ctx = args.env.get(tracing.TRACE_CONTEXT_ENV, "")
        ok, built_image = await self._prepare_image(
            task_id, args.function_def.image_id, env, trace_context=task_trace_ctx
        )
        if not ok:
            return
        env.update(dict(args.env))
        env["MODAL_TPU_CONTAINER_ARGS_PATH"] = args_path
        # container boot spans start the clock at the worker's spawn decision,
        # and the container adopts this supervisor's span sink explicitly
        # (observability/tracing.py)
        env[tracing.TRACE_T0_ENV] = str(t_launch0)
        if tracing.trace_dir():
            env[tracing.TRACE_DIR_ENV] = tracing.trace_dir()
        # profiling sink (observability/profiler.py): where this container
        # drops its folded-stack files — both for the MODAL_TPU_PROFILE env
        # toggle (inherited via os.environ above) and the runtime
        # profile_command delivered on its heartbeats
        env.setdefault(
            "MODAL_TPU_PROFILE_DIR",
            os.path.join(self.state_dir, "observability", "profiles"),
        )
        env["MODAL_TPU_SERVER_URL"] = self.server_url
        # containers inherit the worker's local fast-path coordinates (they
        # never call ClientHello): the control-plane Unix socket and the
        # on-disk blob store, both stat-verified container-side before use
        if self.server_uds:
            env["MODAL_TPU_SERVER_UDS"] = self.server_uds
        if self.blob_local_dir:
            env["MODAL_TPU_BLOB_LOCAL_DIR"] = self.blob_local_dir
        # fleet compile cache (ISSUE 20): co-located containers read the
        # supervisor's store in place (zero HTTP bytes); the URL is the
        # remote leg and the eviction channel
        for key, value in self._compile_cache_env().items():
            env.setdefault(key, value)
        env["MODAL_TPU_TASK_ID"] = task_id
        env["MODAL_TPU_TASK_DIR"] = task_dir
        if config.get("import_trace"):  # env: MODAL_TPU_IMPORT_TRACE
            # per-module import timings land next to the task's logs
            env["MODAL_TPU_TELEMETRY_PATH"] = os.path.join(task_dir, "imports.jsonl")
        # sys.path propagation for "file"-defined functions
        globals_path = args.function_def.experimental_options.get("globals_path", "")
        if globals_path:
            env["PYTHONPATH"] = globals_path + os.pathsep + env.get("PYTHONPATH", "")
        # repo root so `modal_tpu` imports inside the bare subprocess
        pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        env["PYTHONPATH"] = pkg_root + os.pathsep + env.get("PYTHONPATH", "")
        holds_chips = device_env(
            env,
            list(assignment.tpu_chip_ids),
            args.function_def.resources.tpu_config.tpu_type,
            args.world_size,
            config["jax_platform"],
        )
        env["JAX_COMPILATION_CACHE_DIR"] = compile_cache_dir()

        stdout_path = os.path.join(task_dir, "stdout.log")
        stderr_path = os.path.join(task_dir, "stderr.log")
        container_python = built_image.python_bin if built_image is not None else sys.executable
        container_cwd = (built_image.workdir if built_image is not None else "") or globals_path or None

        # Warm-pool handoff first (server/warm_pool.py): a parked interpreter
        # matching this task's image/platform takes the placement in-process —
        # no exec, no imports. Device-count flags apply at adoption (jax is
        # imported but no backend is initialized while parked). Gangs are
        # excluded: jax.distributed state must never leak across
        # placements. So are placements with real chips (holds_chips): an
        # interpreter that has initialized the TPU backend holds its chips
        # until it exits, so one that re-parked would keep them after the
        # scheduler freed them, and the next placement there — the same
        # interpreter with other chips assigned, or a fresh spawn — would run
        # on the wrong chip or fail "already in use". One process per chip,
        # ended with its task, keeps chips_in_use true. Any failure falls
        # back to the fresh spawn below.
        pool_entry = None
        err_offset = 0
        if (
            self.pool is not None
            and not self.draining
            and not holds_chips
            and args.world_size <= 1
            and (args.function_def.group_size or 0) <= 1
        ):
            # trivial image chains materialize to the host venv: their
            # placements match the host-venv ("") pool key
            effective_image = args.function_def.image_id if built_image is not None else ""
            pool_entry = await self.pool.adopt(
                effective_image, env, task_id, args_path, cwd=container_cwd or ""
            )
        if pool_entry is not None:
            proc = pool_entry.proc
            stdout_path, stderr_path = pool_entry.stdout_path, pool_entry.stderr_path
            try:
                out_offset = os.path.getsize(stdout_path)
                err_offset = os.path.getsize(stderr_path)
            except OSError:
                out_offset = err_offset = 0
            tracing.record_span(
                "coldstart.handoff",
                start=t_launch0,
                end=time.time(),
                parent=tracing.parse_context(task_trace_ctx),
                attrs={
                    "task_id": task_id,
                    "worker_id": self.worker_id,
                    "pool_id": pool_entry.pool_id,
                    "pid": proc.pid,
                    "generation": pool_entry.generation,
                    "image_id": args.function_def.image_id,
                },
            )
            logger.debug(
                f"task {task_id} handed to warm interpreter {pool_entry.pool_id} (pid={proc.pid})"
            )
        else:
            out_offset = 0
            if holds_chips:
                await self._await_chips_released(list(assignment.tpu_chip_ids))
            with open(stdout_path, "wb") as out_f, open(stderr_path, "wb") as err_f:
                proc = await asyncio.create_subprocess_exec(
                    container_python,
                    "-u",
                    "-m",
                    "modal_tpu.runtime.container_entrypoint",
                    env=env,
                    stdout=out_f,
                    stderr=err_f,
                    cwd=container_cwd,
                )
            if holds_chips:
                for chip in assignment.tpu_chip_ids:
                    self._chip_procs[chip] = proc
        self._procs[task_id] = proc
        if pool_entry is not None:
            self._pool_tasks[task_id] = pool_entry
        tracing.record_span(
            "worker.launch_task",
            start=t_launch0,
            end=time.time(),
            parent=tracing.parse_context(task_trace_ctx),
            attrs={
                "task_id": task_id,
                "worker_id": self.worker_id,
                "pid": proc.pid,
                "warm_pool_hit": pool_entry is not None,
            },
        )
        logger.debug(f"task {task_id} started pid={proc.pid}")
        if self._consume_early_stop(task_id):  # stop raced in during spawn
            proc.kill()
        elif self.draining:
            # assignment raced the preemption notice: preempt() only signals
            # procs that existed when it ran, so a late-spawned container
            # must get its own checkpoint-flush window before the drain
            # deadline force-reaps it
            self._signal_preempt(task_id, proc, self._drain_grace_s)
        self.router.register_task(task_id, env, container_cwd or os.getcwd(), token=assignment.router_token)
        tail_task = asyncio.create_task(
            self._stream_logs(
                task_id, stdout_path, stderr_path, proc,
                stdout_offset=out_offset, stderr_offset=err_offset,
            )
        )
        if pool_entry is not None:
            # resolved by the router when the interpreter re-parks (next
            # generation's PoolAwaitArguments) or by the pool watcher when
            # the process dies mid-serve
            try:
                outcome, returncode = await pool_entry.task_done
            except asyncio.CancelledError:
                outcome, returncode = "exited", -1
            if outcome == "reparked":
                returncode = 0
                # the process lives on: give the tailer one beat to flush the
                # final log bytes before detaching from the shared files
                await asyncio.sleep(0.25)
        else:
            returncode = await proc.wait()
        del self._procs[task_id]
        self._pool_tasks.pop(task_id, None)
        self.router.unregister_task(task_id)
        tail_task.cancel()
        try:
            await tail_task
        except asyncio.CancelledError:
            pass
        if returncode != 0:
            logger.warning(f"task {task_id} exited rc={returncode}")
            # report failure for containers that died before TaskResult
            try:
                with open(stderr_path, "rb") as f:
                    f.seek(max(err_offset, os.path.getsize(stderr_path) - 4096))
                    tail = f.read().decode(errors="replace")
                await retry_transient_errors(
                    self._stub.TaskResult,
                    api_pb2.TaskResultRequest(
                        task_id=task_id,
                        result=api_pb2.GenericResult(
                            status=api_pb2.GENERIC_STATUS_FAILURE,
                            exception=f"container exited with code {returncode}",
                            traceback=tail,
                        ),
                    ),
                    max_retries=2,
                )
            except Exception as exc:
                logger.warning(f"failed reporting task result: {exc}")
        else:
            try:
                await retry_transient_errors(
                    self._stub.TaskResult,
                    api_pb2.TaskResultRequest(
                        task_id=task_id,
                        result=api_pb2.GenericResult(status=api_pb2.GENERIC_STATUS_SUCCESS),
                    ),
                    max_retries=2,
                )
            except Exception:
                pass

    async def _stream_logs(
        self,
        task_id: str,
        stdout_path: str,
        stderr_path: str,
        proc: asyncio.subprocess.Process,
        stdout_offset: int = 0,
        stderr_offset: int = 0,
    ) -> None:
        """Tail container stdout/stderr into the control plane's app logs
        (client reads them via AppGetLogs). Non-zero offsets: warm-pool
        handoffs share the interpreter's log files across placements — tail
        only the bytes this task produced."""
        import codecs

        offsets = {stdout_path: stdout_offset, stderr_path: stderr_offset}
        fds = {stdout_path: 1, stderr_path: 2}
        decoders = {
            path: codecs.getincrementaldecoder("utf-8")(errors="replace") for path in offsets
        }
        while True:
            sent_any = False
            logs = []
            for path, off in offsets.items():
                try:
                    size = os.path.getsize(path)
                except OSError:
                    continue
                if size > off:
                    with open(path, "rb") as f:
                        f.seek(off)
                        data = f.read(64 * 1024)
                    offsets[path] = off + len(data)
                    text = decoders[path].decode(data)
                    if not text:
                        continue
                    logs.append(
                        api_pb2.TaskLogs(
                            data=text,
                            task_id=task_id,
                            file_descriptor=fds[path],
                            timestamp=time.time(),
                        )
                    )
                    sent_any = True
            if logs:
                try:
                    await retry_transient_errors(
                        self._stub.ContainerLog,
                        api_pb2.ContainerLogRequest(task_id=task_id, logs=logs),
                        max_retries=1,
                    )
                except Exception:
                    pass
            if proc.returncode is not None and not sent_any:
                return
            await asyncio.sleep(0.2 if not sent_any else 0.05)
