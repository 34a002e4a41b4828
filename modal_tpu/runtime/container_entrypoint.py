"""Container entrypoint: the process the worker execs.

Reference: py/modal/_container_entrypoint.py — `main` (:468), `run_function`
(:422), `call_function` (:114); bootstrap from ContainerArguments at
MODAL_CONTAINER_ARGUMENTS_PATH (:475-490); clustered init hook (:451-457).

TPU-first: for gang functions this is where `jax.distributed.initialize` runs
— BEFORE user code imports jax — using rank/coordinator from the
TaskClusterHello rendezvous (replacing the reference's i6pn/NCCL env
bootstrap, _clustered_functions.py:41-83). The persistent XLA compilation
cache is enabled here so warm restarts skip compilation (the TPU analogue of
the reference's CRIU memory snapshots for cold-start elimination).
"""

from __future__ import annotations

import asyncio
import inspect
import os
import sys
import time
import traceback
from typing import Any, Optional

# import tracing hooks in FIRST so the heavy imports below are attributed
# (reference _container_entrypoint.py:12-16)
from .telemetry import maybe_instrument_from_env

maybe_instrument_from_env()

# distributed tracing: adopt the worker-exported span sink before anything
# else runs, so boot/import spans land in the supervisor's trace store
from ..observability import tracing

tracing.maybe_configure_from_env()

from ..client import _Client
from ..config import compile_cache_dir, config, logger, tune_switch_interval
from ..exception import ExecutionError
from ..proto import api_pb2
from .._utils.grpc_utils import retry_transient_errors
from ..serialization import deserialize
from . import execution_context
from .io_manager import ContainerIOManager, IOContext
from .user_code import Service, import_class_service, import_single_function_service


# Warm-pool serving (server/warm_pool.py): True while this process runs a
# placement it received by handoff instead of a fresh exec — echoed on
# ContainerHello so the control plane can stamp the task's timeline.
_WARM_POOL_SERVE = False


def load_container_arguments() -> api_pb2.ContainerArguments:
    path = os.environ.get("MODAL_TPU_CONTAINER_ARGS_PATH")
    if not path:
        raise ExecutionError("MODAL_TPU_CONTAINER_ARGS_PATH not set — not a container environment")
    with open(path, "rb") as f:
        return api_pb2.ContainerArguments.FromString(f.read())


def setup_compilation_cache() -> None:
    """Persistent XLA compilation cache: compiled executables survive across
    container restarts (cold-start elimination, SURVEY §7 hard part 2). The
    worker has already pinned the directory into this process's env
    (config.compile_cache_dir); a container started by hand gets the same."""
    cache_dir = compile_cache_dir()
    os.makedirs(cache_dir, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")


async def initialize_clustered(container_args: api_pb2.ContainerArguments, client: _Client) -> Optional[Any]:
    """Gang rendezvous + jax.distributed.initialize (replaces reference
    initialize_clustered_function, _clustered_functions.py:41)."""
    from .clustered import init_cluster

    return await init_cluster(container_args, client)


async def run_lifecycle_hooks(hooks: list, name: str) -> None:
    for hook in hooks:
        logger.debug(f"running {name} hook {getattr(hook, '__name__', hook)}")
        if inspect.iscoroutinefunction(hook):
            await hook()
            continue
        # Sync hooks run OFF the synchronizer loop (like function bodies,
        # call_user_code above) so they can use the blocking SDK surface —
        # e.g. an @enter that streams weights from a Volume.
        res = await asyncio.to_thread(hook)
        if inspect.isawaitable(res):
            await res


# set by main_async when the function carries runtime_debug: every input is
# wrapped in jax.profiler.trace, xplane dumps land here (SURVEY §5 tracing;
# reference api.proto:1863 runtime_perf_record)
PROFILE_DIR: Optional[str] = None


_profile_active = False  # jax.profiler.trace is not reentrant


def _maybe_profile():
    import contextlib

    if PROFILE_DIR is None:
        return contextlib.nullcontext()

    @contextlib.contextmanager
    def _guarded():
        # concurrent inputs: only one trace at a time; the rest run
        # unprofiled instead of crashing on the profiler's reentrancy check
        global _profile_active
        if _profile_active:
            yield
            return
        import jax

        _profile_active = True
        try:
            with jax.profiler.trace(PROFILE_DIR):
                yield
        finally:
            _profile_active = False

    return _guarded()


async def _call_sync(callable_: Any, args: tuple, kwargs: dict, ctx: IOContext, io: ContainerIOManager) -> Any:
    """Run a sync user callable cancellable-by-signal when possible.

    First choice: the main-thread executor (SIGUSR1 → InputCancellation can
    interrupt it even inside a blocking C call — reference
    _container_entrypoint.py:194-264). When the main thread is already busy
    with another input (concurrency > 1) or no executor exists (tests driving
    main_async directly), fall back to asyncio.to_thread — cancellable only
    at the await, exactly the reference's behavior for its extra-thread
    inputs."""
    from .main_thread_exec import get_executor

    executor = get_executor()
    if executor is not None and executor.idle():
        job = executor.submit(callable_, *args, **kwargs)
        for iid in ctx.input_ids:
            io._mt_jobs[iid] = job
        try:
            return await asyncio.wrap_future(job.future)
        finally:
            for iid in ctx.input_ids:
                io._mt_jobs.pop(iid, None)
    return await asyncio.to_thread(callable_, *args, **kwargs)


async def call_user_code(service: Service, ctx: IOContext, io: ContainerIOManager) -> list[api_pb2.GenericResult]:
    """Run one IOContext (single input or batch) to results (reference
    call_function, _container_entrypoint.py:114)."""
    callable_ = service.get_callable(ctx.method_name)
    is_gen = service.is_gen(ctx.method_name)
    args, kwargs = ctx.batched_args_kwargs()
    t0 = time.monotonic()
    try:
        if is_gen:
            # stream items to the data channel; the unary output records DONE
            count = 0
            gen = callable_(*args, **kwargs)
            if hasattr(gen, "__aiter__"):
                async for item in gen:
                    await io.push_generator_data(ctx.function_call_ids[0], item)
                    count += 1
            else:
                for item in gen:
                    await io.push_generator_data(ctx.function_call_ids[0], item)
                    count += 1
                    await asyncio.sleep(0)
            await io.push_generator_done(ctx.function_call_ids[0], count)
            done = api_pb2.GeneratorDone(items_total=count)
            result = api_pb2.GenericResult(
                status=api_pb2.GENERIC_STATUS_SUCCESS,
                data=done.SerializeToString(),
                data_format=api_pb2.DATA_FORMAT_GENERATOR_DONE,
            )
            return [result]
        else:
            with _maybe_profile():
                if inspect.iscoroutinefunction(callable_):
                    value = await callable_(*args, **kwargs)
                else:
                    value = await _call_sync(callable_, args, kwargs, ctx, io)
            io.note_call_time(time.monotonic() - t0)
            if ctx.is_batch:
                if not isinstance(value, (list, tuple)) or len(value) != len(ctx.input_ids):
                    raise ExecutionError(
                        f"@batched function must return a list with one item per input "
                        f"({len(ctx.input_ids)} inputs, got {type(value).__name__})"
                    )
                return [
                    await io.format_result(v, ctx.data_format or api_pb2.DATA_FORMAT_PICKLE)
                    for v in value
                ]
            return [await io.format_result(value, ctx.data_format or api_pb2.DATA_FORMAT_PICKLE)]
    except BaseException as exc:  # noqa: BLE001 — every failure becomes a result
        if isinstance(exc, (KeyboardInterrupt, SystemExit)):
            raise
        logger.debug(f"user code raised: {type(exc).__name__}: {exc}")
        err = io.format_exception(exc)
        return [err for _ in ctx.input_ids]


async def run_input_loop(service: Service, io: ContainerIOManager) -> None:
    """Concurrent input execution under slots (reference run_inputs_outputs,
    container_io_manager.py:845). Structured: all in-flight inputs finish
    before exit (asyncio.TaskGroup is 3.11+; hand-rolled for 3.10 hosts)."""
    running: set[asyncio.Task] = set()
    first_exc: list[BaseException] = []
    child_failed = asyncio.Event()

    def _on_done(t: asyncio.Task) -> None:
        # TaskGroup semantics: remember the first real child failure so it
        # aborts the loop and propagates (a silently dropped exception here
        # would let the container report SUCCESS with an unpushed output)
        running.discard(t)
        if not t.cancelled():
            exc = t.exception()
            if exc is not None:
                if not first_exc:
                    first_exc.append(exc)
                child_failed.set()

    try:

        async def _run_one(ctx: IOContext) -> None:
            reset = execution_context._set_current_context_ids(
                ctx.input_ids[0], ctx.function_call_ids[0]
            )
            try:
                task = asyncio.current_task()
                for iid in ctx.input_ids:
                    io._running_tasks[iid] = task
                # user-execution span, stitched under the input's delivered
                # trace (falling back to the boot trace). cold_call marks the
                # container's first input — where first-call jit compilation
                # lands (compile time = cold user.execute minus warm ones).
                cold_call = not getattr(io, "_executed_an_input", False)
                io._executed_an_input = True
                parent = tracing.parse_context(
                    io.input_trace_contexts.get(ctx.input_ids[0], "")
                ) or tracing.context_from_env()
                if ctx.fetched_at and parent is not None:
                    # the delivery hop between the scheduler's claim and user
                    # execution: args deserialize + runner-task spawn — a
                    # dispatch-latency segment the attribution would
                    # otherwise report as gap (critical_path.py)
                    tracing.record_span(
                        "container.input_deliver",
                        start=ctx.fetched_at,
                        end=time.time(),
                        parent=parent,
                        attrs={"input_id": ctx.input_ids[0], "task_id": io.task_id},
                    )
                with tracing.span(
                    "user.execute",
                    parent=parent,
                    attrs={
                        "input_id": ctx.input_ids[0],
                        "function_call_id": ctx.function_call_ids[0],
                        "task_id": io.task_id,
                        "batch_size": len(ctx.input_ids),
                        "cold_call": cold_call,
                    },
                ):
                    results = await call_user_code(service, ctx, io)
                    await io.push_outputs(ctx, results)
            except asyncio.CancelledError:
                # input cancelled mid-flight: report TERMINATED
                results = [
                    api_pb2.GenericResult(
                        status=api_pb2.GENERIC_STATUS_TERMINATED, exception="input cancelled"
                    )
                    for _ in ctx.input_ids
                ]
                try:
                    await asyncio.shield(io.push_outputs(ctx, results))
                except Exception:
                    pass
            finally:
                for iid in ctx.input_ids:
                    io._running_tasks.pop(iid, None)
                reset()

        # the fetch races against child failure: a failed input task must
        # abort the loop IMMEDIATELY, not after the next input arrives —
        # generate_inputs can sit in its long poll for seconds while the
        # container would otherwise keep heartbeating with an unpushed output
        gen = io.generate_inputs().__aiter__()
        while True:
            fetch = asyncio.ensure_future(gen.__anext__())
            failed = asyncio.ensure_future(child_failed.wait())
            try:
                await asyncio.wait({fetch, failed}, return_when=asyncio.FIRST_COMPLETED)
            except BaseException:
                # outer cancel (SIGTERM drain) mid-wait: retrieve both racers
                # so neither logs "exception was never retrieved" at exit
                fetch.cancel()
                failed.cancel()
                await asyncio.gather(fetch, failed, return_exceptions=True)
                raise
            failed.cancel()
            if first_exc:
                fetch.cancel()
                fetched = (await asyncio.gather(fetch, return_exceptions=True))[0]
                if isinstance(fetched, IOContext):
                    # the fetch and the failure completed in the same wakeup:
                    # this ctx is already claimed server-side — report it
                    # TERMINATED (like a cancelled input) instead of dropping
                    # it to rot until a reaper notices
                    results = [
                        api_pb2.GenericResult(
                            status=api_pb2.GENERIC_STATUS_TERMINATED,
                            exception="input loop aborted",
                        )
                        for _ in fetched.input_ids
                    ]
                    try:
                        await asyncio.shield(io.push_outputs(fetched, results))
                    except Exception:
                        pass
                raise first_exc[0]
            try:
                ctx = fetch.result()
            except StopAsyncIteration:
                break
            t = asyncio.create_task(_run_one(ctx))
            running.add(t)
            t.add_done_callback(_on_done)
        if running:
            await asyncio.gather(*running, return_exceptions=True)
        # outputs stashed for a next exchange poll that will never come
        # (kill_switch / scaledown exit) flush on the split path
        await io.flush_pending_exchange()
        if first_exc:
            raise first_exc[0]
    except BaseException:
        # TaskGroup semantics: the fetch loop died or we were cancelled —
        # in-flight inputs are cancelled (each reports TERMINATED) and
        # awaited so no result push is abandoned mid-RPC
        for t in running:
            t.cancel()
        if running:
            await asyncio.shield(asyncio.gather(*running, return_exceptions=True))
        raise


async def run_web_endpoint(
    service: Service, io: ContainerIOManager, client: _Client, container_args: api_pb2.ContainerArguments
) -> None:
    """Serve the function as HTTP instead of polling the input queue
    (reference run_server/asgi flow, _container_entrypoint.py:394 +
    _runtime/asgi.py): build the ASGI app, bind a local port, register the
    URL with the control plane, serve until drained."""
    from .asgi import AsgiHttpServer, function_to_asgi, proxy_to_port, wait_for_port, wsgi_to_asgi

    function_def = container_args.function_def
    webhook_type = function_def.webhook_type
    # class-based services name their web method (cls.py from_local); plain
    # functions serve their single callable
    web_method = function_def.experimental_options.get("web_method_name", "")
    callable_ = service.get_callable(web_method)
    if webhook_type == api_pb2.WEB_ENDPOINT_TYPE_ASGI_APP:
        asgi = callable_()  # user factory returns the ASGI app
    elif webhook_type == api_pb2.WEB_ENDPOINT_TYPE_WSGI_APP:
        asgi = wsgi_to_asgi(callable_())
    elif webhook_type == api_pb2.WEB_ENDPOINT_TYPE_FUNCTION:
        method = function_def.experimental_options.get("web_method", "POST")
        asgi = function_to_asgi(callable_, method=method)
    elif webhook_type == api_pb2.WEB_ENDPOINT_TYPE_WEB_SERVER:
        # @web_server: the user function STARTS a server on the declared
        # port (thread/subprocess) and returns; we wait for the port, then
        # reverse-proxy the platform URL to it
        port = int(function_def.experimental_options.get("web_server_port", "0"))
        startup_timeout = float(
            function_def.experimental_options.get("web_server_startup_timeout", "60")
        )
        if not port:
            raise ExecutionError("@web_server function def carries no port")
        if inspect.iscoroutinefunction(callable_):
            await callable_()
        else:
            await asyncio.to_thread(callable_)
        await wait_for_port(port, startup_timeout)
        asgi = proxy_to_port(port)
    else:
        raise ExecutionError(f"unsupported webhook type {webhook_type}")

    server = AsgiHttpServer(asgi)
    await server.start()
    try:
        await retry_transient_errors(
            client.stub.FunctionSetWebUrl,
            api_pb2.FunctionSetWebUrlRequest(
                function_id=container_args.function_id,
                task_id=container_args.task_id,
                web_url=server.url,
            ),
            max_retries=3,
        )
        logger.debug(f"web endpoint registered: {server.url}")
        while not io.terminate:
            await asyncio.sleep(0.3)
    finally:
        await server.stop()


async def main_async() -> int:
    container_args = load_container_arguments()
    task_id = container_args.task_id
    function_def = container_args.function_def
    config.override_locally("task_id", task_id)
    execution_context._set_container_process()
    setup_compilation_cache()
    # dispatch-critical process: shrink the GIL switch interval — every input
    # bounces serving loop ↔ main-thread executor, and each handoff can stall
    # a full default 5 ms interval (ISSUE 8, docs/DISPATCH.md)
    tune_switch_interval()

    client = _Client(
        container_args.server_url or config["server_url"], api_pb2.CLIENT_TYPE_CONTAINER
    )
    await client._open()
    _Client.set_env_client(client)

    await retry_transient_errors(
        client.stub.ContainerHello,
        api_pb2.ContainerHelloRequest(task_id=task_id, warm_pool_hit=_WARM_POOL_SERVE),
        max_retries=5,
    )

    if function_def.experimental_options.get("runtime_debug"):
        global PROFILE_DIR
        task_dir = os.environ.get("MODAL_TPU_TASK_DIR", "")
        PROFILE_DIR = os.path.join(task_dir or ".", "profile")
        os.makedirs(PROFILE_DIR, exist_ok=True)

    io = ContainerIOManager(client, task_id, function_def)
    io._function_id = container_args.function_id
    heartbeat_task = asyncio.create_task(io.heartbeat_loop(), name="heartbeat")

    # continuous profiling (observability/profiler.py): the env toggle starts
    # the sampler at boot; the heartbeat applies runtime start/stop commands
    from ..observability import device_telemetry, profiler as obs_profiler

    obs_profiler.maybe_start_from_env(
        os.environ.get(obs_profiler.PROFILE_DIR_ENV, ""), tag=task_id
    )

    # Container boot span: starts at the worker's spawn decision
    # (MODAL_TPU_TRACE_T0) and ends when the container is ready for inputs —
    # the cold-start segment of the launching input's trace. Children
    # (imports, enter hooks) parent under it.
    boot_start = float(os.environ.get(tracing.TRACE_T0_ENV, "0") or 0) or None
    boot_span = tracing.open_span(
        "container.boot",
        parent=tracing.context_from_env(),
        start=boot_start,
        attrs={"task_id": task_id, "function_id": container_args.function_id},
    )

    exit_status = api_pb2.GENERIC_STATUS_SUCCESS
    exit_exception = ""
    service: Optional[Service] = None
    bucket_states: list = []
    try:
        # Gang functions: rendezvous + jax.distributed BEFORE user imports
        # (reference hook point: _container_entrypoint.py:451-457).
        if function_def.group_size > 1 or container_args.world_size > 1:
            await initialize_clustered(container_args, client)

        # cloud bucket mounts: sync bucket prefixes into their mount paths
        # BEFORE user code (weights may load from them); written back on exit
        if function_def.cloud_bucket_mounts:
            from .bucket_mounts import sync_bucket_mounts

            bucket_states = await sync_bucket_mounts(dict(function_def.cloud_bucket_mounts))

        # import user code + instantiate service
        bound_params = None
        if os.environ.get("MODAL_TPU_BOUND_PARAMS"):
            bound_params = deserialize(bytes.fromhex(os.environ["MODAL_TPU_BOUND_PARAMS"]), client)
        t_imports = time.time()
        if function_def.is_class:
            service = import_class_service(function_def, client, bound_params)
        else:
            service = import_single_function_service(function_def, client)
        tracing.record_span(
            "container.imports",
            start=t_imports,
            end=time.time(),
            parent=boot_span.context,
            attrs={
                "task_id": task_id,
                # per-module detail: `modal_tpu app imports <task_id>`
                # (runtime/telemetry.py, on when MODAL_TPU_IMPORT_TRACE=1)
                "import_trace": bool(os.environ.get("MODAL_TPU_TELEMETRY_PATH")),
            },
        )
        # compile/device telemetry: attach jax.monitoring listeners NOW (user
        # imports just ran, so if the function uses jax it is in sys.modules)
        # — the first-call jit compile must be counted, not just later ones
        device_telemetry.install_compile_hooks()
        # fleet compile cache (ISSUE 20): tier the persistent cache over the
        # fleet store before any enter-hook/first-input jit runs, so even the
        # very first compile of this container's life can be a fleet hit
        device_telemetry.maybe_install_fleet_cache()

        # lifecycle: enter hooks (pre-snapshot = warm weight load). With
        # memory snapshots enabled, later cold boots SKIP the snap-enter
        # hooks and stream the saved state straight to device — the TPU
        # analogue of the reference's CRIU restore
        # (task_lifecycle_manager.py:146-220); see runtime/snapshot.py.
        restored = False
        if function_def.enable_memory_snapshot and service.enter_pre_snapshot:
            from .snapshot import restore_snapshot

            # off-loop: a multi-GB restore must not starve the heartbeat task
            restored = await asyncio.to_thread(
                restore_snapshot, function_def, service.user_instance
            )
        if not restored:
            await run_lifecycle_hooks(service.enter_pre_snapshot, "enter(snap=True)")
        if function_def.enable_memory_snapshot:
            if not restored:
                from .snapshot import save_snapshot

                await asyncio.to_thread(save_snapshot, function_def, service.user_instance)
            # notify the control plane a warm snapshot exists (analogue of
            # the reference's ContainerCheckpoint → CRIU flow)
            await retry_transient_errors(
                client.stub.ContainerCheckpoint,
                api_pb2.ContainerCheckpointRequest(task_id=task_id, checkpoint_id=""),
                max_retries=2,
            )
        t_enter = time.time()
        await run_lifecycle_hooks(service.enter_post_snapshot, "enter")
        if service.enter_post_snapshot:
            tracing.record_span(
                "container.enter_hooks",
                start=t_enter,
                end=time.time(),
                parent=boot_span.context,
                attrs={"task_id": task_id},
            )
        # AOT lowering (ISSUE 20, runtime/aot.py): with MODAL_TPU_AOT_LOWER
        # set, compile the known entry points against abstract shapes NOW —
        # off-loop like the enter hooks — so the first input never traces.
        # Compiles land in the persistent + fleet caches (usually hits).
        if os.environ.get("MODAL_TPU_AOT_LOWER"):
            from .aot import maybe_aot_lower

            t_aot = time.time()
            if await asyncio.to_thread(maybe_aot_lower) is not None:
                tracing.record_span(
                    "container.aot_lower",
                    start=t_aot,
                    end=time.time(),
                    parent=boot_span.context,
                    attrs={"task_id": task_id},
                )

        # boot is complete: the container is about to serve
        tracing.close_span(boot_span)

        if function_def.webhook_type != api_pb2.WEB_ENDPOINT_TYPE_UNSPECIFIED:
            await run_web_endpoint(service, io, client, container_args)
        else:
            await run_input_loop(service, io)
    except BaseException as exc:
        if not boot_span.end:
            tracing.close_span(boot_span, status="error")
        if isinstance(exc, (KeyboardInterrupt, asyncio.CancelledError)):
            # SIGTERM from the worker (app stop / drain): graceful shutdown —
            # fall through so @exit hooks + TaskResult still run before the
            # worker escalates to SIGKILL.
            exit_status = api_pb2.GENERIC_STATUS_TERMINATED
            exit_exception = "terminated"
        else:
            exit_status = api_pb2.GENERIC_STATUS_FAILURE
            exit_exception = f"{type(exc).__name__}: {exc}"
            traceback.print_exc()
    finally:
        io.terminate = True
        if service is not None:
            try:
                await run_lifecycle_hooks(service.exit_hooks, "exit")
            except Exception:
                traceback.print_exc()
        # bucket mounts: upload new/changed files (the "commit" half of the
        # sync-down/write-back mount emulation). Synchronous: awaits in a
        # cancelled task's finally were observed hanging to SIGKILL.
        if bucket_states:
            from .bucket_mounts import writeback_bucket_mounts_sync

            try:
                writeback_bucket_mounts_sync(bucket_states)
            except Exception:
                traceback.print_exc()
        # volume auto-commit on shutdown (reference
        # task_lifecycle_manager.py:117)
        for _path, _vol_id in function_def.volume_mounts.items():
            try:
                await retry_transient_errors(
                    client.stub.VolumeCommit, api_pb2.VolumeCommitRequest(volume_id=_vol_id), max_retries=1
                )
            except Exception:
                pass
        try:
            await retry_transient_errors(
                client.stub.TaskResult,
                api_pb2.TaskResultRequest(
                    task_id=task_id,
                    result=api_pb2.GenericResult(status=exit_status, exception=exit_exception),
                ),
                max_retries=2,
            )
        except Exception:
            pass
        heartbeat_task.cancel()
        try:
            await heartbeat_task
        except asyncio.CancelledError:
            pass
        await client._close()
    # graceful drain (TERMINATED) is an expected shutdown: exit 0 so the
    # worker doesn't classify it as a container failure
    return 0 if exit_status in (api_pb2.GENERIC_STATUS_SUCCESS, api_pb2.GENERIC_STATUS_TERMINATED) else 1


def check_thread_leaks() -> list:
    """Log user threads still alive at container exit (reference
    _container_entrypoint.py:500-510): a leaked non-daemon thread blocks
    process exit until the worker's SIGKILL escalation — surface it loudly
    instead of dying silently. Returns the leaked threads (for tests)."""
    import threading

    known = {"modal-tpu-synchronizer"}  # our own daemon loop thread
    leaked = [
        t
        for t in threading.enumerate()
        if t is not threading.main_thread()
        and t.is_alive()
        and not t.daemon
        and t.name not in known
    ]
    for t in leaked:
        logger.warning(
            f"user code leaked non-daemon thread {t.name!r} still running at "
            f"container exit — it will block process shutdown until the worker kills it"
        )
    return leaked


# ---------------------------------------------------------------------------
# Warm-pool mode (server/warm_pool.py, docs/COLDSTART.md): this process was
# pre-forked by the worker to park with imports done, then serve placements
# by handoff over the task router — no re-exec between tasks.
# ---------------------------------------------------------------------------

# env the scrub removes before parking: cluster/rendezvous and per-task state
# a previous context could leak into a future placement's jax init
_CLUSTER_ENV_SCRUB = (
    "MODAL_TPU_BOUND_PARAMS",
    "MODAL_TPU_TASK_ID",
    "MODAL_TPU_TASK_DIR",
    "MODAL_TPU_CONTAINER_ARGS_PATH",
    "TPU_VISIBLE_DEVICES",
    "TPU_PROCESS_BOUNDS",
    "TPU_PROCESS_ADDRESSES",
    "TPU_WORKER_ID",
    "TPU_WORKER_HOSTNAMES",
    "MEGASCALE_COORDINATOR_ADDRESS",
    "JAX_COORDINATOR_ADDRESS",
)


def _pool_preimport() -> None:
    """Pay the import bill while parked: jax (and anything else configured)
    is imported BUT no backend is initialized — device pinning / XLA flags
    still apply at adoption time, before the first jax computation."""
    import importlib

    setup_compilation_cache()
    for key in _CLUSTER_ENV_SCRUB:
        os.environ.pop(key, None)
    for mod in filter(None, (m.strip() for m in str(config["warm_pool_preimport"]).split(","))):
        t0 = time.time()
        try:
            importlib.import_module(mod)
            tracing.record_span(
                "coldstart.preimport", start=t0, end=time.time(), attrs={"module": mod}
            )
        except Exception as exc:  # noqa: BLE001 — a missing module must not kill the pool
            logger.warning(f"warm pool pre-import of {mod!r} failed: {exc}")
    if os.environ.get("MODAL_TPU_WARM_POOL_PREINIT") == "1":
        # Opt-in: initialize the jax backend and prime the dispatch/compile
        # machinery while parked. The pool spawns interpreters on the CPU
        # platform (server/warm_pool.py), so this cannot take a chip. ONLY
        # safe when every placement's device count equals the pool's spawn
        # default — device flags applied at adoption cannot take effect once
        # the backend exists.
        t0 = time.time()
        try:
            import jax
            import jax.numpy as jnp

            jax.jit(lambda x: (x * 2 + jax.random.normal(jax.random.PRNGKey(0), x.shape)).sum())(
                jnp.ones((8, 8))
            ).block_until_ready()
            tracing.record_span(
                "coldstart.preinit",
                start=t0,
                end=time.time(),
                attrs={"n_devices": len(jax.devices())},
            )
        except Exception as exc:  # noqa: BLE001
            logger.warning(f"warm pool backend pre-init failed: {exc}")
    # AOT lowering at pool-park time (ISSUE 20, runtime/aot.py): a parked
    # interpreter with MODAL_TPU_AOT_LOWER compiles the known entry points
    # while idle — adoption then serves first traffic from cache. The fleet
    # tier is installed first so park-time compiles publish fleet-wide (and
    # usually hit entries another park/prewarm already published).
    if os.environ.get("MODAL_TPU_AOT_LOWER"):
        from .aot import maybe_aot_lower

        t0 = time.time()
        if maybe_aot_lower() is not None:
            tracing.record_span("coldstart.aot_lower", start=t0, end=time.time())


def _reset_process_state(base_env: dict, base_cwd: str, added_paths: list) -> None:
    """The restore contract between placements (docs/COLDSTART.md): env and
    cwd are restored to the park-time snapshot, SDK singletons are cleared,
    and the synchronizer loop + imported *library* modules (jax!) carry over.
    USER modules loaded from the placement's own sys.path additions
    (globals_path / PYTHONPATH delta) are purged along with those paths —
    app B's `import utils` must never resolve to app A's cached module.
    User code must not assume process-global state survives a placement."""
    global PROFILE_DIR
    from ..client import _Client
    from .io_manager import ContainerIOManager

    os.environ.clear()
    os.environ.update(base_env)
    try:
        os.chdir(base_cwd)
    except OSError:
        pass
    if added_paths:
        roots = tuple(os.path.abspath(p) + os.sep for p in added_paths)
        for name, mod in list(sys.modules.items()):
            mod_file = getattr(mod, "__file__", None) or ""
            if mod_file and os.path.abspath(mod_file).startswith(roots):
                del sys.modules[name]
        for p in added_paths:
            try:
                sys.path.remove(p)
            except ValueError:
                pass
    _Client.set_env_client(None)
    ContainerIOManager._singleton = None
    PROFILE_DIR = None


async def _pool_runner(state: dict) -> int:
    """Park → await handoff → serve → re-park, on the synchronizer loop."""
    import json

    import grpc as _grpc

    from .._utils.grpc_utils import create_channel
    from ..proto.rpc import TaskRouterStub

    global _WARM_POOL_SERVE
    pool_id = os.environ["MODAL_TPU_POOL_ID"]
    token = os.environ.get("MODAL_TPU_POOL_TOKEN", "")
    router_addr = os.environ["MODAL_TPU_POOL_ROUTER"]
    channel = create_channel(f"grpc://{router_addr}")
    stub = TaskRouterStub(channel)
    base_env = dict(os.environ)
    base_cwd = os.getcwd()
    generation = 0
    rc = 0
    try:
        while not state["evict"]:
            poll = asyncio.ensure_future(
                stub.PoolAwaitArguments(
                    api_pb2.PoolAwaitRequest(
                        pool_id=pool_id,
                        token=token,
                        generation=generation,
                        pid=os.getpid(),
                        timeout=50.0,
                    )
                )
            )
            state["poll"] = poll
            try:
                resp = await poll
            except asyncio.CancelledError:
                break  # SIGTERM while parked
            except _grpc.aio.AioRpcError as exc:
                # the worker owns this process's lifecycle: a router that
                # stopped answering means the worker is gone — exit, don't spin
                logger.warning(f"warm pool poll failed ({exc.code()}); exiting")
                break
            finally:
                state["poll"] = None
            if resp.evict:
                logger.debug("warm pool interpreter evicted")
                break
            if not resp.has_task:
                continue  # poll window lapsed; park again
            # --- adopt: apply the env delta in-process, ack, serve ---------
            for key in resp.env_unset:
                os.environ.pop(key, None)
            env_set = json.loads(resp.env_set_json or "{}")
            cwd = env_set.pop("MODAL_TPU_POOL_CWD", "")
            os.environ.update(env_set)
            os.environ["MODAL_TPU_CONTAINER_ARGS_PATH"] = resp.args_path
            # PYTHONPATH changes don't retro-apply to sys.path: prepend the
            # task's entries (globals_path etc.) so user imports resolve —
            # tracked so the re-park reset can remove them AND purge the
            # user modules they loaded (cross-app contamination guard)
            added_paths = []
            for entry in reversed(os.environ.get("PYTHONPATH", "").split(os.pathsep)):
                if entry and entry not in sys.path:
                    sys.path.insert(0, entry)
                    added_paths.append(entry)
            if cwd:
                try:
                    os.chdir(cwd)
                except OSError as exc:
                    logger.warning(f"warm pool chdir({cwd!r}) failed: {exc}")
                else:
                    # fresh spawns run `python -m ...` with cwd=container_cwd,
                    # which puts that dir on sys.path[0] — mirror it so
                    # workdir-resolved user imports behave identically on the
                    # pooled path (tracked: removed + purged at re-park)
                    if cwd not in sys.path:
                        sys.path.insert(0, cwd)
                        added_paths.append(cwd)
            try:
                await stub.PoolAdoptAck(
                    api_pb2.PoolAdoptAckRequest(
                        pool_id=pool_id, token=token, handoff_id=resp.handoff_id, task_id=resp.task_id
                    )
                )
            except _grpc.aio.AioRpcError as exc:
                # worker withdrew the handoff (or died): never run a task the
                # worker doesn't believe we own
                logger.warning(f"warm pool adopt-ack rejected ({exc.code()}); exiting")
                rc = 1
                break
            _WARM_POOL_SERVE = True
            task = asyncio.ensure_future(main_async())
            state["task"] = task
            try:
                rc = await task
            except asyncio.CancelledError:
                rc = 0  # graceful termination already reported via TaskResult
            except BaseException:  # noqa: BLE001 — a crashed serve poisons the pool
                traceback.print_exc()
                rc = 1
            finally:
                state["task"] = None
            generation += 1
            _reset_process_state(base_env, base_cwd, added_paths)
            if rc != 0:
                # don't re-park an interpreter whose serve crashed: process
                # state is suspect — exit and let the pool respawn fresh
                break
    finally:
        try:
            await channel.close()
        except Exception:  # noqa: BLE001
            pass
    return rc


def _install_preempt_handler(loop, handle_term) -> None:
    """SIGUSR2 = preemption notice (worker _signal_preempt), shared by main()
    and pool_main() so the flush contract can never drift between fresh and
    pooled interpreters: flush every in-flight input's resume token to the
    control plane (bounded — the grace window is ticking), THEN route into
    the normal graceful-termination path (@exit hooks, TaskResult)."""
    import signal

    async def _preempt_flush() -> None:
        from .io_manager import ContainerIOManager

        io = ContainerIOManager.singleton()
        if io is not None:
            try:
                await asyncio.wait_for(io.flush_resume_tokens(), timeout=8.0)
            except Exception:
                traceback.print_exc()
        handle_term(signal.SIGUSR2, None)

    def _handle_preempt(signum, frame):
        logger.warning("preemption notice received; flushing checkpoints")
        loop.call_soon_threadsafe(lambda: asyncio.ensure_future(_preempt_flush()))

    signal.signal(signal.SIGUSR2, _handle_preempt)


def pool_main() -> None:
    """Entry for MODAL_TPU_POOL_ID processes: identical signal semantics to
    main(), but the body loops placements instead of exiting after one."""
    import signal

    from .._utils.async_utils import synchronizer
    from .main_thread_exec import MainThreadExecutor, set_executor

    _pool_preimport()
    loop = synchronizer._ensure_loop()
    state: dict = {"task": None, "poll": None, "evict": False}

    def _handle_term(signum, frame):
        state["evict"] = True
        task = state.get("task")
        poll = state.get("poll")
        if task is not None:
            loop.call_soon_threadsafe(task.cancel)
        elif poll is not None:
            loop.call_soon_threadsafe(poll.cancel)

    signal.signal(signal.SIGTERM, _handle_term)
    _install_preempt_handler(loop, _handle_term)

    executor = MainThreadExecutor()
    executor.install_signal_handler()
    set_executor(executor)
    cf = asyncio.run_coroutine_threadsafe(_pool_runner(state), loop)
    try:
        executor.run_until(cf)
    except KeyboardInterrupt:
        cf.cancel()
        raise
    finally:
        set_executor(None)
        check_thread_leaks()
    sys.exit(cf.result())


def main() -> None:
    # Run the entrypoint's async main on the synchronizer loop: all SDK
    # coroutines (which the dual-surface wrappers pin to that loop) then run
    # natively, and grpc channels stay loop-affine.
    #
    # SIGTERM (worker stop event) cancels the main task instead of killing
    # the process, so @exit hooks, volume auto-commit, and TaskResult run
    # before the worker's SIGKILL escalation.
    import signal

    from .._utils.async_utils import synchronizer
    from .main_thread_exec import MainThreadExecutor, set_executor

    loop = synchronizer._ensure_loop()
    task_holder: dict = {}
    term_requested = {"flag": False}

    def _handle_term(signum, frame):
        term_requested["flag"] = True
        task = task_holder.get("task")
        if task is not None:
            loop.call_soon_threadsafe(task.cancel)

    signal.signal(signal.SIGTERM, _handle_term)
    _install_preempt_handler(loop, _handle_term)

    # Cancellable sync inputs: the asyncio machinery lives on the
    # synchronizer's daemon thread, leaving THIS (main) thread free to host
    # sync user code where SIGUSR1 → InputCancellation can reach it.
    executor = MainThreadExecutor()
    executor.install_signal_handler()
    set_executor(executor)

    async def _runner() -> int:
        task = asyncio.ensure_future(main_async())
        task_holder["task"] = task
        if term_requested["flag"]:
            # SIGTERM landed before the task was registered: honor it now
            task.cancel()
        try:
            return await task
        except asyncio.CancelledError:
            return 0  # graceful termination already reported via TaskResult

    cf = asyncio.run_coroutine_threadsafe(_runner(), loop)
    try:
        executor.run_until(cf)
    except KeyboardInterrupt:
        cf.cancel()
        raise
    finally:
        set_executor(None)
        check_thread_leaks()
    sys.exit(cf.result())


if __name__ == "__main__":
    if os.environ.get("MODAL_TPU_POOL_ID"):
        pool_main()
    else:
        main()
