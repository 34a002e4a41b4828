"""Warm pool: pre-forked parked interpreters that take placements by handoff.

The cold-start ledger (BENCH_r05) says the warm-state snapshot barely pays
because every cold start still re-execs `container_entrypoint` and re-imports
jax (~3.3 s of the 4.4 s total). The warm pool removes that term: the worker
keeps *booted* interpreters — modal_tpu imported, jax pre-imported, the
persistent XLA compilation cache attached, cluster env scrubbed — parked and
long-polling the worker's task-router plane for their next
`ContainerArguments`. A placement whose image/platform matches a parked
interpreter is handed off in-process (no exec, no import); everything else
falls back to the fresh-spawn path unchanged.

Protocol (all over the existing task router, `server/task_router.py`):

    parked proc --- PoolAwaitArguments(pool_id, token, generation) --->
                <-- PoolAwaitResponse{args_path, env delta, handoff_id} ---
    parked proc --- PoolAdoptAck(handoff_id) ---------------------------->
    parked proc runs main_async() ... reports TaskResult ... re-parks
    parked proc --- PoolAwaitArguments(generation+1) -------------------->

The ack is the commit point: the worker only treats the placement as adopted
once the interpreter confirms delivery. A parked process killed mid-handoff
(chaos knob `warm_kill_handoff`, or a real crash) never acks; the adoption
times out fast and `WorkerAgent._run_task` falls back to a fresh spawn — a
warm pool can make cold starts faster, never less reliable.

Sizing: a baseline pool for the host-venv image comes from
`MODAL_TPU_WARM_POOL`; the scheduler additionally directs per-image pools
(`PoolDirective` on the worker poll stream) from `min_containers` /
`buffer_containers`, and eviction on image change follows the directives.

See docs/COLDSTART.md for the restore contract (what process state survives
between placements).
"""

from __future__ import annotations

import asyncio
import json
import os
import secrets
import sys
import time
from dataclasses import dataclass, field
from typing import Optional

from ..config import compile_cache_dir, config, logger
from ..observability.catalog import (
    WARM_POOL_EVICTIONS,
    WARM_POOL_HANDOFF_SECONDS,
    WARM_POOL_PLACEMENTS,
    WARM_POOL_SIZE,
)
from ..proto import api_pb2

# handoff must fail FAST into the fresh-spawn fallback: a dead parked
# interpreter costing 10 s per placement would be worse than no pool
ACK_TIMEOUT_S = float(os.environ.get("MODAL_TPU_WARM_POOL_ACK_TIMEOUT", "10"))
# park long-poll window served by the router (client asks; server caps)
AWAIT_POLL_CAP_S = 55.0
# reserved env key carrying the task working directory through the env delta
POOL_CWD_ENV = "MODAL_TPU_POOL_CWD"

_EVICT = object()  # handoff-queue sentinel: exit instead of parking again


@dataclass
class PoolEntry:
    pool_id: str
    key: str  # f"{image_id}|{platform}" — what placements must match
    image_id: str
    token: str
    proc: asyncio.subprocess.Process
    spawn_env: dict[str, str]
    stdout_path: str
    stderr_path: str
    created_at: float = field(default_factory=time.time)
    state: str = "booting"  # booting -> parked -> adopting -> serving (-> parked ...) -> dead
    generation: int = 0  # placements completed by this interpreter
    task_id: str = ""
    # handoff plumbing
    handoff_q: asyncio.Queue = field(default_factory=lambda: asyncio.Queue(maxsize=1))
    pending_handoff_id: str = ""
    ack_evt: asyncio.Event = field(default_factory=asyncio.Event)
    dead_evt: asyncio.Event = field(default_factory=asyncio.Event)
    # resolved ("reparked", 0) when the interpreter polls the next generation,
    # ("exited", rc) when the process dies while serving
    task_done: Optional[asyncio.Future] = None
    evicting: bool = False

    @property
    def alive(self) -> bool:
        return self.proc.returncode is None and not self.dead_evt.is_set()


class WarmPool:
    """Owns the parked interpreters of one WorkerAgent."""

    def __init__(self, worker):
        self.worker = worker
        self.state_dir = worker.state_dir
        self.pool_dir = os.path.join(self.state_dir, "pool")
        os.makedirs(self.pool_dir, exist_ok=True)
        # A parked interpreter has been assigned no chip, so like any
        # container without one it is held to the CPU (worker.device_env):
        # nothing it does while parked — pre-import, MODAL_TPU_WARM_POOL_PREINIT,
        # MODAL_TPU_AOT_LOWER — can then take a chip the scheduler believes
        # is free. Placements with real chips never come here (_run_task).
        self.platform = config["jax_platform"] or "cpu"
        # Sizing inputs: a baseline host-venv pool from config plus raw
        # scheduler directives (image_id -> target). `targets` (effective
        # key -> target) is recomputed in _ensure — trivial image chains
        # materialize to the host venv, so their directives collapse onto
        # the host-venv key instead of spawning an unmatchable pool.
        self.baseline = int(config["warm_pool"] or 0)
        self.directives: dict[str, int] = {}
        self._image_keys: dict[str, str] = {}  # raw image_id -> effective key
        self.targets: dict[str, int] = {}
        self.entries: dict[str, PoolEntry] = {}
        self._watchers: set[asyncio.Task] = set()
        self._stopped = False
        self._draining = False
        self._seq = 0
        # serializes _ensure: concurrent runs (directive bursts, watcher
        # respawns) would both count the same deficit across their awaits and
        # double-spawn, churning full python+jax boots
        self._ensure_lock = asyncio.Lock()
        # crash-loop guard: a pool interpreter that dies while still BOOTING
        # strikes its key; three strikes disable the key instead of fork-
        # looping a broken configuration at full speed
        self._boot_strikes: dict[str, int] = {}
        self.MAX_BOOT_STRIKES = 3

    # -- keys ----------------------------------------------------------------

    def _key(self, image_id: str, env: Optional[dict] = None) -> str:
        """What must match for an in-process handoff: the image (interpreter +
        site-packages + baked env) and the jax platform the interpreter was
        booted under. Device counts are applied at adoption — they are read
        at backend init, which a parked interpreter has not done yet."""
        platform = self.platform if env is None else env.get("JAX_PLATFORMS", self.platform)
        return f"{image_id or ''}|{platform}"

    # -- lifecycle -----------------------------------------------------------

    async def start(self) -> None:
        await self._ensure()

    def ready_count(self) -> int:
        return sum(1 for e in self.entries.values() if e.state == "parked" and e.alive)

    def _gauge(self) -> None:
        counts = {"booting": 0, "parked": 0, "serving": 0}
        for e in self.entries.values():
            if e.state in ("booting",):
                counts["booting"] += 1
            elif e.state == "parked":
                counts["parked"] += 1
            elif e.state in ("adopting", "serving"):
                counts["serving"] += 1
        for state, n in counts.items():
            WARM_POOL_SIZE.set(float(n), state=state)

    async def wait_parked(self, n: int = 1, timeout: float = 60.0) -> bool:
        """Block until `n` interpreters are parked (bench/tests: the measured
        cold start must actually go through the pool)."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.ready_count() >= n:
                return True
            await asyncio.sleep(0.05)
        return self.ready_count() >= n

    def set_directive(self, image_id: str, target: int) -> None:
        """Scheduler-driven sizing (PoolDirective). target 0 removes the pool
        for that image — its parked interpreters are evicted (image change)."""
        current = self.directives.get(image_id, 0)
        if current == target:
            return
        logger.debug(f"warm pool directive: image {image_id!r} target {current} -> {target}")
        if target <= 0:
            self.directives.pop(image_id, None)
        else:
            self.directives[image_id] = target
        task = asyncio.create_task(self._ensure())
        self._watchers.add(task)
        task.add_done_callback(self._watchers.discard)

    async def _effective_key(self, image_id: str) -> str:
        """Resolve an image id to the pool key placements will match: chains
        that materialize to the host venv (trivial) collapse onto ''."""
        if not image_id:
            return self._key("")
        cached = self._image_keys.get(image_id)
        if cached is not None:
            return cached
        built = await self.worker._materialize_image(image_id)
        key = self._key("" if built is None else image_id)
        self._image_keys[image_id] = key
        return key

    async def _ensure(self) -> None:
        """Converge entry inventory to the targets: spawn deficits, evict
        surplus/stale-key parked interpreters (newest first, so a re-parked
        veteran keeps serving successive placements from the same PID)."""
        if self._stopped or self._draining:
            return
        # single-flight by design: concurrent converge ticks would double-spawn
        async with self._ensure_lock:  # lint: disable=lock-across-await
            await self._ensure_locked()

    async def _ensure_locked(self) -> None:
        if self._stopped or self._draining:
            return
        targets: dict[str, int] = {}
        if self.baseline > 0:
            targets[self._key("")] = self.baseline
        for image_id, target in dict(self.directives).items():
            try:
                key = await self._effective_key(image_id)
            except Exception as exc:  # noqa: BLE001 — unbuildable image: no pool
                logger.warning(f"warm pool directive for {image_id!r} dropped: {exc}")
                self.directives.pop(image_id, None)
                continue
            targets[key] = max(targets.get(key, 0), target)
        # crash-loop guard: keys whose interpreters keep dying at boot are
        # disabled (placements fall back to fresh spawns, which surface the
        # real error via INIT/TaskResult) instead of fork-looping
        for key in [k for k in targets if self._boot_strikes.get(k, 0) >= self.MAX_BOOT_STRIKES]:
            del targets[key]
        self.targets = targets
        by_key: dict[str, list[PoolEntry]] = {}
        for e in list(self.entries.values()):
            if not e.alive:
                continue
            by_key.setdefault(e.key, []).append(e)
        # evict entries whose key has no target anymore (image change), and
        # surplus beyond target
        for key, group in by_key.items():
            target = self.targets.get(key, 0)
            group.sort(key=lambda e: e.created_at)
            resident = [e for e in group if e.state in ("booting", "parked", "serving", "adopting")]
            surplus = len(resident) - target
            for e in reversed(resident):  # newest first
                if surplus <= 0:
                    break
                if e.state in ("serving", "adopting"):
                    continue  # never yank a serving interpreter; it re-parks and is re-checked
                reason = "image_change" if target == 0 else "target_shrunk"
                self._evict(e, reason)
                surplus -= 1
        for key, target in self.targets.items():
            have = sum(
                1
                for e in self.entries.values()
                if e.alive and e.key == key and e.state in ("booting", "parked", "serving", "adopting")
            )
            for _ in range(max(0, target - have)):
                try:
                    await self._spawn(key)
                except Exception as exc:  # noqa: BLE001 — pool is best-effort
                    logger.warning(f"warm pool spawn failed for {key!r}: {exc}")
                    break
        self._gauge()

    def _evict(self, entry: PoolEntry, reason: str) -> None:
        if entry.evicting or not entry.alive:
            return
        entry.evicting = True
        WARM_POOL_EVICTIONS.inc(reason=reason)
        logger.debug(f"warm pool evicting {entry.pool_id} ({reason})")
        try:
            entry.handoff_q.put_nowait(_EVICT)  # graceful: exit at next poll
        except asyncio.QueueFull:
            pass

        async def _escalate(e=entry) -> None:
            try:
                await asyncio.wait_for(e.proc.wait(), timeout=10.0)
            except asyncio.TimeoutError:
                try:
                    e.proc.kill()
                except ProcessLookupError:
                    pass

        t = asyncio.create_task(_escalate())
        self._watchers.add(t)
        t.add_done_callback(self._watchers.discard)

    async def _spawn(self, key: str) -> PoolEntry:
        image_id, _, platform = key.partition("|")
        self._seq += 1
        pool_id = f"pw-{os.getpid()}-{self._seq}"
        token = secrets.token_urlsafe(24)
        env = dict(os.environ)
        python_bin = sys.executable
        if image_id:
            built = await self.worker._materialize_image(image_id)
            if built is not None:
                env.update(built.env)
                env["MODAL_TPU_IMAGE_ROOT"] = built.rootfs
                env["PATH"] = os.path.dirname(built.python_bin) + os.pathsep + env.get("PATH", "")
                python_bin = built.python_bin
        pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        env["PYTHONPATH"] = pkg_root + os.pathsep + env.get("PYTHONPATH", "")
        env["MODAL_TPU_SERVER_URL"] = self.worker.server_url
        env["MODAL_TPU_POOL_ID"] = pool_id
        env["MODAL_TPU_POOL_TOKEN"] = token
        env["MODAL_TPU_POOL_ROUTER"] = self.worker.router_address
        # fleet compile cache pre-attach (ISSUE 20): a parked interpreter's
        # pre-import jit warmups — and everything the adopted task compiles —
        # hit/feed the fleet store from the first trace, so a cold rollout
        # serves from entries prewarmed by any prior build anywhere
        for cache_key, cache_value in self.worker._compile_cache_env().items():
            env.setdefault(cache_key, cache_value)
        env["JAX_COMPILATION_CACHE_DIR"] = compile_cache_dir()
        env["JAX_PLATFORMS"] = platform
        from ..observability import tracing

        if tracing.trace_dir():
            env[tracing.TRACE_DIR_ENV] = tracing.trace_dir()
        stdout_path = os.path.join(self.pool_dir, f"{pool_id}.out")
        stderr_path = os.path.join(self.pool_dir, f"{pool_id}.err")
        with open(stdout_path, "wb") as out_f, open(stderr_path, "wb") as err_f:
            proc = await asyncio.create_subprocess_exec(
                python_bin,
                "-u",
                "-m",
                "modal_tpu.runtime.container_entrypoint",
                env=env,
                stdout=out_f,
                stderr=err_f,
            )
        entry = PoolEntry(
            pool_id=pool_id,
            key=key,
            image_id=image_id,
            token=token,
            proc=proc,
            spawn_env=env,
            stdout_path=stdout_path,
            stderr_path=stderr_path,
        )
        self.entries[pool_id] = entry
        watcher = asyncio.create_task(self._watch(entry), name=f"pool-watch-{pool_id}")
        self._watchers.add(watcher)
        watcher.add_done_callback(self._watchers.discard)
        logger.debug(f"warm pool spawned {pool_id} (key={key!r}, pid={proc.pid})")
        self._gauge()
        return entry

    async def _watch(self, entry: PoolEntry) -> None:
        rc = await entry.proc.wait()
        entry.dead_evt.set()
        was = entry.state
        entry.state = "dead"
        if entry.task_done is not None and not entry.task_done.done():
            entry.task_done.set_result(("exited", rc))
        self.entries.pop(entry.pool_id, None)
        if not entry.evicting and was != "serving":
            WARM_POOL_EVICTIONS.inc(reason="died")
            logger.warning(f"warm pool interpreter {entry.pool_id} died rc={rc} while {was}")
            if was == "booting":
                # died before ever parking: a broken configuration (bad
                # image python, preinit crash) would otherwise fork/die in a
                # tight loop — strike the key; _ensure disables it at 3
                strikes = self._boot_strikes.get(entry.key, 0) + 1
                self._boot_strikes[entry.key] = strikes
                if strikes >= self.MAX_BOOT_STRIKES:
                    logger.error(
                        f"warm pool key {entry.key!r} disabled after {strikes} boot "
                        f"failures (last rc={rc}); placements will spawn fresh — "
                        f"see {entry.stderr_path}"
                    )
        self._gauge()
        if not self._stopped and not self._draining:
            await self._ensure()

    # -- router-side protocol (called by TaskRouterServicer) ------------------

    def entry_for(self, pool_id: str, token: str) -> Optional[PoolEntry]:
        entry = self.entries.get(pool_id)
        if entry is None:
            return None
        if not secrets.compare_digest(entry.token, token):
            return None
        return entry

    def note_parked(self, entry: PoolEntry, generation: int) -> None:
        """The interpreter is at its PoolAwaitArguments long-poll: booting is
        over, and a poll with an advanced generation means the previous
        placement finished (the restore-without-re-exec 're-park')."""
        if entry.state == "serving" and generation > entry.generation:
            entry.generation = generation
            entry.task_id = ""
            entry.state = "parked"
            if entry.task_done is not None and not entry.task_done.done():
                entry.task_done.set_result(("reparked", 0))
            logger.debug(f"warm pool {entry.pool_id} re-parked (generation {generation})")
        elif entry.state == "booting":
            entry.state = "parked"
            self._boot_strikes.pop(entry.key, None)  # healthy boot clears strikes
            logger.debug(f"warm pool {entry.pool_id} parked (pid {entry.proc.pid})")
        self._gauge()

    # -- adoption --------------------------------------------------------------

    async def adopt(
        self, image_id: str, task_env: dict[str, str], task_id: str, args_path: str, cwd: str = ""
    ) -> Optional[PoolEntry]:
        """Hand a placement to a parked interpreter. Returns the serving entry
        once the interpreter ACKED delivery, or None (caller falls back to a
        fresh spawn). Never raises."""
        if self._stopped or self._draining:
            return None
        key = self._key(image_id, task_env)
        parked = sorted(
            (e for e in self.entries.values() if e.state == "parked" and e.alive),
            key=lambda e: e.created_at,
        )
        candidates = [e for e in parked if e.key == key]
        if not candidates:
            WARM_POOL_PLACEMENTS.inc(outcome="miss_key" if parked else "miss_empty")
            return None
        entry = candidates[0]
        entry.state = "adopting"
        entry.task_id = task_id
        handoff_id = secrets.token_urlsafe(12)
        entry.pending_handoff_id = handoff_id
        entry.ack_evt = asyncio.Event()
        entry.task_done = asyncio.get_running_loop().create_future()
        env_set = dict(task_env)
        if cwd:
            env_set[POOL_CWD_ENV] = cwd
        env_unset = [k for k in entry.spawn_env if k not in env_set]
        payload = api_pb2.PoolAwaitResponse(
            has_task=True,
            task_id=task_id,
            args_path=args_path,
            env_set_json=json.dumps(env_set),
            env_unset=env_unset,
            handoff_id=handoff_id,
        )
        t0 = time.monotonic()
        try:
            entry.handoff_q.put_nowait(payload)
        except asyncio.QueueFull:
            # an evict sentinel is already queued: this entry is on its way out
            WARM_POOL_PLACEMENTS.inc(outcome="handoff_failed")
            return None
        # chaos: kill mid-handoff (payload queued, ack pending) — the fallback
        # below must spawn fresh instead of hanging the placement
        chaos = getattr(self.worker, "chaos", None)
        if chaos is not None and chaos.consume_knob("warm_kill_handoff"):
            logger.warning(f"chaos: killing warm interpreter {entry.pool_id} mid-handoff")
            try:
                entry.proc.kill()
            except ProcessLookupError:
                pass
        ack = asyncio.ensure_future(entry.ack_evt.wait())
        died = asyncio.ensure_future(entry.dead_evt.wait())
        try:
            await asyncio.wait({ack, died}, timeout=ACK_TIMEOUT_S, return_when=asyncio.FIRST_COMPLETED)
        finally:
            ack.cancel()
            died.cancel()
        if not entry.ack_evt.is_set():
            # dead or wedged mid-handoff: drop it and let the caller spawn
            # fresh. _watch() handles cleanup + respawn for the dead case.
            WARM_POOL_PLACEMENTS.inc(outcome="handoff_failed")
            logger.warning(
                f"warm pool handoff to {entry.pool_id} failed "
                f"({'died' if entry.dead_evt.is_set() else 'ack timeout'}); falling back to fresh spawn"
            )
            if entry.alive:
                entry.evicting = True
                try:
                    entry.proc.kill()
                except ProcessLookupError:
                    pass
            if entry.task_done is not None and not entry.task_done.done():
                entry.task_done.cancel()
            return None
        entry.state = "serving"
        WARM_POOL_PLACEMENTS.inc(outcome="hit")
        WARM_POOL_HANDOFF_SECONDS.observe(time.monotonic() - t0)
        self._gauge()
        return entry

    def ack(self, entry: PoolEntry, handoff_id: str) -> bool:
        if entry.pending_handoff_id and secrets.compare_digest(entry.pending_handoff_id, handoff_id):
            entry.ack_evt.set()
            return True
        return False

    # -- teardown --------------------------------------------------------------

    def drain(self) -> None:
        """Preemption: parked interpreters hold no work — evict them all so
        the host can terminate inside its grace window."""
        self._draining = True
        for entry in list(self.entries.values()):
            if entry.state in ("booting", "parked"):
                self._evict(entry, "drain")
        self._gauge()

    def kill_parked(self) -> None:
        """Chaos worker_kill: abrupt host loss takes the parked interpreters
        with it (serving ones are killed via the worker's _procs map)."""
        for entry in list(self.entries.values()):
            if entry.state in ("booting", "parked") and entry.alive:
                entry.evicting = True
                try:
                    entry.proc.kill()
                except ProcessLookupError:
                    pass

    async def stop(self) -> None:
        self._stopped = True
        for entry in list(self.entries.values()):
            if entry.alive:
                entry.evicting = True
                try:
                    entry.proc.kill()
                except ProcessLookupError:
                    pass
        # let the watchers reap the kills (they resolve task_done futures);
        # cancel stragglers after a bounded wait
        if self._watchers:
            _done, pending = await asyncio.wait(self._watchers, timeout=5.0)
            for t in pending:
                t.cancel()
            if pending:
                await asyncio.gather(*pending, return_exceptions=True)
        self.entries.clear()
        self._gauge()
