"""Worker-side image materialization: layer chain → content-addressed venv.

The reference builds images remotely (client waits on `ImageGetOrCreate` →
`ImageJoinStreaming`, reference py/modal/_image.py:426-665); its builder is a
closed server component. This is the TPU build's equivalent for the local
worker backend: an image definition chain (each layer one `Image` proto,
linked by `FROM <parent_image_id>`) materializes into

    <state_dir>/images/<chain-sha256>/
        venv/        # python -m venv --system-site-packages + pip layers
        rootfs/      # COPY targets
        image.json   # {python_bin, env, workdir, entrypoint} for launch
        build.log

Builds are content-addressed (same chain hash ⇒ reuse), built atomically
(tmp dir + os.replace) under a per-hash asyncio lock, and **fail loudly**:
a layer that cannot be honored (unsupported python version, failing RUN,
unreachable index) fails the build, which fails the task with INIT_FAILURE
carrying the build-log tail — the round-1 behavior of silently running the
host venv is gone.

Command interpretation (host-venv backend — no docker/chroot):
- `FROM python:X...`      → venv from host python; python minor version must
                            match the host (else: loud failure).
- `FROM <im-...>`         → parent layer (resolved into the chain).
- `RUN python -m pip ...` / `RUN pip ...`
                          → run with the venv's python/pip.
- `RUN uv pip install --system ...`
                          → rewritten to the venv's `python -m pip ...`
                            (uv itself isn't assumed present).
- `RUN <other>`           → bash -lc under the recorded env/workdir with the
                            venv's bin first on PATH.
- `ENV K=V` / `WORKDIR p` → recorded, applied at container launch.
- `COPY src dst`          → copied under rootfs/<dst>; the container gets
                            MODAL_TPU_IMAGE_ROOT pointing at rootfs.
- `ENTRYPOINT/CMD [...]`  → recorded (sandbox default command).
- `#MOUNT_PYTHON_SOURCE`  → no-op on the local backend (client FS is the
                            worker FS; globals_path already covers imports).
- `#RUN_FUNCTION`         → build_function_serialized executed with the
                            venv's python at build time (weight-baking hook,
                            reference _image.py:2175).
"""

from __future__ import annotations

import asyncio
import hashlib
import json
import os
import re
import shlex
import shutil
import sys
from dataclasses import dataclass, field
from typing import Optional

from ..config import compile_cache_dir, logger
from ..proto import api_pb2


class ImageBuildError(Exception):
    def __init__(self, message: str, log_tail: str = ""):
        super().__init__(message + (f"\n--- build log tail ---\n{log_tail}" if log_tail else ""))
        self.log_tail = log_tail


@dataclass
class BuiltImage:
    python_bin: str
    env: dict[str, str] = field(default_factory=dict)
    workdir: str = ""
    entrypoint: list[str] = field(default_factory=list)
    cmd: list[str] = field(default_factory=list)
    rootfs: str = ""
    # snapshot-image: content to seed a sandbox's workdir with (a dir holding
    # the extracted fs snapshot; Sandbox.snapshot_filesystem round-trip)
    fs_seed_dir: str = ""

    def to_json(self) -> str:
        return json.dumps(self.__dict__)

    @staticmethod
    def from_json(data: str) -> "BuiltImage":
        return BuiltImage(**json.loads(data))


def _is_trivial(chain: list[api_pb2.Image]) -> bool:
    """A chain that only pins a matching-python base needs no venv at all —
    the host venv IS that image. Keeps the zero-layer fast path free."""
    for image in chain:
        for cmd in image.dockerfile_commands:
            c = cmd.strip()
            if not c or c.startswith("#MOUNT_PYTHON_SOURCE"):
                continue
            if c.startswith("FROM "):
                ref = c[5:].strip()
                if ref.startswith("im-"):
                    continue
                m = re.match(r"python:(\d+\.\d+)", ref)
                host = f"{sys.version_info.major}.{sys.version_info.minor}"
                if m and m.group(1) == host:
                    continue
                return False
            return False
        if image.build_function_serialized:
            return False
    return True


def chain_version(chain: list[api_pb2.Image]) -> str:
    """The builder epoch a chain is built under: the newest layer's version
    wins (layers inherit the epoch of the app that created them)."""
    from ..config import config

    for image in reversed(chain):
        if image.version:
            return image.version
    return config["image_builder_version"]


def chain_hash(chain: list[api_pb2.Image]) -> str:
    from .. import builder as builder_epochs

    h = hashlib.sha256()
    for image in chain:
        h.update(image.SerializeToString(deterministic=True))
        h.update(b"\x00")
    # the epoch's pinned-dep content participates in the key: editing an
    # epoch file (or switching epochs) rebuilds every image under it
    try:
        h.update(builder_epochs.epoch_content_hash(chain_version(chain)).encode())
    except builder_epochs.UnknownBuilderVersion:
        pass  # validated loudly at build time; keep hashing total
    return h.hexdigest()[:24]


_builders: dict[str, "ImageBuilder"] = {}


def get_image_builder(state_dir: str) -> "ImageBuilder":
    """One builder per state_dir in this process: all WorkerAgents sharing a
    state_dir (LocalSupervisor) share the per-hash build locks."""
    key = os.path.realpath(state_dir)
    if key not in _builders:
        _builders[key] = ImageBuilder(state_dir)
    return _builders[key]


class ImageBuilder:
    """Materializes image chains on one worker host, with caching."""

    def __init__(self, state_dir: str):
        self.images_dir = os.path.join(state_dir, "images")
        os.makedirs(self.images_dir, exist_ok=True)
        # same root ServerState uses: <state_dir>/compile_cache. Prewarm
        # bakes publish here so the whole fleet hits entries this host baked.
        self.compile_store_dir = os.path.join(state_dir, "compile_cache")
        self._locks: dict[str, asyncio.Lock] = {}

    async def fetch_chain(self, stub, image_id: str) -> list[api_pb2.Image]:
        """Resolve the FROM-linked layer chain, base first."""
        from .._utils.grpc_utils import retry_transient_errors

        chain: list[api_pb2.Image] = []
        current: Optional[str] = image_id
        for _ in range(64):  # chain-length guard
            if not current:
                break
            resp = await retry_transient_errors(
                stub.ImageFromId, api_pb2.ImageFromIdRequest(image_id=current)
            )
            chain.append(resp.definition)
            current = None
            for cmd in resp.definition.dockerfile_commands:
                c = cmd.strip()
                if c.startswith("FROM im-"):
                    current = c[5:].strip()
                    break
        chain.reverse()
        return chain

    async def materialize(self, stub, image_id: str) -> Optional[BuiltImage]:
        """Returns the built image, or None when the chain is trivial (host
        venv is the image). Raises ImageBuildError on any unhonorable layer."""
        chain = await self.fetch_chain(stub, image_id)
        snapshot_blob_id = next((im.fs_snapshot_blob_id for im in chain if im.fs_snapshot_blob_id), "")
        if snapshot_blob_id:
            return await self._materialize_snapshot(stub, snapshot_blob_id)
        if _is_trivial(chain):
            return None
        key = chain_hash(chain)
        final_dir = os.path.join(self.images_dir, key)
        meta_path = os.path.join(final_dir, "image.json")
        if os.path.exists(meta_path):
            with open(meta_path) as f:
                return BuiltImage.from_json(f.read())
        lock = self._locks.setdefault(key, asyncio.Lock())
        # single-flight by design: one build per image key, waiters reuse it
        async with lock:  # lint: disable=lock-across-await
            # cross-process (standalone worker_main agents sharing a state
            # dir): flock serializes the build; in-process the asyncio lock
            # already did. The build happens IN final_dir — venv shebangs are
            # then correct forever — with image.json written LAST as the
            # commit marker; a dir without image.json is a dead build, wiped.
            import fcntl

            lock_file = open(final_dir + ".lock", "w")
            try:
                await asyncio.to_thread(fcntl.flock, lock_file, fcntl.LOCK_EX)
                if os.path.exists(meta_path):  # built while we waited
                    with open(meta_path) as f:
                        return BuiltImage.from_json(f.read())
                shutil.rmtree(final_dir, ignore_errors=True)
                os.makedirs(final_dir)
                try:
                    built = await self._build(chain, final_dir)
                    with open(meta_path, "w") as f:
                        f.write(built.to_json())
                    logger.debug(f"image {key} built at {final_dir}")
                    return built
                except Exception:
                    shutil.rmtree(final_dir, ignore_errors=True)
                    raise
            finally:
                fcntl.flock(lock_file, fcntl.LOCK_UN)
                lock_file.close()

    async def _materialize_snapshot(self, stub, blob_id: str) -> BuiltImage:
        """A snapshot-image is a filesystem tarball, not a layer build: fetch
        the blob once (content-addressed by blob id) and extract it; sandboxes
        using the image get a COPY of the extracted tree as their workdir."""
        from .._utils.blob_utils import blob_download
        from .fs_snapshot import untar_dir

        seed_dir = os.path.join(self.images_dir, f"snapshot-{blob_id}")
        marker = os.path.join(seed_dir, ".complete")
        if not os.path.exists(marker):
            lock = self._locks.setdefault(f"snapshot-{blob_id}", asyncio.Lock())
            # single-flight by design: one snapshot extraction per blob
            async with lock:  # lint: disable=lock-across-await
                # cross-process (standalone worker agents sharing a state
                # dir): same flock discipline as the layer-build path — two
                # processes extracting into one tmp dir would corrupt the
                # seed tree for every future restore
                import fcntl

                lock_file = open(seed_dir + ".lock", "w")
                try:
                    await asyncio.to_thread(fcntl.flock, lock_file, fcntl.LOCK_EX)
                    if not os.path.exists(marker):
                        data = await blob_download(blob_id, stub)
                        tmp_dir = f"{seed_dir}.tmp{os.getpid()}"
                        shutil.rmtree(tmp_dir, ignore_errors=True)
                        await untar_dir(data, tmp_dir)
                        open(os.path.join(tmp_dir, ".complete"), "w").close()
                        shutil.rmtree(seed_dir, ignore_errors=True)
                        os.replace(tmp_dir, seed_dir)
                finally:
                    fcntl.flock(lock_file, fcntl.LOCK_UN)
                    lock_file.close()
        return BuiltImage(python_bin=sys.executable, fs_seed_dir=seed_dir)

    async def _build(self, chain: list[api_pb2.Image], build_dir: str) -> BuiltImage:
        venv_dir = os.path.join(build_dir, "venv")
        rootfs = os.path.join(build_dir, "rootfs")
        log_path = os.path.join(build_dir, "build.log")
        os.makedirs(rootfs)
        log_f = open(log_path, "a")

        def log(line: str) -> None:
            log_f.write(line.rstrip() + "\n")
            log_f.flush()

        def tail() -> str:
            log_f.flush()
            with open(log_path) as f:
                return f.read()[-4000:]

        async def run_shell(cmd: str, env: dict[str, str], cwd: str) -> None:
            log(f"$ {cmd}")
            proc = await asyncio.create_subprocess_shell(
                cmd,
                stdout=asyncio.subprocess.PIPE,
                stderr=asyncio.subprocess.STDOUT,
                env=env,
                cwd=cwd or None,
                executable="/bin/bash",
            )
            out, _ = await proc.communicate()
            log(out.decode(errors="replace"))
            if proc.returncode != 0:
                raise ImageBuildError(f"build command failed (rc={proc.returncode}): {cmd}", tail())

        host = f"{sys.version_info.major}.{sys.version_info.minor}"
        built = BuiltImage(python_bin="", rootfs=rootfs)
        from .. import builder as builder_epochs

        try:
            # Resolve the builder epoch (reference builder/ versioned
            # requirement sets): unknown epochs fail the build loudly; the
            # epoch's base-image config seeds the env and bounds pythons.
            epoch = chain_version(chain)
            epoch_cfg = builder_epochs.base_image_config(epoch)  # raises UnknownBuilderVersion
            log(f"builder epoch {epoch} (content {builder_epochs.epoch_content_hash(epoch)})")
            if epoch_cfg["python"] and host not in epoch_cfg["python"]:
                raise ImageBuildError(
                    f"builder epoch {epoch} supports python {epoch_cfg['python']}, host is {host}",
                    tail(),
                )
            built.env.update(epoch_cfg["tpu_env"])
            # base venv (system-site-packages: host jax/numpy stack available,
            # pip layers shadow/extend it — the local-backend "debian slim")
            log(f"creating venv (python {host}, system-site-packages)")
            proc = await asyncio.create_subprocess_exec(
                sys.executable, "-m", "venv", "--system-site-packages", venv_dir,
                stdout=asyncio.subprocess.PIPE, stderr=asyncio.subprocess.STDOUT,
            )
            out, _ = await proc.communicate()
            log(out.decode(errors="replace"))
            if proc.returncode != 0:
                raise ImageBuildError("venv creation failed", tail())
            built.python_bin = os.path.join(venv_dir, "bin", "python")
            venv_bin = os.path.join(venv_dir, "bin")
            # The worker python is itself typically a venv, so
            # --system-site-packages resolves to the BASE interpreter's
            # site-packages — the worker venv's stack (jax, grpc, setuptools)
            # would be invisible. Bridge it with a .pth so image layers can
            # extend/shadow the host stack (venv's own site dir stays first).
            import sysconfig

            host_purelib = sysconfig.get_paths()["purelib"]
            venv_site = os.path.join(
                venv_dir, "lib", f"python{host}", "site-packages"
            )
            with open(os.path.join(venv_site, "_modal_tpu_host.pth"), "w") as f:
                f.write(host_purelib + "\n")
            log(f"bridged host site-packages: {host_purelib}")

            def shell_env() -> dict[str, str]:
                env = dict(os.environ)
                env.update(built.env)
                # build steps compile into the same cache containers read
                env["JAX_COMPILATION_CACHE_DIR"] = compile_cache_dir()
                env["PATH"] = venv_bin + os.pathsep + env.get("PATH", "")
                env["VIRTUAL_ENV"] = venv_dir
                env["MODAL_TPU_IMAGE_ROOT"] = rootfs
                env["MODAL_TPU_IMAGE_BUILD"] = "1"
                return env

            for image in chain:
                for raw in image.dockerfile_commands:
                    cmd = raw.strip()
                    # '#'-directives: #MOUNT_PYTHON_SOURCE is a local-backend
                    # no-op, #RUN_FUNCTION is handled via
                    # build_function_serialized after the command loop
                    if not cmd or cmd.startswith("#"):
                        continue
                    if cmd.startswith("FROM "):
                        ref = cmd[5:].strip()
                        if ref.startswith("im-"):
                            continue  # parent layer, already in chain
                        m = re.match(r"python:(\d+\.\d+)", ref)
                        if m is None or m.group(1) != host:
                            raise ImageBuildError(
                                f"cannot honor base {ref!r} on the local worker backend "
                                f"(host python is {host}); use a matching python or a "
                                "registry-capable worker",
                                tail(),
                            )
                        continue
                    if cmd.startswith("ENV "):
                        k, _, v = cmd[4:].partition("=")
                        built.env[k.strip()] = _unquote(v)
                        log(f"ENV {k.strip()}={built.env[k.strip()]}")
                        continue
                    if cmd.startswith("WORKDIR "):
                        built.workdir = cmd[8:].strip()
                        wd = built.workdir
                        if not os.path.isabs(wd) or not os.path.isdir(wd):
                            # materialize non-existent workdirs under rootfs
                            wd = os.path.join(rootfs, wd.lstrip("/"))
                            os.makedirs(wd, exist_ok=True)
                            built.workdir = wd
                        log(f"WORKDIR {built.workdir}")
                        continue
                    if cmd.startswith("ENTRYPOINT "):
                        built.entrypoint = json.loads(cmd[len("ENTRYPOINT "):])
                        continue
                    if cmd.startswith("CMD "):
                        built.cmd = json.loads(cmd[len("CMD "):])
                        continue
                    if cmd.startswith("COPY "):
                        parts = shlex.split(cmd[5:])
                        if len(parts) != 2:
                            raise ImageBuildError(f"unsupported COPY form: {cmd}", tail())
                        src, dst = parts
                        target = os.path.join(rootfs, dst.lstrip("/"))
                        if not os.path.exists(src):
                            raise ImageBuildError(f"COPY source missing: {src}", tail())
                        os.makedirs(os.path.dirname(target) or rootfs, exist_ok=True)
                        if os.path.isdir(src):
                            shutil.copytree(src, target, dirs_exist_ok=True)
                        else:
                            shutil.copy2(src, target)
                        log(f"COPY {src} -> {target}")
                        continue
                    if cmd.startswith("RUN "):
                        shell_cmd = _rewrite_run(cmd[4:].strip(), built.python_bin)
                        # bare package names in pip installs get the epoch pin
                        shell_cmd = builder_epochs.constrain_pip_install(shell_cmd, epoch)
                        await run_shell(shell_cmd, shell_env(), built.workdir)
                        continue
                    raise ImageBuildError(f"unsupported image directive: {cmd}", tail())

                if image.build_function_serialized:
                    await self._run_build_function(image, built, run_shell, shell_env, build_dir)
            return built
        finally:
            log_f.close()

    async def _run_build_function(self, image, built, run_shell, shell_env, build_dir) -> None:
        """Execute a run_function() build step with the image's python
        (reference _image.py:2175 — bake weights/caches at build time).

        #PREWARM layers (Image.prewarm, docs/COLDSTART.md): the jit entry
        points the function traces are compiled at BUILD time into the one
        persistent XLA cache every container on this host reads
        (config.compile_cache_dir), so a container launched from this image
        starts with a warm cache."""
        prewarm = any(c.strip() == "#PREWARM" for c in image.dockerfile_commands)
        if prewarm:
            os.makedirs(compile_cache_dir(), exist_ok=True)
            # cache even millisecond compiles: the whole point is that NO
            # first-input compile happens in the container
            built.env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
        payload = os.path.join(build_dir, "build_fn.pkl")
        with open(payload, "wb") as f:
            f.write(image.build_function_serialized)
        pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        telemetry_out = os.path.join(build_dir, "prewarm_compile_events.json")
        # compile-telemetry attribution (observability/device_telemetry.py):
        # the bake's compiles happen in THIS subprocess, whose registry dies
        # with it — so a prewarm runner installs the jax.monitoring hooks up
        # front (source="prewarm" via MODAL_TPU_PREWARM_BUILD below) and
        # dumps the counts for the builder to merge into the live registry
        prewarm_prelude = (
            "try:  # hooks need jax imported; a jax-less bake just skips them\n"
            "    import jax\n"
            "    from modal_tpu.observability import device_telemetry as _dt\n"
            "    _dt.install_compile_hooks()\n"
            "    # path-independent cache keys: the baked entries must hash\n"
            "    # identically in every container, not just under this rootfs\n"
            "    from modal_tpu.runtime.compile_client import normalize_cache_keys\n"
            "    normalize_cache_keys()\n"
            "except Exception:\n"
            "    pass\n"
        ) if prewarm else ""
        prewarm_epilogue = (
            "try:\n"
            "    import json as _json\n"
            "    from modal_tpu.observability.catalog import COMPILE_EVENTS as _ce\n"
            f"    open({telemetry_out!r}, 'w').write(_json.dumps(_ce.snapshot()))\n"
            "except Exception:\n"
            "    pass\n"
        ) if prewarm else ""
        runner = (
            "import sys\n"
            f"sys.path.insert(0, {pkg_root!r})\n"
            + prewarm_prelude
            + "from modal_tpu.serialization import deserialize\n"
            f"fn, (args, kwargs) = deserialize(open({payload!r}, 'rb').read(), None)\n"
            "fn(*args, **kwargs)\n"
            + prewarm_epilogue
        )
        script = os.path.join(build_dir, "build_fn.py")
        with open(script, "w") as f:
            f.write(runner)
        env = shell_env()
        if prewarm:
            # build-subprocess env only, never image env: compiles under the
            # bake count as source="prewarm", not runtime serving cost
            env["MODAL_TPU_PREWARM_BUILD"] = "1"
        await run_shell(f"{shlex.quote(built.python_bin)} {shlex.quote(script)}", env, built.workdir)
        if prewarm:
            self._merge_prewarm_compile_events(telemetry_out)
            self._publish_prewarm_cache(compile_cache_dir())

    def _publish_prewarm_cache(self, cache_dir: str) -> None:
        """Tentpole (c): push the bake's persistent-cache entries into the
        fleet compile store, so containers from OTHER images (or other
        hosts, via the blob-plane /compile routes) hit what this bake
        compiled. Keyed by filename — already jax's content-addressed key.
        Best-effort: a publish failure costs fleet hits, never the build."""
        if not cache_dir or not os.path.isdir(cache_dir):
            return
        try:
            from .compile_cache import CompileCacheStore

            published = CompileCacheStore(self.compile_store_dir).publish_dir(cache_dir)
            if published:
                logger.info(f"prewarm bake published {published} compile-cache entries to fleet store")
        except Exception as exc:  # noqa: BLE001 — never fail a build over cache publishing
            logger.warning(f"prewarm fleet-store publish skipped: {exc}")

    @staticmethod
    def _merge_prewarm_compile_events(path: str) -> None:
        """Fold the bake subprocess's compile-event counts into this
        process's registry: GET /metrics then shows how much compilation the
        prewarm paid (source="prewarm") next to what serving pays at
        runtime. Best-effort — a bake without jax writes nothing."""
        import json

        from ..observability.catalog import COMPILE_EVENTS

        try:
            with open(path) as f:
                snapshot = json.load(f)
        except (OSError, ValueError):
            return
        for key, count in snapshot.items():
            parts = str(key).split(",")
            if len(parts) != 2:
                continue
            try:
                COMPILE_EVENTS.inc(float(count), event=parts[0], source=parts[1])
            except (TypeError, ValueError):
                continue


def _unquote(v: str) -> str:
    v = v.strip()
    try:
        parts = shlex.split(v)
        return parts[0] if len(parts) == 1 else v
    except ValueError:
        return v


def _rewrite_run(cmd: str, python_bin: str) -> str:
    """Map docker-style RUN commands onto the venv backend."""
    q = shlex.quote(python_bin)
    # uv isn't assumed installed; `--system` targets the venv anyway
    cmd = re.sub(r"^uv pip install --system\b", f"{q} -m pip install", cmd)
    cmd = re.sub(r"^uv pip install\b", f"{q} -m pip install", cmd)
    cmd = re.sub(r"^python -m pip\b", f"{q} -m pip", cmd)
    cmd = re.sub(r"^pip install\b", f"{q} -m pip install", cmd)
    cmd = re.sub(r"^python\b", q, cmd)
    return cmd
