"""Configuration system.

Resolution order per setting (reference: py/modal/config.py:299-340):
env var ``MODAL_TPU_<KEY>`` → active profile section of ``~/.modal_tpu.toml``
→ default. Profiles are switched with ``MODAL_TPU_PROFILE`` or the
``active = true`` key in the TOML file.
"""

from __future__ import annotations

import logging
import os
import typing

try:
    import tomllib
except ModuleNotFoundError:  # stdlib tomllib is 3.11+; gate for 3.10 hosts
    import tomli as tomllib  # type: ignore[no-redef]
from typing import Any, Callable, Optional

user_config_path: str = os.environ.get("MODAL_TPU_CONFIG_PATH") or os.path.expanduser("~/.modal_tpu.toml")


def _read_user_config() -> dict:
    if os.path.exists(user_config_path):
        with open(user_config_path, "rb") as f:
            return tomllib.load(f)
    return {}


_user_config = _read_user_config()


def config_profiles() -> list[str]:
    return list(_user_config.keys())


def _config_active_profile() -> str:
    for key, values in _user_config.items():
        if isinstance(values, dict) and values.get("active", False) is True:
            return key
    return "default"


def config_set_active_profile(env: str) -> None:
    for key, values in _user_config.items():
        values.pop("active", None)
    if env not in _user_config:
        _user_config[env] = {}
    _user_config[env]["active"] = True
    _write_user_config(_user_config)


def _write_user_config(new_config: dict) -> None:
    # tomllib has no writer; emit the small subset we need. Strings are
    # escaped (tokens/secrets may contain quotes or backslashes — an
    # unescaped write would corrupt the file and break every later import).
    def _esc(s: str) -> str:
        return s.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")

    lines = []
    for profile, values in new_config.items():
        lines.append(f"[{profile}]")
        for k, v in values.items():
            if isinstance(v, bool):
                lines.append(f"{k} = {'true' if v else 'false'}")
            elif isinstance(v, (int, float)):
                lines.append(f"{k} = {v}")
            else:
                lines.append(f'{k} = "{_esc(str(v))}"')
        lines.append("")
    with open(user_config_path, "w") as f:
        f.write("\n".join(lines))


_profile = os.environ.get("MODAL_TPU_PROFILE") or _config_active_profile()


class _Setting(typing.NamedTuple):
    default: Any = None
    transform: Callable[[str], Any] = lambda x: x


def _to_boolean(x: Any) -> bool:
    return str(x).lower() not in ("", "0", "false", "no", "none")


_SETTINGS: dict[str, _Setting] = {
    "loglevel": _Setting("WARNING", lambda s: s.upper()),
    "log_format": _Setting("STRING", lambda s: s.upper()),
    "server_url": _Setting("grpc://127.0.0.1:9900"),
    # zero-config local mode: when the server_url is local and nothing is
    # listening, Client.from_env boots an in-process LocalSupervisor
    "auto_local_server": _Setting(True, _to_boolean),
    "input_plane_url": _Setting(""),
    "token_id": _Setting(),
    "token_secret": _Setting(),
    "task_id": _Setting(),
    "task_secret": _Setting(),
    "environment": _Setting(""),
    "default_cloud": _Setting(None, lambda x: x or None),
    "profile": _Setting(),
    "heartbeat_interval": _Setting(15.0, float),
    "function_runtime": _Setting(),
    "sync_entrypoint": _Setting(),
    "logs_timeout": _Setting(10.0, float),
    "image_id": _Setting(),
    "automount": _Setting(True, _to_boolean),
    "serve_timeout": _Setting(None, float),
    "image_builder_version": _Setting("2026.07"),
    "force_build": _Setting(False, _to_boolean),
    "traceback": _Setting(False, _to_boolean),
    "strict_parameters": _Setting(False, _to_boolean),
    "snapshot_debug": _Setting(False, _to_boolean),
    "client_retries": _Setting(True, _to_boolean),
    "worker_id": _Setting(),
    # --- TPU-native additions -------------------------------------------
    # Directory for the local single-host backend's state (images, volumes,
    # blobs, compilation cache).
    "state_dir": _Setting(os.path.expanduser("~/.modal_tpu_state")),
    # worker placement labels (matched against SchedulerPlacement)
    "worker_region": _Setting(""),
    "worker_zone": _Setting(""),
    "worker_spot": _Setting(False, _to_boolean),
    "worker_instance_type": _Setting(""),
    # jax persistent compilation cache for cold-start elimination, used where
    # JAX_COMPILATION_CACHE_DIR is not set (compile_cache_dir below). Inside
    # the checkout, at a path that never moves: the directory is what a later
    # run must find again, and a copy of the tree carries it along.
    "compilation_cache_dir": _Setting(
        os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".modal_tpu_state", "jit_cache"
        )
    ),
    # Default TPU runtime visible-device pinning behavior.
    "tpu_chip_pinning": _Setting(True, _to_boolean),
    # Local supervisor: number of simulated hosts for multi-host dev.
    "local_workers": _Setting(1, int),
    # Force JAX platform inside containers (cpu for tests, tpu in prod).
    "jax_platform": _Setting(""),
    # Warm-pool cold starts (server/warm_pool.py): baseline pre-forked
    # parked interpreters per worker for the host-venv image (0 = off; the
    # scheduler can additionally direct per-image pools via min/buffer
    # containers). Env: MODAL_TPU_WARM_POOL.
    "warm_pool": _Setting(0, int),
    # Modules a parked interpreter imports at boot (the expensive part of
    # cold start); comma-separated. Env: MODAL_TPU_WARM_POOL_PREIMPORT.
    "warm_pool_preimport": _Setting("jax"),
    # Per-module import tracing in containers (cold-start attribution;
    # events land in <task_dir>/imports.jsonl — runtime/telemetry.py).
    "import_trace": _Setting(False, _to_boolean),
    # Distributed tracing (observability/tracing.py): span JSONL sink under
    # <state_dir>/traces (or trace_dir when set); rendered by
    # `modal_tpu app trace`. On by default — spans are cheap and the sink
    # only exists where a supervisor runs.
    "trace": _Setting(True, _to_boolean),
    "trace_dir": _Setting(""),
}


class Config:
    def get(self, key: str, profile: Optional[str] = None, use_env: bool = True) -> Any:
        merged = _profile if profile is None else profile
        s = _SETTINGS[key]
        env_var_key = "MODAL_TPU_" + key.upper()
        if use_env and env_var_key in os.environ:
            return s.transform(os.environ[env_var_key])
        elif merged in _user_config and key in _user_config[merged]:
            return s.transform(_user_config[merged][key])
        else:
            return s.default

    def override_locally(self, key: str, value: str) -> None:
        # Used by snapshot-restore to re-point a restored process
        # (reference: config.override_locally, config.py).
        try:
            self.get(key)
            os.environ["MODAL_TPU_" + key.upper()] = value
        except KeyError:
            os.environ[key] = value

    def __getitem__(self, key: str) -> Any:
        return self.get(key)

    def __contains__(self, key: str) -> bool:
        return key in _SETTINGS

    def to_dict(self) -> dict[str, Any]:
        return {key: self.get(key) for key in _SETTINGS.keys()}


config = Config()


def compile_cache_dir() -> str:
    """Where every process this program starts keeps its persistent XLA
    compile cache: the `JAX_COMPILATION_CACHE_DIR` the program itself was
    started with, else the `compilation_cache_dir` setting. The worker pins
    the result into each container, sandbox and image-build environment
    AFTER the image's and the function's own env, so nothing a process
    inherits can point it at a second cache."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or config["compilation_cache_dir"]

# Configure only our own named logger — never the root logger, which belongs
# to the host application (the reference makes the same choice in
# _utils/logger.py).
logger = logging.getLogger("modal_tpu")
if not logger.handlers:
    _handler = logging.StreamHandler()
    _handler.setFormatter(logging.Formatter("%(asctime)s %(name)s %(levelname)s: %(message)s"))
    logger.addHandler(_handler)
    logger.propagate = False
logger.setLevel(config["loglevel"])


def _store_user_config(new_settings: dict, profile: Optional[str] = None) -> None:
    profile = profile or _profile
    user_config = _read_user_config()
    user_config.setdefault(profile, {}).update(**new_settings)
    _write_user_config(user_config)


def tune_switch_interval() -> None:
    """Dispatch-critical processes (supervisor, containers) lower the GIL
    switch interval from CPython's default 5 ms: every `.remote()` crosses
    threads several times (sync caller ↔ synchronizer loop; container serving
    loop ↔ main-thread executor), and each handoff can stall a full switch
    interval when both threads are runnable — at the default that is most of
    the sub-10 ms dispatch budget (ISSUE 8, docs/DISPATCH.md).
    MODAL_TPU_SWITCH_INTERVAL overrides; 0 (or malformed) leaves the
    interpreter default untouched."""
    import sys as _sys

    try:
        interval = float(os.environ.get("MODAL_TPU_SWITCH_INTERVAL", "0.001"))
    except ValueError:
        return
    if interval > 0:
        _sys.setswitchinterval(interval)
