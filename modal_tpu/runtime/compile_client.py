"""Fleet compile-cache client (ISSUE 20): a tiered layer under jax's
persistent compilation cache that makes "compile once anywhere, hit
everywhere" real for the whole fleet.

The XLA persistent cache (container_entrypoint.setup_compilation_cache,
docs/COLDSTART.md) is per-filesystem: a container that compiles something
new pays the full lowering alone and its successor on another host pays it
again. This module wraps jax's cache object with a second tier backed by
the supervisor's content-addressed compile store (server/compile_cache.py),
reachable two ways:

- **local-dir fast path** (``MODAL_TPU_COMPILE_CACHE_DIR``): co-located
  containers read the store's files in place — zero HTTP bytes, same
  trust model as the PR 8 ``MODAL_TPU_BLOB_LOCAL_DIR`` handoff.
- **HTTP** (``MODAL_TPU_COMPILE_CACHE_URL``): ``GET/PUT /compile/<key>``
  on the blob plane for containers on other hosts.

Key scheme
----------
Runtime entries are keyed by jax's own persistent-cache key — already a
digest of (serialized StableHLO module, jaxlib version, backend, compile
options incl. device topology) — so one fleet key names the same
executable everywhere, and the prewarm publisher (server/image_builder.py)
can push baked entries under ``key = cache filename`` with no recompute.
:func:`compile_cache_key` reproduces that digest contract for out-of-band
entries (tests, foreign producers): sha256 over (module bytes, jax
version, jaxlib version, backend, topology), ``xc-`` prefixed so foreign
keys can never collide with jax-native ones.

Degradation
-----------
Every failure is silent and counted, never raised: knob off / no
coordinates / unreachable service / corrupt entry → the local persistent
cache alone, bit-identical behavior. A corrupt fleet entry (integrity
sidecar mismatch) is evicted (DELETE / unlink) so one torn write cannot
poison the fleet forever. After ``_MAX_CONSECUTIVE_ERRORS`` transport
failures the HTTP tier stops trying for ``_ERROR_COOLDOWN_S`` so a dead
service costs one timeout, not one per compile.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Optional

from .._utils.compile_keys import compile_cache_key, entry_digest, sanitize_key

__all__ = [
    "ENV_DIR",
    "ENV_GATE",
    "ENV_URL",
    "FleetCompileCache",
    "TieredJaxCache",
    "compile_cache_key",
    "entry_digest",
    "fleet_cache_enabled",
    "install_fleet_cache",
    "normalize_cache_keys",
    "sanitize_key",
    "uninstall_fleet_cache",
]

ENV_GATE = "MODAL_TPU_COMPILE_CACHE"  # 0 → local-only compile (feature gate)
ENV_URL = "MODAL_TPU_COMPILE_CACHE_URL"  # blob-plane base url (http://host:port)
ENV_DIR = "MODAL_TPU_COMPILE_CACHE_DIR"  # co-located store dir (fast path)

_MAX_CONSECUTIVE_ERRORS = 3
_ERROR_COOLDOWN_S = 30.0
_HTTP_TIMEOUT_S = 5.0

_install_lock = threading.Lock()


def fleet_cache_enabled() -> bool:
    """The ISSUE 20 feature gate: ``MODAL_TPU_COMPILE_CACHE=0`` disables the
    fleet tier entirely (local persistent cache only)."""
    return os.environ.get(ENV_GATE, "1").strip().lower() not in ("0", "false", "no", "off")


def _count(event: str, source: str) -> None:
    """Feed both counter planes: the existing compile-events family (the
    acceptance-criterion signal: source=fleet hits/misses) and the dedicated
    compile-cache families by transport."""
    try:
        from ..observability.catalog import (
            COMPILE_CACHE_HITS,
            COMPILE_CACHE_MISSES,
            COMPILE_CACHE_PUTS,
            COMPILE_EVENTS,
        )

        if event == "hit":
            COMPILE_CACHE_HITS.inc(source=source)
            COMPILE_EVENTS.inc(event="cache_hit", source="fleet")
        elif event == "miss":
            COMPILE_CACHE_MISSES.inc(source=source)
            COMPILE_EVENTS.inc(event="cache_miss", source="fleet")
        elif event == "put":
            COMPILE_CACHE_PUTS.inc(source=source)
    except Exception:  # noqa: BLE001 — metrics must never break the compile path
        pass


def _count_error(kind: str) -> None:
    try:
        from ..observability.catalog import COMPILE_CACHE_ERRORS

        COMPILE_CACHE_ERRORS.inc(kind=kind)
    except Exception:  # noqa: BLE001
        pass


class FleetCompileCache:
    """The fleet tier: get/put bytes by key against the shared store, local
    dir first, HTTP second, silence on every failure. Pure stdlib — usable
    (and tested) without jax in the process."""

    def __init__(self, url: str = "", local_dir: str = "", timeout_s: float = _HTTP_TIMEOUT_S):
        self.url = url.rstrip("/")
        self.local_dir = local_dir
        self.timeout_s = timeout_s
        self._consecutive_errors = 0
        self._cooldown_until = 0.0
        self._lock = threading.Lock()

    @classmethod
    def from_env(cls) -> Optional["FleetCompileCache"]:
        """None when the gate is off or no coordinates are configured — the
        caller then leaves jax's cache untouched (pure local behavior)."""
        if not fleet_cache_enabled():
            return None
        url = os.environ.get(ENV_URL, "").strip()
        local_dir = os.environ.get(ENV_DIR, "").strip()
        if local_dir and not os.path.isdir(local_dir):
            # stat-verify like the blob fast path: a stale env var from a
            # dead supervisor must not break every lookup
            local_dir = ""
        if not url and not local_dir:
            return None
        return cls(url=url, local_dir=local_dir)

    # -- transport error budget ------------------------------------------

    def _http_usable(self) -> bool:
        return bool(self.url) and time.monotonic() >= self._cooldown_until

    def _note_http_error(self) -> None:
        with self._lock:
            self._consecutive_errors += 1
            if self._consecutive_errors >= _MAX_CONSECUTIVE_ERRORS:
                self._cooldown_until = time.monotonic() + _ERROR_COOLDOWN_S
                self._consecutive_errors = 0
        _count_error("unreachable")

    def _note_http_ok(self) -> None:
        with self._lock:
            self._consecutive_errors = 0

    # -- local-dir fast path ---------------------------------------------

    def _local_get(self, key: str) -> Optional[bytes]:
        path = os.path.join(self.local_dir, key)
        try:
            with open(path, "rb") as f:
                data = f.read()
        except OSError:
            return None
        expect = self._local_sidecar(key)
        if expect and entry_digest(data) != expect:
            # torn/corrupt entry: evict so the fleet heals instead of
            # serving the same bad bytes forever
            self._local_evict(key)
            _count_error("corrupt")
            return None
        return data

    def _local_sidecar(self, key: str) -> str:
        try:
            with open(os.path.join(self.local_dir, key + ".sha256")) as f:
                return f.read().strip()
        except OSError:
            return ""

    def _local_evict(self, key: str) -> None:
        for suffix in ("", ".sha256"):
            try:
                os.unlink(os.path.join(self.local_dir, key + suffix))
            except OSError:
                pass

    def _local_put(self, key: str, data: bytes) -> bool:
        # same atomic tmp+replace discipline as the server store: concurrent
        # identical PUTs race to an identical final state (idempotent)
        path = os.path.join(self.local_dir, key)
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            with open(tmp, "wb") as f:
                f.write(data)
            os.replace(tmp, path)
            with open(f"{path}.sha256.tmp.{os.getpid()}", "w") as f:
                f.write(entry_digest(data))
            os.replace(f"{path}.sha256.tmp.{os.getpid()}", path + ".sha256")
            return True
        except OSError:
            return False

    # -- HTTP path --------------------------------------------------------

    def _http_get(self, key: str) -> Optional[bytes]:
        import urllib.error
        import urllib.request

        try:
            with urllib.request.urlopen(
                f"{self.url}/compile/{key}", timeout=self.timeout_s
            ) as resp:
                data = resp.read()
                expect = resp.headers.get("X-Content-SHA256", "")
        except urllib.error.HTTPError as exc:
            exc.close()
            self._note_http_ok()  # the service answered; 404 is a clean miss
            return None
        except Exception:  # noqa: BLE001 — conn refused/timeout/reset
            self._note_http_error()
            return None
        self._note_http_ok()
        if expect and entry_digest(data) != expect:
            self._http_evict(key)
            _count_error("corrupt")
            return None
        return data

    def _http_evict(self, key: str) -> None:
        import urllib.request

        try:
            req = urllib.request.Request(f"{self.url}/compile/{key}", method="DELETE")
            urllib.request.urlopen(req, timeout=self.timeout_s).close()
        except Exception:  # noqa: BLE001 — eviction is best-effort
            pass

    def _http_put(self, key: str, data: bytes) -> bool:
        import urllib.request

        try:
            req = urllib.request.Request(
                f"{self.url}/compile/{key}",
                data=data,
                method="PUT",
                headers={"X-Content-SHA256": entry_digest(data)},
            )
            urllib.request.urlopen(req, timeout=self.timeout_s).close()
        except Exception:  # noqa: BLE001
            self._note_http_error()
            return False
        self._note_http_ok()
        return True

    # -- public api --------------------------------------------------------

    def get(self, key: str) -> Optional[bytes]:
        key = sanitize_key(key)
        if not key:
            return None
        if self.local_dir:
            data = self._local_get(key)
            if data is not None:
                _count("hit", "local_dir")
                return data
        if self._http_usable():
            data = self._http_get(key)
            if data is not None:
                _count("hit", "http")
                if self.local_dir:
                    self._local_put(key, data)  # warm the co-located store
                return data
        _count("miss", "local_dir" if self.local_dir else "http")
        return None

    def put(self, key: str, data: bytes) -> bool:
        key = sanitize_key(key)
        if not key or not isinstance(data, (bytes, bytearray, memoryview)):
            return False
        data = bytes(data)
        ok = False
        if self.local_dir and self._local_put(key, data):
            _count("put", "local_dir")
            ok = True
        # the local dir IS the supervisor's store (worker exports the state
        # sibling): when it took the write, skip the redundant HTTP round trip
        if not ok and self._http_usable() and self._http_put(key, data):
            _count("put", "http")
            ok = True
        return ok


class TieredJaxCache:
    """The object installed as jax's ``compilation_cache._cache``: local
    persistent cache first (a hit there is jax behaving exactly as before),
    fleet tier on local miss; puts land in both so this container's compile
    becomes everyone's hit. Implements the CacheInterface shape jax's
    ``get/put_executable_and_time`` call into; entry bytes pass through
    verbatim (jax's own zstd framing), so the fleet store stays
    format-agnostic."""

    def __init__(self, inner, fleet: FleetCompileCache):
        self._inner = inner
        self._fleet = fleet
        inner_path = getattr(inner, "_path", None)
        if inner_path is None:
            import pathlib

            inner_path = pathlib.Path(fleet.local_dir or "/fleet-compile-cache")
        self._path = inner_path

    def get(self, key: str) -> Optional[bytes]:
        value = None
        if self._inner is not None:
            try:
                value = self._inner.get(key)
            except Exception:  # noqa: BLE001 — a broken local cache must not kill jit
                value = None
        if value is not None:
            return value
        try:
            value = self._fleet.get(key)
        except Exception:  # noqa: BLE001 — the fleet tier never raises into jax
            return None
        if value is not None and self._inner is not None:
            try:
                self._inner.put(key, value)  # next restart on this fs hits locally
            except Exception:  # noqa: BLE001
                pass
        return value

    def put(self, key: str, value: bytes) -> None:
        if self._inner is not None:
            try:
                self._inner.put(key, value)
            except Exception:  # noqa: BLE001
                pass
        try:
            self._fleet.put(key, value)
        except Exception:  # noqa: BLE001
            pass


def normalize_cache_keys() -> None:
    """Make jax's cache keys path-independent so they match across the fleet.

    jax's ``jax_persistent_cache_enable_xla_caches`` defaults to
    ``xla_gpu_per_fusion_autotune_cache_dir``, which bakes the *absolute
    path* of the local persistent-cache dir into
    ``debug_options.xla_gpu_per_fusion_autotune_cache_dir`` — and debug
    options are hashed into the cache key. Two containers with different
    local cache paths then mint different keys for identical programs and
    the fleet store never hits. The autotune cache is a GPU-only feature;
    clearing the flag costs nothing on TPU/CPU and restores deterministic
    keys. An explicit user env override wins (they asked for it)."""
    if os.environ.get("JAX_PERSISTENT_CACHE_ENABLE_XLA_CACHES") is not None:
        return
    import jax

    jax.config.update("jax_persistent_cache_enable_xla_caches", "")


def install_fleet_cache() -> bool:
    """Wrap jax's persistent compilation cache with the fleet tier.

    Idempotent and lazy like install_compile_hooks: a no-op (False) until
    user code has imported jax — this must never be the call that pays the
    jax import bill — and a no-op when the gate is off or no fleet
    coordinates are configured. Called from the heartbeat path
    (device_telemetry.container_report), the container @enter path, and the
    AOT lowering hook (runtime/aot.py)."""
    fleet = FleetCompileCache.from_env()
    if fleet is None:
        return False
    import sys

    if "jax" not in sys.modules:
        return False
    # jax 0.9.0's private cache module (the one installation this repo runs
    # on): a rename there must raise here, not silently drop the fleet tier
    from jax._src import compilation_cache as cc

    normalize_cache_keys()
    with _install_lock:
        current = cc._cache
        if isinstance(current, TieredJaxCache):
            return True
        if current is None:
            # force jax's own (possibly dir-less) initialization first so
            # we wrap whatever local cache it would have used
            cc._initialize_cache()
            current = cc._cache
        cc._cache = TieredJaxCache(current, fleet)
        with cc._cache_initialized_mutex:
            cc._cache_initialized = True
    return True


def uninstall_fleet_cache() -> None:
    """Test hook: restore jax's own cache object (the wrapped inner)."""
    import sys

    if "jax" not in sys.modules:
        return
    from jax._src import compilation_cache as cc

    with _install_lock:
        current = cc._cache
        if isinstance(current, TieredJaxCache):
            cc._cache = current._inner
