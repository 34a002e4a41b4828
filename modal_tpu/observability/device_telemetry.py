"""Device + compile telemetry: HBM gauges, XLA compile events, step times.

Everything here is jax-optional: importing this module never imports jax;
each hook degrades to a no-op when jax (or a live backend) is absent, so the
control plane — which never touches jax — can still render the metric
families with zero samples.

Three instruments (catalog.py):

- ``modal_tpu_device_memory_bytes{device,kind}``: live per-device memory
  gauges from ``Device.memory_stats()`` (``bytes_in_use`` / ``bytes_limit``
  on TPU; CPU backends report no stats and fall back to a process-RSS
  ``host`` sample). Sampled by ``sample_device_memory()`` — containers call
  it from the heartbeat path, loops call it per step batch.
- ``modal_tpu_compile_events_total{event}`` + ``modal_tpu_compile_seconds``:
  hooked off ``jax.monitoring`` — the channel the XLA compilation cache
  (and the warm-pool `Image.prewarm` bake) reports through. Cache hits/
  misses attribute cold starts honestly: a prewarmed image shows hits with
  zero ``backend_compile`` durations (docs/COLDSTART.md).
- ``modal_tpu_step_seconds{kind}``: train/decode step-time histograms,
  observed by the step loops (parallel/train.py, models/sampling.py).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Optional

_install_lock = threading.Lock()
_installed = False

# jax.monitoring event names (jax 0.9.0: _src/compiler.py,
# _src/compilation_cache.py, _src/dispatch.py) -> our compile-event label.
# cache_misses is recorded where jax WRITES an entry, so a compile below
# JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS counts as neither hit nor miss.
_EVENT_MAP = {
    "/jax/compilation_cache/cache_hits": "cache_hit",
    "/jax/compilation_cache/cache_misses": "cache_miss",
    "/jax/compilation_cache/task_disabled_cache": "cache_disabled",
}
_DURATION_MAP = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}


def _compile_source() -> str:
    """Attribution label: compiles during an `Image.prewarm` build are the
    warm-pool bake, not serving-path cost (MODAL_TPU_PREWARM_BUILD is set by
    the image builder's prewarm step)."""
    return "prewarm" if os.environ.get("MODAL_TPU_PREWARM_BUILD") else "runtime"


def install_compile_hooks() -> bool:
    """Register jax.monitoring listeners feeding the compile counters and
    duration histograms. Idempotent; returns False when jax is unavailable."""
    global _installed
    with _install_lock:
        if _installed:
            return True
        import sys

        if "jax" not in sys.modules:
            # never be the reason jax gets imported: a no-op container's cold
            # start must not pay the jax import bill for telemetry hooks —
            # callers retry once user code has pulled jax in (heartbeat path)
            return False
        from jax import monitoring

        from .catalog import COMPILE_EVENTS, COMPILE_SECONDS

        def _on_event(event: str, **kw) -> None:
            try:
                label = _EVENT_MAP.get(event) or ("other" if "compil" in event else None)
                if label is not None:
                    COMPILE_EVENTS.inc(event=label, source=_compile_source())
            except Exception:  # noqa: BLE001 — a metrics bug must not break jit
                pass

        def _on_duration(event: str, duration: float, **kw) -> None:
            try:
                label = _DURATION_MAP.get(event)
                if label is not None:
                    COMPILE_SECONDS.observe(float(duration), phase=label)
                    if label == "backend_compile":
                        COMPILE_EVENTS.inc(event="compile", source=_compile_source())
            except Exception:  # noqa: BLE001
                pass

        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _installed = True
        return True


def maybe_install_fleet_cache() -> bool:
    """Attach the fleet compile-cache tier under jax's persistent cache
    (ISSUE 20, runtime/compile_client.py). Same lazy contract as
    install_compile_hooks: a no-op until user code has imported jax, a no-op
    when the MODAL_TPU_COMPILE_CACHE gate is off or no fleet coordinates are
    set. A failure is logged and the container carries on with the local
    cache — caching must never be the reason a container errors."""
    try:
        from ..runtime.compile_client import install_fleet_cache

        return install_fleet_cache()
    except Exception as exc:  # noqa: BLE001 — degrade to local-only compile, loudly
        from ..config import logger

        logger.warning(f"fleet compile cache not installed: {type(exc).__name__}: {exc}")
        return False


_last_sample_t = 0.0


def sample_device_memory(min_interval_s: float = 0.0) -> int:
    """Refresh the per-device memory gauges; returns the number of devices
    sampled. Safe to call from hot paths with `min_interval_s` throttling.
    Only samples when a jax backend is ALREADY initialized — this must never
    be the call that pays (or misconfigures) backend init."""
    global _last_sample_t
    now = time.monotonic()
    if min_interval_s and now - _last_sample_t < min_interval_s:
        return 0
    import sys

    jax = sys.modules.get("jax")
    if jax is None:
        return 0
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return 0
    from .catalog import DEVICE_MEMORY_BYTES

    _last_sample_t = now
    n = 0
    try:
        devices = jax.local_devices()
    except Exception:  # noqa: BLE001 — backend init failed
        return 0
    for d in devices:
        try:
            stats = d.memory_stats() or {}
        except Exception:  # noqa: BLE001 — CPU backends raise/return None
            stats = {}
        label = f"{d.platform}:{d.id}"
        if stats:
            for key, kind in (
                ("bytes_in_use", "bytes_in_use"),
                ("bytes_limit", "bytes_limit"),
                ("peak_bytes_in_use", "peak_bytes_in_use"),
            ):
                if key in stats:
                    DEVICE_MEMORY_BYTES.set(float(stats[key]), device=label, kind=kind)
            n += 1
    if n == 0 and devices:
        # no per-device stats (CPU backend): record LIVE host RSS so the
        # family still answers "how much memory is this worker using" — not
        # ru_maxrss, whose lifetime-peak semantics can never decrease (the
        # PEAK_RSS_BYTES gauge already covers peaks)
        rss = _live_rss_bytes()
        if rss:
            DEVICE_MEMORY_BYTES.set(float(rss), device="host", kind="rss")
            n = 1
    return n


def _live_rss_bytes() -> int:
    """Current (not peak) resident set size; 0 when unreadable (non-Linux)."""
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * os.sysconf("SC_PAGESIZE")
    except (OSError, ValueError, IndexError):
        return 0


def observe_step_time(seconds: float, kind: str) -> None:
    """Step-time histogram sample (kind: train | decode | prefill)."""
    from .catalog import STEP_SECONDS

    STEP_SECONDS.observe(max(0.0, float(seconds)), kind=kind)


class StepTimer:
    """Context/loop helper: stamps one step-time sample per `mark()`.

    >>> timer = StepTimer("decode")
    >>> for _ in range(steps): run_step(); timer.mark()
    """

    def __init__(self, kind: str):
        self.kind = kind
        self._t = time.perf_counter()

    def mark(self) -> float:
        now = time.perf_counter()
        dt = now - self._t
        self._t = now
        observe_step_time(dt, self.kind)
        return dt


# families a container pushes to the control plane over ContainerHeartbeat
# (metrics.export_families / merge_families)
PUSH_FAMILIES = (
    "modal_tpu_device_memory_bytes",
    "modal_tpu_compile_events_total",
    "modal_tpu_compile_seconds",
    # fleet compile cache (ISSUE 20, docs/COLDSTART.md): per-container
    # hit/miss/put/error counters delta-merge per task on the supervisor, so
    # `modal_tpu metrics` answers "did that rollout compile anything?"
    "modal_tpu_compile_cache_hits_total",
    "modal_tpu_compile_cache_misses_total",
    "modal_tpu_compile_cache_puts_total",
    "modal_tpu_compile_cache_errors_total",
    "modal_tpu_step_seconds",
    "modal_tpu_profiler_samples_total",
    # serving tier (docs/SERVING.md): the SLO signals the scheduler sizes
    # serving replicas on ride the same heartbeat plane. Histograms/counters
    # delta-merge; the p95/tokens-per-s/queue gauges are latest-wins on the
    # supervisor registry — the SCHEDULER reads each task's raw pushed
    # report (TaskState_.telemetry_prev_json), so scaling stays per-replica
    # even when the merged gauge view collapses to one writer.
    "modal_tpu_serving_ttft_seconds",
    "modal_tpu_serving_ttft_p95_seconds",
    "modal_tpu_serving_tokens_per_second",
    "modal_tpu_serving_tokens_total",
    "modal_tpu_serving_queue_depth",
    "modal_tpu_serving_batch_occupancy",
    "modal_tpu_serving_requests_total",
    "modal_tpu_kv_pages_allocated",
    "modal_tpu_kv_pages_free",
    # ISSUE 12 serving depth: prefix-cache effectiveness and speculative
    # acceptance per replica (counters delta-merge; the accept-ratio gauge
    # is per-replica in each task's raw report, latest-wins when merged) —
    # `modal_tpu top` renders hit% and accept from the same pushed report
    "modal_tpu_serving_prefix_cache_hits_total",
    "modal_tpu_serving_prefix_cache_misses_total",
    "modal_tpu_serving_spec_accept_ratio",
    "modal_tpu_serving_sampled_tokens_total",
    "modal_tpu_kv_pages_cow_copies_total",
    # ISSUE 18 fleet: the role gauge lets `modal_tpu top` and the role-aware
    # autoscaler tell prefill/decode/both replicas apart; shipment counters
    # make disaggregation traffic first-class per replica
    "modal_tpu_serving_role",
    "modal_tpu_kv_pages_shipped_total",
    "modal_tpu_kv_ship_seconds",
    # the router's dispatch counter rides too: a router-tier container's
    # heartbeat then carries its routed-by-reason split
    "modal_tpu_serving_router_routed_total",
)


def pushed_gauge(report: dict, name: str) -> Optional[float]:
    """Read one gauge family out of a pushed heartbeat report (the
    export_families JSON shape): the sum across its series, None when the
    family is absent or carries nothing numeric. The ONE parser for the
    per-task report — the SLO autoscaler (scheduler._serving_report) and the
    `modal_tpu top` replica table (server/history.py) must read identical
    values or 'top shows what scaling sees' stops being true."""
    series = (report.get(name) or {}).get("series")
    if not isinstance(series, dict):
        return None
    vals = []
    for v in series.values():
        try:
            vals.append(float(v))
        except (TypeError, ValueError):
            continue
    return sum(vals) if vals else None


def container_report() -> str:
    """The heartbeat payload: sample device memory, then export the push
    whitelist as compact JSON ('' when there is nothing to report)."""
    import json

    # hooks attach lazily: the first report after user code imported jax
    install_compile_hooks()
    maybe_install_fleet_cache()
    sample_device_memory(min_interval_s=5.0)
    from .metrics import export_families

    report = export_families(PUSH_FAMILIES)
    if not report:
        return ""
    try:
        return json.dumps(report, separators=(",", ":"))
    except (TypeError, ValueError):
        return ""


def _scope_device_series(report: dict, task_id: str) -> dict:
    """Prefix the device label with the pushing task's id: every container
    reports its own process-local view of the same physical devices (or the
    'host' RSS fallback), so unscoped gauges from two live containers would
    overwrite each other latest-wins. Bounded by the registry's MAX_SERIES
    overflow cap."""
    if not task_id or not isinstance(report, dict):
        return report
    family = report.get("modal_tpu_device_memory_bytes")
    if not isinstance(family, dict) or not isinstance(family.get("series"), dict):
        return report
    scoped = dict(report)
    scoped["modal_tpu_device_memory_bytes"] = {
        **family,
        "series": {f"{task_id}/{key}": v for key, v in family["series"].items()},
    }
    return scoped


def drop_task_device_series(task_id: str) -> int:
    """Forget a finished task's device-memory gauge series (the task-scoped
    keys `_scope_device_series` created): without this, a long-lived
    supervisor leaks ~devices×kinds series per task until the family hits
    MAX_SERIES and collapses into __overflow__, and dead tasks' stale HBM
    values render on GET /metrics forever. Returns the series dropped."""
    if not task_id:
        return 0
    from .catalog import DEVICE_MEMORY_BYTES

    prefix = f"{task_id}/"
    m = DEVICE_MEMORY_BYTES
    with m._lock:
        victims = [k for k in m._series if k and str(k[0]).startswith(prefix)]
        for k in victims:
            del m._series[k]
    return len(victims)


def merge_container_report(telemetry_json: str, prev_json: str = "", task_id: str = "") -> str:
    """Control-plane side: merge one container's pushed report (deltas vs the
    task's previous push; device gauges scoped per task). Returns the raw
    report to store as the new `prev`. Malformed payloads merge nothing and
    clear the stored prev."""
    import json

    if not telemetry_json:
        return prev_json
    try:
        report = json.loads(telemetry_json)
        prev = json.loads(prev_json) if prev_json else None
    except ValueError:
        return ""
    from .metrics import merge_families

    merge_families(_scope_device_series(report, task_id), prev)
    return telemetry_json


def telemetry_summary() -> dict:
    """Compact roll-up (bench.py, /v1/stats): compile counts and seconds +
    step p50s, when any."""
    from .catalog import (
        COMPILE_CACHE_HITS,
        COMPILE_CACHE_MISSES,
        COMPILE_CACHE_PUTS,
        COMPILE_EVENTS,
        COMPILE_SECONDS,
        STEP_SECONDS,
    )

    out: dict = {}
    if COMPILE_EVENTS.total():
        out["compile_events"] = dict(COMPILE_EVENTS.snapshot())
    fleet = {
        "hits": COMPILE_CACHE_HITS.total(),
        "misses": COMPILE_CACHE_MISSES.total(),
        "puts": COMPILE_CACHE_PUTS.total(),
    }
    if any(fleet.values()):
        out["compile_cache"] = fleet
    if COMPILE_SECONDS.count_total():
        out["compile_p50_s"] = COMPILE_SECONDS.quantile(0.5)
        # {phase: {count, sum}}: backend_compile sums what this process spent
        # compiling, cache_retrieval what it spent reading the persistent cache
        out["compile_seconds"] = COMPILE_SECONDS.snapshot()
    if STEP_SECONDS.count_total():
        out["step_p50_s"] = STEP_SECONDS.quantile(0.5)
    return out
