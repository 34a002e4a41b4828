"""Distributed tracing: span model, context propagation, JSONL sink.

No third-party deps (no opentelemetry in the image) — the span model is the
minimal subset every tracing UI understands: trace_id/span_id/parent_id,
name, start/end wall-clock seconds, string attrs, timestamped events.

Propagation path for one `.remote()` call:

    client `function.call` root span
      → x-modal-tpu-trace-id / x-modal-tpu-span-id gRPC metadata
        (client interceptor, _utils/grpc_utils.py)
      → server handler span (proto/rpc.py instrumented handler)
      → InputState.trace_context (services._enqueue_input)
      → FunctionGetInputsItem.trace_context → container io_manager
      → MODAL_TPU_TRACE_CONTEXT / MODAL_TPU_TRACE_T0 env (scheduler →
        worker → container boot spans)

Sink: one ``spans-<pid>.jsonl`` per process under the trace dir (the
supervisor's ``<state_dir>/traces``; containers inherit it via
``MODAL_TPU_TRACE_DIR``). Appends are line-atomic, so many processes can
share the directory; `modal_tpu app trace` globs all of them.

When no sink is configured, spans still *propagate* (ids are generated and
carried on the wire — a remote process with a sink can record its half) but
nothing is written locally: the hot path stays allocation-cheap.
"""

from __future__ import annotations

import atexit
import json
import os
import threading
import time
import uuid
from contextlib import contextmanager
from contextvars import ContextVar
from dataclasses import dataclass, field
from typing import Any, Iterator, Optional

TRACE_ID_METADATA_KEY = "x-modal-tpu-trace-id"
SPAN_ID_METADATA_KEY = "x-modal-tpu-span-id"
TRACE_DIR_ENV = "MODAL_TPU_TRACE_DIR"
TRACE_CONTEXT_ENV = "MODAL_TPU_TRACE_CONTEXT"
TRACE_T0_ENV = "MODAL_TPU_TRACE_T0"


def new_trace_id() -> str:
    return uuid.uuid4().hex


def new_span_id() -> str:
    return uuid.uuid4().hex[:16]


@dataclass(frozen=True)
class SpanContext:
    trace_id: str
    span_id: str


@dataclass
class Span:
    trace_id: str
    span_id: str
    parent_id: str
    name: str
    start: float
    end: float = 0.0
    attrs: dict[str, Any] = field(default_factory=dict)
    events: list[dict] = field(default_factory=list)
    status: str = "ok"
    # monotonic stamp paired with the wall-clock start: within one process
    # it preserves true creation order even when wall timestamps collide or
    # step backwards (NTP) — the waterfall orders by (normalized start,
    # tree depth, mono) so children never render before parents
    mono: float = field(default_factory=time.monotonic)

    @property
    def context(self) -> SpanContext:
        return SpanContext(self.trace_id, self.span_id)

    def set_attr(self, key: str, value: Any) -> None:
        self.attrs[key] = value

    def add_event(self, name: str, **attrs: Any) -> None:
        self.events.append({"name": name, "t": time.time(), **attrs})

    def to_dict(self) -> dict:
        return {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "start": self.start,
            "end": self.end,
            "status": self.status,
            "attrs": self.attrs,
            "events": self.events,
            "mono": self.mono,
        }


# -- sink ---------------------------------------------------------------------

_sink_lock = threading.Lock()
_sink_file = None
_sink_dir: Optional[str] = None
_sink_bytes = 0

# retention (ISSUE 7 satellite): spans files rotate at this size so a
# long-lived supervisor can't grow one file without bound; ONE rotated
# generation (.jsonl.1) is kept per pid, and gc_trace_dir prunes the store
# (supervisor boot + `modal_tpu trace gc`)
TRACE_MAX_BYTES_ENV = "MODAL_TPU_TRACE_MAX_BYTES"
DEFAULT_SINK_MAX_BYTES = 64 * 1024 * 1024
DEFAULT_STORE_MAX_BYTES = 256 * 1024 * 1024
DEFAULT_STORE_MAX_AGE_S = 7 * 24 * 3600.0
# gc never evicts a LIVE (non-rotated) file written within this window: the
# pid in the filename may belong to ANOTHER process (a running supervisor or
# container) whose open sink an unlink would silently sever
LIVE_SINK_GRACE_S = 300.0


def _sink_max_bytes() -> int:
    try:
        return int(os.environ.get(TRACE_MAX_BYTES_ENV, DEFAULT_SINK_MAX_BYTES))
    except ValueError:
        return DEFAULT_SINK_MAX_BYTES


def configure(trace_dir: str) -> None:
    """Point the process-wide sink at `trace_dir` (created if missing).
    Deliberately does NOT touch os.environ: MODAL_TPU_TRACE_DIR doubles as
    the operator's config override (config.py `trace_dir`), so exporting it
    here would pin every later supervisor in this process to the first
    sink. The worker passes the dir to container processes explicitly."""
    global _sink_file, _sink_dir, _sink_bytes
    with _sink_lock:
        if _sink_dir == trace_dir and _sink_file is not None:
            return
        if _sink_file is not None:
            try:
                _sink_file.close()
            except OSError:
                pass
            _sink_file = None
        os.makedirs(trace_dir, exist_ok=True)
        path = os.path.join(trace_dir, f"spans-{os.getpid()}.jsonl")
        _sink_file = open(path, "a", buffering=1)
        try:
            _sink_bytes = os.path.getsize(path)
        except OSError:
            _sink_bytes = 0
        _sink_dir = trace_dir


def _rotate_locked() -> None:
    """Size-capped rotation (called with _sink_lock held): the open file
    becomes `spans-<pid>.jsonl.1` (replacing the previous generation) and a
    fresh file takes appends — bounded disk, at most one generation lost."""
    global _sink_file, _sink_bytes
    if _sink_file is None or _sink_dir is None:
        return
    path = os.path.join(_sink_dir, f"spans-{os.getpid()}.jsonl")
    try:
        _sink_file.close()
    except OSError:
        pass
    try:
        os.replace(path, path + ".1")
    except OSError:
        pass
    try:
        _sink_file = open(path, "a", buffering=1)
        _sink_bytes = 0
    except OSError:
        _sink_file = None


def maybe_configure_from_env() -> None:
    """Container-side hook: adopt the trace dir the worker exported."""
    trace_dir = os.environ.get(TRACE_DIR_ENV)
    if trace_dir:
        try:
            configure(trace_dir)
        except OSError:
            pass


def enabled() -> bool:
    return _sink_file is not None


def trace_dir() -> Optional[str]:
    return _sink_dir


def _shutdown() -> None:
    global _sink_file
    with _sink_lock:
        if _sink_file is not None:
            try:
                _sink_file.flush()
                _sink_file.close()
            except OSError:
                pass
            _sink_file = None


atexit.register(_shutdown)


# in-process span observers (ISSUE 17: the flight recorder's span tail) —
# invoked before the sink check so a process with no configured sink still
# feeds its black-box ring
_span_taps: list = []


def add_span_tap(tap) -> None:
    if tap not in _span_taps:
        _span_taps.append(tap)


def remove_span_tap(tap) -> None:
    try:
        _span_taps.remove(tap)
    except ValueError:
        pass


# what writing spans to the sink cost this process (ISSUE 26; `/v1/stats`
# `tracing.span_write_seconds`): the seconds every thread spent in `_write`
# on spans that reached the sink's file, taps included. Updated under the
# sink's lock; a process with no sink pays nothing for it.
_span_write_seconds = 0.0


def span_write_seconds() -> float:
    return _span_write_seconds


def _write(span: Span) -> None:
    global _sink_bytes, _span_write_seconds
    taps = list(_span_taps)
    if _sink_file is None and not taps:
        return
    started = time.perf_counter()
    for tap in taps:
        try:
            tap(span)
        except Exception:
            pass
    if _sink_file is None:
        return
    try:
        line = json.dumps(span.to_dict(), default=str)
    except (TypeError, ValueError):
        return
    with _sink_lock:
        if _sink_file is not None:
            try:
                _sink_file.write(line + "\n")
                _sink_bytes += len(line) + 1
                if _sink_bytes >= _sink_max_bytes():
                    _rotate_locked()
                _span_write_seconds += time.perf_counter() - started
            except (OSError, ValueError):
                pass


# -- context ------------------------------------------------------------------

_current_span: ContextVar[Optional[Span]] = ContextVar("modal_tpu_span", default=None)
# context extracted from the wire (server side) with no local span open yet
_remote_context: ContextVar[Optional[SpanContext]] = ContextVar(
    "modal_tpu_remote_span_ctx", default=None
)


def current_span() -> Optional[Span]:
    return _current_span.get()


def current_context() -> Optional[SpanContext]:
    span = _current_span.get()
    if span is not None:
        return span.context
    return _remote_context.get()


def add_event(name: str, **attrs: Any) -> None:
    """Attach an event to the current span, if any (retries, circuit-breaker
    opens, chaos injections). No-op outside a span — callers never gate."""
    span = _current_span.get()
    if span is not None:
        span.add_event(name, **attrs)


def set_attr(key: str, value: Any) -> None:
    span = _current_span.get()
    if span is not None:
        span.set_attr(key, value)


@contextmanager
def span(
    name: str,
    attrs: Optional[dict] = None,
    parent: Optional[SpanContext] = None,
    start: Optional[float] = None,
) -> Iterator[Span]:
    """Open a span as the current one; written to the sink on exit. Parent
    resolution: explicit `parent` → current span → wire-extracted remote
    context → new root trace."""
    ctx = parent or current_context()
    sp = Span(
        trace_id=ctx.trace_id if ctx else new_trace_id(),
        span_id=new_span_id(),
        parent_id=ctx.span_id if ctx else "",
        name=name,
        start=start if start is not None else time.time(),
        attrs=dict(attrs or {}),
    )
    token = _current_span.set(sp)
    try:
        yield sp
    except BaseException as exc:
        sp.status = "error"
        sp.attrs.setdefault("error", f"{type(exc).__name__}: {exc}"[:300])
        raise
    finally:
        _current_span.reset(token)
        sp.end = time.time()
        _write(sp)


def open_span(
    name: str,
    parent: Optional[SpanContext] = None,
    start: Optional[float] = None,
    attrs: Optional[dict] = None,
) -> Span:
    """Manually managed span (close with `close_span`) for long sections that
    don't nest cleanly in a `with` block — e.g. container boot, whose children
    (imports, enter hooks) need its span id before it ends."""
    ctx = parent or current_context()
    return Span(
        trace_id=ctx.trace_id if ctx else new_trace_id(),
        span_id=new_span_id(),
        parent_id=ctx.span_id if ctx else "",
        name=name,
        start=start if start is not None else time.time(),
        attrs=dict(attrs or {}),
    )


def close_span(span: Span, status: str = "ok") -> None:
    span.end = time.time()
    span.status = status
    _write(span)


def record_span(
    name: str,
    start: float,
    end: float,
    parent: Optional[SpanContext] = None,
    attrs: Optional[dict] = None,
) -> None:
    """Record a retroactive span (e.g. queue wait, measured at claim time
    from the input's enqueue timestamp)."""
    ctx = parent or current_context()
    if ctx is None:
        return
    _write(
        Span(
            trace_id=ctx.trace_id,
            span_id=new_span_id(),
            parent_id=ctx.span_id,
            name=name,
            start=start,
            end=end,
            attrs=dict(attrs or {}),
        )
    )


@contextmanager
def remote_context(ctx: Optional[SpanContext]) -> Iterator[None]:
    """Server-side: make a wire-extracted context the ambient parent for the
    duration of a handler (used when no local span is opened)."""
    if ctx is None:
        yield
        return
    token = _remote_context.set(ctx)
    try:
        yield
    finally:
        _remote_context.reset(token)


# -- wire formats -------------------------------------------------------------


def context_metadata(ctx: Optional[SpanContext] = None) -> list[tuple[str, str]]:
    ctx = ctx or current_context()
    if ctx is None:
        return []
    return [(TRACE_ID_METADATA_KEY, ctx.trace_id), (SPAN_ID_METADATA_KEY, ctx.span_id)]


def extract_metadata(metadata: Any) -> Optional[SpanContext]:
    """SpanContext from gRPC invocation metadata (iterable of kv pairs)."""
    if not metadata:
        return None
    md = dict(metadata) if not isinstance(metadata, dict) else metadata
    trace_id = md.get(TRACE_ID_METADATA_KEY, "")
    if not trace_id:
        return None
    return SpanContext(str(trace_id), str(md.get(SPAN_ID_METADATA_KEY, "")))


def format_context(ctx: Optional[SpanContext]) -> str:
    """`"trace_id:span_id"` — the one-string form carried on
    FunctionGetInputsItem.trace_context and MODAL_TPU_TRACE_CONTEXT."""
    if ctx is None:
        return ""
    return f"{ctx.trace_id}:{ctx.span_id}"


def parse_context(value: Optional[str]) -> Optional[SpanContext]:
    if not value or ":" not in value:
        return None
    trace_id, _, span_id = value.partition(":")
    if not trace_id:
        return None
    return SpanContext(trace_id, span_id)


def context_from_env() -> Optional[SpanContext]:
    return parse_context(os.environ.get(TRACE_CONTEXT_ENV, ""))


# -- trace store reader (CLI waterfall / tests) -------------------------------


def span_dirs(trace_dir_path: str) -> list[str]:
    """The given trace dir plus any sibling per-shard span sinks: a sharded
    fleet (server/shards.py) keeps the director's spans in ``<root>/traces``
    and each subprocess shard's in ``<root>/shard-<i>/traces``. Readers merge
    all of them so one routed call renders as one waterfall (ISSUE 17)."""
    dirs = [trace_dir_path]
    root = os.path.dirname(os.path.abspath(trace_dir_path))
    try:
        for name in sorted(os.listdir(root)):
            if name.startswith("shard-"):
                cand = os.path.join(root, name, "traces")
                if cand != os.path.abspath(trace_dir_path) and os.path.isdir(cand):
                    dirs.append(cand)
    except OSError:
        pass
    return dirs


def read_spans(trace_dir_path: str) -> list[dict]:
    """Every span recorded under a trace dir (and any sibling per-shard span
    sinks — see span_dirs), across all process files. Malformed lines (torn
    writes at crash) are skipped."""
    spans: list[dict] = []
    for d in span_dirs(trace_dir_path):
        spans.extend(_read_spans_one(d))
    return spans


def _read_spans_one(trace_dir_path: str) -> list[dict]:
    spans: list[dict] = []
    try:
        names = sorted(os.listdir(trace_dir_path))
    except OSError:
        return spans
    for fname in names:
        # rotated generations (.jsonl.1) read the same as live files
        if not (fname.startswith("spans-") and (fname.endswith(".jsonl") or fname.endswith(".jsonl.1"))):
            continue
        try:
            with open(os.path.join(trace_dir_path, fname)) as f:
                for line in f:
                    try:
                        rec = json.loads(line)
                    except json.JSONDecodeError:
                        continue
                    if isinstance(rec, dict) and rec.get("trace_id"):
                        spans.append(rec)
        except OSError:
            continue
    return spans


def gc_trace_dir(
    trace_dir_path: str,
    max_total_bytes: int = DEFAULT_STORE_MAX_BYTES,
    max_age_s: float = DEFAULT_STORE_MAX_AGE_S,
) -> dict:
    """Prune the span store: drop files older than `max_age_s`, then drop
    oldest-first (rotated generations before live files) until the store is
    under `max_total_bytes`. The current process's open sink file is never
    deleted. Called by the supervisor on boot and `modal_tpu trace gc`."""
    report = {"removed": 0, "removed_bytes": 0, "kept": 0, "kept_bytes": 0}
    try:
        names = os.listdir(trace_dir_path)
    except OSError:
        return report
    own = f"spans-{os.getpid()}.jsonl"
    now = time.time()
    entries = []  # (mtime, is_rotated, path, size)
    for fname in names:
        if not (fname.startswith("spans-") and (fname.endswith(".jsonl") or fname.endswith(".jsonl.1"))):
            continue
        path = os.path.join(trace_dir_path, fname)
        try:
            st = os.stat(path)
        except OSError:
            continue
        entries.append((st.st_mtime, fname.endswith(".1"), path, st.st_size, fname))

    def _remove(path: str, size: int) -> None:
        try:
            os.unlink(path)
            report["removed"] += 1
            report["removed_bytes"] += size
        except OSError:
            pass

    def _protected(is_rotated: bool, mtime: float, fname: str) -> bool:
        # our own open sink, or any recently-written live file (possibly an
        # open sink of another process — unlinking it would silently sever
        # that process's span stream); rotated generations are never open
        return fname == own or (not is_rotated and now - mtime < LIVE_SINK_GRACE_S)

    keep = []
    for mtime, is_rotated, path, size, fname in entries:
        if not _protected(is_rotated, mtime, fname) and now - mtime > max_age_s:
            _remove(path, size)
        else:
            keep.append((mtime, is_rotated, path, size, fname))
    # over the cap: evict rotated generations first, then oldest live files
    keep.sort(key=lambda e: (not e[1], e[0]))  # rotated first, oldest first
    total = sum(e[3] for e in keep)
    kept = []
    for e in keep:
        if total > max_total_bytes and not _protected(e[1], e[0], e[4]):
            _remove(e[2], e[3])
            total -= e[3]
        else:
            kept.append(e)
    report["kept"] = len(kept)
    report["kept_bytes"] = sum(e[3] for e in kept)
    return report


def find_traces(trace_dir_path: str, needle: str) -> dict[str, list[dict]]:
    """Traces matching `needle`: a trace-id prefix, or an app_id /
    function_call_id / input_id / task_id attr of any span. Returns
    {trace_id: spans}."""
    by_trace: dict[str, list[dict]] = {}
    for rec in read_spans(trace_dir_path):
        by_trace.setdefault(rec["trace_id"], []).append(rec)
    if not needle:
        return by_trace
    matched: dict[str, list[dict]] = {}
    for trace_id, spans in by_trace.items():
        if trace_id.startswith(needle):
            matched[trace_id] = spans
            continue
        for rec in spans:
            attrs = rec.get("attrs") or {}
            if needle in (
                attrs.get("app_id"),
                attrs.get("function_call_id"),
                attrs.get("input_id"),
                attrs.get("task_id"),
                attrs.get("function_id"),
            ):
                matched[trace_id] = spans
                break
    return matched
