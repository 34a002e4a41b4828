"""The declared ``MODAL_TPU_*`` env-knob inventory (ISSUE 15 rule 4/5).

Every literal ``MODAL_TPU_*`` string in ``modal_tpu/`` must be declared
here (SPAN_CATALOG discipline: new code can't ship knobs the docs and the
degradation matrix have never heard of), and every declared knob must
still be used — dead entries fail the ``knob-parity`` pass too.

Entry fields:

- ``type``    — how the raw env string is interpreted.
- ``default`` — the effective default when unset (``"-"`` for injected
                plumbing that has no default).
- ``doc``     — the docs file that explains the subsystem.
- ``feature_gate`` — True for default-ON capabilities that degrade cleanly
  when set to 0/off. The ``degradation-symmetry`` pass requires a
  grep-able test toggling every gate off, so "individually degradable"
  stays true by construction.
- ``internal`` — injected by the platform (worker → container, scheduler →
  worker), not set by users.

Settings from ``config.py`` (resolved via the dynamic ``"MODAL_TPU_" +
key.upper()`` path) are synthesized by :func:`config_derived_knobs`;
explicit entries below win when a setting's env name is ALSO read as a
literal somewhere.

The knob table in docs/ANALYSIS.md is generated from this module
(:func:`knob_table_markdown`) and pinned by tests/test_analysis.py.
"""

from __future__ import annotations

from typing import NamedTuple


class Knob(NamedTuple):
    name: str
    type: str
    default: str
    doc: str
    description: str
    feature_gate: bool = False
    internal: bool = False


def _k(name, type_, default, doc, description, *, gate=False, internal=False) -> tuple[str, Knob]:
    return name, Knob(name, type_, default, doc, description, gate, internal)


KNOB_CATALOG: dict[str, Knob] = dict(
    [
        # -- chaos injection (docs/CHAOS.md) --------------------------------
        _k("MODAL_TPU_CHAOS", "bool", "0", "docs/CHAOS.md",
           "master switch for seeded fault injection (RPC errors/latency, crashes)"),
        _k("MODAL_TPU_CHAOS_SEED", "int", "0", "docs/CHAOS.md",
           "deterministic seed for the injection schedule"),
        _k("MODAL_TPU_CHAOS_ERROR_RATE", "float", "0", "docs/CHAOS.md",
           "default injected-UNAVAILABLE rate for every RPC"),
        _k("MODAL_TPU_CHAOS_RPCS", "csv", "", "docs/CHAOS.md",
           "per-RPC rates: 'Name=0.05,Other' (bare names use the default rate)"),
        _k("MODAL_TPU_CHAOS_LATENCY_MS", "float", "0", "docs/CHAOS.md",
           "injected latency base per targeted RPC"),
        _k("MODAL_TPU_CHAOS_LATENCY_JITTER_MS", "float", "0", "docs/CHAOS.md",
           "uniform jitter added to injected latency"),
        _k("MODAL_TPU_CHAOS_LATENCY_RATE", "float", "1", "docs/CHAOS.md",
           "fraction of targeted RPCs that receive injected latency"),
        _k("MODAL_TPU_CHAOS_SUPERVISOR_CRASH_AFTER", "csv", "", "docs/CHAOS.md",
           "crash+journal-recover the supervisor after N mutating RPCs (list = repeat)"),
        _k("MODAL_TPU_CHAOS_SHARD_KILL_AFTER", "csv", "", "docs/CHAOS.md",
           "kill shard S dead after N outputs ('S:N', list = repeat); director must take over"),
        _k("MODAL_TPU_CHAOS_SHARD_PARTITION", "csv", "", "docs/CHAOS.md",
           "partition shard S from health probes after N outputs for D seconds ('S:N:D')"),
        _k("MODAL_TPU_CHAOS_WARM_KILL_HANDOFF", "int", "0", "docs/CHAOS.md",
           "kill the next N warm-pool interpreters mid-handoff"),
        _k("MODAL_TPU_CHAOS_STREAM_RESETS", "int", "0", "docs/CHAOS.md",
           "abort the next N FunctionStreamOutputs streams (prove poll degrade)"),
        _k("MODAL_TPU_CHAOS_SERVING_STREAM_RESETS", "int", "0", "docs/SERVING.md",
           "abort the next N serving SSE streams mid-flight"),
        _k("MODAL_TPU_CHAOS_SERVING_STEP_DELAY_S", "float", "0", "docs/SERVING.md",
           "inject per-decode-step delay into the serving engine"),
        _k("MODAL_TPU_CHAOS_KV_SHIP_DROP", "int", "0", "docs/SERVING.md",
           "drop the next N KV-page shipments at admission (decode re-prefills locally)"),
        _k("MODAL_TPU_CHAOS_REPL_TORN_TAIL", "int", "0", "docs/CHAOS.md",
           "tear the record tail of the next N replicated journal batches (follower crash mid-write)"),
        _k("MODAL_TPU_CHAOS_REPL_DISK_FULL", "int", "0", "docs/CHAOS.md",
           "refuse the next N replicated journal appends (follower disk full)"),
        _k("MODAL_TPU_CHAOS_REPL_ACK_DROP", "int", "0", "docs/CHAOS.md",
           "durably append but drop the ack for the next N replicated batches (partition-during-commit)"),
        _k("MODAL_TPU_CHAOS_REPL_LAG_MS", "float", "0", "docs/CHAOS.md",
           "extra delay before every replicated journal append batch"),
        # -- dispatch fast path (docs/DISPATCH.md) --------------------------
        _k("MODAL_TPU_FASTPATH", "bool", "1", "docs/DISPATCH.md",
           "whole local-transport ladder (in-process/UDS) off → TCP only", gate=True),
        _k("MODAL_TPU_FASTPATH_INPROC", "bool", "1", "docs/DISPATCH.md",
           "in-process direct-handler rung of the transport ladder", gate=True),
        _k("MODAL_TPU_FASTPATH_UDS", "bool", "1", "docs/DISPATCH.md",
           "Unix-domain-socket rung of the transport ladder", gate=True),
        _k("MODAL_TPU_FASTPATH_BLOB", "bool", "1", "docs/DISPATCH.md",
           "co-located blob payloads by file reference instead of HTTP copy", gate=True),
        _k("MODAL_TPU_DISPATCH_COALESCE", "bool", "1", "docs/DISPATCH.md",
           "coalesced scheduling RPCs (FunctionMapBatch/AttemptStartBatch, map pump)", gate=True),
        _k("MODAL_TPU_DISPATCH_EXCHANGE", "bool", "1", "docs/DISPATCH.md",
           "one-RPC container turnaround (put outputs + claim inputs)", gate=True),
        _k("MODAL_TPU_STREAM_OUTPUTS", "bool", "1", "docs/DISPATCH.md",
           "push-streamed outputs (FunctionStreamOutputs); off → unary poll", gate=True),
        _k("MODAL_TPU_SWITCH_INTERVAL", "float", "0.001", "docs/DISPATCH.md",
           "GIL switch interval for dispatch-critical processes (0 = interpreter default)"),
        _k("MODAL_TPU_CIRCUIT_BREAKER", "bool", "1", "docs/DISPATCH.md",
           "per-(channel,method) circuit breaker on the retry engine", gate=True),
        _k("MODAL_TPU_CIRCUIT_BREAKER_THRESHOLD", "int", "10", "docs/DISPATCH.md",
           "consecutive transient failures before the circuit opens"),
        _k("MODAL_TPU_CIRCUIT_BREAKER_COOLDOWN", "float", "1.0", "docs/DISPATCH.md",
           "seconds an open circuit fast-fails before half-open probe"),
        _k("MODAL_TPU_DISABLE_INPUT_PLANE", "bool", "0", "docs/DISPATCH.md",
           "=1 forces control-plane dispatch even when an input plane is advertised"),
        _k("MODAL_TPU_SERVER_URL", "str", "grpc://127.0.0.1:9900", "docs/STATUS.md",
           "control-plane address (config.py 'server_url'; exported to containers)"),
        _k("MODAL_TPU_SERVER_UDS", "path", "-", "docs/DISPATCH.md",
           "co-located UDS path advertised on ClientHello", internal=True),
        _k("MODAL_TPU_BLOB_LOCAL_DIR", "path", "-", "docs/DISPATCH.md",
           "co-located blob store dir for by-reference payloads", internal=True),
        # -- durable control plane (docs/RECOVERY.md) -----------------------
        _k("MODAL_TPU_JOURNAL", "bool", "1", "docs/RECOVERY.md",
           "write-ahead journaling of the control plane; off → in-memory only", gate=True),
        _k("MODAL_TPU_JOURNAL_FSYNC", "bool", "0", "docs/RECOVERY.md",
           "fsync per append (host-crash durability; page-cache durable when off)"),
        _k("MODAL_TPU_JOURNAL_SEGMENT_RECORDS", "int", "4096", "docs/RECOVERY.md",
           "records per journal segment before rotation"),
        _k("MODAL_TPU_JOURNAL_COMPACT_EVERY", "int", "20000", "docs/RECOVERY.md",
           "records since snapshot that trigger periodic compaction"),
        _k("MODAL_TPU_IDEMPOTENCY_MAX", "int", "8192", "docs/RECOVERY.md",
           "journal-backed RPC-dedupe seen-set capacity"),
        _k("MODAL_TPU_JOURNAL_REPLICAS", "int", "2", "docs/RECOVERY.md",
           "follower shards per journal writer (quorum replication); 0 → byte-identical single-writer path", gate=True),
        _k("MODAL_TPU_JOURNAL_QUORUM_TIMEOUT", "float", "5.0", "docs/RECOVERY.md",
           "seconds a mutating RPC waits at the quorum-commit barrier before UNAVAILABLE"),
        # -- sharded control plane (docs/CONTROL_PLANE.md) ------------------
        _k("MODAL_TPU_SHARDS", "int", "1", "docs/CONTROL_PLANE.md",
           "control-plane shard count; 1 = the monolith (no director, no routing)"),
        # -- observability (docs/OBSERVABILITY.md) --------------------------
        _k("MODAL_TPU_TRACE", "bool", "1", "docs/OBSERVABILITY.md",
           "distributed tracing (span JSONL sink under <state_dir>/traces)", gate=True),
        _k("MODAL_TPU_TRACE_DIR", "path", "<state_dir>/traces", "docs/OBSERVABILITY.md",
           "span-store override; doubles as the cross-process sink handoff"),
        _k("MODAL_TPU_TRACE_MAX_BYTES", "int", "67108864", "docs/OBSERVABILITY.md",
           "span-sink rotation threshold (64 MiB)"),
        _k("MODAL_TPU_TRACE_CONTEXT", "str", "-", "docs/OBSERVABILITY.md",
           "propagated trace context (scheduler → worker → container)", internal=True),
        _k("MODAL_TPU_TRACE_T0", "float", "-", "docs/OBSERVABILITY.md",
           "spawn-decision timestamp anchoring container.boot spans", internal=True),
        _k("MODAL_TPU_PROFILE", "enum(0|1|<hz>)", "0", "docs/OBSERVABILITY.md",
           "start the folded-stack sampling profiler at process boot (19 Hz default)"),
        _k("MODAL_TPU_PROFILE_DIR", "path", "<state_dir>/observability/profiles",
           "docs/OBSERVABILITY.md", "where folded-stack profiles flush"),
        _k("MODAL_TPU_TS_INTERVAL", "float", "10.0", "docs/OBSERVABILITY.md",
           "supervisor time-series sampler base interval; 0/off disables the store", gate=True),
        _k("MODAL_TPU_TS_FAMILIES", "csv", "", "docs/OBSERVABILITY.md",
           "extra metric families the time-series store tracks"),
        _k("MODAL_TPU_IMPORT_TRACE", "bool", "0", "docs/OBSERVABILITY.md",
           "per-module import tracing in containers (cold-start attribution)"),
        _k("MODAL_TPU_TELEMETRY_PATH", "path", "-", "docs/OBSERVABILITY.md",
           "import-trace JSONL destination, set by the worker", internal=True),
        _k("MODAL_TPU_SLO_FAST_WINDOW_S", "float", "60", "docs/OBSERVABILITY.md",
           "burn-rate alert fast window"),
        _k("MODAL_TPU_SLO_SLOW_WINDOW_S", "float", "600", "docs/OBSERVABILITY.md",
           "burn-rate alert slow window"),
        _k("MODAL_TPU_SLO_TTFT_P95_S", "float", "2.5", "docs/OBSERVABILITY.md",
           "serving TTFT p95 SLO threshold"),
        _k("MODAL_TPU_SLO_TOKENS_PER_REPLICA", "float", "0", "docs/OBSERVABILITY.md",
           "tokens/s-per-replica SLO (0 = rule disabled)"),
        _k("MODAL_TPU_SLO_DISPATCH_P50_S", "float", "0.25", "docs/OBSERVABILITY.md",
           "dispatch p50 SLO threshold"),
        _k("MODAL_TPU_SLO_CALL_ERROR_RATE", "float", "0.05", "docs/OBSERVABILITY.md",
           "call error-rate SLO threshold"),
        _k("MODAL_TPU_SLO_SCALE_COOLDOWN", "float", "10", "docs/OBSERVABILITY.md",
           "SLO-autoscaler cooldown between scale decisions"),
        _k("MODAL_TPU_FEDERATION", "bool", "1", "docs/OBSERVABILITY.md",
           "director-resident metrics federation + fleet-scope SLO evaluation "
           "(sharded plane only); off → per-shard history endpoints answer alone", gate=True),
        _k("MODAL_TPU_FEDERATION_TIMEOUT", "float", "2.0", "docs/OBSERVABILITY.md",
           "per-shard fan-out timeout for one federated history query; a shard "
           "slower than this degrades the answer to a labeled partial"),
        _k("MODAL_TPU_FLIGHT_RECORDER", "bool", "1", "docs/OBSERVABILITY.md",
           "per-shard crash-forensics ring (raw samples, span/journal tails, chaos "
           "events) frozen + dumped as postmortem-<event>.json on crash/takeover/alert",
           gate=True),
        _k("MODAL_TPU_FLIGHT_RECORDER_RING", "int", "60", "docs/OBSERVABILITY.md",
           "flight-recorder ring capacity in ~1 Hz samples (≈ seconds of history)"),
        # -- serving tier (docs/SERVING.md) ---------------------------------
        _k("MODAL_TPU_SERVING_SAMPLING", "bool", "1", "docs/SERVING.md",
           "per-request sampling (temperature/top_k/top_p/seed); off → greedy-only", gate=True),
        _k("MODAL_TPU_SERVING_SPEC", "bool", "1", "docs/SERVING.md",
           "speculative decoding with the configured draft model", gate=True),
        _k("MODAL_TPU_SERVING_PREFIX_CACHE", "bool", "1", "docs/SERVING.md",
           "shared-prefix KV reuse (CoW pages)", gate=True),
        _k("MODAL_TPU_SERVING_SPANS", "bool", "1", "docs/SERVING.md",
           "per-request serving timeline spans (queue/prefill/decode/stream)", gate=True),
        _k("MODAL_TPU_SERVING_SPAN_TOKENS", "int", "8", "docs/SERVING.md",
           "decode-span granularity (tokens per span mark)"),
        _k("MODAL_TPU_SERVING_ROUTER", "bool", "1", "docs/SERVING.md",
           "prefix-aware fleet routing; off → seeded-random replica choice", gate=True),
        _k("MODAL_TPU_SERVING_ROLE", "enum(both|prefill|decode)", "both", "docs/SERVING.md",
           "disaggregation role of this replica (prefill exports KV pages, decode imports)"),
        # -- cold start (docs/COLDSTART.md) ---------------------------------
        _k("MODAL_TPU_WARM_POOL", "int", "0", "docs/COLDSTART.md",
           "baseline pre-forked parked interpreters per worker (config.py 'warm_pool')"),
        _k("MODAL_TPU_WARM_POOL_PREINIT", "bool", "0", "docs/COLDSTART.md",
           "pre-initialize the jax backend while parked (CPU sim only)"),
        _k("MODAL_TPU_WARM_POOL_ACK_TIMEOUT", "float", "10", "docs/COLDSTART.md",
           "seconds to wait for a parked interpreter to ack a handoff"),
        _k("MODAL_TPU_POOL_ID", "str", "-", "docs/COLDSTART.md",
           "parked-interpreter identity", internal=True),
        _k("MODAL_TPU_POOL_TOKEN", "str", "-", "docs/COLDSTART.md",
           "parked-interpreter handoff auth token", internal=True),
        _k("MODAL_TPU_POOL_ROUTER", "str", "-", "docs/COLDSTART.md",
           "router address a parked interpreter registers with", internal=True),
        _k("MODAL_TPU_POOL_CWD", "path", "-", "docs/COLDSTART.md",
           "working dir restored after a warm handoff", internal=True),
        _k("MODAL_TPU_SNAPSHOT_DIR", "path", "<state_dir>/snapshots", "docs/COLDSTART.md",
           "memory-snapshot store override"),
        _k("MODAL_TPU_PREWARM_BUILD", "bool", "-", "docs/COLDSTART.md",
           "set during Image.prewarm builds (compile-cache source attribution)", internal=True),
        _k("MODAL_TPU_IMAGE_ROOT", "path", "-", "docs/COLDSTART.md",
           "built image rootfs a container/builder runs against", internal=True),
        _k("MODAL_TPU_IMAGE_BUILD", "bool", "-", "docs/COLDSTART.md",
           "set inside image-build subprocesses", internal=True),
        _k("MODAL_TPU_IMAGE_BUILDER_VERSION", "str", "2026.07", "docs/STATUS.md",
           "image-builder epoch baked into content-addressed build hashes"),
        _k("MODAL_TPU_COMPILE_CACHE", "bool", "1", "docs/COLDSTART.md",
           "fleet compile-cache client (fetch-before-compile, push-after); "
           "off → jax's local persistent cache only", gate=True),
        _k("MODAL_TPU_COMPILE_CACHE_URL", "url", "-", "docs/COLDSTART.md",
           "fleet compile-cache service base URL (worker → container)", internal=True),
        _k("MODAL_TPU_COMPILE_CACHE_DIR", "path", "-", "docs/COLDSTART.md",
           "co-located fleet store dir for the local fast path (worker → container)",
           internal=True),
        _k("MODAL_TPU_AOT_LOWER", "csv", "", "docs/COLDSTART.md",
           "entry points to AOT-lower at @enter/pool-park time "
           "('train,prefill,decode,verify,sample' + cfg=/shape overrides)"),
        _k("MODAL_TPU_KV_SHIP_URL", "url", "-", "docs/SERVING.md",
           "blob-plane base URL for cross-host KV-page shipping when no "
           "shared filesystem exists (worker → container)", internal=True),
        # -- data plane (docs/DATAPLANE.md) ---------------------------------
        _k("MODAL_TPU_BLOB_SPILL_BYTES", "int", "33554432", "docs/DATAPLANE.md",
           "download size above which blob bodies spill to disk (32 MiB)"),
        _k("MODAL_TPU_MULTIPART_THRESHOLD", "int", "1073741824", "docs/DATAPLANE.md",
           "blob size that switches uploads to multipart (1 GiB)"),
        _k("MODAL_TPU_MULTIPART_PART_LEN", "int", "67108864", "docs/DATAPLANE.md",
           "multipart part length (64 MiB)"),
        _k("MODAL_TPU_HTTP_BLOCK_PARALLELISM", "int", "8", "docs/DATAPLANE.md",
           "concurrent HTTP Range block fetches per volume read"),
        _k("MODAL_TPU_NATIVE_HASH", "bool", "0", "docs/DATAPLANE.md",
           "=1 uses the C++ block hasher (many-core workers)"),
        # -- server / worker / runtime (docs/STATUS.md) ---------------------
        _k("MODAL_TPU_AUTH_TOKEN_TTL", "float", "1200", "docs/STATUS.md",
           "input-plane JWT lifetime"),
        _k("MODAL_TPU_EPHEMERAL_TTL", "float", "900", "docs/STATUS.md",
           "reap timeout for ephemeral objects that stop heartbeating"),
        _k("MODAL_TPU_EPHEMERAL_HEARTBEAT", "float", "300", "docs/STATUS.md",
           "client-side ephemeral-object heartbeat interval"),
        _k("MODAL_TPU_PREEMPT_GRACE", "float", "10", "docs/CHAOS.md",
           "seconds between preemption warning and task kill"),
        _k("MODAL_TPU_READOPT_GRACE", "float", "30", "docs/RECOVERY.md",
           "post-restart window in which workers may re-adopt running tasks"),
        _k("MODAL_TPU_STOP_GRACE", "float", "10", "docs/STATUS.md",
           "graceful container-stop window before SIGKILL"),
        _k("MODAL_TPU_SIDECAR_BOOT_WAIT", "float", "600", "docs/STATUS.md",
           "seconds the main container waits for sidecar readiness"),
        _k("MODAL_TPU_WORKER_TPU_TYPE", "str", "", "docs/STATUS.md",
           "override detected TPU type for a worker"),
        _k("MODAL_TPU_WORKER_NUM_CHIPS", "int", "0", "docs/STATUS.md",
           "override detected chip count"),
        _k("MODAL_TPU_WORKER_TOPOLOGY", "str", "", "docs/STATUS.md",
           "override detected TPU topology"),
        _k("MODAL_TPU_JAX_PLATFORM", "str", "", "docs/STATUS.md",
           "=cpu holds every container to CPU jax and simulates asked-for chips (tests)"),
        _k("MODAL_TPU_SKIP_JAX_DISTRIBUTED", "bool", "0", "docs/STATUS.md",
           "=1 skips jax.distributed.initialize in gang containers (tests)"),
        _k("MODAL_TPU_CONFIG_PATH", "path", "~/.modal_tpu.toml", "docs/STATUS.md",
           "user-config TOML location"),
        _k("MODAL_TPU_TASK_ID", "str", "-", "docs/STATUS.md",
           "container's task identity", internal=True),
        _k("MODAL_TPU_TASK_DIR", "path", "-", "docs/STATUS.md",
           "container's scratch/telemetry dir", internal=True),
        _k("MODAL_TPU_CONTAINER_ARGS_PATH", "path", "-", "docs/STATUS.md",
           "serialized container-args handoff file", internal=True),
        _k("MODAL_TPU_BOUND_PARAMS", "hex", "-", "docs/STATUS.md",
           "serialized parametrized-class bind args", internal=True),
        _k("MODAL_TPU_PROXY_IP", "str", "-", "docs/STATUS.md",
           "static-egress address a proxied container sees", internal=True),
    ]
)


def config_derived_knobs() -> dict[str, Knob]:
    """Knobs implied by config.py settings (resolved through the dynamic
    ``MODAL_TPU_<KEY>`` env path, so no literal appears in the source).
    Exempt from the dead-entry check for exactly that reason."""
    from ..config import _SETTINGS

    out: dict[str, Knob] = {}
    for key, setting in _SETTINGS.items():
        name = "MODAL_TPU_" + key.upper()
        if name in KNOB_CATALOG:
            continue
        type_ = {bool: "bool", int: "int", float: "float"}.get(type(setting.default), "str")
        out[name] = Knob(
            name=name,
            type=type_,
            default=repr(setting.default),
            doc="docs/STATUS.md",
            description=f"config.py setting {key!r} (env overrides profile/TOML)",
        )
    return out


def declared_knobs() -> dict[str, Knob]:
    merged = config_derived_knobs()
    merged.update(KNOB_CATALOG)
    return merged


def feature_gates() -> dict[str, Knob]:
    return {name: k for name, k in KNOB_CATALOG.items() if k.feature_gate}


def knob_table_markdown() -> str:
    """The docs/ANALYSIS.md knob table (generated; pinned by test)."""
    lines = [
        "| knob | type | default | gate | doc | description |",
        "|---|---|---|---|---|---|",
    ]
    for name in sorted(KNOB_CATALOG):
        k = KNOB_CATALOG[name]
        flag = "gate" if k.feature_gate else ("internal" if k.internal else "")
        lines.append(
            f"| `{name}` | {k.type} | `{k.default}` | {flag} | {k.doc} | {k.description} |"
        )
    return "\n".join(lines)
