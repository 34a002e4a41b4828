#!/usr/bin/env python
"""Sharded-control-plane bench (server/shards.py, docs/CONTROL_PLANE.md).

Boots an in-process ShardedSupervisor (director + N shards, real gRPC + the
client-side shard router), then drives a control-plane-shaped load:

- ``--calls`` concurrent function-call maps (FunctionMap + batched
  FunctionPutInputs) totalling ``--inputs`` inputs, spread across apps homed
  on every partition.  Shard schedulers are stopped so the numbers isolate
  the CONTROL plane — routing, handler, journal append — not container
  execution.
- mid-run, one shard is killed dead (``kill_shard`` = the in-process
  kill -9 analogue); the director's health loop fences it and a survivor
  replays its journal.  **takeover-to-first-placement** is measured as the
  wall time from the kill to the first post-kill input accepted on the dead
  shard's partition (the client rides UNAVAILABLE → map refresh → redial),
  and cross-checked against the takeover gauge on the successor's
  time-series store.

Reported (CONTROL_BENCH_RESULT JSON line):

- ``control_placement_p99_s`` / ``_p50_s`` — client-observed latency of one
  routed put-inputs RPC (placement = input accepted into shard state).
- ``control_calls_per_s`` — completed map-calls per second.
- ``control_inputs_per_s`` — accepted inputs per second.
- ``control_takeover_s`` — takeover-to-first-placement recovery time.
- ``federation_query_p50_s`` / ``federation_direct_p50_s`` /
  ``federation_overhead_x`` — fleet-merged /metrics/history query latency vs
  one shard's direct endpoint (ISSUE 17: merged must stay <= 2x direct at 3
  shards), plus ``flight_dump_s`` / ``flight_ring_bytes`` for the flight
  recorder's postmortem dump.
- ``journal_quorum_p50_s`` / ``journal_local_p50_s`` /
  ``journal_quorum_overhead_x`` — placement p50 with quorum journal
  replication on (MODAL_TPU_JOURNAL_REPLICAS=2) vs off (=0); the ISSUE 19
  bar is overhead <= 1.5x.  ``replica_takeover_s`` / ``replica_takeover_mode``
  time the dead-DISK takeover (shard killed + journal directory deleted;
  recovery must come from the survivors' replica streams).

Usage (full scale ≈ 1M inputs / 10k calls; scale down for CI):
    JAX_PLATFORMS=cpu python tools/bench_control_plane.py \
        [--inputs 1000000] [--calls 10000] [--shards 3] [--batch 100]
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import sys
import tempfile
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

# a CPU micro-bench of the control plane, whatever the environment says
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("MODAL_TPU_AUTO_LOCAL_SERVER", "0")


def _quantile(sorted_vals: list[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(q * len(sorted_vals)))
    return sorted_vals[idx]


async def _create_partition_apps(client, num_partitions: int):
    """One app+function per partition: app names are chosen so their crc32
    hash lands on each partition in turn (the creates route by name, the
    minted ids then pin everything downstream)."""
    import zlib

    from modal_tpu._utils.grpc_utils import retry_transient_errors
    from modal_tpu.proto import api_pb2

    functions = {}
    suffix = 0
    for part in range(num_partitions):
        while zlib.crc32(f"bench-cp-{suffix}".encode()) % num_partitions != part:
            suffix += 1
        name = f"bench-cp-{suffix}"
        suffix += 1
        app = await retry_transient_errors(
            client.stub.AppCreate, api_pb2.AppCreateRequest(description=name)
        )
        fn = await retry_transient_errors(
            client.stub.FunctionCreate,
            api_pb2.FunctionCreateRequest(
                app_id=app.app_id,
                function=api_pb2.Function(function_name="bench_fn"),
                tag="bench_fn",
            ),
        )
        functions[part] = fn.function_id
    return functions


async def _one_call(client, function_id: str, n_inputs: int, batch: int, payload: bytes,
                    latencies: list[float]) -> None:
    from modal_tpu._utils.grpc_utils import retry_transient_errors
    from modal_tpu.proto import api_pb2

    call = await retry_transient_errors(
        client.stub.FunctionMap,
        api_pb2.FunctionMapRequest(
            function_id=function_id, function_call_type=api_pb2.FUNCTION_CALL_TYPE_MAP
        ),
        max_retries=8,
    )
    idx = 0
    while idx < n_inputs:
        chunk = min(batch, n_inputs - idx)
        req = api_pb2.FunctionPutInputsRequest(
            function_id=function_id,
            function_call_id=call.function_call_id,
            inputs=[
                api_pb2.FunctionPutInputsItem(
                    idx=idx + k, input=api_pb2.FunctionInput(args=payload)
                )
                for k in range(chunk)
            ],
        )
        t0 = time.perf_counter()
        await retry_transient_errors(client.stub.FunctionPutInputs, req, max_retries=8)
        latencies.append(time.perf_counter() - t0)
        idx += chunk


async def _probe_recovery(client, function_id: str, t_kill: float, payload: bytes) -> float:
    """Hammer the dead partition with single-input placements until one lands
    — the client-observed takeover-to-first-placement time."""
    from modal_tpu._utils.grpc_utils import retry_transient_errors
    from modal_tpu.proto import api_pb2

    while True:
        try:
            call = await retry_transient_errors(
                client.stub.FunctionMap,
                api_pb2.FunctionMapRequest(
                    function_id=function_id,
                    function_call_type=api_pb2.FUNCTION_CALL_TYPE_MAP,
                ),
                max_retries=0,
                attempt_timeout=2.0,
            )
            await retry_transient_errors(
                client.stub.FunctionPutInputs,
                api_pb2.FunctionPutInputsRequest(
                    function_id=function_id,
                    function_call_id=call.function_call_id,
                    inputs=[api_pb2.FunctionPutInputsItem(
                        idx=0, input=api_pb2.FunctionInput(args=payload)
                    )],
                ),
                max_retries=0,
                attempt_timeout=2.0,
            )
            return time.monotonic() - t_kill
        except Exception:  # noqa: BLE001 — UNAVAILABLE until the takeover lands
            await asyncio.sleep(0.02)


async def _bench_federation(repeats: int = 20) -> dict:
    """Federation phase (ISSUE 17): merged /metrics/history query latency vs
    one shard's direct rendered `top` answer, plus the flight recorder's dump
    latency and serialized ring size.

    Runs against its OWN 3-shard subprocess fleet: the production deployment
    shape is one process per shard, so the fan-out's server-side work is
    genuinely concurrent. (An in-process fleet serializes all three handlers
    on one event loop, which turns overhead_x into a measure of the bench
    harness, not the federation.)"""
    from modal_tpu.observability import flight_recorder
    from modal_tpu.observability.federation import FederatedHistory
    from modal_tpu.server.shards import ShardedSupervisor

    fed_dir = tempfile.mkdtemp(prefix="bench-federation-")
    sup = ShardedSupervisor(
        num_shards=3,
        num_workers=3,
        state_dir=fed_dir,
        worker_chips=8,
        worker_tpu_type="local-sim",
        subprocess_shards=True,
        health_interval_s=5.0,
    )
    out: dict = {}
    try:
        await sup.start()
        await asyncio.sleep(2.5)  # let each shard's sampler populate its store
        fed = FederatedHistory(fed_dir, shared_registry=False)
        live = [s for s in fed.topology() if not s.get("dead")]
        if live:
            # the single-shard arm is what an operator runs against a
            # monolith: the shard's OWN rendered `top` answer over the same
            # transport — so overhead_x isolates the fan-out + merge cost
            await fed.payload("top")  # warm connections on both arms
            await fed._fetch(live[0], "top", 600.0)
            fed_lat: list[float] = []
            direct_lat: list[float] = []
            for _ in range(repeats):
                t0 = time.perf_counter()
                await fed.payload("top")
                fed_lat.append(time.perf_counter() - t0)
                t0 = time.perf_counter()
                await fed._fetch(live[0], "top", 600.0)
                direct_lat.append(time.perf_counter() - t0)
            # the merge itself (namespacing + fleet_summary + per-shard rows)
            # is the only work federation ADDS beyond the fetches — time it
            # separately so the additive cost is guarded host-independently
            snaps, missing, dead = await fed._gather(600.0)
            merge_lat: list[float] = []
            for _ in range(repeats):
                t0 = time.perf_counter()
                merged = fed.merged(snaps)
                fed._top_payload(snaps, missing, dead, merged, fed._fed_meta(snaps, missing, dead))
                merge_lat.append(time.perf_counter() - t0)
            await fed.close()
            fed_lat.sort()
            direct_lat.sort()
            merge_lat.sort()
            fp50 = _quantile(fed_lat, 0.5)
            dp50 = _quantile(direct_lat, 0.5)
            out.update(
                {
                    "federation_query_p50_s": round(fp50, 6),
                    "federation_query_p99_s": round(_quantile(fed_lat, 0.99), 6),
                    "federation_direct_p50_s": round(dp50, 6),
                    "federation_merge_p50_s": round(_quantile(merge_lat, 0.5), 6),
                    "federation_overhead_x": round(fp50 / dp50, 3) if dp50 > 0 else None,
                    "federation_shards": len(live),
                    # on a host with fewer cores than shards every fetch's
                    # client+server CPU serializes, so overhead_x floors at
                    # ~N regardless of transport — the guard reads this to
                    # pick the bar it can honestly hold
                    "federation_cores": os.cpu_count() or 1,
                }
            )
        fr = flight_recorder.FlightRecorder(
            os.path.join(fed_dir, "bench-flight"), scope="bench", interval_s=0.0
        )
        for _ in range(fr.samples.maxlen or 60):
            fr.record_sample()
        t0 = time.perf_counter()
        dump_path = fr.dump("bench")
        out["flight_dump_s"] = round(time.perf_counter() - t0, 6)
        out["flight_ring_bytes"] = os.path.getsize(dump_path) if dump_path else 0
    finally:
        await sup.stop()
        shutil.rmtree(fed_dir, ignore_errors=True)
    return out


async def _bench_replication(args) -> dict:
    """ISSUE 19 A/B: identical placement loads against two fresh in-process
    fleets — quorum journal replication ON (MODAL_TPU_JOURNAL_REPLICAS=2)
    vs OFF (=0, the byte-identical single-writer degrade). The acceptance
    bar is quorum p50 <= 1.5x local-only p50 on the same host. The ON fleet
    then loses a shard AND its journal directory (disk death, not process
    death) and the replica-stream takeover is timed."""
    from modal_tpu.client import _Client
    from modal_tpu.server.shards import ShardedSupervisor

    REPL_CALLS = 120
    REPL_INPUTS_PER_CALL = 20
    prior = os.environ.get("MODAL_TPU_JOURNAL_REPLICAS")
    metrics: dict = {}
    try:
        for env_value, key in (("0", "journal_local_p50_s"), ("2", "journal_quorum_p50_s")):
            os.environ["MODAL_TPU_JOURNAL_REPLICAS"] = env_value
            state_dir = tempfile.mkdtemp(prefix=f"bench-repl-{env_value}-")
            sup = ShardedSupervisor(
                num_shards=args.shards,
                num_workers=args.shards,
                state_dir=state_dir,
                worker_chips=8,
                worker_tpu_type="local-sim",
                health_interval_s=0.2,
            )
            await sup.start()
            for shard in sup.shards:
                if shard is not None:
                    await shard.scheduler.stop()
            client = _Client(sup.server_url, 1)
            await client._open()
            try:
                await client.hello()
                functions = await _create_partition_apps(client, args.shards)
                payload = b"x" * args.payload_bytes
                latencies: list[float] = []
                sem = asyncio.Semaphore(min(args.concurrency, 32))

                async def _guarded(part: int) -> None:
                    async with sem:
                        await _one_call(
                            client,
                            functions[part],
                            REPL_INPUTS_PER_CALL,
                            min(args.batch, REPL_INPUTS_PER_CALL),
                            payload,
                            latencies,
                        )

                await asyncio.gather(*(_guarded(i % args.shards) for i in range(REPL_CALLS)))
                latencies.sort()
                metrics[key] = round(_quantile(latencies, 0.50), 6)
                if env_value == "2":
                    # dead-disk takeover: kill the shard AND delete its journal
                    # — only the survivors' replica streams can rehydrate it
                    kill_index = 1 % args.shards
                    await sup.kill_shard(kill_index)
                    shutil.rmtree(
                        os.path.join(state_dir, f"shard-{kill_index}", "journal"),
                        ignore_errors=True,
                    )
                    deadline = time.monotonic() + 60.0
                    while time.monotonic() < deadline:
                        if sup.assignments[kill_index] != kill_index:
                            break
                        await asyncio.sleep(0.05)
                    entries = [
                        e for e in sup.takeover_log if e["dead_shard"] == kill_index
                    ]
                    if entries:
                        metrics["replica_takeover_s"] = entries[-1]["seconds"]
                        metrics["replica_takeover_mode"] = entries[-1]["mode"]
            finally:
                await client._close()
                await sup.stop()
                shutil.rmtree(state_dir, ignore_errors=True)
        local = metrics.get("journal_local_p50_s") or 0.0
        quorum = metrics.get("journal_quorum_p50_s") or 0.0
        if local > 0 and quorum > 0:
            metrics["journal_quorum_overhead_x"] = round(quorum / local, 3)
    finally:
        if prior is None:
            os.environ.pop("MODAL_TPU_JOURNAL_REPLICAS", None)
        else:
            os.environ["MODAL_TPU_JOURNAL_REPLICAS"] = prior
    return metrics


async def run_bench(args) -> dict:
    from modal_tpu.client import _Client
    from modal_tpu.server.shards import ShardedSupervisor

    # replication A/B first: its two small fleets must not share CPU with the
    # main load (quorum overhead is a latency ratio — contamination skews it)
    replication_metrics = await _bench_replication(args)
    state_dir = tempfile.mkdtemp(prefix="bench-control-")
    os.environ["MODAL_TPU_STATE_DIR"] = state_dir
    sup = ShardedSupervisor(
        num_shards=args.shards,
        num_workers=args.shards,
        state_dir=state_dir,
        worker_chips=8,
        worker_tpu_type="local-sim",
        health_interval_s=0.2,
    )
    await sup.start()
    # control-plane isolation: no container execution behind the handlers
    for shard in sup.shards:
        if shard is not None:
            await shard.scheduler.stop()
    client = _Client(sup.server_url, 1)
    await client._open()
    try:
        await client.hello()
        functions = await _create_partition_apps(client, args.shards)
        payload = b"x" * args.payload_bytes
        per_call = max(1, args.inputs // args.calls)
        latencies: list[float] = []
        sem = asyncio.Semaphore(args.concurrency)

        async def _guarded(part: int) -> None:
            async with sem:
                await _one_call(client, functions[part], per_call, args.batch,
                                payload, latencies)

        kill_index = 1 % args.shards
        calls_first = args.calls // 2
        t_start = time.perf_counter()
        await asyncio.gather(
            *(_guarded(i % args.shards) for i in range(calls_first))
        )
        # federation phase between the load halves (its own subprocess fleet;
        # the main in-process fleet is idle while it runs)
        federation_metrics = await _bench_federation()
        # kill one shard mid-run, keep pumping, and race the recovery probe
        t_kill = time.monotonic()
        await sup.kill_shard(kill_index)
        probe = asyncio.create_task(
            _probe_recovery(client, functions[kill_index], t_kill, payload)
        )
        await asyncio.gather(
            *(_guarded(i % args.shards) for i in range(args.calls - calls_first))
        )
        takeover_s = await probe
        total_s = time.perf_counter() - t_start

        latencies.sort()
        successor = sup.assignments[kill_index]
        gauge_takeover = None
        succ_sup = sup.shards[successor]
        if succ_sup is not None and succ_sup.state.timeseries is not None:
            stats = succ_sup.state.timeseries.gauge_stats(
                "modal_tpu_shard_takeover_seconds", 600.0
            )
            if stats:
                gauge_takeover = stats.get("last")
        return {
            "inputs": per_call * args.calls,
            "calls": args.calls,
            "shards": args.shards,
            "batch": args.batch,
            "payload_bytes": args.payload_bytes,
            "control_placement_p50_s": round(_quantile(latencies, 0.50), 6),
            "control_placement_p99_s": round(_quantile(latencies, 0.99), 6),
            "control_calls_per_s": round(args.calls / total_s, 2),
            "control_inputs_per_s": round(per_call * args.calls / total_s, 2),
            "control_takeover_s": round(takeover_s, 4),
            "takeover_gauge_s": gauge_takeover,
            "takeover_epoch": sup.epoch,
            "takeover_log": sup.takeover_log,
            "total_s": round(total_s, 2),
            **federation_metrics,
            **replication_metrics,
        }
    finally:
        await client._close()
        await sup.stop()
        shutil.rmtree(state_dir, ignore_errors=True)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--inputs", type=int, default=1_000_000)
    parser.add_argument("--calls", type=int, default=10_000)
    parser.add_argument("--shards", type=int, default=3)
    parser.add_argument("--batch", type=int, default=100)
    parser.add_argument("--payload-bytes", type=int, default=64)
    parser.add_argument("--concurrency", type=int, default=256)
    args = parser.parse_args()
    # _Client's methods are synchronize_api-wrapped in place: from a foreign
    # asyncio loop they'd block instead of returning coroutines, so the whole
    # bench must run ON the synchronizer loop (same as bench_dataplane.py)
    from modal_tpu._utils.async_utils import synchronizer

    result = synchronizer.run(run_bench(args))
    print("CONTROL_BENCH_RESULT " + json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
