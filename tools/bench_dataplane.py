#!/usr/bin/env python
"""Data-plane microbench: serialize / blob round-trip / Volume→device GB/s.

Runs against an in-process LocalSupervisor (no workers) so the numbers
measure the data plane itself — out-of-band serialization, streaming blob
HTTP, and the striped Volume read engine — not scheduling. Emits ONE JSON
line (``DATAPLANE_RESULT {...}``) so CI and the bench driver can fold it.

Usage:
    JAX_PLATFORMS=cpu python tools/bench_dataplane.py [--size-mb 1024]

The Volume section reports both the sequential chunk-loop baseline (the
pre-zero-copy ``read_file_into``) and the parallel striped engine; the
acceptance bar is parallel ≥ 2× sequential on a ≥ 1 GiB checkpoint.
"""

from __future__ import annotations

import argparse
import io
import json
import os
import resource
import sys
import tempfile
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)


def bench_serialization(size_mb: int) -> dict:
    import numpy as np

    from modal_tpu.serialization import deserialize, serialize_payload

    rng = np.random.default_rng(7)
    # a realistic checkpoint-shaped pytree: a few large tensors + metadata
    n = size_mb * 1024 * 1024 // 4 // 4
    tree = {
        "wq": rng.standard_normal(n, dtype=np.float32),
        "wk": rng.standard_normal(n, dtype=np.float32),
        "scales": rng.standard_normal(n, dtype=np.float32),
        "tokens": rng.integers(0, 127, size=n, dtype=np.int32),
        "meta": {"step": 1234, "names": ["wq", "wk"]},
    }
    nbytes = sum(a.nbytes for a in tree.values() if hasattr(a, "nbytes"))
    t0 = time.perf_counter()
    payload = serialize_payload(tree)
    blob = payload.join()
    ser_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = deserialize(blob)
    deser_s = time.perf_counter() - t0
    assert out["meta"]["step"] == 1234
    return {
        "serialize_gbps": round(nbytes / ser_s / 1e9, 3),
        "deserialize_gbps": round(nbytes / deser_s / 1e9, 3),
        "payload_overhead_bytes": payload.nbytes - nbytes,
    }


async def _bench_blob(size_mb: int) -> dict:
    import numpy as np

    from modal_tpu._utils.blob_utils import blob_download, blob_upload
    from modal_tpu.client import _Client

    client = await _Client.from_env()
    rng = np.random.default_rng(11)
    payload = rng.integers(0, 256, size=size_mb * 1024 * 1024, dtype=np.uint8).tobytes()
    t0 = time.perf_counter()
    blob_id = await blob_upload(payload, client.stub)
    up_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    back = await blob_download(blob_id, client.stub)
    down_s = time.perf_counter() - t0
    assert bytes(back[:64]) == payload[:64] and len(back) == len(payload)
    spilled = isinstance(back, memoryview)
    return {
        "blob_upload_gbps": round(len(payload) / up_s / 1e9, 3),
        "blob_download_gbps": round(len(payload) / down_s / 1e9, 3),
        "blob_download_spilled": spilled,
    }


async def _bench_volume(size_mb: int) -> dict:
    """Sequential chunk-loop baseline vs the striped parallel engine, plus
    the read_file_range_into→device path the weights loader takes."""
    import numpy as np

    from modal_tpu.client import _Client
    from modal_tpu.volume import _Volume

    client = await _Client.from_env()
    vol = await _Volume.ephemeral(client=client)
    rng = np.random.default_rng(13)
    data = rng.integers(0, 256, size=size_mb * 1024 * 1024, dtype=np.uint8).tobytes()
    async with vol.batch_upload(force=True) as batch:
        batch.put_data(data, "ckpt/blob.bin")

    # sequential baseline: one VolumeBlockGet at a time, appended in order —
    # the single-streamed read the striped engine replaces. Best of 2 runs
    # (both paths) so scheduler noise doesn't skew the ratio.
    from modal_tpu._utils.grpc_utils import retry_transient_errors
    from modal_tpu.proto import api_pb2

    meta = await vol._get_file_meta("ckpt/blob.bin")

    async def _seq_run() -> float:
        t0 = time.perf_counter()
        seq_total = 0
        buf = io.BytesIO()
        for sha in meta.file.block_sha256_hex:
            r = await retry_transient_errors(
                client.stub.VolumeBlockGet, api_pb2.VolumeBlockGetRequest(sha256_hex=sha)
            )
            buf.write(r.data)
            seq_total += len(r.data)
        assert seq_total == len(data)
        return time.perf_counter() - t0

    seq_s = min([await _seq_run() for _ in range(2)])

    # parallel striped engine into a preallocated temp file
    async def _par_run() -> float:
        with tempfile.NamedTemporaryFile(delete=False) as tmp:
            tmp_path = tmp.name
        try:
            with open(tmp_path, "r+b") as f:
                t0 = time.perf_counter()
                got = await vol.read_file_into("ckpt/blob.bin", f)
                elapsed = time.perf_counter() - t0
            assert got == len(data)
            return elapsed
        finally:
            os.unlink(tmp_path)

    par_s = min([await _par_run() for _ in range(2)])

    # Volume→device: ranged blocks land in a preallocated host buffer which
    # the device ingests directly (the weights-loader fast path)
    import jax.numpy as jnp

    host = bytearray(len(data))
    t0 = time.perf_counter()
    written = await vol.read_file_range_into("ckpt/blob.bin", 0, len(data), host)
    dev = jnp.asarray(np.frombuffer(host, np.uint8))
    dev.block_until_ready()
    dev_s = time.perf_counter() - t0
    assert written == len(data)
    assert np.array_equal(np.asarray(dev[:64]), np.frombuffer(data[:64], np.uint8))
    return {
        "volume_seq_gbps": round(len(data) / seq_s / 1e9, 3),
        "volume_parallel_gbps": round(len(data) / par_s / 1e9, 3),
        "volume_to_device_gbps": round(len(data) / dev_s / 1e9, 3),
        "volume_speedup": round(seq_s / par_s, 2),
    }


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--size-mb", type=int, default=1024, help="payload size per section (MiB)")
    parser.add_argument("--skip-volume", action="store_true")
    parser.add_argument("--skip-blob", action="store_true")
    args = parser.parse_args()

    # a CPU micro-bench of the host data plane, whatever the environment says
    os.environ["JAX_PLATFORMS"] = "cpu"
    result: dict = {"size_mb": args.size_mb}
    result.update(bench_serialization(args.size_mb))

    from modal_tpu._utils.async_utils import synchronizer
    from modal_tpu.client import _Client
    from modal_tpu.server.supervisor import LocalSupervisor

    state_dir = tempfile.mkdtemp(prefix="modal_tpu_dataplane_")
    sup = LocalSupervisor(num_workers=0, state_dir=state_dir)
    synchronizer.run(sup.start())
    os.environ["MODAL_TPU_SERVER_URL"] = sup.server_url
    _Client.set_env_client(None)
    try:
        if not args.skip_blob:
            result.update(synchronizer.run(_bench_blob(args.size_mb)))
        if not args.skip_volume:
            result.update(synchronizer.run(_bench_volume(args.size_mb)))
    finally:
        synchronizer.run(sup.stop())

    from modal_tpu.observability.metrics import REGISTRY

    summary = REGISTRY.bench_summary()
    if summary:
        result["metrics"] = summary
    result["peak_rss_gb"] = round(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6, 2)
    print("DATAPLANE_RESULT " + json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
