"""No-op dispatch microbench: per-segment latency attribution + profiler A/B.

The ROADMAP item 3 baseline artifact: `measure_call_wall_s` ≈ 0.2 s per
trivial call caps serving throughput, and this bench says WHERE that floor
lives before anyone tries to shave it. It drives N no-op `.remote()` calls
through the REAL stack (supervisor → scheduler → worker → container), then:

1. reads the span store back and computes the critical-path attribution of
   every measured call (observability/critical_path.py) — queue_wait, place,
   handoff, serialize, rpc, user.execute, output delivery, and the honest
   ``gap`` (unaccounted wall time; acceptance: ≤ 10%);
2. re-runs the measured loop with the sampling profiler ON
   (observability/profiler.py) and reports the overhead (acceptance: ≤ 5%).

Prints ONE line: DISPATCH_BENCH_RESULT {json}; bench.py folds the fields in
as ``dispatch_*`` (``dispatch_p50_s``, ``dispatch_attribution``, ...). The
follow-up latency PR must beat these numbers, not vibes.

Run directly: JAX_PLATFORMS=cpu python tools/bench_dispatch.py [--calls 30]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)


def _make_app(tag: str):
    import modal_tpu

    app = modal_tpu.App(f"dispatch-bench-{tag}")

    @app.function(serialized=True, timeout=120)
    def noop(x: int) -> int:
        return x

    return app, noop


def _boot_supervisor(state_dir: str):
    from modal_tpu._utils.async_utils import synchronizer
    from modal_tpu.client import _Client
    from modal_tpu.server.supervisor import LocalSupervisor

    os.environ["MODAL_TPU_STATE_DIR"] = state_dir
    sup = LocalSupervisor(
        num_workers=1, state_dir=state_dir, worker_chips=8, worker_tpu_type="local-sim"
    )
    synchronizer.run(sup.start())
    os.environ["MODAL_TPU_SERVER_URL"] = sup.server_url
    _Client.set_env_client(None)
    return sup, synchronizer


def _timed_calls(fn, n: int) -> list[float]:
    walls = []
    for i in range(n):
        t0 = time.perf_counter()
        assert fn.remote(i) == i
        walls.append(time.perf_counter() - t0)
    return walls


def _quantile(vals: list[float], q: float) -> float:
    # one quantile contract for the whole report: the bench's p50/p95 must
    # agree with the attribution table computed from the same run
    from modal_tpu.observability.quantile import quantile as shared_quantile

    return shared_quantile(sorted(vals), q)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--calls", type=int, default=30, help="measured no-op calls")
    parser.add_argument("--warmup", type=int, default=3, help="unmeasured warmup calls")
    args = parser.parse_args()

    # a CPU micro-bench of host overhead, whatever the environment says: an
    # outer JAX_PLATFORMS=tpu must not turn it into something that looks
    # like a chip run (its numbers are not device metrics)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["MODAL_TPU_JAX_PLATFORM"] = "cpu"
    os.environ["MODAL_TPU_AUTO_LOCAL_SERVER"] = "0"
    state_dir = tempfile.mkdtemp(prefix="dispatch_bench_")

    from modal_tpu.observability import critical_path as cp
    from modal_tpu.observability.catalog import DISPATCH_LATENCY

    sup, synchronizer = _boot_supervisor(state_dir)
    result: dict = {}
    try:
        app, noop = _make_app("attr")
        with app.run():
            _timed_calls(noop, args.warmup)  # container boot + jit amortized out
            t_measured0 = time.time()
            walls = _timed_calls(noop, args.calls)
            t_measured1 = time.time()

        result["calls"] = args.calls
        result["p50_s"] = round(_quantile(walls, 0.5), 4)
        result["p95_s"] = round(_quantile(walls, 0.95), 4)
        result["calls_per_s"] = round(args.calls / sum(walls), 2)

        # attribution over the measured window's traces (skip warmup: its
        # cold boot would smear container.boot over the steady-state story)
        trace_dir = os.path.join(state_dir, "traces")
        from modal_tpu.observability import tracing

        traces = {}
        for rec in tracing.read_spans(trace_dir):
            traces.setdefault(rec["trace_id"], []).append(rec)
        measured = [
            spans
            for spans in traces.values()
            if any(
                s["name"] == cp.ROOT_SPAN and t_measured0 <= s["start"] <= t_measured1
                for s in spans
            )
        ]
        per_trace = [a for spans in measured if (a := cp.attribute_trace(spans)) is not None]
        agg = cp.aggregate_attributions(per_trace)
        print(cp.format_attribution_table(agg), file=sys.stderr)
        result["attribution"] = {
            seg: round(v["p50_s"], 5) for seg, v in agg.get("segments", {}).items()
        }
        result["attribution_share"] = {
            seg: round(v["share"], 4) for seg, v in agg.get("segments", {}).items()
        }
        result["gap_share"] = round(agg.get("gap_share", 1.0), 4)
        result["attributed_share"] = round(1.0 - agg.get("gap_share", 1.0), 4)

        # exemplar proof: the dispatch histogram carries trace ids that exist
        # in the store (the acceptance path GET /metrics renders)
        ex_trace_ids = set()
        for series in DISPATCH_LATENCY._series.values():
            ex_trace_ids |= {tid for tid, _v, _t in series.exemplars.values()}
        result["exemplar_trace_ids_resolve"] = bool(ex_trace_ids) and all(
            tid in traces for tid in ex_trace_ids
        )

        # --- profiler overhead A/B on the same loop ------------------------
        # interleaved blocks (off, on, off, on, ...): supervisor state drifts
        # over a run, so back-to-back halves would measure drift, not the
        # sampler; per-call medians of the pooled blocks are drift-robust
        from modal_tpu.observability import profiler

        profiles_dir = os.path.join(state_dir, "observability", "profiles")
        app2, noop2 = _make_app("prof")
        base: list[float] = []
        profiled: list[float] = []
        block = max(3, args.calls // 4)
        with app2.run():
            _timed_calls(noop2, args.warmup)
            for i in range(8):
                if i % 2:
                    profiler.start(profiles_dir, tag="bench", hz=profiler.DEFAULT_HZ)
                    profiled += _timed_calls(noop2, block)
                    profiler.stop()
                else:
                    base += _timed_calls(noop2, block)
        base_p50, prof_p50 = _quantile(base, 0.5), _quantile(profiled, 0.5)
        result["profiler_hz"] = profiler.DEFAULT_HZ
        result["profiler_overhead_pct"] = round(100.0 * (prof_p50 - base_p50) / base_p50, 2)
        result["profiler_samples"] = profiler.current().n_samples if profiler.current() else 0

        # --- concurrency sweep (ISSUE 8 satellite) -------------------------
        # 1/8/64 in-flight callers against one concurrent container: the
        # coalesced submit/claim/publish planes should hold calls/s roughly
        # flat per RPC while concurrency grows
        from concurrent.futures import ThreadPoolExecutor

        import modal_tpu

        app4 = modal_tpu.App("dispatch-bench-sweep")

        def noop_c(x: int) -> int:
            return x

        noop_c = modal_tpu.concurrent(max_inputs=64)(noop_c)
        noop_c = app4.function(serialized=True, timeout=120)(noop_c)
        sweep: dict = {}
        with app4.run():
            _timed_calls(noop_c, args.warmup)
            for conc in (1, 8, 64):
                n_calls = max(16, conc * 3)

                def _one(i: int) -> float:
                    t0 = time.perf_counter()
                    assert noop_c.remote(i) == i
                    return time.perf_counter() - t0

                t_sw0 = time.perf_counter()
                with ThreadPoolExecutor(max_workers=conc) as pool:
                    call_walls = list(pool.map(_one, range(n_calls)))
                wall = time.perf_counter() - t_sw0
                sweep[str(conc)] = {
                    "calls": n_calls,
                    "calls_per_s": round(n_calls / wall, 2),
                    "p50_s": round(_quantile(sorted(call_walls), 0.5), 4),
                    "p95_s": round(_quantile(sorted(call_walls), 0.95), 4),
                }
                print(f"sweep conc={conc}: {sweep[str(conc)]}", file=sys.stderr)
        result["sweep"] = sweep
        result["max_calls_per_s"] = max(v["calls_per_s"] for v in sweep.values())
    finally:
        synchronizer.run(sup.stop())

    print("DISPATCH_BENCH_RESULT " + json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
