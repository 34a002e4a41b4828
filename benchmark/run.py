#!/usr/bin/env python3
"""One run of one benchmark cell, through the entry point users call.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

    modal_tpu.App -> modal_tpu.serving.llm_service (its own load(), engine and
      ASGI app; benchlib/incontainer.py adds /bench/* beside them)
      -> app.run() -> the worker places ONE container with one pinned chip
      -> get_web_url() -> warm-up requests (every shape the window uses)
      -> the window: the traffic file's schedule, POST /v1/generate streamed
      -> /v1/stats at both ends, /bench/device, (traced: /bench/trace/*)
      -> the app is stopped, the container exits, the chip is free
      -> the configuration's reference (its key `reference`, default
         benchlib/reference.py) in a child: the plain reference over a sample
         of the requests the window finished decides `correct`
      -> one last line on standard output, built by benchlib/emit.py

This process never imports jax: a process that has touched jax holds the
chip and its container could not. The device is probed from a child; with
no TPU, a device that `peaks.json` does not list, or fewer chips than the
cell asks for, it exits non-zero and prints no result line.
"""

from __future__ import annotations

import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import importlib  # noqa: E402
import inspect  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import urllib.parse  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)

from benchlib import client, emit as emit_mod, stats, traffic  # noqa: E402
from kernels import counts_for  # noqa: E402


class RunFailed(Exception):
    """The run cannot give a result: exit non-zero, print no last line."""


def log(msg: str) -> None:
    sys.stderr.write(f"[bench {time.monotonic() - PROCESS_START:7.1f}s] {msg}\n")
    sys.stderr.flush()


# -- what the cell is ----------------------------------------------------------


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: str, workload: str) -> dict:
    """The cell's entry, configuration, traffic and metrics, found by name.
    `root` holds BENCHMARK.json, the configuration's file and the traffic;
    what a configuration or a metric names in turn is this directory's."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise RunFailed(f"BENCHMARK.json has no workload {workload!r}")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(root, conf["file"]))
    e2e = {m["name"]: m for m in bench["end_to_end"] if workload in m.get("workloads", [workload])}
    # a per-layer metric without a list of cells belongs to every cell that
    # reports the end-to-end metric it moves
    per_layer = {
        m["name"]: m for m in bench["per_layer"]
        if (workload in m["workloads"] if "workloads" in m else m["moves"] in e2e)
    }
    return {
        "bench": bench,
        "cell": cell,
        "config": config,
        "reference": check_names(conf["file"], config, per_layer),
        "traffic": traffic.load(traffic.find(os.path.join(root, bench["paths"][0], "traffic"), cell["traffic"])),
        "end_to_end": e2e,
        "per_layer": per_layer,
    }


def check_names(conf_file: str, config: dict, per_layer: dict) -> str:
    """Everything the cell finds by a NAME in a file, found now, before
    anything boots: the configuration's reference and counts (files of this
    directory; another architecture brings its own) and, for each of the
    cell's per-layer metrics, the functions and the configuration key its
    reader will look up. A name that finds nothing ends the run, naming the
    file and the key: never a fall-back to the default, never an error
    after the window. Returns the reference's path."""
    named = {
        "reference": config.get("reference", "benchlib/reference.py"),
        "counts": os.path.join("kernels", config.get("counts", "counts") + ".py"),
    }
    for key, rel in named.items():
        if not os.path.isfile(os.path.join(BENCH_DIR, rel)):
            raise RunFailed(f"{conf_file}: key {key!r} names {config.get(key)!r}: no file {os.path.join(BENCH_DIR, rel)}")
    counts = counts_for(config)
    for name in per_layer:
        reader, args = layer_metric(name)
        takes = inspect.signature(reader.read).parameters
        for key in ("bytes_fn", "flops_fn", "calls_key"):  # the reader's default where the file names none
            if key not in takes:
                continue
            value = args.get(key, takes[key].default)
            found = value in config if key == "calls_key" else callable(getattr(counts, value, None))
            if not found:
                where = conf_file if key == "calls_key" else named["counts"] + " (" + conf_file + ")"
                raise RunFailed(f"layer_metrics/{name}.json: {key} names {value!r}: not in {where}")
    return os.path.join(BENCH_DIR, named["reference"])


def layer_metric(name: str) -> tuple:
    """A per-layer metric is a file of its own naming a reader of its own."""
    spec = load_json(os.path.join(BENCH_DIR, "layer_metrics", name + ".json"))
    return importlib.import_module("readers." + spec["reader"]), spec.get("args", {})


def read_layer_metric(name: str, ctx: dict):
    reader, args = layer_metric(name)
    return reader.read(ctx, **args)


# -- the device, from a child ---------------------------------------------------


def probe_device(peaks: dict, chips: int) -> dict:
    from modal_tpu.server.worker import TpuProbeError, probe_jax_devices

    try:
        count, platform, kind = probe_jax_devices(timeout_s=300)
    except TpuProbeError as exc:
        raise RunFailed(f"device probe failed: {exc}")
    if platform != "tpu":
        raise RunFailed(f"jax finds no TPU here (platform {platform!r}): the benchmark runs on the chip only")
    if kind not in peaks:
        raise RunFailed(f"device_kind {kind!r} is not in peaks.json: a device without peaks is an error, not a default")
    if count < chips:
        raise RunFailed(f"the cell asks for {chips} chip(s), jax finds {count}")
    return {"platform": platform, "kind": kind, "count": count}


# -- one window -------------------------------------------------------------------


async def warm_up(url: str, spec: dict, vocab: int, seed: int) -> list:
    """Requests that touch every shape the window will use: each prefill
    bucket up to the chunk, page growth and copy-on-write, the decode step."""
    rng = random.Random(int(seed) + 17)
    warm = spec["warmup"]
    parsed = urllib.parse.urlparse(url)
    records, tasks = [], []
    for i, n in enumerate(warm["prompt_tokens"]):
        prompt = [rng.randrange(vocab) for _ in range(int(n))]
        rec = client.Record(-1 - i, len(prompt), int(warm["output_tokens"]), time.monotonic())
        records.append(rec)
        tasks.append(asyncio.create_task(client.generate(parsed.hostname, parsed.port, prompt, rec)))
    await asyncio.gather(*tasks)
    bad = [r.error or "short" for r in records if r.error or len(r.tokens) != r.max_new_tokens]
    if bad:
        raise RunFailed(f"warm-up requests failed: {bad[:3]}")
    return records


async def traced_part(url: str, t0: float, spec: dict, trace_dir: str, window_s: float) -> dict:
    """Bracket a steady sub-window with the profiler, in the container."""
    plan = spec.get("trace", {})
    length = min(float(plan.get("length_s", 4.0)), max(1.0, window_s / 3))
    start = min(float(plan.get("start_s", 5.0)), max(0.0, window_s - length - 3.0))
    await asyncio.sleep(max(0.0, t0 + start - time.monotonic()))
    await client.http_json(url + "/bench/trace/start", {"dir": trace_dir}, timeout=120)
    t_a = time.monotonic()
    await asyncio.sleep(length)
    t_b = time.monotonic()
    stopped = await client.http_json(url + "/bench/trace/stop", {}, timeout=240)
    return {"t_start": t_a, "t_stop": t_b, "window_s": stopped["window_s"], "stop_s": time.monotonic() - t_b}


async def window(url: str, cell: dict, schedule, seed: int, seconds: float, traced: bool, trace_dir: str) -> dict:
    spec, vocab = cell["traffic"], int(cell["config"]["vocab_size"])
    stats_start = await client.http_json(url + "/v1/stats")
    t0 = time.monotonic()
    tracer = asyncio.create_task(traced_part(url, t0, spec, trace_dir, seconds)) if traced else None
    drain = float(spec.get("drain_s", 60))
    if schedule.loop == "open":
        records = await client.run_open(url, schedule.requests, t0, seconds, drain)
    else:
        queue, rounds = list(schedule.requests), [0]

        def next_request():
            if not queue:
                rounds[0] += 1
                queue.extend(traffic.refill(schedule, spec, seed, rounds[0], vocab))
            return queue.pop(0)

        records = await client.run_closed(url, schedule.clients, next_request, t0, seconds, drain)
    trace_window = await tracer if tracer else None
    stats_end = await client.http_json(url + "/v1/stats")
    device = await client.http_json(url + "/bench/device")
    return {
        "t0": t0, "records": records, "stats_start": stats_start, "stats_end": stats_end,
        "device": device, "trace_window": trace_window, "drain_s": drain,
    }


def traced_work(records: list, tw: dict) -> dict:
    """What the model had to process inside the traced window, from the
    client's records: every output token received in it, and the prompts of
    the requests whose FIRST token arrived in it (`/v1/stats` has no counter
    of prompt tokens prefilled: exact over many requests, a prompt coarse
    over a few seconds)."""
    a, b = tw["t_start"], tw["t_stop"]
    prompts = [r.prompt_len for r in records if r.token_times and a <= r.token_times[0] <= b]
    contexts = [
        r.prompt_len + i - 1
        for r in records
        for i, t in enumerate(r.token_times)
        if i >= 1 and a <= t <= b
    ]
    active, live, samples = 0.0, 0.0, 64
    for k in range(samples):
        t = a + (b - a) * (k + 0.5) / samples
        for r in records:
            if r.token_times and r.token_times[0] <= t < r.token_times[-1]:
                active += 1
                live += r.prompt_len + sum(1 for x in r.token_times if x <= t)
    return {
        "prefilled_prompts": prompts, "decode_contexts": contexts,
        "mean_active_slots": active / samples, "mean_live_kv_tokens": live / samples,
    }


# -- after the window: the chip is free, the reference runs ---------------------------


def wait_for_exit(pid: int, timeout_s: float = 60.0) -> None:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            os.kill(pid, 0)
        except OSError:
            return
        time.sleep(0.2)
    raise RunFailed(f"the container (pid {pid}) still holds the chip {timeout_s:.0f} s after the app stopped")


def pick_sample(records: list, seed: int, k: int) -> list:
    """A sample, drawn from the seed, of the requests the window finished,
    with the longest in it."""
    done = [r for r in records if r.finished and r.tokens]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.prompt_len + len(r.tokens), -r.index))
    rest = [r for r in done if r is not longest]
    random.Random(int(seed) + 31).shuffle(rest)
    return [longest] + rest[: max(0, k - 1)]


def run_child(argv: list, timeout_s: float, env: dict | None = None) -> None:
    proc = subprocess.run([sys.executable] + argv, env=env, capture_output=True, text=True, timeout=timeout_s)
    if proc.returncode != 0:
        raise RunFailed(f"{os.path.basename(argv[0])} exited {proc.returncode}; stderr ends:\n{proc.stderr[-2000:]}")


def run_reference(cell: dict, seed: int, sample: list, state_dir: str, control: str, platform: str) -> dict:
    spec = cell["traffic"]
    longest = int(spec["prompt_tokens"].get("max", spec["prompt_tokens"].get("value", 0))) + int(
        spec["output_tokens"].get("max", spec["output_tokens"].get("value", 0))
    )
    job = {
        "config": cell["config"], "seed": seed, "control": control, "require_platform": platform,
        # every sequence is padded to the mix's longest, so a cell's reference is ONE compiled shape
        "pad_to": -(-longest // 512) * 512,
        "requests": [{"index": r.index, "prompt": r.prompt, "tokens": r.tokens} for r in sample],
    }
    job_path, out_path = os.path.join(state_dir, "reference_job.json"), os.path.join(state_dir, "reference_out.json")
    with open(job_path, "w") as f:
        json.dump(job, f)
    run_child([cell["reference"], job_path, out_path], timeout_s=300)
    return load_json(out_path)


def reduce_trace(trace_dir: str, state_dir: str, clock_window_s: float, keep_table: bool) -> dict:
    out_path = os.path.join(state_dir, "trace_summary.json")
    argv = [os.path.join(BENCH_DIR, "benchlib", "trace_reduce.py"), trace_dir, out_path, repr(float(clock_window_s))]
    if keep_table:
        argv += ["--table", os.path.join(state_dir, "trace_table.json")]
    run_child(argv, timeout_s=200, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    return load_json(out_path)


# -- the run -----------------------------------------------------------------------


def container_stderr_tails(limit: int = 1200) -> str:
    """What the run's containers said before a boot gave up: out of memory,
    or the pid that holds the chip."""
    tasks_dir = os.path.join(os.environ.get("MODAL_TPU_STATE_DIR", ""), "tasks")
    tails = []
    for task_id in sorted(os.listdir(tasks_dir)) if os.path.isdir(tasks_dir) else ():
        try:
            with open(os.path.join(tasks_dir, task_id, "stderr.log"), errors="replace") as f:
                text = f.read().strip()
        except OSError:
            continue
        if text:
            tails.append(f"--- {task_id} stderr (tail) ---\n{text[-limit:]}")
    return "\n".join(tails[-2:]) or "(no container stderr)"


def stop_supervisor() -> None:
    from modal_tpu.client import _Client

    sup = _Client._local_supervisor
    if sup is not None:
        from modal_tpu._utils.async_utils import synchronizer

        synchronizer.run(sup.stop())


def serve_and_measure(cell: dict, args, state_dir: str, timings: dict) -> dict:
    import modal_tpu
    from modal_tpu.exception import ExecutionError

    from benchlib import incontainer

    cfg, spec = cell["config"], cell["traffic"]
    vocab = int(cfg["vocab_size"])
    schedule = traffic.build(spec, args.seed, args.seconds, vocab, rate_scale=args.rate_scale)
    app = modal_tpu.App("bench-" + cell["cell"]["name"].replace(".", "-"))
    service, passed = incontainer.build_service(app, cfg, args.seed)
    timings["service_options"] = {k: v for k, v in passed.items() if isinstance(v, (int, float, str, bool))}
    trace_dir = os.path.join(state_dir, "trace")
    t_run = time.monotonic()
    with app.run():
        try:
            url = service.get_web_url(timeout=args.boot_timeout)
        except ExecutionError as exc:
            raise RunFailed(f"no container served within {args.boot_timeout:.0f} s: {exc}\n{container_stderr_tails()}")
        timings["boot_to_url_s"] = time.monotonic() - t_run
        log(f"service up at {url} after {timings['boot_to_url_s']:.1f} s")

        async def drive() -> dict:
            t_w = time.monotonic()
            await warm_up(url, spec, vocab, args.seed)
            timings["warmup_s"] = time.monotonic() - t_w
            timings["setup_s"] = time.monotonic() - PROCESS_START
            log(f"warm-up {timings['warmup_s']:.1f} s; window of {args.seconds:.0f} s starts")
            return await window(url, cell, schedule, args.seed, args.seconds, bool(args.trace), trace_dir)

        result = asyncio.run(drive())
    timings["window_closed_s"] = time.monotonic() - PROCESS_START
    return result


def decide_correct(cell: dict, args, records: list, bad_streams: int, state_dir: str, platform: str) -> tuple:
    """The plain reference over a sample of what the window finished; every
    number compared beside its limit (the configuration's file has them).
    Under `--control reference_fp8` the reference in float8 stands in the
    program's place: ITS gaps on the same prompts and tokens are the ones
    compared, and the run has to come out as not correct."""
    rules = cell["config"]["correct"]
    sample = pick_sample(records, args.seed, int(rules["sample_requests"]))
    control = args.control == "reference_fp8"
    reference = {}
    if sample and not args.no_reference:
        reference = run_reference(cell, args.seed, sample, state_dir, "fp8" if control else "", platform)
    judged = "control_" if control else ""
    readings = {
        "logit_gap_max": reference.get(judged + "logit_gap_max"),
        "logit_gap_mean": reference.get(judged + "logit_gap_mean"),
        "bad_streams": bad_streams,
    }
    compared = {name: {"value": readings[name], "limit": limit} for name, limit in rules["limits"].items()}
    correct = bool(sample) and all(p["value"] is not None and p["value"] <= p["limit"] for p in compared.values())
    return reference, compared, correct


def traced_metrics(cell: dict, ctx: dict, records: list, trace_window: dict, state_dir: str, keep_table: bool) -> tuple:
    """Reduce the trace, add it and the traced window's work to `ctx`, and
    let every per-layer metric of the cell read its number from there."""
    trace = reduce_trace(os.path.join(state_dir, "trace"), state_dir, trace_window["window_s"], keep_table)
    ctx["trace"], ctx["traced_work"] = trace, traced_work(records, trace_window)
    required = {name: m["unit"] for name, m in cell["per_layer"].items()}
    reported = {name: read_layer_metric(name, ctx) for name in required}
    # the contract: a reader that finds nothing returns nothing, and the
    # metric is left out of the line (never a 0). The driver refuses a line
    # that lacks a metric the cell has to report; the names go on the line
    silent = sorted(n for n, v in reported.items() if v is None)
    for name in silent:
        log(f"WARNING: per-layer metric {name}: its reader found nothing to read; left out of the line")
        del reported[name], required[name]
    if not reported:
        raise emit_mod.MalformedLine("no per-layer metric of this cell found anything to read")
    return required, reported, {"device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"]}, silent


def measure(args, root: str = REPO_ROOT, state_root: str = "") -> dict:
    """Everything between the arguments and the line: returns the line.
    `root` is where BENCHMARK.json and its data files are (tests pass a
    directory of their own); the system under test is always REPO_ROOT's."""
    cell = load_cell(root, args.workload)
    peaks = load_json(os.path.join(BENCH_DIR, "peaks.json"))
    chips = int(cell["cell"]["chips"])
    if not os.path.isdir(os.path.join(REPO_ROOT, "modal_tpu")):
        raise RunFailed("no modal_tpu package beside the benchmark: there is no system here to measure")
    if REPO_ROOT not in sys.path:
        sys.path.insert(0, REPO_ROOT)
    probed = probe_device(peaks, chips)
    log(f"device: {probed}")

    # state and caches at fixed paths inside the checkout (the path is part
    # of the compile cache's key); JAX_COMPILATION_CACHE_DIR wins if set
    bench_state = state_root or os.path.join(REPO_ROOT, ".bench_state")
    state_dir = os.path.join(bench_state, "run")
    shutil.rmtree(state_dir, ignore_errors=True)
    os.makedirs(state_dir, exist_ok=True)
    os.environ["MODAL_TPU_STATE_DIR"] = os.path.join(state_dir, "modal_tpu")
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(bench_state, "jit_cache"))
    os.environ["PYTHONPATH"] = os.pathsep.join(p for p in (BENCH_DIR, os.environ.get("PYTHONPATH", "")) if p)
    if probed["platform"] == "tpu":
        # the worker would probe a second time (about 15 s): hand it ours
        os.environ.setdefault("MODAL_TPU_WORKER_TPU_TYPE", probed["kind"])
        os.environ.setdefault("MODAL_TPU_WORKER_NUM_CHIPS", str(probed["count"]))

    timings: dict = {}
    try:
        result = serve_and_measure(cell, args, state_dir, timings)
    finally:
        stop_supervisor()
    device = result["device"]
    if probed["platform"] == "tpu":  # the reference needs the chip the container held
        wait_for_exit(int(device["pid"]))
    timings["chip_free_s"] = time.monotonic() - PROCESS_START
    if probed["platform"] == "tpu" and "jax" in sys.modules:
        raise RunFailed("the harness process imported jax: it would hold the chip")

    records, t0 = result["records"], result["t0"]
    numbers = stats.window_numbers(records, t0, args.seconds, result["drain_s"])
    attempted = [r for r in records if not r.cut or r.error]
    failed = [r for r in attempted if r.error]
    finished = [r for r in records if r.finished]
    vocab = int(cell["config"]["vocab_size"])
    bad_streams = sum(
        1 for r in finished
        if len(r.tokens) != r.max_new_tokens or any(not (isinstance(t, int) and 0 <= t < vocab) for t in r.tokens)
    )
    for r in failed[:5]:
        log(f"failed request {r.index}: {r.error}")

    reference, compared, correct = decide_correct(cell, args, records, bad_streams, state_dir, probed["platform"])
    timings["reference_s"] = time.monotonic() - PROCESS_START - timings["chip_free_s"]

    metrics: dict = {"setup_s": timings["setup_s"]}
    if numbers["ttft_ms"]:
        metrics["ttft_p90_ms"] = stats.percentile(numbers["ttft_ms"], 90)
    if numbers["itl_ms"]:
        metrics["itl_p99_ms"] = stats.percentile(numbers["itl_ms"], 99)
    metrics["serve_tokens_per_s"] = numbers["tokens_in_window"] / args.seconds
    e2e_required = {name: m["unit"] for name, m in cell["end_to_end"].items()}
    e2e = {name: metrics.get(name) for name in e2e_required}

    out_device = {
        "platform": device["platform"], "kind": device["kind"], "count": device["count"],
        "memory_peak_bytes": device["memory_peak_bytes"],
    }
    extra = {
        "workload": args.workload, "seed": args.seed, "seconds": args.seconds,
        "requests": {"sent": len(records), "finished": len(finished), "cut_at_close": sum(1 for r in records if r.cut and not r.error)},
        "compiles_in_window": compile_events(result["stats_end"]) - compile_events(result["stats_start"]),
        "timings": {k: v for k, v in timings.items() if isinstance(v, float)},
        # `compared` holds the gaps that were judged; under a control these are the program's own
        "reference": {k: reference.get(k) for k in ("tokens_compared", "requests_compared", "logit_gap_max", "logit_gap_mean", "init_s", "compare_s")},
    }
    if args.control:
        extra["control"] = args.control
    if extra["compiles_in_window"]:
        log(f"WARNING: {extra['compiles_in_window']} compile event(s) inside the window")
    breakdown, required, reported, ctx = None, e2e_required, e2e, None
    if args.trace:
        ctx = {
            "config": cell["config"], "peaks": peaks[device["kind"]], "chips": chips, "timings": timings,
            "stats_start": result["stats_start"], "stats_end": result["stats_end"],
            "client": {**numbers, "first_tokens_in_window": sum(
                1 for r in records if r.token_times and t0 <= r.token_times[0] <= t0 + args.seconds
            )},
        }
        required, reported, breakdown, extra["silent_metrics"] = traced_metrics(
            cell, ctx, records, result["trace_window"], state_dir, bool(args.dump)
        )
        out_device["busy_s"], out_device["window_s"] = ctx["trace"]["busy_s"], ctx["trace"]["window_s"]
        extra["end_to_end_traced"] = {n: {"value": v, "unit": e2e_required[n]} for n, v in e2e.items()}
        work = ctx["traced_work"]
        extra["traced_work"] = {
            "prompt_tokens": sum(work["prefilled_prompts"]),
            "output_tokens": len(work["decode_contexts"]),
            "mean_active_slots": work["mean_active_slots"], "mean_live_kv_tokens": work["mean_live_kv_tokens"],
        }
        extra["trace_stop_s"] = result["trace_window"]["stop_s"]
        # `device.window_s` is the larger of the two (benchlib/trace_reduce.py says why)
        extra["traced_window"] = {"clock_s": ctx["trace"]["clock_window_s"], "span_s": ctx["trace"]["span_s"]}
    line = emit_mod.build_line(
        correct=correct, attempted=len(attempted), failed=len(failed), metrics=reported, required=required,
        device=out_device, traced=bool(args.trace), compared=compared, breakdown=breakdown, extra=extra,
    )
    if args.dump:
        dump(args.dump, state_dir, line, result, numbers, reference, timings, ctx)
    return line


def compile_events(stats_: dict) -> int:
    events = (stats_.get("compile") or {}).get("compile_events") or {}
    return int(sum(v for k, v in events.items() if "cache" in k or "compile" in k))


def dump(path: str, state_dir: str, line: dict, result: dict, numbers: dict, reference: dict, timings: dict, ctx: dict | None) -> None:
    """Development only: everything a run saw, for the output directory.
    `ctx` (traced runs) is what the readers read: fed to another tree's
    readers it shows, offline, whether a per-layer metric moved."""
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    recs = [
        {"index": r.index, "prompt_len": r.prompt_len, "max_new_tokens": r.max_new_tokens, "due": r.due - result["t0"],
         "sent": r.sent - result["t0"], "first": (r.token_times[0] - result["t0"]) if r.token_times else None,
         "last": (r.token_times[-1] - result["t0"]) if r.token_times else None, "n": len(r.tokens),
         "error": r.error, "cut": r.cut, "server_ttft_s": r.server_ttft_s}
        for r in result["records"]
    ]
    with open(path, "w") as f:
        json.dump({
            "line": line, "records": recs, "stats_start": result["stats_start"], "stats_end": result["stats_end"],
            "device": result["device"], "reference": reference, "timings": timings, "ctx": ctx,
            "ttft_ms": sorted(numbers["ttft_ms"]), "itl_ms_percentiles": {q: stats.percentile(numbers["itl_ms"], q) for q in (50, 90, 99)} if numbers["itl_ms"] else {},
        }, f)
    table = os.path.join(state_dir, "trace_table.json")
    if os.path.isfile(table):
        shutil.copy(table, os.path.splitext(path)[0] + ".trace_table.json")


def parse(argv: list):
    parser = argparse.ArgumentParser(description="one run of one benchmark cell")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # development only (the driver never passes these)
    parser.add_argument("--rate-scale", type=float, default=1.0, help="multiply an open loop's rate (the sweep)")
    parser.add_argument("--control", default="", choices=("", "reference_fp8"), help="judge the reference in float8 in the program's place: the run has to read correct: false")
    parser.add_argument("--no-reference", action="store_true", help="skip the reference (the sweep): the run reads as not correct")
    parser.add_argument("--dump", default="", help="write everything the run saw to this file")
    parser.add_argument("--boot-timeout", type=float, default=420.0)
    return parser.parse_args(argv)


def main(argv: list) -> int:
    args = parse(argv)
    try:
        line = measure(args)
        emit_mod.emit(line)
    except (RunFailed, emit_mod.MalformedLine) as exc:
        sys.stderr.write(f"benchmark: no result: {type(exc).__name__}: {exc}\n")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
