#!/usr/bin/env python3
"""Does the system still start on the chip? The quickest end-to-end proof.

    python chip_smoke.py              # one TPU chip: the served path
    python chip_smoke.py --chips 4    # a four-chip host: trainer + 4 replicas
    python chip_smoke.py --cpu        # explicit CPU dry run on `tiny`

One chip (the default) drives the path a user of `llm_service` takes:

    modal_tpu.App -> modal_tpu.serving.llm_service(app, tpu="v5e-1")
      -> app.run() (auto-booted in-process LocalSupervisor: control plane,
         scheduler, blob server, one worker that probes the host's chips)
      -> the worker places ONE container with one pinned chip
      -> @enter builds params + ServingEngine -> get_web_url()
      -> HTTP POST /v1/generate, buffered and SSE

at Llama-3-8B's published widths (depth cut to what one 16 GB chip holds,
random weights from a seed), and checks what came out by the repo's own
means. Phases run one after another, each in its own `app.run()`, because a
container owns its chip until it exits:

  boot 1   service up, eight requests in flight together, /v1/stats read
  boot 2   the same again in a fresh container: its step programs must come
           from the persistent compile cache (the cache directory is stable)
  parity   in a container on the chip: one decode step's logits with the
           Pallas kernel against the gather path on the same cache and tokens,
           at a benchmark cell's engine geometry (32 slots x 512 pages, ragged
           lengths, a slot that holds a prompt and does not decode, 20 idle)
           and 12 layers (the gather path's temporaries need the room)

This process never imports jax — a process that has touched jax holds the
chip and its containers could not. Everything about the device is read from
a child process (the probe) or from the server (`/v1/stats`).

Standard output ends with two lines: `summary: {...}`, what every phase saw,
and last the verdict `{"ok": true, "device": {"platform": ..., "kind": ...,
"count": ...}}` with exactly those keys and the device as jax reports it.
Exit status 0 and `"ok": true` only if every phase passed on a TPU (or on the
CPU under --cpu); a failed phase exits 1 with `"ok": false` and no summary.
No TPU and no --cpu: exit 2, and no result line at all. Nothing here is a
measurement: the summary ends with `"claim": null`.
"""

from __future__ import annotations

import argparse
import http.client
import json
import math
import os
import random
import shutil
import sys
import threading
import time
import urllib.parse
import urllib.request

REPO_ROOT = os.path.dirname(os.path.abspath(__file__))
STATE_DIR = os.path.join(REPO_ROOT, ".modal_tpu_state", "chip_smoke")

# Llama-3-8B at its published widths (models/llama.py CONFIGS["llama3-8b"]:
# dim 4096, 32 query / 8 KV heads of 128, FFN 14336, vocab 128256, bf16).
# Depth: 16 of 32 layers = 4.54 B parameters = 8.47 GiB, which leaves one
# 15.75 GiB chip room for the engine's default KV pool (2,048 pages of 16
# tokens = 2 GiB at this depth) twice over — a step holds the donated pool
# and its successor at once (my chip run, PR 21: a 4 GiB pool ran out of HBM
# on the first step, 3 GiB and 2 GiB ran).
CHIP_MODEL = {"name": "llama3-8b", "n_layers": 16}
# the parity phase runs the decode step at a benchmark cell's engine geometry (32 slots x 512 pages),
# where the GATHER path it compares with makes 5 GB of span-wide temporaries: 12 layers (6.4 GiB of
# weights, a 2.25 GiB pool; 9.8 GB in use after the phase) leave it room. Not fewer: the two paths'
# logits differ by 0.159 at 8 layers and 0.182 at 12 (my chip runs, PR 33) against tolerances of
# 0.162 and 0.205: the difference shrinks more slowly with the depth than the tolerance below does
PARITY_MODEL = {"name": "llama3-8b", "n_layers": 12}
CHIP_WIDTHS = {"dim": 4096, "n_heads": 32, "n_kv_heads": 8, "head_dim": 128, "ffn_dim": 14336, "vocab_size": 128256, "dtype": "bfloat16"}
# the trainer keeps weights, grads and both Adam moments (8 bytes a
# parameter): tried deepest first, each attempt in a fresh container
TRAIN_LAYERS = (16, 12, 8)
CPU_MODEL = "tiny"

NEW_TOKENS = 64
# eight requests in flight together. Chunks of prefill_chunk=128: one prompt
# past 1,024 tokens (nine chunks), one short, tails that land in three
# PREFILL_BUCKETS (32, 64, 128)
CHIP_PROMPT_LENS = (1100, 24, 300, 64, 500, 40, 200, 128)
CPU_PROMPT_LENS = (150, 24, 100, 64, 130, 40, 90, 17)  # tiny: context 256
STREAMED = (0, 1)  # indices sent with "stream": true (the longest and the shortest)


def log(msg: str) -> None:
    print(msg, flush=True)


class PhaseFailed(Exception):
    pass


# -- the device, from a child process ----------------------------------------


def probe_device() -> dict:
    """`jax.devices()` as a child reports it (the worker's own probe; the
    child exits, so it holds no chip afterwards). A probe that fails is a
    failed smoke, never a CPU run."""
    from modal_tpu.server.worker import TpuProbeError, probe_jax_devices

    try:
        count, platform, kind = probe_jax_devices(timeout_s=300)
    except TpuProbeError as exc:
        sys.stderr.write(f"chip_smoke: {exc}\n")
        sys.exit(2)
    return {"platform": platform, "kind": kind, "count": count}


# -- running one phase through the stack ---------------------------------------


def supervisor():
    """The LocalSupervisor `app.run()` auto-booted in this process."""
    from modal_tpu.client import _Client

    return _Client._local_supervisor


def container_stderr_tails(limit: int = 1500) -> str:
    """What the containers of this run said before a phase gave up — for a
    chip another process holds, libtpu names the pid there."""
    tasks_dir = os.path.join(STATE_DIR, "tasks")
    tails = []
    for task_id in sorted(os.listdir(tasks_dir)) if os.path.isdir(tasks_dir) else ():
        try:
            with open(os.path.join(tasks_dir, task_id, "stderr.log"), errors="replace") as f:
                text = f.read().strip()
        except OSError:
            continue
        if text:
            tails.append(f"--- {task_id} stderr (tail) ---\n{text[-limit:]}")
    return "\n".join(tails[-4:]) or "(no container stderr)"


def http_json(url: str, body: dict | None = None, timeout: float = 600.0) -> dict:
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, headers={"content-type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def generate(url: str, prompt: list, stream: bool, out: dict) -> None:
    """One POST /v1/generate; fills `out` with tokens and, for a stream, when
    the first token and the done event arrived."""
    body = {"prompt": prompt, "max_new_tokens": NEW_TOKENS, "stream": stream}
    t0 = time.monotonic()
    try:
        if not stream:
            res = http_json(url + "/v1/generate", body)
            out.update(tokens=res["tokens"], error=res.get("error"))
            return
        parsed = urllib.parse.urlparse(url)
        conn = http.client.HTTPConnection(parsed.hostname, parsed.port, timeout=600)
        conn.request("POST", "/v1/generate", json.dumps(body), {"content-type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            raise RuntimeError(f"HTTP {resp.status}: {resp.read()[:300]!r}")
        tokens, event = [], ""
        for raw in resp:
            line = raw.decode().rstrip("\n")
            if line.startswith("event: "):
                event = line[len("event: "):]
            elif line.startswith("data: ") and event == "token":
                tokens.append(json.loads(line[len("data: "):])["token"])
                out.setdefault("first_token_s", time.monotonic() - t0)
            elif line.startswith("data: ") and event == "done":
                out["done_s"] = time.monotonic() - t0
                out["error"] = json.loads(line[len("data: "):]).get("error")
                break
        conn.close()
        out["tokens"] = tokens
        if "done_s" not in out:
            raise RuntimeError("stream ended without a done event")
    except Exception as exc:  # noqa: BLE001 — counted as a failed request, reported by name
        out["error"] = f"{type(exc).__name__}: {exc}"


def check_tokens(tokens: list, vocab: int) -> str:
    if len(tokens) != NEW_TOKENS:
        return f"{len(tokens)} tokens, expected {NEW_TOKENS}"
    bad = [t for t in tokens if not (isinstance(t, int) and 0 <= t < vocab)]
    return f"token ids outside [0, {vocab}): {bad[:5]}" if bad else ""


def serve_once(label: str, model, vocab: int, prompt_lens: tuple, want_platform: str, timeout_s: float) -> dict:
    """One boot of the service in its own app.run(): all requests at once,
    then /v1/stats. Raises PhaseFailed with the reason."""
    import modal_tpu
    from modal_tpu.exception import ExecutionError

    app = modal_tpu.App(f"chip-smoke-{label}")
    service = modal_tpu.serving.llm_service(
        app, model=model, tpu="v5e-1", name="SmokeLLM", max_slots=8, min_containers=1, max_containers=1
    )
    rng = random.Random(0)
    prompts = [[rng.randrange(vocab) for _ in range(n)] for n in prompt_lens]
    t0 = time.monotonic()
    with app.run():
        try:
            url = service.get_web_url(timeout=timeout_s)
        except ExecutionError as exc:
            raise PhaseFailed(f"{label}: no container served within {timeout_s:.0f}s ({exc})\n{container_stderr_tails()}")
        boot_s = time.monotonic() - t0
        results = [{} for _ in prompts]
        threads = [
            threading.Thread(target=generate, args=(url, p, i in STREAMED, results[i]), daemon=True)
            for i, p in enumerate(prompts)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=timeout_s)
        stats = http_json(url + "/v1/stats")
    failed = {}
    for i, res in enumerate(results):
        problem = res.get("error") or ("no answer" if "tokens" not in res else check_tokens(res["tokens"], vocab))
        if problem:
            failed[i] = problem
    streams = [results[i] for i in STREAMED]
    summary = {
        "boot_s": round(boot_s, 1),
        "requests_sent": len(prompts),
        "requests_succeeded": len(prompts) - len(failed),
        "requests_failed": len(failed),
        "streamed": len(streams),
        "stream_first_token_before_last": all(
            s.get("first_token_s", math.inf) < s.get("done_s", -1) for s in streams
        ),
        "platform": stats["device"]["platform"],
        "device_kind": stats["device"]["device_kind"],
        "device_count": stats["device"]["count"],
        "visible_chips": stats["device"]["visible_chips"],
        "attn_impl": stats["attn_impl"],
        "decode_steps": stats["steps"],
        "tokens_generated": stats["tokens_generated"],
        # first tokens come out of prefill; the rest one per active slot per step
        "mean_decode_batch": round((stats["tokens_generated"] - len(prompts)) / max(1, stats["steps"]), 2),
        "kv_pages_high_water": stats["kv_pages_high_water"],
        "compile_events": stats["compile"].get("compile_events", {}),
        "compile_seconds": stats["compile"].get("compile_seconds", {}),
    }
    log(f"[{label}] {json.dumps(summary)}")
    if failed:
        raise PhaseFailed(f"{label}: {len(failed)} of {len(prompts)} requests failed: {failed}\n{container_stderr_tails()}")
    if not summary["stream_first_token_before_last"]:
        raise PhaseFailed(f"{label}: a stream's first token did not arrive before its last: {streams}")
    if summary["platform"] != want_platform:
        raise PhaseFailed(f"{label}: server ran on platform {summary['platform']!r}, wanted {want_platform!r}")
    if want_platform == "tpu" and summary["attn_impl"] != "kernel":
        raise PhaseFailed(f"{label}: server reports attn_impl={summary['attn_impl']!r} on a TPU, wanted 'kernel'")
    if summary["mean_decode_batch"] <= 1.0:
        raise PhaseFailed(f"{label}: decode never held more than one slot ({summary['mean_decode_batch']})")
    return summary


def cache_counts(boot: dict) -> tuple[int, int]:
    events = boot["compile_events"]
    return int(events.get("cache_hit,runtime", 0)), int(events.get("cache_miss,runtime", 0))


def compile_s(boot: dict) -> float:
    return round(boot["compile_seconds"].get("backend_compile", {}).get("sum", 0.0), 1)


# -- functions that run INSIDE a container (cloudpickled; jax lives there) ----


def parity_in_container(model, seed: int) -> dict:
    """Decode-step logits, Pallas kernel against gather, same cache and
    tokens, at the service's widths and geometry; plus the one observation
    ROADMAP S3 asks for. Returns plain numbers."""
    import math as _math
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from modal_tpu.models import paged_kv as pk
    from modal_tpu.models.llama import get_config, init_params
    from modal_tpu.models.sampling import host_sync
    from modal_tpu.serving.pages import PageAllocator

    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    kernel_impl = "kernel" if on_tpu else "kernel_interpret"
    cfg = get_config(model)
    params = init_params(cfg, jax.random.PRNGKey(seed))
    # the engine geometry of a benchmark cell (mistral-7b.chat-saturated: 32 slots, 512 pages of 16
    # tokens a slot, a pool of 3,072 pages), not a toy one; `tiny` (--cpu) keeps the slots and
    # scales the rest to its context
    slots, page = 32, pk.DEFAULT_PAGE_SIZE
    pps = _math.ceil(cfg.max_seq_len / page)
    num_pages = 1 + min(3071, (slots * pps) // 2)
    cache = pk.PagedKVCache.create(cfg, slots, num_pages, page, pps)
    alloc = PageAllocator(num_pages, page)
    rng = np.random.default_rng(seed)
    # (prompt length, decodes this step): ragged, one slot far longer than the rest, lengths that
    # end mid-page, on a page boundary, on the kernel's block boundary (256 positions at these
    # widths: the query at 255, 256 and 511) and after several prefill chunks; slot 7 holds a
    # prompt and does not decode (half prefilled, as the engine sees it); the other 20 slots hold
    # nothing and stay idle (scratch page)
    held = [(1100, True), (17, True), (130, True), (64, True), (511, True), (256, True), (255, True), (300, False),
            (700, True), (33, True), (15, True), (16, True)]
    held = [(min(n, cfg.max_seq_len - 70), decodes) for n, decodes in held]
    lengths = [n for n, _decodes in held]
    tokens = np.zeros((slots,), np.int32)
    active = np.zeros((slots,), bool)
    prefill_finite = True
    for slot, (n, decodes) in enumerate(held):
        pages = alloc.alloc(alloc.pages_for(n + 1))
        row = pages + [0] * (pps - len(pages))
        cache = pk.assign_pages(cache, slot, 0, jnp.asarray(row, jnp.int32))
        prompt = rng.integers(0, cfg.vocab_size, size=n)
        for start in range(0, n, 128):  # the engine's prefill_chunk
            chunk = prompt[start : start + 128]
            padded = np.zeros((pk.prefill_bucket(len(chunk), pps * page),), np.int32)
            padded[: len(chunk)] = chunk
            logits, next_tok, cache = pk.paged_prefill(
                params, cfg, jnp.asarray(padded), jnp.int32(len(chunk)), cache, jnp.int32(slot), jnp.int32(start)
            )
        prefill_finite = prefill_finite and bool(jnp.isfinite(logits).all())
        tokens[slot], active[slot] = int(next_tok), decodes
    tokens_j, active_j = jnp.asarray(tokens), jnp.asarray(active)
    start_lens = jnp.asarray(np.asarray(cache.seq_lens))

    def step(impl, cache):
        return pk.paged_decode_step(params, cfg, tokens_j, cache, active_j, impl)

    def rewind(cache):  # the next step starts from the same cache again
        return pk.set_seq_lens(cache, start_lens, active_j)

    mosaic_calls = {
        impl: pk.paged_decode_step.lower(params, cfg, tokens_j, cache, active_j, impl).as_text().count("tpu_custom_call")
        for impl in ((kernel_impl, "gather") if on_tpu else ())
    }
    logits_k, _next, cache = step(kernel_impl, cache)
    logits_g, _next, cache = step("gather", rewind(cache))
    lk = np.asarray(logits_k, np.float32)[active]
    lg = np.asarray(logits_g, np.float32)[active]
    rms = float(np.sqrt(np.mean(lg**2)))
    # Tolerance, from the dtype. bf16 keeps 8 significand bits: eps = 2**-8.
    # The two paths round at different points in every layer (gather casts
    # the softmax probabilities to bf16 before the value product; the kernel
    # keeps them in f32 and rounds its output once), so after L layers the
    # residual streams differ by about eps*sqrt(2L) relative, and a logit —
    # the normalised stream against one lm_head column, spread `rms` — by
    # about that share of rms. Allow 8x for the largest of slots*vocab draws.
    # A wrong page, mask or head mapping moves logits by ~rms itself.
    tol = 8 * 2.0**-8 * _math.sqrt(2 * cfg.n_layers) * rms
    max_abs_diff = float(np.max(np.abs(lk - lg)))

    # ROADMAP S3, one observation: the same warm decode step timed to
    # block_until_ready on its output, and to device_get of a dependent
    # scalar (sampling.host_sync)
    host_sync(logits_k)  # compile the probe outside the timing
    timings = {}
    for name, wait in (("block_until_ready_ms", jax.block_until_ready), ("host_sync_device_get_ms", host_sync)):
        cache = rewind(cache)
        jax.block_until_ready(cache)
        t0 = _time.perf_counter()
        out, _next, cache = step(kernel_impl, cache)
        wait(out)
        timings[name] = round((_time.perf_counter() - t0) * 1e3, 3)
    mem = devices[0].memory_stats() or {}
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "visible_chips": os.environ.get("TPU_VISIBLE_DEVICES", ""),
        "widths": {
            "dim": cfg.dim, "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "ffn_dim": cfg.ffn_dim, "vocab_size": cfg.vocab_size, "dtype": jnp.dtype(cfg.dtype).name,
        },
        "n_layers": cfg.n_layers,
        "kernel_impl": kernel_impl,
        "geometry": {"slots": slots, "pages_per_slot": pps, "pool_pages": num_pages, "decoding": int(active.sum())},
        "slot_lengths": lengths,
        "logits_max_abs_diff": round(max_abs_diff, 5),
        "logits_rms": round(rms, 4),
        "tolerance": round(tol, 5),
        "logits_finite": bool(np.isfinite(lk).all() and np.isfinite(lg).all() and prefill_finite),
        "mosaic_custom_calls": mosaic_calls,
        "s3_one_decode_step": timings,
        "hbm_bytes_in_use": mem.get("bytes_in_use"),
        "hbm_bytes_limit": mem.get("bytes_limit"),
    }


def train_in_container(model, mesh_axes: dict, steps: int, seq_len: int) -> dict:
    import jax

    from modal_tpu.parallel.train import train_demo

    out = train_demo(model, mesh_axes, steps=steps, per_device_batch=1, seq_len=seq_len)
    devices = jax.devices()
    out.update(platform=devices[0].platform, device_kind=devices[0].device_kind, device_count=len(devices))
    return out


# -- the phases -----------------------------------------------------------------


def run_parity(model, want_platform: str, timeout_s: int) -> dict:
    import modal_tpu

    app = modal_tpu.App("chip-smoke-parity")
    fn = app.function(tpu="v5e-1", serialized=True, timeout=timeout_s)(parity_in_container)
    with app.run():
        try:
            res = fn.remote(model, 0)
        except Exception as exc:  # noqa: BLE001 — any container failure fails the phase
            raise PhaseFailed(f"parity: {type(exc).__name__}: {exc}\n{container_stderr_tails()}")
    log(f"[parity] {json.dumps(res)}")
    if res["platform"] != want_platform:
        raise PhaseFailed(f"parity ran on {res['platform']!r}, wanted {want_platform!r}")
    if not res["logits_finite"]:
        raise PhaseFailed("parity: non-finite logits")
    if not res["logits_max_abs_diff"] <= res["tolerance"]:
        raise PhaseFailed(
            f"parity: kernel and gather logits differ by {res['logits_max_abs_diff']} > tolerance {res['tolerance']}"
        )
    if want_platform == "tpu" and not (
        res["mosaic_custom_calls"].get("kernel", 0) > 0 and res["mosaic_custom_calls"].get("gather", 1) == 0
    ):
        raise PhaseFailed(f"parity: Mosaic custom calls in the lowered decode step: {res['mosaic_custom_calls']}")
    return res


def run_one_chip(cpu: bool) -> dict:
    model, lens = (CPU_MODEL, CPU_PROMPT_LENS) if cpu else (CHIP_MODEL, CHIP_PROMPT_LENS)
    vocab = 512 if cpu else CHIP_WIDTHS["vocab_size"]
    want = "cpu" if cpu else "tpu"
    boot1 = serve_once("boot1", model, vocab, lens, want, timeout_s=500)
    boot2 = serve_once("boot2", model, vocab, lens, want, timeout_s=500)
    hits2, misses1 = cache_counts(boot2)[0], cache_counts(boot1)[1]
    log(
        f"[cache] boot1 compiled {compile_s(boot1)}s (hits/misses {cache_counts(boot1)}), "
        f"boot2 compiled {compile_s(boot2)}s (hits/misses {cache_counts(boot2)})"
    )
    # every entry boot 1 wrote must be found again by boot 2 (a cache
    # directory that moved between the two would give zero hits)
    if hits2 < max(1, misses1):
        raise PhaseFailed(
            f"boot2 reports {hits2} persistent-cache hits after boot1 wrote {misses1} entries: "
            "the compile cache directory is not stable between containers"
        )
    parity = run_parity(CPU_MODEL if cpu else PARITY_MODEL, want, timeout_s=700)
    if not cpu and parity["widths"] != CHIP_WIDTHS:
        raise PhaseFailed(f"widths are not Llama-3-8B's: {parity['widths']}")
    return {
        "model": {"name": "llama3-8b" if not cpu else CPU_MODEL, **parity["widths"], "n_layers": parity["n_layers"] if cpu else CHIP_MODEL["n_layers"]},
        "served": {"boot1": boot1, "boot2": boot2},
        "compile_seconds": {"boot1": compile_s(boot1), "boot2": compile_s(boot2)},
        "persistent_cache_hits_boot2": hits2,
        "parity": parity,
    }


def run_trainer(cpu: bool) -> dict:
    import modal_tpu

    attempts = []
    for n_layers in (2,) if cpu else TRAIN_LAYERS:
        model = {"name": CPU_MODEL if cpu else "llama3-8b", "n_layers": n_layers}
        seq_len = 128 if cpu else 2048
        app = modal_tpu.App(f"chip-smoke-train-{n_layers}")
        fn = app.function(
            tpu="v5e-4", mesh={"fsdp": 2, "model": 2}, serialized=True, timeout=1100
        )(train_in_container)
        with app.run():
            try:
                res = fn.remote(model, {"fsdp": 2, "model": 2}, 3, seq_len)
            except Exception as exc:  # noqa: BLE001 — e.g. out of HBM at this depth: try the next
                attempts.append({"n_layers": n_layers, "error": f"{type(exc).__name__}: {str(exc)[:400]}"})
                log(f"[trainer] {n_layers} layers failed: {attempts[-1]['error']}")
                continue
        res["n_layers"], res["seq_len"] = n_layers, seq_len
        log(f"[trainer] {json.dumps(res)}")
        losses, mem = res["losses"], res["device_bytes_in_use"]
        if not all(math.isfinite(x) for x in losses):
            raise PhaseFailed(f"trainer: non-finite loss {losses}")
        # the schedule warms up from lr=0, so the first steps barely move:
        # falling or flat, to within bf16 noise on a ~12-nat loss
        if any(b > a + 0.05 for a, b in zip(losses, losses[1:])):
            raise PhaseFailed(f"trainer: loss rose over three steps: {losses}")
        if res["device_count"] != 4:
            raise PhaseFailed(f"trainer saw {res['device_count']} devices, wanted 4")
        if not cpu:
            if res["mosaic_custom_calls"] < 1:
                raise PhaseFailed("trainer: no Mosaic custom call in the lowered train step")
            if not all(m and m > 0.5 * max(mem) for m in mem):
                raise PhaseFailed(f"trainer: state is not spread over the four devices: {mem}")
        res["attempts_failed"] = attempts
        return res
    raise PhaseFailed(f"trainer: no depth fitted: {attempts}\n{container_stderr_tails()}")


def run_replicas(cpu: bool) -> dict:
    """Four one-chip replicas on the one four-chip worker: each must answer a
    request and report its own chip."""
    import modal_tpu

    model = CPU_MODEL if cpu else CHIP_MODEL
    vocab = 512 if cpu else CHIP_WIDTHS["vocab_size"]
    app = modal_tpu.App("chip-smoke-replicas")
    modal_tpu.serving.llm_service(
        app, model=model, tpu="v5e-1", name="SmokeReplica", max_slots=8, min_containers=4, max_containers=4
    )
    rng = random.Random(1)
    prompt = [rng.randrange(vocab) for _ in range(40)]
    replicas = []
    with app.run():
        deadline = time.monotonic() + 700
        urls: dict = {}
        while time.monotonic() < deadline and len(urls) < 4:
            # each replica's own endpoint: the function-level URL is only the
            # last one to register
            urls = {t.task_id: t.web_url for t in supervisor().state.tasks.values() if t.web_url}
            time.sleep(1.0)
        if len(urls) < 4:
            raise PhaseFailed(f"replicas: {len(urls)} of 4 containers came up\n{container_stderr_tails()}")
        for task_id, url in sorted(urls.items()):
            out: dict = {}
            generate(url, prompt, False, out)
            stats = http_json(url + "/v1/stats")
            problem = out.get("error") or check_tokens(out.get("tokens", []), vocab)
            replicas.append({
                "task_id": task_id, "answered": not problem, "problem": problem,
                "platform": stats["device"]["platform"], "device_count": stats["device"]["count"],
                "visible_chips": stats["device"]["visible_chips"], "attn_impl": stats["attn_impl"],
            })
    log(f"[replicas] {json.dumps(replicas)}")
    if not all(r["answered"] for r in replicas):
        raise PhaseFailed(f"replicas: not every replica answered: {replicas}")
    if not cpu:
        chips = sorted(r["visible_chips"] for r in replicas)
        if chips != ["0", "1", "2", "3"]:
            raise PhaseFailed(f"replicas: chips reported {chips}, wanted four different ones")
        if not all(r["platform"] == "tpu" and r["device_count"] == 1 and r["attn_impl"] == "kernel" for r in replicas):
            raise PhaseFailed(f"replicas: not all on one TPU chip with the kernel: {replicas}")
    return {"replicas": replicas}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1, help="1: served path; 4: trainer + four replicas")
    parser.add_argument("--cpu", action="store_true", help="explicit CPU dry run on the tiny preset (prints platform=cpu)")
    args = parser.parse_args()
    if not os.path.isdir(os.path.join(REPO_ROOT, "modal_tpu")):
        sys.stderr.write("chip_smoke: no modal_tpu package beside this script; run it from the repo checkout\n")
        return 2
    if args.cpu:
        # the one way onto the CPU: said out loud, switched to `tiny`, and
        # with the documented simulated inventory so tpu= functions place
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["MODAL_TPU_JAX_PLATFORM"] = "cpu"
        os.environ["MODAL_TPU_WORKER_TPU_TYPE"] = "cpu-sim"
        os.environ["MODAL_TPU_WORKER_NUM_CHIPS"] = "4"
        # tiny's programs compile in well under the 1 s the persistent cache
        # asks for by default: cache them all, or boot 2 has nothing to find
        os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    sys.path.insert(0, REPO_ROOT)
    device = probe_device()
    log(f"device: platform={device['platform']} device_kind={device['kind']!r} count={device['count']}")
    if args.cpu:
        if device["platform"] != "cpu":
            sys.stderr.write(f"chip_smoke --cpu: jax did not stay on the CPU ({device})\n")
            return 2
    elif device["platform"] != "tpu":
        sys.stderr.write(
            f"chip_smoke: no TPU found (jax reports platform={device['platform']!r}). "
            "This smoke runs on the chip; `--cpu` is the explicit dry run.\n"
        )
        return 2
    if device["count"] < args.chips and not args.cpu:
        sys.stderr.write(f"chip_smoke --chips {args.chips}: the host has {device['count']} chip(s)\n")
        return 2

    # state of this run, at a fixed place inside the checkout; emptied so no
    # journal of an earlier run is replayed. The compile cache is NOT here:
    # it is where JAX_COMPILATION_CACHE_DIR says, else .modal_tpu_state/jit_cache
    shutil.rmtree(STATE_DIR, ignore_errors=True)
    os.environ["MODAL_TPU_STATE_DIR"] = STATE_DIR
    from modal_tpu.config import compile_cache_dir, config

    log(
        f"config: warm_pool={config['warm_pool']} (repo default: pool off) "
        f"compile_cache_dir={compile_cache_dir()} "
        f"(JAX_COMPILATION_CACHE_DIR {'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'not set'})"
    )
    t0 = time.monotonic()
    ok, error, result = True, None, {}
    try:
        if args.chips == 1:
            result = run_one_chip(args.cpu)
        else:
            result = {"trainer": run_trainer(args.cpu)}
            result.update(run_replicas(args.cpu))
    except PhaseFailed as exc:
        ok, error = False, str(exc)
        sys.stderr.write(f"chip_smoke: FAILED: {exc}\n")
    finally:
        sup = supervisor()
        if sup is not None:  # stops the worker, which stops every container
            from modal_tpu._utils.async_utils import synchronizer

            synchronizer.run(sup.stop())
    if "jax" in sys.modules:
        ok, error = False, "the parent process imported jax (it would hold the chip)"
        sys.stderr.write(f"chip_smoke: FAILED: {error}\n")
    if ok:
        summary = {
            "mode": f"{'cpu dry run' if args.cpu else 'chip'}, --chips {args.chips}",
            "wall_s": round(time.monotonic() - t0, 1),
            **result,
            "claim": None,
        }
        log(f"summary: {json.dumps(summary)}")
    # the verdict, last and alone on its line, with exactly these keys: the
    # driver's accelerator check parses it and refuses anything else
    log(json.dumps({"ok": ok, "device": device}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
