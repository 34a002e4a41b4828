"""The benchmark's half that runs inside the container that holds the chip.

`build_service(...)` is called by the harness (which never imports jax): it
calls the program's own `modal_tpu.serving.llm_service` on a throw-away App,
takes the class it built, and returns a subclass that inherits `load()` and
`shutdown()` unchanged and overrides only `serve()`: the parent's `serve` is
called for `llm_service`'s own ASGI app, every path is passed through to
it, and `/bench/*` is added:

    GET  /bench/device        the device as jax reports it, memory_stats, pid
    POST /bench/trace/start   {"dir": ...}: jax.profiler.start_trace there
    POST /bench/trace/stop    stop_trace; answers this clock between start_trace returning and
                              this call: a lower bound of the recording (trace_reduce.py)

Only the process that holds the chip can trace it or read its memory, so
these cannot live in the harness. Nothing here touches the engine.
"""

from __future__ import annotations

import asyncio
import json
import os
import time


def service_arguments(cfg: dict, seed: int) -> dict:
    """`llm_service`'s keyword arguments for a configuration file: the model
    as {"name": preset, **overrides} from the published keys, the weights'
    seed, and the deployment's engine geometry. Everything the file does not
    state stays at `llm_service`'s own default."""
    model = dict(cfg["program_model"])
    for published_key, program_key in cfg["program_keys"].items():
        model[program_key] = cfg[published_key]
    args = {
        "model": model,
        "seed": int(seed) & 0x7FFFFFFF,  # jax.random.PRNGKey takes 32 bits
        "tpu": cfg.get("tpu", "v5e-1"),
        "min_containers": 1,
        "max_containers": 1,
    }
    args.update(cfg.get("engine", {}))
    return args


def build_service(app, cfg: dict, seed: int, name: str = "BenchLLM"):
    """Register on `app` the class `llm_service` builds, with `/bench/*`
    added. Returns (service, options llm_service passed to app.cls)."""
    import modal_tpu
    from modal_tpu.serving import llm_service

    scratch = modal_tpu.App("bench-scratch")
    passed: dict = {}
    register = scratch.cls

    def recording_cls(**opts):
        passed.update(opts)
        return register(**opts)

    scratch.cls = recording_cls
    built = llm_service(scratch, name=name, **service_arguments(cfg, seed))
    base = built._user_cls
    if base is None:
        raise RuntimeError("llm_service returned a class without its user class: the object model changed")

    parent_serve = base.serve.raw_f  # llm_service's own @asgi_app method

    class _Bench(base):
        @modal_tpu.asgi_app()
        def serve(self):
            from benchlib.incontainer import with_bench_routes

            return with_bench_routes(parent_serve(self))

    _Bench.__name__ = _Bench.__qualname__ = name
    return app.cls(**passed)(_Bench), passed


def with_bench_routes(inner):
    """ASGI app: `/bench/*` handled here, everything else by `inner`."""
    state = {"trace_started": 0.0, "trace_dir": ""}

    async def read_body(receive) -> bytes:
        body = b""
        while True:
            msg = await receive()
            if msg["type"] != "http.request":
                return body
            body += msg.get("body", b"")
            if not msg.get("more_body"):
                return body

    async def send_json(send, status: int, payload: dict) -> None:
        data = json.dumps(payload).encode()
        await send({
            "type": "http.response.start", "status": status,
            "headers": [(b"content-type", b"application/json"), (b"content-length", str(len(data)).encode())],
        })
        await send({"type": "http.response.body", "body": data})

    def device() -> dict:
        import jax

        devices = jax.devices()
        stats = [d.memory_stats() or {} for d in devices]
        return {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max((s.get("peak_bytes_in_use", 0) for s in stats), default=0),
            "memory_limit_bytes": max((s.get("bytes_limit", 0) for s in stats), default=0),
            "memory_in_use_bytes": max((s.get("bytes_in_use", 0) for s in stats), default=0),
            "pid": os.getpid(),
        }

    def trace_start(trace_dir: str) -> dict:
        import jax

        os.makedirs(trace_dir, exist_ok=True)
        # the device's own timeline is what is reduced; the Python tracer
        # (on by default) slows the engine's host loop and fattens the file
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        state["trace_started"] = time.monotonic()
        state["trace_dir"] = trace_dir
        return {"started": True}

    def trace_stop() -> dict:
        import jax

        window_s = time.monotonic() - state["trace_started"]
        jax.profiler.stop_trace()
        return {"window_s": window_s, "dir": state["trace_dir"], "stop_s": time.monotonic() - state["trace_started"] - window_s}

    async def app(scope, receive, send):
        path = scope.get("path", "") if scope["type"] == "http" else ""
        if not path.startswith("/bench/"):
            return await inner(scope, receive, send)
        raw = await read_body(receive)
        try:
            body = json.loads(raw) if raw else {}
            if path == "/bench/device":
                payload = await asyncio.to_thread(device)
            elif path == "/bench/trace/start":
                payload = await asyncio.to_thread(trace_start, str(body["dir"]))
            elif path == "/bench/trace/stop":
                payload = await asyncio.to_thread(trace_stop)
            else:
                return await send_json(send, 404, {"error": f"no route {path}"})
        except Exception as exc:  # noqa: BLE001 — the harness reads the reason
            return await send_json(send, 500, {"error": f"{type(exc).__name__}: {exc}"})
        await send_json(send, 200, payload)

    return app
