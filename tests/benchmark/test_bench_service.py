"""The class the benchmark registers IS llm_service's: it inherits `load`
and `shutdown` unchanged, overrides only `serve`, passes every `/v1/*` path
through to llm_service's own ASGI app and adds `/bench/*` (CPU, `tiny`)."""

import asyncio
import json

import pytest

from . import _paths  # noqa: F401
from benchlib import incontainer

TINY = {
    "hidden_size": 128, "intermediate_size": 256, "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 2, "max_position_embeddings": 256, "vocab_size": 512, "rms_norm_eps": 1e-05,
    "rope_theta": 500000.0, "program_model": {"name": "tiny"},
    "program_keys": {
        "vocab_size": "vocab_size", "hidden_size": "dim", "num_hidden_layers": "n_layers",
        "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads", "intermediate_size": "ffn_dim",
        "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta", "max_position_embeddings": "max_seq_len",
    },
    "engine": {"max_slots": 4, "num_pages": 40}, "tpu": "v5e-1",
}


async def call(app, method, path, body=None):
    sent = []
    raw = json.dumps(body).encode() if body is not None else b""
    inbox = [{"type": "http.request", "body": raw, "more_body": False}]

    async def receive():
        return inbox.pop(0) if inbox else {"type": "http.disconnect"}

    async def send(message):
        sent.append(message)

    await app({"type": "http", "path": path, "method": method, "headers": []}, receive, send)
    status = next(m["status"] for m in sent if m["type"] == "http.response.start")
    data = b"".join(m.get("body", b"") for m in sent if m["type"] == "http.response.body")
    return status, json.loads(data)


@pytest.fixture(scope="module")
def built():
    import modal_tpu

    app = modal_tpu.App("bench-service-test")
    service, passed = incontainer.build_service(app, TINY, seed=3)
    return service, passed


def test_the_arguments_come_from_the_file_and_the_rest_stays_llm_services_default():
    import inspect

    from modal_tpu.serving import llm_service

    args = incontainer.service_arguments(TINY, seed=2**31 + 5)
    assert args["model"] == {
        "name": "tiny", "vocab_size": 512, "dim": 128, "n_layers": 2, "n_heads": 4, "n_kv_heads": 2,
        "ffn_dim": 256, "norm_eps": 1e-05, "rope_theta": 500000.0, "max_seq_len": 256,
    }
    assert args["max_slots"] == 4 and args["num_pages"] == 40 and args["seed"] == 5
    defaults = inspect.signature(llm_service).parameters
    assert all(k in defaults for k in args if k != "tpu")
    assert not {"prefill_chunk", "page_size", "pages_per_slot", "prefix_cache"} & set(args)


def test_the_subclass_inherits_load_and_shutdown_and_overrides_only_serve(built):
    from modal_tpu.partial_function import _PartialFunctionFlags, find_partial_methods_for_user_cls

    service, passed = built
    cls = service._user_cls
    base = next(c for c in cls.__mro__ if c.__module__ == "modal_tpu.serving.service")
    assert base is not cls and issubclass(cls, base)
    assert "load" not in vars(cls) and "shutdown" not in vars(cls)
    assert cls.load is base.load and cls.shutdown is base.shutdown and cls.serve is not base.serve
    web = find_partial_methods_for_user_cls(cls, _PartialFunctionFlags.WEB_ENDPOINT)
    assert list(web) == ["serve"] and web["serve"] is not vars(base)["serve"]
    # the options are the ones llm_service itself passes to app.cls
    assert passed["serialized"] is True and passed["tpu"] == "v5e-1"
    assert passed["min_containers"] == 1 and passed["max_containers"] == 1


def test_v1_paths_pass_through_to_llm_services_own_app_and_bench_routes_are_added(built):
    service, _passed = built
    obj = service._user_cls()
    type(obj).load.raw_f(obj)  # llm_service's own load(): its params, its engine
    try:
        app = type(obj).serve.raw_f(obj)

        async def drive():
            status, health = await call(app, "GET", "/healthz")
            assert status == 200 and health["ok"] is True
            status, stats = await call(app, "GET", "/v1/stats")
            assert status == 200 and stats["max_slots"] == 4 and stats["kv_pages_total"] == 39
            status, gen = await call(app, "POST", "/v1/generate", {"prompt": [5, 6, 7, 8], "max_new_tokens": 6})
            assert status == 200 and len(gen["tokens"]) == 6
            expect = obj.engine.submit([5, 6, 7, 8], 6).result(timeout=60)
            assert gen["tokens"] == expect
            status, dev = await call(app, "GET", "/bench/device")
            assert status == 200 and dev["platform"] == "cpu" and dev["count"] >= 1 and dev["pid"] > 0
            status, missing = await call(app, "GET", "/bench/nothing")
            assert status == 404
            status, other = await call(app, "GET", "/v1/nothing")
            assert status == 404 and "no route" in other["error"]

        asyncio.run(drive())
    finally:
        type(obj).shutdown.raw_f(obj)
