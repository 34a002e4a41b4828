"""Disaggregated cache-aware serving fleet (ISSUE 18).

Three layers under test:

- **router** (serving/router.py): the prefix map routes followers to the
  replica already holding their pages; cold prefixes consistent-hash;
  sessions stay pinned and survive replica death with the SAME request id
  riding the re-route (exactly-once); MODAL_TPU_SERVING_ROUTER=0 degrades
  the whole tier to seeded-random choice.
- **prefill/decode split** (engine export/import + /v1/prefill[ed]):
  remotely-prefilled pages land token-identically, publish into the local
  prefix cache, and EVERY shipment defect — chaos-dropped frame, garbage
  kv_ref, geometry mismatch — degrades to a full local prefill with zero
  token loss.
- **overlapped speculative verify**: spec rounds split the batch so group
  B's draft chain runs under group A's in-flight verify; token streams are
  byte-identical to the non-speculative engine's, and
  spec mode no longer disables the prefix cache (the draft pool runs its
  own full-page-only cache).

Token-identity pins run the tiny config in fp32: bf16 reductions can
differ across batch compositions; fp32 per-row ops are composition-
independent (same caveat as the PR 11 spec pins — docs/SERVING.md)."""

import json
import os
import threading

import pytest

SLOTS, PAGES, PAGE, PAGES_PER_SLOT = 4, 25, 16, 8


@pytest.fixture(scope="module")
def tiny_fp32():
    import jax
    import jax.numpy as jnp

    from modal_tpu.models.llama import get_config, init_params

    cfg = get_config("tiny", dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    draft_cfg = get_config("tiny", dtype=jnp.float32)
    draft_params = init_params(draft_cfg, jax.random.PRNGKey(1))
    return params, cfg, draft_params, draft_cfg


def _engine(params, cfg, **overrides):
    from modal_tpu.serving.engine import ServingEngine

    kwargs = dict(
        max_slots=SLOTS, num_pages=PAGES, page_size=PAGE,
        pages_per_slot=PAGES_PER_SLOT, prefill_chunk=32,
    )
    kwargs.update(overrides)
    return ServingEngine(params, cfg, **kwargs)


PROMPT = list(range(40, 77))  # 37 tokens = 2 full pages + a partial


# ---------------------------------------------------------------------------
# router unit matrix (fake transports — no engines, no model)
# ---------------------------------------------------------------------------


class _FakeReplica:
    """Transport double: records calls, optionally dies (ConnectionError)."""

    def __init__(self, name: str):
        self.name = name
        self.calls: list[tuple[str, dict]] = []
        self.dead = False

    def __call__(self, path: str, body: dict):
        if self.dead:
            raise ConnectionError(f"{self.name} unreachable")
        self.calls.append((path, dict(body)))
        if path == "/v1/prefill":
            return {"kv_ref": f"/tmp/{self.name}.bin", "first_token": 7,
                    "n_tokens": len(body["prompt"]), "request_id": body.get("request_id", "")}
        return {"request_id": body.get("request_id", ""), "replica": self.name}


def _fleet(n=3, **kw):
    from modal_tpu.serving.router import ServingRouter

    reps = {f"r{i}": _FakeReplica(f"r{i}") for i in range(n)}
    return ServingRouter({k: v for k, v in reps.items()}, page_size=PAGE, **kw), reps


def test_router_prefix_map_routes_followers_to_the_holder():
    """First request for a prefix lands somewhere (cold); every follower
    with the same full-page prefix routes to THAT replica via the map —
    both from route-time observation and from a stats refresh."""
    router, reps = _fleet()
    body = {"prompt": PROMPT, "max_new_tokens": 4}
    router.route(dict(body))
    first = next(n for n, r in reps.items() if r.calls)
    for _ in range(5):
        name, reason = router.pick(PROMPT)
        assert (name, reason) == (first, "prefix")
        router.route(dict(body))
    assert all(not r.calls for n, r in reps.items() if n != first)
    # a longer prompt sharing the full-page prefix follows too
    name, reason = router.pick(PROMPT + [1, 2, 3])
    assert (name, reason) == (first, "prefix")
    # stats refresh feeds the map the same way (replica-side digests)
    from modal_tpu.serving.router import prefix_digest

    router2, _ = _fleet()
    router2.refresh_from_stats("r2", {"prefix_digests": [prefix_digest(PROMPT[:PAGE])]})
    assert router2.pick(PROMPT) == ("r2", "prefix")


def test_router_cold_prefixes_consistent_hash_deterministically():
    """A prefix never seen by anyone ring-hashes — deterministic across
    router instances (two directors agree with no shared state), and
    different prefixes actually spread over the fleet."""
    router_a, _ = _fleet()
    router_b, _ = _fleet()
    picks = set()
    for base in range(0, 200, 10):
        prompt = list(range(base, base + PAGE))
        na, ra = router_a.pick(prompt)
        nb, rb = router_b.pick(prompt)
        assert ra == rb == "cold" and na == nb
        picks.add(na)
    assert len(picks) >= 2  # the ring spreads, not funnels


def test_router_session_affinity_survives_replica_death_exactly_once():
    """A pinned session keeps hitting its replica; when that replica dies
    mid-fleet, the SAME request id re-routes to a survivor (the dead one
    never answered — the resend IS the request, ShardRouterStub
    discipline), the map is repaired, and the session re-pins."""
    router, reps = _fleet()
    body = {"prompt": PROMPT, "max_new_tokens": 4, "request_id": "sess-req-1"}
    router.route(dict(body), session="s1")
    pinned = next(n for n, r in reps.items() if r.calls)
    assert router.pick(PROMPT, session="s1") == (pinned, "affinity")
    reps[pinned].dead = True
    out = router.route({"prompt": PROMPT, "request_id": "sess-req-2"}, session="s1")
    survivor = out["replica"]
    assert survivor != pinned
    # exactly-once: the id reached exactly one LIVE replica, verbatim
    ids = [b.get("request_id") for n, r in reps.items() if n != pinned for _p, b in r.calls]
    assert ids.count("sess-req-2") == 1
    assert router.reroutes == 1
    st = router.stats()
    assert pinned not in st["replicas"]
    # the dead replica's map entries are gone; the session follows the move
    assert router.pick(PROMPT, session="s1")[0] == survivor


def test_router_off_degrades_to_seeded_random(monkeypatch):
    """MODAL_TPU_SERVING_ROUTER=0: no map, no affinity, no ring — seeded-
    random spread (the bench's A/B baseline arm)."""
    monkeypatch.setenv("MODAL_TPU_SERVING_ROUTER", "0")
    router, reps = _fleet(seed=7)
    assert not router.enabled
    seen = set()
    for i in range(24):
        name, reason = router.pick(PROMPT, session="s1")
        assert reason == "random"
        seen.add(name)
        router.route({"prompt": PROMPT})
    assert len(seen) >= 2  # same prompt, same session — still scattered
    assert router.stats()["routed"]["random"] == 24
    # and the default (knob unset) really is routing
    monkeypatch.delenv("MODAL_TPU_SERVING_ROUTER")
    router2, _ = _fleet()
    assert router2.enabled


def test_router_disaggregated_two_legs_and_degrade():
    """split_prefill drives /v1/prefill on the prefill tier then
    /v1/prefilled (with the kv_ref) on the decode pick; a dead prefill
    replica degrades the SAME request to direct /v1/generate."""
    router, reps = _fleet(3, prefill_replicas=("r0",))
    body = {"prompt": PROMPT, "max_new_tokens": 4, "request_id": "dq-1"}
    router.route(dict(body), split_prefill=True)
    pre_calls = [p for p, _b in reps["r0"].calls]
    assert "/v1/prefill" in pre_calls
    dec = [(n, p, b) for n, r in reps.items() for p, b in r.calls if p == "/v1/prefilled"]
    assert len(dec) == 1 and dec[0][2]["kv_ref"] == "/tmp/r0.bin"
    assert dec[0][2]["request_id"] == "dq-1"
    # prefill replica dies → fallback to direct generate, request survives
    reps["r0"].dead = True
    out = router.route({"prompt": PROMPT, "request_id": "dq-2"}, split_prefill=True)
    assert out["request_id"] == "dq-2"
    gen = [b for n, r in reps.items() for p, b in r.calls if p == "/v1/generate"]
    assert any(b["request_id"] == "dq-2" for b in gen)
    assert router.prefill_fallbacks == 1


# ---------------------------------------------------------------------------
# prefill/decode disaggregation: export → ship → import, token-identical
# ---------------------------------------------------------------------------


def test_kv_shipment_roundtrip_token_identity_and_prefix_publish(tiny_fp32):
    """A prompt prefilled on replica A and decoded on replica B emits the
    exact token stream a single-replica engine does; the imported pages
    then serve B's OWN prefix cache (followers hit without prefill)."""
    params, cfg, _dp, _dc = tiny_fp32
    ref_eng = _engine(params, cfg).start()
    pre_eng = _engine(params, cfg, role="prefill").start()
    dec_eng = _engine(params, cfg, role="decode").start()
    try:
        ref = ref_eng.submit(PROMPT, 12).result(timeout=120)
        r = pre_eng.prefill_export(PROMPT)
        assert r.result(timeout=120) == ref[:1]  # the shipped first token
        ship = r.shipment
        assert ship is not None and ship["k"].shape[1] == 3  # ceil(37/16) pages
        assert pre_eng.stats()["kv_pages_shipped"] == 3
        assert pre_eng.stats()["role"] == "prefill"

        out = dec_eng.submit_prefilled(PROMPT, ship, 12).result(timeout=120)
        assert out == ref
        st = dec_eng.stats()
        assert st["remote_prefills"] == 1 and st["role"] == "decode"
        # follower: the imported prompt is now B's cached prefix
        assert dec_eng.submit(PROMPT, 12).result(timeout=120) == ref
        assert dec_eng.stats()["prefix_cache_hits"] >= 1
        # replicas advertise their cache content for the router's map
        assert len(dec_eng.stats()["prefix_digests"]) >= 1
    finally:
        for e in (ref_eng, pre_eng, dec_eng):
            e.stop()


def test_chaos_kv_ship_drop_falls_back_to_local_prefill(tiny_fp32, monkeypatch):
    """MODAL_TPU_CHAOS_KV_SHIP_DROP=1 eats the next shipment at admission
    (the prefill replica 'died mid-ship'): the decode replica re-prefills
    locally and the stream is identical — no token loss, TTFT pays."""
    from modal_tpu.serving.engine import _reset_kv_ship_chaos_for_tests

    params, cfg, _dp, _dc = tiny_fp32
    pre_eng = _engine(params, cfg).start()
    eng = _engine(params, cfg).start()
    try:
        r = pre_eng.prefill_export(PROMPT)
        r.result(timeout=120)
        ship = r.shipment
        ref = pre_eng.submit(PROMPT, 12).result(timeout=120)

        monkeypatch.setenv("MODAL_TPU_CHAOS_KV_SHIP_DROP", "1")
        _reset_kv_ship_chaos_for_tests()
        out = eng.submit_prefilled(PROMPT, ship, 12).result(timeout=120)
        assert out == ref  # dropped shipment, identical tokens
        st = eng.stats()
        assert st["kv_ship_drops"] == 1 and st["remote_prefills"] == 0

        # budget consumed + off-toggle: the next shipment imports normally
        monkeypatch.setenv("MODAL_TPU_CHAOS_KV_SHIP_DROP", "0")
        _reset_kv_ship_chaos_for_tests()
        out2 = eng.submit_prefilled(list(PROMPT), ship, 12).result(timeout=120)
        assert out2 == ref
        assert eng.stats()["kv_ship_drops"] == 1  # unchanged
        assert eng.stats()["remote_prefills"] == 1
    finally:
        _reset_kv_ship_chaos_for_tests()
        pre_eng.stop()
        eng.stop()


def test_mismatched_shipment_is_rejected_not_imported(tiny_fp32):
    params, cfg, _dp, _dc = tiny_fp32
    eng = _engine(params, cfg).start()
    try:
        r = _engine(params, cfg).start()
        try:
            req = r.prefill_export(PROMPT)
            req.result(timeout=120)
            ship = req.shipment
        finally:
            r.stop()
        with pytest.raises(ValueError, match="shipment"):
            eng.submit_prefilled(PROMPT + [1], ship, 4)  # wrong prompt
        bad = dict(ship, k=ship["k"][:, :1])  # wrong page count
        with pytest.raises(ValueError, match="shipment"):
            eng.submit_prefilled(PROMPT, bad, 4)
    finally:
        eng.stop()


def test_serving_role_knob_resolution(tiny_fp32, monkeypatch):
    """role unset → both; MODAL_TPU_SERVING_ROLE steers the default; an
    explicit constructor role wins; the gauge carries the numeric code."""
    from modal_tpu.observability.catalog import SERVING_ROLE
    from modal_tpu.serving.engine import ROLE_GAUGE_VALUES, resolve_role

    params, cfg, _dp, _dc = tiny_fp32
    monkeypatch.delenv("MODAL_TPU_SERVING_ROLE", raising=False)
    assert resolve_role() == "both"
    eng = _engine(params, cfg)
    assert eng.role == "both"
    monkeypatch.setenv("MODAL_TPU_SERVING_ROLE", "prefill")
    assert resolve_role() == "prefill"
    eng2 = _engine(params, cfg)
    assert eng2.role == "prefill"
    assert SERVING_ROLE.value() == float(ROLE_GAUGE_VALUES["prefill"])
    eng3 = _engine(params, cfg, role="decode")
    assert eng3.role == "decode"
    monkeypatch.setenv("MODAL_TPU_SERVING_ROLE", "bogus")
    assert resolve_role() == "both"  # malformed → safe default


# ---------------------------------------------------------------------------
# overlapped speculative verify + spec/prefix coexistence
# ---------------------------------------------------------------------------


def _run_spec_batch(params, cfg, draft, prompts, n=10, **overrides):
    eng = _engine(params, cfg, draft=draft, spec_k=2, **overrides).start()
    try:
        reqs = [eng.submit(p, n) for p in prompts]
        outs = [r.result(timeout=180) for r in reqs]
        return outs, eng.stats()
    finally:
        eng.stop()


def test_spec_overlap_streams_byte_identical_to_the_plain_engine(tiny_fp32):
    """The overlapped round (group B's draft chain under group A's verify:
    the only round there is) emits the same bytes as the non-speculative
    engine (spec is a throughput knob, never a correctness one)."""
    params, cfg, dp, dc = tiny_fp32
    prompts = [list(range(10 + j, 31 + j)) for j in range(SLOTS)]

    ovl, st_ovl = _run_spec_batch(params, cfg, (dp, dc), prompts)
    assert st_ovl["spec_rounds"] > 0 and "spec_overlap" not in st_ovl

    plain_eng = _engine(params, cfg).start()
    try:
        plain = [plain_eng.submit(p, 10).result(timeout=180) for p in prompts]
    finally:
        plain_eng.stop()
    assert ovl == plain


def test_spec_mode_keeps_the_prefix_cache_and_reuses_draft_pages(tiny_fp32):
    """ISSUE 18 lifts the old exclusion: with spec on, BOTH pools cache
    prefixes — the target with CoW partial pages, the draft full-page-only
    (no CoW machinery on that pool) — and a repeat prompt hits both."""
    params, cfg, dp, dc = tiny_fp32
    eng = _engine(params, cfg, draft=(dp, dc), spec_k=2).start()
    try:
        assert eng.pages.prefix_cache is not None and eng.draft_pages.prefix_cache is not None
        assert eng.pages.prefix_cache.partial_pages and not eng.draft_pages.prefix_cache.partial_pages
        a = eng.submit(PROMPT, 10).result(timeout=180)
        b = eng.submit(PROMPT, 10).result(timeout=180)
        assert a == b
        st = eng.stats()
        assert st["prefix_cache_hits"] >= 1
        assert st["draft_prefix_cache_entries"] >= 1
        assert st["draft_prefix_cache_hits"] >= 1
    finally:
        eng.stop()


def test_remote_prefill_into_spec_engine_token_identity(tiny_fp32):
    """The chaos matrix corner: a shipment lands on a SPECULATIVE decode
    replica — target side imports, draft side still prefills locally, and
    the stream matches the spec engine's own local run."""
    params, cfg, dp, dc = tiny_fp32
    pre_eng = _engine(params, cfg, role="prefill").start()
    spec_a = _engine(params, cfg, draft=(dp, dc), spec_k=2).start()
    spec_b = _engine(params, cfg, draft=(dp, dc), spec_k=2, role="decode").start()
    try:
        ref = spec_a.submit(PROMPT, 10).result(timeout=180)
        r = pre_eng.prefill_export(PROMPT)
        r.result(timeout=120)
        out = spec_b.submit_prefilled(PROMPT, r.shipment, 10).result(timeout=180)
        assert out == ref
        assert spec_b.stats()["remote_prefills"] == 1
    finally:
        for e in (pre_eng, spec_a, spec_b):
            e.stop()


# ---------------------------------------------------------------------------
# HTTP surface: /v1/prefill → /v1/prefilled over the blob-plane local dir
# ---------------------------------------------------------------------------


@pytest.fixture()
def fleet_server(tiny_fp32, tmp_path, monkeypatch):
    """One engine behind the real ASGI server (role=both serves both legs;
    the router normally spreads them over distinct replicas)."""
    import asyncio

    from modal_tpu.runtime.asgi import AsgiHttpServer
    from modal_tpu.serving.api import serving_asgi_app

    monkeypatch.setenv("MODAL_TPU_BLOB_LOCAL_DIR", str(tmp_path / "blobs"))
    params, cfg, _dp, _dc = tiny_fp32
    engine = _engine(params, cfg).start()
    loop = asyncio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True).start()
    server = AsgiHttpServer(serving_asgi_app(engine))
    asyncio.run_coroutine_threadsafe(server.start(), loop).result(30)
    try:
        yield server.port, engine
    finally:
        asyncio.run_coroutine_threadsafe(server.stop(), loop).result(10)
        loop.call_soon_threadsafe(loop.stop)
        engine.stop()


def _post(port: int, path: str, body: dict) -> dict:
    import socket

    payload = json.dumps(body).encode()
    s = socket.create_connection(("127.0.0.1", port), timeout=120)
    try:
        s.sendall(
            f"POST {path} HTTP/1.1\r\nhost: t\r\ncontent-length: {len(payload)}\r\n\r\n".encode()
            + payload
        )
        chunks = []
        while True:
            data = s.recv(65536)
            if not data:
                break
            chunks.append(data)
        return json.loads(b"".join(chunks).split(b"\r\n\r\n", 1)[1])
    finally:
        s.close()


def test_prefill_endpoint_ships_and_prefilled_decodes(fleet_server, tmp_path):
    port, engine = fleet_server
    direct = _post(port, "/v1/generate", {"prompt": PROMPT, "max_new_tokens": 8})
    ship = _post(port, "/v1/prefill", {"prompt": PROMPT})
    assert ship["n_tokens"] == len(PROMPT)
    assert ship["first_token"] == direct["tokens"][0]
    assert str(tmp_path / "blobs") in ship["kv_ref"] and os.path.exists(ship["kv_ref"])
    out = _post(
        port, "/v1/prefilled",
        {"prompt": PROMPT, "kv_ref": ship["kv_ref"], "max_new_tokens": 8},
    )
    assert out["tokens"] == direct["tokens"]
    assert engine.stats()["remote_prefills"] == 1
    # garbage kv_ref: degrade to local prefill, same tokens, HTTP 200
    out2 = _post(
        port, "/v1/prefilled",
        {"prompt": PROMPT, "kv_ref": str(tmp_path / "nope.bin"), "max_new_tokens": 8},
    )
    assert out2["tokens"] == direct["tokens"]
    # missing kv_ref is a caller error, not a degrade
    bad = _post(port, "/v1/prefilled", {"prompt": PROMPT, "max_new_tokens": 8})
    assert "error" in bad


# ---------------------------------------------------------------------------
# observability + scheduler parity
# ---------------------------------------------------------------------------


def test_fleet_metrics_and_spans_are_cataloged():
    from modal_tpu.observability import METRIC_CATALOG
    from modal_tpu.observability.device_telemetry import PUSH_FAMILIES
    from modal_tpu.observability.catalog import SPAN_CATALOG

    for fam in (
        "modal_tpu_serving_router_routed_total",
        "modal_tpu_serving_role",
        "modal_tpu_kv_pages_shipped_total",
        "modal_tpu_kv_ship_seconds",
    ):
        assert fam in METRIC_CATALOG, fam
        assert fam in PUSH_FAMILIES, fam
    assert "serving.route" in SPAN_CATALOG
    assert "serving.kv_ship" in SPAN_CATALOG


def test_slo_autoscaler_excludes_prefill_replicas_from_idle_math(tmp_path):
    """A prefill-role replica streams ~no decode tokens by design; its zero
    tokens/s must not drag the fleet's mean under the scale-down threshold
    (and its role must surface in the scheduler's per-replica report)."""
    from modal_tpu.proto import api_pb2
    from modal_tpu.server.scheduler import Scheduler
    from modal_tpu.server.state import FunctionState, ServerState, TaskState_

    def _push(ttft, tps, role_code=None):
        fams = {
            "modal_tpu_serving_ttft_p95_seconds": {"kind": "gauge", "series": {"": ttft}},
            "modal_tpu_serving_tokens_per_second": {"kind": "gauge", "series": {"": tps}},
            "modal_tpu_serving_queue_depth": {"kind": "gauge", "series": {"": 0.0}},
        }
        if role_code is not None:
            fams["modal_tpu_serving_role"] = {"kind": "gauge", "series": {"": role_code}}
        return json.dumps(fams)

    state = ServerState(str(tmp_path / "state"))
    definition = api_pb2.Function(
        function_name="svc", webhook_type=api_pb2.WEB_ENDPOINT_TYPE_ASGI_APP
    )
    definition.autoscaler_settings.min_containers = 1
    definition.autoscaler_settings.max_containers = 8
    definition.autoscaler_settings.target_ttft_ms = 500.0
    definition.autoscaler_settings.target_tokens_per_replica = 1000.0
    fn = FunctionState(function_id="fu-dis", app_id="ap-1", tag="svc", definition=definition)
    state.functions["fu-dis"] = fn
    sched = Scheduler(state)

    def _task(tid, push):
        state.tasks[tid] = TaskState_(task_id=tid, function_id="fu-dis", app_id="ap-1")
        state.tasks[tid].telemetry_prev_json = push
        return tid

    # the role rides the report
    _task("ta-x", _push(0.1, 0.0, role_code=1))
    assert sched._serving_report(state.tasks["ta-x"])["role"] == "prefill"

    # 2 busy decode replicas + 1 prefill replica at ~0 tokens/s: per-decode
    # utilization is 400 tokens/s (> 0.3 × 1000) — NOT idle, hold the fleet
    live = [
        _task("ta-1", _push(0.1, 400, role_code=2)),
        _task("ta-2", _push(0.1, 400, role_code=2)),
        _task("ta-3", _push(0.05, 0.0, role_code=1)),
    ]
    fn.slo_last_scale_at = 0.0
    assert sched._slo_desired(fn, live) == 3
    # same fleet counted naively (all roles 'both') WOULD scale down
    live_naive = [
        _task("tb-1", _push(0.1, 400)),
        _task("tb-2", _push(0.1, 400)),
        _task("tb-3", _push(0.05, 0.0)),
    ]
    fn.slo_last_scale_at = 0.0
    assert sched._slo_desired(fn, live_naive) == 2


def test_top_replica_rows_carry_the_role_column(tmp_path):
    from modal_tpu.server.history import _replica_rows
    from modal_tpu.server.state import ServerState, TaskState_

    state = ServerState(str(tmp_path / "state"))
    task = TaskState_(task_id="ta-r", function_id="fu-1", app_id="ap-1")
    task.telemetry_prev_json = json.dumps(
        {
            "modal_tpu_serving_tokens_per_second": {"kind": "gauge", "series": {"": 42.0}},
            "modal_tpu_serving_role": {"kind": "gauge", "series": {"": 2.0}},
        }
    )
    state.tasks["ta-r"] = task
    rows = _replica_rows(state)
    assert rows and rows[0]["role"] == "decode"


def test_router_knob_is_cataloged_with_the_fleet_knobs():
    from modal_tpu.analysis.knob_catalog import KNOB_CATALOG

    for knob in (
        "MODAL_TPU_SERVING_ROUTER",
        "MODAL_TPU_SERVING_ROLE",
        "MODAL_TPU_CHAOS_KV_SHIP_DROP",
    ):
        assert knob in KNOB_CATALOG, knob
    assert KNOB_CATALOG["MODAL_TPU_SERVING_ROUTER"].feature_gate


# ---------------------------------------------------------------------------
# KV-page shipping with NO shared filesystem (ISSUE 20 satellite): the
# shipment routes through the blob HTTP plane (MODAL_TPU_KV_SHIP_URL)
# ---------------------------------------------------------------------------


def test_kv_ship_over_blob_http_plane_no_shared_fs(tiny_fp32, supervisor, monkeypatch):
    """Two engines that share no filesystem: /v1/prefill on engine A PUTs
    the shipment through the supervisor's blob plane and answers an http
    kv_ref; /v1/prefilled on engine B dereferences the URL and decodes
    token-identically to a direct generate. The local-dir handoff is
    explicitly absent (MODAL_TPU_BLOB_LOCAL_DIR unset)."""
    import asyncio

    from modal_tpu.runtime.asgi import AsgiHttpServer
    from modal_tpu.serving.api import serving_asgi_app

    monkeypatch.delenv("MODAL_TPU_BLOB_LOCAL_DIR", raising=False)
    blob_url = supervisor.state.blob_url_base
    assert blob_url, "supervisor blob plane not up"
    monkeypatch.setenv("MODAL_TPU_KV_SHIP_URL", blob_url)

    params, cfg, _dp, _dc = tiny_fp32
    eng_a = _engine(params, cfg, role="prefill").start()
    eng_b = _engine(params, cfg, role="decode").start()
    loop = asyncio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True).start()
    srv_a = AsgiHttpServer(serving_asgi_app(eng_a))
    srv_b = AsgiHttpServer(serving_asgi_app(eng_b))
    asyncio.run_coroutine_threadsafe(srv_a.start(), loop).result(30)
    asyncio.run_coroutine_threadsafe(srv_b.start(), loop).result(30)
    try:
        direct = _post(srv_b.port, "/v1/generate", {"prompt": PROMPT, "max_new_tokens": 8})
        ship = _post(srv_a.port, "/v1/prefill", {"prompt": PROMPT})
        assert ship["kv_ref"].startswith("http://"), ship["kv_ref"]
        assert f"{blob_url}/blob/" in ship["kv_ref"]
        out = _post(
            srv_b.port, "/v1/prefilled",
            {"prompt": PROMPT, "kv_ref": ship["kv_ref"], "max_new_tokens": 8},
        )
        assert out["tokens"] == direct["tokens"]
        assert eng_b.stats()["remote_prefills"] == 1
    finally:
        asyncio.run_coroutine_threadsafe(srv_a.stop(), loop).result(10)
        asyncio.run_coroutine_threadsafe(srv_b.stop(), loop).result(10)
        loop.call_soon_threadsafe(loop.stop)
        eng_a.stop()
        eng_b.stop()


def test_kv_ship_url_unreachable_degrades_to_local_file(tiny_fp32, monkeypatch, tmp_path):
    """A dead blob plane must not fail the prefill leg: the shipment falls
    back to the local-file handoff (tempdir) and the decode leg still lands
    it — degradation symmetry for the new knob."""
    import asyncio

    from modal_tpu.runtime.asgi import AsgiHttpServer
    from modal_tpu.serving.api import serving_asgi_app

    monkeypatch.delenv("MODAL_TPU_BLOB_LOCAL_DIR", raising=False)
    monkeypatch.setenv("MODAL_TPU_KV_SHIP_URL", "http://127.0.0.1:9")  # discard port

    params, cfg, _dp, _dc = tiny_fp32
    engine = _engine(params, cfg).start()
    loop = asyncio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True).start()
    server = AsgiHttpServer(serving_asgi_app(engine))
    asyncio.run_coroutine_threadsafe(server.start(), loop).result(30)
    try:
        ship = _post(server.port, "/v1/prefill", {"prompt": PROMPT})
        assert not ship["kv_ref"].startswith("http"), ship["kv_ref"]
        assert os.path.exists(ship["kv_ref"])
        out = _post(
            server.port, "/v1/prefilled",
            {"prompt": PROMPT, "kv_ref": ship["kv_ref"], "max_new_tokens": 8},
        )
        assert len(out["tokens"]) == 8
        assert engine.stats()["remote_prefills"] == 1
    finally:
        asyncio.run_coroutine_threadsafe(server.stop(), loop).result(10)
        loop.call_soon_threadsafe(loop.stop)
        engine.stop()
