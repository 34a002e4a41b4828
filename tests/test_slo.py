"""ISSUE 11: fleet SLO observability — embedded time-series store,
burn-rate alerting, per-request serving timelines, live `top` dashboard.

Contracts pinned here (docs/OBSERVABILITY.md):
- the store's windows hold DELTAS: a TTFT spike outside the window can
  neither fire nor block an alert, and memory is bounded by construction
  (ring tiers × series cap), never by uptime;
- one quantile contract across the stack (observability/quantile.py): the
  registry, the attribution aggregate, and the store agree on p50;
- multi-window burn-rate alerting: fires only when fast AND slow windows
  burn the budget, resolves only on fast-window evidence, and holds state
  through silence (no data ≠ healthy);
- alert transitions are journaled: a firing alert survives a supervisor
  crash_restart and can only resolve on real post-restart samples
  (the ISSUE 11 acceptance demo, chaos-injected serving latency included);
- per-request serving timelines decompose TTFT / per-token latency into
  queue/prefill/decode/stream with explicit gap residue, survive span-store
  rotation, and stay within the observability overhead budget;
- serving request ids are globally unique (task/replica-prefixed).
"""

import json
import os
import time

import pytest

pytestmark = pytest.mark.observability

# same engine geometry as test_serving.py: the jitted paged executables key
# on these shapes, so this module rides compiles test_serving already paid
SLOTS, PAGES, PAGE, PAGES_PER_SLOT = 4, 25, 16, 8


@pytest.fixture(scope="module")
def tiny_model():
    import jax

    from modal_tpu.models.llama import get_config, init_params

    cfg = get_config("tiny")
    return init_params(cfg, jax.random.PRNGKey(0)), cfg


def _engine(params, cfg, **overrides):
    from modal_tpu.serving.engine import ServingEngine

    kwargs = dict(
        max_slots=SLOTS, num_pages=PAGES, page_size=PAGE,
        pages_per_slot=PAGES_PER_SLOT, prefill_chunk=32,
    )
    kwargs.update(overrides)
    return ServingEngine(params, cfg, **kwargs)


def _registry_with_families():
    """A private registry carrying the families the store/rules read."""
    from modal_tpu.observability.metrics import MetricsRegistry

    reg = MetricsRegistry()
    reg.histogram(
        "modal_tpu_serving_ttft_seconds", "ttft", buckets=(0.01, 0.05, 0.1, 0.5, 1.0, 5.0)
    )
    reg.histogram("modal_tpu_dispatch_latency_seconds", "disp", buckets=(0.01, 0.1, 1.0))
    reg.counter("modal_tpu_task_results_total", "results", ("status",))
    reg.gauge("modal_tpu_serving_tokens_per_second", "tps")
    reg.gauge("modal_tpu_serving_queue_depth", "queue")
    return reg


def _store(reg, interval_s=0.05):
    from modal_tpu.observability.timeseries import TimeSeriesStore

    return TimeSeriesStore(registry=reg, interval_s=interval_s)


# ---------------------------------------------------------------------------
# the shared quantile contract (dedupe satellite)
# ---------------------------------------------------------------------------


def test_shared_quantile_helpers():
    from modal_tpu.observability.metrics import Histogram
    from modal_tpu.observability.quantile import bucket_quantile, quantile

    # nearest-rank: empty, single, interior, extremes
    assert quantile([], 0.5) == 0.0
    assert quantile([3.0], 0.99) == 3.0
    vals = sorted(float(i) for i in range(1, 102))  # 1..101: odd length
    assert quantile(vals, 0.0) == 1.0
    assert quantile(vals, 1.0) == 101.0
    assert quantile(vals, 0.5) == 51.0  # exact middle

    # bucket quantile: empty, +Inf overflow collapses to last finite bound
    assert bucket_quantile((1.0, 2.0), [0, 0], 0.5) is None
    assert bucket_quantile((1.0, 2.0), [10, 0], 0.5) == 1.0
    assert bucket_quantile((1.0, 2.0), [0, 0], 0.5, total=5) == 2.0  # all +Inf

    # the registry's Histogram.quantile and the helper agree by construction
    h = Histogram("x", "", (), buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.05, 0.5, 0.5, 0.5, 5.0):
        h.observe(v)
    with h._lock:
        merged = list(next(iter(h._series.values())).counts)
    assert h.quantile(0.5) == bucket_quantile(h.buckets, merged, 0.5, total=6) == 1.0

    # the attribution module's historical name is the same function
    from modal_tpu.observability.critical_path import _quantile

    assert _quantile is quantile


# ---------------------------------------------------------------------------
# time-series store
# ---------------------------------------------------------------------------


def test_store_counter_deltas_and_window_rates():
    reg = _registry_with_families()
    c = reg.get("modal_tpu_task_results_total")
    store = _store(reg)
    c.inc(100, status="SUCCESS")  # pre-store history
    store.sample(now=0.0)  # baseline: history must NOT land in any window
    assert store.counter_sum("modal_tpu_task_results_total", 100, now=0.1) is None
    for i in range(5):
        c.inc(2, status="SUCCESS")
        c.inc(1, status="FAILURE")
        store.sample(now=1.0 + i)
    # only the deltas since the baseline are in the window
    assert store.counter_sum("modal_tpu_task_results_total", 100, now=5.5) == 15
    assert store.counter_sum("modal_tpu_task_results_total", 100, now=5.5, label_filter="FAILURE") == 5
    # rate = sum/window; a window holding no points answers None, not 0
    assert store.counter_rate("modal_tpu_task_results_total", 10, now=5.5) == pytest.approx(1.5)
    assert store.counter_rate("modal_tpu_task_results_total", 1.0, now=100.0) is None


def test_store_hist_window_quantile_excludes_old_spikes():
    reg = _registry_with_families()
    h = reg.get("modal_tpu_serving_ttft_seconds")
    store = _store(reg)
    store.sample(now=0.0)
    # old spike: p95 in ITS window is terrible
    for _ in range(20):
        h.observe(4.0)
    store.sample(now=1.0)
    assert store.hist_quantile("modal_tpu_serving_ttft_seconds", 0.95, 10, now=1.1) == 5.0
    # recent window: healthy samples only — the spike is outside and gone
    for _ in range(20):
        h.observe(0.03)
    store.sample(now=20.0)
    assert store.hist_quantile("modal_tpu_serving_ttft_seconds", 0.95, 5.0, now=20.1) == 0.05
    # and a window with no observations answers None (stale ≠ healthy)
    assert store.hist_quantile("modal_tpu_serving_ttft_seconds", 0.95, 5.0, now=60.0) is None


def test_store_gauge_minmax_rollup_and_bounded_memory():
    reg = _registry_with_families()
    g = reg.get("modal_tpu_serving_tokens_per_second")
    store = _store(reg, interval_s=1.0)  # tiers: 1 s raw, 6 s, 60 s
    for i in range(400):  # > raw maxlen (360)
        g.set(float(i))
        store.sample(now=float(i))
    raw = store.tiers[0]
    dq = raw.data[("modal_tpu_serving_tokens_per_second", "")]
    assert len(dq) == raw.maxlen  # ring bound holds
    # the 6 s rollup kept min/max across its bucket, not just the last value
    mid = store.tiers[1]
    pts = mid.data[("modal_tpu_serving_tokens_per_second", "")]
    assert pts, "rollup tier never flushed"
    t, last, mn, mx = pts[-1]
    assert mn < mx and last == mx  # monotonic ramp: last == max, min < max
    # windows wider than raw retention pick the rollup tier
    stats = store.gauge_stats("modal_tpu_serving_tokens_per_second", 395.0, now=399.0)
    assert stats is not None and stats["max"] >= 390


def test_store_series_cap_overflow():
    from modal_tpu.observability.timeseries import OVERFLOW_KEY

    reg = _registry_with_families()
    c = reg.get("modal_tpu_task_results_total")
    store = _store(reg)
    store.sample(now=0.0)
    for i in range(50):  # > MAX_TRACKED_SERIES distinct label values
        c.inc(1, status=f"s{i}")
    store.sample(now=1.0)
    keys = {k for (f, k) in store.tiers[0].data if f == "modal_tpu_task_results_total"}
    from modal_tpu.observability.timeseries import MAX_TRACKED_SERIES

    assert len(keys) <= MAX_TRACKED_SERIES + 1
    assert OVERFLOW_KEY in keys
    # nothing lost: the overflow series absorbed the excess counts
    assert store.counter_sum("modal_tpu_task_results_total", 10, now=1.1) == 50


# ---------------------------------------------------------------------------
# burn-rate evaluation + alert state machine
# ---------------------------------------------------------------------------


def _ttft_rule(threshold=0.5, fast=1.0, slow=3.0):
    from modal_tpu.observability.slo import SLORule

    return SLORule(
        name="ttft", description="test ttft", family="modal_tpu_serving_ttft_seconds",
        kind="hist_quantile", q=0.95, threshold=threshold,
        fast_window_s=fast, slow_window_s=slow,
    )


def test_multiwindow_burn_fire_and_resolve():
    from modal_tpu.observability.slo import SLOEvaluator

    reg = _registry_with_families()
    h = reg.get("modal_tpu_serving_ttft_seconds")
    store = _store(reg)
    ev = SLOEvaluator(store, rules=[_ttft_rule()])
    store.sample(now=0.0)
    # seed the slow window with a healthy mass, then one fresh spike: the
    # fast window burns hard but the slow window's p95 stays healthy
    # (1 of 31 observations) -> multi-window logic must NOT fire on it
    for _ in range(30):
        h.observe(0.03)
    store.sample(now=1.0)
    h.observe(4.0)
    store.sample(now=3.5)
    assert ev.evaluate(now=3.5) == []
    # sustained breach: spike mass dominates both windows -> fires
    for i in range(10):
        h.observe(4.0)
        store.sample(now=4.0 + i * 0.3)
    transitions = ev.evaluate(now=7.0)
    assert [t["state"] for t in transitions] == ["firing"]
    assert ev.alerts["ttft"]["state"] == "firing"
    assert ev.burn_rate("ttft", now=7.0) > 1.0
    # silence: no samples in the fast window -> alert HOLDS (no data ≠ ok)
    assert ev.evaluate(now=100.0) == []
    assert ev.alerts["ttft"]["state"] == "firing"
    # recovery evidence in the fast window -> resolves
    for i in range(10):
        h.observe(0.03)
        store.sample(now=200.0 + i * 0.05)
    transitions = ev.evaluate(now=200.6)
    assert [t["state"] for t in transitions] == ["resolved"]


def test_throughput_style_rule_burns_inverted():
    from modal_tpu.observability.slo import SLOEvaluator, SLORule

    reg = _registry_with_families()
    g = reg.get("modal_tpu_serving_tokens_per_second")
    store = _store(reg)
    rule = SLORule(
        name="tps", description="floor", family="modal_tpu_serving_tokens_per_second",
        kind="gauge", threshold=100.0, op="<", fast_window_s=1.0, slow_window_s=3.0,
    )
    ev = SLOEvaluator(store, rules=[rule])
    g.set(25.0)  # 4x under the floor
    for i in range(8):
        store.sample(now=i * 0.5)
    assert [t["state"] for t in ev.evaluate(now=4.0)] == ["firing"]
    assert ev.burn_rate("tps", now=4.0) == pytest.approx(4.0)
    g.set(400.0)
    for i in range(4):
        store.sample(now=5.0 + i * 0.3)
    assert [t["state"] for t in ev.evaluate(now=6.0)] == ["resolved"]


def test_alert_transitions_are_journaled_and_replayable(tmp_path):
    from modal_tpu.observability.slo import SLOEvaluator
    from modal_tpu.server.journal import Journal, recover_state
    from modal_tpu.server.state import ServerState

    reg = _registry_with_families()
    h = reg.get("modal_tpu_serving_ttft_seconds")
    store = _store(reg)
    journal = Journal(str(tmp_path / "state"))
    ev = SLOEvaluator(store, rules=[_ttft_rule()], journal=journal)
    store.sample(now=0.0)
    for i in range(12):
        h.observe(4.0)
        store.sample(now=0.5 + i * 0.3)
    assert ev.evaluate(now=4.0), "alert should fire"
    journal.close()
    # replay into a fresh state: the firing alert is rebuilt
    state = ServerState(str(tmp_path / "state2"))
    recover_state(state, Journal(str(tmp_path / "state")))
    assert state.alerts["ttft"]["state"] == "firing"
    assert state.alerts["ttft"]["burn_rate"] > 1.0
    # a fresh evaluator ADOPTS the recovered state and, with an empty
    # store (post-restart), cannot resolve it — silence is not recovery
    store2 = _store(_registry_with_families())
    ev2 = SLOEvaluator(store2, rules=[_ttft_rule()], alerts=state.alerts)
    assert ev2.evaluate(now=10.0) == []
    assert state.alerts["ttft"]["state"] == "firing"


def test_throughput_floor_catches_wedged_producer():
    """The floor rule reads a RATE over the cumulative token counter: a
    wedged engine freezes the tokens/s gauge at its last healthy value
    (invisible staleness), but the counter's zero deltas read honestly as
    zero throughput and the alert fires."""
    from modal_tpu.observability.slo import SLOEvaluator, SLORule

    reg = _registry_with_families()
    tokens = reg.counter("modal_tpu_serving_tokens_total", "tok")
    stale_gauge = reg.get("modal_tpu_serving_tokens_per_second")
    store = _store(reg)
    rule = SLORule(
        name="tps_floor", description="floor", family="modal_tpu_serving_tokens_total",
        kind="counter_rate", threshold=100.0, op="<", fast_window_s=2.0, slow_window_s=5.0,
    )
    ev = SLOEvaluator(store, rules=[rule])
    store.sample(now=0.0)
    # healthy: ~200 tokens/s; the gauge agrees
    for i in range(10):
        tokens.inc(100)
        stale_gauge.set(200.0)
        store.sample(now=0.5 + i * 0.5)
    assert ev.evaluate(now=5.5) == []
    # wedge: the engine stops emitting — the gauge FREEZES at 200 (stale),
    # but the counter's deltas go to zero and the rate-based rule fires
    for i in range(12):
        store.sample(now=6.0 + i * 0.5)
    assert stale_gauge.value() == 200.0  # the trap the gauge rule fell into
    assert [t["state"] for t in ev.evaluate(now=12.0)] == ["firing"]
    assert ev.alerts["tps_floor"]["value"] == 0.0


# ---------------------------------------------------------------------------
# scheduler: burn rate as the scale-up urgency signal
# ---------------------------------------------------------------------------


def test_scheduler_consumes_burn_rate_urgency(tmp_path):
    from modal_tpu.proto import api_pb2
    from modal_tpu.server.scheduler import Scheduler
    from modal_tpu.server.state import FunctionState, ServerState

    state = ServerState(str(tmp_path / "state"))
    definition = api_pb2.Function(
        function_name="svc", webhook_type=api_pb2.WEB_ENDPOINT_TYPE_ASGI_APP
    )
    definition.autoscaler_settings.min_containers = 1
    definition.autoscaler_settings.max_containers = 8
    definition.autoscaler_settings.target_ttft_ms = 100.0
    fn = FunctionState(function_id="fu-burn", app_id="ap-1", tag="svc", definition=definition)
    state.functions["fu-burn"] = fn
    sched = Scheduler(state)

    # attach a store whose fast window shows p95 TTFT ~50x the target:
    # urgency steps the fleet by MORE than one replica per cooldown move
    reg = _registry_with_families()
    h = reg.get("modal_tpu_serving_ttft_seconds")
    store = _store(reg)
    state.timeseries = store
    store.sample()
    for _ in range(20):
        h.observe(4.0)
    store.sample()
    assert sched._ttft_burn_rate(fn, 0.1) > 8.0
    live = ["ta-1"]  # no task records needed: the burn path is report-free
    assert sched._slo_desired(fn, live) == 1 + 3  # max urgency step
    # moderate burn -> moderate step
    fn.slo_last_scale_at = 0.0
    state.timeseries = None
    reg2 = _registry_with_families()
    h2 = reg2.get("modal_tpu_serving_ttft_seconds")
    store2 = _store(reg2)
    state.timeseries = store2
    store2.sample()
    for _ in range(20):
        h2.observe(0.3)  # p95 -> 0.5 bucket = 5x target
    store2.sample()
    burn = sched._ttft_burn_rate(fn, 0.1)
    assert 2.0 <= burn < 8.0
    assert sched._slo_desired(fn, live) == 1 + 2
    # healthy burn (<0.5): no up-step; idle scale-down applies when the
    # throughput side says so
    fn.slo_last_scale_at = 0.0
    state.timeseries = None
    reg3 = _registry_with_families()
    h3 = reg3.get("modal_tpu_serving_ttft_seconds")
    store3 = _store(reg3)
    state.timeseries = store3
    store3.sample()
    for _ in range(20):
        h3.observe(0.005)
    store3.sample()
    assert sched._ttft_burn_rate(fn, 0.1) < 0.5
    assert sched._slo_desired(fn, live) == 1
    # without a store, behavior is the raw-report fallback (None burn)
    state.timeseries = None
    assert sched._ttft_burn_rate(fn, 0.1) is None
    # the fleet TTFT histogram is UNLABELED: with ANY other function running
    # live serving replicas (SLO-targeted or not — a target-less slow
    # service feeds the same histogram), the windowed p95 is not
    # attributable to this function's objective — burn degrades to None
    # (per-replica raw reports) instead of scaling fn on the other's latency
    state.timeseries = store3
    assert sched._ttft_burn_rate(fn, 0.1) is not None
    defn2 = api_pb2.Function(function_name="svc2", webhook_type=api_pb2.WEB_ENDPOINT_TYPE_ASGI_APP)
    fn2 = FunctionState(function_id="fu-other", app_id="ap-1", tag="svc2", definition=defn2)
    state.functions["fu-other"] = fn2
    from modal_tpu.server.state import TaskState_

    other_task = TaskState_(
        task_id="ta-other", function_id="fu-other", app_id="ap-1",
        state=api_pb2.TASK_STATE_ACTIVE,
    )
    other_task.telemetry_prev_json = json.dumps(
        {"modal_tpu_serving_ttft_p95_seconds": {"kind": "gauge", "series": {"": 5.0}}}
    )
    state.tasks["ta-other"] = other_task
    fn2.task_ids.add("ta-other")
    assert sched._ttft_burn_rate(fn, 0.1) is None
    # ...but a non-serving neighbor (no pushed serving telemetry) does not
    # disable the burn signal
    other_task.telemetry_prev_json = ""
    assert sched._ttft_burn_rate(fn, 0.1) is not None


# ---------------------------------------------------------------------------
# per-request serving timelines + serving attribution (tentpole c)
# ---------------------------------------------------------------------------


def test_serving_timelines_and_attribution(tiny_model, tmp_path, monkeypatch):
    from modal_tpu.observability import critical_path as cp, tracing
    from modal_tpu.observability.catalog import SERVING_TTFT

    params, cfg = tiny_model
    trace_dir = str(tmp_path / "traces")
    monkeypatch.setenv("MODAL_TPU_SERVING_SPANS", "1")
    monkeypatch.setenv("MODAL_TPU_SERVING_SPAN_TOKENS", "4")
    tracing.configure(trace_dir)
    engine = _engine(params, cfg).start()
    try:
        reqs = [engine.submit([7, 8, 9], max_new_tokens=12) for _ in range(3)]
        for r in reqs:
            r.result(timeout=60)
    finally:
        engine.stop()
    spans = tracing.read_spans(trace_dir)
    by_name: dict[str, list] = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    # the full lifecycle is on disk: root, queue-admit, prefill (+chunks),
    # periodic decode marks carrying batch occupancy + KV pool attrs
    for name in ("serving.request", "serving.admit", "serving.prefill",
                 "serving.prefill_chunk", "serving.decode"):
        assert by_name.get(name), f"missing {name} spans"
    mark = by_name["serving.decode"][0]
    assert "batch_occupancy" in mark["attrs"] and "kv_pages_free" in mark["attrs"]
    roots = by_name["serving.request"]
    assert all(r["attrs"].get("tokens") == 12 for r in roots if r.get("end"))

    # attribution: TTFT + per-token latency decompose into the serving
    # segments with small gap residue (acceptance bar is <=10% on bench)
    agg, per_trace = cp.attribute_store(trace_dir, "", serving=True)
    assert agg["calls"] == 3
    for segment in ("queue", "prefill", "decode"):
        assert segment in agg["segments"], agg["segments"].keys()
    assert agg["gap_share"] <= 0.10

    # TTFT histogram exemplars resolve to these traces
    trace_ids = {s["trace_id"] for s in spans}
    ex_ids = set()
    for series in SERVING_TTFT._series.values():
        ex_ids |= {tid for tid, _v, _t in series.exemplars.values()}
    assert ex_ids & trace_ids, "no TTFT exemplar resolves to a recorded timeline"


def test_serving_spans_disabled_knob(tiny_model, tmp_path, monkeypatch):
    from modal_tpu.observability import tracing

    params, cfg = tiny_model
    trace_dir = str(tmp_path / "traces-off")
    monkeypatch.setenv("MODAL_TPU_SERVING_SPANS", "0")
    tracing.configure(trace_dir)
    engine = _engine(params, cfg).start()
    try:
        engine.submit([1, 2, 3], max_new_tokens=4).result(timeout=60)
    finally:
        engine.stop()
    names = {s["name"] for s in tracing.read_spans(trace_dir)}
    assert not names & {"serving.request", "serving.prefill_chunk", "serving.decode"}


def test_trace_retention_under_serving_span_volume(tiny_model, tmp_path, monkeypatch):
    """ISSUE 11 satellite: per-request timelines at high request rate must
    rotate within MODAL_TPU_TRACE_MAX_BYTES without evicting the live sink,
    and `app attribute --serving` must still resolve recent traces
    post-rotation."""
    from click.testing import CliRunner

    from modal_tpu.cli.entry_point import cli
    from modal_tpu.observability import critical_path as cp, tracing

    params, cfg = tiny_model
    state_dir = str(tmp_path / "state")
    trace_dir = os.path.join(state_dir, "traces")
    monkeypatch.setenv("MODAL_TPU_SERVING_SPANS", "1")
    monkeypatch.setenv("MODAL_TPU_TRACE_MAX_BYTES", "20000")  # tiny: force rotation
    tracing.configure(trace_dir)
    engine = _engine(params, cfg).start()
    try:
        # real per-request timelines...
        for _ in range(2):
            engine.submit([5, 6], max_new_tokens=6).result(timeout=60)
        # ...then synthetic timeline volume (full lifecycle each) until the
        # sink has rotated several times — the cheap stand-in for a high
        # request rate, emitting the exact same span names
        for i in range(400):
            root = tracing.open_span("serving.request", attrs={"request_id": f"flood-{i}"})
            t0 = time.time()
            tracing.record_span(
                "serving.admit", start=t0 - 0.02, end=t0 - 0.015, parent=root.context
            )
            tracing.record_span(
                "serving.decode", start=t0 - 0.015, end=t0, parent=root.context
            )
            tracing.close_span(root)
        # the most recent request AFTER the flood must survive rotation
        final = engine.submit([5, 6], max_new_tokens=6)
        final.result(timeout=60)
    finally:
        engine.stop()
    live = os.path.join(trace_dir, f"spans-{os.getpid()}.jsonl")
    rotated = live + ".1"
    assert os.path.exists(live), "live sink evicted by rotation"
    assert os.path.exists(rotated), "sink never rotated under span volume"
    assert os.path.getsize(live) + os.path.getsize(rotated) < 3 * 20000
    # attribution still resolves the RECENT timelines (readers merge .1:
    # the live file alone may be seconds old, or empty, when the last span
    # written was the one that rotated it)
    agg, _ = cp.attribute_store(trace_dir, "", serving=True)
    assert agg["calls"] >= 1
    result = CliRunner().invoke(
        cli, ["app", "attribute", "", "--state-dir", state_dir, "--serving"],
        catch_exceptions=False,
    )
    assert result.exit_code == 0, result.output
    assert "decode" in result.output and "gap share" in result.output
    # gc with the live-sink grace never unlinks the sink we're writing, down
    # to a store of one byte; the rotated generation is what it may take.
    # Last, because it takes it: the attribution above needs both files
    # whenever the final request's spans straddle a rotation (1 position in
    # 26 of the 20,000-byte boundary)
    report = tracing.gc_trace_dir(trace_dir, max_total_bytes=1)
    assert os.path.exists(live) and not os.path.exists(rotated) and report["removed"] >= 1


def test_request_ids_globally_unique(monkeypatch):
    from modal_tpu.serving import engine as eng

    monkeypatch.setenv("MODAL_TPU_TASK_ID", "ta-alpha")
    eng._replica_id_cache.clear()
    r1 = eng.GenRequest([1], 1)
    assert "ta-alpha" in r1.id
    eng._replica_id_cache.clear()
    monkeypatch.setenv("MODAL_TPU_TASK_ID", "ta-beta")
    r2 = eng.GenRequest([1], 1)
    assert "ta-beta" in r2.id and r1.id != r2.id
    eng._replica_id_cache.clear()
    monkeypatch.delenv("MODAL_TPU_TASK_ID")
    r3 = eng.GenRequest([1], 1)  # outside a container: host-pid prefix
    assert str(os.getpid()) in r3.id
    eng._replica_id_cache.clear()


# ---------------------------------------------------------------------------
# history plane: MetricsHistory RPC, GET /metrics/history, alerts/top CLI
# ---------------------------------------------------------------------------


def test_history_rpc_http_and_cli(supervisor, tmp_path):
    import urllib.request

    from click.testing import CliRunner

    from modal_tpu._utils.async_utils import synchronizer
    from modal_tpu.cli.entry_point import cli
    from modal_tpu.observability.catalog import SERVING_QUEUE_DEPTH, SERVING_TTFT
    from modal_tpu.proto import api_pb2

    state = supervisor.state
    assert state.timeseries is not None and state.slo is not None
    # feed the store without waiting for the 10 s cadence
    SERVING_TTFT.observe(0.04)
    SERVING_QUEUE_DEPTH.set(2.0)
    state.timeseries.sample()
    SERVING_TTFT.observe(0.06)
    state.timeseries.sample()
    state.slo.evaluate()

    async def _history(**kw):
        from modal_tpu.client import _Client

        client = await _Client.from_env()
        return await client.stub.MetricsHistory(api_pb2.MetricsHistoryRequest(**kw))

    resp = synchronizer.run(_history(query="describe"))
    desc = json.loads(resp.payload_json)
    assert "modal_tpu_serving_ttft_seconds" in desc["families"]
    resp = synchronizer.run(
        _history(query="quantile", family="modal_tpu_serving_ttft_seconds", window_s=60.0, q=0.95)
    )
    assert json.loads(resp.payload_json)["value"] is not None
    resp = synchronizer.run(_history(query="alerts"))
    alerts = json.loads(resp.payload_json)
    assert any(r["rule"] == "serving_ttft_p95" for r in alerts["rules"])
    resp = synchronizer.run(_history(query="top"))
    top = json.loads(resp.payload_json)
    assert top["fleet"]["queue_depth"] == 2.0

    # same queries over HTTP (the plane the CLI uses)
    url = f"http://127.0.0.1:{supervisor.blob_server.port}/metrics/history?query=top"
    http_top = json.loads(urllib.request.urlopen(url, timeout=10).read())
    assert http_top["fleet"]["queue_depth"] == 2.0
    series_url = (
        f"http://127.0.0.1:{supervisor.blob_server.port}/metrics/history"
        "?query=series&family=modal_tpu_serving_ttft_seconds&window_s=60"
    )
    series = json.loads(urllib.request.urlopen(series_url, timeout=10).read())
    assert series["kind"] == "histogram" and series["series"]

    # CLI: alerts table + one top frame, via the metrics_url breadcrumb
    state_dir = supervisor.state_dir
    result = CliRunner().invoke(
        cli, ["alerts", "--state-dir", state_dir], catch_exceptions=False
    )
    assert result.exit_code == 0, result.output
    assert "serving_ttft_p95" in result.output and "firing" in result.output
    result = CliRunner().invoke(
        cli, ["top", "--once", "--state-dir", state_dir], catch_exceptions=False
    )
    assert result.exit_code == 0, result.output
    assert "modal_tpu top" in result.output and "TTFT" in result.output
    result = CliRunner().invoke(
        cli, ["top", "--json", "--state-dir", state_dir], catch_exceptions=False
    )
    assert json.loads(result.output)["fleet"]["queue_depth"] == 2.0


# ---------------------------------------------------------------------------
# ISSUE 11 acceptance: chaos-injected serving latency -> burn-rate alert
# fires in the fast window, shows in `modal_tpu alerts` + the journal,
# survives crash_restart, resolves after the injection stops
# ---------------------------------------------------------------------------


def test_e2e_chaos_alert_fire_crash_survive_resolve(tiny_model, tmp_path, monkeypatch):
    from click.testing import CliRunner

    from modal_tpu._utils.async_utils import synchronizer
    from modal_tpu.cli.entry_point import cli
    from modal_tpu.server.supervisor import LocalSupervisor

    params, cfg = tiny_model
    state_dir = str(tmp_path / "state")
    monkeypatch.setenv("MODAL_TPU_STATE_DIR", state_dir)
    # tight windows + a TTFT objective the chaos injection blows through
    # but healthy CPU requests stay WELL under, with margins sized for a
    # loaded CI host (bucket-resolution honest: chaos TTFT ≥1 s → ≥2.5
    # bucket → burn ≥2.5; healthy ≈0.01-0.3 s → ≤0.5 bucket → burn ≤0.5)
    monkeypatch.setenv("MODAL_TPU_TS_INTERVAL", "0.15")
    monkeypatch.setenv("MODAL_TPU_SLO_FAST_WINDOW_S", "1.2")
    monkeypatch.setenv("MODAL_TPU_SLO_SLOW_WINDOW_S", "3.0")
    monkeypatch.setenv("MODAL_TPU_SLO_TTFT_P95_S", "1.0")
    monkeypatch.setenv("MODAL_TPU_SERVING_SPANS", "0")  # not under test here
    sup = LocalSupervisor(num_workers=0, state_dir=state_dir)
    synchronizer.run(sup.start())
    engine = None
    try:
        assert sup.state.timeseries is not None and sup.state.timeseries.interval_s == 0.15
        # chaos-injected latency on the serving path: every engine loop
        # iteration stalls ≥0.5 s, so TTFT (admit + prefill ≈ 2+ iterations)
        # lands far over the 1 s objective
        monkeypatch.setenv("MODAL_TPU_CHAOS_SERVING_STEP_DELAY_S", "0.5")
        engine = _engine(params, cfg).start()
        assert engine.chaos_step_delay == 0.5
        deadline = time.time() + 30
        fired = False
        while time.time() < deadline and not fired:
            engine.submit([3, 4, 5], max_new_tokens=3).result(timeout=60)
            fired = sup.state.alerts.get("serving_ttft_p95", {}).get("state") == "firing"
        assert fired, f"alert never fired; alerts={sup.state.alerts}"
        # visible in `modal_tpu alerts` (served over the history plane)
        result = CliRunner().invoke(
            cli, ["alerts", "--state-dir", state_dir], catch_exceptions=False
        )
        assert result.exit_code == 0, result.output
        assert "serving_ttft_p95" in result.output and "firing" in result.output
        # ...and in the journal as a typed record
        assert sup.state.journal is not None
        snap, tail = sup.state.journal.replay()
        assert any(
            rec.get("t") == "alert" and rec.get("state") == "firing"
            for rec in list(snap) + list(tail)
        )
        # supervisor crash + journal recovery: the alert SURVIVES (and an
        # empty post-restart store cannot resolve it)
        engine.chaos_step_delay = 0.0  # stop the injection
        synchronizer.run(sup.crash_restart())
        assert sup.state.alerts["serving_ttft_p95"]["state"] == "firing"
        # healthy traffic after the injection stopped: the fast window
        # fills with sub-objective TTFTs and the alert resolves
        deadline = time.time() + 30
        resolved = False
        while time.time() < deadline and not resolved:
            engine.submit([3, 4, 5], max_new_tokens=3).result(timeout=60)
            resolved = sup.state.alerts.get("serving_ttft_p95", {}).get("state") == "resolved"
        assert resolved, f"alert never resolved; alerts={sup.state.alerts}"
    finally:
        if engine is not None:
            engine.stop()
        synchronizer.run(sup.stop())


# ---------------------------------------------------------------------------
# top payload: per-replica rows from raw heartbeat pushes
# ---------------------------------------------------------------------------


def test_top_payload_per_replica_rows(tmp_path):
    from modal_tpu.server.history import top_payload
    from modal_tpu.server.state import ServerState, TaskState_

    state = ServerState(str(tmp_path / "state"))
    push = json.dumps(
        {
            "modal_tpu_serving_ttft_p95_seconds": {"kind": "gauge", "series": {"": 0.12}},
            "modal_tpu_serving_tokens_per_second": {"kind": "gauge", "series": {"": 321.0}},
            "modal_tpu_serving_queue_depth": {"kind": "gauge", "series": {"": 1.0}},
            "modal_tpu_kv_pages_free": {"kind": "gauge", "series": {"": 9.0}},
            "modal_tpu_serving_batch_occupancy": {
                "kind": "histogram",
                "series": {"": {"counts": [1, 1], "sum": 12.0, "count": 4}},
            },
        }
    )
    t = TaskState_(task_id="ta-top", function_id="fu-x", app_id="ap-x")
    t.telemetry_prev_json = push
    t.started_at = time.time() - 5
    state.tasks["ta-top"] = t
    # a task pushing only device telemetry (no serving families) is skipped
    t2 = TaskState_(task_id="ta-dev", function_id="fu-x", app_id="ap-x")
    t2.telemetry_prev_json = json.dumps(
        {"modal_tpu_device_memory_bytes": {"kind": "gauge", "series": {"host,rss": 1e9}}}
    )
    state.tasks["ta-dev"] = t2
    payload = top_payload(state)
    rows = payload["replicas"]
    assert len(rows) == 1
    row = rows[0]
    assert row["task_id"] == "ta-top"
    assert row["ttft_p95_s"] == 0.12
    assert row["tokens_per_s"] == 321.0
    assert row["kv_pages_free"] == 9.0
    assert row["batch_occupancy_mean"] == pytest.approx(3.0)
