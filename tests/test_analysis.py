"""Static-analysis pass suite (ISSUE 15, modal_tpu/analysis/): per-rule
fixture tests — each pass must catch a minimized reproduction of its
motivating shipped bug and must NOT flag the corrected code — plus the
tier-1 gate that runs the full suite over modal_tpu/ and fails on any
unsuppressed finding, the pinned `modal_tpu lint --json` shape, and the
degradation-symmetry off-toggle backfill for feature gates that had no
off-path test."""

import json
import textwrap

import pytest

from modal_tpu.analysis.core import module_from_source, run_pass


def _mod(src: str, relpath: str = "server/fixture.py"):
    return module_from_source(textwrap.dedent(src), relpath)


# ---------------------------------------------------------------------------
# Rule 1: lock-across-await — pinned on BOTH PR 8 shipped bugs
# ---------------------------------------------------------------------------


def test_lock_across_await_catches_keepalive_yield_bug():
    """PR 8 shipped bug #1 (minimized): the keep-alive yield inside the
    output condition lock — the yield suspends for the whole flow-controlled
    gRPC send, so one stalled stream consumer blocked every producer's
    notify_all for the call."""
    mod = _mod(
        """
        import asyncio

        async def stream_outputs(call, context):
            while True:
                async with call.output_condition:
                    try:
                        await asyncio.wait_for(call.output_condition.wait(), timeout=5.0)
                    except asyncio.TimeoutError:
                        yield make_keepalive()
        """
    )
    found = run_pass("lock-across-await", [mod])
    assert len(found) == 1, [f.message for f in found]
    assert "yield" in found[0].message
    assert "call.output_condition" in found[0].message
    assert found[0].scope == "stream_outputs"


def test_lock_across_await_passes_corrected_keepalive():
    """The PR 8 fix: condition self-wait stays inside (it RELEASES the lock
    while waiting — the legitimate idiom), the keep-alive yield moves out."""
    mod = _mod(
        """
        import asyncio

        async def stream_outputs(call, context):
            while True:
                timed_out = False
                async with call.output_condition:
                    try:
                        await asyncio.wait_for(call.output_condition.wait(), timeout=5.0)
                    except asyncio.TimeoutError:
                        timed_out = True
                if timed_out:
                    yield make_keepalive()
        """
    )
    assert run_pass("lock-across-await", [mod]) == []


def test_lock_across_await_catches_journal_group_bug():
    """PR 8 shipped bug #2 (minimized): journal.group() held across the
    per-item awaits — before groups became task-scoped this deferred every
    concurrent handler's flush to this handler's exit."""
    mod = _mod(
        """
        async def put_outputs(self, request):
            with self.journal.group():
                for item in request.items:
                    await self.apply(item)
        """
    )
    found = run_pass("lock-across-await", [mod])
    assert len(found) == 1
    assert "journal-group" in found[0].message


def test_lock_across_await_passes_corrected_journal_group():
    mod = _mod(
        """
        async def put_outputs(self, request):
            applied = [await self.apply(item) for item in request.items]
            with self.journal.group():
                for result in applied:
                    self.journal.append("output", result)
        """
    )
    assert run_pass("lock-across-await", [mod]) == []


def test_lock_across_await_catches_threading_lock_and_async_for():
    mod = _mod(
        """
        async def refresh(self):
            with self._cache_lock:
                await self._fetch()

        async def pump(self, stream):
            async with self._write_lock:
                async for chunk in stream:
                    self.buf.append(chunk)
        """
    )
    found = run_pass("lock-across-await", [mod])
    assert {f.scope for f in found} == {"refresh", "pump"}
    assert any("async for" in f.message for f in found)


def test_lock_across_await_ignores_sync_functions_and_nested_defs():
    mod = _mod(
        """
        def sync_path(self):
            with self._lock:
                self.counter += 1

        async def spawn(self):
            with self._lock:
                async def later():
                    await self.task()
                self.pending.append(later)
        """
    )
    assert run_pass("lock-across-await", [mod]) == []


def test_lock_across_await_inline_disable_suppresses(tmp_path):
    from modal_tpu.analysis.core import run_analysis

    src = textwrap.dedent(
        """
        async def single_flight(self):
            async with self._dial_lock:  # lint: disable=lock-across-await
                await self.dial()
        """
    )
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(src)
    res = run_analysis(
        src_root=str(pkg), rules=["lock-across-await"], baseline_path=str(tmp_path / "nope.json")
    )
    assert res.findings == []
    assert len(res.suppressed_inline) == 1


# ---------------------------------------------------------------------------
# Rule 2: blocking-in-async
# ---------------------------------------------------------------------------


def test_blocking_in_async_catches_sleep_and_subprocess():
    mod = _mod(
        """
        import time, asyncio, subprocess

        async def tick(self):
            time.sleep(0.1)
            await asyncio.sleep(0.1)
            subprocess.run(["ls"])

        def sync_tick():
            time.sleep(1.0)
        """
    )
    found = run_pass("blocking-in-async", [mod])
    assert {f.token for f in found} == {"time.sleep", "subprocess.run"}
    assert all(f.scope == "tick" for f in found)


def test_blocking_in_async_catches_unbounded_queue_get():
    """The dispatch-floor class: a sync queue.get with no timeout parks the
    whole event loop until a producer shows up."""
    mod = _mod(
        """
        async def drain(self, work_queue):
            item = work_queue.get()
            bounded = work_queue.get(timeout=1.0)
            awaited = await work_queue.get()
            scheduled = asyncio.ensure_future(work_queue.get())
            return item, bounded, awaited, scheduled
        """
    )
    found = run_pass("blocking-in-async", [mod])
    assert len(found) == 1
    assert "work_queue.get" in found[0].message
    assert found[0].line == 3


def test_blocking_in_async_file_io_only_on_hot_path_modules():
    src = """
    async def load(self, path):
        with open(path) as f:
            return f.read()
    """
    hot = _mod(src, relpath="server/services.py")
    cold = _mod(src, relpath="models/weights.py")
    assert len(run_pass("blocking-in-async", [hot])) == 1
    assert run_pass("blocking-in-async", [cold]) == []
    # offloaded to a thread = fine, even on the hot path
    fixed = _mod(
        """
        import asyncio

        async def load(self, path):
            f = await asyncio.to_thread(open, path)
            try:
                return await asyncio.to_thread(f.read)
            finally:
                await asyncio.to_thread(f.close)
        """,
        relpath="server/services.py",
    )
    assert run_pass("blocking-in-async", [fixed]) == []


# ---------------------------------------------------------------------------
# Rule 3: jit-purity
# ---------------------------------------------------------------------------


def test_jit_purity_catches_env_time_random_and_global():
    """Motivating class (PAPERS.md, AOT compilation): trace-time side
    effects bake into the executable — an env read in a jitted step is a
    CONSTANT by the time the prewarm cache serves it."""
    mod = _mod(
        """
        import os, time, random
        import jax

        @jax.jit
        def bad_env_step(x):
            scale = float(os.environ.get("SCALE", "1"))
            return x * scale

        def stamped(x):
            return x + time.time()

        stamped_jit = jax.jit(stamped)

        @jax.jit
        def seeded(x):
            random.seed(0)
            return x

        COUNTER = 0

        @jax.jit
        def counting(x):
            global COUNTER
            COUNTER += 1
            return x
        """,
        relpath="models/fixture.py",
    )
    found = run_pass("jit-purity", [mod])
    by_scope = {f.scope: f.token for f in found}
    assert "bad_env_step" in by_scope and by_scope["bad_env_step"].startswith("os.environ")
    assert by_scope.get("stamped") == "time.time"
    assert by_scope.get("seeded", "").startswith("random.")
    assert "counting" in by_scope and by_scope["counting"].startswith("global")


def test_jit_purity_passes_pure_and_jax_random():
    mod = _mod(
        """
        import jax
        from functools import partial

        @jax.jit
        def good_step(x, scale):
            return x * scale

        @partial(jax.jit, static_argnums=(1,))
        def bucketed(x, n):
            return x[:n]

        def sample(key, shape):
            return jax.random.normal(key, shape)

        sample_jit = jax.jit(sample)

        kernel_call = pallas_call(lambda ref, o: o.store(ref[...] * 2), out_shape=None)
        """,
        relpath="models/fixture.py",
    )
    assert run_pass("jit-purity", [mod]) == []


def test_jit_purity_catches_config_read_in_pallas_kernel():
    mod = _mod(
        """
        from ..config import config

        def kernel(q_ref, o_ref):
            if config["jax_platform"] == "cpu":
                o_ref[...] = q_ref[...]

        out = pallas_call(kernel, out_shape=None)
        """,
        relpath="ops/fixture.py",
    )
    found = run_pass("jit-purity", [mod])
    assert len(found) == 1 and found[0].token == "config"


# ---------------------------------------------------------------------------
# Rule: donation-audit (ISSUE 20 — carried state must be donated)
# ---------------------------------------------------------------------------


def test_donation_audit_catches_pre_audit_prefill_shape():
    """Pin the EXACT pre-audit bug: models/sampling.prefill threaded the KV
    cache through itself with no donate_argnames — two full caches live per
    prefill. The audit FIXED it (donate_argnames=("cache",)); this fixture
    is the pre-fix source shape and must stay a finding so the rule keeps
    guarding the fix."""
    mod = _mod(
        """
        from functools import partial
        import jax

        @partial(jax.jit, static_argnames=("cfg",))
        def prefill(params, cfg, prompt_tokens, cache):
            positions = jnp.arange(prompt_tokens.shape[1])[None, :]
            logits, cache = forward(params, cfg, prompt_tokens, cache, positions)
            return logits[:, -1, :], cache
        """,
        relpath="models/sampling.py",
    )
    found = run_pass("donation-audit", [mod])
    assert len(found) == 1
    assert found[0].scope == "prefill" and found[0].token == "cache"
    assert "donate" in found[0].message


def test_donation_audit_passes_fixed_prefill_and_replace_form():
    """The shipped (post-audit) shapes are clean: donate_argnames on the
    carried cache, and donate_argnums=(0,) on the ``_replace`` returners
    (the paged_kv table-maintenance steps)."""
    mod = _mod(
        """
        from functools import partial
        import jax

        @partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache",))
        def prefill(params, cfg, prompt_tokens, cache):
            logits, cache = forward(params, cfg, prompt_tokens, cache)
            return logits[:, -1, :], cache

        @partial(jax.jit, donate_argnums=(0,))
        def assign_pages(cache, slot, pages, length):
            return cache._replace(page_table=pages, seq_lens=length)
        """,
        relpath="models/sampling.py",
    )
    assert run_pass("donation-audit", [mod]) == []


def test_donation_audit_catches_undonated_replace_return():
    mod = _mod(
        """
        from functools import partial
        import jax

        @partial(jax.jit)
        def assign_pages(cache, slot, pages):
            return cache._replace(page_table=pages)
        """,
        relpath="models/paged_kv.py",
    )
    found = run_pass("donation-audit", [mod])
    assert len(found) == 1 and found[0].token == "cache"


def test_donation_audit_exempts_passthrough_and_static_args():
    """Returned-unmodified params are forwarded by XLA without a copy (no
    donation needed), and static args aren't buffers at all."""
    mod = _mod(
        """
        from functools import partial
        import jax

        @jax.jit
        def passthrough(x, y):
            z = x + y
            return x, z

        @partial(jax.jit, static_argnames=("cfg",))
        def uses_static(params, cfg, tokens):
            cfg = resolve(cfg)
            return cfg, params
        """,
        relpath="models/fixture.py",
    )
    assert run_pass("donation-audit", [mod]) == []


def test_donation_audit_catches_use_after_donate():
    """Reading a variable after passing it to a donating jit fn only blows
    up on donation-honoring backends (TPU), never in CPU tests — exactly the
    class of bug a static pass must catch."""
    mod = _mod(
        """
        from functools import partial
        import jax

        @partial(jax.jit, donate_argnames=("cache",))
        def step(params, tok, cache):
            cache = update(cache, tok)
            return logits_of(cache), cache

        def drive_bad(params, toks, cache):
            logits, new_cache = step(params, toks, cache)
            return cache.k.sum()  # donated buffer: deleted on TPU

        def drive_ok(params, toks, cache):
            logits, cache = step(params, toks, cache)
            return cache.k.sum()  # rebound by the call statement
        """,
        relpath="serving/fixture.py",
    )
    found = run_pass("donation-audit", [mod])
    assert len(found) == 1
    assert found[0].scope == "drive_bad" and found[0].token == "cache@step"
    assert "after being donated" in found[0].message


def test_donation_audit_inline_disable_suppresses(tmp_path):
    from modal_tpu.analysis.core import run_analysis

    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(
        "from functools import partial\n"
        "import jax\n"
        "\n"
        "@partial(jax.jit)  # lint: disable=donation-audit\n"
        "def roll(state, x):\n"
        "    state = state + x\n"
        "    return state\n"
    )
    res = run_analysis(
        src_root=str(pkg), rules=["donation-audit"], baseline_path=str(tmp_path / "b.json")
    )
    assert res.findings == [] and len(res.suppressed_inline) == 1


# ---------------------------------------------------------------------------
# Rules 4+5: knob-parity / degradation-symmetry (synthetic catalog fixtures)
# ---------------------------------------------------------------------------


def _knob(name, gate=False):
    from modal_tpu.analysis.knob_catalog import Knob

    return Knob(name, "bool", "1", "docs/STATUS.md", "fixture", gate, False)


def test_knob_parity_flags_undeclared_and_dead_knobs():
    from modal_tpu.analysis.knobs import knob_parity_findings

    mod = _mod(
        """
        import os
        FLAG = os.environ.get("MODAL_TPU_FAKE_KNOB", "1")
        PREFIX_FRAGMENT = "MODAL_TPU_TRACE_"  # startswith() helper, not a knob
        """,
        relpath="server/fixture.py",
    )
    catalog = {"MODAL_TPU_DEAD_KNOB": _knob("MODAL_TPU_DEAD_KNOB")}
    found = knob_parity_findings([mod], catalog=catalog, declared=dict(catalog))
    tokens = {f.token for f in found}
    assert tokens == {"MODAL_TPU_FAKE_KNOB", "MODAL_TPU_DEAD_KNOB"}
    undeclared = next(f for f in found if f.token == "MODAL_TPU_FAKE_KNOB")
    assert undeclared.path == "server/fixture.py" and undeclared.line == 3
    dead = next(f for f in found if f.token == "MODAL_TPU_DEAD_KNOB")
    assert "dead" in dead.message


def test_degradation_symmetry_requires_off_toggle_test(tmp_path):
    from modal_tpu.analysis.knobs import degradation_findings

    gates = {"MODAL_TPU_FAKE_GATE": _knob("MODAL_TPU_FAKE_GATE", gate=True)}
    tests = tmp_path / "tests"
    tests.mkdir()
    (tests / "test_other.py").write_text('monkeypatch.setenv("MODAL_TPU_FAKE_GATE", "1")\n')
    found = degradation_findings([], str(tests), gates=gates)
    assert len(found) == 1 and found[0].token == "MODAL_TPU_FAKE_GATE"
    # an off-toggle line anywhere under tests/ satisfies the contract
    (tests / "test_degrade.py").write_text('monkeypatch.setenv("MODAL_TPU_FAKE_GATE", "0")\n')
    assert degradation_findings([], str(tests), gates=gates) == []


# ---------------------------------------------------------------------------
# Baseline mechanics
# ---------------------------------------------------------------------------


def test_baseline_roundtrip_and_reason_required(tmp_path):
    from modal_tpu.analysis.core import load_baseline, save_baseline

    path = str(tmp_path / "baseline.json")
    save_baseline({"rule:path:scope:token": "intentional: fixture"}, path)
    assert load_baseline(path) == {"rule:path:scope:token": "intentional: fixture"}
    with open(path, "w") as f:
        json.dump({"entries": {"k": ""}}, f)
    with pytest.raises(ValueError, match="justification"):
        load_baseline(path)
    assert load_baseline(str(tmp_path / "missing.json")) == {}


def test_baseline_suppresses_by_key_not_line(tmp_path):
    from modal_tpu.analysis.core import run_analysis, save_baseline

    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(
        "import time\n\nasync def tick():\n    time.sleep(1)\n"
    )
    bp = str(tmp_path / "baseline.json")
    res = run_analysis(src_root=str(pkg), rules=["blocking-in-async"], baseline_path=bp)
    assert len(res.findings) == 1
    save_baseline({res.findings[0].key: "fixture: intentional"}, bp)
    # shift the finding by two lines: the key (no line numbers) still matches
    (pkg / "mod.py").write_text(
        "import time\n# pad\n# pad\n\nasync def tick():\n    time.sleep(1)\n"
    )
    res2 = run_analysis(src_root=str(pkg), rules=["blocking-in-async"], baseline_path=bp)
    assert res2.findings == [] and len(res2.suppressed_baseline) == 1
    assert res2.stale_baseline_keys == []


# ---------------------------------------------------------------------------
# The tier-1 gate: the suite runs CLEAN over modal_tpu/ (ISSUE 15 acceptance)
# ---------------------------------------------------------------------------


def test_lint_clean_over_modal_tpu():
    """Zero unsuppressed findings over the real tree — every violation the
    passes surface is either fixed or carries an explicit justification
    (inline disable or baseline entry). This is the CI gate."""
    from modal_tpu.analysis import run_analysis

    res = run_analysis()
    assert res.modules_scanned > 100  # the walker actually walked the tree
    formatted = "\n".join(f"{f.path}:{f.line} [{f.rule}] {f.message}" for f in res.findings)
    assert not res.findings, f"unsuppressed static-analysis findings:\n{formatted}"
    # stale baseline entries hide shrinkage progress — prune them when seen
    assert not res.stale_baseline_keys, res.stale_baseline_keys
    # suppressions exist and stayed justified (load_baseline enforces reasons)
    assert len(res.baseline) >= 1


def test_knob_catalog_is_in_lockstep_with_the_tree():
    """Acceptance: every literal MODAL_TPU_* knob in modal_tpu/ is cataloged
    (type/default/doc) and every cataloged knob is live — the knob-parity
    pass being green is re-derived here from first principles so a broken
    pass can't silently pass the gate."""
    from modal_tpu.analysis.core import load_modules
    from modal_tpu.analysis.knob_catalog import KNOB_CATALOG, declared_knobs, feature_gates
    from modal_tpu.analysis.knobs import collect_knob_literals

    modules = load_modules()
    literals = set(collect_knob_literals(modules))
    assert len(literals) >= 90, f"knob inventory shrank suspiciously: {len(literals)}"
    assert literals == set(KNOB_CATALOG), (
        f"undeclared: {sorted(literals - set(KNOB_CATALOG))}; "
        f"dead: {sorted(set(KNOB_CATALOG) - literals)}"
    )
    for knob in declared_knobs().values():
        assert knob.type and isinstance(knob.default, str) and knob.doc.startswith("docs/"), knob
        assert knob.description, knob
    assert len(feature_gates()) >= 10  # the degradation matrix is cataloged


def test_excluded_files_are_not_walked(tmp_path):
    """Satellite bugfix: the shared walker skips __pycache__ and generated
    proto/api_pb2.py — the exclusion the three pre-framework parity walks
    each re-implemented (or forgot)."""
    from modal_tpu.analysis.core import iter_source_files

    pkg = tmp_path / "pkg"
    (pkg / "proto").mkdir(parents=True)
    (pkg / "__pycache__").mkdir()
    (pkg / "ok.py").write_text("x = 1\n")
    (pkg / "proto" / "api_pb2.py").write_text("x = 1\n")
    (pkg / "proto" / "rpc.py").write_text("x = 1\n")
    (pkg / "__pycache__" / "junk.py").write_text("x = 1\n")
    rels = [rel for _, rel in iter_source_files(str(pkg))]
    assert rels == ["ok.py", "proto/rpc.py"]
    # and the real walk never yields either exclusion
    real = [rel for _, rel in iter_source_files()]
    assert "proto/api_pb2.py" not in real
    assert not any("__pycache__" in r for r in real)


def test_docs_knob_table_is_generated_from_catalog():
    """docs/ANALYSIS.md's knob table is generated from knob_catalog.py —
    regenerate and compare, so the docs can't drift from the code."""
    import os

    from modal_tpu.analysis.core import repo_root
    from modal_tpu.analysis.knob_catalog import knob_table_markdown

    text = open(os.path.join(repo_root(), "docs", "ANALYSIS.md")).read()
    begin = text.index("knob-table:begin")
    begin = text.index("\n", begin) + 1
    end = text.index("<!-- knob-table:end -->")
    assert text[begin:end].strip() == knob_table_markdown().strip(), (
        "docs/ANALYSIS.md knob table is stale — regenerate it from "
        "knob_catalog.knob_table_markdown()"
    )


# ---------------------------------------------------------------------------
# CLI: `modal_tpu lint` — JSON shape pinned (bench.py parses it)
# ---------------------------------------------------------------------------


def test_lint_cli_json_shape():
    from click.testing import CliRunner

    from modal_tpu.cli.entry_point import cli

    result = CliRunner().invoke(cli, ["lint", "--json"], catch_exceptions=False)
    assert result.exit_code == 0, result.output
    payload = json.loads(result.output)
    assert payload["version"] == 1
    assert payload["rules"] == [
        "lock-across-await",
        "blocking-in-async",
        "donation-audit",
        "jit-purity",
        "knob-parity",
        "degradation-symmetry",
    ]
    assert payload["findings"] == []
    counts = payload["counts"]
    assert set(counts) == {
        "total", "by_rule", "suppressed_inline", "suppressed_baseline", "baseline_stale",
    }
    assert counts["total"] == 0
    assert counts["suppressed_inline"] >= 1  # the justified-at-site holds
    assert isinstance(payload["baseline_size"], int) and payload["baseline_size"] >= 1
    assert payload["stale_baseline_keys"] == []
    assert payload["modules_scanned"] > 100


def test_lint_cli_rule_filter_and_unknown_rule():
    from click.testing import CliRunner

    from modal_tpu.cli.entry_point import cli

    runner = CliRunner()
    result = runner.invoke(cli, ["lint", "--json", "--rule", "knob-parity"], catch_exceptions=False)
    assert result.exit_code == 0, result.output
    assert json.loads(result.output)["rules"] == ["knob-parity"]
    bad = runner.invoke(cli, ["lint", "--rule", "no-such-rule"])
    assert bad.exit_code != 0
    assert "unknown rule" in bad.output


def test_lint_cli_nonzero_exit_and_update_baseline(tmp_path, monkeypatch):
    """A tree with a finding exits 1; --update-baseline writes the TODO
    entry and a rerun is clean."""
    from click.testing import CliRunner

    from modal_tpu.analysis import core as analysis_core
    from modal_tpu.cli.entry_point import cli

    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text("import time\n\nasync def tick():\n    time.sleep(1)\n")
    bp = str(tmp_path / "baseline.json")
    monkeypatch.setattr(analysis_core, "default_baseline_path", lambda: bp)
    runner = CliRunner()
    dirty = runner.invoke(cli, ["lint", "--src-root", str(pkg)])
    assert dirty.exit_code == 1
    assert "[blocking-in-async]" in dirty.output
    updated = runner.invoke(cli, ["lint", "--src-root", str(pkg), "--update-baseline"])
    assert updated.exit_code == 0, updated.output
    assert "baseline rewritten" in updated.output
    clean = runner.invoke(cli, ["lint", "--src-root", str(pkg), "--json"])
    assert clean.exit_code == 0, clean.output
    payload = json.loads(clean.output)
    assert payload["counts"]["suppressed_baseline"] == 1


# ---------------------------------------------------------------------------
# Degradation-symmetry backfill: off-path tests for the cataloged gates that
# had none (the grep-able lines below are exactly what the pass requires)
# ---------------------------------------------------------------------------


def test_fastpath_uds_rung_degrades_off(monkeypatch):
    from modal_tpu._utils import local_transport

    monkeypatch.delenv("MODAL_TPU_FASTPATH", raising=False)
    monkeypatch.setenv("MODAL_TPU_FASTPATH_UDS", "0")
    assert not local_transport.uds_enabled()
    monkeypatch.delenv("MODAL_TPU_FASTPATH_UDS", raising=False)
    assert local_transport.uds_enabled()


def test_circuit_breaker_degrades_off(monkeypatch):
    from types import SimpleNamespace

    from modal_tpu._utils.grpc_utils import _breaker_for

    fn = SimpleNamespace(_method=b"/modal.test/Probe", _breaker_scope="t")
    monkeypatch.setenv("MODAL_TPU_CIRCUIT_BREAKER", "0")
    assert _breaker_for(fn) is None
    monkeypatch.delenv("MODAL_TPU_CIRCUIT_BREAKER", raising=False)
    assert _breaker_for(fn) is not None


def test_journaling_degrades_off(monkeypatch):
    from modal_tpu.server.supervisor import _journal_enabled

    monkeypatch.setenv("MODAL_TPU_JOURNAL", "0")
    assert not _journal_enabled()
    monkeypatch.delenv("MODAL_TPU_JOURNAL", raising=False)
    assert _journal_enabled()


def test_tracing_degrades_off(monkeypatch):
    from modal_tpu.config import config

    monkeypatch.setenv("MODAL_TPU_TRACE", "0")
    assert config.get("trace") is False
    monkeypatch.delenv("MODAL_TPU_TRACE", raising=False)
    assert config.get("trace") is True


def test_timeseries_sampler_degrades_off(monkeypatch):
    from modal_tpu.observability import timeseries

    monkeypatch.setenv("MODAL_TPU_TS_INTERVAL", "0")
    assert not timeseries.sampling_enabled()
    monkeypatch.delenv("MODAL_TPU_TS_INTERVAL", raising=False)
    assert timeseries.sampling_enabled()


def test_serving_sampling_spec_prefix_degrade_off(monkeypatch):
    from modal_tpu.serving import engine

    monkeypatch.setenv("MODAL_TPU_SERVING_SAMPLING", "0")
    assert not engine._env_on(engine.SAMPLING_ENV)
    monkeypatch.setenv("MODAL_TPU_SERVING_SPEC", "0")
    assert not engine._env_on(engine.SPEC_ENV)
    monkeypatch.setenv("MODAL_TPU_SERVING_PREFIX_CACHE", "0")
    assert not engine._env_on(engine.PREFIX_CACHE_ENV)
    for knob in ("MODAL_TPU_SERVING_SAMPLING", "MODAL_TPU_SERVING_SPEC", "MODAL_TPU_SERVING_PREFIX_CACHE"):
        monkeypatch.delenv(knob, raising=False)
    assert engine._env_on(engine.SAMPLING_ENV)
    assert engine._env_on(engine.SPEC_ENV)
    assert engine._env_on(engine.PREFIX_CACHE_ENV)


def test_paged_kernel_degrades_to_gather(monkeypatch):
    """The platform alone chooses: the kernel on a TPU, the gather path
    everywhere else; no environment value reaches the choice."""
    import jax

    from modal_tpu.models.paged_kv import resolve_attn_impl

    assert jax.default_backend() != "tpu" and resolve_attn_impl() == "gather"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_attn_impl() == "kernel"
