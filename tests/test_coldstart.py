"""Warm-pool cold starts (server/warm_pool.py, docs/COLDSTART.md):
pre-forked parked interpreters, placement handoff without re-exec,
compile-cache prewarm at image-build time, chaos fallback, drain."""

import os
import sys
import time

import pytest


@pytest.fixture
def pool_supervisor(tmp_path, monkeypatch):
    """conftest.supervisor with a baseline warm pool of ONE parked
    interpreter (MODAL_TPU_WARM_POOL=1 must be set before worker start)."""
    from modal_tpu._utils.async_utils import synchronizer
    from modal_tpu.chaos import ChaosPolicy
    from modal_tpu.client import _Client
    from modal_tpu.server.supervisor import LocalSupervisor

    monkeypatch.setenv("MODAL_TPU_STATE_DIR", str(tmp_path / "state"))
    monkeypatch.setenv("MODAL_TPU_WARM_POOL", "1")
    sup = LocalSupervisor(
        num_workers=1,
        state_dir=str(tmp_path / "state"),
        worker_chips=8,
        worker_tpu_type="local-sim",
        chaos=ChaosPolicy(seed=0),
    )
    synchronizer.run(sup.start())
    monkeypatch.setenv("MODAL_TPU_SERVER_URL", f"grpc://127.0.0.1:{sup.port}")
    _Client.set_env_client(None)
    try:
        yield sup
    finally:
        env_client = _Client._client_from_env
        if env_client is not None and not env_client._closed:
            env_client._close()
        _Client.set_env_client(None)
        synchronizer.run(sup.stop())


def _wait_parked(sup, n=1, timeout=90.0) -> bool:
    from modal_tpu._utils.async_utils import synchronizer

    return synchronizer.run(sup.workers[0].pool.wait_parked(n, timeout))


def test_handoff_without_reexec_same_pid(pool_supervisor):
    """The core contract: two successive placements are served by the SAME
    pre-forked interpreter process — no re-exec, no re-import — and both
    are stamped warm_pool_hit on the server-side timeline."""
    import modal_tpu

    sup = pool_supervisor
    assert _wait_parked(sup), "warm pool never parked an interpreter"
    pool_pid = next(iter(sup.workers[0].pool.entries.values())).proc.pid

    app = modal_tpu.App("coldstart-pid")

    @app.function(serialized=True)
    def whoami(x):
        import os

        return (os.getpid(), x * 2)

    with app.run():
        fc = whoami.spawn(21)
        pid1, v1 = fc.get(timeout=60)
        tl = fc.get_timeline()
    assert v1 == 42
    assert pid1 == pool_pid, "placement was not served by the parked interpreter"
    assert tl.tasks and tl.tasks[0].warm_pool_hit, "timeline must prove the warm path"

    # the interpreter re-parks after the app stops; the next placement gets
    # the same process (restore-state handoff without re-exec)
    assert _wait_parked(sup), "interpreter did not re-park after the first app"
    with app.run():
        pid2, v2 = whoami.remote(4)
    assert v2 == 8
    assert pid2 == pid1, "second placement must reuse the same interpreter PID"
    hits = [t.warm_pool_hit for t in sup.state.tasks.values()]
    assert hits.count(True) >= 2


def test_warm_pool_place_evict_size_lifecycle(pool_supervisor):
    """Pool sizing converges to directives: grow on a directive, evict on
    target shrink, evict all on image-change (target 0 leaves baseline)."""
    import asyncio

    from modal_tpu._utils.async_utils import synchronizer

    sup = pool_supervisor
    pool = sup.workers[0].pool
    assert _wait_parked(sup, 1)

    async def _directive(image_id, target):
        pool.set_directive(image_id, target)

    # grow the host-venv pool to 2 via a directive for a trivial image: ""
    synchronizer.run(_directive("", 2))
    assert synchronizer.run(pool.wait_parked(2, 90.0)), "pool did not grow to directive target"
    assert pool.ready_count() >= 2

    # shrink back: the surplus (newest) parked interpreter is evicted
    synchronizer.run(_directive("", 0))

    async def _wait_shrunk():
        for _ in range(200):
            if pool.ready_count() <= 1 and len(pool.entries) <= 1:
                return True
            await asyncio.sleep(0.1)
        return False

    assert synchronizer.run(_wait_shrunk()), (
        f"pool did not shrink: ready={pool.ready_count()} entries={len(pool.entries)}"
    )
    # baseline survives the directive removal
    assert pool.ready_count() == 1


def test_scheduler_directive_preforks_for_buffer_containers(supervisor):
    """min_containers/buffer_containers keep BOOTED interpreters parked via
    scheduler PoolDirectives (no baseline env pool here), and stopping the
    app evicts them (image no longer scheduled)."""
    import asyncio

    import modal_tpu
    from modal_tpu._utils.async_utils import synchronizer

    sup = supervisor
    pool = sup.workers[0].pool
    assert pool.ready_count() == 0  # no baseline pool in this fixture

    app = modal_tpu.App("coldstart-directive")

    @app.function(serialized=True, buffer_containers=1)
    def noop(x):
        return x

    with app.run():
        assert synchronizer.run(pool.wait_parked(1, 90.0)), (
            "scheduler directive did not pre-fork a parked interpreter"
        )
        assert noop.remote(3) == 3

    # app stopped -> directive withdrawn -> parked interpreters evicted
    async def _wait_drained():
        for _ in range(300):
            if pool.ready_count() == 0 and not pool.directives:
                return True
            await asyncio.sleep(0.1)
        return False

    assert synchronizer.run(_wait_drained()), "directive pool not evicted after app stop"


def test_chaos_kill_mid_handoff_falls_back_to_fresh_spawn(pool_supervisor):
    """A parked interpreter killed between handoff delivery and ack must not
    lose the placement: the worker falls back to a fresh spawn and the call
    still succeeds (just cold)."""
    import modal_tpu

    sup = pool_supervisor
    assert _wait_parked(sup)
    sup.chaos.set_knob("warm_kill_handoff", 1)

    app = modal_tpu.App("coldstart-chaos")

    @app.function(serialized=True)
    def double(x):
        import os

        return (os.getpid(), x * 2)

    with app.run():
        pid, v = double.remote(5)
    assert v == 10
    assert sup.chaos.get_knob("warm_kill_handoff") == 0, "chaos knob was not consumed"
    # the serving task must NOT be a warm hit (the warm interpreter died)
    assert not any(t.warm_pool_hit for t in sup.state.tasks.values())
    # and the fallback was recorded
    from modal_tpu.observability.catalog import WARM_POOL_PLACEMENTS

    assert WARM_POOL_PLACEMENTS.value(outcome="handoff_failed") >= 1


def test_warm_pool_drains_under_preemption(pool_supervisor):
    """Preemption notice: parked interpreters hold no work and must exit
    inside the grace window, not linger as orphans of a dying host."""
    import asyncio

    from modal_tpu._utils.async_utils import synchronizer

    sup = pool_supervisor
    assert _wait_parked(sup)
    entry = next(iter(sup.workers[0].pool.entries.values()))
    synchronizer.run(sup.workers[0].preempt(grace_s=2.0))

    async def _wait_exit():
        for _ in range(150):
            if entry.proc.returncode is not None and not sup.workers[0].pool.entries:
                return True
            await asyncio.sleep(0.1)
        return False

    assert synchronizer.run(_wait_exit()), "parked interpreter survived the drain"
    assert sup.workers[0].pool.ready_count() == 0


def test_snapshot_restore_without_reexec(pool_supervisor, tmp_path):
    """Warm-state snapshot restore from an already-imported interpreter: the
    snap-enter hook runs once, the second boot restores in the SAME process
    (handoff), and both cold paths go through the warm pool."""
    import modal_tpu

    sup = pool_supervisor
    assert _wait_parked(sup)
    marker = str(tmp_path / "enter_count.txt")

    app = modal_tpu.App("coldstart-snap")

    @app.cls(serialized=True, enable_memory_snapshot=True)
    class Model:
        @modal_tpu.enter(snap=True)
        def load(self):
            import jax.numpy as jnp

            with open(marker, "a") as f:
                f.write("x")
            self.w = jnp.arange(8.0)

        @modal_tpu.method()
        def total(self, k):
            import os

            return (os.getpid(), float(self.w.sum()) * k)

    with app.run():
        pid1, v1 = Model().total.remote(2)
    assert v1 == 28.0 * 2
    assert os.path.getsize(marker) == 1
    assert _wait_parked(sup), "interpreter did not re-park after snapshot save"
    with app.run():
        pid2, v2 = Model().total.remote(3)
    assert v2 == 28.0 * 3
    assert os.path.getsize(marker) == 1, "restore boot must skip the snap-enter hook"
    assert pid2 == pid1, "restore must run in the SAME interpreter (no re-exec)"


def test_compile_cache_prewarm_bakes_and_hits(supervisor, monkeypatch, tmp_path):
    """Image.prewarm(fn) compiles the fn's jit entry points at BUILD time
    into the one persistent cache containers read — here the directory the
    program's own JAX_COMPILATION_CACHE_DIR names — and the container's
    first call hits it (no new entries written)."""
    outer = str(tmp_path / "outer_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outer)

    import modal_tpu

    def warm():
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            return (x * 2.0 + 1.0).sum()

        f(jnp.ones((64, 64))).block_until_ready()

    app = modal_tpu.App("coldstart-prewarm")
    image = modal_tpu.Image.debian_slim().prewarm(warm)

    @app.function(serialized=True, image=image)
    def compute(n):
        import glob
        import os

        import jax
        import jax.numpy as jnp

        cache = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
        before = len(glob.glob(os.path.join(cache, "*")))

        @jax.jit
        def f(x):
            return (x * 2.0 + 1.0).sum()

        v = float(f(jnp.ones((64, 64))).block_until_ready())
        after = len(glob.glob(os.path.join(cache, "*")))
        return {"cache": cache, "before": before, "after": after, "v": v}

    with app.run():
        r = compute.remote(1)
    assert r["v"] == 64 * 64 * 3.0
    assert r["cache"] == outer, f"a prewarm image moved the cache dir: {r}"
    assert r["before"] > 0, "prewarm baked no compilation-cache entries at build time"
    assert r["after"] == r["before"], "first container call must HIT the baked cache"


def test_retry_queue_single_drainer_batches(supervisor, tmp_path, monkeypatch):
    """Satellite (VERDICT r5 weak #3): retried map inputs ride ONE
    timestamp-heap drainer (batched FunctionRetryInputs) — not one asyncio
    timer task per retried input. The drainer serializes re-submissions, so
    spy invocations never overlap; every failed input is re-submitted
    exactly once and the map completes."""
    import modal_tpu
    from modal_tpu import parallel_map as pm

    calls = []
    active = {"now": 0, "max": 0}
    for cls in (pm._ControlPlaneMapTransport, pm._InputPlaneMapTransport):
        orig = cls.retry_inputs

        def make_spy(orig=orig):
            async def spy(self, call_id, entries):
                active["now"] += 1
                active["max"] = max(active["max"], active["now"])
                try:
                    calls.append(len(entries))
                    return await orig(self, call_id, entries)
                finally:
                    active["now"] -= 1

            return spy

        monkeypatch.setattr(cls, "retry_inputs", make_spy())

    app = modal_tpu.App("retry-heap")
    attempts_dir = str(tmp_path / "attempts")
    os.makedirs(attempts_dir)

    def flaky(x):
        marker = os.path.join(attempts_dir, str(x))
        with open(marker, "a") as f:
            f.write("x")
        if os.path.getsize(marker) == 1:
            raise ValueError(f"transient {x}")
        return x + 100

    flaky = modal_tpu.concurrent(max_inputs=30)(flaky)
    f = app.function(
        serialized=True,
        retries=modal_tpu.Retries(max_retries=2, initial_delay=1.0),
    )(flaky)
    n = 30
    with app.run():
        results = list(f.map(range(n)))
    assert sorted(results) == [x + 100 for x in range(n)]
    assert sum(calls) == n, f"every failed input retried exactly once: {calls}"
    # ONE drainer: re-submissions never overlap (the old shape ran one timer
    # task per retried input, all firing concurrently)
    assert active["max"] == 1, f"retry re-submissions overlapped ({active['max']} concurrent)"


def test_pipeline_moe_rejected_at_mesh_build_time():
    """Satellite (VERDICT r5 weak #7): pipe × MoE fails when the mesh/state
    is BUILT, with a documented constraint error — not mid-run inside the
    jitted loss."""
    from modal_tpu.models.llama import get_config
    from modal_tpu.parallel import MeshConstraintError, build_mesh, validate_mesh_constraints

    cfg = get_config("tiny-moe")
    with pytest.raises(MeshConstraintError, match="expert parallelism"):
        build_mesh({"pipe": 2}, model_cfg=cfg)
    with pytest.raises(MeshConstraintError):
        validate_mesh_constraints({"pipe": 2, "expert": 2})
    # dense config with pipe stays legal; moe without pipe stays legal
    build_mesh({"pipe": 2}, model_cfg=get_config("tiny"))
    build_mesh({"expert": 2}, model_cfg=cfg)
