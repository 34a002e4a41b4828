"""Cold-start microbench: warm-pool handoff vs fresh spawn, snap A/B.

Measures the north-star metric (cold-start-to-first-step) through the REAL
stack — scheduler placement → worker → interpreter → first output — with
server-stamped timestamps (TaskGetTimeline), in three configurations:

1. fresh spawn (warm pool off): exec container_entrypoint per placement
2. warm-pool handoff: placement adopted by a pre-forked parked interpreter
3. snapshot A/B on the warm-pool path: fresh @enter(snap=True) vs
   warm-state restore (runtime/snapshot.py) — both without process re-exec

Prints ONE line: COLDSTART_BENCH_RESULT {json}. bench.py folds the fields
into the round result as coldstart_*. The warm_pool_hit field is the
acceptance proof that the measured path went through a parked interpreter.

Run directly: JAX_PLATFORMS=cpu python tools/bench_coldstart.py
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)


def _make_app(tag: str):
    import modal_tpu

    app = modal_tpu.App(f"coldstart-bench-{tag}")

    @app.function(serialized=True, timeout=120)
    def first_step(x: int) -> int:
        # representative first step: import jax (free on the warm path — the
        # parked interpreter pre-imported it) and run one jitted computation
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(v):
            return (v * 2.0 + 1.0).sum()

        return float(f(jnp.ones((256, 256)) * x).block_until_ready())

    return app, first_step


def _make_snap_app():
    import modal_tpu

    app = modal_tpu.App("coldstart-bench-snap")

    @app.cls(serialized=True, enable_memory_snapshot=True, timeout=120)
    class SnapModel:
        @modal_tpu.enter(snap=True)
        def load(self):
            import jax
            import jax.numpy as jnp

            # the expensive enter: init + one jit (what restore skips)
            key = jax.random.PRNGKey(0)
            self.w = jax.random.normal(key, (512, 512))
            self.b = jnp.ones((512,))
            (self.w @ self.b).block_until_ready()

        @modal_tpu.method()
        def step(self) -> float:
            import jax.numpy as jnp

            return float(jnp.tanh(self.w @ self.b).sum())

    return app, SnapModel


def _timed_call(app, fn, *args) -> tuple[float, bool]:
    """(server-stamped cold_start_to_first_step_s, warm_pool_hit)."""
    with app.run():
        fc = fn.spawn(*args)
        fc.get(timeout=120)
        tl = fc.get_timeline()
    t0 = tl.tasks[0]
    return t0.first_output_at - t0.created_at, t0.warm_pool_hit


def _timed_snap_call(app, snap_model) -> tuple[float, bool]:
    with app.run():
        obj = snap_model()
        fc = obj.step.spawn()
        fc.get(timeout=120)
        tl = fc.get_timeline()
    t0 = tl.tasks[0]
    return t0.first_output_at - t0.created_at, t0.warm_pool_hit


def _boot_supervisor(warm_pool: int):
    from modal_tpu._utils.async_utils import synchronizer
    from modal_tpu.client import _Client
    from modal_tpu.server.supervisor import LocalSupervisor

    state_dir = tempfile.mkdtemp(prefix="coldstart_bench_")
    os.environ["MODAL_TPU_STATE_DIR"] = state_dir
    os.environ["MODAL_TPU_WARM_POOL"] = str(warm_pool)
    sup = LocalSupervisor(
        num_workers=1, state_dir=state_dir, worker_chips=8, worker_tpu_type="local-sim"
    )
    synchronizer.run(sup.start())
    os.environ["MODAL_TPU_SERVER_URL"] = sup.server_url
    _Client.set_env_client(None)
    return sup, synchronizer


def main() -> None:
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    # a CPU micro-bench of host overhead, whatever the environment says: an
    # outer JAX_PLATFORMS=tpu must not turn it into something that looks
    # like a chip run (its numbers are not device metrics)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["MODAL_TPU_JAX_PLATFORM"] = "cpu"
    os.environ["MODAL_TPU_AUTO_LOCAL_SERVER"] = "0"
    result: dict = {}

    # --- 1. fresh-spawn baseline (pool off) --------------------------------
    sup, synchronizer = _boot_supervisor(warm_pool=0)
    app, first_step = _make_app("fresh")
    cold_fresh, hit = _timed_call(app, first_step, 3)
    assert not hit, "pool-off run must not report a warm hit"
    result["cold_start_fresh_spawn_s"] = round(cold_fresh, 3)
    synchronizer.run(sup.stop())

    # --- 2. warm-pool handoff ----------------------------------------------
    sup, synchronizer = _boot_supervisor(warm_pool=1)
    pool = sup.workers[0].pool
    assert synchronizer.run(pool.wait_parked(1, 120.0)), "warm pool never parked"
    app, first_step = _make_app("warm")
    cold_warm, hit = _timed_call(app, first_step, 3)
    result["cold_start_warm_pool_s"] = round(cold_warm, 3)
    result["warm_pool_hit"] = bool(hit)
    if cold_warm > 0:
        result["warm_pool_speedup"] = round(cold_fresh / cold_warm, 2)

    # --- 3. snapshot A/B on the warm path ----------------------------------
    synchronizer.run(pool.wait_parked(1, 60.0))
    snap_app, snap_model = _make_snap_app()
    fresh_enter, hit_a = _timed_snap_call(snap_app, snap_model)
    synchronizer.run(pool.wait_parked(1, 60.0))
    restore, hit_b = _timed_snap_call(snap_app, snap_model)
    result["cold_start_fresh_enter_s"] = round(fresh_enter, 3)
    result["cold_start_snap_restore_s"] = round(restore, 3)
    result["snap_warm_pool_hit"] = bool(hit_a and hit_b)
    if restore > 0:
        result["snap_restore_speedup"] = round(fresh_enter / restore, 2)
    from modal_tpu.observability.catalog import WARM_POOL_PLACEMENTS

    result["warm_pool_hits_total"] = int(WARM_POOL_PLACEMENTS.value(outcome="hit"))
    synchronizer.run(sup.stop())

    print("COLDSTART_BENCH_RESULT " + json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
