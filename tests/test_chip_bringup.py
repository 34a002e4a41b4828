"""What the program must get right to run on a real chip, checked on the CPU.

The chip itself is exercised by `chip_smoke.py` (through the chip tool) and
the TPU-gated tests in test_ops.py; these tests pin the decisions around it:
which device a container may use, where the compile cache lives, that a
failed chip probe is loud, that the smoke cannot pass silently on the CPU,
and that the kernels still LOWER for a TPU (Mosaic's lowering rules run
without one).
"""

from __future__ import annotations

import asyncio
import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- (b) the env a container is launched with ---------------------------------


def _device_env(chip_ids, tpu_type="", world_size=1, jax_platform="", inherited=None):
    from modal_tpu.server.worker import device_env

    env = dict(inherited or {"JAX_PLATFORMS": "tpu,cpu", "TPU_CHIPS_PER_HOST_BOUNDS": "2,2,1"})
    device_env(env, chip_ids, tpu_type, world_size, jax_platform)
    return env


def test_device_env_one_chip_is_pinned_and_cannot_fall_back():
    env = _device_env([2], "v5e-1")
    # tpu alone: a chip another process holds is a start-up error, not a CPU run
    assert env["JAX_PLATFORMS"] == "tpu"
    assert env["TPU_VISIBLE_DEVICES"] == "2"
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    # without this, concurrent one-chip processes fight over libtpu's lockfile
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"


def test_device_env_four_chips_in_one_process():
    env = _device_env([0, 1, 2, 3], "v5e-4")
    assert env["JAX_PLATFORMS"] == "tpu"
    assert env["TPU_VISIBLE_DEVICES"] == "0,1,2,3"
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "2,2,1"


def test_device_env_no_chips_cannot_take_one():
    env = _device_env([])
    assert env["JAX_PLATFORMS"] == "cpu"
    assert "TPU_VISIBLE_DEVICES" not in env


def test_device_env_forced_cpu_simulates_the_slice():
    env = _device_env([0, 1, 2, 3], "v5e-4", jax_platform="cpu", inherited={"XLA_FLAGS": "--foo --xla_force_host_platform_device_count=8"})
    assert env["JAX_PLATFORMS"] == "cpu" and "TPU_VISIBLE_DEVICES" not in env
    assert env["XLA_FLAGS"] == "--foo --xla_force_host_platform_device_count=4"
    assert _device_env([], jax_platform="cpu")["JAX_PLATFORMS"] == "cpu"


def test_chip_bounds_layouts():
    from modal_tpu.server.worker import chip_bounds

    assert [chip_bounds(n) for n in (1, 2, 4)] == ["1,1,1", "2,1,1", "2,2,1"]
    with pytest.raises(ValueError):
        chip_bounds(3)


# -- (c) the chip probe ---------------------------------------------------------


def _unforced(monkeypatch):
    for var in ("JAX_PLATFORMS", "MODAL_TPU_JAX_PLATFORM", "MODAL_TPU_WORKER_TPU_TYPE"):
        monkeypatch.delenv(var, raising=False)


def _fake_probe(monkeypatch, returncode=0, stdout="", stderr="", raises=None):
    def run(*args, **kwargs):
        if raises is not None:
            raise raises
        return types.SimpleNamespace(returncode=returncode, stdout=stdout, stderr=stderr)

    monkeypatch.setattr(subprocess, "run", run)


def test_probe_failure_on_an_unforced_host_raises(monkeypatch):
    from modal_tpu.server.worker import TpuProbeError, detect_tpu_inventory

    _unforced(monkeypatch)
    _fake_probe(monkeypatch, returncode=1, stderr="RuntimeError: The TPU is already in use by process with pid 1357")
    with pytest.raises(TpuProbeError, match="pid 1357"):
        detect_tpu_inventory()
    _fake_probe(monkeypatch, raises=subprocess.TimeoutExpired("probe", 120))
    with pytest.raises(TpuProbeError, match="did not finish"):
        detect_tpu_inventory()


def test_probe_reports_what_jax_finds(monkeypatch):
    from modal_tpu.server.worker import detect_tpu_inventory

    _unforced(monkeypatch)
    monkeypatch.setenv("TPU_TOPOLOGY", "2x2")
    _fake_probe(monkeypatch, stdout="noise\nPROBE|4|tpu|TPU v5 lite\n")
    assert detect_tpu_inventory() == ("TPU v5 lite", 4, "2x2")
    # a host where jax finds only the CPU honestly has no chips
    _fake_probe(monkeypatch, stdout="PROBE|1|cpu|cpu\n")
    assert detect_tpu_inventory() == ("", 0, "")
    # forced to the CPU: no probe at all
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    _fake_probe(monkeypatch, raises=AssertionError("probe ran on a forced-CPU host"))
    assert detect_tpu_inventory() == ("", 0, "")


def test_several_workers_may_not_each_claim_the_hosts_chips(monkeypatch):
    from modal_tpu.server import worker

    _unforced(monkeypatch)
    monkeypatch.setattr(worker, "detect_tpu_inventory", lambda: ("TPU v5 lite", 4, "2x2"))
    with pytest.raises(worker.TpuProbeError, match="each claim all 4"):
        asyncio.run(worker.chips_per_worker(2, None))
    assert asyncio.run(worker.chips_per_worker(1, None)) is None  # the one worker probes
    assert asyncio.run(worker.chips_per_worker(2, 2)) == 2  # divided explicitly
    monkeypatch.setattr(worker, "detect_tpu_inventory", lambda: ("", 0, ""))
    assert asyncio.run(worker.chips_per_worker(3, None)) == 0


# -- (a) where the compile cache lives -----------------------------------------

DEFAULT_CACHE = os.path.join(REPO_ROOT, ".modal_tpu_state", "jit_cache")


def test_compile_cache_dir_rule(monkeypatch):
    from modal_tpu.config import compile_cache_dir

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("MODAL_TPU_COMPILATION_CACHE_DIR", raising=False)
    # inside the checkout, at a path made of no temp name, pid or time
    assert compile_cache_dir() == DEFAULT_CACHE
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/outer")
    assert compile_cache_dir() == "/somewhere/outer"


def test_no_image_carries_its_own_cache_dir():
    """The worker pins the directory after the image env; the images
    themselves must not name another (the parent's did: /cache/jax,
    /tmp/modal_tpu_jit_cache, <rootfs>/cache/jax)."""
    import modal_tpu.image
    from modal_tpu import builder

    for version in builder.known_versions():
        assert "JAX_COMPILATION_CACHE_DIR" not in builder.base_image_config(version)["tpu_env"]
    import inspect

    assert "ENV JAX_COMPILATION_CACHE_DIR" not in inspect.getsource(modal_tpu.image)


def _read_cache_env():
    import os

    return os.environ.get("JAX_COMPILATION_CACHE_DIR", "")


def test_default_image_container_gets_the_one_cache_dir(supervisor, monkeypatch, tmp_path):
    import modal_tpu

    app = modal_tpu.App("cache-placement")
    f = app.function(serialized=True)(_read_cache_env)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    with app.run():
        assert f.remote() == DEFAULT_CACHE
    outer = str(tmp_path / "outer_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outer)
    with app.run():
        assert f.remote() == outer


# -- (d) the smoke cannot pass silently on the CPU ------------------------------


def _smoke(*args):
    env = {k: v for k, v in os.environ.items() if k not in ("MODAL_TPU_AUTO_LOCAL_SERVER", "MODAL_TPU_SERVER_URL")}
    return subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=300, env=env,
    )


def test_chip_smoke_without_a_tpu_fails_and_prints_no_result():
    proc = _smoke()
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_chip_smoke_explicit_cpu_dry_run_passes_on_tiny():
    proc = _smoke("--cpu")
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert lines[0].startswith("device: platform=cpu")
    # last, the verdict with exactly the keys the driver's check accepts
    verdict = json.loads(lines[-1])
    assert set(verdict) == {"ok", "device"} and set(verdict["device"]) == {"platform", "kind", "count"}
    assert verdict["ok"] is True and verdict["device"]["platform"] == "cpu"
    assert isinstance(verdict["device"]["kind"], str) and type(verdict["device"]["count"]) is int
    assert lines[-2].startswith("summary: ")
    summary = json.loads(lines[-2][len("summary: "):])
    assert summary["model"]["name"] == "tiny"
    boot1, boot2 = summary["served"]["boot1"], summary["served"]["boot2"]
    assert boot1["requests_sent"] == boot1["requests_succeeded"] >= 8 and boot1["streamed"] >= 1
    assert boot2["platform"] == "cpu"  # read from the server, not assumed
    assert summary["persistent_cache_hits_boot2"] >= 1
    assert summary["parity"]["logits_max_abs_diff"] <= summary["parity"]["tolerance"]
    assert list(summary)[-1] == "claim" and summary["claim"] is None


def test_llm_service_rejects_a_multi_chip_type():
    """The engine has no mesh: tpu="v5e-4" would hold four chips and use one."""
    import modal_tpu

    with pytest.raises(ValueError, match="3 of 4 idle"):
        modal_tpu.serving.llm_service(modal_tpu.App("too-many-chips"), tpu="v5e-4")
    modal_tpu.serving.llm_service(modal_tpu.App("one-chip"), tpu="v5e-1", model={"name": "tiny", "n_layers": 1})


# -- the kernels still lower for a TPU ------------------------------------------


def test_sharded_train_step_lowers_for_tpu_with_mosaic_calls(monkeypatch):
    """Under a mesh the flash kernels must sit inside shard_map: the SPMD
    partitioner refuses a bare Mosaic call ("cannot be automatically
    partitioned" — what the parent's train step did on the chip). Lowering
    for the TPU platform runs those rules on the CPU."""
    from modal_tpu.models.llama import get_config
    from modal_tpu.parallel.mesh import build_mesh
    from modal_tpu.parallel.train import TrainConfig, create_sharded_state, make_optimizer, make_train_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the dispatch's view only
    cfg = get_config("tiny", n_layers=1, dim=256, n_heads=2, n_kv_heads=2, max_seq_len=512)  # heads of 128
    mesh = build_mesh({"fsdp": 2, "model": 2}, devices=jax.devices()[:4], model_cfg=cfg)
    tc = TrainConfig(warmup_steps=10, total_steps=100)
    with mesh:
        state, step_fn, token_sharding = create_sharded_state(mesh, cfg, tc)
        tokens = jax.device_put(jnp.zeros((2, 256), jnp.int32), token_sharding)
        text = step_fn.trace(state, tokens).lower(lowering_platforms=("tpu",)).as_text()
        assert text.count("tpu_custom_call") >= 3  # forward, dQ, dK/dV
        bare = make_train_step(
            cfg, tc, make_optimizer(tc), attn_impl=None,
            state_shardings=jax.tree.map(lambda x: x.sharding, state),
        )
        with pytest.raises(NotImplementedError, match="shard_map"):
            bare.trace(state, tokens).lower(lowering_platforms=("tpu",))


def test_paged_decode_step_lowers_for_tpu_with_the_kernel():
    from modal_tpu.models.llama import get_config, init_params
    from modal_tpu.models.paged_kv import PagedKVCache, paged_decode_step

    cfg = get_config("tiny", dim=512, n_heads=4, n_kv_heads=2)  # heads of 128, GQA
    params = init_params(cfg, jax.random.PRNGKey(0))
    cache = PagedKVCache.create(cfg, 4, 33, 16, 8)
    args = (params, cfg, jnp.zeros((4,), jnp.int32), cache, jnp.ones((4,), bool))
    lowered = {
        impl: paged_decode_step.trace(*args, impl).lower(lowering_platforms=("tpu",)).as_text()
        for impl in ("kernel", "gather")
    }
    assert lowered["kernel"].count("tpu_custom_call") == 1 and "tpu_custom_call" not in lowered["gather"]


def test_flash_dispatch_says_which_path_and_why():
    from modal_tpu.ops.attention import VMEM_STAGED_BUDGET_BYTES, flash_kernel_refusal

    q = jnp.zeros((1, 256, 2, 128), jnp.bfloat16)
    assert "platform is cpu" in flash_kernel_refusal(q, q, None)
    import unittest.mock as mock

    with mock.patch.object(jax, "default_backend", lambda: "tpu"), mock.patch.object(
        type(q), "devices", lambda self: (_ for _ in ()).throw(RuntimeError("tracer"))
    ):
        assert flash_kernel_refusal(q, q, None) == ""
        assert "mask" in flash_kernel_refusal(q, q, jnp.zeros((1, 1, 256, 256)))
        assert "multiple" in flash_kernel_refusal(q[:, :200], q[:, :200], None)
        too_long = jax.ShapeDtypeStruct((1, VMEM_STAGED_BUDGET_BYTES // 256, 1, 128), jnp.bfloat16)
        assert "VMEM" in flash_kernel_refusal(too_long, too_long, None)
