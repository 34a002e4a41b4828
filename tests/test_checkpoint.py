"""Volume checkpointing: save/restore pytrees with sharded device placement."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def test_checkpoint_roundtrip_sharded(supervisor):
    import modal_tpu
    from modal_tpu.checkpoint import VolumeCheckpointer
    from modal_tpu.models.llama import forward, get_config, init_params
    from modal_tpu.parallel.mesh import build_mesh
    from modal_tpu.parallel.sharding import param_shardings

    vol = modal_tpu.Volume.from_name("ckpt-test", create_if_missing=True)
    vol.hydrate()
    ckpt = VolumeCheckpointer(vol)

    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.PRNGKey(0))
    manifest = ckpt.save("run/step1", params)
    assert len(manifest["leaves"]) == 12  # 4 top-level + 9 stacked... (flattened)

    mesh = build_mesh({"fsdp": 4, "model": 2})
    restored = ckpt.restore("run/step1", shardings=param_shardings(mesh, cfg))
    tokens = jnp.ones((1, 8), jnp.int32)
    l1, _ = forward(params, cfg, tokens)
    l2, _ = forward(restored, cfg, tokens)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), rtol=1e-2, atol=1e-2)
    assert "fsdp" in str(restored["embed"].sharding.spec)


def test_checkpoint_plain_tree(supervisor):
    import modal_tpu
    from modal_tpu.checkpoint import VolumeCheckpointer

    vol = modal_tpu.Volume.from_name("ckpt-test2", create_if_missing=True)
    vol.hydrate()
    ckpt = VolumeCheckpointer(vol)
    tree = {"a": jnp.arange(10.0), "nested": {"b": jnp.ones((3, 3), jnp.bfloat16)}, "l": [jnp.zeros(2), jnp.ones(2)]}
    ckpt.save("t/1", tree)
    back = ckpt.restore("t/1")
    np.testing.assert_array_equal(np.asarray(tree["a"]), np.asarray(back["a"]))
    assert back["nested"]["b"].dtype == jnp.bfloat16
    assert isinstance(back["l"], list) and len(back["l"]) == 2
    assert ckpt.exists("t/1") and not ckpt.exists("t/nope")


@pytest.mark.slow  # re-tier (ISSUE 11): ~12 s; test_checkpoint_roundtrip_sharded keeps sharded coverage
def test_checkpoint_sharded_format(supervisor):
    """Per-shard save format: each shard file holds one device's slice; the
    manifest's shard table is derived from the sharding (identical on every
    process, SURVEY §7 hard part 6). Restore assembles only needed shards,
    reading files in parallel — exercised here on an 8-device CPU mesh."""
    import modal_tpu
    from modal_tpu.checkpoint import VolumeCheckpointer
    from modal_tpu.models.llama import forward, get_config, init_params
    from modal_tpu.parallel.mesh import build_mesh
    from modal_tpu.parallel.sharding import param_shardings

    vol = modal_tpu.Volume.from_name("ckpt-shard", create_if_missing=True)
    vol.hydrate()
    ckpt = VolumeCheckpointer(vol)

    cfg = get_config("tiny")
    mesh = build_mesh({"fsdp": 4, "model": 2})
    shardings = param_shardings(mesh, cfg)
    params = jax.jit(lambda k: init_params(cfg, k), out_shardings=shardings)(jax.random.PRNGKey(0))
    manifest = ckpt.save("sh/1", params, shard_leaves_over=0)
    assert any("shards" in m for m in manifest["leaves"]), "no leaf took the shard format"
    sharded_meta = next(m for m in manifest["leaves"] if "shards" in m and len(m["shards"]) > 1)
    assert len(sharded_meta["shards"]) >= 2

    tokens = jnp.ones((1, 8), jnp.int32)
    l_ref, _ = forward(params, cfg, tokens)

    # restore with the same shardings
    r1 = ckpt.restore("sh/1", shardings=shardings)
    l1, _ = forward(r1, cfg, tokens)
    np.testing.assert_allclose(np.asarray(l_ref), np.asarray(l1), rtol=1e-2, atol=1e-2)

    # restore with a DIFFERENT mesh shape (shard regridding)
    mesh2 = build_mesh({"fsdp": 2, "model": 4})
    r2 = ckpt.restore("sh/1", shardings=param_shardings(mesh2, cfg))
    l2, _ = forward(r2, cfg, tokens)
    np.testing.assert_allclose(np.asarray(l_ref), np.asarray(l2), rtol=1e-2, atol=1e-2)

    # restore unsharded (full assembly)
    r3 = ckpt.restore("sh/1")
    l3, _ = forward(r3, cfg, tokens)
    np.testing.assert_allclose(np.asarray(l_ref), np.asarray(l3), rtol=1e-2, atol=1e-2)


def test_checkpoint_trainstate_roundtrip(supervisor):
    """TrainState (NamedTuple + optax opt_state) must round-trip with its
    original treedef via example_tree so restore feeds straight back into
    train_step (ADVICE r1: path-based rebuild returned plain dicts/lists)."""
    import modal_tpu
    from modal_tpu.checkpoint import VolumeCheckpointer
    from modal_tpu.models.llama import get_config, init_params
    from modal_tpu.parallel.train import TrainConfig, TrainState, make_optimizer, make_train_step

    vol = modal_tpu.Volume.from_name("ckpt-test3", create_if_missing=True)
    vol.hydrate()
    ckpt = VolumeCheckpointer(vol)

    cfg = get_config("debug-1l")
    tc = TrainConfig(warmup_steps=2, total_steps=10, remat=False)
    optimizer = make_optimizer(tc)
    params = init_params(cfg, jax.random.PRNGKey(0))
    state = TrainState(params, optimizer.init(params), jnp.zeros((), jnp.int32))
    step_fn = make_train_step(cfg, tc, optimizer)
    tokens = jnp.ones((2, 16), jnp.int32)
    state, _ = step_fn(state, tokens)

    ckpt.save("ts/1", state)
    example = jax.eval_shape(lambda: state)
    back = ckpt.restore("ts/1", example_tree=example)
    assert isinstance(back, TrainState)
    assert int(back.step) == 1
    # restored state must be directly usable by train_step (donated argnums)
    state2, metrics = step_fn(back, tokens)
    assert int(state2.step) == 2 and float(metrics["loss"]) > 0


def test_checkpoint_cross_mesh_regrid(supervisor):
    """Save on one mesh, restore onto a DIFFERENT shard grid (BASELINE
    config 5: elastic resume after slice reshape). Save fsdp=8 (per-shard
    format), restore with data=2 x fsdp=2 x model=2 shardings — the restore
    path assembles each target shard from the overlapping saved shards."""
    import modal_tpu
    from modal_tpu.checkpoint import VolumeCheckpointer
    from modal_tpu.models.llama import forward, get_config, init_params
    from modal_tpu.parallel.mesh import build_mesh
    from modal_tpu.parallel.sharding import param_shardings

    vol = modal_tpu.Volume.from_name("ckpt-regrid", create_if_missing=True)
    vol.hydrate()
    ckpt = VolumeCheckpointer(vol)

    cfg = get_config("tiny")
    mesh_a = build_mesh({"fsdp": 8})
    sh_a = param_shardings(mesh_a, cfg)
    params = jax.jit(lambda k: init_params(cfg, k), out_shardings=sh_a)(jax.random.PRNGKey(0))
    ckpt.save("regrid/step1", params, shard_leaves_over=0)

    mesh_b = build_mesh({"data": 2, "fsdp": 2, "model": 2})
    sh_b = param_shardings(mesh_b, cfg)
    restored = ckpt.restore("regrid/step1", shardings=sh_b)
    assert restored["layers"]["wq"].sharding == sh_b["layers"]["wq"]

    tokens = jnp.ones((2, 8), jnp.int32)
    la, _ = forward(params, cfg, tokens)
    lb, _ = forward(restored, cfg, tokens)
    np.testing.assert_allclose(np.asarray(la), np.asarray(lb), rtol=1e-2, atol=1e-2)


@pytest.mark.slow  # re-tier (ISSUE 11): ~14 s; test_checkpoint_cross_mesh_regrid keeps regrid coverage
def test_checkpoint_regrid_to_more_devices(supervisor, tmp_path):
    """Save on THIS process's 8-device mesh, restore in a SUBPROCESS with 16
    virtual devices on a 16-way mesh (BASELINE config 5: resume after slice
    rescale — the restore path regrids saved shards onto more devices than
    the checkpoint ever saw)."""
    import os
    import subprocess
    import sys

    import modal_tpu
    from modal_tpu.checkpoint import VolumeCheckpointer
    from modal_tpu.models.llama import get_config, init_params
    from modal_tpu.parallel.mesh import build_mesh
    from modal_tpu.parallel.sharding import param_shardings

    vol = modal_tpu.Volume.from_name("ckpt-regrid-16", create_if_missing=True)
    vol.hydrate()
    ckpt = VolumeCheckpointer(vol)

    cfg = get_config("tiny")
    mesh_a = build_mesh({"fsdp": 8})
    sh_a = param_shardings(mesh_a, cfg)
    params = jax.jit(lambda k: init_params(cfg, k), out_shardings=sh_a)(jax.random.PRNGKey(0))
    ckpt.save("regrid16/step1", params, shard_leaves_over=0)
    tokens = jnp.ones((2, 8), jnp.int32)
    from modal_tpu.models.llama import forward

    ref_logits = np.asarray(forward(params, cfg, tokens)[0])
    ref_path = str(tmp_path / "ref_logits.npy")
    np.save(ref_path, ref_logits)

    child_code = f"""
import os
import numpy as np
import jax, jax.numpy as jnp
import modal_tpu
from modal_tpu.checkpoint import VolumeCheckpointer
from modal_tpu.models.llama import forward, get_config
from modal_tpu.parallel.mesh import build_mesh
from modal_tpu.parallel.sharding import param_shardings

assert len(jax.devices()) == 16, jax.devices()
cfg = get_config("tiny")
vol = modal_tpu.Volume.from_name("ckpt-regrid-16")
vol.hydrate()
ckpt = VolumeCheckpointer(vol)
mesh = build_mesh({{"data": 2, "fsdp": 4, "model": 2}})
sh = param_shardings(mesh, cfg)
restored = ckpt.restore("regrid16/step1", shardings=sh)
assert restored["layers"]["wq"].sharding == sh["layers"]["wq"]
tokens = jnp.ones((2, 8), jnp.int32)
logits = np.asarray(forward(restored, cfg, tokens)[0])
ref = np.load({ref_path!r})
np.testing.assert_allclose(logits, ref, rtol=1e-2, atol=1e-2)
print("REGRID-16-OK")
"""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    env["MODAL_TPU_SERVER_URL"] = f"grpc://127.0.0.1:{supervisor.port}"
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-c", child_code], env=env, capture_output=True, text=True, timeout=300
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "REGRID-16-OK" in r.stdout
