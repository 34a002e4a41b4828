"""Distributed training step: FSDP/TP pjit over the mesh.

The judged configs (BASELINE.json 4-5) are Llama-3 8B/70B pretrain on
v5p slices. The step is a standard jit-of-grad with NamedSharding
constraints — XLA turns the FSDP specs into per-layer all-gathers under the
layer scan (overlapped with compute) and reduce-scatters on the grads.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.llama import LlamaConfig, init_params
from .mesh import build_mesh
from .sharding import param_shardings


class TrainState(NamedTuple):
    params: dict
    opt_state: Any
    step: jax.Array


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 3e-4
    weight_decay: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    grad_clip: float = 1.0
    warmup_steps: int = 2000
    total_steps: int = 100_000
    remat: bool = True  # jax.checkpoint the layer body: memory for FLOPs
    num_microbatches: int = 0  # pipeline microbatches; 0 = 2 × pipe stages

    def resolve_num_microbatches(self, n_stages: int) -> int:
        """Single source of truth — make_train_step and train_demo must
        agree or pipeline_loss rejects the batch at trace time."""
        return self.num_microbatches or 2 * n_stages


def make_optimizer(tc: TrainConfig) -> optax.GradientTransformation:
    schedule = optax.warmup_cosine_decay_schedule(
        init_value=0.0,
        peak_value=tc.learning_rate,
        warmup_steps=tc.warmup_steps,
        decay_steps=tc.total_steps,
        end_value=tc.learning_rate * 0.1,
    )
    return optax.chain(
        optax.clip_by_global_norm(tc.grad_clip),
        optax.adamw(schedule, b1=tc.b1, b2=tc.b2, weight_decay=tc.weight_decay),
    )


def loss_fn(
    params: dict, cfg: LlamaConfig, tokens: jax.Array, remat: bool, attn_impl: Optional[Callable] = None
) -> jax.Array:
    # forward over the full (evenly sharded) sequence, then shift for
    # next-token loss — keeps S divisible for sequence parallelism.
    # remat is applied inside forward() to the layer-scan body (true
    # per-layer checkpointing: one layer's residuals live at a time).
    # MoE configs add the load-balancing aux loss (keeps routing trainable).
    from ..models.llama import forward_with_aux

    logits, _, aux = forward_with_aux(params, cfg, tokens, attn_impl=attn_impl, remat=remat)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll) + cfg.moe_aux_coef * aux


def make_train_step(
    cfg: LlamaConfig,
    tc: TrainConfig,
    optimizer: optax.GradientTransformation,
    attn_impl: Optional[Callable] = None,
    pipeline_mesh: Optional[Mesh] = None,
    state_shardings: Optional[TrainState] = None,
) -> Callable:
    """Returns train_step(state, tokens) -> (state, metrics) — jit with
    donated state. With `pipeline_mesh` the loss is the GPipe-microbatched
    pipeline over its `pipe` axis (parallel/pipeline.py).

    `state_shardings` (a TrainState of NamedShardings, as built by
    create_sharded_state) pins out_shardings == in_shardings for the carried
    state. Without the pin XLA may choose a different output layout, which
    inserts a reshard (copy/all-gather) between consecutive steps AND breaks
    donation (a donated buffer can only be reused in place when the output
    sharding matches) — the ISSUE 20 audit asserts the pinned HLO carries no
    such copy."""
    if pipeline_mesh is not None:
        from .mesh import validate_mesh_constraints
        from .pipeline import pipeline_loss

        validate_mesh_constraints(dict(pipeline_mesh.shape), cfg)
        n_stages = pipeline_mesh.shape["pipe"]
        num_micro = tc.resolve_num_microbatches(n_stages)

        def compute_loss(params, tokens):
            return pipeline_loss(
                params, cfg, tokens, pipeline_mesh, num_micro, attn_impl=attn_impl
            )
    else:
        def compute_loss(params, tokens):
            return loss_fn(params, cfg, tokens, tc.remat, attn_impl)

    jit_kwargs: dict = {"donate_argnums": (0,)}
    if state_shardings is not None:
        # metrics stay unconstrained (scalars; XLA replicates them anyway)
        jit_kwargs["out_shardings"] = (state_shardings, None)

    @partial(jax.jit, **jit_kwargs)
    def train_step(state: TrainState, tokens: jax.Array):
        loss, grads = jax.value_and_grad(compute_loss)(state.params, tokens)
        updates, new_opt_state = optimizer.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        gnorm = optax.global_norm(grads)
        new_state = TrainState(new_params, new_opt_state, state.step + 1)
        return new_state, {"loss": loss, "grad_norm": gnorm, "step": new_state.step}

    return train_step


def _keypath_strs(path) -> tuple:
    out = []
    for k in path:
        for attr in ("key", "name", "idx"):
            v = getattr(k, attr, None)
            if v is not None:
                out.append(str(v))
                break
        else:
            out.append(str(k))
    return tuple(out)


def mirror_opt_shardings(abstract_opt, p_shardings, mesh: Mesh):
    """Shardings for the optimizer state that MIRROR the param shardings:
    optax moment trees (mu/nu) repeat the params pytree as subtrees, so each
    moment leaf gets the sharding of the param whose key-path it ends with;
    bookkeeping scalars (count) replicate. Found by the ISSUE 20 audit:
    ``jax.jit(optimizer.init)(params)`` does NOT inherit the params'
    shardings — the whole opt state landed on one device, and every train
    step then paid a full gather/scatter of both Adam moments."""
    flat_shardings = {
        _keypath_strs(path): s
        for path, s in jax.tree_util.tree_flatten_with_path(p_shardings)[0]
    }
    replicated = NamedSharding(mesh, P())

    def pick(path, _leaf):
        keys = _keypath_strs(path)
        for i in range(len(keys)):
            if keys[i:] in flat_shardings:
                return flat_shardings[keys[i:]]
        return replicated

    return jax.tree_util.tree_map_with_path(pick, abstract_opt)


def create_sharded_state(
    mesh: Mesh, cfg: LlamaConfig, tc: TrainConfig, seed: int = 0
) -> tuple[TrainState, Callable, NamedSharding]:
    """Initialize params DIRECTLY sharded on the mesh (jit with out_shardings
    — no host-memory spike for 70B-scale trees) and build the step function.
    When the mesh has a seq axis > 1, attention runs as ring attention with
    the sequence sharded (context parallelism).

    Returns (state, train_step, token_sharding).
    """
    from .mesh import validate_mesh_constraints

    # constraint check BEFORE sharded init: pipe × MoE must fail here, not
    # minutes later inside the jitted loss (mesh-build-time contract)
    validate_mesh_constraints(dict(mesh.shape), cfg)
    optimizer = make_optimizer(tc)
    pipe = mesh.shape.get("pipe", 1) > 1
    p_shardings = param_shardings(mesh, cfg, pipe=pipe)
    attn_impl = None
    if mesh.shape.get("seq", 1) > 1:
        # ring attention (context parallelism) — composes with the pipeline:
        # the pipe shard_map manualizes only its own axis, so the nested ring
        # shard_map over seq stays legal inside each stage
        from .ring_attention import make_ring_attention_impl

        attn_impl = make_ring_attention_impl(mesh, "seq", batch_axes=("data", "fsdp"))
    elif not pipe:
        # forward()'s default flash dispatch, with the Mosaic kernels kept
        # per-device (the pipeline's stage scan uses the einsum reference)
        from ..ops.attention import make_sharded_flash_attention

        attn_impl = make_sharded_flash_attention(mesh)

    @partial(jax.jit, out_shardings=p_shardings)
    def _init(key):
        return init_params(cfg, key)

    params = _init(jax.random.PRNGKey(seed))
    # optimizer state mirrors the params — pinned EXPLICITLY via
    # out_shardings (propagation alone leaves it single-device, see
    # mirror_opt_shardings)
    abstract_opt = jax.eval_shape(optimizer.init, params)
    opt_shardings = mirror_opt_shardings(abstract_opt, p_shardings, mesh)
    opt_state = jax.jit(optimizer.init, out_shardings=opt_shardings)(params)
    # step must live ON the mesh (replicated): a host-created scalar carries
    # SingleDeviceSharding, which would poison the out_shardings pin below
    # with a cross-platform device mismatch
    step0 = jax.device_put(jnp.zeros((), jnp.int32), NamedSharding(mesh, P()))
    state = TrainState(params=params, opt_state=opt_state, step=step0)
    # donation/resharding audit (ISSUE 20): carry the realized shardings into
    # the step's out_shardings so step N's outputs land exactly where step
    # N+1's donated inputs live — no reshard copy between consecutive steps
    state_shardings = jax.tree.map(lambda x: x.sharding, state)
    step_fn = make_train_step(
        cfg,
        tc,
        optimizer,
        attn_impl=attn_impl,
        pipeline_mesh=mesh if pipe else None,
        state_shardings=state_shardings,
    )
    token_spec = P(("data", "fsdp"), "seq" if mesh.shape.get("seq", 1) > 1 else None)
    return state, step_fn, NamedSharding(mesh, token_spec)


def train_demo(
    cfg_name: Any = "tiny",  # anything models.llama.get_config accepts
    mesh_axes: Optional[dict] = None,
    steps: int = 2,
    per_device_batch: int = 1,
    seq_len: int = 128,
) -> dict:
    """Tiny end-to-end pretrain demo (used by dryrun + tests): build mesh,
    shard state, run a few steps on synthetic data."""
    from ..models.llama import get_config

    cfg = get_config(cfg_name)
    mesh = build_mesh(mesh_axes, model_cfg=cfg)
    tc = TrainConfig(warmup_steps=10, total_steps=100)
    with mesh:
        state, step_fn, token_sharding = create_sharded_state(mesh, cfg, tc)
        n_batch = mesh.shape["data"] * mesh.shape["fsdp"] * per_device_batch
        if mesh.shape.get("pipe", 1) > 1:
            # round UP so each MICROBATCH still divides the (data, fsdp)
            # token sharding — ring attention inside a stage shards the
            # microbatch's batch dim over those axes — and never silently
            # shrink the requested batch
            num_micro = tc.resolve_num_microbatches(mesh.shape["pipe"])
            group = mesh.shape["data"] * mesh.shape["fsdp"]
            unit = group * num_micro
            n_batch = (n_batch + unit - 1) // unit * unit
        tokens = jax.device_put(
            jax.random.randint(jax.random.PRNGKey(1), (n_batch, seq_len), 0, cfg.vocab_size, jnp.int32),
            token_sharding,
        )
        from ..observability.device_telemetry import StepTimer, sample_device_memory

        # which attention path this shape compiled to is read off the lowered
        # step, not assumed: Mosaic kernels appear as tpu_custom_call
        mosaic_calls = step_fn.lower(state, tokens).as_text().count("tpu_custom_call")
        metrics = {}
        losses = []
        timer = StepTimer("train")
        for _ in range(steps):
            state, metrics = step_fn(state, tokens)
            # jax dispatch is async: block on the step's outputs so the mark
            # records step wall time, not enqueue latency (the first mark
            # still includes trace+compile — that's the honest cold step)
            jax.block_until_ready(metrics)
            timer.mark()
            losses.append(float(metrics["loss"]))
        sample_device_memory()
        out = {k: float(v) for k, v in metrics.items()}
        out["losses"] = losses
        out["mosaic_custom_calls"] = mosaic_calls
        # per-device residency (empty where the backend reports none, e.g.
        # CPU): shows the state is spread over the mesh, not on device 0
        out["device_bytes_in_use"] = [
            (d.memory_stats() or {}).get("bytes_in_use") for d in mesh.devices.flat
        ]
        return out
