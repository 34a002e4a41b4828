"""`llm_service`: a deployable continuous-batching LLM endpoint in one call.

Glues the pieces the serving tier is built from: an `@app.cls` whose
`@enter(snap=True)` hook builds params + the `ServingEngine` (so the warm
pool's snapshot/restore covers the loaded weights), an `@asgi_app` method
returning the SSE/JSON surface (serving/api.py), and SLO-driven autoscaler
settings (`target_ttft_ms` / `target_tokens_per_replica`) the scheduler
sizes replicas with from pushed serving telemetry.

    app = modal_tpu.App("llm")
    Service = modal_tpu.serving.llm_service(
        app, model="llama3-8b", tpu="v5e-1", checkpoint="/vol/ckpt",
        max_slots=32, target_ttft_ms=500,
    )
    # deploy; POST {url}/v1/generate with {"prompt": [...], "stream": true}
"""

from __future__ import annotations

from typing import Any, Optional

# What this build's served path can run beyond the dense block, by mechanism: the names a caller
# may put in `llm_service(requires=...)`. A build that lacks a mechanism lacks its name here.
MECHANISMS = ("window_kv", "routed_experts", "latent_kv", "router_groups")


def llm_service(
    app: Any,
    *,
    # anything models.llama.get_config accepts: a preset name, or
    # {"name": preset, **LlamaConfig overrides} (e.g. a depth cut) — a form
    # the caller can build without importing jax
    model: Any = "tiny",
    checkpoint: Optional[str] = None,  # volume/local path for weights.load_params
    quantize_int8: bool = False,
    seed: int = 0,
    max_slots: int = 8,
    num_pages: Optional[int] = None,
    page_size: int = 16,
    pages_per_slot: Optional[int] = None,
    prefill_chunk: int = 128,
    name: str = "LLMService",
    min_containers: int = 1,
    max_containers: int = 4,
    target_ttft_ms: float = 0.0,
    target_tokens_per_replica: float = 0.0,
    # ISSUE 12: service-level sampling defaults (request bodies override;
    # POST /v1/generate validates both) + serving-depth knobs
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    sampling_seed: int = 0,
    draft_model: Optional[str] = None,  # legacy alias for draft_config
    # ISSUE 18: a REAL smaller draft — `draft_config` names the draft's model
    # config, `draft_weights` points at its own trained checkpoint (omitted ⇒
    # random init from `seed`, fine for benches, useless for acceptance rate)
    draft_config: Optional[str] = None,
    draft_weights: Optional[str] = None,
    spec_k: int = 3,
    prefix_cache: Optional[bool] = None,  # None = env default (on; off for a model with window layers)
    role: Optional[str] = None,  # prefill|decode|both (None = env/both)
    # a model with window attention keeps a second, bounded KV pool for those
    # layers: its size in pages (None = every slot's window + one chunk)
    window_num_pages: Optional[int] = None,
    # requests that may wait for a slot; one more is refused (the engine's own bound)
    max_waiting: int = 1024,
    # mechanisms (names of MECHANISMS) the caller's model needs of the served path: one this build
    # does not list is refused HERE, in the calling process, before any container is asked for
    # (a container that met a preset it lacks would crash-loop until the boot timeout). It selects
    # no path and no container ever sees it
    requires: Any = (),
    **cls_kwargs: Any,
) -> Any:
    """Register a serving class on `app` and return it (an `@app.cls`
    result: instantiate + `.get_web_url()` under a run, or deploy it)."""
    import modal_tpu
    from modal_tpu.tpu_config import parse_tpu_config

    lacking = [name for name in requires if name not in MECHANISMS]
    if lacking:
        raise ValueError(
            f"llm_service(requires={list(requires)!r}): this build's served path has no {', '.join(map(repr, lacking))}; "
            f"it has {', '.join(MECHANISMS)}"
        )

    # ServingEngine has no mesh: params, KV pool and every step live on the
    # default device, so a multi-chip placement would hold chips it never uses
    spec = parse_tpu_config(cls_kwargs.get("tpu"))
    if spec is not None and spec.chips > 1:
        raise ValueError(
            f"llm_service(tpu={cls_kwargs['tpu']!r}): the serving engine runs on one chip and "
            f"would leave {spec.chips - 1} of {spec.chips} idle; use a one-chip type and "
            "min_containers/max_containers for more replicas"
        )

    opts = dict(
        serialized=True,
        min_containers=min_containers,
        max_containers=max_containers,
        target_ttft_ms=target_ttft_ms,
        target_tokens_per_replica=target_tokens_per_replica,
    )
    opts.update(cls_kwargs)

    class _LLMService:
        @modal_tpu.enter(snap=True)
        def load(self):
            # pre-snapshot: weights + engine warm-up land in the warm-state
            # snapshot, so restored replicas skip straight to serving
            import jax

            from modal_tpu.models.llama import get_config, init_params
            from modal_tpu.observability import device_telemetry

            # the container attached these at import time only if user code
            # had imported jax by then; this class imports it here, and the
            # compiles below must be counted (/v1/stats "compile")
            device_telemetry.install_compile_hooks()
            device_telemetry.maybe_install_fleet_cache()
            cfg = get_config(model)
            if checkpoint:
                from modal_tpu.models.weights import load_params

                params = load_params(checkpoint, cfg)
            else:
                params = init_params(cfg, jax.random.PRNGKey(seed))
            if quantize_int8:
                from modal_tpu.models.quant import quantize_params

                params = quantize_params(params)
            draft = None
            draft_name = draft_config or draft_model
            if draft_name:
                draft_cfg = get_config(draft_name)
                if draft_weights:
                    from modal_tpu.models.weights import load_params

                    draft_params = load_params(draft_weights, draft_cfg)
                else:
                    draft_params = init_params(draft_cfg, jax.random.PRNGKey(seed))
                draft = (draft_params, draft_cfg)
            from modal_tpu.serving.engine import ServingEngine

            self.engine = ServingEngine(
                params,
                cfg,
                max_slots=max_slots,
                num_pages=num_pages,
                page_size=page_size,
                pages_per_slot=pages_per_slot,
                prefill_chunk=prefill_chunk,
                draft=draft,
                spec_k=spec_k,
                prefix_cache=prefix_cache,
                role=role,
                window_num_pages=window_num_pages,
                max_waiting=max_waiting,
            ).start()

        @modal_tpu.exit()
        def shutdown(self):
            self.engine.stop()

        @modal_tpu.asgi_app()
        def serve(self):
            from modal_tpu.serving.api import serving_asgi_app

            return serving_asgi_app(
                self.engine,
                sampling_defaults={
                    "temperature": temperature,
                    "top_k": top_k,
                    "top_p": top_p,
                    "seed": sampling_seed,
                },
            )

    # rename BEFORE decoration: @app.cls registers under __name__, and the
    # deployed class/function tag must match the caller's `name`
    _LLMService.__name__ = name
    _LLMService.__qualname__ = name
    return app.cls(**opts)(_LLMService)
