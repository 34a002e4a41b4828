"""Continuous-batching decode loop over the paged KV pool.

The dense serving story (`sampling.greedy_generate`) runs one request at a
time: tokens/s/chip is batch=1 math and every queued request's TTFT includes
the whole queue ahead of it. This engine keeps ONE decode loop running and
lets requests join and leave it per step:

- **slots**: the decode batch has `max_slots` fixed positions; a request is
  admitted into a free slot the moment one (plus KV pages) is available —
  mid-decode, without restarting in-flight sequences (`paged_decode_step` is
  one fixed-shape executable; admission is data, not shape).
- **prefill/decode separation**: prompts prefill in `prefill_chunk`-token
  slices, one slice per loop iteration, interleaved with decode steps — a
  4k-token prompt cannot stall everyone else's token cadence for its whole
  prefill, it pays its own TTFT instead.
- **paged KV**: all slots share one page pool (models/paged_kv.py). HBM is
  bounded by the pool, not `num_requests × max_len`; when the pool runs dry
  the youngest request is preempted (pages freed, request requeued with its
  generated prefix — tokens already streamed are never re-emitted).
- **streaming**: generated tokens append to a per-request buffer;
  consumers (SSE handlers, `.result()`) read with a cursor, so a dropped
  stream re-reads from the buffer — exactly-once regardless of transport.

The loop runs on its own thread (jax releases the GIL during device
compute); `submit()` is thread-safe and returns immediately — TTFT is the
engine's admission+prefill latency, not queue drain.
"""

from __future__ import annotations

import itertools
import os
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Optional

from ..config import logger
from ..observability import tracing
from ..observability.catalog import (
    ENGINE_PHASES,
    KV_PAGES_ALLOCATED,
    KV_PAGES_COW,
    KV_PAGES_FREE,
    KV_PAGES_SHIPPED,
    KV_SHIP_SECONDS,
    SERVING_BATCH_OCCUPANCY,
    SERVING_PREEMPTIONS,
    SERVING_PREFIX_HITS,
    SERVING_PREFIX_MISSES,
    SERVING_QUEUE_DEPTH,
    SERVING_REQUESTS,
    SERVING_ROLE,
    SERVING_SAMPLED_TOKENS,
    SERVING_SPEC_ACCEPT_RATIO,
    SERVING_TOKENS,
    SERVING_TOKENS_PER_S,
    SERVING_TTFT,
    SERVING_TTFT_P95,
)
from .pages import ModelPages

_req_counter = itertools.count()
_replica_id_cache: dict = {}


def replica_id() -> str:
    """Globally-unique replica prefix for request ids (ISSUE 11 satellite):
    the container's task id when running under the stack (every container
    gets MODAL_TPU_TASK_ID from its worker), else host-pid. Request ids were
    replica-local before — a buffered-degrade refetch after replica death
    404'd *ambiguously* (the same `gr-0-...` could exist on the new replica
    for a different request); with the task-id prefix a 404 is unambiguous:
    that id's replica is gone (docs/SERVING.md degradation matrix)."""
    cached = _replica_id_cache.get("id")
    if cached is None:
        import socket

        cached = os.environ.get("MODAL_TPU_TASK_ID") or f"{socket.gethostname()}-{os.getpid()}"
        _replica_id_cache["id"] = cached
    return cached


# per-request timeline spans (ISSUE 11): every N generated tokens the engine
# records a serving.decode progress mark carrying batch occupancy + KV pool
# attrs; MODAL_TPU_SERVING_SPANS=0 turns the whole per-request timeline off
# (the A/B knob bench_serving's observability-overhead guard flips)
SPANS_ENV = "MODAL_TPU_SERVING_SPANS"
SPAN_TOKENS_ENV = "MODAL_TPU_SERVING_SPAN_TOKENS"
# chaos (ISSUE 11 acceptance): inject latency into every engine loop
# iteration — TTFT and tokens/s degrade together, which is exactly the
# signal shape the burn-rate alerting must catch (docs/CHAOS.md)
CHAOS_STEP_DELAY_ENV = "MODAL_TPU_CHAOS_SERVING_STEP_DELAY_S"

# ISSUE 12 degradation knobs (docs/SERVING.md degradation matrix): each new
# serving capability individually collapsible to the PR 9 behavior.
SAMPLING_ENV = "MODAL_TPU_SERVING_SAMPLING"  # 0 → greedy-only engine
PREFIX_CACHE_ENV = "MODAL_TPU_SERVING_PREFIX_CACHE"  # 0 → no shared-prefix reuse
SPEC_ENV = "MODAL_TPU_SERVING_SPEC"  # 0 → ignore any configured draft model

# ISSUE 18 fleet knobs (docs/SERVING.md degradation matrix):
# - role: what this replica does in a disaggregated fleet. "prefill" replicas
#   serve /v1/prefill (KV-page shipments out), "decode" replicas accept
#   /v1/prefilled admissions; unset/"both" is the PR 11 all-in-one replica —
#   the role never *disables* an engine path, it only advertises intent to
#   the router/autoscaler, so a mis-set role degrades to slower routing, not
#   to refused requests.
ROLE_ENV = "MODAL_TPU_SERVING_ROLE"  # prefill | decode | both (unset → both)
# chaos (ISSUE 18): drop the next N inbound KV-page shipments at the decode
# boundary — exactly what a prefill replica dying mid-ship looks like. The
# decode side must fall back to a full local prefill with zero token loss.
CHAOS_KV_SHIP_DROP_ENV = "MODAL_TPU_CHAOS_KV_SHIP_DROP"

_kv_ship_chaos: dict = {}


def _consume_kv_ship_drop() -> bool:
    """One chaos-drop budget unit, lazily seeded from the env (same
    budget-consume pattern as api._consume_stream_reset: tests set the env
    then `_reset_kv_ship_chaos_for_tests()`)."""
    budget = _kv_ship_chaos.get("budget")
    if budget is None:
        try:
            budget = int(os.environ.get(CHAOS_KV_SHIP_DROP_ENV, "0") or 0)
        except ValueError:
            budget = 0
        _kv_ship_chaos["budget"] = budget
    if budget > 0:
        _kv_ship_chaos["budget"] = budget - 1
        return True
    return False


def _reset_kv_ship_chaos_for_tests() -> None:
    _kv_ship_chaos.clear()


def resolve_role() -> str:
    """MODAL_TPU_SERVING_ROLE → "prefill" | "decode" | "both". Anything
    unrecognized (including unset) is "both": a typo'd role must degrade to
    the do-everything replica, never to a replica that refuses work."""
    val = os.environ.get(ROLE_ENV, "").strip().lower()
    return val if val in ("prefill", "decode") else "both"


# the serving_role gauge encodes the role as a number (gauges carry floats
# over the heartbeat); history._replica_rows maps it back for `modal_tpu top`
ROLE_GAUGE_VALUES = {"both": 0, "prefill": 1, "decode": 2}


def _env_on(name: str, default: str = "1") -> bool:
    return os.environ.get(name, default).strip().lower() not in ("0", "false", "no", "off")


def _spans_enabled() -> bool:
    return _env_on(SPANS_ENV)


def _span_mark_tokens() -> int:
    try:
        return max(1, int(os.environ.get(SPAN_TOKENS_ENV, "8")))
    except ValueError:
        return 8


class EngineStopped(RuntimeError):
    pass


def _refuse_unservable(cfg: Any, params: dict, speculative: bool, role: str) -> None:
    """What the paged path cannot run yet, refused at construction with the
    mechanism in the message (never by a model's name). What a model's pools
    cannot hold, the page manager refuses (serving/pages.py)."""
    import jax

    if getattr(cfg, "is_moe", False):
        raise ValueError(
            "n_experts is the mesh trainer's switch layer (parallel/moe.py): top-1 routing with a capacity "
            "that drops tokens, dispatched over the whole batch; the paged path serves routed experts as a "
            "layer kind (ffn_pattern, models/experts.py) and never drops a token"
        )
    if getattr(cfg, "uniform", True):
        return
    kinds = "window layers" if cfg.has_window else "layers of more than one kind"
    if speculative:
        raise ValueError(
            f"speculative decoding (a draft, paged_verify_step) over a model with {kinds} or routed experts: "
            "the verify step writes k+1 positions it may roll back, which a window layer's bounded pool "
            "cannot keep, and the draft mirror assumes one pool of one layer kind"
        )
    if role in ("prefill", "decode"):
        raise ValueError(
            f"role={role!r} ships KV pages between replicas, and a shipment addresses ONE pool; this model "
            "keeps a pool a layer kind and a shipment over two pools does not exist yet"
        )
    if cfg.has_experts and any(leaf.dtype == "int8" for leaf in jax.tree_util.tree_leaves(params)):
        raise ValueError(
            "quantize_int8 over routed-expert weights: models/quant.py scales a matrix per output channel "
            "over its second-to-last axis and models/experts.py contracts stacked [expert, in, out] weights "
            "without a dequantizing read"
        )


class _LoopPhases:
    """The engine thread's clock-switch: `switch(name)` ENDS the phase that
    was open and STARTS the next, so the phases (observability/catalog.py
    ENGINE_PHASES) partition the thread's time by construction. Each phase
    is recorded twice from that one call: as a profiler annotation named
    `engine.<phase>` (in the same trace as the device's operations when a
    profiler session is on, a no-op otherwise) and as seconds for
    `/v1/stats`. Only the engine thread calls `switch`/`close`; any thread
    may call `snapshot` (one atomic copy of a dict of floats)."""

    def __init__(self, annotation: Any):
        self._annotation = annotation  # jax.profiler.TraceAnnotation
        self._seconds: dict[str, float] = dict.fromkeys(ENGINE_PHASES, 0.0)
        self._open: Optional[tuple] = None  # (name, started, annotation)

    def switch(self, name: str, **attrs: Any) -> None:
        now = time.perf_counter()
        ended = self._open
        if ended is not None:
            ended[2].__exit__(None, None, None)
        # the annotation takes its start time when it is made: made right
        # after the last one's end, a trace shows no gap between two phases
        annotation = self._annotation("engine." + name, **attrs)
        annotation.__enter__()
        self._open = (name, now, annotation)
        if ended is not None:
            self._seconds[ended[0]] += now - ended[1]

    def close(self) -> None:
        ended, self._open = self._open, None
        if ended is not None:
            ended[2].__exit__(None, None, None)
            self._seconds[ended[0]] += time.perf_counter() - ended[1]

    def snapshot(self) -> dict[str, float]:
        return dict(self._seconds)


class GenRequest:
    """One generation request: prompt in, token stream out.

    `tokens` is the buffered, exactly-once source of truth — stream
    consumers keep a cursor into it (`wait_new` / `wait_new_async`), so a
    reset stream resumes (or degrades to a buffered read) without loss or
    duplication."""

    def __init__(
        self,
        prompt: list[int],
        max_new_tokens: int,
        request_id: str = "",
        eos_token_id: Optional[int] = None,
        trace_context: Optional[Any] = None,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 1.0,
        seed: int = 0,
    ):
        self.id = request_id or f"gr-{replica_id()}-{next(_req_counter)}"
        self.prompt = list(prompt)
        self.max_new_tokens = max_new_tokens
        self.eos_token_id = eos_token_id
        self.trace_context = trace_context
        # sampling params (ISSUE 12): temperature 0 = greedy; the PRNG key
        # for this request's token #i is fold_in(PRNGKey(seed), i) — a pure
        # function of (seed, position), so the stream is bit-reproducible
        # under mid-decode joins and preemption/re-prefill
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.seed = int(seed) & 0x7FFFFFFF  # PRNGKey seed space (int32-safe)
        self.created_at = time.time()
        self.admitted_at = 0.0
        self.first_token_at = 0.0
        self.finished_at = 0.0
        self.preemptions = 0
        self.tokens: list[int] = []
        self.done = False
        self.error: Optional[str] = None
        # prefill/decode disaggregation (ISSUE 18): `shipment` is the
        # export-side result (a host KV-page bundle, set before _finish);
        # `_shipment` is an inbound remotely-prefilled bundle consumed at
        # first admission (a later preemption re-prefills locally)
        self.shipment: Optional[dict] = None
        self._shipment: Optional[dict] = None
        self._export = False
        # per-request timeline (ISSUE 11): the root span every lifecycle
        # span (admit → prefill chunks → decode marks → preempt → stream)
        # parents under; queue_from anchors the NEXT admit span (request
        # creation, then each preemption)
        self.root_span: Optional[Any] = None
        self.queue_from = self.created_at
        self._cond = threading.Condition()
        self._async_waiters: list[tuple[Any, Any]] = []  # (loop, asyncio.Event)

    # -- engine side --------------------------------------------------------

    def _append(self, token: int) -> None:
        with self._cond:
            if self.first_token_at == 0.0:
                self.first_token_at = time.time()
            self.tokens.append(token)
            self._wake()

    def _finish(self, error: Optional[str] = None) -> None:
        with self._cond:
            self.done = True
            self.error = error
            self.finished_at = time.time()
            self._wake()
        if self.root_span is not None:
            self.root_span.attrs.update(
                {
                    "request_id": self.id,
                    "tokens": len(self.tokens),
                    "preemptions": self.preemptions,
                    "ttft_s": round(self.ttft_s, 6) if self.ttft_s is not None else None,
                }
            )
            tracing.close_span(self.root_span, status="error" if error else "ok")
            self.root_span = None

    def _wake(self) -> None:
        self._cond.notify_all()
        for loop, event in self._async_waiters:
            try:
                loop.call_soon_threadsafe(event.set)
            except RuntimeError:
                pass  # consumer's loop is gone; the buffer still has the tokens
        self._async_waiters.clear()

    # -- consumer side ------------------------------------------------------

    @property
    def ttft_s(self) -> Optional[float]:
        if self.first_token_at:
            return self.first_token_at - self.created_at
        return None

    def reached_end(self) -> bool:
        """The ONE completion predicate — `_maybe_finish` and the decode-mark
        flush both call it, so a future stop condition (stop sequences,
        budgets) cannot leave the final decode span unflushed."""
        return len(self.tokens) >= self.max_new_tokens or (
            self.eos_token_id is not None
            and bool(self.tokens)
            and self.tokens[-1] == self.eos_token_id
        )

    def wait_new(self, offset: int, timeout: Optional[float] = None) -> tuple[list[int], bool]:
        """Block until tokens beyond `offset` exist (or done/timeout);
        returns (new_tokens, done)."""
        with self._cond:
            self._cond.wait_for(lambda: len(self.tokens) > offset or self.done, timeout)
            return list(self.tokens[offset:]), self.done

    async def wait_new_async(self, offset: int, timeout: Optional[float] = None) -> tuple[list[int], bool]:
        """Async twin of `wait_new` (no thread parked per waiting stream —
        the engine wakes the consumer's loop directly)."""
        import asyncio

        deadline = (time.monotonic() + timeout) if timeout is not None else None
        while True:
            with self._cond:
                if len(self.tokens) > offset or self.done:
                    return list(self.tokens[offset:]), self.done
                event = asyncio.Event()
                self._async_waiters.append((asyncio.get_running_loop(), event))
            remaining = None if deadline is None else deadline - time.monotonic()
            if remaining is not None and remaining <= 0:
                return list(self.tokens[offset:]), self.done
            try:
                await asyncio.wait_for(event.wait(), remaining)
            except asyncio.TimeoutError:
                return list(self.tokens[offset:]), self.done

    def result(self, timeout: Optional[float] = None) -> list[int]:
        """Block until completion; returns the full generated token list."""
        with self._cond:
            if not self._cond.wait_for(lambda: self.done, timeout):
                raise TimeoutError(f"request {self.id} not done after {timeout}s")
        if self.error:
            raise EngineStopped(self.error)
        return list(self.tokens)


@dataclass
class _Slot:
    # what the slot holds of the KV pools is the page manager's, by slot index
    request: GenRequest
    pos: int = 0  # tokens written to the slot's pages (mirrors seq_lens)
    prefill_tokens: list[int] = field(default_factory=list)  # prompt (+ regenerated prefix)
    prefill_done: int = 0  # tokens of prefill_tokens already written (target pool)
    draft_prefill_done: int = 0  # draft-pool prefill progress (may lead via its own prefix hits)
    first_emitted: bool = False  # this slot's prefill-completion token went out
    cur_token: int = 0  # token to feed the next decode step
    state: str = "prefill"  # "prefill" | "decode"
    admitted_step: int = 0
    # decode progress marks (ISSUE 11 timelines): the last serving.decode
    # span's end time and the token count it covered up to
    last_mark_t: float = 0.0
    tokens_at_mark: int = 0


class ServingEngine:
    """The serving tier's model runtime: one shared paged-KV pool + one
    continuous decode loop (docs/SERVING.md)."""

    def __init__(
        self,
        params: dict,
        cfg: Any,
        *,
        max_slots: int = 8,
        num_pages: Optional[int] = None,
        page_size: int = 16,
        pages_per_slot: Optional[int] = None,
        prefill_chunk: int = 128,
        max_waiting: int = 1024,
        draft: Optional[tuple] = None,  # (draft_params, draft_cfg) → speculative decoding
        spec_k: int = 3,  # draft tokens proposed per speculative round
        prefix_cache: Optional[bool] = None,  # None = env default (on; off with window layers)
        role: Optional[str] = None,  # prefill | decode | both; None = env default
        # pages of the window layers' pool (a model with window attention);
        # None = every slot's window + one chunk's headroom, which no
        # admission pattern exhausts (paged_kv.default_window_num_pages)
        window_num_pages: Optional[int] = None,
    ):
        import math

        from ..models.paged_kv import DEFAULT_PAGE_SIZE, resolve_attn_impl

        page_size = page_size or DEFAULT_PAGE_SIZE
        pages_per_slot = pages_per_slot or math.ceil(cfg.max_seq_len / page_size)
        role = role if role in ("prefill", "decode", "both") else resolve_role()
        speculative = draft is not None and _env_on(SPEC_ENV)
        _refuse_unservable(cfg, params, speculative, role)
        if num_pages is None:
            # default pool: half of what dense per-slot max_len caches would
            # take — the whole point is sharing
            num_pages = 1 + max(2 * max_slots, (max_slots * pages_per_slot) // 2)
        self.params = params
        self.cfg = cfg
        self.max_slots = max_slots
        self.page_size = page_size
        self.pages_per_slot = pages_per_slot
        self.prefill_chunk = prefill_chunk
        self.max_context = pages_per_slot * page_size
        self.max_waiting = max_waiting
        # shared-prefix KV reuse: content-keyed lookup + CoW pages; off where a
        # window layer would need its last positions from a hit too
        if prefix_cache is None:
            prefix_cache = _env_on(PREFIX_CACHE_ENV) and not cfg.has_window
        geometry = dict(
            max_slots=max_slots, num_pages=num_pages, page_size=page_size,
            pages_per_slot=pages_per_slot, prefill_chunk=prefill_chunk, prefix_cache=bool(prefix_cache),
        )
        # the page manager of the model served (serving/pages.py): its device
        # cache goes into a step as `self.pages.cache` and the step's comes back
        self.pages = ModelPages(cfg, window_num_pages=window_num_pages, **geometry)
        # routed experts: pairs routed and expert-layer calls are counted here,
        # from what the loop launches; the pairs the held experts computed come
        # back from the device with a step's tokens (cache.moe_pairs)
        self.moe_expert_layers = sum(1 for k in cfg.layer_kinds if k.experts)
        self.moe_assignments = 0
        self.moe_local_assignments = 0
        self.moe_expert_calls = 0
        self.moe_experts_touched = 0
        self._moe_pairs_seen = 0
        self._moe_touched_seen = 0
        # what this engine actually runs on, as jax reports it — /v1/stats
        # carries it so a client never has to assume the device
        import jax
        import jax.profiler

        self._phases = _LoopPhases(jax.profiler.TraceAnnotation)
        self._phase = self._phases.switch
        devices = jax.devices()
        self.device = {
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "count": len(devices),
            # which of the host's chips the worker pinned this process to
            "visible_chips": os.environ.get("TPU_VISIBLE_DEVICES", ""),
        }
        # ISSUE 12 capability knobs, each individually degradable -----------
        self.attn_impl = resolve_attn_impl()  # "kernel" on a TPU, "gather" elsewhere
        self.sampling_enabled = _env_on(SAMPLING_ENV)
        # speculative decoding: a small-config draft proposes spec_k tokens,
        # the target verifies them in ONE multi-token step
        self.draft_params: Optional[dict] = None
        self.draft_cfg: Optional[Any] = None
        self.draft_pages: Optional[ModelPages] = None
        self.spec_k = 0
        if speculative:
            draft_params, draft_cfg = draft
            if draft_cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab ({draft_cfg.vocab_size}) != target vocab ({cfg.vocab_size})"
                )
            _refuse_unservable(draft_cfg, draft_params, True, "both")
            self.draft_params = draft_params
            self.draft_cfg = draft_cfg
            self.spec_k = max(1, int(spec_k))
            # the draft mirrors the target's slot/page geometry 1:1, with a
            # prefix cache of its OWN over full pages only (ISSUE 18): nothing
            # shared is ever written there, so a prefix-skipping target
            # prefill cannot desync from the draft and the draft copies nothing
            self.draft_pages = ModelPages(draft_cfg, partial_pages=False, **geometry)
        # every model's pages, the target's first: what both do, the loop does over these
        self._models = [self.pages] + ([self.draft_pages] if self.draft_pages is not None else [])
        # ISSUE 18 fleet mode: the advertised role
        self.role = role
        SERVING_ROLE.set(float(ROLE_GAUGE_VALUES[self.role]))
        self.kv_pages_shipped = 0
        self.kv_ship_drops = 0
        self.remote_prefills = 0
        self.slots: list[Optional[_Slot]] = [None] * max_slots
        self.waiting: deque[GenRequest] = deque()
        self.requests: dict[str, GenRequest] = {}  # id -> request (bounded retention)
        self._retired: deque[str] = deque()
        self.step_count = 0
        self.tokens_generated = 0
        self.sampled_tokens = 0
        self.requests_completed = 0
        self.preemptions = 0
        # what the loop did, for /v1/stats (ISSUE 26): prompt tokens are the
        # target model's chunks (a prefix-cache hit is not in them, a
        # re-prefill after a preemption is)
        self.loop_iterations = 0
        self.requests_admitted = 0
        self.queue_wait_seconds = 0.0
        self.prompt_tokens_prefilled = 0
        self.prefill_chunks = 0
        self.prefill_bucket_tokens = 0
        self.prefill_kv_attended = 0  # positions the chunks' attention loops visited
        self.prefill_kv_span = 0  # max_context a chunk: what a span-wide attention would visit
        # the per-request timeline knobs, read once: an engine keeps the
        # setting it was built under
        self.spans_on = _spans_enabled()
        self.span_mark_tokens = _span_mark_tokens()
        # speculative acceptance over a trailing window (the accept-ratio
        # gauge the heartbeat pushes per replica)
        self._spec_window: deque[tuple[int, int]] = deque(maxlen=200)  # (accepted, proposed)
        self.spec_rounds = 0
        try:
            self.chaos_step_delay = float(os.environ.get(CHAOS_STEP_DELAY_ENV, "0") or 0)
        except ValueError:
            self.chaos_step_delay = 0.0
        self._ttft_window: deque[float] = deque(maxlen=100)
        self._rate_window: deque[tuple[float, int]] = deque()
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._stop = False
        self._thread: Optional[threading.Thread] = None

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "ServingEngine":
        if self._thread is not None:
            return self
        self._thread = threading.Thread(target=self._run, name="serving-engine", daemon=True)
        self._thread.start()
        return self

    def stop(self, timeout: float = 10.0) -> None:
        with self._work:
            self._stop = True
            self._work.notify_all()
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None
        # fail anything still in flight — consumers must not hang
        with self._lock:
            leftovers = [s.request for s in self.slots if s is not None] + list(self.waiting)
            self.slots = [None] * self.max_slots
            self.waiting.clear()
            for req in leftovers:
                self._retired.append(req.id)
        for req in leftovers:
            req._finish(error="engine stopped")
            SERVING_REQUESTS.inc(outcome="stopped")
        # release the prefix caches' page holds (their entries are the one
        # thing that outlives completed requests by design)
        for model in self._models:
            model.clear_prefixes()
        self._sync_page_gauges()

    # -- submission ---------------------------------------------------------

    def submit(
        self,
        prompt: list[int],
        max_new_tokens: int = 64,
        *,
        request_id: str = "",
        eos_token_id: Optional[int] = None,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 1.0,
        seed: int = 0,
        shipment: Optional[dict] = None,  # remotely-prefilled KV bundle (submit_prefilled)
        export: bool = False,  # prefill-only: ship KV pages out (prefill_export)
    ) -> GenRequest:
        """Thread-safe admission into the running loop. Returns immediately;
        consume via the returned request's wait_new/result.

        temperature=0 is exact greedy; temperature>0 samples with optional
        top_k/top_p cuts, keyed by fold_in(PRNGKey(seed), token_index) — the
        stream is bit-reproducible for a fixed seed regardless of batch
        companions or preemption. With MODAL_TPU_SERVING_SAMPLING=0 the
        engine degrades every request to greedy (documented, not an error)."""
        if not prompt:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        temperature = float(temperature)
        if temperature != temperature or temperature < 0 or temperature == float("inf"):
            raise ValueError(f"temperature must be finite and >= 0, got {temperature}")
        top_k = int(top_k)
        if top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {top_k}")
        top_p = float(top_p)
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        # speculative mode reserves spec_k positions of slack: a verify round
        # starting on the request's LAST token still writes k speculative
        # positions past it, and the page table cannot grow past
        # pages_per_slot (an out-of-range assign would silently clamp onto a
        # live table entry and corrupt that slot's KV)
        effective_context = self.max_context - self.spec_k
        if len(prompt) + max_new_tokens > effective_context:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new_tokens}) exceeds the "
                f"engine's context limit ({effective_context} = pages_per_slot × page_size"
                + (f" − spec_k ({self.spec_k})" if self.spec_k else "")
                + ")"
            )
        if self.pages.pages_for(len(prompt) + max_new_tokens) > self.pages.total_pages:
            raise ValueError(
                f"request needs more KV pages than the whole pool ({self.pages.total_pages})"
            )
        if not self.sampling_enabled:
            temperature = 0.0  # degrade: greedy-only engine (SAMPLING_ENV=0)
        req = GenRequest(
            prompt, max_new_tokens, request_id=request_id, eos_token_id=eos_token_id,
            trace_context=tracing.current_context(),
            temperature=temperature, top_k=top_k, top_p=top_p, seed=int(seed),
        )
        req._export = bool(export)
        req._shipment = shipment
        if self.spans_on:
            # per-request timeline root (ISSUE 11): parents under the
            # ambient context when one exists (a .remote() chain), else
            # starts its own trace — either way every lifecycle span below
            # stitches under ONE id, and the TTFT histogram's exemplar
            # resolves to it via `app trace` / `app attribute --serving`
            req.root_span = tracing.open_span(
                "serving.request", attrs={"request_id": req.id, "prompt_tokens": len(prompt)}
            )
            req.trace_context = req.root_span.context
        with self._work:
            if self._stop:
                raise EngineStopped("engine stopped")
            if len(self.waiting) >= self.max_waiting:
                raise EngineStopped(f"admission queue full ({self.max_waiting})")
            self.waiting.append(req)
            self.requests[req.id] = req
            self._retire_requests()
            SERVING_QUEUE_DEPTH.set(float(len(self.waiting)))
            self._work.notify_all()
        return req

    def prefill_export(
        self,
        prompt: list[int],
        *,
        request_id: str = "",
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 1.0,
        seed: int = 0,
    ) -> GenRequest:
        """Prefill-role entry point (ISSUE 18 disaggregation): run ONLY the
        prompt's prefill, emit the single continuation token, and attach the
        finished KV pages to `req.shipment` as a host-side bundle —
        {prompt, first_token, n_tokens, k, v} — ready to ride a blob-plane
        frame to a decode replica. The request completes with exactly one
        token; its slot (and pages, once the prefix-cache entry is the only
        holder) free immediately, so a prefill replica's pool turns over at
        admission rate, not at generation length."""
        self.pages.check_ships("prefill_export")
        return self.submit(
            prompt, max_new_tokens=1, request_id=request_id,
            temperature=temperature, top_k=top_k, top_p=top_p, seed=seed,
            export=True,
        )

    def submit_prefilled(
        self,
        prompt: list[int],
        shipment: Optional[dict],
        max_new_tokens: int = 64,
        *,
        request_id: str = "",
        eos_token_id: Optional[int] = None,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 1.0,
        seed: int = 0,
    ) -> GenRequest:
        """Decode-role admission of a remotely-prefilled prompt: the
        shipment's pages are imported at covered offset (no local prefill),
        its first token is this replica's first emission, and the imported
        prompt is published into the local prefix cache for followers.

        A shipment that doesn't match this engine's geometry — or one the
        chaos knob MODAL_TPU_CHAOS_KV_SHIP_DROP eats — degrades to a plain
        `submit` (full local prefill): token streams are identical either
        way, only TTFT pays (docs/SERVING.md degradation matrix)."""
        if shipment is not None:
            self.pages.check_ships("submit_prefilled")
        if shipment is None:
            # no bundle at all (unreadable kv_ref upstream): plain admission
            return self.submit(
                prompt, max_new_tokens, request_id=request_id, eos_token_id=eos_token_id,
                temperature=temperature, top_k=top_k, top_p=top_p, seed=seed,
            )
        if not (
            prompt
            and list(shipment.get("prompt", ())) == list(prompt)
            and self.pages.shipment_fits(len(prompt), shipment)
        ):
            raise ValueError("shipment does not match this prompt/engine geometry")
        if _consume_kv_ship_drop():
            # chaos: the prefill replica "died mid-ship" — import nothing,
            # prefill locally, lose no tokens
            self.kv_ship_drops += 1
            shipment = None
        return self.submit(
            prompt, max_new_tokens, request_id=request_id, eos_token_id=eos_token_id,
            temperature=temperature, top_k=top_k, top_p=top_p, seed=seed,
            shipment=shipment,
        )

    def get(self, request_id: str) -> Optional[GenRequest]:
        with self._lock:
            return self.requests.get(request_id)

    def _retire_requests(self, keep: int = 512) -> None:
        # bounded completed-request retention (buffered-degrade reads window)
        while len(self.requests) > keep and self._retired:
            victim = self._retired.popleft()
            self.requests.pop(victim, None)

    # -- the loop -----------------------------------------------------------

    def _run(self) -> None:
        logger.debug(
            f"serving engine up: slots={self.max_slots} pages={self.pages.total_pages} "
            f"page_size={self.page_size} pool={self.pages.cache.pool_bytes() / 1e6:.1f}MB"
        )
        try:
            while True:
                with self._work:
                    if not self._stop and not self.waiting and not any(self.slots):
                        self._phase("wait_work")
                        while not self._stop and not self.waiting and not any(self.slots):
                            self._work.wait(timeout=0.5)
                    if self._stop:
                        return
                self.loop_iterations += 1
                try:
                    if self.chaos_step_delay > 0:
                        time.sleep(self.chaos_step_delay)
                    self._admit()
                    self._prefill_one()
                    self._decode_step()
                except Exception as exc:  # noqa: BLE001 — loop must survive
                    logger.exception(f"serving loop iteration failed: {exc}")
                    self._fail_all(f"engine loop error: {type(exc).__name__}: {exc}")
        finally:
            self._phases.close()

    def _fail_all(self, message: str) -> None:
        with self._lock:
            victims = [(i, s) for i, s in enumerate(self.slots) if s is not None]
            self.slots = [None] * self.max_slots
            # error-finished requests must still age out of the registry
            # (the retirement queue is what _retire_requests evicts from)
            for _i, s in victims:
                self._retired.append(s.request.id)
        for i, s in victims:
            for model in self._models:
                model.release(i, on_device=False)  # the failed step may have taken the cache with it
            s.request._finish(error=message)
            SERVING_REQUESTS.inc(outcome="error")
        self._sync_page_gauges()

    def _note_moe_pairs(self, cumulative: Any) -> None:
        """`cache.moe_pairs` and `cache.moe_touched` as they came back with a
        step's tokens: uint32s that wrap, so what counts is how far they moved."""
        pairs, touched = (int(c) for c in cumulative)
        self.moe_local_assignments += (pairs - self._moe_pairs_seen) & 0xFFFFFFFF
        self.moe_experts_touched += (touched - self._moe_touched_seen) & 0xFFFFFFFF
        self._moe_pairs_seen, self._moe_touched_seen = pairs, touched

    def _note_moe_call(self, tokens: int) -> None:
        self.moe_assignments += tokens * self.cfg.experts_per_token * self.moe_expert_layers
        self.moe_expert_calls += self.moe_expert_layers * self.cfg.experts_held[1]

    def _sync_page_gauges(self) -> None:
        """The Prometheus gauges track the target's growing pool."""
        KV_PAGES_ALLOCATED.set(float(self.pages.allocated_pages))
        KV_PAGES_FREE.set(float(self.pages.free_pages))

    def _reserve(self, wants: list) -> bool:
        """wants: [(slot index, first position, last position)] of the next
        step's writes. Every model's pools hold them, each model all or none
        (`ModelPages.reserve`); False is the cue to preempt and ask again."""
        copies = self.pages.cow_copies
        ok = all(model.reserve(wants) for model in self._models)
        if self.pages.cow_copies > copies:
            KV_PAGES_COW.inc(self.pages.cow_copies - copies)
        self._sync_page_gauges()
        return ok

    def _admit(self) -> None:
        """Move waiting requests into free slots while pages allow. FIFO —
        skipping the head for a smaller request would starve long prompts.

        With the prefix cache on, admission first looks the prompt up by
        content: a hit hands the slot refcounted pages holding an already-
        prefilled prefix, and only the suffix pays prefill — the fleet-wide
        system-prompt case prefills once, then every follower's TTFT is the
        suffix's."""
        while True:
            with self._lock:
                if not self.waiting:
                    return
                free_idx = next((i for i, s in enumerate(self.slots) if s is None), None)
                if free_idx is None:
                    return
                req = self.waiting[0]
                self._phase("admit", request_id=req.id)
                prefill_tokens = req.prompt + req.tokens  # preempted: regen prefix too
                shipment = req._shipment
                # each model looks the prompt up in its own prefix cache (the
                # draft's shares full pages only); a shipment brings the
                # target's pages filled, so there is nothing to look up there
                hits = [
                    None if shipment is not None and model is self.pages else model.lookup(prefill_tokens)
                    for model in self._models
                ]
                # every pool of every model must hold it, cached prefixes evicted first
                fits = [model.can_admit(len(prefill_tokens), hit) for model, hit in zip(self._models, hits)]
                if not all(fits):
                    for model, hit in zip(self._models, hits):
                        model.drop(hit)  # the lookup's refs
                    self._sync_page_gauges()
                    return  # pool dry; decode-side preemption or completions will free
                self.waiting.popleft()
                SERVING_QUEUE_DEPTH.set(float(len(self.waiting)))
                slot = _Slot(request=req, prefill_tokens=prefill_tokens, admitted_step=self.step_count)
                self.slots[free_idx] = slot
            # the commit: pages handed out, the rows written, and the hit or miss
            # counted once — not per dry-pool retry above (a remote-prefill
            # import is neither: the prefix work happened on another replica)
            covered = [model.admit(free_idx, len(prefill_tokens), hit) for model, hit in zip(self._models, hits)]
            slot.prefill_done = slot.pos = covered[0]
            slot.draft_prefill_done = covered[1] if self.spec_k else 0
            if hits[0] is not None:
                (SERVING_PREFIX_HITS if covered[0] else SERVING_PREFIX_MISSES).inc()
            req.admitted_at = time.time()
            self.requests_admitted += 1
            self.queue_wait_seconds += req.admitted_at - req.queue_from
            self._sync_page_gauges()
            if req.trace_context is not None:
                # queue segment: creation (or last preemption) → slot grant
                tracing.record_span(
                    "serving.admit",
                    start=req.queue_from,
                    end=req.admitted_at,
                    parent=req.trace_context,
                    attrs={
                        "request_id": req.id,
                        "slot": free_idx,
                        "pages": len(self.pages.pages[free_idx]),
                        "prefix_tokens": covered[0],
                        "draft_prefix_tokens": slot.draft_prefill_done,
                        "remote_prefill": shipment is not None,
                        "requeue": req.preemptions > 0,
                    },
                )
            if shipment is not None:
                self._import_shipment(free_idx, slot, shipment)

    def _import_shipment(self, idx: int, slot: _Slot, shipment: dict) -> None:
        """Land a remotely-prefilled KV bundle in the slot's fresh pages:
        import the page payload, set the slot's length to the covered
        prompt, publish the prompt into the local prefix cache (the imported
        pages serve followers exactly like locally-prefilled ones), and emit
        the shipped continuation token as this replica's first emission. In
        spec mode the target side is done but the draft still prefills
        locally — the slot stays in "prefill" until the mirror catches up."""
        import jax.numpy as jnp
        import numpy as np

        from ..models.paged_kv import set_seq_lens

        req = slot.request
        req._shipment = None  # consumed: a later preemption re-prefills locally
        t0 = time.time()
        n_ship = self.pages.import_shipment(idx, len(req.prompt), shipment)
        lens = np.zeros((self.max_slots,), np.int32)
        upd = np.zeros((self.max_slots,), bool)
        lens[idx] = len(req.prompt)
        upd[idx] = True
        self.pages.cache = set_seq_lens(self.pages.cache, jnp.asarray(lens), jnp.asarray(upd))
        slot.prefill_done = len(slot.prefill_tokens)
        slot.pos = len(req.prompt)
        self.remote_prefills += 1
        if req.trace_context is not None and self.spans_on:
            tracing.record_span(
                "serving.kv_ship",
                start=t0,
                end=time.time(),
                parent=req.trace_context,
                attrs={"request_id": req.id, "side": "import", "pages": n_ship},
            )
        self.pages.publish(idx, req.prompt)
        self._phase("emit", request_id=req.id, tokens=1)
        self._emit_first(idx, slot, int(shipment["first_token"]))

    def _emit_first(self, idx: int, slot: _Slot, tok: int) -> None:
        """The slot's prefill-completion emission (shared by local prefill
        completion and shipment import): first decode feed, TTFT mark, and —
        when the draft mirror (if any) is also resident — the prefill →
        decode state flip."""
        req = slot.request
        slot.cur_token = tok
        slot.first_emitted = True
        if not self.spec_k or slot.draft_prefill_done >= len(slot.prefill_tokens):
            slot.state = "decode"
        slot.last_mark_t = time.time()
        slot.tokens_at_mark = len(req.tokens) + 1  # the token appended below
        req._append(tok)
        if len(req.tokens) == 1:
            self._note_ttft(req)
        self.tokens_generated += 1
        self._note_rate(1)
        self._maybe_finish(idx, slot)

    def _prefill_one(self) -> None:
        """Advance the oldest prefilling slot by one chunk. One chunk per
        loop iteration: decode steps interleave, so in-flight token cadence
        survives long-prompt arrivals.

        Target and draft pools progress INDEPENDENTLY (ISSUE 18): each has
        its own prefix cache, so their covered offsets differ — the target
        may start mid-page (partial-page extension + CoW) while the draft
        starts at its last full-page boundary, and a remote-prefill import
        leaves the target fully covered while the draft still prefills
        locally. The first token goes out the moment the TARGET completes;
        decode waits for both."""
        import jax.numpy as jnp
        import numpy as np

        from ..models.paged_kv import paged_prefill, prefill_bucket, prefill_kv_attended

        with self._lock:
            candidates = [
                (i, s) for i, s in enumerate(self.slots) if s is not None and s.state == "prefill"
            ]
        if not candidates:
            return
        idx, slot = min(candidates, key=lambda t: t[1].admitted_step)
        req = slot.request
        total = len(slot.prefill_tokens)
        target_done_now = False
        logits = None
        next_tok = None
        t0 = time.time()
        if slot.prefill_done < total:
            chunk = slot.prefill_tokens[slot.prefill_done : slot.prefill_done + self.prefill_chunk]
            bucket = prefill_bucket(len(chunk), self.max_context)
            self._phase(
                "prefill_prep", request_id=req.id, chunk_tokens=len(chunk),
                offset=slot.prefill_done, bucket=bucket,
            )
            if not self._reserve([(idx, slot.prefill_done, slot.prefill_done + len(chunk) - 1)]):
                # a copy-on-write (or the window pool, which holds the chunk
                # and the window before it) starved for a page: free capacity
                # the hard way and retry next iteration. The needy slot itself
                # is a valid victim — if it alone holds the pool, preempting it
                # (requeue, pages freed) is the only move that ever unsticks
                # the loop
                self._preempt_youngest()
                return
            padded = np.zeros((bucket,), np.int32)
            padded[: len(chunk)] = chunk
            tokens_j, length_j = jnp.asarray(padded), jnp.int32(len(chunk))
            slot_j, start_j = jnp.int32(idx), jnp.int32(slot.prefill_done)
            self._phase("prefill_dispatch", request_id=req.id)
            logits, next_tok, self.pages.cache = paged_prefill(
                self.params, self.cfg, tokens_j, length_j, self.pages.cache, slot_j, start_j
            )
            self._phase("emit", request_id=req.id, tokens=0)
            # a window pool's chunk gives back all but the window at its end
            self.pages.trim(idx, slot.prefill_done + len(chunk))
            if self.moe_expert_layers:
                self._note_moe_call(len(chunk))
            self.prompt_tokens_prefilled += len(chunk)
            self.prefill_chunks += 1
            self.prefill_bucket_tokens += bucket
            self.prefill_kv_attended += prefill_kv_attended(
                slot.prefill_done + len(chunk), self.pages_per_slot, self.page_size
            )
            self.prefill_kv_span += self.max_context
            if req.trace_context is not None and self.spans_on:
                tracing.record_span(
                    "serving.prefill_chunk",
                    start=t0,
                    end=time.time(),
                    parent=req.trace_context,
                    attrs={
                        "request_id": req.id,
                        "chunk_tokens": len(chunk),
                        "offset": slot.prefill_done,
                        "bucket": bucket,
                    },
                )
            slot.prefill_done += len(chunk)
            slot.pos = slot.prefill_done
            target_done_now = slot.prefill_done >= total
        if self.spec_k and slot.draft_prefill_done < total:
            # the draft mirror advances its own chunk from its own covered
            # offset; draft KV content is chunk-split-independent, so the
            # two pools never desync on values, only on progress
            dchunk = slot.prefill_tokens[
                slot.draft_prefill_done : slot.draft_prefill_done + self.prefill_chunk
            ]
            dbucket = prefill_bucket(len(dchunk), self.max_context)
            self._phase(
                "prefill_prep", request_id=req.id, chunk_tokens=len(dchunk),
                offset=slot.draft_prefill_done, bucket=dbucket, draft=1,
            )
            dpadded = np.zeros((dbucket,), np.int32)
            dpadded[: len(dchunk)] = dchunk
            tokens_j, length_j = jnp.asarray(dpadded), jnp.int32(len(dchunk))
            slot_j, start_j = jnp.int32(idx), jnp.int32(slot.draft_prefill_done)
            self._phase("prefill_dispatch", request_id=req.id, draft=1)
            _dl, _dn, self.draft_pages.cache = paged_prefill(
                self.draft_params, self.draft_cfg, tokens_j, length_j, self.draft_pages.cache, slot_j, start_j
            )
            self._phase("emit", request_id=req.id, tokens=0)
            slot.draft_prefill_done += len(dchunk)
            if slot.draft_prefill_done >= total:
                self.draft_pages.publish(idx, req.prompt)
                if slot.first_emitted and slot.state == "prefill":
                    slot.state = "decode"  # target finished earlier (import)
        if target_done_now:
            # prefill complete: the model's continuation after the whole
            # prefix is a NEW token — for a fresh request the first one
            # (TTFT); for a preempted-and-readmitted one the next one
            # (already-emitted tokens re-entered via prefill_tokens and are
            # never re-appended — the continuation after them is new)
            self.pages.publish(idx, req.prompt)  # the prompt's KV is resident: followers may share it
            self._phase("prefill_sync", request_id=req.id)
            if req.temperature > 0:
                # first/continuation token sampled with the request's own
                # (seed, token-index) key — companion-independent by
                # construction (models/sampling.sample_step)
                from ..models.sampling import sample_step

                tok_arr = sample_step(
                    logits[None, :],
                    jnp.asarray([req.seed], jnp.int32),
                    jnp.asarray([len(req.tokens)], jnp.int32),
                    jnp.asarray([req.temperature], jnp.float32),
                    jnp.asarray([req.top_k], jnp.int32),
                    jnp.asarray([req.top_p], jnp.float32),
                )
                self.sampled_tokens += 1
                SERVING_SAMPLED_TOKENS.inc()
                first_tok = int(tok_arr[0])
            elif self.moe_expert_layers:
                import jax

                counts = self.pages.cache.moe_pairs, self.pages.cache.moe_touched
                first_tok, pairs = jax.device_get((next_tok, counts))  # one fetch
                first_tok = int(first_tok)
                self._note_moe_pairs(pairs)
            else:
                first_tok = int(next_tok)
            self._phase("emit", request_id=req.id, tokens=1)
            if req._export:
                self._export_shipment(idx, slot, first_tok)
            if req.trace_context is not None:
                tracing.record_span(
                    "serving.prefill",
                    start=req.admitted_at or t0,
                    end=time.time(),
                    parent=req.trace_context,
                    attrs={"request_id": req.id, "prompt_tokens": len(slot.prefill_tokens)},
                )
            self._emit_first(idx, slot, first_tok)

    def _export_shipment(self, idx: int, slot: _Slot, first_token: int) -> None:
        """Pull the slot's prompt-covering pages off the device and attach
        them to the request as a shipment bundle (prefill_export path). Runs
        BEFORE the emission below can finish/free the slot — the pages must
        still be live to read."""
        req = slot.request
        t0 = time.time()
        data, n_ship = self.pages.export_shipment(idx, len(req.prompt))
        dt = time.time() - t0
        req.shipment = {
            "prompt": list(req.prompt),
            "first_token": int(first_token),
            "n_tokens": len(req.prompt),
            "k": data["k"],
            "v": data["v"],
        }
        self.kv_pages_shipped += n_ship
        KV_PAGES_SHIPPED.inc(n_ship)
        KV_SHIP_SECONDS.observe(dt)
        if req.trace_context is not None and self.spans_on:
            tracing.record_span(
                "serving.kv_ship",
                start=t0,
                end=t0 + dt,
                parent=req.trace_context,
                attrs={"request_id": req.id, "side": "export", "pages": n_ship},
            )

    def _note_ttft(self, req: GenRequest) -> None:
        ttft = req.first_token_at - req.created_at
        SERVING_TTFT.observe(
            ttft,
            exemplar=req.trace_context.trace_id if req.trace_context is not None else None,
        )
        self._ttft_window.append(ttft)
        window = sorted(self._ttft_window)
        SERVING_TTFT_P95.set(window[min(len(window) - 1, int(0.95 * len(window)))])

    def _note_rate(self, n: int) -> None:
        now = time.time()
        SERVING_TOKENS.inc(n)
        self._rate_window.append((now, n))
        while self._rate_window and now - self._rate_window[0][0] > 10.0:
            self._rate_window.popleft()
        span = max(1e-3, now - self._rate_window[0][0]) if len(self._rate_window) > 1 else 1.0
        SERVING_TOKENS_PER_S.set(sum(c for _, c in self._rate_window) / span)

    def _reserve_decode(self) -> list:
        """Before a decode step: the pools hold every decoding slot's
        upcoming writes (one token, or k+1 in a speculative round). A dry
        pool has evicted its cached prefixes by the time `_reserve` says no;
        then the youngest slot is preempted and the rest ask again. Returns
        the slots that decode, none if nothing can."""
        ahead = self.spec_k  # positions written past the fed token's
        while True:
            with self._lock:
                decoding = [(i, s) for i, s in enumerate(self.slots) if s is not None and s.state == "decode"]
            if not decoding or self._reserve([(i, s.pos, s.pos + ahead) for i, s in decoding]):
                return decoding
            if not self._preempt_youngest():
                return []

    def _preempt_youngest(self) -> bool:
        """Free the most-recently-admitted slot's pages and requeue its
        request (generated prefix preserved: re-admission re-prefills
        prompt+tokens, the stream never sees a duplicate)."""
        with self._lock:
            victims = [(i, s) for i, s in enumerate(self.slots) if s is not None]
            if not victims:
                return False
            idx, slot = max(victims, key=lambda t: t[1].admitted_step)
            self.slots[idx] = None
            self.waiting.appendleft(slot.request)
            SERVING_QUEUE_DEPTH.set(float(len(self.waiting)))
        for model in self._models:
            model.release(idx)
        req = slot.request
        req.preemptions += 1
        self.preemptions += 1
        SERVING_PREEMPTIONS.inc()
        self._sync_page_gauges()
        now = time.time()
        if req.trace_context is not None and self.spans_on:
            # flush the open decode interval, then mark the preemption; the
            # NEXT serving.admit span (anchored at queue_from) covers the
            # requeue wait as `queue` in the attribution
            if slot.last_mark_t and slot.state == "decode":
                tracing.record_span(
                    "serving.decode",
                    start=slot.last_mark_t,
                    end=now,
                    parent=req.trace_context,
                    attrs={"request_id": req.id, "tokens": len(req.tokens), "preempted": True},
                )
            tracing.record_span(
                "serving.preempt",
                start=now,
                end=now,
                parent=req.trace_context,
                attrs={"request_id": req.id, "slot": idx, "tokens_kept": len(req.tokens)},
            )
        req.queue_from = now
        logger.debug(
            f"serving: preempted request {req.id} (slot {idx}, "
            f"{len(req.tokens)} tokens kept)"
        )
        return True

    def _sampling_arrays(self, decoding: list, np) -> tuple:
        """Per-slot (seeds, indices, temps, top_ks, top_ps) for sample_step.
        indices[i] = the slot's NEXT token index (len of its stream) — the
        fold_in coordinate that makes sampling companion-independent."""
        seeds = np.zeros((self.max_slots,), np.int32)
        indices = np.zeros((self.max_slots,), np.int32)
        temps = np.zeros((self.max_slots,), np.float32)
        top_ks = np.zeros((self.max_slots,), np.int32)
        top_ps = np.ones((self.max_slots,), np.float32)
        for i, s in decoding:
            req = s.request
            seeds[i] = req.seed
            indices[i] = len(req.tokens)
            temps[i] = req.temperature
            top_ks[i] = req.top_k
            top_ps[i] = req.top_p
        return seeds, indices, temps, top_ks, top_ps

    def _decode_step(self) -> None:
        import jax.numpy as jnp
        import numpy as np

        from ..models.paged_kv import paged_decode_step

        self._phase("decode_prep")
        if self.spec_k:
            return self._spec_round()
        decoding = self._reserve_decode()
        if not decoding:
            return
        tokens = np.zeros((self.max_slots,), np.int32)
        active = np.zeros((self.max_slots,), bool)
        for i, s in decoding:
            tokens[i] = s.cur_token
            active[i] = True
        tokens_j, active_j = jnp.asarray(tokens), jnp.asarray(active)
        self._phase("decode_dispatch", batch=len(decoding))
        logits, next_tokens, self.pages.cache = paged_decode_step(
            self.params, self.cfg, tokens_j, self.pages.cache, active_j, self.attn_impl
        )
        if any(s.request.temperature > 0 for _i, s in decoding):
            # one extra fixed-shape dispatch ONLY when a sampling request is
            # in the batch — a pure-greedy batch keeps the PR 9 single-
            # dispatch hot path (and sample_step's temp-0 rows are exact
            # argmax, so mixed batches stay bit-identical for greedy slots)
            from ..models.sampling import sample_step

            seeds, indices, temps, top_ks, top_ps = self._sampling_arrays(decoding, np)
            next_tokens = sample_step(
                logits, jnp.asarray(seeds), jnp.asarray(indices),
                jnp.asarray(temps), jnp.asarray(top_ks), jnp.asarray(top_ps),
            )
            n_sampled = sum(1 for _i, s in decoding if s.request.temperature > 0)
            self.sampled_tokens += n_sampled
            SERVING_SAMPLED_TOKENS.inc(n_sampled)
        self._phase("decode_sync", batch=len(decoding))
        if self.moe_expert_layers:
            import jax

            # the held experts' pair count rides with the step's tokens: one fetch
            counts = self.pages.cache.moe_pairs, self.pages.cache.moe_touched
            next_host, pairs = jax.device_get((next_tokens, counts))
            self._note_moe_pairs(pairs)
            self._note_moe_call(len(decoding))
        else:
            next_host = np.asarray(next_tokens)
        self._phase("emit", tokens=len(decoding))
        self.step_count += 1
        SERVING_BATCH_OCCUPANCY.observe(float(len(decoding)))
        emitted = 0
        spans_on, mark_every = self.spans_on, self.span_mark_tokens
        for i, s in decoding:
            s.pos += 1  # the fed token was written at its position
            tok = int(next_host[i])
            s.cur_token = tok
            req = s.request
            req._append(tok)
            emitted += 1
            if spans_on and req.trace_context is not None:
                if req.reached_end() or len(req.tokens) - s.tokens_at_mark >= mark_every:
                    # periodic decode progress mark: contiguous [last mark →
                    # now] coverage, so per-token latency attributes to
                    # `decode` with the step's batch occupancy + KV pool
                    # state attached (ISSUE 11 timelines)
                    now = time.time()
                    tracing.record_span(
                        "serving.decode",
                        start=s.last_mark_t or now,
                        end=now,
                        parent=req.trace_context,
                        attrs={
                            "request_id": req.id,
                            "tokens": len(req.tokens),
                            "batch_occupancy": len(decoding),
                            "kv_pages_free": self.pages.free_pages,
                            "kv_pages_allocated": self.pages.allocated_pages,
                        },
                    )
                    s.last_mark_t = now
                    s.tokens_at_mark = len(req.tokens)
            self._maybe_finish(i, s)
        self.tokens_generated += emitted
        self._note_rate(emitted)

    def _spec_round(self) -> None:
        """One speculative decoding round (ISSUE 12): the draft proposes
        spec_k tokens per slot (k+1 small decode steps — the extra feed
        writes the last proposal's KV so a fully-accepted round leaves the
        draft cache complete), the target verifies all of them in ONE
        `paged_verify_step`, and emission takes the longest prefix where the
        draft matched the target's own sampled/greedy chain, plus the
        target's correction token.

        Exactness: emitted tokens are ALWAYS the target's chain — the draft
        only decides how many land per round. At temperature 0 that chain is
        the target argmax chain; at temperature>0 it is the same
        fold_in(seed, index)-keyed chain the non-speculative path samples.
        Acceptance rate is a throughput knob, never a correctness one.

        With ≥2 decoding slots the round is pipelined: `_spec_dispatch`
        enqueues a slot-group's whole device program without syncing, so
        group B's draft chain overlaps group A's verify — continuous
        batching for the verify stage."""
        decoding = self._reserve_decode()
        if not decoding:
            return
        k = self.spec_k
        t0 = time.time()
        # ISSUE 18 overlap: split the batch in two and enqueue BOTH groups'
        # device work (draft chain + verify + target sampling — all async
        # dispatch, no host sync) before forcing either group's results.
        # Group B's draft steps run while group A's verify is in flight.
        # Per-row ops are batch-composition-independent, and seq_lens rolls
        # are masked per group, so token streams are byte-identical to the
        # non-speculative engine's (test-pinned).
        self.step_count += 1
        SERVING_BATCH_OCCUPANCY.observe(float(len(decoding)))
        groups = [decoding]
        if len(decoding) >= 2:
            mid = (len(decoding) + 1) // 2
            groups = [decoding[:mid], decoding[mid:]]
        pendings = [self._spec_dispatch(g) for g in groups]
        totals = [
            self._spec_accept(g, p, batch=len(decoding)) for g, p in zip(groups, pendings)
        ]
        total_emitted = sum(t[0] for t in totals)
        total_accepted = sum(t[1] for t in totals)
        n_sampled = sum(t[2] for t in totals)

        self.spec_rounds += 1
        self._spec_window.append((total_accepted, k * len(decoding)))
        acc = sum(a for a, _p in self._spec_window)
        prop_total = max(1, sum(p for _a, p in self._spec_window))
        SERVING_SPEC_ACCEPT_RATIO.set(acc / prop_total)
        if n_sampled:
            self.sampled_tokens += n_sampled
            SERVING_SAMPLED_TOKENS.inc(n_sampled)
        if self.spans_on:
            rep = min(decoding, key=lambda t: t[1].admitted_step)[1].request
            if rep.trace_context is not None:
                tracing.record_span(
                    "serving.spec_verify",
                    start=t0,
                    end=time.time(),
                    parent=rep.trace_context,
                    attrs={
                        "proposed": k * len(decoding),
                        "accepted": total_accepted,
                        "batch": len(decoding),
                        "groups": len(groups),
                    },
                )
        self.tokens_generated += total_emitted
        self._note_rate(total_emitted)

    def _spec_dispatch(self, group: list) -> tuple:
        """Enqueue one group's speculative round — k draft decode steps (the
        proposals stay ON DEVICE between steps), the extra draft feed, the
        target verify, and the target-chain sampling — without a single host
        sync. Returns (proposals_dev [slots,k], targets_dev) still in
        flight; `_spec_accept` forces them."""
        import jax.numpy as jnp
        import numpy as np

        from ..models.paged_kv import paged_decode_step, paged_verify_step
        from ..models.sampling import sample_step

        k, k1 = self.spec_k, self.spec_k + 1
        self._phase("decode_prep")
        cur = np.zeros((self.max_slots,), np.int32)
        active = np.zeros((self.max_slots,), bool)
        for i, s in group:
            cur[i] = s.cur_token
            active[i] = True
        active_j = jnp.asarray(active)
        seeds, indices, temps, top_ks, top_ps = self._sampling_arrays(group, np)
        seeds_j, temps_j = jnp.asarray(seeds), jnp.asarray(temps)
        top_ks_j, top_ps_j = jnp.asarray(top_ks), jnp.asarray(top_ps)
        self._phase("decode_dispatch", batch=len(group))

        # 1) draft chain: propose k tokens with the SAME (seed, index) keys
        # the target will sample with — a good draft then agrees often even
        # at temperature > 0 (identical gumbel noise, similar logits)
        props = []
        feed = jnp.asarray(cur)
        for j in range(k):
            dlogits, _g, self.draft_pages.cache = paged_decode_step(
                self.draft_params, self.draft_cfg, feed, self.draft_pages.cache, active_j,
                self.attn_impl,
            )
            prop = sample_step(
                dlogits, seeds_j, jnp.asarray(indices + j), temps_j, top_ks_j, top_ps_j
            )
            props.append(prop)
            feed = prop
        # extra feed: write the last proposal's KV so a fully-accepted round
        # leaves the draft cache complete
        _dl, _dg, self.draft_pages.cache = paged_decode_step(
            self.draft_params, self.draft_cfg, feed, self.draft_pages.cache, active_j, self.attn_impl
        )

        # 2) target verifies [cur, d_1..d_k] in one fixed-shape step
        proposals_dev = jnp.stack(props, axis=1)  # [slots, k]
        fed = jnp.concatenate([jnp.asarray(cur)[:, None], proposals_dev], axis=1)
        vlogits, self.pages.cache = paged_verify_step(self.params, self.cfg, fed, self.pages.cache, active_j)

        # 3) the target's own chain at every verified position
        flat = vlogits.reshape(self.max_slots * k1, vlogits.shape[-1])
        idx_f = (indices[:, None] + np.arange(k1, dtype=np.int32)[None, :]).reshape(-1)
        targets_dev = sample_step(
            flat,
            jnp.asarray(np.repeat(seeds, k1)),
            jnp.asarray(idx_f.astype(np.int32)),
            jnp.asarray(np.repeat(temps, k1)),
            jnp.asarray(np.repeat(top_ks, k1)),
            jnp.asarray(np.repeat(top_ps, k1)),
        )
        return proposals_dev, targets_dev

    def _spec_accept(self, group: list, pending: tuple, batch: int) -> tuple[int, int, int]:
        """Host side of a group's round: force the sync, walk acceptance,
        emit tokens, roll BOTH pools' seq_lens for this group's rows only
        (masked update — the other group's in-flight verify reads its own
        rows untouched), then release finished slots. Returns
        (emitted, accepted, sampled)."""
        import jax.numpy as jnp
        import numpy as np

        from ..models.paged_kv import set_seq_lens

        k, k1 = self.spec_k, self.spec_k + 1
        proposals_dev, targets_dev = pending
        self._phase("decode_sync", batch=len(group))
        proposals = np.asarray(proposals_dev)  # [slots, k] — THE host sync
        targets = np.asarray(targets_dev).reshape(self.max_slots, k1)
        self._phase("emit", tokens=len(group))
        spans_on, mark_every = self.spans_on, self.span_mark_tokens
        new_lens = np.zeros((self.max_slots,), np.int32)
        update = np.zeros((self.max_slots,), bool)
        total_emitted = 0
        total_accepted = 0
        n_sampled = 0
        for i, s in group:
            req = s.request
            emitted = 0
            for j in range(k1):
                tok = int(targets[i, j])
                req._append(tok)
                emitted += 1
                if req.temperature > 0:
                    n_sampled += 1
                if req.reached_end() or j == k:
                    break
                if int(proposals[i, j]) != tok:
                    break  # draft diverged: tok IS the target's correction
                total_accepted += 1
            new_lens[i] = s.pos + emitted
            update[i] = True
            s.pos += emitted
            s.cur_token = int(targets[i, emitted - 1])
            total_emitted += emitted
            if spans_on and req.trace_context is not None:
                if req.reached_end() or len(req.tokens) - s.tokens_at_mark >= mark_every:
                    now = time.time()
                    tracing.record_span(
                        "serving.decode",
                        start=s.last_mark_t or now,
                        end=now,
                        parent=req.trace_context,
                        attrs={
                            "request_id": req.id,
                            "tokens": len(req.tokens),
                            "batch_occupancy": batch,
                            "speculative": True,
                            "kv_pages_free": self.pages.free_pages,
                            "kv_pages_allocated": self.pages.allocated_pages,
                        },
                    )
                    s.last_mark_t = now
                    s.tokens_at_mark = len(req.tokens)

        # roll both pools' lengths to the accepted frontier — the verify
        # wrote k+1 positions, only pos+emitted of them are real; the draft
        # over-advanced by its k+1 feeds and rolls back to match. BEFORE any
        # slot release: a release zeroes the slot's length on the device, and
        # this roll must not scribble a stale value back onto a freed slot
        for model in self._models:
            model.cache = set_seq_lens(model.cache, jnp.asarray(new_lens), jnp.asarray(update))
        for i, s in group:
            self._maybe_finish(i, s)
        return total_emitted, total_accepted, n_sampled

    def _maybe_finish(self, idx: int, slot: _Slot) -> None:
        req = slot.request
        if not req.reached_end():
            return
        with self._lock:
            self.slots[idx] = None
            self._retired.append(req.id)
        for model in self._models:
            model.release(idx)
        self.requests_completed += 1
        SERVING_REQUESTS.inc(outcome="ok")
        self._sync_page_gauges()
        req._finish()

    # -- introspection ------------------------------------------------------

    def prefix_digests(self, limit: int = 512) -> list[str]:
        """Digests of every full-page prefix key the target prefix cache
        currently serves, capped (content-blind: a digest identifies a
        prefix without shipping its tokens). The fleet router folds these
        into its prefix→replica map via /v1/stats (serving/router.py)."""
        from .router import prefix_digest

        return [prefix_digest(key) for key in self.pages.prefix_keys(limit)]

    def stats(self) -> dict:
        from ..observability.device_telemetry import telemetry_summary

        with self._lock:
            active = sum(1 for s in self.slots if s is not None)
            waiting = len(self.waiting)
        acc = sum(a for a, _p in self._spec_window)
        prop = sum(p for _a, p in self._spec_window)
        # the loop's own account of its time, from ONE snapshot of the phase
        # table: wait (nothing to do) + work = seconds, and of the work host
        # (not waiting on the device) + the two syncs (waiting on it)
        phases = self._phases.snapshot()
        by_kind = {"wait": 0.0, "sync": 0.0, "host": 0.0}
        for name, seconds in phases.items():
            by_kind[ENGINE_PHASES[name][1]] += seconds
        work_seconds = by_kind["host"] + by_kind["sync"]
        draft = self.draft_pages.stats() if self.draft_pages is not None else {}
        moe = {
            "assignments": self.moe_assignments,
            "local_assignments": self.moe_local_assignments,
            "expert_calls": self.moe_expert_calls,
            "experts_touched": self.moe_experts_touched,
        }
        return {
            "loop": {
                "iterations": self.loop_iterations,
                "seconds": work_seconds + by_kind["wait"],
                "wait_seconds": by_kind["wait"],
                "work_seconds": work_seconds,
                "host_seconds": by_kind["host"],
                "phase_seconds": phases,
            },
            "requests_admitted": self.requests_admitted,
            "queue_wait_seconds": self.queue_wait_seconds,
            "prompt_tokens_prefilled": self.prompt_tokens_prefilled,
            "prefill_chunks": self.prefill_chunks,
            "prefill_bucket_tokens": self.prefill_bucket_tokens,
            "prefill_kv_attended": self.prefill_kv_attended,
            "prefill_kv_span": self.prefill_kv_span,
            "tracing": {"span_write_seconds": tracing.span_write_seconds()},
            "max_slots": self.max_slots,
            "active_slots": active,
            "waiting": waiting,
            "steps": self.step_count,
            "tokens_generated": self.tokens_generated,
            "sampled_tokens": self.sampled_tokens,
            "requests_completed": self.requests_completed,
            "preemptions": self.preemptions,
            # the pools and the prefix cache of the model served: kv_pages_*,
            # kv_pool_bytes, prefix_cache_*, kv_pages_cow_copies, and
            # kv_window_* only where there are window layers
            **self.pages.stats(),
            **({"moe": moe} if self.moe_expert_layers else {}),
            "attn_impl": self.attn_impl,
            "device": self.device,
            "compile": telemetry_summary(),
            "sampling_enabled": self.sampling_enabled,
            "spec_k": self.spec_k,
            "spec_rounds": self.spec_rounds,
            "spec_accept_ratio": round(acc / prop, 4) if prop else None,
            "role": self.role,
            "remote_prefills": self.remote_prefills,
            "kv_pages_shipped": self.kv_pages_shipped,
            "kv_ship_drops": self.kv_ship_drops,
            "draft_prefix_cache_entries": draft.get("prefix_cache_entries", 0),
            "draft_prefix_cache_hits": draft.get("prefix_cache_hits", 0),
            "prefix_digests": self.prefix_digests(),
            "tokens_per_s": SERVING_TOKENS_PER_S.value(),
            "ttft_p95_s": SERVING_TTFT_P95.value(),
        }
