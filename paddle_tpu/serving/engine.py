"""Continuous-batching serving engine over the static KV-cache decode path.

The GPT flagship already has the fast half of a serving stack: a
single-program decode step with donated fixed-shape cache buffers
(models/gpt.py static cache; the AnalysisPredictor zero-copy run analog).
What it lacked is the request level — this module adds it, in the shape
production LLM servers (vLLM/Orca-style continuous batching) converged on:

* a **slot pool**: ONE set of ``[max_slots+1, max_len, heads, head_dim]``
  per-layer cache buffers; each in-flight request owns a slot row, freed on
  completion and recycled for the next request (SlotPool).  Row max_slots
  is a scratch slot: the padding lanes of a prefix-hit row copy name it.
* a **scheduler loop** (daemon thread): each iteration sweeps
  cancellations/deadlines, admits queued requests into free slots and
  prefills each with the ONE-ROW prefill program of its prompt's
  power-of-two bucket (one program per bucket, so compile count stays
  logarithmic), then runs ONE batched decode step for ALL
  active slots — fixed shapes, so after the first iteration the decode is
  a single compiled program forever, regardless of request churn
  (asserted via the retrace sentinel's signature count).  With the device
  sampler and one token a step it keeps ONE decode step queued behind the
  running one: step n+1 is dispatched from step n's tokens on the device,
  and step n is fetched and emitted under it (docs/serving.md).
* a **request/response API**: ``submit() -> RequestHandle`` (Future-style:
  ``result`` / ``done`` / ``cancel`` / ``exception``), per-token streaming
  callbacks, a bounded admission queue that rejects with
  :class:`QueueFullError` when full (backpressure), and per-request
  deadlines.
* **observability**: spans + flight events for admit/prefill/decode/evict,
  gauges for active slots and queue depth, histograms for time-to-first-
  token and per-token latency — all through the paddle_tpu.observability
  registry, live from request one.  Every scheduler iteration is cut into
  ``serving.*`` phases (docs/serving.md) that reach the JAX profiler's trace.

**Decode fast path** (docs/serving.md "Decode fast path"): decode is
HBM-bandwidth-bound — every step reads the full weights + KV pool to emit
one token per slot (docs/PERF.md round 5) — so three flag-gated,
composable attacks on that bound ride the same single-signature loop:

* ``prefix_cache=True`` — completed requests' KV rows are RETAINED in the
  pool behind a content-addressed index (prefix_cache.PrefixIndex); a
  request whose prompt starts with a cached row's tokens copies the row
  and prefills only the tail (shared system prompts skip re-prefill).
* ``speculative_k=k`` — draft ``k-1`` tokens per step (prompt-lookup
  n-gram drafter by default, ``drafter=`` seam for a draft model) and
  verify all of them in ONE ``k``-wide batched forward; the matched
  prefix is accepted, so each pool read yields up to ``k`` tokens.
  Greedy output stays token-identical to the plain path by construction.
* ``kv_dtype="int8"`` — pools stored int8 with per-row scales
  (models/kv_cache.py), dequantized inside the attention read: half the pool bytes,
  double the slots in the same HBM.

**Multi-LoRA serving** (docs/serving.md "Multi-LoRA serving"):
``Engine(adapters=AdapterRegistry(...))`` serves many LoRA-fine-tuned
variants of the same base weights — per-slot int32 adapter ids gather
each row's low-rank factors from stacked device banks inside the SAME
decode program (bank row 0 = the exact base model), with refcount+LRU
HBM residency and admission-time cold loads; pair with
``weight_dtype="int8"`` to store the base weights themselves quantized.

Sampling runs ON DEVICE by default (``sample_on_device=True``):
temperature / top-k / greedy with per-slot parameters and counter-based
PRNG keys live in the decode program, so only ``[B(, k)]`` token ids —
not ``[B, V]`` logits — cross the host boundary per step.

Per-slot cache positions ride the models' static-cache protocol with a
VECTOR length: ``caches = [(k_buf, v_buf, lengths[B])]`` makes each row
write its new keys at its own offset and attend under a per-row validity
mask (models/gpt.py per-slot branch; the int8 form appends per-row scale
buffers as a 5-tuple).

Thread-safety: the engine runs the model from its scheduler thread via the
functional state swap; do not run the same model's eager forward
concurrently with in-flight requests.
"""
from __future__ import annotations

import atexit
import functools
import itertools
import os
import threading
import time
import weakref
from collections import Counter, deque
from concurrent.futures import CancelledError
from typing import Callable, NamedTuple, Optional

import numpy as np

from ..core.tensor import Tensor
from ..observability import flight, registry, span
from ..observability.trace import phase
from ..observability import perfscope as _perfscope
from ..observability import steps as _steps
from ..observability import watchdog as _watchdog
from ..observability.retrace import instrument_jit
from ..testing import faults
from .kv_tier import HostPrefixTier
from .paged_kv import PageAllocator
from .prefix_cache import PrefixEntry, PrefixIndex
from .slot_pool import SlotPool
from .speculative import NgramDrafter

__all__ = ["Engine", "RequestHandle", "QueueFullError",
           "DeadlineExceededError", "EngineClosedError", "EngineDeadError",
           "EngineDrainingError", "EngineStalledError",
           "RequestInterruptedError"]

# -- metric names (paddle_tpu.observability registry) -------------------------
SERVING_ACTIVE_SLOTS = "paddle_tpu_serving_active_slots"
SERVING_QUEUE_DEPTH = "paddle_tpu_serving_queue_depth"
SERVING_REQUESTS = "paddle_tpu_serving_requests_total"
SERVING_TOKENS = "paddle_tpu_serving_tokens_total"
SERVING_TTFT = "paddle_tpu_serving_ttft_seconds"
SERVING_TOKEN_LATENCY = "paddle_tpu_serving_token_seconds"
SERVING_BATCH_SECONDS = "paddle_tpu_serving_batch_seconds"
SERVING_REDISPATCHED = "paddle_tpu_serving_requests_redispatched_total"
SERVING_INTERRUPTED = "paddle_tpu_serving_requests_interrupted_total"
SERVING_PREFIX_HITS = "paddle_tpu_serving_prefix_cache_hits_total"
SERVING_PREFIX_MISSES = "paddle_tpu_serving_prefix_cache_misses_total"
SERVING_PREFIX_EVICTIONS = "paddle_tpu_serving_prefix_cache_evictions_total"
SERVING_SPEC_DRAFTED = "paddle_tpu_serving_speculative_tokens_drafted_total"
SERVING_SPEC_ACCEPTED = \
    "paddle_tpu_serving_speculative_tokens_accepted_total"
SERVING_KV_POOL_BYTES = "paddle_tpu_serving_kv_pool_bytes"
SERVING_KV_PAGES_FREE = "paddle_tpu_serving_kv_pages_free"
SERVING_KV_PAGES_ACTIVE = "paddle_tpu_serving_kv_pages_active"
SERVING_KV_PAGES_CACHED = "paddle_tpu_serving_kv_pages_cached"
SERVING_KV_COW_COPIES = "paddle_tpu_serving_kv_page_cow_copies_total"
SERVING_ADAPTERS_RESIDENT = "paddle_tpu_serving_adapters_resident"
SERVING_ADAPTER_TOKENS = "paddle_tpu_serving_adapter_tokens_total"
SERVING_ADAPTER_TTFT = "paddle_tpu_serving_adapter_ttft_seconds"
SERVING_ADAPTER_LOADS = "paddle_tpu_serving_adapter_loads_total"
SERVING_ADAPTER_EVICTIONS = "paddle_tpu_serving_adapter_evictions_total"
SERVING_ADAPTER_STALLS = "paddle_tpu_serving_adapter_load_stalls_total"
SERVING_WEIGHT_BYTES = "paddle_tpu_serving_weight_bytes"
SERVING_HOST_PREFIX_HITS = "paddle_tpu_serving_host_prefix_hits_total"
SERVING_HOST_PREFIX_PROMOTES = \
    "paddle_tpu_serving_host_prefix_promotes_total"
SERVING_HOST_PREFIX_PROMOTE_SECONDS = \
    "paddle_tpu_serving_host_prefix_promote_seconds"
SERVING_MOE_ASSIGNMENTS = "paddle_tpu_serving_moe_assignments_total"
SERVING_MOE_EXPERTS_TOUCHED = "paddle_tpu_serving_moe_experts_touched_total"
SERVING_MOE_LOAD_MAX = "paddle_tpu_serving_moe_load_max_total"
SERVING_MOE_ROUTED = "paddle_tpu_serving_moe_routed_total"
SERVING_DECODE_SAMPLED_STEPS = "paddle_tpu_serving_decode_sampled_steps_total"
SERVING_DECODE_TOPK_STEPS = "paddle_tpu_serving_decode_topk_steps_total"
SERVING_PREFILL_WAVES = "paddle_tpu_serving_prefill_waves_total"
SERVING_DECODE_LOOKAHEAD_STEPS = \
    "paddle_tpu_serving_decode_lookahead_steps_total"
SERVING_DECODE_OVERSHOOT_ROWS = \
    "paddle_tpu_serving_decode_overshoot_rows_total"


class QueueFullError(RuntimeError):
    """Admission queue is at capacity — backpressure; retry later."""


class DeadlineExceededError(TimeoutError):
    """The request's deadline passed before it finished."""


class EngineClosedError(RuntimeError):
    """The engine was shut down with this request still in flight."""


class EngineDrainingError(EngineClosedError):
    """The engine is draining: no new admissions, in-flight work finishes
    (the graceful-shutdown analogue of QueueFullError — retry elsewhere)."""


class EngineDeadError(RuntimeError):
    """The scheduler thread crashed: the engine is permanently dead and
    rejects new work, naming the original exception — restarting the loop
    over an already-failed pool would serve garbage.  A request that had
    emitted ZERO tokens when the engine died also fails with this type
    (unless a supervisor re-dispatches it): the caller knows nothing
    reached any consumer, so a retry is duplication-safe."""

    def __init__(self, cause: BaseException):
        super().__init__(
            f"serving scheduler died: {type(cause).__name__}: {cause}")
        self.cause = cause


class EngineStalledError(RuntimeError):
    """The scheduler stopped making progress with work pending (decode
    hang): a supervisor declared the engine dead via :meth:`Engine.abandon`
    — the stuck thread cannot be killed, but the engine stops accepting
    work and its requests are classified exactly like a crash."""


class RequestInterruptedError(RuntimeError):
    """The engine died AFTER this request streamed token(s): replaying it
    elsewhere would duplicate tokens already delivered, so instead of a
    silent re-run the caller gets this typed error naming how far the
    stream got and the underlying engine failure."""

    def __init__(self, request_id: int, tokens_streamed: int,
                 cause: BaseException):
        super().__init__(
            f"request {request_id} interrupted after {tokens_streamed} "
            f"streamed token(s): {type(cause).__name__}: {cause}")
        self.request_id = request_id
        self.tokens_streamed = tokens_streamed
        self.cause = cause


_ids = itertools.count(1)


class RequestHandle:
    """Future-style handle for one submitted request.

    ``result(timeout)`` blocks for the generated token ids (raises the
    request's error instead — CancelledError / DeadlineExceededError /
    EngineClosedError).  ``tokens`` is the stream-so-far and ``logprobs``
    each token's log-probability under the model's own distribution (before
    temperature and top-k), computed by the step that chose it; ``ttft_s`` and
    ``token_latencies_s`` carry the latency telemetry the serving bench
    aggregates into p50/p99.
    """

    def __init__(self, engine, prompt, max_new_tokens, eos_token_id,
                 temperature, top_k, seed, deadline_s, stream,
                 adapter=None, journey=None, conversation=None):
        self.request_id = next(_ids)
        self.redispatches = 0        # times re-enqueued after an engine death
        self.adapter = adapter       # LoRA adapter name (None = base model)
        self.conversation = conversation  # prefix-index namespace qualifier
        self.journey = journey       # observability.journey.Journey or None
        self._adapter_slot = 0       # bank row while active (0 = zero adapter)
        self._adapter_pinned = False
        self.prompt = prompt
        self.max_new_tokens = int(max_new_tokens)
        self.eos_token_id = eos_token_id
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.seed = int(seed)
        self._rng = np.random.RandomState(seed)
        self._stream = stream
        self._engine = engine
        self._state = "queued"            # queued|active|done
        self._torn = False                # torn off a dead/abandoned engine
        self._cancel_requested = False
        self._done = threading.Event()
        self._error: Optional[BaseException] = None
        self._tokens: list[int] = []
        self._logprobs: list[float] = []
        self.slot: Optional[int] = None
        self._prefix_src = None           # PrefixEntry this request copied
        self._prefix_match = 0            # tokens covered by that copy
        self._pages: Optional[list] = None    # paged mode: backing pages
        self._cow = None                  # pending (src, dst) page COW copy
        self._promote = None              # pending (host entry, match) upload
        now = time.perf_counter()
        self.t_submit = now
        self.t_queue = now           # engine-queue entry (reset on resubmit)
        self._stall_t0: Optional[float] = None   # HOL stall began (journey)
        self._stall_kind: Optional[str] = None   # adapter_stall | page_stall
        self.t_admit: Optional[float] = None
        self._t_last_token = now
        self.ttft_s: Optional[float] = None
        self.prefix_hit = False           # admitted via a prefix-cache copy
        self.promote_s: Optional[float] = None  # host-tier promote wall s
        self.token_latencies_s: list[float] = []
        self.deadline = None if deadline_s is None else now + float(deadline_s)

    # -- future surface ------------------------------------------------------
    def done(self) -> bool:
        return self._done.is_set()

    def cancel(self) -> bool:
        """Request cancellation; returns False if already finished.  A
        queued request is failed immediately; an active one is evicted on
        the scheduler's next sweep."""
        return self._engine._request_cancel(self)

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"request {self.request_id} not finished within {timeout}s")
        if self._error is not None:
            raise self._error
        return np.asarray(self._tokens, np.int64)

    def exception(self, timeout: Optional[float] = None):
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"request {self.request_id} not finished within {timeout}s")
        return self._error

    @property
    def tokens(self) -> list[int]:
        """Generated token ids so far (streaming view)."""
        return list(self._tokens)

    @property
    def generated(self) -> list[int]:
        return list(self._tokens)

    @property
    def logprobs(self) -> list[float]:
        """log p(token | context) of each generated token so far."""
        return list(self._logprobs)

    def text(self) -> str:
        """Decode the generated tokens (requires the engine's tokenizer)."""
        tok = self._engine.tokenizer
        if tok is None:
            raise ValueError("engine has no tokenizer")
        return tok.decode(self.tokens)

    # -- engine internals ----------------------------------------------------
    def _finish(self, error: Optional[BaseException] = None):
        # tokens still held back by the engine reach their consumers before
        # the outcome does (a request that never emitted, queued or parked,
        # has none: no callback runs under its caller's locks)
        if self._tokens:
            self._engine._flush_streams()
        self._state = "done"
        # readers (result/exception) block on the _done Event before
        # touching _error, so the Event publishes the write
        self._error = error  # tpu-lint: ok(concurrency)
        self._done.set()

    def _emit(self, token: int, logprob: float):
        if self._done.is_set() or self._torn:
            # the request was torn off a dead/abandoned engine while a
            # stuck dispatch was still in flight: never stream past the
            # interruption point (a parked zero-token handle must STAY
            # zero-token or its re-dispatch would duplicate output)
            return
        self._tokens.append(int(token))
        self._logprobs.append(float(logprob))
        if self._stream is not None:
            self._engine._held_streams.append((self._stream, int(token)))

    def __repr__(self):
        return (f"RequestHandle(id={self.request_id}, state={self._state}, "
                f"slot={self.slot}, tokens={len(self._tokens)})")


def _row_logprob(logits_row: np.ndarray, token: int) -> float:
    """log-softmax of one logits row at `token` (the host sampler's side of
    `RequestHandle.logprobs`)."""
    logits = np.asarray(logits_row, np.float32)
    top = logits.max()
    return float(logits[token] - top - np.log(np.exp(logits - top).sum()))


def _sample_row(logits_row: np.ndarray, temperature: float, top_k: int,
                rng) -> int:
    """Sample one token from one row of last-position logits (host side —
    per-request temperature/top_k/rng; greedy at temperature 0).  The
    reference the device sampler's greedy path is parity-tested against
    (``sample_on_device=False`` escape hatch)."""
    logits = np.asarray(logits_row, np.float32)
    if temperature == 0.0:
        return int(logits.argmax())
    logits = logits / max(temperature, 1e-6)
    if top_k:
        kth = np.partition(logits, -top_k)[-top_k]
        logits = np.where(logits < kth, -1e30, logits)
    logits = logits - logits.max()
    p = np.exp(logits)
    p /= p.sum()
    return int(rng.choice(len(p), p=p))


def _sample_rows(lg, temps, topks, keys, positions, live):
    """Device sampler, one row each: greedy at temp 0, else temperature +
    optional top-k via Gumbel-max (categorical sampling without
    materializing probabilities).  It branches on what its ``live`` rows ask
    for: the draw runs only in a step where one of them has ``temp > 0``,
    the sort behind the top-k mask only where such a row also has
    ``top_k > 0`` (then every row sorts).  Both predicates are scalars over
    the batch, taken outside the ``vmap`` — a batched predicate would lower
    to a select that runs every side — so a step of greedy rows costs the
    argmax alone.  ``live`` keeps a freed slot's leftover parameters out."""
    import jax
    import jax.numpy as jnp
    greedy = jnp.argmax(lg, axis=-1)
    hot = live & (temps > 0)

    def draw(mask_topk: bool):
        def row(l_row, temp, k, key):
            l32 = l_row.astype(jnp.float32) / jnp.maximum(temp, 1e-6)
            if mask_topk:
                v = l_row.shape[-1]
                srt = jnp.sort(l32)                 # ascending
                kth = srt[jnp.clip(v - k, 0, v - 1)]
                l32 = jnp.where((k <= 0) | (l32 >= kth), l32, -1e30)
            g = jax.random.gumbel(key, l32.shape, jnp.float32)
            return jnp.argmax(l32 + g)

        # counter-based per-draw keys: the row's base key folded with the
        # position its logits sit at — stateless, so no key state ever
        # returns to the host, and the draw for 'token after position p' is
        # identical whichever path (cold prefill, tail prefill, decode)
        # produced it
        step_keys = jax.vmap(jax.random.fold_in)(keys, positions)
        sampled = jax.vmap(row)(lg, temps, topks, step_keys)
        return jnp.where(temps > 0, sampled, greedy)

    which = (jnp.any(hot).astype(jnp.int32) +
             jnp.any(hot & (topks > 0)).astype(jnp.int32))
    return jax.lax.switch(which, [lambda: greedy,
                                  functools.partial(draw, False),
                                  functools.partial(draw, True)])


def _sampling_rows(batch) -> tuple[int, int]:
    """Of one admission wave: the requests that draw (`temperature > 0`) and
    those of them that mask (`top_k > 0`) — what the device sampler's two
    branches of that wave's prefill program turn on."""
    hot = [r for r in batch if r.temperature > 0]
    return len(hot), sum(r.top_k > 0 for r in hot)


# a decode dispatch span's KV stats -> the `stats()` counters they sum into
_KV_STATS = {f"kv_{what}{kind}": f"decode_kv_{what}_positions{kind}"
             for what in ("live", "read")
             for kind in ("", "_window", "_global")}


def _trunk(model):
    """The model's transformer stack where it exposes trunk + head (`.gpt`
    of the GPT family, `.decoder` of models/decoder.py), else None: its
    `config` states the position limit and the head count, and the serving
    jits run the head on the gathered positions only."""
    return getattr(model, "gpt", None) or getattr(model, "decoder", None)


def _bucket(n: int, lo: int, hi: int) -> int:
    """Smallest power of two >= n, clamped to [lo, hi] — prompt padding
    buckets keep the prefill compile count logarithmic in max_len."""
    b = lo
    while b < n:
        b *= 2
    return min(b, hi)


class _DecodeStep(NamedTuple):
    """One dispatched decode step until its tokens are fetched and emitted."""
    ordinal: int        # the `step` stat of its dispatch and emit spans
    live: dict          # {slot: request} of the rows it computes
    drafts: dict        # speculative drafts by slot (depth 0 only)
    lengths: np.ndarray  # every row's position as dispatched (parked: idle)
    out: object         # its packed output, on the device
    t0: float           # when it was dispatched


class Engine:
    """Continuous-batching inference engine over a cached decoder model.

    Args:
        model: a Layer with the GPT-style cached forward
            ``model(ids, caches=..., use_cache=True) -> (logits, caches)``
            (e.g. ``GPTForPretraining``); when it exposes ``.gpt`` +
            ``.lm_head`` the head runs only on the gathered positions.
        tokenizer: optional — lets ``submit`` accept strings (``encode``)
            and handles expose ``text()`` (``decode``).
        max_slots: concurrent requests sharing the batched decode step.
        max_len: per-slot cache length; every request needs
            ``len(prompt) + max_new_tokens <= max_len``.
        max_queue: admission-queue bound; submits beyond it raise
            :class:`QueueFullError` (default ``2 * max_slots``).
        prefill_batch: the most requests admitted between two decode
            steps (default ``min(4, max_slots)``).  Not a shape of the
            prefill: each admitted request is one dispatch of the one-row
            prefill program of its own bucket (only the prefix-hit row
            copy still has ``prefill_batch`` lanes).
        eos_token_id: default end-of-sequence id for requests.
        auto_start: start the scheduler thread on first submit (tests set
            False to stage a queue deterministically, then call start()).
        admission_hook: optional ``hook(request, load)`` called by
            ``submit`` after validation, BEFORE the request enters the
            queue, with the would-be :class:`RequestHandle` and a
            :meth:`load` snapshot.  Raising any exception rejects the
            request (counted as ``rejected``) and propagates to the
            caller — the seam an external admission layer (the serving
            gateway) uses to shed load without reaching into engine
            internals.
        redispatch_hook: optional ``hook(requests, cause) -> taken`` called
            from the dying scheduler thread when the engine fails, with the
            zero-tokens-emitted requests (queued or active) and the
            original exception; it returns the subset it takes ownership
            of (an :class:`EngineSupervisor` parks them for re-dispatch
            into the rebuilt engine — SAME handles, so callers never
            notice).  Requests not taken fail with
            :class:`EngineDeadError`; requests that already streamed
            tokens always fail with :class:`RequestInterruptedError` and
            are never offered to the hook.
        decode_timeout_s: arm the PR 2 step watchdog around every batched
            prefill/decode dispatch (default: the
            ``PADDLE_TPU_DECODE_TIMEOUT_S`` env var): a stalled XLA call
            produces a crash-dump bundle naming the stuck phase instead
            of a silent hang, and :meth:`health` exposes the progress age
            a supervisor uses for stall detection.
        prefix_cache: retain completed requests' KV rows behind a
            content-addressed prefix index; admissions sharing a cached
            prompt prefix copy the row and prefill only the tail
            (docs/serving.md "Decode fast path").
        prefix_block: prefix-match granularity in tokens (the index
            registers cached rows at block-boundary prefixes — the
            vLLM-style block hash; smaller blocks match more, hash more).
        speculative_k: verify ``k`` positions per decode dispatch
            (``k - 1`` drafted tokens; 0/1 disables).  Greedy requests
            accept the matched draft prefix — up to ``k`` tokens per pool
            read; sampled (temperature > 0) requests fall back to one
            token per step, correctly sampled, in the same program.
        drafter: ``drafter(context_ids, n) -> n proposed ids`` (default
            :class:`~paddle_tpu.serving.speculative.NgramDrafter`) — the
            seam a learned draft model plugs into.
        kv_dtype: None (model dtype) or ``"int8"`` — store the K/V pools
            quantized with per-row scales, dequantized inside the
            attention read (half the pool bytes → 2x slots in the same
            HBM; see models/kv_cache.py).
        paged_kv: store K/V in fixed-size **pages** instead of dense
            per-slot rows (docs/serving.md "Paged KV").  A host-side
            :class:`~paddle_tpu.serving.paged_kv.PageAllocator` owns the
            refcounted page pool; each slot carries an int32 page table
            that is just another decode-program operand, so the decode
            signature count stays at ONE per config.  HBM scales with
            the tokens actually resident (admission reserves exactly the
            pages a request can write and blocks on page exhaustion),
            sequences may grow past ``max_len`` up to
            ``max_pages_per_slot * page_size``, and prefix-cache hits
            share pages by reference with copy-on-write instead of a
            device row copy.  Greedy output is token-identical to the
            dense pool; composable with every other flag here.
        page_size: positions per page (default ``prefix_block``, 16 —
            the prefix cache's hash granularity is the natural physical
            allocation unit: block-aligned hits share only whole pages).
        num_pages: physical pages in the pool (default
            ``max_slots * ceil(max_len / page_size)`` — dense-equivalent
            capacity; size it to the traffic, not the worst case, for
            the HBM win).
        max_pages_per_slot: page-table width per slot (default
            ``ceil(max_len / page_size)``); sets the virtual per-slot
            length ``max_pages_per_slot * page_size``, which may exceed
            ``max_len`` — long-context past the dense pool's compiled
            row length.
        decode_kernel: ``"xla"`` (default) or ``"pallas"`` — how the
            decode step READS the paged pool.  ``"pallas"`` (requires
            ``paged_kv=True``) routes the per-slot attention read
            through the fused Pallas kernel
            (kernels/paged_attention.py): the page-table walk, the int8
            dequant and the masked softmax run in one custom call that
            DMAs pages straight from HBM — no ``[B, L_virt, ...]``
            gather temp, int8 pools stream int8 bytes.  Greedy output
            is token-identical to the XLA read; decode stays ONE
            compiled signature and composes with every flag here.  On
            the ``cpu`` backend the kernel runs in Pallas interpret mode
            (the parity gate tier-1 exercises); anywhere else Mosaic
            compiles it, and a ``max_pages_per_slot`` x heads whose
            scores row cannot fit VMEM is a ``ValueError`` here.
            The **dense** pool (the default) has no setting: on the TPU
            its decode step reads, per slot, only the 128-position
            blocks that hold live KV and nothing for an idle slot
            (``kernels/paged_attention.py`` ``dense_decode_attention``,
            routed from the same scope where ``dense_read_block``
            applies); on the ``cpu`` backend, for an int8 pool and in
            ``tail_prefill`` the masked XLA read over the whole pool
            stays.  ``stats()`` counts both sides of it:
            ``decode_kv_live_positions`` / ``decode_kv_read_positions``.
        sample_on_device: fuse temperature/top-k/greedy sampling into the
            decode program (per-slot params + counter-based PRNG keys);
            only ``[B(, k)]`` token ids cross the host boundary per step.
            False restores the host sampler (``_sample_row``) — the
            per-request numpy RNG stream, at a ``[B, V]`` logits transfer
            per step.  The device sampler branches on what its live rows
            ask for (``_sample_rows``): all greedy, a step runs the
            argmax alone; a live row with ``temperature > 0`` turns on
            the Gumbel draw, and only one that also has ``top_k > 0`` the
            full-vocabulary sort (a freed slot's leftover parameters
            count for nothing: parked rows are masked out).  ``stats()``
            ``decode_sampled_steps`` / ``decode_topk_steps`` count the
            decode steps that took each branch.
        adapters: an :class:`~paddle_tpu.serving.adapters.AdapterRegistry`
            — serve many LoRA-fine-tuned variants of the base model from
            this one engine (docs/serving.md "Multi-LoRA serving"):
            ``submit(adapter=name)`` rows gather that adapter's factors
            from stacked device banks inside the same decode program
            (bank row 0 = the exact base model).  The registry persists
            across supervisor rebuilds; bank residency (refcount+LRU,
            admission-time cold loads, fully-pinned-bank backpressure)
            is fresh per engine build.
        weight_dtype: None (model dtype) or ``"int8"`` — store the
            serving weight operands quantized per output channel
            (adapters/weight_quant.py), dequantized at the top of each
            serving jit: HBM between steps holds the int8 bytes (the
            weight half of the decode HBM bound; parity-gated).
    """

    def __init__(self, model, tokenizer=None, max_slots: int = 8,
                 max_len: int = 256, max_queue: Optional[int] = None,
                 prefill_batch: Optional[int] = None, eos_token_id=None,
                 auto_start: bool = True,
                 admission_hook: Optional[Callable] = None,
                 redispatch_hook: Optional[Callable] = None,
                 decode_timeout_s: Optional[float] = None,
                 prefix_cache: bool = False,
                 prefix_block: int = 16,
                 speculative_k: int = 0,
                 drafter: Optional[Callable] = None,
                 kv_dtype: Optional[str] = None,
                 sample_on_device: bool = True,
                 paged_kv: bool = False,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 max_pages_per_slot: Optional[int] = None,
                 decode_kernel: str = "xla",
                 adapters=None,
                 weight_dtype: Optional[str] = None,
                 host_prefix_mb: Optional[float] = None,
                 host_prefix=None):
        self.model = model
        self.tokenizer = tokenizer
        self.max_slots = int(max_slots)
        self.max_len = int(max_len)
        if self.max_slots < 1 or self.max_len < 2:
            raise ValueError("need max_slots >= 1 and max_len >= 2")
        cfg = getattr(_trunk(model) or model, "config", None)
        limit = getattr(cfg, "max_position_embeddings", None)
        if limit is not None and self.max_len > int(limit):
            raise ValueError(
                f"max_len={self.max_len} exceeds the model's "
                f"max_position_embeddings={limit}")
        # what this model's layers cannot serve is refused here, by name,
        # never answered wrongly: {option: reason} on the model's class
        asked = {"adapters": adapters is not None,
                 "decode_kernel='pallas'": decode_kernel == "pallas",
                 "paged_kv": bool(paged_kv),
                 "kv_dtype='int8'": kv_dtype == "int8"}
        for option, why in getattr(model, "serving_unsupported", {}).items():
            if asked.get(option):
                raise ValueError(f"{type(model).__name__} cannot be served "
                                 f"with {option}: {why}")
        self.max_queue = (2 * self.max_slots if max_queue is None
                          else int(max_queue))
        self.prefill_batch = (min(4, self.max_slots) if prefill_batch is None
                              else max(1, min(int(prefill_batch),
                                              self.max_slots)))
        self.eos_token_id = eos_token_id
        self._auto_start = bool(auto_start)
        self.admission_hook = admission_hook
        self.redispatch_hook = redispatch_hook
        if decode_timeout_s is None:
            raw = os.environ.get("PADDLE_TPU_DECODE_TIMEOUT_S", "")
            try:
                decode_timeout_s = float(raw)
            except ValueError:
                decode_timeout_s = None
        self._decode_timeout_s = (decode_timeout_s
                                  if decode_timeout_s and
                                  decode_timeout_s > 0 else None)
        # -- decode fast-path flags (each composable, each keeping the
        # ONE-compiled-decode-signature invariant per engine config) --------
        if kv_dtype not in (None, "int8"):
            raise ValueError(f"kv_dtype must be None or 'int8', "
                             f"got {kv_dtype!r}")
        self.kv_dtype = kv_dtype
        self._kv_quant = kv_dtype == "int8"
        k = int(speculative_k)
        if k < 0:
            raise ValueError(f"speculative_k must be >= 0, got {k}")
        self.speculative_k = k
        self._spec_width = max(1, k)          # decode dispatch width
        self._drafter = (drafter if drafter is not None
                         else (NgramDrafter() if self._spec_width > 1
                               else None))
        self.sample_on_device = bool(sample_on_device)
        self._prefix = (PrefixIndex(block=prefix_block) if prefix_cache
                        else None)
        # -- multi-LoRA adapters (docs/serving.md "Multi-LoRA serving"):
        # the registry is PERSISTENT (shared across supervisor rebuilds);
        # the residency tracker — bank slots, pins, LRU — is fresh per
        # engine build, so a rebuilt engine starts with empty banks and
        # zero pins by construction --------------------------------------
        self.adapter_registry = adapters
        self._adapters = None
        if adapters is not None:
            if cfg is None:
                raise ValueError(
                    "adapters= needs a GPT-style model (config with "
                    "hidden_size/num_layers) to size the banks")
            self._adapters = adapters.residency()
        self._adapter_uploads: dict = {}     # name -> bank slot, pending
        self._adapter_load_times: list = []  # cold-load wall seconds
        self._adapter_stalled = False
        # -- int8 base weights (serving/adapters/weight_quant.py) --------
        if weight_dtype not in (None, "int8"):
            raise ValueError(f"weight_dtype must be None or 'int8', "
                             f"got {weight_dtype!r}")
        self.weight_dtype = weight_dtype
        self._weight_quant = weight_dtype == "int8"
        self._weight_bytes = 0
        # -- paged KV pool (docs/serving.md "Paged KV") ----------------------
        self.paged_kv = bool(paged_kv)
        if not self.paged_kv and (page_size is not None or
                                  num_pages is not None or
                                  max_pages_per_slot is not None):
            raise ValueError("page_size/num_pages/max_pages_per_slot "
                             "require paged_kv=True")
        if decode_kernel not in ("xla", "pallas"):
            raise ValueError(f"decode_kernel must be 'xla' or 'pallas', "
                             f"got {decode_kernel!r}")
        if decode_kernel == "pallas" and not paged_kv:
            raise ValueError(
                "decode_kernel='pallas' requires paged_kv=True — the "
                "fused kernel reads the pool through the page table")
        self.decode_kernel = decode_kernel
        self._page_alloc: Optional[PageAllocator] = None
        self._page_tables = None
        if self.paged_kv:
            P = int(prefix_block if page_size is None else page_size)
            if P < 1:
                raise ValueError(f"page_size must be >= 1, got {P}")
            dense_pages = -(-self.max_len // P)          # ceil
            n_pt = (dense_pages if max_pages_per_slot is None
                    else int(max_pages_per_slot))
            if n_pt < 1:
                raise ValueError(
                    f"max_pages_per_slot must be >= 1, got {n_pt}")
            n_pages = (self.max_slots * dense_pages if num_pages is None
                       else int(num_pages))
            if decode_kernel == "pallas":
                # what Mosaic cannot compile is refused here, by name —
                # never run interpreted, never quietly read through XLA
                from ..kernels.paged_attention import check_supported
                check_supported(
                    page_size=P, max_pages_per_slot=n_pt,
                    heads=int(getattr(cfg, "num_attention_heads", 1)),
                    width=self._spec_width)
            self._page_alloc = PageAllocator(n_pages, P)
            self._max_pages_per_slot = n_pt
            # virtual per-slot length: how far a slot's page table can
            # address — may exceed max_len (long context), capped by the
            # model's position-embedding table
            virt = n_pt * P
            self._limit = virt if limit is None else min(virt, int(limit))
        else:
            self._limit = self.max_len

        # -- host-DRAM prefix tier (kv_tier.py; docs/serving.md "KV
        # tiering & conversations"): strictly opt-in.  host_prefix_mb=
        # builds an engine-OWNED tier (closed by shutdown);
        # host_prefix= shares a pre-built tier across supervisor
        # rebuilds / replicas (never closed by this engine) ---------------
        self._host_tier = None
        self._own_host_tier = False
        if host_prefix is not None and host_prefix_mb is not None:
            raise ValueError(
                "pass host_prefix_mb= (engine-owned tier) OR host_prefix= "
                "(shared tier), not both")
        if host_prefix is not None or host_prefix_mb is not None:
            if not (self.paged_kv and self._prefix is not None):
                raise ValueError("the host prefix tier requires "
                                 "paged_kv=True and prefix_cache=True")
            if host_prefix is not None:
                if host_prefix.block != self._prefix.block:
                    raise ValueError(
                        f"host tier block={host_prefix.block} does not "
                        f"match prefix_block={self._prefix.block}")
                self._host_tier = host_prefix
            else:
                self._host_tier = HostPrefixTier(
                    capacity_mb=float(host_prefix_mb),
                    block=self._prefix.block)
                self._own_host_tier = True

        # -- rings (models/kv_cache.py "Rings"): on the dense pool a
        # sliding-window layer's row holds its last `ring_len` positions
        # where that is shorter than max_len; `_ring_block` is the unit the
        # ring is rounded up to (None: no layer is a ring).  What a ring
        # cannot honour is refused here, by name
        self._ring_block = None
        # {(window, ring positions or None): layers of that kind}
        self._kv_kinds: dict = {}
        self._pool_bytes_window = 0
        trunk = _trunk(model)
        windows = (list(trunk.attention_windows())
                   if hasattr(trunk, "attention_windows") else [])
        if not self.paged_kv and any(windows):
            from ..kernels.paged_attention import dense_block
            from ..models.kv_cache import ring_len
            heads = int(cfg.num_attention_heads)
            blk = dense_block(
                heads, int(getattr(cfg, "num_key_value_heads", heads)),
                self.max_len)
            rings = [ring_len(w, self._spec_width, blk, self.max_len)
                     for w in windows if w]
            if min(rings) < self.max_len:
                self._ring_block = blk
                if self._prefix is not None:
                    raise ValueError(
                        f"{type(model).__name__} cannot be served with "
                        f"prefix_cache on the dense pool: a sliding-window "
                        f"layer's row is a ring of its last {min(rings)} "
                        f"positions (max_len {self.max_len}), so the row a "
                        f"prefix hit copies holds its donor's last "
                        f"positions and not the prefix's, and the tail "
                        f"prefill behind it would read them; paged_kv=True "
                        f"keeps every position")

        self._pool = SlotPool(self.max_slots)
        self._queue: deque = deque()
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._stop = False
        self._draining = False
        self._dead: Optional[BaseException] = None
        self._last_progress = time.perf_counter()
        self._thread: Optional[threading.Thread] = None
        self._spawning = False
        self._built = False
        self._values = None
        self._kv_pool = None        # models.kv_cache.KVPool, every layer's
        # stream callbacks of the tokens last emitted, held back until the
        # next program is on the device (`_flush_streams`): their consumers
        # (one gateway thread a stream) then wake while the device works,
        # not while the scheduler thread prepares the dispatch beside them
        self._held_streams: deque = deque()
        self._stream_lock = threading.RLock()
        # the look-ahead: how many decode steps the scheduler keeps queued
        # behind the running one.  1 where step n+1 can be dispatched from
        # what the host knows before it has step n's tokens: the device
        # sampler's packed output holds every row's next input token
        # (merged inside the decode program) and a step emits exactly one
        # token a live row, so lengths, keys and pages are known a step
        # ahead.  0 where the next token exists only on the host (the host
        # sampler) or the next lengths hang on the acceptance computed at
        # emit (speculative decoding): the same loop, nothing queued
        self._lookahead = int(self.sample_on_device and
                              self._spec_width == 1)
        self._flying: Optional[_DecodeStep] = None  # dispatched, unfetched
        self._last_out = None       # device: the newest step's packed output
        self._step_ordinal = 0      # decode steps dispatched
        self._t_landed = 0.0        # when the newest fetch returned
        self._moe_load = False      # the model's expert layers count load
        self._load_counters: list = []   # (stats key, registry counter)
        self._kv_windows: list = []  # per layer: sliding window or None
        self._pool_bytes = 0
        n_rows = self.max_slots + 1           # + scratch row
        self._ids = np.zeros((n_rows, self._spec_width), np.int64)
        # free / cached / scratch rows park at the pool's addressable end
        # (max_len, or the paged virtual length): the decode scatter DROPS
        # their writes (mode="drop"), so K/V retained by the prefix cache
        # is never clobbered by an idle slot's garbage step
        self._park = (self._max_pages_per_slot * self._page_alloc.page_size
                      if self.paged_kv else self.max_len)
        self._lengths = np.full(n_rows, self._park, np.int32)
        if self.paged_kv:
            # per-slot page tables, sentinel-filled: entry num_pages is
            # out of range, so a gather clamps it (masked read) and a
            # scatter at it DROPS the write — unallocated virtual
            # positions are unwritable by construction
            self._page_tables = np.full(
                (n_rows, self._max_pages_per_slot),
                self._page_alloc.num_pages, np.int32)
        # per-slot sampling params + PRNG base keys, pool-resident mirrors
        # uploaded with every dispatch (device draws fold the key with the
        # row's position, so no key state ever crosses back to the host)
        self._temps = np.zeros(n_rows, np.float32)
        self._topks = np.zeros(n_rows, np.int32)
        self._keys = np.zeros((n_rows, 2), np.uint32)
        # per-slot adapter bank row (0 = the zero adapter: base model)
        self._aids = np.zeros(n_rows, np.int32)
        self._counts = {"submitted": 0, "completed": 0, "rejected": 0,
                        "cancelled": 0, "deadline_expired": 0, "failed": 0,
                        "decode_steps": 0, "prefill_batches": 0,
                        "prefill_waves": 0,
                        "prefill_tokens": 0, "prefill_padded_tokens": 0,
                        "decode_kv_live_positions": 0,
                        "decode_kv_read_positions": 0,
                        "decode_kv_live_positions_window": 0,
                        "decode_kv_live_positions_global": 0,
                        "decode_kv_read_positions_window": 0,
                        "decode_kv_read_positions_global": 0,
                        "decode_sampled_steps": 0, "decode_topk_steps": 0,
                        "decode_lookahead_steps": 0,
                        "decode_overshoot_rows": 0,
                        "moe_assignments": 0, "moe_experts_touched": 0,
                        "moe_load_max": 0, "moe_routed": 0,
                        "tokens": 0, "resubmitted": 0, "redispatched": 0,
                        "interrupted": 0, "prefix_hits": 0,
                        "prefix_misses": 0, "prefix_evictions": 0,
                        "prefix_inserts": 0, "spec_drafted": 0,
                        "spec_accepted": 0, "page_cow_copies": 0,
                        "page_alloc_stalls": 0, "adapter_hits": 0,
                        "adapter_loads": 0, "adapter_evictions": 0,
                        "adapter_load_stalls": 0, "host_prefix_hits": 0,
                        "host_prefix_promotes": 0}
        self._active_pages = 0     # pages referenced by in-flight requests
        self._cached_pages = 0     # pages referenced by prefix entries
        self._page_stalled = False
        # HBM ownership ledger rows (observability/perfscope.py): one per
        # long-lived device allocation this build owns, registered by
        # _build and released by shutdown — a rebuilt engine registers
        # fresh rows, so leaked ledger bytes mean leaked HBM
        self._ledger_rows: list = []
        self._ledger_prefix = None     # nested sub-account of kv_pool
        self._row_bytes = 0            # dense pool: bytes per slot row
        self._was_training = model.training
        model.eval()
        # interpreter exit with a live scheduler thread mid-XLA-call
        # aborts the process; the weakref keeps the hook from pinning the
        # engine alive
        ref = weakref.ref(self)
        atexit.register(lambda: (lambda e: e and e.shutdown())(ref()))

    # -- request API ---------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int = 16, eos_token_id=...,
               temperature: float = 0.0, top_k: int = 0, seed: int = 0,
               deadline_s: Optional[float] = None,
               stream: Optional[Callable[[int], None]] = None,
               adapter: Optional[str] = None,
               journey=None,
               conversation: Optional[str] = None) -> RequestHandle:
        """Queue one request; returns a Future-style handle.  Raises
        :class:`QueueFullError` when the bounded admission queue is at
        capacity (backpressure: the caller sheds load or retries) and
        ValueError when the request cannot fit a slot.  ``adapter``
        names a registered LoRA adapter (``Engine(adapters=registry)``);
        unknown names and ranks that can never fit the bank raise the
        registry's typed errors HERE, not after queueing.  ``journey``
        is an optional :class:`~paddle_tpu.observability.journey.Journey`
        the engine appends its phase records to (engine queue wait,
        adapter/page stalls, prefill, each decode dispatch) — the
        request-scoped trace context the gateway threads through the
        whole serving path (docs/observability.md "Request journeys").
        ``conversation`` qualifies the prefix-cache namespace to
        ``(adapter, conversation)`` — turn N+1 of the same conversation
        re-uses turn N's cached KV and pays tail-prefill only
        (docs/serving.md "KV tiering & conversations")."""
        # lock-free monitor-flag reads: _dead/_stop/_draining make single
        # benign transitions; at worst a racing submit lands one sweep
        # late and fails through the death classification instead
        if self._dead is not None:  # tpu-lint: ok(concurrency)
            raise EngineDeadError(self._dead) from self._dead
        if self._stop:
            raise EngineClosedError("engine is shut down")
        if self._draining:
            raise EngineDrainingError(
                "engine is draining; no new admissions")
        if isinstance(prompt, str):
            if self.tokenizer is None:
                raise ValueError("string prompt needs a tokenizer")
            prompt = self.tokenizer.encode(prompt)
        ids = np.asarray(
            prompt._value if isinstance(prompt, Tensor) else prompt
        ).astype(np.int64).reshape(-1)
        if ids.size < 1:
            raise ValueError("empty prompt")
        if int(max_new_tokens) < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if ids.size + int(max_new_tokens) > self._limit:
            what = ("paged limit (max_pages_per_slot * page_size, capped "
                    "by the model's positions)" if self.paged_kv
                    else "max_len")
            raise ValueError(
                f"prompt ({ids.size}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds {what}={self._limit}")
        if self.paged_kv and self._pages_for(
                ids.size + int(max_new_tokens)) > self._page_alloc.num_pages:
            raise ValueError(
                f"request needs {self._pages_for(ids.size + int(max_new_tokens))} "
                f"pages but the pool has only {self._page_alloc.num_pages}")
        if adapter is not None:
            from .adapters.registry import AdapterRankError
            if self._adapters is None:
                raise ValueError(
                    "this engine has no adapter registry "
                    "(Engine(adapters=AdapterRegistry(...)))")
            entry = self.adapter_registry.get(adapter)   # typed: unknown
            if entry.rank > self.adapter_registry.max_rank:
                raise AdapterRankError(
                    f"adapter {adapter!r} rank {entry.rank} exceeds the "
                    f"bank width max_rank="
                    f"{self.adapter_registry.max_rank}: it can never "
                    f"become resident")
        eos = self.eos_token_id if eos_token_id is ... else eos_token_id
        req = RequestHandle(self, ids, max_new_tokens, eos, temperature,
                            top_k, seed, deadline_s, stream,
                            adapter=adapter, journey=journey,
                            conversation=conversation)
        hook = self.admission_hook
        if hook is not None:
            try:
                hook(req, self.load())
            except Exception:
                with self._lock:
                    self._counts["rejected"] += 1
                flight.record("serving", "reject", request=req.request_id,
                              reason="admission_hook")
                registry().counter(
                    SERVING_REQUESTS, "serving requests by outcome").inc(
                    1.0, labels={"outcome": "rejected"})
                raise
        with self._lock:
            if len(self._queue) >= self.max_queue:
                self._counts["rejected"] += 1
                self._gauges_locked()
                flight.record("serving", "reject", request=req.request_id,
                              queue_depth=len(self._queue),
                              max_queue=self.max_queue)
                registry().counter(
                    SERVING_REQUESTS, "serving requests by outcome").inc(
                    1.0, labels={"outcome": "rejected"})
                raise QueueFullError(
                    f"admission queue full ({self.max_queue}); retry later")
            self._queue.append(req)
            self._counts["submitted"] += 1
            self._gauges_locked()
        registry().counter(SERVING_REQUESTS,
                           "serving requests by outcome").inc(
            1.0, labels={"outcome": "submitted"})
        if self._auto_start:
            self.start()
        self._wake.set()
        return req

    def resubmit(self, req: RequestHandle) -> RequestHandle:
        """Re-enqueue a handle taken off a dead engine (the supervisor's
        re-dispatch path): the SAME handle object rides into this
        engine's queue, so a caller blocked on ``result()`` never notices
        the failover.  Only zero-token handles are accepted — re-running
        a request that already streamed tokens would silently duplicate
        delivered output.  Bypasses the admission hook and the queue
        bound (the request was admitted once already)."""
        if req._tokens:
            raise ValueError(
                f"request {req.request_id} already streamed "
                f"{len(req._tokens)} token(s); re-dispatch would "
                f"duplicate them")
        if req.adapter is not None and self._adapters is None:
            raise ValueError(
                f"request {req.request_id} needs adapter "
                f"{req.adapter!r} but this engine has no adapter "
                f"registry")
        if self._dead is not None:
            raise EngineDeadError(self._dead) from self._dead
        if self._stop:
            raise EngineClosedError("engine is shut down")
        req._engine = self
        req._state = "queued"
        req._torn = False       # live again: this engine may emit for it
        req.t_queue = time.perf_counter()   # journey engine_queue restarts
        req._stall_t0 = None
        req._stall_kind = None
        req.slot = None
        req._prefix_src = None  # the dead engine's pool (and index) is gone
        req._prefix_match = 0
        req._pages = None
        req._cow = None
        req._promote = None     # promote refs die with the dead engine's
        req.prefix_hit = False  # admission (_release_pages_locked)
        req._adapter_slot = 0    # the dead engine's banks (and pins) died
        req._adapter_pinned = False
        req.redispatches += 1
        with self._lock:
            self._queue.append(req)
            self._counts["resubmitted"] += 1
            self._gauges_locked()
        flight.record("serving", "resubmit", request=req.request_id,
                      redispatches=req.redispatches)
        registry().counter(
            SERVING_REDISPATCHED,
            "requests re-dispatched after an engine death").inc(
            1.0, labels={"layer": "supervisor"})
        if self._auto_start:
            self.start()
        self._wake.set()
        return req

    def start(self):
        """Start the scheduler thread (idempotent).  The check-and-spawn
        runs under the engine lock: two racing callers (e.g. a gateway
        handler submitting while a supervisor resubmits parked work)
        must never BOTH see a missing thread and spawn two schedulers —
        the second would dispatch against a pool the first is still
        building."""
        if self._dead is not None:
            raise EngineDeadError(self._dead) from self._dead
        if self._stop:
            raise EngineClosedError("engine is shut down")
        # double-checked: the common already-running path stays lock-free
        # (submit calls start() per request); a stale read just falls
        # through to the locked re-check.  The claim happens under the
        # lock but Thread.start() runs OUTSIDE it — the new scheduler's
        # first sweep takes this same lock, and making it queue behind
        # the spawner costs the admission loop its head start.  The
        # _spawning flag covers the claimed-but-not-yet-alive window so
        # two racing callers can never both spawn.
        if self._thread is None or not self._thread.is_alive():
            t = None
            with self._lock:
                if not self._spawning and (self._thread is None or
                                           not self._thread.is_alive()):
                    self._spawning = True
                    t = threading.Thread(
                        target=self._loop, name="paddle-tpu-serving",
                        daemon=True)
                    self._thread = t
            if t is not None:
                try:
                    t.start()
                finally:
                    with self._lock:
                        self._spawning = False

    def join(self, timeout: Optional[float] = None) -> bool:
        """Block until queue and slots are empty; False on timeout."""
        deadline = None if timeout is None else time.perf_counter() + timeout
        while True:
            with self._lock:
                idle = not self._queue and self._pool.n_active == 0
            if idle:
                return True
            if deadline is not None and time.perf_counter() > deadline:
                return False
            time.sleep(0.005)

    def drain(self, deadline_s: float = 30.0) -> bool:
        """Graceful shutdown, phase one: stop admission (new submits
        raise :class:`EngineDrainingError` and ``load()`` advertises
        not-alive so routers stop picking this replica) while the
        scheduler keeps finishing every queued and in-flight request.
        Returns True when all of them completed before the deadline —
        the engine is then idle and a ``shutdown()`` drops nothing."""
        with self._lock:
            self._draining = True
            depth, active = len(self._queue), self._pool.n_active
        flight.record("serving", "drain_begin", queue_depth=depth,
                      active_slots=active, deadline_s=float(deadline_s))
        if (depth or active) and self._dead is None and not self._stop:
            self.start()        # pending work with no scheduler: run it out
        ok = self.join(timeout=deadline_s) and self._dead is None
        flight.record("serving", "drain_done", drained=ok)
        return ok

    def undrain(self):
        """Reverse :meth:`drain` on a replica that never finished
        leaving — the warm-pool route-in (ISSUE 20): a parked spare is
        built and immediately drained (``load()`` advertises not-alive,
        so it refuses work while parked) until a flash scale-up routes
        it back into the fleet.  No-op on a live engine; raises on a
        dead or shut-down one, which must never re-enter a router."""
        if self._dead is not None:
            raise EngineDeadError(self._dead) from self._dead
        if self._stop:
            raise EngineClosedError("engine is shut down")
        with self._lock:
            was = self._draining
            self._draining = False
        if was:
            flight.record("serving", "undrain")

    def abandon(self, cause: Optional[BaseException] = None):
        """A supervisor declares this engine dead from OUTSIDE the
        scheduler thread (decode stall: the thread is stuck inside an
        XLA call and cannot be killed).  The engine stops accepting work
        and its requests are classified exactly as a scheduler crash —
        zero-token requests are offered to the redispatch hook, streamed
        ones get :class:`RequestInterruptedError`.  Idempotent; a no-op
        on an engine that is already dead or shut down."""
        if self._dead is not None or self._stop:
            return
        self._fail_as_dead(cause or EngineStalledError(
            "engine abandoned by its supervisor"))
        self._wake.set()        # a parked scheduler wakes up and exits

    def shutdown(self):
        """Stop the scheduler; in-flight and queued requests fail with
        EngineClosedError.  Restores the model's train/eval mode."""
        if self._stop:
            return
        # monitor flag: single False->True transition, polled by the
        # scheduler loop; a stale read costs one extra 20 ms iteration
        self._stop = True  # tpu-lint: ok(concurrency)
        self._wake.set()
        if self._thread is not None:
            # a DEAD engine's thread is exiting (or, after abandon(),
            # permanently stuck in an XLA call) — don't wait long for it
            self._thread.join(timeout=30 if self._dead is None else 2)
        err = EngineClosedError("engine shut down")
        with self._lock:
            pending = list(self._queue) + list(self._pool.active().values())
            self._queue.clear()
            for slot in list(self._pool.active()):
                req = self._pool.free(slot)
                self._release_pages_locked(req)
                if self._adapters is not None:
                    self._unpin_adapter_locked(req)
            if self._prefix is not None:
                # the pool the cached rows/pages point into is going away
                for e in self._prefix.drop_all():
                    if self.paged_kv and e.pages:
                        for p in e.pages:
                            self._page_alloc.deref(p)
                        self._cached_pages -= len(e.pages)
                for slot in list(self._pool.cached()):
                    self._pool.release_cached(slot)
            if self.paged_kv:
                self._page_alloc.check()     # zero leaked pages at teardown
            if self._adapters is not None:
                self._adapters.check()       # zero leaked adapter pins
            self._gauges_locked()
            ledger_rows, self._ledger_rows = self._ledger_rows, []
            self._ledger_prefix = None
        # this build's HBM is going away with its pools/banks: release
        # the ledger rows (a leaked row here means leaked device bytes —
        # the chaos lane asserts zero after the kill matrix)
        for row in ledger_rows:
            row.release()
        # an engine-OWNED host tier dies with the engine; a SHARED tier
        # (host_prefix=) outlives it on purpose — that is the rebuild /
        # replica survival story, and whoever built it closes it
        if self._own_host_tier and self._host_tier is not None:
            self._host_tier.close()
        _steps.record_memory_stats()
        for req in pending:
            req._finish(err)
        if self._was_training:
            self.model.train()

    close = shutdown

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown()
        return False

    # -- introspection -------------------------------------------------------
    def queue_depth(self) -> int:
        """Unadmitted queued requests right now (O(1), one lock hop)."""
        with self._lock:
            return len(self._queue)

    def slots_in_use(self) -> int:
        """Slots currently owned by in-flight requests (O(1) — the pool
        keeps the count; no slot-array scan).  Cached (prefix-retained)
        rows don't count: they are reclaimable on demand."""
        with self._lock:
            return self._pool.n_active

    def adapter_resident(self, name: str) -> bool:
        """True when the LoRA adapter already occupies a bank row in
        THIS build (loaded or mid-upload) — the router's locality
        tiebreak: dispatching onto a resident replica skips the
        admission-time cold load entirely."""
        with self._lock:
            return (self._adapters is not None and
                    self._adapters.slot_of(name) is not None)

    def load(self) -> dict:
        """One-lock-hop load snapshot for external admission/routing
        (queue depth, slot occupancy, capacity, liveness).  Every field
        comes from O(1) counters — safe to poll per-request from a
        gateway without perturbing the scheduler."""
        with self._lock:
            out = {
                "queue_depth": len(self._queue),
                "slots_in_use": self._pool.n_active,
                "cached_slots": self._pool.n_cached,
                "max_slots": self.max_slots,
                "max_queue": self.max_queue,
                "max_len": self.max_len,
                "alive": (self._dead is None and not self._stop and
                          not self._draining),
                "draining": self._draining,
            }
            if self.paged_kv:
                out["kv_pages_free"] = self._page_alloc.n_free
                out["kv_num_pages"] = self._page_alloc.num_pages
            return out

    def stats(self) -> dict:
        with self._lock:
            out = dict(self._counts)
            out["active_slots"] = self._pool.n_active
            out["queue_depth"] = len(self._queue)
            out["slot_allocs"] = self._pool.alloc_total
            out["slot_reuses"] = self._pool.reuse_total
            out["cached_slots"] = self._pool.n_cached
            out["prefix_entries"] = (0 if self._prefix is None
                                     else len(self._prefix))
            out["kv_pool_bytes"] = self._pool_bytes
            # by layer kind: sliding-window layers (rings on the dense
            # pool, `kv_ring_len` positions a row; 0 = none is a ring) and
            # global layers
            out["kv_pool_bytes_window"] = self._pool_bytes_window
            out["kv_pool_bytes_global"] = (self._pool_bytes -
                                           self._pool_bytes_window)
            out["kv_ring_len"] = max([r or 0 for _, r in self._kv_kinds],
                                     default=0)
            out["weight_bytes"] = self._weight_bytes
            if self._adapters is not None:
                out["adapters_resident"] = self._adapters.n_resident
                out["adapters_pinned"] = self._adapters.n_pinned
                out["adapter_bank_capacity"] = self._adapters.capacity
            if self.paged_kv:
                out["kv_num_pages"] = self._page_alloc.num_pages
                out["kv_page_size"] = self._page_alloc.page_size
                out["kv_pages_free"] = self._page_alloc.n_free
                out["kv_pages_used"] = self._page_alloc.n_used
                out["kv_pages_active"] = self._active_pages
                out["kv_pages_cached"] = self._cached_pages
        if self._host_tier is not None:
            out["host_prefix"] = self._host_tier.stats()
        out.update(self.compile_stats())
        return out

    def pool_bytes(self) -> int:
        """Total bytes of the device KV pools (+ int8 scale buffers);
        0 before the first admission builds them."""
        with self._lock:
            return self._pool_bytes

    def weight_bytes(self) -> int:
        """Device bytes of the serving weight operands as STORED (int8 +
        scale sidecars under ``weight_dtype='int8'``); 0 before the
        first admission builds them."""
        with self._lock:
            return self._weight_bytes

    def compile_stats(self) -> dict:
        """Distinct jit signatures per entry point (retrace sentinel
        counters; decode must stay at 1 — THE continuous-batching
        invariant, with every fast-path flag on)."""
        pf = getattr(self, "_prefill_fn", None)
        dc = getattr(self, "_decode_fn", None)
        tl = getattr(self, "_tail_fn", None)
        cp = getattr(self, "_copy_fn", None)
        return {
            "prefill_compiles": len(pf._signatures) if pf is not None else 0,
            "decode_compiles": len(dc._signatures) if dc is not None else 0,
            "tail_prefill_compiles":
                len(tl._signatures) if tl is not None else 0,
            "prefix_copy_compiles":
                len(cp._signatures) if cp is not None else 0,
        }

    # -- jitted pieces -------------------------------------------------------
    def _build(self):
        import contextlib

        import jax
        import jax.numpy as jnp

        from ..models.kv_cache import KernelRead, KVPool
        from ..nn.functional_call import _swapped_state, state_values

        model = self.model
        n_rows, L = self.max_slots + 1, self.max_len
        on_device = self.sample_on_device
        self._values = state_values(model)

        from ..incubate.distributed.models.moe.dropless import (
            collect_load as _collect_load)

        def _kv_struct():
            def f(vals, ii):
                with _swapped_state(model, vals), _collect_load() as load:
                    _, caches = model(Tensor(ii, _internal=True),
                                      use_cache=True)
                return ([(k._value, None if v is None else v._value)
                         for k, v in caches], load.total())
            return jax.eval_shape(f, self._values,
                                  jnp.zeros((1, 1), jnp.int64))

        # the pools are sized from the cache shapes the model returns (KV
        # heads, not query heads; one latent array and no V from a
        # latent-attention layer); a model with expert layers also counts
        # their load, returned behind each step's tokens
        kv, load_struct = _kv_struct()
        self._moe_load = load_struct is not None
        # the registry's side of the expert load, resolved once: the emit
        # phase of every step adds to these
        self._load_counters = [
            (key, registry().counter(name, what)) for name, key, what in (
                (SERVING_MOE_ASSIGNMENTS, "moe_assignments",
                 "token-to-expert assignments computed (tokens x top-k, "
                 "summed over layers)"),
                (SERVING_MOE_EXPERTS_TOUCHED, "moe_experts_touched",
                 "experts with at least one token, summed over layers and "
                 "steps"),
                (SERVING_MOE_LOAD_MAX, "moe_load_max",
                 "largest expert load of each layer, summed over layers "
                 "and steps"),
                (SERVING_MOE_ROUTED, "moe_routed",
                 "token-to-expert assignments routed (real tokens x top-k, "
                 "summed over layers), whoever holds the expert"))
        ] if self._moe_load else []
        trunk = _trunk(model)
        # per layer: the sliding window its attention reads, None = all
        self._kv_windows = (list(trunk.attention_windows())
                            if hasattr(trunk, "attention_windows")
                            else [None] * len(kv))

        def _leaf_bytes(leaves):
            return sum(int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
                       for x in leaves
                       if hasattr(x, "shape") and hasattr(x, "dtype"))

        if self._weight_quant:
            # int8 base weights: the STORED serving operands go int8 with
            # per-channel f32 scales; every jitted entry dequantizes at
            # the top of its trace, so HBM between steps holds int8 bytes
            # (docs/serving.md "Multi-LoRA serving").
            from .adapters.weight_quant import (dequantize_state,
                                                quantize_state, state_bytes)
            self._values, _wq_dtypes = quantize_state(self._values)
            wbytes = state_bytes(self._values)

            def _dq(vals, _d=_wq_dtypes):
                return dequantize_state(vals, _d)
        else:
            wbytes = _leaf_bytes(self._values.values())

            def _dq(vals):
                return vals
        wrow = _perfscope.ledger().register(
            "weights", wbytes,
            detail=("serving weight operands, int8 + scales"
                    if self._weight_quant else "serving weight operands"))
        with self._lock:
            self._weight_bytes = wbytes
            self._ledger_rows.append(wrow)
        registry().gauge(
            SERVING_WEIGHT_BYTES,
            "device bytes of the serving weight operands as stored").set(
            float(wbytes))

        # -- multi-LoRA adapter banks: fixed-shape device operands every
        # serving dispatch carries (row 0 = the zero adapter) -------------
        use_adp = self._adapters is not None
        if use_adp:
            from .adapters.lora import adapter_scope as _adapter_scope
            areg = self.adapter_registry
            Rcap = self._adapters.capacity
            r_max, n_layers, h = areg.max_rank, areg.num_layers, areg.hidden
            self._abank = jnp.zeros((Rcap + 1, n_layers, h, r_max),
                                    jnp.float32)
            self._bbank = jnp.zeros((Rcap + 1, n_layers, r_max, 3 * h),
                                    jnp.float32)
            self._ascale = jnp.zeros((Rcap + 1,), jnp.float32)
            brow = _perfscope.ledger().register(
                "adapter_bank", areg.bank_nbytes(),
                detail=f"stacked LoRA banks, {Rcap} rows + zero adapter")
            with self._lock:
                self._ledger_rows.append(brow)

        # The decode program's attention read, chosen here once and carried
        # to the model as the static `read` field of its layer caches
        # (prefill and tail-prefill keep the masked XLA read): the paged
        # pool takes its Pallas kernel when asked to
        # (decode_kernel="pallas"); the dense pool its kernel wherever
        # `dense_read_block` says it applies (the TPU; never an int8
        # pool).  `_decode_read_block`: the positions per block or page
        # of that kernel, None on an XLA read (the kv_read count of
        # `_decode_step`).
        if self.paged_kv:
            decode_read = (KernelRead("paged", self._page_alloc.page_size)
                           if self.decode_kernel == "pallas" else None)
        elif kv[0][1] is None:
            from ..kernels.paged_attention import latent_read_block
            blk = latent_read_block(width=int(kv[0][0].shape[-1]),
                                    dtype=kv[0][0].dtype, max_len=L)
            decode_read = None if blk is None else KernelRead("dense", blk)
        else:
            from ..kernels.paged_attention import dense_read_block
            k0 = kv[0][0]
            blk = dense_read_block(
                heads=int(getattr(getattr(trunk, "config", None),
                                  "num_attention_heads", k0.shape[2])),
                kv_heads=int(k0.shape[2]), head_dim=int(k0.shape[3]),
                dtype=jnp.int8 if self._kv_quant else k0.dtype,
                width=self._spec_width, max_len=L)
            decode_read = None if blk is None else KernelRead("dense", blk)
        self._decode_read_block = (None if decode_read is None
                                   else decode_read.block)

        @contextlib.contextmanager
        def _mstate(values, adp, valid=None):
            """Swapped model state, plus the batched-adapter scope when
            the dispatch carries adapter operands.  Yields the collector
            of the expert layers' load over the tokens `valid` marks as
            real."""
            with contextlib.ExitStack() as st:
                st.enter_context(_swapped_state(model, values))
                if adp is not None:
                    st.enter_context(_adapter_scope(*adp))
                yield st.enter_context(_collect_load(valid))

        def _pack(out, load, logits=None):
            """A step's output as ONE array, so that one fetch brings it
            all: the device sampler's token ids, behind them each token's
            log-probability under `logits` (the model's own distribution,
            before temperature and top-k; float32 bits as integers), and
            last the expert load's four counts where the model has expert
            layers.  Host-sampled logits (no `logits` argument) go as they
            are, the counts beside them as a second, 16-byte array."""
            tot = load.total()
            if logits is None:
                return out if tot is None else (out, tot)
            l32 = logits.astype(jnp.float32)
            lp = (jnp.take_along_axis(l32, out[..., None], -1)[..., 0] -
                  jax.nn.logsumexp(l32, axis=-1))
            parts = [out.reshape(-1),
                     jax.lax.bitcast_convert_type(
                         lp, jnp.int32).reshape(-1).astype(out.dtype)]
            if tot is not None:
                parts.append(tot.astype(out.dtype))
            return jnp.concatenate(parts)

        # dense: a row of max_len positions per slot + the scratch row.
        # paged: [num_pages, page_size, kv_heads, head_dim] per layer — HBM
        # holds pages, slots address them through int32 page tables (just
        # another decode operand)
        paged = self.paged_kv
        self._kv_pool = KVPool.zeros(
            kv, layout="paged" if paged else "dense",
            rows=self._page_alloc.num_pages if paged else n_rows,
            row_len=self._page_alloc.page_size if paged else L,
            quantized=self._kv_quant, windows=self._kv_windows,
            ring_span=self._spec_width, ring_block=self._ring_block)
        total = self._kv_pool.nbytes
        rings = self._kv_pool.ring_lens
        led = _perfscope.ledger()
        krow = led.register(
            "kv_pool", total,
            detail=(f"paged KV pool, {self._page_alloc.num_pages} pages"
                    if paged else f"dense KV pool, {n_rows} slot rows" + (
                        f", window layers rings of {max(filter(None, rings))}"
                        if any(rings) else "")))
        # prefix-cache sub-account: cached rows/pages live INSIDE the
        # pool bytes, so the ledger tracks them as a nested owner
        # (informational, never double-counted)
        prow = (led.register(
            "prefix_cache", 0, nested=True,
            detail="retained KV rows/pages (bytes inside kv_pool)")
            if self._prefix is not None else None)
        with self._lock:
            self._pool_bytes = total
            self._pool_bytes_window = sum(
                b for b, w in zip(self._kv_pool.layer_nbytes,
                                  self._kv_windows) if w)
            self._kv_kinds = Counter(
                zip(self._kv_windows, rings))
            self._ledger_rows.append(krow)
            if paged:
                self._page_alloc.bytes_per_page = total // max(
                    self._kv_pool.rows, 1)
            else:
                self._row_bytes = total // n_rows
            if prow is not None:
                self._ledger_prefix = prow
                self._ledger_rows.append(prow)
        registry().gauge(
            SERVING_KV_POOL_BYTES,
            "device bytes of the serving KV pools (incl. int8 scales)"
        ).set(float(total))

        def _fwd_last(ids_t, caches_t, gather_idx=None):
            """(per-row logits at the last real position, new caches); when
            the model exposes trunk + head, the vocab matmul runs on ONLY
            the gathered positions."""
            inner = trunk
            head = getattr(model, "lm_head", None)
            if inner is not None and callable(head):
                x, new_caches = inner(ids_t, caches=caches_t, use_cache=True)
                h = x._value
                h_last = (h[:, -1] if gather_idx is None
                          else h[jnp.arange(h.shape[0]), gather_idx])
                logits = head(Tensor(h_last[:, None],
                                     _internal=True))._value[:, 0]
            else:
                lg, new_caches = model(ids_t, caches=caches_t,
                                       use_cache=True)
                lg = lg._value
                logits = (lg[:, -1] if gather_idx is None
                          else lg[jnp.arange(lg.shape[0]), gather_idx])
            return logits, new_caches

        def _fwd_all(ids_t, caches_t):
            """Logits at EVERY input position — the speculative verify
            needs the model's choice after each drafted prefix."""
            inner = trunk
            head = getattr(model, "lm_head", None)
            if inner is not None and callable(head):
                x, new_caches = inner(ids_t, caches=caches_t, use_cache=True)
                logits = head(Tensor(x._value, _internal=True))._value
            else:
                lg, new_caches = model(ids_t, caches=caches_t,
                                       use_cache=True)
                logits = lg._value
            return logits, new_caches

        park = self._park

        def _tail_valid(ids, lengths, gather_idx):
            # a hit's tail up to its last real position; rows outside the
            # wave are parked
            return ((lengths < park)[:, None] &
                    (jnp.arange(ids.shape[1])[None, :] <=
                     gather_idx[:, None]))

        def prefill(values, ids, pool, addr, prompt_lens, temps, topks,
                    keys, adp=None):
            # the per-request caches are BUILT inside this jit with a
            # python-int length 0 (static prefill: the prompt keeps the
            # causal flash path — the prompt math is the same whatever the
            # pool, so greedy outputs match across layouts bitwise), then
            # the pool writes them where `addr` says: the request's slot
            # index, or its page-table row.  The engine traces it with ONE
            # row (`ids` is [1, bucket]): every lane names a request.
            caches_t = pool.prompt_caches(*ids.shape)
            valid = jnp.arange(ids.shape[1])[None, :] < prompt_lens[:, None]
            with _mstate(_dq(values), adp, valid=valid) as load:
                logits, new_caches = _fwd_last(
                    Tensor(ids, _internal=True), caches_t,
                    gather_idx=prompt_lens - 1)
            pool = pool.with_prompts(new_caches, addr, prompt_lens)
            if on_device:
                toks = _sample_rows(logits, temps, topks, keys,
                                    prompt_lens - 1,
                                    jnp.ones_like(prompt_lens, bool))
                return _pack(toks, load, logits), pool
            return _pack(logits, load), pool

        def decode(values, ids, pool, lengths, tables, temps, topks, keys,
                   carry=None, adp=None):
            # ONE batched step over every slot row (+ scratch): idle rows
            # are parked at the addressable end so their writes DROP (a
            # prefix-cached row is never clobbered) and their logits are
            # garbage that is never read.  ids is [n_rows, W]: W=1 is the
            # plain decode, W=k the speculative verify — same program
            # shape either way.  `tables` (None on the dense pool) rides
            # along as one more int32 operand: ONE signature per engine
            # config.  `carry` (the look-ahead; None at depth 0) is the
            # packed output of the step before, still on the device, and
            # the rows whose input token the host supplies all the same
            # (admitted since, or every row after a fetched step): the
            # others take the token that step chose for them.
            if carry is not None:
                last, host_rows = carry
                ids = jnp.where(host_rows[:, None], ids,
                                last[:n_rows, None].astype(ids.dtype))
            with _mstate(_dq(values), adp, valid=lengths < park) as load:
                logits, new_caches = _fwd_all(
                    Tensor(ids, _internal=True),
                    pool.caches(lengths, tables, read=decode_read))
            pool = pool.updated(new_caches)
            if on_device:
                greedy = jnp.argmax(logits, axis=-1)        # [B, W]
                first = _sample_rows(logits[:, 0], temps, topks, keys,
                                     lengths, lengths < park)
                toks = greedy.at[:, 0].set(first)
                return _pack(toks, load, logits), pool
            return _pack(logits, load), pool

        def tail_prefill(values, ids, pool, lengths, tables, gather_idx,
                         temps, topks, keys, adp=None):
            # prefix-cache hit path: the prompt HEAD is already in the
            # row (copied, or shared by page reference), only the tail
            # runs through the per-slot caches (rows not in this admit
            # batch park at the addressable end: writes drop)
            with _mstate(_dq(values), adp,
                         valid=_tail_valid(ids, lengths, gather_idx)) as load:
                logits, new_caches = _fwd_last(
                    Tensor(ids, _internal=True),
                    pool.caches(lengths, tables), gather_idx=gather_idx)
            pool = pool.updated(new_caches)
            if on_device:
                toks = _sample_rows(logits, temps, topks, keys,
                                    lengths + gather_idx, lengths < park)
                return _pack(toks, load, logits), pool
            return _pack(logits, load), pool

        def prefix_copy(pool, src, dst):
            return pool.copied(src, dst)

        # the pool is donated: prefill/decode update HBM in place (no
        # donation on CPU — it only warns there)
        on_cpu = jax.default_backend() == "cpu"
        self._prefill_fn = instrument_jit(
            jax.jit(prefill, donate_argnums=() if on_cpu else (2,)),
            "serving.prefill")
        self._decode_fn = instrument_jit(
            jax.jit(decode, donate_argnums=() if on_cpu else (2,)),
            "serving.decode")
        self._tail_fn = instrument_jit(
            jax.jit(tail_prefill, donate_argnums=() if on_cpu else (2,)),
            "serving.tail_prefill")
        self._copy_fn = instrument_jit(
            jax.jit(prefix_copy, donate_argnums=() if on_cpu else (0,)),
            "serving.prefix_copy")
        if self._lookahead:
            # what the first step is handed as the output of the step before
            # (every row's token comes from the host then): the packed
            # layout of `_pack`, so that the program keeps ONE signature
            tok = jax.eval_shape(
                lambda: jnp.argmax(jnp.zeros((1, 1)), -1)).dtype
            n_load = load_struct.shape[0] if self._moe_load else 0
            self._last_out = jnp.zeros((2 * n_rows + n_load,), tok)
        with self._lock:
            self._built = True
        # the build just placed the big long-lived allocations: refresh
        # the backend device-memory gauges so a pure-serving process
        # exports them without a train loop in sight
        _steps.record_memory_stats()

    # -- scheduler loop ------------------------------------------------------
    def _loop(self):
        while not self._stop and self._dead is None:
            try:
                did = self._step_once()
            except Exception as e:  # noqa: BLE001 — fail loudly, not hang
                self._fail_as_dead(e)
                raise
            with self._lock:
                # progress heartbeat: freezes while a dispatch is stuck
                # inside XLA (the supervisor's stall detector reads the
                # age via health())
                self._last_progress = time.perf_counter()
            if not did:
                self._flush_streams()
                with phase("serving.wait") as idle:
                    if not self._wake.wait(0.02):
                        idle.drop()         # nobody called: no record
                self._wake.clear()
        # a step still in flight is dropped unfetched: its requests are
        # failed by whoever stopped the loop, never emitted to
        self._flying = None

    def _fail_as_dead(self, cause: BaseException):
        """Death path, from the dying scheduler thread (crash) or a
        supervisor (:meth:`abandon` on a stall): mark the engine DEAD —
        a later submit() must not restart the loop over an already-failed
        pool — then classify the in-flight work by what already reached a
        consumer: requests with ZERO streamed tokens are duplication-safe
        and are offered to the redispatch hook (untaken ones fail with
        EngineDeadError); requests that streamed tokens fail with the
        typed RequestInterruptedError, never a silent replay."""
        with self._lock:
            if self._dead is not None:      # lost the race: already dead
                return
            # single None->exc transition; racing lock-free readers at
            # worst see the engine alive one sweep late
            self._dead = cause  # tpu-lint: ok(concurrency)
            queued = list(self._queue)
            active = list(self._pool.active().values())
            self._queue.clear()
            for slot in list(self._pool.active()):
                req = self._pool.free(slot)
                self._release_pages_locked(req)
                if self._adapters is not None:
                    self._unpin_adapter_locked(req)
            if self._prefix is not None:
                # dead pool: every cached row/page dies with it — a
                # rebuilt engine starts with an EMPTY index and a fresh
                # allocator (no stale-row or stale-page reuse)
                for e in self._prefix.drop_all():
                    if self.paged_kv and e.pages:
                        for p in e.pages:
                            self._page_alloc.deref(p)
                        self._cached_pages -= len(e.pages)
                for slot in list(self._pool.cached()):
                    self._pool.release_cached(slot)
            for r in queued + active:
                # freeze the token streams FIRST: after abandon() a
                # stuck dispatch may still come back and try to emit
                r._torn = True
                r._prefix_src = None
        flight.record("serving", "scheduler_error",
                      error=f"{type(cause).__name__}: {cause}",
                      queued=len(queued), active=len(active))
        fresh = [r for r in queued + active if not r._tokens]
        streamed = [r for r in active if r._tokens]
        taken_ids: set = set()
        hook = self.redispatch_hook
        if hook is not None and fresh:
            try:
                taken_ids = {id(r) for r in hook(list(fresh), cause)}
            except Exception:  # noqa: BLE001
                taken_ids = set()   # a broken hook must not mask the death
        lost = [r for r in fresh if id(r) not in taken_ids]
        with self._lock:
            self._counts["failed"] += len(lost) + len(streamed)
            self._counts["redispatched"] += len(taken_ids)
            self._counts["interrupted"] += len(streamed)
            self._gauges_locked()
        for r in lost:
            r._finish(EngineDeadError(cause))
        reg = registry()
        for r in streamed:
            flight.record("serving", "interrupted", request=r.request_id,
                          tokens=len(r._tokens))
            reg.counter(SERVING_INTERRUPTED,
                        "requests failed mid-stream by an engine death"
                        ).inc(1.0)
            r._finish(RequestInterruptedError(
                r.request_id, len(r._tokens), cause))
        if taken_ids:
            flight.record("serving", "handoff", n=len(taken_ids),
                          requests=",".join(
                              str(r.request_id) for r in fresh
                              if id(r) in taken_ids))

    def _step_once(self) -> bool:
        """One scheduler iteration: sweep, admit (one-row prefills), the
        dispatch of one batched decode step and the fetch and emit of the
        one before it (`_decode_step`).  Returns whether any work happened.
        Every instant of it lies in one ``serving.*`` leaf phase (the table
        in docs/observability.md)."""
        faults.fault_point("serving.scheduler")
        # unlocked reads: the counts only label the iteration's record
        with phase("serving.iteration", active=self._pool.n_active,
                   queued=len(self._queue)) as it:
            with phase("serving.sweep"):
                self._sweep()
            did = self._admit()
            did = self._decode_step() or did
            if not did:
                it.drop()       # an idle turn leaves the span ring alone
        return did

    def health(self) -> dict:
        """Liveness snapshot: ``alive`` is True only while the engine can
        still take and make progress on requests.  ``progress_age_s`` is
        the time since the scheduler last completed an iteration — with
        work pending, a growing age means the thread is stuck inside a
        dispatch (the supervisor's stall signal)."""
        with self._lock:
            active, depth = self._pool.n_active, len(self._queue)
            progress_age = time.perf_counter() - self._last_progress
            built = self._built
        return {
            "alive": (self._dead is None and not self._stop and
                      not self._draining),
            "dead": self._dead is not None,
            "draining": self._draining,
            "error": (None if self._dead is None
                      else f"{type(self._dead).__name__}: {self._dead}"),
            "stopped": self._stop,
            "scheduler_running": (self._thread is not None and
                                  self._thread.is_alive()),
            "active_slots": active,
            "queue_depth": depth,
            "progress_age_s": progress_age,
            # warm = the decode program exists: dispatches are now
            # bounded, so a frozen progress age means a genuine stall
            # (cold engines legitimately sit in multi-second compiles)
            "warm": built and
            self.compile_stats()["decode_compiles"] >= 1,
        }

    def _sweep(self):
        """Evict cancelled / past-deadline requests (queued and active)."""
        now = time.perf_counter()
        to_finish = []
        with self._lock:
            for req in list(self._queue):
                if req._cancel_requested or (req.deadline is not None and
                                             now > req.deadline):
                    self._queue.remove(req)
                    outcome = ("cancelled" if req._cancel_requested
                               else "deadline_expired")
                    self._evicted_counters_locked(req, outcome)
                    to_finish.append((req, outcome))
            for slot, req in self._pool.active().items():
                if req._cancel_requested or (req.deadline is not None and
                                             now > req.deadline):
                    outcome = ("cancelled" if req._cancel_requested
                               else "deadline_expired")
                    self._evict_locked(req, outcome)
                    to_finish.append((req, outcome))
            self._gauges_locked()
        for req, outcome in to_finish:
            err = (CancelledError() if outcome == "cancelled" else
                   DeadlineExceededError(
                       f"request {req.request_id} missed its deadline"))
            req._finish(err)

    def _request_cancel(self, req: RequestHandle) -> bool:
        if req.done():
            return False
        req._cancel_requested = True
        with self._lock:
            if req in self._queue:       # not yet admitted: fail right away
                self._queue.remove(req)
                self._evicted_counters_locked(req, "cancelled")
                self._gauges_locked()
                req._finish(CancelledError())
                return True
        self._wake.set()                 # active: next sweep evicts
        return True

    # -- admission -----------------------------------------------------------
    def _pages_for(self, n_tokens: int) -> int:
        """Pages covering positions [0, n_tokens) at the pool page size."""
        return -(-int(n_tokens) // self._page_alloc.page_size)

    @staticmethod
    def _journey_admit_locked(req: RequestHandle, **attrs):
        """Close the request's engine-queue window on its journey: one
        ``engine_queue`` phase (queue entry -> admit), split at the
        stall boundary into an explicit ``adapter_stall`` /
        ``page_stall`` phase when the head-of-line request spent part of
        that window blocked on bank pins or page exhaustion — the
        attribution that turns "TTFT was 480 ms" into "300 ms of it was
        a page stall"."""
        j = req.journey
        if j is None:
            return
        stall_t0, kind = req._stall_t0, req._stall_kind
        req._stall_t0 = None
        req._stall_kind = None
        if stall_t0 is not None and kind is not None and \
                stall_t0 > req.t_queue:
            j.phase("engine_queue", req.t_queue, stall_t0 - req.t_queue,
                    **attrs)
            j.phase(kind, stall_t0, req.t_admit - stall_t0)
        else:
            j.phase("engine_queue", req.t_queue,
                    req.t_admit - req.t_queue, **attrs)

    def _mark_stall_locked(self, req: RequestHandle, kind: str):
        """First time the head-of-line request blocks this episode:
        remember when, so the admit-time journey phase can attribute the
        stalled tail of the queue wait to its cause."""
        if req._stall_t0 is None:
            req._stall_t0 = time.perf_counter()
            req._stall_kind = kind

    def _pin_adapter_locked(self, req: RequestHandle) -> bool:
        """Make the request's adapter RESIDENT and pinned before its slot
        is taken, scheduling a cold bank upload when needed.  False means
        every bank row is pinned by other in-flight work — the request
        stays QUEUED (head-of-line backpressure, the same semantics as
        page exhaustion; admitted work never waits, so the bank always
        frees up)."""
        if req.adapter is None or req._adapter_pinned:
            return True
        res = self._adapters
        ev0 = res.evictions
        got = res.acquire(req.adapter)
        if got is None:
            self._mark_stall_locked(req, "adapter_stall")
            if not self._adapter_stalled:
                self._adapter_stalled = True
                self._counts["adapter_load_stalls"] += 1
                flight.record("serving", "adapter_load_stall",
                              request=req.request_id, adapter=req.adapter,
                              resident=res.n_resident)
                registry().counter(
                    SERVING_ADAPTER_STALLS,
                    "admissions stalled on a fully-pinned adapter bank"
                ).inc(1.0)
            return False
        slot, cold = got
        self._adapter_stalled = False
        req._adapter_slot = slot
        req._adapter_pinned = True
        dev = res.evictions - ev0
        if dev:
            self._counts["adapter_evictions"] += dev
            flight.record("serving", "adapter_evict", n=dev,
                          request=req.request_id,
                          for_adapter=req.adapter)
            registry().counter(
                SERVING_ADAPTER_EVICTIONS,
                "refs-0 adapters evicted from the bank (LRU)").inc(
                float(dev))
        if cold:
            if req.adapter not in self._adapter_uploads:
                self._counts["adapter_loads"] += 1
                self._adapter_uploads[req.adapter] = (slot, req.request_id)
        else:
            self._counts["adapter_hits"] += 1
        return True

    def _unpin_adapter_locked(self, req: RequestHandle):
        """Drop the request's pin (the bank row stays resident at refs 0
        for the next hit; only LRU pressure reclaims it)."""
        if req._adapter_pinned:
            self._adapters.release(req.adapter)
            req._adapter_pinned = False
        req._adapter_slot = 0

    def _req_ns(self, req: RequestHandle):
        """Prefix-index namespace for one request: the adapter alone, or
        ``(adapter, conversation)`` when the request carries a
        conversation id — each conversation owns its cached turns, so a
        returning user's turn N+1 hits turn N's KV and nobody else's."""
        return (req.adapter if req.conversation is None
                else (req.adapter, req.conversation))

    def _demote_locked(self, e):
        """Hand an evicted prefix entry's page bytes to the host tier.

        The gather (``pool[pages]`` per layer per pool group) is EAGER
        and runs here, under the lock, BEFORE the pages are deref'd:
        the engine's jits donate the pools operand on device, so a raw
        ``self._kv_pool`` snapshot is invalidated by the very next
        dispatch — fresh gathered arrays are the only thing the spill
        worker can safely ``device_get`` later, off this hot path."""
        if self._kv_pool is None or not e.pages:
            return
        try:
            import jax.numpy as jnp
            idx = jnp.asarray(np.asarray(e.pages, np.int32))
            gathered = [[pool[idx] for pool in grp]
                        for grp in self._kv_pool.groups()]
        except Exception:  # noqa: BLE001 — a dying device must not
            return         # turn an eviction into an engine failure
        self._host_tier.demote_async(e.ns, e.tokens, gathered)

    def _admit_dense_locked(self):
        """Dense-pool admission: head-of-queue requests admit while a
        free slot AND (when they name one) a pinnable adapter bank row
        are available, evicting unreferenced prefix rows under slot
        pressure.  An unpinnable adapter is head-of-line backpressure
        (FIFO fairness, like page exhaustion in the paged pool)."""
        evicted = 0
        want = min(self.prefill_batch, len(self._queue))
        if want == 0:
            self._adapter_stalled = False
            return [], 0
        if self._prefix is not None and want > self._pool.n_free:
            # reclaim cache capacity: LRU unreferenced entries go back
            # to the free list.  Referenced rows (copy sources for
            # in-flight requests) survive the sweep, and so do the
            # entries the incoming wave itself is about to hit — a
            # peek pass finds them first, otherwise a fully-cached
            # pool would evict exactly the rows the queue wants
            protect = set()
            for req in itertools.islice(self._queue, want):
                hit = self._prefix.lookup(req.prompt, peek=True,
                                          ns=self._req_ns(req))
                if hit is not None:
                    protect.add(id(hit[0]))
            for e in self._prefix.evict_lru(want - self._pool.n_free,
                                            protect=protect):
                self._pool.release_cached(e.slot)
                self._counts["prefix_evictions"] += 1
                evicted += 1
                flight.record("serving", "prefix_evict", slot=e.slot,
                              cached_tokens=e.n)
        batch = []
        while self._queue and len(batch) < want and self._pool.n_free > 0:
            req = self._queue[0]
            if not self._pin_adapter_locked(req):
                break
            self._queue.popleft()
            req.slot = self._pool.alloc(req)
            req._state = "active"
            req.t_admit = time.perf_counter()
            self._journey_admit_locked(req, slot=req.slot)
            if self._prefix is not None:
                hit = self._prefix.lookup(req.prompt,
                                          ns=self._req_ns(req))
                if hit is not None:
                    entry, matched = hit
                    self._prefix.acquire(entry)
                    req._prefix_src = entry
                    req._prefix_match = matched
                    req.prefix_hit = True
                    self._counts["prefix_hits"] += 1
                else:
                    self._counts["prefix_misses"] += 1
            batch.append(req)
        return batch, evicted

    def _admit_paged_locked(self):
        """Paged-pool admission: head-of-queue requests admit while a
        slot lane AND their page reservation both fit.  A request
        reserves every page it can ever write (``ceil((prompt +
        max_new_tokens) / page_size)``, minus fully-shared prefix
        pages), so decode can never hit mid-flight page exhaustion —
        exhaustion is an ADMISSION condition: the request stays queued
        (backpressure, like slot exhaustion in the dense pool) until
        retiring work or cache eviction frees pages.  No deadlock:
        admitted requests never wait on pages, so they always retire."""
        alloc = self._page_alloc
        P = alloc.page_size
        evicted = 0
        want = min(self.prefill_batch, len(self._queue))
        if want == 0:
            # stall episode over (the stalled request retired or was
            # cancelled): the next exhaustion is a fresh flight event
            self._page_stalled = False
            self._adapter_stalled = False
            return [], 0
        protect = set()
        if self._prefix is not None:
            for req in itertools.islice(self._queue, want):
                hit = self._prefix.lookup(req.prompt, peek=True,
                                          ns=self._req_ns(req))
                if hit is not None:
                    protect.add(id(hit[0]))
        batch = []
        while self._queue and len(batch) < want and self._pool.n_free > 0:
            req = self._queue[0]
            if not self._pin_adapter_locked(req):
                break                # HOL backpressure: bank fully pinned
            total = self._pages_for(req.prompt.size + req.max_new_tokens)
            hit = (self._prefix.lookup(req.prompt, peek=True,
                                       ns=self._req_ns(req))
                   if self._prefix is not None else None)
            # an HBM miss probes the host tier (kv_tier.py): a host hit
            # still allocates the FULL reservation — the promoted prefix
            # uploads into this request's own fresh pages
            # (_flush_promotes), then shares them back into the device
            # index, so `need` stays `total` here
            promote = (self._host_tier.lookup(req.prompt, peek=True,
                                              ns=self._req_ns(req))
                       if hit is None and self._host_tier is not None
                       else None)
            # fully-matched pages are shared by reference; a partial
            # boundary page (match not page-aligned) is replaced by a
            # one-page COW copy, so its replacement stays in `need`
            shared_full = (hit[1] // P) if hit is not None else 0
            need = total - shared_full
            while (need > alloc.n_free and self._prefix is not None):
                # reclaim pages from unreferenced LRU entries, sparing
                # the ones this wave is about to hit; with a host tier
                # attached the victim's bytes demote instead of dying
                victims = self._prefix.evict_lru(1, protect=protect)
                if not victims:
                    break
                e = victims[0]
                if self._host_tier is not None and e.pages:
                    self._demote_locked(e)
                for p in e.pages:
                    alloc.deref(p)
                self._cached_pages -= len(e.pages)
                self._counts["prefix_evictions"] += 1
                evicted += 1
                flight.record("serving", "prefix_evict",
                              pages=len(e.pages), cached_tokens=e.n)
            pages = alloc.alloc(need)
            if pages is None:
                # page exhaustion: head-of-line request stays queued
                # (FIFO fairness — no small-request overtake that would
                # starve the head); the pin taken above is dropped so a
                # parked request never holds bank capacity; flight-record
                # the stall once per stall episode, not per 20 ms sweep
                self._unpin_adapter_locked(req)
                self._mark_stall_locked(req, "page_stall")
                if not self._page_stalled:
                    self._page_stalled = True
                    self._counts["page_alloc_stalls"] += 1
                    flight.record("serving", "page_alloc_stall",
                                  request=req.request_id, need=need,
                                  free=alloc.n_free,
                                  cached_pages=self._cached_pages)
                break
            self._page_stalled = False
            self._queue.popleft()
            req.slot = self._pool.alloc(req)
            req._state = "active"
            req.t_admit = time.perf_counter()
            self._journey_admit_locked(req, slot=req.slot,
                                       pages_reserved=len(pages),
                                       pages_shared=shared_full)
            if hit is not None:
                entry, matched = hit
                self._prefix.touch(entry)      # count the peeked hit
                self._prefix.acquire(entry)
                req._prefix_src = entry
                req._prefix_match = matched
                req.prefix_hit = True
                self._counts["prefix_hits"] += 1
            elif promote is not None:
                # HBM miss, host hit: still a device-index miss (both
                # counters tell the truth), but the upload in
                # _flush_promotes turns it into a normal zero-copy hit
                # before prefill — tail-only from there on
                hentry, matched = promote
                self._host_tier.touch(hentry)  # count the peeked hit
                self._host_tier.acquire(hentry)   # un-droppable mid-flight
                req._promote = (hentry, matched)
                self._counts["host_prefix_hits"] += 1
                self._prefix.miss()
                self._counts["prefix_misses"] += 1
            elif self._prefix is not None:
                self._prefix.miss()
                self._counts["prefix_misses"] += 1
                if self._host_tier is not None:
                    self._host_tier.miss()     # missed BOTH tiers
            self._map_pages_locked(req, pages)
            batch.append(req)
        return batch, evicted

    def _map_pages_locked(self, req: RequestHandle, fresh):
        """Fill the slot's page table: the hit entry's fully-matched
        pages by reference (refcount++ each), then the fresh pages.
        When the hit boundary lands inside a shared page, schedule the
        copy-on-write clone of exactly that page into the first fresh
        page — the writer diverges on a private copy, the cached
        entry's bytes are untouched."""
        alloc = self._page_alloc
        P = alloc.page_size
        table = self._page_tables[req.slot]
        table[:] = alloc.num_pages
        pages = []
        m = req._prefix_match
        shared_full = m // P
        req._cow = None
        if req._prefix_src is not None:
            src_pages = req._prefix_src.pages
            for i in range(shared_full):
                alloc.share(src_pages[i])
                table[i] = src_pages[i]
                pages.append(src_pages[i])
            if m % P:
                req._cow = (src_pages[shared_full], fresh[0])
        for j, p in enumerate(fresh):
            table[shared_full + j] = p
            pages.append(p)
        req._pages = pages
        self._active_pages += len(pages)

    def _admit(self) -> bool:
        import jax

        with phase("serving.admit"), self._lock:
            if self.paged_kv:
                batch, evicted = self._admit_paged_locked()
            else:
                batch, evicted = self._admit_dense_locked()
            prefix_metrics = None
            if self._prefix is not None and batch:
                prefix_metrics = (sum(1 for r in batch if r.prefix_hit),
                                  sum(1 for r in batch if not r.prefix_hit))
            self._gauges_locked()
        if not batch:
            return False
        if not self._built:
            t_b0 = time.perf_counter()
            with span("serving.build"):
                self._build()
            dt_b = time.perf_counter() - t_b0
            for req in batch:
                if req.journey is not None:
                    # cold start: the first admission wave pays the pool
                    # build — attribute it, don't leave a mystery gap
                    req.journey.phase("build", t_b0, dt_b)
        with phase("serving.admit.wave", n=len(batch)):
            # what the admitted wave needs before its prefill
            self._flush_adapter_uploads(batch)
            self._flush_promotes(batch)
            if evicted:
                registry().counter(
                    SERVING_PREFIX_EVICTIONS,
                    "prefix-cache rows evicted back to the free list").inc(
                    float(evicted))
            if prefix_metrics is not None:
                reg = registry()
                hits, misses = prefix_metrics
                if hits:
                    reg.counter(
                        SERVING_PREFIX_HITS,
                        "admissions served from the prefix cache").inc(
                        float(hits))
                if misses:
                    reg.counter(
                        SERVING_PREFIX_MISSES,
                        "admissions with no usable cached prefix").inc(
                        float(misses))
            for req in batch:
                # per-request PRNG base key for the device sampler (one
                # tiny eager op per ADMISSION, not per token)
                req._base_key = np.asarray(jax.random.PRNGKey(req.seed),
                                           np.uint32)
            cold = [r for r in batch if r._prefix_src is None]
            hits = [r for r in batch if r._prefix_src is not None]
        if cold:
            self._prefill_cold(cold)
        if hits:
            self._prefill_hits(hits)
        return True

    def _set_slot_params_locked(self, req: RequestHandle):
        slot = req.slot
        self._temps[slot] = req.temperature
        self._topks[slot] = req.top_k
        self._keys[slot] = req._base_key
        self._aids[slot] = req._adapter_slot

    def _flush_adapter_uploads(self, batch=()):
        """Admission-time load of cold adapters: upload every scheduled
        adapter's zero-padded factors into its bank row (eager device
        writes, once per cold admission — never per token).  Runs on the
        scheduler thread after ``_build`` so the banks exist; the
        residency mapping is re-checked under the lock in case a stalled
        request's row was LRU-reused before its upload ran.  ``batch``
        is this admission wave — every admitted request waiting on a
        loaded adapter gets an ``adapter_load`` phase on its journey."""
        if self._adapters is None:
            return
        with self._lock:
            if not self._adapter_uploads:
                return
            ups = [(name, slot, rid) for name, (slot, rid) in
                   self._adapter_uploads.items()
                   if self._adapters.slot_of(name) == slot]
            self._adapter_uploads.clear()
        for name, slot, rid in ups:
            t0 = time.perf_counter()
            with span("serving.adapter_load", adapter=name, bank_slot=slot):
                self._load_adapter_bank(slot,
                                        self.adapter_registry.get(name))
            dt = time.perf_counter() - t0
            with self._lock:
                if self._adapters.slot_of(name) == slot:
                    self._adapters.mark_loaded(name)
                self._adapter_load_times.append(dt)
            registry().counter(
                SERVING_ADAPTER_LOADS,
                "cold adapter loads into the device bank").inc(1.0)
            flight.record("serving", "adapter_load", adapter=name,
                          bank_slot=slot, request=rid,
                          load_ms=round(dt * 1e3, 3))
            for req in batch:
                if req.adapter == name and req.journey is not None:
                    req.journey.phase("adapter_load", t0, dt, adapter=name,
                                      bank_slot=slot)

    def _flush_promotes(self, batch=()):
        """Host-tier promotion: upload each promoted request's cached
        prefix bytes into the fresh device pages admission reserved for
        it, then re-insert the prefix into the device index so the NEXT
        turn hits in HBM directly.

        Runs on the scheduler thread after ``_build`` (the pools exist)
        and before prefill partitioning — a promoted request leaves here
        as a normal zero-copy hit (``_prefix_src`` set, tail-prefill
        only).  The writes are EAGER ``.at[pages].set`` updates per pool
        per layer, never a jitted entry point, so the decode signature
        count stays at ONE; the page bytes land verbatim (int8 payload +
        f32 scales), so greedy output is bitwise-identical to a
        never-evicted hit.  The upload runs OFF-lock (device work);
        the mapping is re-checked under the lock first in case the
        engine shut down while this wave was in flight."""
        if self._host_tier is None:
            return
        import jax.numpy as jnp
        tier = self._host_tier
        todo = []
        with self._lock:
            for req in batch:
                if req._promote is None:
                    continue
                hentry, m = req._promote
                if req._pages is None or req.slot is None:
                    req._promote = None
                    tier.release(hentry)
                    continue
                todo.append((req, hentry, m))
        P = self._page_alloc.page_size
        for req, hentry, m in todo:
            q = -(-m // P)                       # ceil: pages holding m
            pids = req._pages[:q]
            t0 = time.perf_counter()
            try:
                payload = tier.payload(hentry, q)
            except KeyError:
                # the entry vanished under us (tier closed externally):
                # the request still holds its full reservation — fall
                # back to a plain cold prefill, never an engine death
                with self._lock:
                    req._promote = None
                    tier.release(hentry)
                continue
            idx = jnp.asarray(np.asarray(pids, np.int32))
            self._kv_pool = self._kv_pool.with_groups(
                [pool.at[idx].set(jnp.asarray(arr, pool.dtype))
                 for pool, arr in zip(grp, host_grp)]
                for grp, host_grp in zip(self._kv_pool.groups(), payload))
            dt = time.perf_counter() - t0
            nbytes = sum(a.nbytes for g in payload for a in g)
            with self._lock:
                req._promote = None
                entry = self._prefix.insert(None, hentry.tokens[:m],
                                            pages=list(pids),
                                            ns=hentry.ns)
                if entry is not None:
                    # the index and this request each hold a page ref
                    for p in pids:
                        self._page_alloc.share(p)
                    self._cached_pages += q
                else:
                    # pathological duplicate (an unaddressable entry
                    # already owns (ns, tokens[:m])): ride the hit path
                    # on a DETACHED entry — not in the index, no page
                    # sharing; release just decrements its refs
                    entry = PrefixEntry(None, hentry.tokens[:m], 0,
                                        pages=None, ns=hentry.ns)
                self._prefix.acquire(entry)
                req._prefix_src = entry
                req._prefix_match = m
                req._cow = None                  # page-aligned by block
                req.prefix_hit = True
                req.promote_s = dt
                self._counts["host_prefix_promotes"] += 1
                tier.release(hentry)
            reg = registry()
            reg.counter(
                SERVING_HOST_PREFIX_HITS,
                "admissions whose prefix was found in the host tier").inc(
                1.0)
            reg.counter(
                SERVING_HOST_PREFIX_PROMOTES,
                "host-tier prefixes re-uploaded into device pages").inc(1.0)
            reg.histogram(
                SERVING_HOST_PREFIX_PROMOTE_SECONDS,
                "host->device promote wall seconds (upload + re-index)"
            ).observe(dt)
            flight.record("serving", "host_prefix_promote",
                          request=req.request_id, cached_tokens=m,
                          pages=q, bytes=nbytes,
                          promote_ms=round(dt * 1e3, 3))
            if req.journey is not None:
                req.journey.phase("prefix_promote", t0, dt,
                                  cached_tokens=m, pages=q, bytes=nbytes)

    def _load_adapter_bank(self, slot: int, adapter):
        """Write one adapter's factors (zero-padded to the bank's
        ``r_max``) into bank row ``slot``; padding columns contribute
        exact zeros to the delta."""
        import jax.numpy as jnp
        r = adapter.rank
        a = np.zeros(tuple(self._abank.shape[1:]), np.float32)
        b = np.zeros(tuple(self._bbank.shape[1:]), np.float32)
        for i in range(adapter.num_layers):
            a[i, :, :r] = adapter.a[i]
            b[i, :r, :] = adapter.b[i]
        self._abank = self._abank.at[slot].set(jnp.asarray(a))
        self._bbank = self._bbank.at[slot].set(jnp.asarray(b))
        self._ascale = self._ascale.at[slot].set(float(adapter.scale))

    def _adp_args(self, aids):
        """The adapter operand tuple one dispatch carries: per-row bank
        ids + the stacked banks (fixed shapes — ONE decode signature)."""
        import jax.numpy as jnp
        return (jnp.asarray(aids, jnp.int32), self._abank, self._bbank,
                self._ascale)

    def _prefill_cold(self, batch) -> None:
        """Cold prefill of one admission wave: the requests with no cached
        prefix (the only admission path when the prefix cache is off).  The
        prefill program has ONE row, so a wave of n requests is n
        dispatches, each at the bucket of its own prompt — all of them
        before the first fetch, so that the device runs them back to back
        while the host prepares the next; then one fetch and one emit per
        dispatch, in admission order."""
        with self._lock:
            self._counts["prefill_waves"] += 1
        registry().counter(
            SERVING_PREFILL_WAVES,
            "admission waves that ran at least one cold prefill").inc(1.0)
        lo = min(8, self._limit)
        buckets = [_bucket(r.prompt.size, lo, self._limit) for r in batch]
        with span("serving.prefill", n=len(batch), bucket=max(buckets)):
            flying = []
            try:
                for req, bucket in zip(batch, buckets):
                    n_prompt = int(req.prompt.size)
                    sampled, topk = _sampling_rows([req])
                    with phase("serving.prefill.dispatch", rows=1,
                               batch_rows=1, bucket=bucket,
                               prompt_tokens=n_prompt, padded_tokens=bucket,
                               sampled=sampled, topk=topk):
                        (ids, addr, plens, temps, topks, keys,
                         aid_rows) = self._prefill_rows(req, bucket)
                        t0 = time.perf_counter()
                        faults.fault_point("serving.prefill", n=len(batch))
                        if self._decode_timeout_s is not None:
                            # each dispatch moves the deadline on: it times
                            # the programs still queued behind this one
                            _watchdog.arm("serving.prefill",
                                          self._decode_timeout_s)
                        extra = ((self._adp_args(aid_rows),)
                                 if self._adapters is not None else ())
                        out, self._kv_pool = self._prefill_fn(
                            self._values, ids, self._kv_pool, addr, plens,
                            temps, topks, keys, *extra)
                        self._dispatched(out)
                    flying.append((req, bucket, n_prompt, t0, out))
                for req, bucket, n_prompt, t0, out in flying:
                    with phase("serving.prefill.fetch"):
                        out, lps, load = self._fetch(out, (1,))
                    with phase("serving.prefill.emit",
                               **self._load_stats(load)):
                        self._count_load(load)
                        dt = time.perf_counter() - t0
                        with self._lock:
                            self._counts["prefill_batches"] += 1
                            self._counts["prefill_tokens"] += n_prompt
                            self._counts["prefill_padded_tokens"] += bucket
                        registry().histogram(
                            SERVING_BATCH_SECONDS,
                            "prefill/decode batch wall time").observe(
                            dt, labels={"phase": "prefill"})
                        if req.journey is not None:
                            req.journey.phase("prefill", t0, dt,
                                              n=len(batch), bucket=bucket,
                                              prompt=n_prompt)
                        self._emit_first_tokens([req], out, lps,
                                                by_slot=False)
            finally:
                if self._decode_timeout_s is not None:
                    _watchdog.disarm()

    def _prefill_rows(self, req, bucket: int):
        """The host arrays of one cold prefill dispatch, one lane each: the
        request's prompt right-padded to ``bucket`` positions, where the
        pool writes it (its slot index, or on the paged pool its page-table
        row), its length, sampling parameters, key and adapter bank row."""
        ids = np.zeros((1, bucket), np.int64)
        ids[0, :req.prompt.size] = req.prompt
        with self._lock:
            addr = (self._page_tables[req.slot:req.slot + 1].copy()
                    if self.paged_kv else np.array([req.slot], np.int32))
            self._set_slot_params_locked(req)
            flight.record("serving", "admit", request=req.request_id,
                          slot=req.slot, prompt_len=int(req.prompt.size),
                          queue_wait_ms=round(
                              1e3 * (req.t_admit - req.t_submit), 3))
        return (ids, addr, np.array([req.prompt.size], np.int32),
                np.array([req.temperature], np.float32),
                np.array([req.top_k], np.int32),
                np.asarray(req._base_key, np.uint32)[None],
                np.array([req._adapter_slot], np.int32))

    def _prefill_hits(self, hits) -> None:
        """Prefix-cache hit path.  Dense pool: device-copy the cached
        rows into the new slots, then prefill ONLY the prompt tails
        through the per-slot branch — admission cost scales with the
        tail, not the prompt.  Paged pool: ZERO-copy — the hit already
        shares the cached pages by reference through the page table
        (host-side int writes); only a partial boundary page needs its
        one-page COW clone before the tail writes into it."""
        import jax.numpy as jnp
        paged = self.paged_kv
        n_rows = self.max_slots + 1
        tails = [r.prompt.size - r._prefix_match for r in hits]
        tail_tokens = int(sum(tails))
        tb = _bucket(max(tails), 1, self._limit)
        sampled, topk = _sampling_rows(hits)
        with span("serving.tail_prefill", n=len(hits), bucket=tb):
            try:
                # `serving.prefix_copy`, the copy program's dispatch, is a
                # named step inside this phase
                with phase("serving.tail_prefill.dispatch", rows=len(hits),
                           batch_rows=n_rows, bucket=tb,
                           prompt_tokens=tail_tokens,
                           padded_tokens=n_rows * tb,
                           sampled=sampled, topk=topk):
                    (src, dst, n_copy, cow_ids, ids, lens, gidx, tables,
                     aids_snap) = self._tail_rows(hits, tb)
                    t0 = time.perf_counter()
                    faults.fault_point("serving.prefill", n=len(hits))
                    if self._decode_timeout_s is not None:
                        _watchdog.arm("serving.tail_prefill",
                                      self._decode_timeout_s)
                    if n_copy or not paged:
                        # dense: whole-row clone per hit; paged: only the
                        # COW'd boundary pages (usually zero — block ==
                        # page size makes every shared page a full page)
                        with span("serving.prefix_copy", n=n_copy):
                            self._kv_pool = self._copy_fn(
                                self._kv_pool, jnp.asarray(src),
                                jnp.asarray(dst))
                        if paged and n_copy:
                            with self._lock:
                                self._counts["page_cow_copies"] += n_copy
                            registry().counter(
                                SERVING_KV_COW_COPIES,
                                "shared KV pages cloned for a diverging "
                                "writer").inc(float(n_copy))
                            flight.record(
                                "serving", "page_cow", copies=n_copy,
                                requests=",".join(map(str, cow_ids)))
                    t_copy_end = time.perf_counter()
                    extra = ((self._adp_args(aids_snap),)
                             if self._adapters is not None else ())
                    out, self._kv_pool = self._tail_fn(
                        self._values, ids, self._kv_pool, lens, tables,
                        gidx, jnp.asarray(self._temps),
                        jnp.asarray(self._topks), jnp.asarray(self._keys),
                        *extra)
                    self._dispatched(out)
                with phase("serving.tail_prefill.fetch"):
                    out, lps, load = self._fetch(out, (n_rows,))
            finally:
                if self._decode_timeout_s is not None:
                    _watchdog.disarm()
            with phase("serving.tail_prefill.emit",
                       **self._load_stats(load)):
                self._count_load(load)
                t_end = time.perf_counter()
                dt = t_end - t0
                with self._lock:
                    self._counts["prefill_batches"] += 1
                    self._counts["prefill_tokens"] += tail_tokens
                    self._counts["prefill_padded_tokens"] += n_rows * tb
                registry().histogram(
                    SERVING_BATCH_SECONDS,
                    "prefill/decode batch wall time").observe(
                    dt, labels={"phase": "tail_prefill"})
                cow_set = set(cow_ids)
                for req in hits:
                    if req.journey is None:
                        continue
                    m = req._prefix_match
                    # dense hits device-copy their cached row; paged hits
                    # share pages by reference (zero-copy) unless a
                    # boundary page COWed
                    if not paged or req.request_id in cow_set:
                        req.journey.phase("prefix_copy", t0, t_copy_end - t0,
                                          cached_tokens=m)
                    req.journey.phase(
                        "tail_prefill", t_copy_end, t_end - t_copy_end,
                        cached_tokens=m, tail=int(req.prompt.size - m),
                        zero_copy=bool(paged and
                                       req.request_id not in cow_set))
                self._emit_first_tokens(hits, out, lps, by_slot=True)

    def _tail_rows(self, hits, tb: int):
        """The host arrays of one prefix-hit wave: copy sources and
        targets, and every slot row's tail padded to ``tb`` positions."""
        P = self.prefill_batch
        paged = self.paged_kv
        sentinel = self._page_alloc.num_pages if paged else self.max_slots
        n_rows = self.max_slots + 1
        src = np.full(P, sentinel, np.int32)
        dst = np.full(P, sentinel, np.int32)
        n_copy = 0
        cow_ids: list[int] = []      # requests whose boundary page COWs
        ids = np.zeros((n_rows, tb), np.int64)
        lens = np.full(n_rows, self._park, np.int32)
        gidx = np.zeros(n_rows, np.int32)
        tables = None
        with self._lock:
            for i, req in enumerate(hits):
                e, m = req._prefix_src, req._prefix_match
                if paged:
                    if req._cow is not None:
                        src[n_copy], dst[n_copy] = req._cow
                        n_copy += 1
                        cow_ids.append(req.request_id)
                        req._cow = None
                else:
                    src[i], dst[i] = e.slot, req.slot
                    n_copy += 1
                tail = req.prompt[m:]
                ids[req.slot, :tail.size] = tail
                lens[req.slot] = m
                gidx[req.slot] = tail.size - 1
                self._set_slot_params_locked(req)
                flight.record("serving", "prefix_admit",
                              request=req.request_id, slot=req.slot,
                              src_slot=-1 if e.slot is None else e.slot,
                              cached_tokens=m, tail=int(tail.size),
                              queue_wait_ms=round(
                                  1e3 * (req.t_admit - req.t_submit), 3))
            if paged:
                tables = np.array(self._page_tables)
            aids_snap = np.array(self._aids)
        return src, dst, n_copy, cow_ids, ids, lens, gidx, tables, aids_snap

    def _emit_first_tokens(self, batch, out, lps, by_slot: bool):
        """Shared tail of both admission paths: record TTFT and emit each
        request's first token (``out`` is device-sampled token ids with
        their log-probabilities ``lps``, or logits rows when
        ``sample_on_device=False``)."""
        now = time.perf_counter()
        finishers = []
        for i, req in enumerate(batch):
            at = req.slot if by_slot else i
            row = out[at]
            req.ttft_s = now - req.t_submit
            req._t_last_token = now
            registry().histogram(SERVING_TTFT,
                                 "time to first token").observe(req.ttft_s)
            if req.adapter is not None:
                registry().histogram(
                    SERVING_ADAPTER_TTFT,
                    "time to first token, per adapter").observe(
                    req.ttft_s, labels={"adapter": req.adapter})
            if req.done() or req._torn or req._engine is not self:
                continue
            token = (int(row) if self.sample_on_device else
                     _sample_row(row, req.temperature, req.top_k, req._rng))
            logprob = (lps[at] if self.sample_on_device else
                       _row_logprob(row, token))
            if req.journey is not None:
                req.journey.mark_first_token(now)
            finished = self._emit_one(req, token, logprob)
            if req.adapter is not None:
                registry().counter(
                    SERVING_ADAPTER_TOKENS,
                    "tokens served, per adapter").inc(
                    1.0, labels={"adapter": req.adapter})
            slot = req.slot
            with self._lock:
                self._counts["tokens"] += 1
                self._lengths[slot] = req.prompt.size
                if finished:
                    self._evict_locked(req, "completed")
                else:
                    self._ids[slot, 0] = token
            if finished:
                finishers.append(req)
        for req in finishers:
            req._finish(None)
        with self._lock:
            self._gauges_locked()

    # -- decode --------------------------------------------------------------
    def _decode_step(self) -> bool:
        """Dispatch the next decode step, then fetch and emit the one still
        unfetched (`_flying`).  At depth 1 (`_lookahead`) the new step stays
        in flight, so that everything the host does until its fetch runs
        under a program; at depth 0 it is fetched and emitted at once."""
        with self._lock:
            active = self._pool.active()
        flying = self._flying
        if not active and flying is None:
            return False
        behind = None       # the step this turn leaves in flight
        with span("serving.decode", active=len(active)):
            try:
                step = (self._decode_dispatch(active, flying) if active
                        else None)
                behind = step if self._lookahead else None
                if flying is not None:
                    # an engine that runs empty fetches its last step here,
                    # before `serving.wait`
                    self._decode_land(flying)
                self._flying = behind
                if step is not None and behind is None:
                    self._decode_land(step)
                if step is None:
                    # no dispatch whose tail would let the held tokens go
                    self._flush_streams()
            finally:
                # armed anew by every dispatch: it times the step in flight
                if self._decode_timeout_s is not None and behind is None:
                    _watchdog.disarm()
        return step is not None or flying is not None

    def _decode_dispatch(self, active: dict,
                         flying: Optional[_DecodeStep]):
        """Build and dispatch one decode step over `active`, seen one token
        ahead of the host's arrays where `flying` is still unfetched.
        Returns None, with nothing dispatched, when no row would compute
        (every one of them ends with the unfetched step's token)."""
        with phase("serving.decode.build"):
            (drafts, ids, lengths, temps, topks, keys, aids, tables,
             host_rows) = self._decode_inputs(active, flying)
            live = {slot: req for slot, req in active.items()
                    if lengths[slot] < self._park}
            if not live:
                return None
            kv = self._decode_kv_positions(live, lengths)
            sampled, topk = self._decode_sampling_rows(lengths, temps, topks)
            self._step_ordinal += 1
        with phase("serving.decode.dispatch", active=len(live), **kv,
                   sampled=sampled, topk=topk, step=self._step_ordinal):
            t0 = time.perf_counter()
            faults.fault_point("serving.decode", active=len(live))
            if self._decode_timeout_s is not None:
                _watchdog.arm("serving.decode", self._decode_timeout_s)
            carry = (self._last_out, host_rows) if self._lookahead else None
            extra = ((self._adp_args(aids),)
                     if self._adapters is not None else ())
            # the slot-state snapshots go in as numpy: the jit call
            # transfers them itself, without a Python-level `device_put`
            # apiece
            out, self._kv_pool = self._decode_fn(
                self._values, ids, self._kv_pool, lengths, tables, temps,
                topks, keys, carry, *extra)
            if self._lookahead:
                self._last_out = out
            if flying is not None:
                with self._lock:
                    self._counts["decode_lookahead_steps"] += 1
                registry().counter(
                    SERVING_DECODE_LOOKAHEAD_STEPS,
                    "decode steps dispatched while the step before was "
                    "still unfetched").inc(1.0)
            self._dispatched(out)
        return _DecodeStep(self._step_ordinal, live, drafts, lengths, out,
                           t0)

    def _decode_land(self, step: _DecodeStep):
        """Fetch one dispatched decode step and emit its tokens.  Their
        stream callbacks stay held until the tail of the next dispatch, also
        where a step is queued behind this one already: the consumers they
        wake (one thread a stream) then run while the scheduler waits in the
        next fetch, not beside its sweep, build and dispatch."""
        with phase("serving.decode.fetch"):
            out, lps, load = self._fetch(step.out, self._ids.shape)
            # the step behind it had the device from here on at the latest
            t0, self._t_landed = (max(step.t0, self._t_landed),
                                  time.perf_counter())
        with phase("serving.decode.emit", step=step.ordinal,
                   **self._load_stats(load)):
            self._count_load(load)
            self._decode_emit(step, out, lps, t0)

    def _flush_streams(self):
        """Hand every held-back token to its stream callback, in the order
        emitted.  Called once a program is on the device (the tail of a
        dispatch), before any request finishes (`RequestHandle._finish`),
        at the end of a decode turn that dispatched nothing and when the
        scheduler goes idle; the lock keeps two flushing threads from
        reordering a stream."""
        with self._stream_lock:
            while self._held_streams:
                stream, token = self._held_streams.popleft()
                try:
                    stream(token)
                except Exception:
                    pass  # a broken stream consumer must not kill the batch

    def _dispatched(self, out):
        """The tail of every dispatch phase: ask for the step's one
        device-to-host copy now, so that it starts when the program ends and
        not when `_fetch` comes to ask; then let the last step's tokens go."""
        for a in (out if isinstance(out, tuple) else (out,)):
            a.copy_to_host_async()
        self._flush_streams()

    def _fetch(self, out, shape):
        """The one device-to-host transfer of a step: (token ids `shape`, or
        logits rows; the tokens' log-probabilities, None beside logits rows;
        the expert layers' load counts, None for a model without them).  On
        the device sampler all three are one array (`_pack`)."""
        if isinstance(out, tuple):          # host sampling: logits, counts
            return np.asarray(out[0]), None, np.asarray(out[1])
        out = np.asarray(out)
        if not self.sample_on_device:
            return out, None, None
        n = int(np.prod(shape))
        lps = out[n:2 * n].astype(np.int32).view(np.float32).reshape(shape)
        return (out[:n].reshape(shape), lps,
                out[2 * n:] if self._moe_load else None)

    @staticmethod
    def _load_stats(load) -> dict:
        """One step's expert load as the short scalar stats of its emit
        span ({} for a model without expert layers): `moe_assignments` real
        tokens x top-k on the experts held here, `moe_experts_touched`
        experts with at least one of them, `moe_load_max` the largest
        expert load, `moe_routed` real tokens x top-k whoever holds the
        expert (= `moe_assignments` where all are held), each summed over
        the layers."""
        if load is None:
            return {}
        return {"moe_assignments": int(load[0]),
                "moe_experts_touched": int(load[1]),
                "moe_load_max": int(load[2]), "moe_routed": int(load[3])}

    def _count_load(self, load):
        """Add one step's expert load to `stats()` and the registry (inside
        the step's emit phase)."""
        stats = self._load_stats(load)
        if not stats:
            return
        with self._lock:
            for k, v in stats.items():
                self._counts[k] += v
        for k, counter in self._load_counters:
            counter.inc(float(stats[k]))

    def _decode_kv_positions(self, live: dict, lengths):
        """Summed over the layers, the KV positions one decode dispatch
        needs (`kv_live`: each live slot's context and its new span; on a
        sliding-window layer only what the window admits) and the positions
        its attention read streams (`kv_read`): every row whole on an XLA
        read (a ring's row is the ring), each row's live blocks or pages on
        a kernel (on a window layer from the window's first block on; on a
        ring its live ring blocks).  Returns the dispatch span's stats: the
        two sums and each by layer kind (`_window`, `_global`)."""
        from ..kernels.paged_attention import live_blocks
        W = self._spec_width
        span = (self._max_pages_per_slot * self._page_alloc.page_size
                if self.paged_kv else self.max_len)
        P = self._decode_read_block
        ctx = np.asarray([int(lengths[s]) + W for s in live], np.int64)
        out = dict.fromkeys(_KV_STATS, 0)
        for (window, ring), n_layers in self._kv_kinds.items():
            need = ctx if window is None else np.minimum(ctx, window + W - 1)
            if P is None:
                read = len(lengths) * (ring or span)
            else:
                nb = live_blocks(lengths, W, span, P, window, ring)
                if self.paged_kv:
                    # the paged kernel's index map stands on one clamped
                    # page for a parked row
                    nb = np.maximum(nb, 1)
                read = int(nb.sum()) * P
            kind = "global" if window is None else "window"
            out["kv_live_" + kind] += n_layers * int(need.sum())
            out["kv_read_" + kind] += n_layers * read
        out["kv_live"] = out["kv_live_window"] + out["kv_live_global"]
        out["kv_read"] = out["kv_read_window"] + out["kv_read_global"]
        with self._lock:
            for k, v in out.items():
                self._counts[_KV_STATS[k]] += v
        return out

    def _decode_sampling_rows(self, lengths, temps, topks):
        """The live rows of one decode dispatch that draw (`sampled`:
        `temperature > 0`) and those of them that mask (`topk`:
        `top_k > 0`) — the device sampler's two predicates (`_sample_rows`)
        over the same snapshot; a freed slot keeps its last request's
        parameters and is parked, so it counts on neither side."""
        hot = (lengths < self._park) & (temps > 0)
        sampled = int(hot.sum())
        if not sampled:
            return 0, 0
        topk = int((hot & (topks > 0)).sum())
        if not self.sample_on_device:    # the host sampler: no branch taken
            return sampled, topk
        with self._lock:
            self._counts["decode_sampled_steps"] += 1
            self._counts["decode_topk_steps"] += bool(topk)
        reg = registry()
        reg.counter(SERVING_DECODE_SAMPLED_STEPS,
                    "decode steps whose sampler drew (a live row with "
                    "temperature > 0)").inc(1.0)
        if topk:
            reg.counter(SERVING_DECODE_TOPK_STEPS,
                        "decode steps whose sampler sorted (a live drawing "
                        "row with top_k > 0)").inc(1.0)
        return sampled, topk

    def _decode_inputs(self, active: dict, flying: Optional[_DecodeStep]):
        """`serving.decode.build`: the drafts and the locked snapshot of
        the slot-state arrays one decode dispatch carries.  With `flying`
        unfetched the snapshot is moved one token on for the rows live in
        it: each emits exactly one token there, so its next position is
        known, and so is a budget that ends with that token (the row is
        parked, not computed).  An EOS is not: that row computes once more
        and `_decode_emit` discards it.  `host_rows` (None at depth 0): the
        rows whose input token `ids` holds; the others take it from
        `flying`'s output on the device."""
        W = self._spec_width
        drafts: dict = {}
        if W > 1:
            for slot, req in active.items():
                if req.temperature == 0.0:
                    # prompt-lookup drafting is greedy-only: an accepted
                    # draft must equal the token the model WOULD emit,
                    # which is only well-defined for argmax decoding
                    ctx = np.concatenate(
                        [req.prompt, np.asarray(req._tokens, np.int64)])
                    drafts[slot] = np.asarray(
                        self._drafter(ctx, W - 1), np.int64)
        with self._lock:
            # snapshot the slot-state arrays under the lock: shutdown()
            # mutates slot state from the caller thread (tpu-lint
            # concurrency.unguarded-shared-attr)
            for slot in active:
                d = drafts.get(slot)
                if W > 1:
                    self._ids[slot, 1:] = (d if d is not None
                                           else self._ids[slot, 0])
            ids = np.array(self._ids)
            lengths = np.array(self._lengths)
            temps = np.array(self._temps)
            topks = np.array(self._topks)
            keys = np.array(self._keys)
            aids = np.array(self._aids)
            tables = (np.array(self._page_tables) if self.paged_kv
                      else None)
        host_rows = None
        if self._lookahead:
            host_rows = np.ones(len(lengths), bool)
            for slot, req in (flying.live.items() if flying is not None
                              else ()):
                if active.get(slot) is not req:
                    continue        # evicted since: the slot is another's
                host_rows[slot] = False
                if len(req._tokens) + 1 >= req.max_new_tokens:
                    lengths[slot] = self._park
                else:
                    lengths[slot] = flying.lengths[slot] + 1
        return (drafts, ids, lengths, temps, topks, keys, aids, tables,
                host_rows)

    def _decode_emit(self, step: _DecodeStep, out, lps, t0):
        """`serving.decode.emit`: accept, stream and account the tokens
        of one fetched decode batch; retire what finished.  A row whose
        request ended while the step was in flight (an EOS, a cancel or a
        deadline the look-ahead could not know of) is discarded: nothing
        streamed, nothing counted but `decode_overshoot_rows`."""
        W = self._spec_width
        drafts, lengths = step.drafts, step.lengths
        dt = time.perf_counter() - t0
        with self._lock:
            self._counts["decode_steps"] += 1
        registry().histogram(SERVING_BATCH_SECONDS,
                             "prefill/decode batch wall time").observe(
            dt, labels={"phase": "decode"})
        now = time.perf_counter()
        tok_hist = registry().histogram(SERVING_TOKEN_LATENCY,
                                        "per-token decode latency")
        drafted_total = accepted_total = overshoot = 0
        finishers = []
        for slot, req in step.live.items():
            if req.done() or req._torn or req._engine is not self:
                # ended while this batch ran, or torn away by a supervisor
                # abandon (or already re-dispatched into a REBUILT engine):
                # its outcome is settled elsewhere
                overshoot += 1
                continue
            if self.sample_on_device:
                toks_row = out[slot]                      # [W] token ids
                lps_row = lps[slot]
            else:
                row_logits = out[slot]                    # [W, V] logits
                first = _sample_row(row_logits[0], req.temperature,
                                    req.top_k, req._rng)
                toks_row = np.concatenate(
                    [[first], row_logits[1:].argmax(-1)]) \
                    if W > 1 else np.array([first])
                lps_row = [_row_logprob(r, int(t))
                           for r, t in zip(row_logits, toks_row)]
            # acceptance: the draft at position j (ids[slot, j]) is kept
            # iff it equals the model's choice at position j-1; the run
            # t_0..t_m then emits m+1 tokens for this one pool read
            run = [int(toks_row[0])]
            d = drafts.get(slot)
            if d is not None:
                for j in range(1, W):
                    if int(d[j - 1]) != int(toks_row[j - 1]):
                        break
                    run.append(int(toks_row[j]))
                drafted_total += W - 1
                accepted_total += len(run) - 1
            old_len = int(lengths[slot])
            lat = now - req._t_last_token
            req._t_last_token = now
            emitted = 0
            finished = False
            for token, logprob in zip(run, lps_row):
                finished = self._emit_one(req, token, logprob)
                emitted += 1
                if finished:
                    break
            # one pool read emitted `emitted` tokens: split the wall time
            # so the per-token histogram stays sum-preserving
            for _ in range(emitted):
                req.token_latencies_s.append(lat / max(emitted, 1))
                tok_hist.observe(lat / max(emitted, 1))
            if req.adapter is not None and emitted:
                registry().counter(
                    SERVING_ADAPTER_TOKENS,
                    "tokens served, per adapter").inc(
                    float(emitted), labels={"adapter": req.adapter})
            if req.journey is not None:
                # one phase per batched DISPATCH the request rode (the
                # existing per-token boundary), never per token
                attrs = {"emitted": emitted, "active": len(step.live)}
                if d is not None:
                    attrs["drafted"] = W - 1
                    attrs["accepted"] = len(run) - 1
                req.journey.phase("decode", t0, dt, **attrs)
            with self._lock:
                self._counts["tokens"] += emitted
                self._lengths[slot] = old_len + emitted
                if finished:
                    self._evict_locked(req, "completed")
                else:
                    self._ids[slot, 0] = run[emitted - 1]
            if finished:
                finishers.append(req)
        if drafted_total:
            with self._lock:
                self._counts["spec_drafted"] += drafted_total
                self._counts["spec_accepted"] += accepted_total
            reg = registry()
            reg.counter(SERVING_SPEC_DRAFTED,
                        "speculative tokens drafted").inc(
                float(drafted_total))
            if accepted_total:
                reg.counter(SERVING_SPEC_ACCEPTED,
                            "speculative tokens accepted").inc(
                    float(accepted_total))
            flight.record("serving", "spec_verify", drafted=drafted_total,
                          accepted=accepted_total,
                          rejected=drafted_total - accepted_total)
        if overshoot:
            with self._lock:
                self._counts["decode_overshoot_rows"] += overshoot
            registry().counter(
                SERVING_DECODE_OVERSHOOT_ROWS,
                "rows a decode step computed for a request that had "
                "already ended").inc(float(overshoot))
        for req in finishers:
            req._finish(None)
        with self._lock:
            self._gauges_locked()

    def _emit_one(self, req: RequestHandle, token: int,
                  logprob: float) -> bool:
        """Stream one token to the request; returns whether the request
        is now finished (budget or EOS)."""
        faults.fault_point("serving.stream", request=req.request_id)
        req._emit(token, logprob)
        registry().counter(SERVING_TOKENS, "tokens generated").inc(1.0)
        return (len(req._tokens) >= req.max_new_tokens or
                (req.eos_token_id is not None and
                 token == req.eos_token_id))

    # -- eviction / retention ------------------------------------------------
    def _release_pages_locked(self, req: RequestHandle):
        """Drop the request's page references (freed at refcount 0) and
        sentinel its table row.  No-op outside paged mode."""
        if req._promote is not None and self._host_tier is not None:
            # a pending promote dies with the admission (shutdown /
            # engine death before _flush_promotes ran): drop the tier
            # pin so the entry becomes LRU-droppable again
            self._host_tier.release(req._promote[0])
            req._promote = None
        if not self.paged_kv or req._pages is None:
            return
        for p in req._pages:
            self._page_alloc.deref(p)
        self._active_pages -= len(req._pages)
        req._pages = None
        req._cow = None
        if req.slot is not None:
            self._page_tables[req.slot, :] = self._page_alloc.num_pages

    def _evict_locked(self, req: RequestHandle, outcome: str):
        slot = req.slot
        if req._prefix_src is not None:
            self._prefix.release(req._prefix_src)
            req._prefix_src = None
        retained = False
        if self._prefix is not None and outcome == "completed":
            # the slot holds the K/V of prompt + generated[:-1] (exactly
            # `lengths[slot]` positions) — retain it as a reusable
            # prefix instead of recycling it; duplicates free normally
            n = int(self._lengths[slot])
            cached = np.concatenate(
                [req.prompt, np.asarray(req._tokens, np.int64)])[:n]
            if self.paged_kv:
                # the ENTRY takes ownership of the pages covering the
                # cached tokens (refcounts transfer, no device work);
                # the unused tail of the reservation is released.  The
                # slot LANE is always recycled — cached prefixes hold
                # pages, never decode capacity.
                keep = self._pages_for(n) if n > 0 else 0
                entry = (self._prefix.insert(
                    None, cached, pages=req._pages[:keep],
                    ns=self._req_ns(req))
                    if keep > 0 else None)
                if entry is not None:
                    for p in req._pages[keep:]:
                        self._page_alloc.deref(p)
                    self._active_pages -= len(req._pages)
                    self._cached_pages += keep
                    req._pages = None
                    self._counts["prefix_inserts"] += 1
                    flight.record("serving", "prefix_insert", pages=keep,
                                  request=req.request_id, cached_tokens=n)
                    retained = True
            else:
                entry = (self._prefix.insert(slot, cached,
                                             ns=self._req_ns(req))
                         if n > 0 else None)
                if entry is not None:
                    self._pool.retain(slot, entry)
                    self._counts["prefix_inserts"] += 1
                    flight.record("serving", "prefix_insert", slot=slot,
                                  request=req.request_id, cached_tokens=n)
                    retained = True
        if self.paged_kv:
            self._release_pages_locked(req)
            self._page_tables[slot, :] = self._page_alloc.num_pages
            self._pool.free(slot)
        elif not retained:
            self._pool.free(slot)
        if self._adapters is not None:
            self._unpin_adapter_locked(req)
            self._aids[slot] = 0
        # park the row: idle (and cached) rows' pool writes must DROP
        self._lengths[slot] = self._park
        self._evicted_counters_locked(req, outcome)

    def _evicted_counters_locked(self, req: RequestHandle, outcome: str):
        self._counts[outcome] = self._counts.get(outcome, 0) + 1
        flight.record("serving", "evict", request=req.request_id,
                      slot=-1 if req.slot is None else req.slot,
                      outcome=outcome, tokens=len(req._tokens))
        registry().counter(SERVING_REQUESTS,
                           "serving requests by outcome").inc(
            1.0, labels={"outcome": outcome})

    def _gauges_locked(self):
        reg = registry()
        if self._ledger_prefix is not None and self._built:
            # retained-row bytes: cached slot rows (dense) or cached
            # pages (paged) — a sub-account of the kv_pool owner
            nb = (self._cached_pages * self._page_alloc.bytes_per_page
                  if self.paged_kv else
                  self._pool.n_cached * self._row_bytes)
            self._ledger_prefix.update(nb)
        reg.gauge(SERVING_ACTIVE_SLOTS,
                  "slots currently owned by requests").set(
            float(self._pool.n_active))
        reg.gauge(SERVING_QUEUE_DEPTH, "queued, unadmitted requests").set(
            float(len(self._queue)))
        if self._adapters is not None:
            reg.gauge(SERVING_ADAPTERS_RESIDENT,
                      "adapters resident in the device bank").set(
                float(self._adapters.n_resident))
        if self.paged_kv:
            reg.gauge(SERVING_KV_PAGES_FREE,
                      "KV pages on the free list").set(
                float(self._page_alloc.n_free))
            reg.gauge(SERVING_KV_PAGES_ACTIVE,
                      "KV pages referenced by in-flight requests "
                      "(shared pages count once per reference)").set(
                float(self._active_pages))
            reg.gauge(SERVING_KV_PAGES_CACHED,
                      "KV pages referenced by prefix-cache entries "
                      "(shared pages count once per reference)").set(
                float(self._cached_pages))
