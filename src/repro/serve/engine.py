"""Continuation-driven continuous-batching decode engine.

The serving analogue of the paper's completion-notification claim: instead
of an application-space synchronous loop (``steps.greedy_generate`` — run a
static batch to the longest member, block, repeat), the decode loop keeps a
fixed set of *slots*, each holding one in-flight sequence with its own KV
cache and position:

* **decode** — one vmapped decode step advances every occupied slot by one
  token (per-slot positions, donated cache). The step's next-token
  ``jax.Array`` is wrapped in an ``ArrayOp`` whose continuation does the
  bookkeeping when the device work *actually* finishes: records
  first-token latency, retires sequences that reached their token budget
  (freeing their slots), and releases the in-flight window so the loop can
  dispatch further ahead. The Python loop never blocks on device work.
* **admission** — new requests queue on the ``Batcher``'s
  ``poll_only + enqueue_complete`` CR (paper §3.5) and are admitted into
  free slots at step boundaries; their prefill dispatches while previously
  issued decode steps are still in flight on device, so prefill of new
  requests overlaps in-flight decode.
* **retirement** — a finished ``Request`` is itself a ``Completable``:
  its continuation fires for whoever attached one, and ``request.wait()``
  unblocks the submitting client.
* **per-token delivery** — the same step-completion continuations
  *deliver* each newly accepted token to the request on the host
  (``Request.deliver``): attached ``TokenStream``s (``serve.api``) wake
  per token with no polling thread, stop sequences match as tokens land,
  and deadline-expired slots are retired by the very continuation that
  releases their pages. These continuations commit in dispatch order
  (``_after_device``), whatever order their arrays are discovered
  complete in. QoS: admission pops strictly by
  ``GenerationConfig.priority`` (the ``Batcher`` heap) and steps carrying
  priority work jump the scheduler's ready queue via the
  per-registration ``priority`` flag.
* **speculation** (``speculate=K``, paged mode) — each iteration becomes
  a draft/verify pair: a host-side ``Drafter`` (n-gram prompt lookup by
  default, pluggable) guesses K tokens per slot, and ONE multi-token
  verify step scores all K+1 positions through the paged
  ``decode_attention``, accepting the longest matching prefix. The
  accept bookkeeping — per-slot position advance, token pushes,
  retirement of slots that finish mid-accepted-run — is itself a
  continuation on the verify step's output array, so the loop still
  never blocks on device work; slots simply become re-steppable when
  their verify completes. Token streams are identical to non-speculative
  greedy decode (the verify step emits only what the model itself
  argmaxes); speculation changes the schedule, never the tokens.

**Memory** comes in two flavours:

* *paged* (default where supported, see ``serve.kv_cache``) — slots index
  into a shared ``PagePool`` through per-request page tables; a request
  holds ``ceil((prompt + max_new) / page_size)`` pages instead of a full
  ``max_cache_len`` lane, so at equal pool memory the engine sustains a
  larger effective batch. Prompts sharing a page-aligned prefix with a
  resident request map those pages read-only and skip re-prefilling them;
  pages return to the pool in the retirement continuation (the paper's
  callback-driven lifecycle owns deallocation too). Paged steps default
  to the **fused** Pallas paged-attention kernel
  (``kernels.paged_attention``): one kernel walks the page tables on
  device — gather, flash-style attend, accept-masked KV write — so
  decode/verify/suffix never materialize a contiguous per-slot view and
  need no host-built write tables (``fused=False`` keeps the unfused
  gather/scatter steps as the A/B baseline). Page tables live device-
  resident between steps, refreshed only for slots whose placement
  changed.
* *dense* (``paged=False``, and automatically for SSM/hybrid/audio/SWA
  configs) — the original per-slot stacked cache, each slot padded to
  ``max_cache_len``.

Continuous batching beats static batching whenever output lengths vary or
arrivals straggle: finished slots are refilled immediately instead of
padding along until the longest member of a static batch completes.
"""
from __future__ import annotations

import threading
import time
from typing import Any, List, Optional, Sequence, Set, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ArrayOp, ContinueFlags, Engine, Promise, Scheduler
from repro.models import lm
from repro.obs import events as _obs_events
from repro.obs import tracer as _obs
from repro.models.common import AUDIO, ModelConfig
from repro.serve.batcher import Batcher
from repro.serve.drafter import Drafter, NgramDrafter
from repro.serve.kv_cache import PagePool, paged_supported, pages_for
from repro.serve.metrics import ServeMetrics
from repro.serve.request import Request, RequestState, summarize
from repro.serve.steps import (make_batched_decode_step,
                               make_fused_paged_decode_step,
                               make_fused_paged_suffix_step,
                               make_fused_paged_verify_step,
                               make_paged_decode_step, make_paged_suffix_step,
                               make_paged_verify_step, make_prefill_scatter,
                               make_prefill_step)

# every step/prefill/verify registration: never take the immediate-
# completion fast path, so bookkeeping always runs through the
# continuation machinery even when the device raced ahead
_STEP_FLAGS = ContinueFlags(enqueue_complete=True)
# steps carrying priority>0 requests additionally jump the scheduler's
# ready queue (per-registration priority flag); cached per level, bounded
# — priorities are arbitrary caller ints, so an unbounded cache would be
# a process-lifetime leak under priority-per-request workloads
_PRIO_FLAGS: dict = {}
_PRIO_FLAGS_MAX = 64


def _home_device(params: Any) -> jax.Device:
    """The one device holding ``params``."""
    devices = jax.tree_util.tree_leaves(params)[0].devices()
    if len(devices) != 1:
        raise ValueError(f"ServeEngine params must sit on one device, got "
                         f"{sorted(map(str, devices))}")
    return next(iter(devices))


def _step_flags(priority: int) -> ContinueFlags:
    if priority <= 0:
        return _STEP_FLAGS
    flags = _PRIO_FLAGS.get(priority)
    if flags is None:
        flags = ContinueFlags(enqueue_complete=True, priority=priority)
        if len(_PRIO_FLAGS) < _PRIO_FLAGS_MAX:
            _PRIO_FLAGS[priority] = flags
    return flags


class ServeEngine:
    """Continuous-batching engine over ``max_batch`` decode slots.

    Single-consumer: exactly one thread drives ``step()``/``run()`` (the
    decode loop); any thread may ``submit()``. Slot state is touched only
    by the loop thread — continuations registered here run on it because
    the CRs use the default ``thread=application`` policy and the loop is
    the only thread that calls into the engine.

    Paged-mode knobs: ``page_size`` tokens per KV page, ``max_seq_len``
    (prompt + generation bound per request, default ``max_cache_len``),
    ``total_pages`` in the pool (default ``max_batch * ceil(max_seq_len /
    page_size)`` — shrink it, or raise ``max_batch``, to oversubscribe).

    Speculative knobs (paged only): ``speculate=K`` compiles a verify
    step scoring K drafts + 1 real token per slot per iteration;
    ``drafter`` plugs any ``serve.drafter.Drafter`` (default: n-gram
    prompt lookup). Requests opt out (``speculate=0``) or cap their own
    K per step; accepted runs advance a slot several tokens per step
    while staying token-identical to non-speculative greedy decode.
    """

    def __init__(self, cfg: ModelConfig, params: Any, *,
                 max_batch: int = 4,
                 max_cache_len: int = 256,
                 max_inflight: int = 2,
                 engine: Optional[Engine] = None,
                 scheduler: Union[str, Scheduler] = "fifo",
                 paged: Optional[bool] = None,
                 page_size: int = 16,
                 total_pages: Optional[int] = None,
                 max_seq_len: Optional[int] = None,
                 speculate: int = 0,
                 drafter: Optional[Drafter] = None,
                 fused: Optional[bool] = None) -> None:
        if cfg.family == AUDIO:
            raise NotImplementedError(
                "ServeEngine drives token-in/token-out LM decode; audio "
                "enc-dec serving still goes through serve.steps directly")
        if paged is None:
            paged = paged_supported(cfg)
        elif paged and not paged_supported(cfg):
            raise ValueError(
                f"paged KV cache unsupported for {cfg.name!r} "
                "(needs dense/MoE family, scan_layers, no sliding window)")
        if speculate and not paged:
            raise ValueError(
                "speculative decoding runs through the paged verify step; "
                "speculate > 0 requires paged=True")
        if fused and not paged:
            raise ValueError("fused paged-attention steps require paged=True")
        self.cfg = cfg
        self.params = params
        # every array the engine makes lives beside its params (a router
        # replica's params sit on that replica's chip)
        self.device = _home_device(params)
        self.max_batch = int(max_batch)
        self.max_cache_len = int(max_cache_len)
        self.max_inflight = max(1, int(max_inflight))
        self.paged = bool(paged)
        # fused (default in paged mode): the whole batch runs through ONE
        # lm_paged_decode call — the paged-attention kernel walks page
        # tables on device (gather + attend + accept-masked write), so
        # paged decode needs no _gather_pages view, no write tables, and
        # no per-slot vmap. fused=False keeps the original unfused
        # gather/scatter steps (the A/B baseline the kernel benchmark
        # measures against).
        self.fused = bool(fused) if fused is not None else self.paged
        self.speculate = max(0, int(speculate))
        self.drafter = drafter if drafter is not None else NgramDrafter()
        self._own_engine = engine is None
        self.engine = engine if engine is not None else \
            Engine(scheduler=scheduler)
        self.batcher = Batcher(self.engine)
        # decode-step completions ride a plain CR; the enqueue_complete
        # knob (even an already-materialized step flows through the
        # continuation path) attaches per registration via _STEP_FLAGS —
        # no dedicated CR per flag combination needed anymore
        self.cr_steps = self.engine.continue_init()

        S = self.max_batch
        self.pool: Optional[PagePool] = None
        if self.paged:
            self.page_size = int(page_size)
            self.max_seq_len = int(max_seq_len or max_cache_len)
            self.max_pages = pages_for(self.max_seq_len, self.page_size)
            # padded gather width: every per-slot view is _table_pages
            # pages — max_pages a request may hold, plus scratch slack so
            # a verify step starting on the last real page can write its
            # whole K+1 window without dynamic-slice clamping (the slack
            # is table-padded to the null page, so the overflow lands in
            # the scratch page, never a real one)
            self._spec_pad = pages_for(self.speculate, self.page_size) \
                if self.speculate else 0
            self._table_pages = self.max_pages + self._spec_pad
            self._padded_len = self._table_pages * self.page_size
            n_pool = int(total_pages) if total_pages is not None \
                else S * self.max_pages
            self.pool = PagePool(cfg, n_pool, self.page_size,
                                 device=self.device)
            self._tables = np.full((S, self._table_pages),
                                   self.pool.null_page, np.int32)
            # device-resident mirror of _tables, refreshed incrementally:
            # only rows touched since the last step re-upload (placement /
            # eviction), instead of the full (S, table_pages) host → device
            # transfer every dispatch
            self._tables_dev: Optional[jax.Array] = None
            self._tables_dirty: Set[int] = set()
            self._prefill_fn = jax.jit(
                make_prefill_step(cfg, self._padded_len))
            if self.fused:
                self._decode_fn = jax.jit(
                    make_fused_paged_decode_step(cfg, self.page_size),
                    donate_argnums=(1,))
                self._suffix_fn = jax.jit(
                    make_fused_paged_suffix_step(cfg, self.page_size),
                    donate_argnums=(1,))
            else:
                self._decode_fn = jax.jit(
                    make_paged_decode_step(cfg, self.page_size,
                                           return_tokens=True),
                    donate_argnums=(1,))
                self._suffix_fn = jax.jit(
                    make_paged_suffix_step(cfg, self.page_size),
                    donate_argnums=(1,))
            self._scatter_fn = jax.jit(
                make_prefill_scatter(cfg, self.page_size),
                donate_argnums=(0,))
            if self.speculate:
                vf = make_fused_paged_verify_step(cfg, self.page_size,
                                                  self.speculate) \
                    if self.fused else \
                    make_paged_verify_step(cfg, self.page_size,
                                           self.speculate)
                self._verify_fn = jax.jit(vf, donate_argnums=(1,))
                self._verify_pages = 1 + pages_for(self.speculate,
                                                   self.page_size)
        else:
            self._prefill_fn = jax.jit(
                make_prefill_step(cfg, self.max_cache_len))
            self._decode_fn = jax.jit(make_batched_decode_step(cfg),
                                      donate_argnums=(1,))

        # -- slot state (loop thread only) --
        self._slots: List[Optional[Request]] = [None] * S
        self._draining: Set[int] = set()      # token budget met, step in flight
        self._verifying: Set[int] = set()     # verify step in flight
        self._pos = np.zeros(S, np.int32)     # next write position per slot
        self._cache: Any = None               # dense mode: stacked caches
        self._tokens: Any = None              # next input tokens (S, 1, 1)
        # speculative: per-slot host context (prompt + emitted tokens),
        # appended by the prefill/verify continuations as device steps
        # actually complete — what the drafter matches against
        self._ctx: List[Optional[List[int]]] = [None] * S
        self._inflight = 0                    # dispatched, not-yet-complete steps
        # in-order commit of device-work continuations (``_after_device``):
        # sequence numbers handed out at dispatch / next one to commit, and
        # continuations that ran ahead of an earlier one, parked by number
        self._dispatched = 0
        self._committed = 0
        self._parked: dict = {}
        self._stalled_at: Optional[int] = None  # pages_in_use at last deferral
        self._retired: List[Request] = []
        self._lock = threading.Lock()         # guards _retired for readers
        self.stats = {"steps": 0, "prefills": 0, "retired": 0,
                      "slot_steps": 0, "padded_steps": 0, "cancelled": 0,
                      "suffix_steps": 0, "suffix_tokens": 0, "deferred": 0,
                      "max_active": 0, "verify_steps": 0, "spec_tokens": 0,
                      "draft_proposed": 0, "draft_accepted": 0,
                      "stopped": 0, "expired": 0}

    # ------------------------------------------------------------- clients
    def submit(self, request: Request) -> Request:
        """Thread-safe request intake (delegates to the Batcher CR)."""
        if self.paged:
            plen = int(np.asarray(request.prompt).reshape(-1).shape[0])
            total = plen + request.max_new_tokens
            if total > self.max_seq_len:
                raise ValueError(
                    f"request needs {total} tokens > max_seq_len="
                    f"{self.max_seq_len}")
            if pages_for(total, self.page_size) > self.pool.total_pages:
                raise ValueError(
                    f"request needs more pages than the pool holds "
                    f"({self.pool.total_pages})")
        tr = _obs.TRACE
        if tr is not None and tr.want(request.req_id):
            tr.evt(_obs_events.REQ_SUBMIT, request.req_id, "engine")
        return self.batcher.submit(request)

    def submit_async(self, request: Request) -> Promise:
        """Submit and get an awaitable ``Promise`` for the request.

        The promise resolves with the generated token list at retirement
        (a ``Request`` is a ``Completable``; its completion payload is the
        tokens) and rejects with ``PromiseCancelled`` if the request is
        cancelled. ``promise.cancel()`` cancels the request. Awaitable from
        asyncio (loop-safe wakeups) or blockable via ``promise.result()``
        — but never from the decode-loop thread itself.
        """
        # submit first: a rejected submit (seq-len/page validation, closed
        # intake) must not leave a never-settling registration on the
        # promise CR. Wrap-after-submit is safe — the resolution
        # registration uses enqueue_complete, so a request that races to
        # retirement still resolves through the continuation path.
        self.submit(request)
        return self.engine.wrap(request)

    def close_intake(self) -> None:
        self.batcher.close()

    @property
    def retired(self) -> List[Request]:
        with self._lock:
            return list(self._retired)

    # ---------------------------------------------------------- slot state
    def _put(self, host: Any) -> jax.Array:
        """Upload host state to the engine's device as a private copy.

        The engine mutates ``_pos`` and ``_tables`` right after
        dispatching a step that reads them. On the CPU backend
        ``jnp.asarray``/``jax.device_put`` may alias an aligned NumPy
        buffer, and ``jnp.array`` may read it after returning, so the step
        could see a later step's state. A NumPy copy that nothing else
        holds cannot change under it."""
        return jax.device_put(np.array(host), self.device)

    def _after_device(self, out: Any, fn, meta: Any, priority: int) -> None:
        """Run ``fn(meta)`` once ``out`` is complete, in dispatch order.

        Every prefill, decode and verify step reads what the steps before
        it wrote, yet a poll scan can find a later step's array ready
        first (it polled the earlier one just before that completed), and
        the scheduler runs higher-priority continuations first. Tokens
        must be delivered and slots retired in dispatch order, so a
        continuation that runs ahead of an earlier one is parked until
        that one has committed."""
        seq = self._dispatched
        self._dispatched += 1
        self.engine.continue_when(ArrayOp(out), self._commit,
                                  (seq, fn, meta), cr=self.cr_steps,
                                  flags=_step_flags(priority))

    def _commit(self, statuses, data) -> None:
        seq, fn, meta = data
        self._parked[seq] = (fn, meta)
        while self._committed in self._parked:
            fn, meta = self._parked.pop(self._committed)
            self._committed += 1
            fn(meta)

    def _ensure_state(self) -> None:
        if self._tokens is None:
            self._tokens = jnp.zeros((self.max_batch, 1, 1), jnp.int32,
                                     device=self.device)
        if self.paged:
            self.pool.ensure_arrays()
        elif self._cache is None:
            base = lm.init_cache(self.cfg, 1, self.max_cache_len)
            self._cache = jax.device_put(jax.tree_util.tree_map(
                lambda x: jnp.stack([x] * self.max_batch), base),
                self.device)

    def _free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self._slots) if r is None]

    @property
    def active(self) -> int:
        return sum(1 for r in self._slots if r is not None)

    # ------------------------------------------------------------ admission
    def _admit(self) -> int:
        # after a capacity deferral, don't re-pop and re-hash the queue
        # every loop spin — admission can only succeed once a retirement
        # or cancellation has returned pages to the pool
        if self._stalled_at is not None:
            if self.pool.pages_in_use >= self._stalled_at:
                return 0
            self._stalled_at = None
        free = self._free_slots()
        reqs = self.batcher.admit(len(free))
        admitted = 0
        for i, req in enumerate(reqs):
            if not self._place(req, free):
                # page pool can't cover the request's worst case yet:
                # return it (and everything behind it, preserving arrival
                # order) to the queue head; stats count stall events, not
                # retries
                self.stats["deferred"] += 1
                self._stalled_at = self.pool.pages_in_use
                for r in reversed(reqs[i:]):
                    self.batcher.requeue(r)
                break
            admitted += 1
        return admitted

    def _place(self, req: Request, free: List[int]) -> bool:
        """Prefill ``req`` and seat it in a slot. False = defer (paged
        capacity); True = placed (or answered outright by prefill)."""
        prompt = self._put(np.asarray(req.prompt, np.int32).reshape(1, -1))
        plen = prompt.shape[1]
        tr = _obs.TRACE
        t0 = None
        if tr is not None and tr.want(req.req_id):
            # the admission span runs arrival -> placement: the queue
            # delay the SLO report attributes to intake, not compute
            t0 = tr.now()
            tr.evt(_obs_events.REQ_ADMIT, req.req_id, "engine",
                   ts=req.arrival_time, dur=t0 - req.arrival_time)
        if req.max_new_tokens == 1:
            # single-token request: prefill answers it outright; it never
            # occupies a decode slot (nor, in paged mode, any pages)
            logits, _ = self._prefill_fn(self.params, {"tokens": prompt})
            first = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            req.push_device_token(first[0])
            self.stats["prefills"] += 1
            self._after_device(first, self._on_prefill_done,
                               (req, True, None, first, t0), req.priority)
            return True

        self._ensure_state()
        if self.paged:
            placed = self._prefill_paged(req, prompt)
            if placed is None:
                return False
            first = placed
        else:
            logits, cache1 = self._prefill_fn(self.params, {"tokens": prompt})
            first = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)

        slot = free.pop(0)
        if not self.paged:
            self._cache = jax.tree_util.tree_map(
                lambda sc, pc: sc.at[slot].set(pc), self._cache, cache1)
        req.push_device_token(first[0])
        self.stats["prefills"] += 1
        ctx = None
        if self.speculate:
            # host context for the drafter: the prompt now; the first
            # token when its array completes (prefill continuation), and
            # every accepted run as verify continuations fire
            ctx = [int(t) for t in
                   np.asarray(req.prompt, np.int32).reshape(-1)]
        self._seat_slot(slot, req, first[:, None], plen, ctx=ctx)
        self._after_device(first, self._on_prefill_done,
                           (req, False, slot, first, t0), req.priority)
        return True

    def _seat_slot(self, slot: int, req: Request, token0: Any, plen: int,
                   *, ctx: Optional[List[int]] = None) -> None:
        """Seat an already-prefilled request into decode slot ``slot`` —
        the role-neutral half of placement, shared by the colocated
        prefill path (``_place``) and remote KV ingestion
        (``serve.disagg.DecodeWorker``). ``token0`` is the request's next
        input token: a device ``(1, 1)`` array from a local prefill, or a
        host int delivered by a remote prefill role. ``req.page_ids``
        must already hold the request's pages (paged mode)."""
        tr = _obs.TRACE
        if tr is not None and tr.want(req.req_id):
            tr.evt(_obs_events.REQ_SEAT, req.req_id, "engine", meta=slot)
        if self.paged:
            self._tables[slot, :] = self.pool.null_page
            self._tables[slot, :len(req.page_ids)] = req.page_ids
            self._tables_dirty.add(slot)
        self._tokens = self._tokens.at[slot].set(token0)
        self._pos[slot] = plen
        self._slots[slot] = req
        self._ctx[slot] = ctx

    def _prefill_paged(self, req: Request,
                       prompt: jax.Array) -> Optional[jax.Array]:
        """Allocate pages, reuse any cached prefix, fill the prompt KV.

        Returns the first-token array (1,), or None when the pool can't
        cover the worst-case footprint (defer — nothing was allocated)."""
        pool, ps = self.pool, self.page_size
        plen = prompt.shape[1]
        n_pages = pages_for(plen + req.max_new_tokens, ps)
        shared = pool.match_prefix(req.prompt)
        owned = pool.alloc(n_pages - len(shared))
        if owned is None:
            return None
        for p in shared:
            pool.retain(p)
        table = shared + owned
        req.page_ids = table
        req.shared_prefix_tokens = len(shared) * ps
        tr = _obs.TRACE
        if tr is not None and tr.want(req.req_id):
            tr.evt(_obs_events.REQ_PAGES_ALLOC, req.req_id, "engine",
                   meta=len(table))

        if shared:
            # prefix hit: shared pages already hold positions [0, m*ps);
            # one chunked suffix-prefill call runs the remaining prompt
            # tokens against them — the shared prefix is never recomputed
            # and writes land in owned pages only (scatter table maps
            # shared entries to the null page)
            pool.stats["prefix_hits"] += 1
            pool.stats["prefix_tokens_reused"] += len(shared) * ps
            start = len(shared) * ps
            tail = plen - start
            scat = np.full(self._table_pages, pool.null_page, np.int32)
            scat[len(shared):len(table)] = table[len(shared):]
            # pad the tail to a page multiple so at most max_pages suffix
            # shapes ever compile; pad rows are causally invisible to the
            # real rows, and the garbage they write at positions >= plen
            # is overwritten by the decode step for that position before
            # anything attends to it
            padded = pages_for(tail, ps) * ps
            suffix = prompt[:, start:]
            if padded != tail:
                suffix = jnp.pad(suffix, ((0, 0), (0, padded - tail)))
            if self.fused:
                # the fused kernel writes rows [0, tail) through the
                # gather table itself — the prefix is page-aligned, so
                # every written entry is request-owned; shared pages and
                # padding rows are untouched (n_valid masks the pad)
                logits, pool.arrays = self._suffix_fn(
                    self.params, pool.arrays, suffix,
                    self._put([start]), self._padded_table(table)[None],
                    self._put([tail]))
            else:
                logits, pool.arrays = self._suffix_fn(
                    self.params, pool.arrays, suffix, jnp.int32(start),
                    self._padded_table(table), self._put(scat))
            self.stats["suffix_steps"] += 1
            self.stats["suffix_tokens"] += tail
            first = jnp.argmax(logits[:, tail - 1], axis=-1).astype(jnp.int32)
        else:
            # cold: dense prefill over the whole prompt, then blit the
            # prompt pages into the pool in one scatter
            logits, cache1 = self._prefill_fn(self.params, {"tokens": prompt})
            first = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            scatter_table = np.full(self._table_pages, pool.null_page,
                                    np.int32)
            n_prompt_pages = pages_for(plen, ps)
            scatter_table[:n_prompt_pages] = table[:n_prompt_pages]
            pool.arrays = self._scatter_fn(pool.arrays, cache1,
                                           self._put(scatter_table))
        pool.register_prefix(req.prompt, table)
        return first

    def _padded_table(self, table: Sequence[int]) -> jax.Array:
        out = np.full(self._table_pages, self.pool.null_page, np.int32)
        out[:len(table)] = table
        return self._put(out)

    def _device_tables(self) -> jax.Array:
        """Device copy of the page tables, updated incrementally.

        Placement and eviction mark their slot dirty; each dispatch then
        uploads only the dirty rows into the resident array instead of
        re-transferring all (S, table_pages) entries. The row-set scatter
        compiles once per distinct dirty-row COUNT — bounded by
        ``max_batch + 1`` shapes over the engine's lifetime. Steady-state
        decode (no placements) re-uses the resident array with zero
        transfer."""
        if self._tables_dev is None:
            self._tables_dev = self._put(self._tables)
            self._tables_dirty.clear()
        elif self._tables_dirty:
            rows = sorted(self._tables_dirty)
            self._tables_dev = self._tables_dev.at[
                self._put(np.asarray(rows, np.int32))].set(
                self._put(self._tables[rows]))
            self._tables_dirty.clear()
        return self._tables_dev

    def _on_prefill_done(self, meta) -> None:
        req, retire_now, slot, first, t0 = meta
        if t0 is not None:
            tr = _obs.TRACE
            if tr is not None:
                tr.evt(_obs_events.REQ_PREFILL, req.req_id, "engine",
                       ts=t0, dur=tr.now() - t0)
        req.on_first_token()
        # deliver the first token (array complete by continuation time, so
        # int() never blocks): streams see it here — before retirement —
        # and stop-sequence matching starts with it
        finished = req.deliver([int(first[0])])
        if retire_now:
            # the budget is complete: the output the engine already paid
            # for is returned even if the deadline just lapsed
            self._retire(req, stopped=finished == "stop")
            return
        # speculative context append. Guard against the slot having been
        # evicted (cancel) and possibly reseated before this fires.
        if (slot is not None and self._ctx[slot] is not None
                and self._slots[slot] is req):
            self._ctx[slot].append(int(first[0]))
        if req.req_state in (RequestState.PREFILLING, RequestState.DECODING):
            if finished == "stop":
                self._finish_slot(slot, req, "stop")
            elif req.past_deadline():
                self._finish_slot(slot, req, "expired")

    # --------------------------------------------------------------- decode
    def _sweep_dead(self, live: List[Tuple[int, Request]]) -> None:
        """Drop cancellations and already-missed deadlines before paying
        for a step (shared by the plain-decode and speculative-verify
        dispatch paths). Deadline expiry is normally noticed by the
        step-completion continuation; this dispatch-side sweep only saves
        the step for work that is already doomed."""
        now = time.monotonic()
        for i, r in list(live):
            if r.req_state is RequestState.CANCELLED:
                self._evict_slot(i, r)
                self.stats["cancelled"] += 1
                live.remove((i, r))
            elif r.past_deadline(now):
                self._evict_slot(i, r)
                self._expire(r)
                live.remove((i, r))

    def _dispatch_step(self) -> bool:
        if self.speculate:
            return self._dispatch_verify()
        live = [(i, r) for i, r in enumerate(self._slots)
                if r is not None and i not in self._draining]
        self._sweep_dead(live)
        if not live:
            return False
        if self.paged and self.fused:
            # n_valid: 1 = write this slot's token, 0 = idle/draining slot
            # (the kernel then writes nothing and outputs zeros for it —
            # strictly tighter than the unfused path, which runs idle
            # lanes too and parks their garbage writes on the null page)
            nv = np.zeros(self.max_batch, np.int32)
            nv[[i for i, _ in live]] = 1
            nxt, self.pool.arrays = self._decode_fn(
                self.params, self.pool.arrays, self._tokens,
                self._put(self._pos), self._device_tables(), self._put(nv))
        elif self.paged:
            nxt, self.pool.arrays = self._decode_fn(
                self.params, self.pool.arrays, self._tokens,
                self._put(self._pos), self._device_tables())
        else:
            nxt, self._cache = self._decode_fn(
                self.params, self._cache, self._tokens,
                self._put(self._pos))
        # the jitted step surfaces per-slot next tokens directly: (S, 1)
        self._tokens = nxt[..., None]                       # (S, 1, 1)
        stepped: List[Tuple[int, Request, bool]] = []
        prio = 0
        for i, r in live:
            r.push_device_token(nxt[i, 0])
            self._pos[i] += 1
            done = r.remaining == 0
            if done:
                self._draining.add(i)
            stepped.append((i, r, done))
            prio = max(prio, r.priority)
        self._inflight += 1
        self.stats["steps"] += 1
        self.stats["slot_steps"] += len(live)
        self.stats["padded_steps"] += self.max_batch - len(live)
        self.stats["max_active"] = max(self.stats["max_active"], len(live))
        tr = _obs.TRACE
        t0 = tr.now() if tr is not None else None
        self._after_device(nxt, self._on_step_done, (stepped, nxt, t0), prio)
        return True

    def _on_step_done(self, meta) -> None:
        """Per-token bookkeeping when the step's device work is actually
        complete: deliver each slot's token (streams wake here), then
        retire slots that finished — by budget, by a stop-sequence match,
        or by deadline expiry — releasing their pages in this same
        continuation."""
        stepped, nxt, t0 = meta
        self._inflight -= 1
        arr = np.asarray(nxt)
        now = time.monotonic()
        tr = _obs.TRACE
        if tr is not None and t0 is not None:
            # one span per sampled request riding this step: dispatch ->
            # device-complete, the timeline's per-token compute block
            for slot, req, _ in stepped:
                if tr.want(req.req_id):
                    tr.evt(_obs_events.REQ_STEP, req.req_id, "engine",
                           ts=t0, dur=now - t0, meta=slot)
        for slot, req, done in stepped:
            if done:
                self._draining.discard(slot)
            finished = req.deliver([int(arr[slot, 0])])
            state = req.req_state
            if state is RequestState.FINISHED or \
                    state is RequestState.EXPIRED:
                # an earlier continuation (stop/deadline) already finished
                # this request and freed the slot; the delivery above was
                # dropped there too
                continue
            if state is RequestState.CANCELLED:
                # non-draining slots are swept at the next dispatch; a
                # draining slot sees no further dispatch, so free it here
                if done and self._slots[slot] is req:
                    self._evict_slot(slot, req)
                    self.stats["cancelled"] += 1
                continue
            if finished == "stop":
                self._finish_slot(slot, req, "stop")
            elif done:
                # a completed budget outranks a just-lapsed deadline:
                # the full output is in hand, return it
                self._finish_slot(slot, req, "retire")
            elif req.past_deadline(now):
                self._finish_slot(slot, req, "expired")

    # ---------------------------------------------------------- speculative
    def _slot_drafts(self, slot: int, req: Request) -> List[int]:
        """Draft tokens for one slot: the per-request knob caps the
        engine's compiled K, the token budget caps the window (never
        propose past ``remaining - 1`` — the verify step always emits at
        least one real token), and the drafter may return fewer still."""
        k = self.speculate if req.speculate is None \
            else min(req.speculate, self.speculate)
        k = min(k, req.remaining - 1)
        if k <= 0 or self._ctx[slot] is None:
            return []
        return list(self.drafter.draft(self._ctx[slot], k))[:k]

    def _dispatch_verify(self) -> bool:
        """One speculative verify step for every steppable slot.

        Slots whose previous verify continuation has not fired yet are
        excluded (their position/token state is only updated when the
        device step completes); freshly admitted slots join immediately.
        Slots with no usable drafts run with k=0 — the verify step then
        degenerates to plain greedy decode for them (one emitted token),
        so mixed speculative / non-speculative batches share one step.
        """
        live = [(i, r) for i, r in enumerate(self._slots)
                if r is not None and i not in self._verifying]
        self._sweep_dead(live)
        if not live:
            return False
        S, K = self.max_batch, self.speculate
        drafts = np.zeros((S, K), np.int32)
        n_drafts = np.zeros(S, np.int32)
        for i, r in live:
            d = self._slot_drafts(i, r)
            n_drafts[i] = len(d)
            drafts[i, :len(d)] = d
        tokens = jnp.concatenate(
            [self._tokens, self._put(drafts)[:, None, :]], axis=2)
        if self.fused:
            # no host-built write tables at all: the kernel accept-masks
            # the window to n_valid = 1 + live drafts (0 for idle /
            # still-verifying slots) and routes overflow into the scratch
            # page through the gather table's null padding
            nv = np.zeros(S, np.int32)
            for i, _ in live:
                nv[i] = 1 + n_drafts[i]
            emitted, accepts, self.pool.arrays = self._verify_fn(
                self.params, self.pool.arrays, tokens,
                self._put(self._pos), self._device_tables(), self._put(nv))
        else:
            # write tables: rows for idle / still-verifying slots stay all
            # null, so their (garbage) lanes scatter into the scratch page
            wtables = np.full((S, self._verify_pages), self.pool.null_page,
                              np.int32)
            for i, r in live:
                wtables[i] = self.pool.write_table(r.page_ids,
                                                   int(self._pos[i]),
                                                   self._verify_pages)
            emitted, accepts, self.pool.arrays = self._verify_fn(
                self.params, self.pool.arrays, tokens,
                self._put(self._pos), self._device_tables(),
                self._put(wtables), self._put(n_drafts))
        self._verifying.update(i for i, _ in live)
        self._inflight += 1
        self.stats["steps"] += 1
        self.stats["verify_steps"] += 1
        self.stats["slot_steps"] += len(live)
        self.stats["padded_steps"] += self.max_batch - len(live)
        self.stats["draft_proposed"] += int(n_drafts.sum())
        self.stats["max_active"] = max(self.stats["max_active"], len(live))
        tr = _obs.TRACE
        t0 = tr.now() if tr is not None else None
        self._after_device(emitted, self._on_verify_done,
                           (live, emitted, accepts, n_drafts, t0),
                           max(r.priority for _, r in live))
        return True

    def _on_verify_done(self, meta) -> None:
        """Accept bookkeeping — runs when the verify step's arrays are
        actually complete, so the host reads below never block. Mixed
        accept lengths advance each slot independently; a slot whose
        accepted run reaches its token budget retires right here,
        mid-verify, through the same continuation."""
        live, emitted, accepts, n_drafts, t0 = meta
        self._inflight -= 1
        emitted = np.asarray(emitted)
        accepts = np.asarray(accepts)
        now = time.monotonic()
        tr = _obs.TRACE
        if tr is not None and t0 is not None:
            for i, req in live:
                if tr.want(req.req_id):
                    tr.evt(_obs_events.REQ_STEP, req.req_id, "engine",
                           ts=t0, dur=now - t0, meta=i)
        upd_slots: List[int] = []
        upd_tokens: List[int] = []
        for i, req in live:
            state = req.req_state
            # stale entry: an earlier continuation (prefill stop/deadline)
            # already finished this request and freed the slot — which may
            # since have been reseated (possibly with its own verify in
            # flight). Touch NOTHING keyed by the slot index then.
            stale = self._slots[i] is not req
            if not stale:
                self._verifying.discard(i)
            if state is RequestState.FINISHED or \
                    state is RequestState.EXPIRED:
                continue
            if state is RequestState.CANCELLED:
                # cancel mid-verify: the whole accepted run is dropped —
                # deliver() would refuse it anyway (cancel() returned
                # while this step was in flight), so don't even push
                if not stale:
                    self._evict_slot(i, req)
                    self.stats["cancelled"] += 1
                continue
            a = int(accepts[i])
            n_emit = min(a + 1, req.remaining)   # a <= remaining-1 by cap
            toks = [int(t) for t in emitted[i, :n_emit]]
            for t in toks:
                req.push_device_token(t)
            req.draft_tokens_proposed += int(n_drafts[i])
            req.draft_tokens_accepted += a
            self.stats["draft_accepted"] += a
            self.stats["spec_tokens"] += n_emit
            # the whole accepted run delivers in one call: streams see a
            # burst, stop matching scans it token by token
            finished = req.deliver(toks)
            if self._ctx[i] is not None:
                self._ctx[i].extend(toks)
            self._pos[i] += n_emit
            if finished == "stop":
                self._finish_slot(i, req, "stop")
            elif req.remaining == 0:
                # completed budget outranks a just-lapsed deadline
                self._finish_slot(i, req, "retire")
            elif req.past_deadline(now):
                self._finish_slot(i, req, "expired")
            else:
                upd_slots.append(i)
                upd_tokens.append(toks[-1])
        if upd_slots:
            # fixed-shape masked update (a variable-length index scatter
            # would recompile per distinct count of advancing slots)
            mask = np.zeros(self.max_batch, bool)
            vals = np.zeros(self.max_batch, np.int32)
            mask[upd_slots] = True
            vals[upd_slots] = upd_tokens
            self._tokens = jnp.where(
                self._put(mask)[:, None, None],
                self._put(vals)[:, None, None], self._tokens)

    def _finish_slot(self, slot: Optional[int], req: Request,
                     kind: str) -> None:
        """Terminal transition from a step-completion continuation: free
        the slot — releasing the request's pages in this same continuation
        — and finish the request (``kind``: "retire" for budget, "stop"
        for a stop-sequence match, "expired" for a missed deadline). A
        later step already in flight for this slot may still write the
        released pages: the same stale-write window the cancel path
        tolerates (device dispatch order plus causal masking keep the
        garbage invisible before it is overwritten)."""
        if slot is not None and self._slots[slot] is req:
            self._draining.discard(slot)
            self._verifying.discard(slot)
            self._evict_slot(slot, req)
        else:
            # slot already freed (or reseated) by an earlier path — make
            # sure the pages still can't leak (release is idempotent)
            self._release_pages(req)
        if kind == "expired":
            self._expire(req)
        else:
            self._retire(req, stopped=kind == "stop")

    def _evict_slot(self, slot: int, req: Request) -> None:
        """Free a slot and return the request's pages to the pool (every
        exit path — retirement, cancellation mid-decode or mid-drain —
        funnels through here, so pages can never leak)."""
        self._slots[slot] = None
        self._pos[slot] = 0
        self._ctx[slot] = None
        if self.paged:
            self._tables[slot, :] = self.pool.null_page
            self._tables_dirty.add(slot)
        self._release_pages(req)

    def _release_pages(self, req: Request) -> None:
        if self.paged and req.page_ids:
            tr = _obs.TRACE
            if tr is not None and tr.want(req.req_id):
                tr.evt(_obs_events.REQ_PAGES_RELEASE, req.req_id, "engine",
                       meta=len(req.page_ids))
            self.pool.release(req.page_ids)
            req.page_ids = []

    def _retire(self, req: Request, stopped: bool = False) -> None:
        if not req.retire():
            # lost the race to a concurrent cancel() (an idempotent
            # re-retire of an already-finished request counts nothing)
            if req.req_state is RequestState.CANCELLED:
                self.stats["cancelled"] += 1
            return
        if stopped:
            self.stats["stopped"] += 1
        with self._lock:
            self._retired.append(req)
        self.stats["retired"] += 1

    def _expire(self, req: Request) -> None:
        """Deadline-expired: fail the request (partial tokens kept)."""
        if req.expire():
            self.stats["expired"] += 1
        elif req.req_state is RequestState.CANCELLED:
            self.stats["cancelled"] += 1

    # ----------------------------------------------------------------- loop
    def step(self) -> bool:
        """One loop iteration: admit, dispatch (windowed), progress.

        Returns True if any work was started or completed.
        """
        admitted = self._admit()
        dispatched = False
        if self._inflight < self.max_inflight:
            dispatched = self._dispatch_step()
        before = self.stats["retired"]
        self.engine.tick()   # discover step completions, run continuations
        return bool(admitted) or dispatched or \
            self.stats["retired"] != before

    @property
    def idle(self) -> bool:
        """Nothing queued, occupied, or in flight — including prefill/step
        continuations still registered on the step CR (a single-token
        request's whole life is one prefill continuation)."""
        return (not self._pending_intake() and self.active == 0
                and self._inflight == 0
                and self.cr_steps.active_count == 0)

    def _pending_intake(self) -> bool:
        return bool(self.batcher.queued or self.batcher.cr.active_count)

    def run(self, timeout: Optional[float] = None,
            idle_sleep: float = 5e-5, until=None) -> List[Request]:
        """Drive the loop until intake is closed and everything retired
        (or until the ``until()`` predicate flips true, when given —
        benchmarks use it to serve a fixed workload on a warm engine)."""
        deadline = None if timeout is None else time.monotonic() + timeout
        done = until if until is not None else \
            (lambda: self.batcher.closed and self.idle)
        while not done():
            if deadline is not None and time.monotonic() > deadline:
                raise TimeoutError(
                    f"serve loop timed out: active={self.active} "
                    f"inflight={self._inflight} queued={self.batcher.queued}")
            if not self.step():
                time.sleep(idle_sleep)
        return self.retired

    def _metrics_flat(self) -> dict:
        """Flat metrics dict — subclasses extend this before it is
        wrapped into the typed ``ServeMetrics`` by ``metrics()``."""
        out = summarize(self.retired)
        out.update(self.stats)
        out["paged"] = self.paged
        out["fused"] = self.fused
        out["speculate"] = self.speculate
        if self.stats["draft_proposed"]:
            # engine-wide accept rate (includes cancelled requests;
            # summarize() reports the finished-request rate)
            out["accept_rate_engine"] = (self.stats["draft_accepted"]
                                         / self.stats["draft_proposed"])
        if self.paged:
            out.update(self.pool.metrics())
        return out

    def metrics(self) -> ServeMetrics:
        return ServeMetrics.from_flat(self._metrics_flat())

    def shutdown(self) -> None:
        self.batcher.close()
        if self._own_engine:
            self.engine.shutdown()


def serve_requests(cfg: ModelConfig, params: Any,
                   requests: Sequence[Request], *,
                   max_batch: int = 4, max_cache_len: int = 256,
                   timeout: float = 300.0,
                   **kwargs: Any) -> List[Request]:
    """Convenience: serve a fixed request list to completion, in order."""
    eng = ServeEngine(cfg, params, max_batch=max_batch,
                      max_cache_len=max_cache_len, **kwargs)
    try:
        for r in requests:
            eng.submit(r)
        eng.close_intake()
        eng.run(timeout=timeout)
    finally:
        eng.shutdown()
    return list(requests)
