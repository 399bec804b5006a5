"""DecodeEngine: continuous-batching generation with chunked prefill.

One engine owns (a) a paged KV cache (``cache.PagedKVCache`` + the
per-layer device arrays) and (b) ONE compiled *mixed* step bound at a
fixed slot capacity — ``models.transformer.get_mixed_step_symbol`` —
that every iteration processes up to K prefill-chunk tokens of one
admitted prompt AND one decode token for every active slot in the same
donated launch (Sarathi-Serve-style stall-free scheduling: prompt
processing piggybacks on the memory-bound decode iteration instead of
monopolizing the device for a full-prompt prefill).  The pow2 prefill
ladder this replaced cost one compiled program per bucket and stalled
every in-flight stream for the length of the longest prompt.

Execution discipline (the PR 2/3 invariant, extended to serving):

* every iteration is exactly ONE device launch — the compiled mixed
  step runs all slots plus the current prompt chunk; padded slots ride
  along masked (position -1), an empty chunk rides along with
  ``chunk_len == 0``;
* sequence raggedness (positions, chunk offsets/lengths, block tables)
  enters as runtime arrays, so steady state NEVER retraces — witnessed
  by ``decode_retraces``, which counts only retraces after each
  program's first (expected) compile;
* the only per-iteration host sync is reading the sampled token back
  (that readback *is* the streamed response); a completed prefill adds
  one first-token readback per ADMISSION, not per step.

Scheduling policy lives in ``scheduler.py``; this module is the device
half: mixed-step dispatch, cache threading (each step's new cache
arrays replace the bound inputs via ``NDArray._set_data``, so every
iteration sees one coherent cache), sampling, and telemetry.
"""
from __future__ import annotations

import collections as _collections
import threading
import time

import numpy as _np

from ..base import MXNetError
from ..pallas.dispatch import paged_attn_impl as _paged_attn_impl
from ..serving.batcher import (DeadlineExceededError, QueueFullError,
                               ServerClosedError, percentile as _percentile)
from ..telemetry import REGISTRY, tracing as _tracing
from .cache import CacheOOMError, PagedKVCache
from .scheduler import Scheduler, Sequence
from .spec import (ACCEPT_RATE, SPEC_ACCEPTED, SPEC_PROPOSED,
                   TOKENS_PER_LAUNCH, choose_spec_impl, make_drafter)

__all__ = ["DecodeEngine"]

QUEUE_DEPTH = REGISTRY.gauge(
    "decode_queue_depth", "sequences waiting for a decode slot",
    unit="sequences")
ACTIVE_SEQS = REGISTRY.gauge(
    "decode_active_sequences", "sequences occupying decode slots",
    unit="sequences")
ADMITTED = REGISTRY.counter(
    "decode_admitted", "sequences accepted into the wait queue")
COMPLETED = REGISTRY.counter(
    "decode_completed", "sequences finished (eos or length)")
FAILED = REGISTRY.counter(
    "decode_failed", "sequences failed (cache OOM, engine stop, error)")
EXPIRED = REGISTRY.counter(
    "decode_expired", "sequences expired before finishing (deadline)")
CANCELLED = REGISTRY.counter(
    "decode_cancelled", "sequences cancelled by the client "
    "(StreamHandle.cancel / dropped HTTP stream)")
PREFILLS = REGISTRY.counter(
    "decode_prefills", "prompts admitted into chunked prefill "
    "(admissions + preemption recomputes)")
PREFILL_CHUNKS = REGISTRY.counter(
    "decode_prefill_chunks", "prompt chunks processed by mixed decode "
    "steps (chunked prefill — one per iteration with a prompt in "
    "flight)")
CHUNK_BUDGET = REGISTRY.gauge(
    "decode_chunk_tokens", "per-iteration chunked-prefill token budget "
    "(MXNET_DECODE_CHUNK, pow2-padded)", unit="tokens")
PREEMPTIONS = REGISTRY.counter(
    "decode_preemptions", "sequences preempted-by-recompute on cache "
    "pressure")
STEPS = REGISTRY.counter(
    "decode_steps", "decode iterations dispatched (one compiled launch "
    "each)")
TOKENS = REGISTRY.counter(
    "decode_tokens", "tokens generated (prefill first-tokens included)")
STEP_MS = REGISTRY.histogram(
    "decode_step_ms", "wall time of one decode iteration (dispatch + "
    "token readback + bookkeeping)", unit="ms")
TTFT_MS = REGISTRY.histogram(
    "decode_ttft_ms", "time to first token (submit -> first streamed "
    "token, queue wait included)", unit="ms")
RETRACES = REGISTRY.counter(
    "decode_retraces", "decode/prefill program retraces AFTER each "
    "program's first compile — pinned at zero by tests", vital=True)
RELOADS = REGISTRY.counter(
    "decode_reloads", "successful hot weight reloads into a live engine")
TTFT_STEPS = REGISTRY.histogram(
    "decode_ttft_steps", "steps to first token (submit -> first emit, "
    "in mixed-step iterations) — the dispatch-count TTFT witness "
    "sentinel SLO rules watch (wall-clock is bandwidth noise in CPU "
    "containers)", unit="steps",
    bounds=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024))
ACCEPT_WINDOW = REGISTRY.gauge(
    "decode_accept_rate_window", "accepted/proposed draft-token ratio "
    "over the last MXNET_DECODE_ACCEPT_WINDOW slot-spans (default 256) "
    "— the sentinel's drift witness; decode_accept_rate is cumulative "
    "and cannot recover after a bad stretch", unit="ratio")


def _chunk_budget(chunk_tokens, max_context):
    """Resolve the per-iteration prefill-chunk token budget K:
    explicit arg > ``MXNET_DECODE_CHUNK`` > 64, capped at the context
    length and padded up to a power of two (one bind-time geometry —
    every chunk rides the same compiled mixed step)."""
    import os
    ck = chunk_tokens
    if ck is None:
        ck = int(os.environ.get("MXNET_DECODE_CHUNK", "0") or 0)
    ck = int(ck) if int(ck or 0) > 0 else 64
    p = 1
    while p < ck:
        p *= 2
    return min(p, int(max_context))


class DecodeEngine:
    """Generative serving engine for the decoder-only transformer
    (module docstring; knobs in docs/DECODE.md).

    Parameters
    ----------
    arg_params : training-checkpoint parameters (name -> NDArray/numpy)
    model_config : the ``transformer.get_symbol`` kwargs this checkpoint
        was trained with (num_classes, num_layers, d_model, num_heads,
        ffn_dim, seq_len, ...) — ``seq_len`` doubles as the maximum
        context length a sequence may reach.
    capacity : fixed decode batch slots (the compiled step's batch dim)
    block_size, num_blocks : KV-cache geometry (per layer, K and V each
        are ``(num_blocks, block_size, H, D)``)
    chunk_tokens : per-iteration prefill-chunk token budget K (default:
        ``MXNET_DECODE_CHUNK`` or 64; pow2-padded, capped at seq_len).
        Any prompt under ``seq_len`` is admissible — it prefills over
        ``ceil(len/K)`` mixed iterations without stalling decode.
    max_prefill_len, prefill_buckets : accepted-but-ignored (the pow2
        prefill ladder these configured is retired; chunked prefill
        serves every prompt length through the one mixed step)
    admission : 'continuous' (default) or 'static' (run-to-completion)
    eos_id : default end-of-sequence token id (None = length-stop only)
    """

    def __init__(self, arg_params, model_config, capacity=8, block_size=16,
                 num_blocks=64, chunk_tokens=None, max_prefill_len=None,
                 prefill_buckets=None, ctx=None, eos_id=None,
                 max_waiting=256, admission="continuous",
                 default_max_new_tokens=64, warmup=False, start=True,
                 spec_k=None, spec_impl=None, prefix_cache=None,
                 draft_params=None, draft_config=None):
        import os as _os
        from ..context import current_context
        from ..models import transformer
        from ..ndarray.ndarray import NDArray

        self._cfg = dict(model_config)
        self._cfg.pop("dropout", None)          # inference graphs
        self._ctx = ctx if ctx is not None else current_context()
        self.capacity = int(capacity)
        self._eos = eos_id
        self._default_max_new = int(default_max_new_tokens)
        self._max_context = int(self._cfg.get("seq_len", 1024))
        self._num_layers = int(self._cfg.get("num_layers", 12))
        bs = int(block_size)
        self._table_width = -(-self._max_context // bs)
        self._chunk_tokens = _chunk_budget(chunk_tokens,
                                           self._max_context)
        CHUNK_BUDGET.set(self._chunk_tokens)

        # --- speculative decoding + prefix sharing knobs (both default
        # OFF: docs/DECODE.md).  spec_k > 0 binds the span-verify step
        # (S = spec_k + 1 tokens per slot per launch) instead of the
        # one-token mixed step; the drafter follows the auto/force/off
        # contract of pallas.dispatch.choose_impl.
        from .. import config as _config
        if spec_k is None:
            spec_k = int(_os.environ.get("MXNET_DECODE_SPEC_K", "0") or 0)
        self._spec_k = max(int(spec_k), 0)
        self._spec_impl = None
        self._drafter = None
        if self._spec_k > 0:
            raw = (spec_impl if spec_impl is not None
                   else _os.environ.get("MXNET_DECODE_SPEC_IMPL", "auto"))
            self._spec_impl = choose_spec_impl(raw,
                                               draft_params is not None)
            if self._spec_impl is None:      # MXNET_DECODE_SPEC_IMPL=off
                self._spec_k = 0
            else:
                self._drafter = make_drafter(
                    self._spec_impl, draft_params, draft_config,
                    ctx=self._ctx, forced=(raw == "draft"))
                self._spec_impl = self._drafter.name
        self._span = self._spec_k + 1
        if prefix_cache is None:
            prefix_cache = _config.env_bool("MXNET_DECODE_PREFIX_CACHE",
                                            default=False)
        self._prefix_cache = bool(prefix_cache)
        self._prefix_flush = False    # set by swap_params, drained by _tick

        self.cache = PagedKVCache(num_blocks, bs,
                                  prefix_sharing=self._prefix_cache)
        self._sched = Scheduler(self.capacity, self.cache,
                                max_waiting=max_waiting,
                                admission=admission)

        # --- bind the ONE step at fixed capacity + chunk budget: the
        # mixed step (one decode token per slot) or, with speculation
        # on, the span-verify step (S tokens per slot through the same
        # chunk-attention primitive — get_spec_step_symbol)
        if self._spec_k > 0:
            msym = transformer.get_spec_step_symbol(
                block_size=bs, num_blocks=int(num_blocks), **self._cfg)
            self._exe = msym.simple_bind(
                ctx=self._ctx, grad_req="null",
                data=(self.capacity, self._span),
                positions=(self.capacity, self._span),
                span_start=(self.capacity,),
                span_len=(self.capacity,),
                block_table=(self.capacity, self._table_width),
                chunk_data=(1, self._chunk_tokens),
                chunk_positions=(1, self._chunk_tokens),
                chunk_start=(1,), chunk_len=(1,),
                chunk_table=(1, self._table_width))
        else:
            msym = transformer.get_mixed_step_symbol(
                block_size=bs, num_blocks=int(num_blocks), **self._cfg)
            self._exe = msym.simple_bind(
                ctx=self._ctx, grad_req="null", data=(self.capacity, 1),
                positions=(self.capacity, 1),
                block_table=(self.capacity, self._table_width),
                chunk_data=(1, self._chunk_tokens),
                chunk_positions=(1, self._chunk_tokens),
                chunk_start=(1,), chunk_len=(1,),
                chunk_table=(1, self._table_width))
        # the paged-attention implementation the step program is built
        # with: the knob is read when the program is traced, so a later
        # change of the environment must not change what stats() says
        self._attn_impl = _paged_attn_impl()
        self._cache_names = []
        for i in range(self._num_layers):
            self._cache_names += ["layer%d_k_cache" % i,
                                  "layer%d_v_cache" % i]
        self._cache_arrs = [self._exe.arg_dict[n] for n in self._cache_names]
        self.cache.attach_arrays(self._cache_arrs)
        # donated caches (MXNET_DECODE_DONATE, default on): the compiled
        # step takes the k/v cache buffers by donation and every dispatch
        # re-points the cache NDArrays at the step's outputs
        # (_commit_caches), so XLA updates the caches where they live —
        # no whole-cache copy in and out per token (docs/DECODE.md).
        # Block tables/positions are NOT donated: they are rebuilt
        # host-side and fed by copy each iteration.
        self._donate = _config.env_bool("MXNET_DECODE_DONATE",
                                        default=True)
        if self._donate:
            self._donate = bool(self._exe.donate_args(self._cache_names))
        self._inputs = ("data", "positions", "block_table", "chunk_data",
                        "chunk_positions", "chunk_start", "chunk_len",
                        "chunk_table", "span_start", "span_len")
        self._weight_names = [n for n in self._exe.arg_dict
                              if n not in self._inputs
                              and n not in self._cache_names]
        self._check_params(arg_params)
        self._exe.copy_params_from(
            # analyze: ok(hostsync) checkpoint params are host-resident; one staging copy at engine construction, not on the step path
            {k: v if isinstance(v, NDArray) else NDArray(_np.asarray(v))
             for k, v in arg_params.items() if k in self._weight_names}, {},
            allow_extra_params=True)
        self._exe._commit_args()

        # accounting (instance state; registry series are process-wide)
        self._warm = set()
        self._n_steps = 0
        self._n_prefills = 0
        self._n_prefill_chunks = 0
        self._n_step_dispatches = 0
        self._occ_sum = 0
        self._cache_occ_sum = 0.0
        self._steady_retraces = 0
        self._n_tokens = 0
        # speculative accounting: slot-iterations vs slot-tokens give
        # tokens_per_launch (exactly 1.0 without speculation); proposed
        # vs accepted give the draft acceptance rate
        self._n_slot_iters = 0
        self._n_slot_tokens = 0
        self._n_spec_proposed = 0
        self._n_spec_accepted = 0
        # sliding acceptance window: (proposed, accepted) per slot-span,
        # feeding the decode_accept_rate_window sentinel gauge — the
        # cumulative ACCEPT_RATE can never recover after a bad stretch
        import os as _os
        self._spec_window = _collections.deque(
            maxlen=max(16, int(_os.environ.get(
                "MXNET_DECODE_ACCEPT_WINDOW", "256") or 256)))
        self._n_completed = 0
        self._n_failed = 0
        self._n_expired = 0
        self._n_preemptions = 0
        self._n_admitted = 0
        self._n_cancelled = 0
        # last-4096 window only: stats() p99 never reads further back,
        # and a long-lived server must not accumulate one float/request
        self._ttfts = _collections.deque(maxlen=4096)
        # steps-to-first-token (submit -> first emit, in mixed-step
        # iterations): the CPU-container TTFT witness — wall-clock there
        # is bandwidth noise, dispatch counts are exact
        self._ttft_steps = _collections.deque(maxlen=4096)
        self._rid = 0
        self._model_version = None

        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._mid_admission = 0
        self._step_lock = threading.Lock()   # excludes step vs reload
        self._closing = False
        self._abort = False
        self._thread = None
        # hang watchdog over decode iterations (MXNET_WATCHDOG_FACTOR;
        # 0 = off, the default — docs/OBSERVABILITY.md)
        self._watchdog = None
        import os as _os
        if float(_os.environ.get("MXNET_WATCHDOG_FACTOR", "0") or 0) > 0:
            from ..telemetry import Watchdog
            self._watchdog = Watchdog("decode")
        if warmup:
            self.warmup()
        if start:
            self.start()

    # ------------------------------------------------------------------
    def _check_params(self, arg_params):
        missing = [n for n in self._weight_names if n not in arg_params]
        if missing:
            raise MXNetError("decode: params missing for %s"
                             % sorted(missing))
        bad = []
        for name in self._weight_names:
            v = arg_params[name]
            shape = getattr(v, "shape", None) or _np.shape(v)
            if tuple(shape) != self._exe.arg_dict[name].shape:
                bad.append(name)
        if bad:
            raise MXNetError("decode: param shapes do not match the bound "
                             "model for %s (cache layout is preserved only "
                             "across same-architecture reloads)"
                             % sorted(bad))

    def _idle_feeds(self):
        """All-slots-inactive, empty-chunk input set for the mixed step
        (warmup and tests): positions -1 mask every decode row, and
        ``chunk_len == 0`` makes the chunk stream a no-op (its zero-row
        writes re-emit existing cache bytes, so no allocator state is
        touched)."""
        K = self._chunk_tokens
        M = self._table_width
        feeds = dict(
            chunk_data=_np.zeros((1, K), _np.float32),
            chunk_positions=_np.zeros((1, K), _np.float32),
            chunk_start=_np.zeros((1,), _np.float32),
            chunk_len=_np.zeros((1,), _np.float32),
            chunk_table=_np.zeros((1, M), _np.float32))
        if self._spec_k > 0:
            # span step: span_len == 0 masks a row (chunk-attention
            # zero-length no-op), positions pad at 0 harmlessly
            feeds.update(
                data=_np.zeros((self.capacity, self._span), _np.float32),
                positions=_np.zeros((self.capacity, self._span),
                                    _np.float32),
                span_start=_np.zeros((self.capacity,), _np.float32),
                span_len=_np.zeros((self.capacity,), _np.float32),
                block_table=_np.zeros((self.capacity, M), _np.float32))
        else:
            feeds.update(
                data=_np.zeros((self.capacity, 1), _np.float32),
                positions=_np.full((self.capacity, 1), -1.0, _np.float32),
                block_table=_np.zeros((self.capacity, M), _np.float32))
        return feeds

    # ------------------------------------------------------------------
    def start(self):
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, name="mx-decode-engine", daemon=True)
            self._thread.start()

    def warmup(self):
        """Compile the ONE mixed step up front (vs the retired pow2
        ladder's one compile per bucket): a single all-slots-inactive,
        empty-chunk dispatch.  Runs inside an AOT-warming phase so the
        step program is flagged ``warmed`` in telemetry.programs() and
        disk-loads from the persistent compile cache on a restart
        (docs/AOT.md)."""
        from ..telemetry import programs as _programs
        with self._step_lock, _programs.warming():
            outs = self._exe.forward(is_train=False, **self._idle_feeds())
            # block until compiled+run; warmup exists to absorb this
            # cost before serving
            outs[1].asnumpy()  # analyze: ok(hostsync) warmup deliberately blocks until the compile+first run completes
            # donated caches: the dummy dispatch consumed the cache
            # buffers — re-point them at the outputs like any step.
            # _warm is shared with the engine thread's _dispatch
            # bookkeeping — every write holds _step_lock
            self._commit_caches(outs, base=4)
            self._warm.add("spec" if self._spec_k > 0 else "mixed")

    def aot_warm(self, manifest=None):
        """mx.aot.warm hook: the engine's step signature is fixed by its
        construction knobs, so warming is the same single dispatch
        whatever the manifest says; already-warm engines no-op.
        Returns the number of programs dispatched."""
        with self._step_lock:
            if self._warm:
                return 0
        self.warmup()
        return 1

    # ------------------------------------------------------------------
    # client API
    # ------------------------------------------------------------------
    def submit(self, tokens, max_new_tokens=None, eos_id="default",
               timeout_ms=None, temperature=0.0, seed=None, sampler=None,
               collect_logits=False, speculative=True):
        """Queue one generation; returns a :class:`StreamHandle`
        (iterate it for streamed tokens, or ``.result()`` for the full
        output).  Raises ``QueueFullError`` on backpressure and
        ``MXNetError`` for an inadmissible prompt.  ``speculative=False``
        opts this request out of draft-verify spans on a spec-enabled
        engine (it decodes one verified token per iteration)."""
        tokens = [int(t) for t in tokens]
        if not tokens:
            raise MXNetError("decode: empty prompt")
        if max_new_tokens is not None and int(max_new_tokens) < 1:
            raise MXNetError("decode: max_new_tokens must be >= 1 "
                             "(got %s)" % (max_new_tokens,))
        # chunked prefill retired the max_prefill_len submit rejection:
        # ANY prompt that fits the context (with one slot to generate)
        # and whose full footprint fits the cache is admissible
        if len(tokens) >= self._max_context:
            raise MXNetError("decode: prompt of %d tokens leaves no "
                             "room to generate within seq_len=%d"
                             % (len(tokens), self._max_context))
        if self.cache.blocks_for(len(tokens)) > self.cache.num_blocks:
            raise MXNetError("decode: prompt needs %d cache blocks, the "
                             "cache only has %d"
                             % (self.cache.blocks_for(len(tokens)),
                                self.cache.num_blocks))
        deadline = (time.monotonic() + timeout_ms / 1e3
                    if timeout_ms is not None else None)
        with self._cv:
            if self._closing:
                raise ServerClosedError("decode engine is stopped")
            self._rid += 1
            seq = Sequence(
                self._rid, tokens,
                max_new_tokens if max_new_tokens is not None
                else self._default_max_new,
                eos_id=self._eos if eos_id == "default" else eos_id,
                deadline=deadline, temperature=temperature, seed=seed,
                sampler=sampler, collect_logits=collect_logits,
                speculative=speculative)
            seq.submit_step = self._n_steps   # steps-to-first-token base
            self._sched.enqueue(seq)          # may raise QueueFullError
            if _tracing.enabled():
                # submit -> finish span, parented under the submitting
                # thread's context (the /generate handler's http span —
                # W3C traceparent already joined upstream callers there)
                seq.trace_span = _tracing.start_span(
                    "decode.request", rid=seq.rid,
                    prompt_len=len(tokens),
                    max_new_tokens=seq.max_new_tokens)
                seq.queue_span = _tracing.start_span(
                    "decode.queued", parent=seq.trace_span.context)
            self._n_admitted += 1
            ADMITTED.inc()
            QUEUE_DEPTH.set(len(self._sched.waiting))
            self._cv.notify_all()
        return seq.handle

    def generate(self, tokens, timeout=None, **kwargs):
        """Synchronous convenience: submit + wait; returns the
        generated token list."""
        return self.submit(tokens, **kwargs).result(timeout)

    # ------------------------------------------------------------------
    # engine thread
    # ------------------------------------------------------------------
    def _loop(self):
        while True:
            with self._cv:
                while (not self._closing
                       and not self._sched.waiting
                       and not self._sched.has_active()):
                    self._cv.wait(0.1)
                abort = self._abort
                drained = (self._closing and not self._sched.waiting
                           and not self._sched.has_active())
            # _fail_everything re-acquires _cv (a plain Lock), so it
            # must run OUTSIDE the monitor or abort deadlocks
            if abort:
                self._fail_everything(
                    ServerClosedError("decode engine stopped"))
                return
            if drained:
                return
            try:
                with _tracing.span("decode.tick"):
                    worked = self._tick()
            except Exception as exc:   # noqa: BLE001 — engine must survive
                self._fail_everything(exc)
                continue
            if not worked:
                time.sleep(0.002)      # blocked on cache; don't spin hot

    def _fail_everything(self, exc):
        with self._cv:
            seqs = list(self._sched.waiting)
            self._sched.waiting.clear()
        seqs += [s for _, s in self._sched.active()]
        for seq in seqs:
            self._finish(seq, error=exc)

    def _tick(self):
        """One scheduler iteration; returns False when nothing ran."""
        with self._step_lock:
            flush, self._prefix_flush = self._prefix_flush, False
        if flush:
            # deferred from swap_params: the trie's cached rows were
            # computed under the OLD weights.  Flushing here (engine
            # thread, before this tick's admissions) keeps the cache
            # single-owner; the transient is the same mixed-version
            # window hot reload already accepts for mid-prefill
            # sequences (swap_params docstring).
            self.cache.flush_prefixes()
        now = time.monotonic()
        with self._cv:
            expired = self._sched.take_expired_waiting(now)
            cancelled = [s for s in self._sched.waiting
                         if s.handle.cancelled()]
            for s in cancelled:
                self._sched.waiting.remove(s)
            QUEUE_DEPTH.set(len(self._sched.waiting))
        for seq in expired:
            self._finish(seq, error=DeadlineExceededError(
                "request %d expired before a decode slot freed" % seq.rid))
        for seq in cancelled:
            self._finish(seq, reason="cancelled")
        for _, seq in self._sched.active():
            if seq.handle.cancelled():
                self._finish(seq, reason="cancelled")
            elif seq.expired(now):
                self._finish(seq, error=DeadlineExceededError(
                    "request %d deadline expired mid-generation" % seq.rid))
        progressed = False
        batch_open = not self._sched.has_active()
        while True:
            with self._cv:
                if not self._sched.may_admit(batch_open):
                    break
                seq = self._sched.waiting[0]
                # admission gates on the FIRST chunk's footprint only —
                # chunked prefill grows the table incrementally, and
                # later chunks may preempt (youngest first) for blocks
                need = self.cache.blocks_for(
                    min(len(seq.tokens), self._chunk_tokens))
                if need > self.cache.free_count:
                    break             # FIFO: wait for blocks, no bypass
                self._sched.waiting.popleft()
                # visible to drain(): the sequence is in neither waiting
                # nor slots until place()
                self._mid_admission += 1
                QUEUE_DEPTH.set(len(self._sched.waiting))
            slot = self._sched.free_slot()
            try:
                with _tracing.span("decode.admit"):
                    self._admit(seq, slot)
                progressed = True
            except Exception as exc:   # noqa: BLE001 — the sequence is
                # already off the wait queue and may not be placed yet,
                # so _fail_everything would never see it: ANY failure
                # here must settle its handle, not just MXNetError
                self._finish(seq, error=exc)
            finally:
                with self._cv:
                    self._mid_admission -= 1
        # grow every DECODING sequence's block table BEFORE the step —
        # the step writes cache position seq.pos, and a missing table
        # entry would default to block 0 and corrupt whoever owns it.
        # Growth may preempt (youngest first), so re-snapshot after.
        for _, seq in self._sched.active():
            if seq.slot is None:      # preempted by an earlier growth
                continue
            if seq.n_prefilled < seq.prefill_target:
                continue              # prefilling: grown with its chunk
            try:
                self._ensure_blocks(seq, seq.pos // self.cache.block_size)
            except CacheOOMError as exc:
                self._finish(seq, error=exc)
        # pick THIS iteration's prefill chunk (oldest prefilling
        # sequence) and make sure the chunk's cache blocks exist
        chunk_seq = self._sched.pick_prefilling()
        chunk_len = 0
        if chunk_seq is not None:
            chunk_len = min(self._chunk_tokens,
                            chunk_seq.prefill_target
                            - chunk_seq.n_prefilled)
            last_row = chunk_seq.n_prefilled + chunk_len - 1
            try:
                self._ensure_blocks(chunk_seq,
                                    last_row // self.cache.block_size)
            except CacheOOMError as exc:
                self._finish(chunk_seq, error=exc)
                chunk_seq, chunk_len = None, 0
        active = self._sched.active()
        ACTIVE_SEQS.set(len(active))
        if active:
            if self._spec_k > 0:
                self._step_spec(active, chunk_seq, chunk_len)
            else:
                self._step(active, chunk_seq, chunk_len)
            progressed = True
        return progressed

    # ------------------------------------------------------------------
    def _ensure_blocks(self, seq, block_idx):
        """Make sure table entry ``block_idx`` exists, preempting the
        youngest other sequence on cache pressure."""
        while block_idx >= len(seq.blocks):
            try:
                seq.blocks += self.cache.alloc(1)
            except CacheOOMError:
                victim = self._sched.pick_victim(exclude=(seq,))
                if victim is None:
                    raise
                self._preempt(victim)

    def _preempt(self, victim):
        with self._cv:
            self._sched.preempt(victim)
            QUEUE_DEPTH.set(len(self._sched.waiting))
        if victim.prefill_span is not None:   # preempted mid-prefill
            victim.prefill_span.end(preempted=True)
            victim.prefill_span = None
        self._n_preemptions += 1
        PREEMPTIONS.inc()

    def _commit_caches(self, outs, base):
        for j, nd in enumerate(self._cache_arrs):
            nd._set_data(outs[base + j]._data)

    def _dispatch(self, exe, warm_key, **feeds):
        """Forward with retrace/dispatch accounting: the first launch of
        each program is the expected compile; anything after bumps the
        steady-state witness ``decode_retraces``.  Both counts are read
        from the executor's PER-THREAD tallies (jax traces and launches
        on the dispatching thread — this one), so another thread
        dispatching or compiling concurrently (a serving replica under
        mixed /predict traffic) can never inflate the decode
        witnesses."""
        from ..executor import _DISPATCH_TALLY, _SITE
        r0 = _SITE._tally.count
        d0 = _DISPATCH_TALLY.count
        outs = exe.forward(is_train=False, **feeds)
        dd = _DISPATCH_TALLY.count - d0
        rd = _SITE._tally.count - r0
        if warm_key in self._warm:
            if rd:
                self._steady_retraces += rd
                RETRACES.inc(rd)
        else:
            self._warm.add(warm_key)
        return outs, dd

    def _admit(self, seq, slot):
        """Place a waiting sequence into a slot for chunked prefill.

        No dispatch happens here — the mixed step carries the prompt
        into the cache one chunk per iteration, so admission is just
        bookkeeping: open the prefill span, arm the chunk cursor, and
        hand the sequence to the scheduler."""
        P = len(seq.tokens)
        if seq.queue_span is not None:
            seq.queue_span.end()
            seq.queue_span = None
        if seq.trace_span is not None:
            seq.prefill_span = _tracing.start_span(
                "decode.prefill",
                parent=getattr(seq.trace_span, "context", None),
                chunk_tokens=self._chunk_tokens, prompt_len=P,
                preemptions=seq.preemptions)
        seq.prefill_target = P
        seq.n_prefilled = 0
        seq.pos = 0
        # prefix-cache hit: adopt the trie's already-prefilled blocks
        # (COW — acquire_prefix increfs them for this sequence) and
        # start chunked prefill at the first unshared row.  At most
        # (P-1)//block_size blocks can match, so at least one prompt
        # token always prefills and the chunk head still emits the
        # sequence's first token.
        if self._prefix_cache and not seq.blocks:
            shared, rows = self.cache.acquire_prefix(seq.tokens[:P])
            if shared:
                seq.blocks = list(shared)
                seq.n_prefilled = rows
        self._n_prefills += 1
        PREFILLS.inc()
        with self._cv:
            self._sched.place(seq, slot)

    def _step(self, active, chunk_seq=None, chunk_len=0):
        t0 = time.perf_counter()
        if self._watchdog is not None:
            self._watchdog.begin()
        # per-sequence per-iteration spans: each live stream's trace
        # gets its own decode.iteration child (duration = this compiled
        # launch + readback), so one request renders submit -> prefill
        # -> N iterations -> done as a single connected tree
        it_spans = None
        if _tracing.enabled():
            it_spans = [
                _tracing.start_span(
                    "decode.iteration",
                    parent=getattr(s.trace_span, "context", None),
                    step=self._n_steps, slot=slot, pos=s.pos)
                for slot, s in active if s.trace_span is not None]
        if chunk_seq is not None and chunk_seq.slot is None:
            chunk_seq, chunk_len = None, 0   # preempted after selection
        # decode rows feed only FULLY-prefilled sequences; a sequence
        # mid-prefill rides the step at pos=-1 (inactive row) until its
        # last chunk lands, when the chunk head emits its first token
        decoding = [(slot, seq) for slot, seq in active
                    if seq.n_prefilled >= seq.prefill_target]
        data = _np.zeros((self.capacity, 1), _np.float32)
        pos = _np.full((self.capacity, 1), -1.0, _np.float32)
        table = _np.zeros((self.capacity, self._table_width), _np.float32)
        for slot, seq in decoding:
            data[slot, 0] = seq.last_token
            pos[slot, 0] = seq.pos
            table[slot, :len(seq.blocks)] = seq.blocks
        K = self._chunk_tokens
        cdata = _np.zeros((1, K), _np.float32)
        cpos = _np.zeros((1, K), _np.float32)
        cstart = _np.zeros((1,), _np.float32)
        clen = _np.zeros((1,), _np.float32)
        ctable = _np.zeros((1, self._table_width), _np.float32)
        if chunk_seq is not None:
            s0 = chunk_seq.n_prefilled
            cdata[0, :chunk_len] = chunk_seq.tokens[s0:s0 + chunk_len]
            cpos[0, :chunk_len] = _np.arange(s0, s0 + chunk_len)
            cstart[0] = s0
            clen[0] = chunk_len
            ctable[0, :len(chunk_seq.blocks)] = chunk_seq.blocks
        with _tracing.span("decode.step"), self._step_lock:
            outs, dd = self._dispatch(
                self._exe, "mixed", data=data, positions=pos,
                block_table=table, chunk_data=cdata,
                chunk_positions=cpos, chunk_start=cstart,
                chunk_len=clen, chunk_table=ctable)
            self._commit_caches(outs, base=4)
        self._n_steps += 1
        self._n_step_dispatches += dd
        self._occ_sum += len(active)
        self._cache_occ_sum += self.cache.occupancy
        STEPS.inc()
        with _tracing.span("decode.emit"):
            if chunk_seq is not None:
                self._advance_chunk(chunk_seq, chunk_len, outs)
            # ONE host copy of the (capacity, vocab) logits per step, shared
            # by every sampling/temperature/collect_logits sequence (rows
            # are per-slot, so a misbehaving user sampler can only touch its
            # own row)
            logits_host = None
            if any(self._needs_logits(s) for _, s in decoding):
                # analyze: ok(hostsync) the step's ONE logits readback, shared by every sampling/temperature slot (documented in the module doc)
                logits_host = outs[0].asnumpy()
            # likewise ONE readback of the greedy-token output for the
            # whole step, not one per active slot
            next_host = None
            if decoding:
                # analyze: ok(hostsync) the greedy-token readback IS the streamed response — the documented one sync per decode iteration
                next_host = outs[1].asnumpy()
            for slot, seq in decoding:
                seq.pos += 1
                self._n_slot_iters += 1
                try:
                    tok = self._pick_token(seq, outs, slot, logits_host,
                                           next_host)
                except Exception as exc:   # noqa: BLE001 — user sampler;
                    self._finish(seq, error=exc)   # contain to this stream
                    continue
                self._n_slot_tokens += 1
                self._emit(seq, tok)
                self._maybe_finish(seq, tok)
        if it_spans:
            for sp in it_spans:
                sp.end()
        if self._watchdog is not None:
            self._watchdog.end()
        STEP_MS.observe((time.perf_counter() - t0) * 1e3)

    def _advance_chunk(self, chunk_seq, chunk_len, outs):
        """Account this iteration's prefill chunk; on the LAST chunk,
        publish sharable full blocks into the prefix trie and emit the
        sequence's first token from the chunk head (outputs base 2)."""
        chunk_seq.n_prefilled += chunk_len
        self._n_prefill_chunks += 1
        PREFILL_CHUNKS.inc()
        if chunk_seq.n_prefilled < chunk_seq.prefill_target:
            return
        # last chunk landed: the chunk head's greedy token (or
        # logits row) is this sequence's FIRST token
        chunk_seq.pos = chunk_seq.prefill_target
        if chunk_seq.prefill_span is not None:
            chunk_seq.prefill_span.end()
            chunk_seq.prefill_span = None
        if self._prefix_cache:
            # publish the finished prefill's FULL blocks for COW reuse
            # (the trie takes its own reference on each; the partial
            # tail block is never shared, so generation writes stay
            # exclusive by construction)
            self.cache.register_prefix(
                chunk_seq.tokens[:chunk_seq.prefill_target],
                chunk_seq.prefill_target, chunk_seq.blocks)
        # per-sequence containment: a bad user sampler must
        # fail ONLY its own stream, never the engine
        try:
            tok = self._pick_token(chunk_seq, outs, 0, base=2)
        except Exception as exc:   # noqa: BLE001
            self._finish(chunk_seq, error=exc)
        else:
            self._emit(chunk_seq, tok)
            self._maybe_finish(chunk_seq, tok)

    def _fork_block(self, seq, idx):
        """COW safety valve: give ``seq`` a private copy of table entry
        ``idx`` when that block is shared.  Device-side row copy (one
        eager op per cache array, never on the steady-state step path:
        full-blocks-only sharing means the engine's writes always land
        past every shared row, so this triggers only through direct
        cache manipulation)."""
        old = seq.blocks[idx]
        new = self.cache.fork_for_write(old)
        if new is None:
            return
        for nd in self._cache_arrs:
            nd._set_data(nd._data.at[new].set(nd._data[old]))
        seq.blocks[idx] = new

    def _step_spec(self, active, chunk_seq=None, chunk_len=0):
        """One draft-verify iteration (docs/DECODE.md): propose up to
        ``spec_k`` tokens per decoding slot, verify every span in ONE
        compiled donated launch of the span step, and commit the
        longest draft prefix that matches the target model's own greedy
        tokens.  Greedy acceptance keeps the stream token-identical to
        non-speculative decoding by construction — draft token j
        commits only when it equals greedy output j-1, so every emitted
        token is the argmax the one-token engine would have produced.
        A rejected tail rolls back by CURSOR arithmetic alone: the next
        span's scatter overwrites rows from the new ``pos`` before its
        gather, and surviving stale rows sit at positions above every
        query's causal mask (rollback math in docs/DECODE.md)."""
        t0 = time.perf_counter()
        if self._watchdog is not None:
            self._watchdog.begin()
        it_spans = None
        if _tracing.enabled():
            it_spans = [
                _tracing.start_span(
                    "decode.iteration",
                    parent=getattr(s.trace_span, "context", None),
                    step=self._n_steps, slot=slot, pos=s.pos)
                for slot, s in active if s.trace_span is not None]
        if chunk_seq is not None and chunk_seq.slot is None:
            chunk_seq, chunk_len = None, 0   # preempted after selection
        decoding = [(slot, seq) for slot, seq in active
                    if seq.n_prefilled >= seq.prefill_target]
        S = self._span
        bs = self.cache.block_size
        vocab = int(self._cfg.get("num_classes", 0))
        data = _np.zeros((self.capacity, S), _np.float32)
        pos = _np.zeros((self.capacity, S), _np.float32)
        sstart = _np.zeros((self.capacity,), _np.float32)
        slen = _np.zeros((self.capacity,), _np.float32)
        table = _np.zeros((self.capacity, self._table_width), _np.float32)
        drafts = {}
        for slot, seq in decoding:
            draft = []
            # budget: the span's rows must fit the context, and tokens
            # past this stream's length stop are wasted verification
            budget = min(self._spec_k,
                         self._max_context - seq.pos - 1,
                         seq.max_new_tokens - seq.n_generated - 1)
            if (budget > 0 and seq.speculative
                    and not self._needs_logits(seq)):
                try:
                    draft = [int(t) for t in
                             self._drafter.propose(seq.tokens, budget)]
                except Exception:   # noqa: BLE001 — a drafter bug costs
                    draft = []      # speedup, never a stream
                draft = [t for t in draft[:budget] if 0 <= t < vocab]
            # opportunistic span-block growth: row seq.pos is already
            # guaranteed by _tick's _ensure_blocks; extra draft rows
            # TRIM on pressure instead of preempting (the `active`
            # snapshot must stay placed through this step)
            L = 1 + len(draft)
            while (seq.pos + L - 1) // bs >= len(seq.blocks):
                try:
                    seq.blocks += self.cache.alloc(1)
                except CacheOOMError:
                    L = min(1 + len(draft),
                            max(1, len(seq.blocks) * bs - seq.pos))
                    draft = draft[:L - 1]
                    break
            # COW guard: fork any shared block the span would write
            for bi in range(seq.pos // bs, (seq.pos + L - 1) // bs + 1):
                if self.cache.ref(seq.blocks[bi]) > 1:
                    self._fork_block(seq, bi)
            drafts[slot] = draft
            data[slot, :L] = [seq.last_token] + draft
            pos[slot, :L] = _np.arange(seq.pos, seq.pos + L)
            sstart[slot] = seq.pos
            slen[slot] = L
            table[slot, :len(seq.blocks)] = seq.blocks
            if draft:
                self._n_spec_proposed += len(draft)
                SPEC_PROPOSED.inc(len(draft))
        K = self._chunk_tokens
        cdata = _np.zeros((1, K), _np.float32)
        cpos = _np.zeros((1, K), _np.float32)
        cstart = _np.zeros((1,), _np.float32)
        clen = _np.zeros((1,), _np.float32)
        ctable = _np.zeros((1, self._table_width), _np.float32)
        if chunk_seq is not None:
            s0 = chunk_seq.n_prefilled
            cdata[0, :chunk_len] = chunk_seq.tokens[s0:s0 + chunk_len]
            cpos[0, :chunk_len] = _np.arange(s0, s0 + chunk_len)
            cstart[0] = s0
            clen[0] = chunk_len
            ctable[0, :len(chunk_seq.blocks)] = chunk_seq.blocks
        with _tracing.span("decode.step"), self._step_lock:
            outs, dd = self._dispatch(
                self._exe, "spec", data=data, positions=pos,
                span_start=sstart, span_len=slen, block_table=table,
                chunk_data=cdata, chunk_positions=cpos,
                chunk_start=cstart, chunk_len=clen, chunk_table=ctable)
            self._commit_caches(outs, base=4)
        self._n_steps += 1
        self._n_step_dispatches += dd
        self._occ_sum += len(active)
        self._cache_occ_sum += self.cache.occupancy
        STEPS.inc()
        with _tracing.span("decode.emit"):
            if chunk_seq is not None:
                self._advance_chunk(chunk_seq, chunk_len, outs)
            # same readback discipline as the mixed step: ONE logits copy
            # shared by every sampling slot, ONE greedy-token copy for the
            # whole step — span rows are (slot * S + j)
            logits_host = None
            if any(self._needs_logits(s) for _, s in decoding):
                # analyze: ok(hostsync) the step's ONE logits readback, shared by every sampling/temperature slot (documented in the module doc)
                logits_host = outs[0].asnumpy()
            next_host = None
            if decoding:
                # analyze: ok(hostsync) the greedy-token readback IS the streamed response — the documented one sync per decode iteration
                next_host = outs[1].asnumpy()
            for slot, seq in decoding:
                draft = drafts.get(slot, [])
                L = 1 + len(draft)
                self._n_slot_iters += 1
                accepted = 0
                for j in range(L):
                    # row j is the target's verdict GIVEN span tokens
                    # 0..j; it is reached only while every earlier draft
                    # token matched the target's greedy choice
                    seq.pos += 1
                    try:
                        tok = self._pick_token(seq, outs, slot * S + j,
                                               logits_host, next_host)
                    except Exception as exc:   # noqa: BLE001 — user
                        self._finish(seq, error=exc)   # sampler: contain
                        break
                    self._n_slot_tokens += 1
                    if j > 0:
                        accepted += 1
                    self._emit(seq, tok)
                    self._maybe_finish(seq, tok)
                    if seq.slot is None:
                        break                  # finished mid-span
                    if j < L - 1 and draft[j] != tok:
                        break                  # tail rejected: cursor stays
                if accepted:
                    self._n_spec_accepted += accepted
                    SPEC_ACCEPTED.inc(accepted)
                if draft:
                    self._spec_window.append((len(draft), accepted))
        if self._n_spec_proposed:
            ACCEPT_RATE.set(self._n_spec_accepted
                            / float(self._n_spec_proposed))
            wp = sum(p for p, _ in self._spec_window)
            if wp:
                ACCEPT_WINDOW.set(
                    sum(a for _, a in self._spec_window) / float(wp))
        if self._n_slot_iters:
            TOKENS_PER_LAUNCH.set(self._n_slot_tokens
                                  / float(self._n_slot_iters))
        if it_spans:
            for sp in it_spans:
                sp.end()
        if self._watchdog is not None:
            self._watchdog.end()
        STEP_MS.observe((time.perf_counter() - t0) * 1e3)

    # ------------------------------------------------------------------
    @staticmethod
    def _needs_logits(seq):
        return (seq.sampler is not None or seq.temperature > 0
                or seq.handle.logits is not None)

    def _pick_token(self, seq, outs, row, logits_host=None, next_host=None,
                    base=0):
        """Greedy reads the on-device argmax output; samplers and
        temperature read the logits row.  Host-side on purpose: the
        readback is the stream, and numpy sampling keeps the device
        program fixed-shape.  ``base`` selects the output pair — 0 for
        the shared decode head, 2 for the chunk head that yields a
        prompt's first token on its final prefill chunk."""
        if self._needs_logits(seq):
            if logits_host is None:
                # analyze: ok(hostsync) chunk-completion readback of the first token's logits (once per admission, not per step)
                logits_host = outs[base].asnumpy()
            logits = logits_host[row]
            if seq.handle.logits is not None:
                # analyze: ok(hostsync) copies an already-host logits row into the user-visible handle
                seq.handle.logits.append(_np.array(logits, copy=True))
            if seq.sampler is not None:
                return int(seq.sampler(logits))
            if seq.temperature > 0:
                z = logits / max(seq.temperature, 1e-6)
                z = z - z.max()
                p = _np.exp(z)
                p /= p.sum()
                return int(seq.rng().choice(len(p), p=p))
            return int(logits.argmax())
        if next_host is None:
            # analyze: ok(hostsync) chunk-completion first-token readback; that token is the stream's first byte
            next_host = outs[base + 1].asnumpy()
        return int(next_host[row])

    def _emit(self, seq, tok):
        now = time.monotonic()
        seq.tokens.append(tok)
        seq.last_token = tok
        if seq.t_first is None:
            seq.t_first = now
            ttft = (now - seq.t_submit) * 1e3
            seq.handle.ttft_ms = ttft
            TTFT_MS.observe(ttft)
            # under _cv: stats() iterates these deques from other threads
            with self._cv:
                self._ttfts.append(ttft)
                if seq.submit_step is not None:
                    steps = self._n_steps - seq.submit_step
                    self._ttft_steps.append(steps)
                    TTFT_STEPS.observe(steps)
        seq.handle._emit(tok)
        self._n_tokens += 1
        TOKENS.inc()

    def _maybe_finish(self, seq, tok):
        if seq.eos_id is not None and tok == seq.eos_id:
            self._finish(seq, reason="eos")
        elif seq.n_generated >= seq.max_new_tokens:
            self._finish(seq, reason="length")
        elif seq.pos >= self._max_context:
            self._finish(seq, reason="context")

    def _finish(self, seq, reason=None, error=None):
        with self._cv:
            self._sched.release(seq)
        if seq.queue_span is not None:       # finished while waiting
            seq.queue_span.end()
            seq.queue_span = None
        if seq.prefill_span is not None:     # finished mid-prefill
            seq.prefill_span.end()
            seq.prefill_span = None
        if seq.trace_span is not None:
            seq.trace_span.end(
                finish_reason=(reason if error is None else "error"),
                error=(type(error).__name__ if error is not None
                       else None),
                tokens=seq.n_generated, preemptions=seq.preemptions)
            seq.trace_span = None
        if error is None and reason == "cancelled":
            self._n_cancelled += 1
            CANCELLED.inc()
        elif error is None:
            self._n_completed += 1
            COMPLETED.inc()
        elif isinstance(error, DeadlineExceededError):
            self._n_expired += 1
            EXPIRED.inc()
        else:
            self._n_failed += 1
            FAILED.inc()
        seq.handle._finish(reason=reason, error=error)

    # ------------------------------------------------------------------
    # weights: hot reload
    # ------------------------------------------------------------------
    def check_params(self, arg_params):
        """Validate a candidate checkpoint against the bound model +
        cache layout (server reload calls this BEFORE touching any
        replica, so a bad checkpoint is a clean 409)."""
        self._check_params(arg_params)

    def swap_params(self, arg_params, aux_params=None, version=None):
        """Hot-swap weights under the step lock: in-flight sequences
        continue on the new weights at the next iteration, the KV cache
        (and therefore every stream) is preserved.  ``version`` (a tag
        or epoch) stamps ``stats()["model_version"]`` atomically with
        the swap.  Raises ``MXNetError`` — without touching anything —
        when shapes don't match."""
        import jax
        from ..ndarray.ndarray import NDArray
        self._check_params(arg_params)
        with self._step_lock:
            for name in self._weight_names:
                v = arg_params[name]
                if not isinstance(v, NDArray):
                    # analyze: ok(hostsync) hot-reload weight staging crosses the host by contract; not on the per-iteration path
                    v = NDArray(_np.asarray(v))
                dst = self._exe.arg_dict[name]
                data = v._data
                if data.dtype != dst._data.dtype:
                    data = data.astype(dst._data.dtype)
                # re-shard onto the destination's bind-time placement:
                # under a TP mesh (mx.fleet) params carry NamedShardings
                # that a plain single-device put would clobber.
                dst._set_data(jax.device_put(data, dst._data.sharding))
            if version is not None:
                self._model_version = version
        if self._prefix_cache:
            # the trie's cached rows were computed under the replaced
            # weights; the engine thread flushes at its next tick (the
            # cache stays single-owner).  An engine that never started
            # has no owner thread — flush inline.
            with self._step_lock:
                self._prefix_flush = self._thread is not None
            if self._thread is None:
                self.cache.flush_prefixes()
        RELOADS.inc()

    def reload(self, prefix, tag=None, epoch=None):
        """Load an mx.checkpoint (``tag``/newest) or legacy
        ``prefix-%04d.params`` (``epoch``) and hot-swap (docs/DECODE.md
        + docs/CHECKPOINT.md)."""
        from ..checkpoint import resolve_params
        arg_params, _aux, version = resolve_params(
            prefix, tag, epoch, what="decode reload")
        self.swap_params(arg_params, version=version)
        return version

    # ------------------------------------------------------------------
    def drain(self, timeout=None):
        """Wait until all submitted work has settled."""
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            with self._cv:
                idle = (not self._sched.waiting
                        and not self._sched.has_active()
                        and not self._mid_admission)
            if idle:
                return True
            if deadline is not None and time.monotonic() > deadline:
                return False
            time.sleep(0.005)

    def stop(self, drain=True, timeout=None):
        """Stop the engine; ``drain=True`` finishes queued work first,
        ``drain=False`` fails it with ``ServerClosedError``."""
        with self._cv:
            self._closing = True
            if not drain:
                self._abort = True
            self._cv.notify_all()
        if self._watchdog is not None:
            self._watchdog.disarm()
        if self._thread is not None:
            self._thread.join(timeout)
            # a timed-out join leaves the loop running: keep _thread so
            # start() can't spawn a SECOND loop over the same slots
            if not self._thread.is_alive():
                self._thread = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    # ------------------------------------------------------------------
    def stats(self):
        """Operational snapshot (glossary in docs/DECODE.md)."""
        with self._cv:
            depth = len(self._sched.waiting)
            active = sum(1 for s in self._sched.slots if s is not None)
            ttfts = sorted(self._ttfts)
            ttft_steps = sorted(self._ttft_steps)
        p99 = _percentile(ttfts, 0.99)
        steps_p99 = _percentile(ttft_steps, 0.99)
        return {
            "capacity": self.capacity,
            "queue_depth": depth,
            "active_sequences": active,
            "admitted": self._n_admitted,
            "completed": self._n_completed,
            "failed": self._n_failed,
            "expired": self._n_expired,
            "cancelled": self._n_cancelled,
            "tokens_generated": self._n_tokens,
            "steps": self._n_steps,
            "prefills": self._n_prefills,
            "preemptions": self._n_preemptions,
            "mean_slot_occupancy": (self._occ_sum / self._n_steps
                                    if self._n_steps else None),
            "mean_cache_occupancy": (self._cache_occ_sum / self._n_steps
                                     if self._n_steps else None),
            "steady_state_retraces": self._steady_retraces,
            "decode_step_dispatches": self._n_step_dispatches,
            "dispatches_per_step": (self._n_step_dispatches / self._n_steps
                                    if self._n_steps else None),
            "prefill_chunks": self._n_prefill_chunks,
            "prefill_chunks_per_iter": (self._n_prefill_chunks
                                        / self._n_steps
                                        if self._n_steps else None),
            "chunk_tokens": self._chunk_tokens,
            "ttft_p99_ms": p99,
            "ttft_steps_p99": steps_p99,
            "model_version": self._model_version,
            "attn_impl": self._attn_impl,
            "cache_donation": self._donate,
            "spec_k": self._spec_k,
            "spec_impl": self._spec_impl,
            "spec_proposed": self._n_spec_proposed,
            "spec_accepted": self._n_spec_accepted,
            "accept_rate": (self._n_spec_accepted
                            / self._n_spec_proposed
                            if self._n_spec_proposed else None),
            "accept_rate_window": (
                sum(a for _, a in self._spec_window)
                / float(sum(p for p, _ in self._spec_window))
                if sum(p for p, _ in self._spec_window) else None),
            "tokens_per_launch": (self._n_slot_tokens
                                  / self._n_slot_iters
                                  if self._n_slot_iters else None),
            "cache": {
                "num_blocks": self.cache.num_blocks,
                "block_size": self.cache.block_size,
                "blocks_used": self.cache.used_count,
                "blocks_free": self.cache.free_count,
                "occupancy": round(self.cache.occupancy, 4),
                "prefix_sharing": self._prefix_cache,
                "prefix_hit_blocks":
                    self.cache.prefix_stats["hit_blocks"],
                "prefix_trie_blocks":
                    self.cache.prefix_stats["trie_blocks"],
            },
        }
