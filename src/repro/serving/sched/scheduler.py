"""Continuous-batching scheduler over the step-level serving engine.

One ``ContinuousScheduler`` owns a fixed pool of decode slots backed by
a single static slot-batched KV cache.  Each tick interleaves three
phases — admission, chunked prefill, batched decode — so new requests
join a running batch without draining it:

  1. **Admission**: the oldest queued request claims a free slot (free
     list, LIFO recycling) and becomes the in-flight prefill.
  2. **Chunked prefill**: up to ``prefill_chunks_per_step`` bucketed
     chunks (see ``buckets.BucketSpec``) of the in-flight request run
     against a private B=1 cache.  When the last chunk completes, the
     first token is sampled from its logits, the cache row is grafted
     into the slot cache, and the slot joins the decode batch.
  3. **Decode**: one slot-indexed decode step over all slots (inactive
     rows compute garbage that per-row valid-length masking keeps
     unreadable); each active slot samples its next token, streams it,
     and is evicted on its stop token or token budget.

Every jitted program the loop touches has a traffic-independent shape
(slot count × chunk buckets).  The scheduler plans nothing: a GOMA
kernel in one of those programs resolves its tiling when the program is
traced (``core.tpu_mapping``, through the engine's plan store), and jit
caching keeps steady-state traffic free of solver invocations.

Outputs are token-identical to running each request alone through the
static ``Engine.generate`` oracle: chunk padding is causally masked,
slot rows are batch-independent, and the decode recurrence visits the
same (token, position) sequence — see tests/test_serving_sched.py.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from ...faults import inject
from ...obs.registry import get_registry
from ...obs.tracing import get_tracer
from ...obs.tracing import span as _span
from ...obs.tracing import trace_event
from ..engine import NOT_FINITE, Engine, gumbel_argmax
from .buckets import BucketSpec, Chunk
from .metrics import ServingMetrics
from .requests import Request, RequestResult, RequestState
from .slots import Slot, SlotManager

_REG = get_registry()

# Families whose cache is a pure per-layer KV tensor with batch on axis 1
# (slot grafting + slot-indexed writes assume that layout).  Recurrent
# families (rwkv/ssm/hybrid) carry cross-step state that chunked prefill
# cannot replay position-independently; encdec/vlm need frontend
# prefixes the chunk loop does not thread through.
SUPPORTED_FAMILIES = ("dense", "moe")


def ensure_supported_family(model_cfg) -> None:
    """Raise a clear ValueError at construction time when a model's
    family cannot be continuously batched, instead of failing deep in
    slot grafting.  The router consults this to fall back to the static
    ``Engine.generate`` path for unsupported families."""
    fam = model_cfg.family
    if fam not in SUPPORTED_FAMILIES:
        raise ValueError(
            f"continuous batching supports families "
            f"{SUPPORTED_FAMILIES}, not {fam!r} (recurrent state / "
            f"frontend prefixes are not slot-graftable)")
    if fam == "moe" and \
            getattr(model_cfg, "moe_dispatch", "dense") == "gathered":
        # gathered dispatch computes expert capacity over the whole
        # batch: garbage rows in free slots would compete with
        # active rows for capacity, breaking row independence (and
        # with it the token-identity-to-oracle guarantee)
        raise ValueError(
            "continuous batching requires row-independent compute; "
            "moe_dispatch='gathered' couples rows through expert "
            "capacity — use moe_dispatch='dense'")


@dataclasses.dataclass
class SchedConfig:
    slots: int = 8
    chunk_widths: tuple[int, ...] = (8, 32, 128)
    prefill_chunks_per_step: int = 1
    max_queue: int | None = None        # admission control; None = unbounded
    temperature: float = 0.0
    stop_token: int | None = None       # default; requests may override
    rng_seed: int = 0                   # per-request sampling keys
    # --- degradation knobs (DESIGN.md §Resilience) ---
    shed_on_full: bool = False          # queue full: return a terminal
    #                                     REJECTED result instead of
    #                                     raising (load shedding)
    default_deadline_s: float | None = None   # per-request deadline
    #                                     relative to arrival, applied
    #                                     when Request.deadline_s is None;
    #                                     requests still queued past it
    #                                     are EXPIRED at the next tick
    watchdog_tick_s: float | None = None      # wall-clock budget for one
    #                                     tick; slower ticks trip
    #                                     sched.watchdog_trips (detection
    #                                     only — the tick still completes)
    # --- speculative decoding (serving.router.spec) ---
    spec_width: int | None = None       # with a drafter installed, the
    #                                     decode phase becomes a batched
    #                                     verify step over windows of
    #                                     this width (1 committed token
    #                                     + spec_width - 1 draft tokens
    #                                     per row); accepted tokens are
    #                                     the target model's own greedy
    #                                     continuations, so streams stay
    #                                     byte-identical to width-1
    #                                     decoding.  Requires greedy
    #                                     sampling (temperature == 0).


@dataclasses.dataclass
class _Prefill:
    """The in-flight chunked prefill (at most one at a time)."""

    slot: Slot
    cache: dict                          # the persistent B=1 prefill
    #                                      cache, advanced chunk by chunk
    chunks: collections.deque            # of Chunk
    padded: np.ndarray                   # (1, padded_len) prompt buffer


class ContinuousScheduler:
    def __init__(self, engine: Engine, cfg: SchedConfig, *,
                 arch_id: str | None = None,
                 on_token: Callable[[Request, int], None] | None = None,
                 on_finish: Callable[[RequestResult], None] | None = None,
                 on_tick: Callable[["ContinuousScheduler"], None]
                 | None = None,
                 clock: Callable[[], float] | None = None,
                 prefix_cache=None, drafter=None):
        ensure_supported_family(engine.model.cfg)
        self.engine = engine
        self.cfg = cfg
        # optional KV prefix cache (serving.router.prefix): admission
        # grafts cached rows for a shared prompt prefix instead of
        # re-prefilling them
        self.prefix_cache = prefix_cache
        # optional speculative-decoding drafter (serving.router.spec)
        self.drafter = drafter
        if drafter is not None:
            if cfg.temperature > 0.0:
                raise ValueError(
                    "speculative decoding requires greedy sampling "
                    "(temperature == 0): acceptance compares drafts "
                    "against the target's greedy continuation")
            if cfg.spec_width is None or cfg.spec_width < 2:
                raise ValueError(
                    f"a drafter needs spec_width >= 2 (1 committed + "
                    f">= 1 draft token per window), got "
                    f"{cfg.spec_width!r}")
        self._lookahead = (cfg.spec_width - 1) if drafter is not None \
            else 0
        self.buckets = BucketSpec(cfg.chunk_widths)
        self.slots = SlotManager(cfg.slots)
        self.queue: collections.deque[Request] = collections.deque()
        self.metrics = ServingMetrics()
        self.results: list[RequestResult] = []
        self.on_token = on_token
        self.on_finish = on_finish
        self.on_tick = on_tick
        # per-request lifecycle spans (admit -> first token -> finish),
        # keyed by req_id; detached because they straddle many ticks
        self._req_spans: dict[int, object] = {}
        if clock is None:
            t0 = time.perf_counter()
            clock = lambda: time.perf_counter() - t0  # noqa: E731
        self.clock = clock
        self._base_key = jax.random.PRNGKey(cfg.rng_seed)
        self._prefill: _Prefill | None = None
        # one persistent B=1 prefill cache, reused across admissions:
        # stale content from earlier occupants is invisible (causal +
        # valid-length masking) and overwritten chunk by chunk — the
        # same invariant that lets slot rows go uncleared
        self._prefill_cache = engine.new_cache(1)
        self.rejected = 0               # admission-control rejections
        # device-side decode state: next input token + write position per
        # slot (kept as host arrays; one transfer per tick)
        self._cur = np.zeros((cfg.slots,), np.int32)
        self._pos = np.zeros((cfg.slots,), np.int32)
        self.slot_cache = engine.new_cache(cfg.slots)
        # a label only: nothing reads it (chipbench's harness passes it)
        self.arch_id = arch_id

    # ---------------------------------------------------------- admission
    def submit(self, req: Request) -> RequestResult | None:
        """Validate and enqueue.  Raises ValueError when the request can
        never fit the static cache (clear error instead of a silent
        overflow).  A full queue raises RuntimeError, unless
        ``shed_on_full`` is set — then the request is shed with an
        explicit terminal REJECTED result (returned, recorded, and
        streamed through ``on_finish`` like any other completion)."""
        self.engine.validate_capacity(req.prompt_len, req.max_new_tokens,
                                      lookahead=self._lookahead)
        padded = self.buckets.padded_len(req.prompt_len)
        if padded > self.engine.cfg.cache_len:
            raise ValueError(
                f"request {req.req_id}: bucket-padded prompt needs "
                f"{padded} cache positions but cache_len="
                f"{self.engine.cfg.cache_len}")
        if self.cfg.max_queue is not None and \
                len(self.queue) >= self.cfg.max_queue:
            self.rejected += 1
            if self.cfg.shed_on_full:
                _REG.inc("degraded.sched.shed")
                return self._finish_unstarted(
                    req, RequestState.REJECTED, self.clock())
            raise RuntimeError(
                f"admission queue full ({self.cfg.max_queue}); request "
                f"{req.req_id} rejected")
        self.queue.append(req)
        return None

    def _deadline_of(self, req: Request) -> float | None:
        if req.deadline_s is not None:
            return req.deadline_s
        if self.cfg.default_deadline_s is not None:
            return req.arrival_s + self.cfg.default_deadline_s
        return None

    def _expire_queue(self, now: float) -> None:
        """Drop queued requests whose deadline already passed — serving
        them would waste prefill on an answer nobody is waiting for.
        In-flight requests are never expired: once a slot is claimed the
        work is sunk and the token stream stays oracle-identical."""
        if not self.queue:
            return
        keep: collections.deque[Request] = collections.deque()
        for req in self.queue:
            dl = self._deadline_of(req)
            if dl is not None and now > dl:
                _REG.inc("degraded.sched.expired")
                self._finish_unstarted(req, RequestState.EXPIRED, now)
            else:
                keep.append(req)
        self.queue = keep

    def _finish_unstarted(self, req: Request, state: RequestState,
                          now: float) -> RequestResult:
        """Terminal result for a request shed before its first token."""
        res = RequestResult(
            req_id=req.req_id, tokens=[], finish_reason=state.value,
            prompt_len=req.prompt_len, arrival_s=req.arrival_s,
            first_token_s=float("nan"), finish_s=now)
        self.results.append(res)
        self.metrics.record_result(res)
        trace_event(f"sched.{state.value}", req_id=req.req_id)
        if self.on_finish is not None:
            self.on_finish(res)
        return res

    @property
    def busy(self) -> bool:
        return bool(self.queue) or self._prefill is not None or \
            self.slots.n_busy > 0

    def state_of(self, slot: Slot) -> RequestState:
        if slot.free:
            return RequestState.FINISHED
        if self._prefill is not None and self._prefill.slot is slot:
            return RequestState.PREFILLING
        return RequestState.ACTIVE

    # ------------------------------------------------------------ stepping
    def step(self) -> None:
        """One scheduler tick: admit -> prefill chunk(s) -> decode.

        Observability: the tick is one ``sched.tick`` span (a step on
        the profiler's clock) with children ``sched.admit``,
        ``sched.prefill_chunk``, ``sched.graft``, ``sched.decode_batch``
        (itself split into ``sched.decode.dispatch`` and
        ``sched.decode.read``, the tick's one blocking read of the
        decode's result) and ``sched.emit``, each with ``tick=``;
        admission opens a detached per-request ``sched.request`` span that
        ``_emit`` closes at finish.  Registry counters mirror the
        ``ServingMetrics`` tick accounting under ``sched.*``."""
        wall0 = time.perf_counter()
        tick = self.metrics.steps
        with _span("sched.tick", step_num=tick, tick=tick) as tick_sp:
            self._step_inner(tick_sp, tick)
        wall = time.perf_counter() - wall0
        if self.cfg.watchdog_tick_s is not None and \
                wall > self.cfg.watchdog_tick_s:
            # stuck-tick watchdog: detection only — the tick already ran
            # to completion, so state is consistent; the trip surfaces in
            # counters/traces for the operator instead of wedging silently
            _REG.inc("sched.watchdog_trips")
            trace_event("sched.watchdog", duration_s=wall,
                        budget_s=self.cfg.watchdog_tick_s)
        if self.on_tick is not None:
            self.on_tick(self)

    def _step_inner(self, tick_sp, tick: int) -> None:
        if not self.metrics.steps:
            self.metrics.started_s = self.clock()
        chunks_run = 0
        padded_tokens = 0
        _REG.inc("sched.ticks")
        hit = inject("sched.slow_tick")
        if hit is not None:             # chaos: stall this tick so the
            time.sleep(float(hit.payload.get("stall_s", 0.02)))  # watchdog
        #                                 has something real to catch

        # 0. deadline sweep over the queue (before admission, so a
        # request that expired while waiting never claims a slot)
        self._expire_queue(self.clock())

        # 1. admission: start prefilling the oldest queued request
        if self._prefill is None and self.queue and self.slots.n_free:
            with _span("sched.admit", tick=tick):
                self._admit()

        # 2. chunked prefill of the in-flight request
        budget = max(1, self.cfg.prefill_chunks_per_step)
        while self._prefill is not None and budget > 0:
            chunk: Chunk = self._prefill.chunks.popleft()
            toks = self._prefill.padded[:, chunk.start:chunk.start
                                        + chunk.width]
            with _span("sched.prefill_chunk", width=chunk.width,
                       start=chunk.start, real=chunk.n_real, tick=tick):
                logits, self._prefill.cache = self.engine.prefill_chunk(
                    self._prefill.cache, toks, chunk.start)
            chunks_run += 1
            padded_tokens += chunk.width - chunk.n_real
            _REG.inc("sched.prefill_chunks")
            _REG.inc("sched.padded_prefill_tokens",
                     chunk.width - chunk.n_real)
            budget -= 1
            if not self._prefill.chunks:
                with _span("sched.graft", tick=tick):
                    self._activate(self._prefill, logits, chunk)
                self._prefill = None

        # 3. slot-indexed decode over the whole pool
        active = [s for s in self.slots.busy()
                  if self._prefill is None or s is not self._prefill.slot]
        decoded = False
        if active and self.drafter is not None:
            decoded = True
            active = self._decode_spec(active)
        elif active:
            decoded = True
            with _span("sched.decode_batch", rows=len(active),
                       slots=len(self.slots), tick=tick):
                with _span("sched.decode.dispatch", tick=tick):
                    tokens = jnp.asarray(self._cur[:, None])
                    positions = jnp.asarray(self._pos)
                    logits, self.slot_cache = self.engine.decode_slots(
                        self.slot_cache, tokens, positions)
                    hit = inject("kernel.nan_row")
                    if hit is not None:     # chaos: poison one active
                        #                         row's logits in place
                        victim = active[hit.index % len(active)].idx
                        bad = float(hit.payload.get("value",
                                                    float("nan")))
                        logits = logits.at[victim, -1].set(bad)
                    picked = self._pick_rows(logits, active)
                with _span("sched.decode.read", tick=tick):
                    nxt = np.asarray(picked)
                _REG.inc("sched.decode.reads")
                active = self._evict_not_finite(nxt, active)
            now = self.clock()
            with _span("sched.emit", tick=tick):
                for slot in active:
                    tok = int(nxt[slot.idx])
                    self._pos[slot.idx] += 1
                    self._cur[slot.idx] = tok
                    slot.next_token = tok
                    self._emit(slot, tok, now)
            _REG.inc("sched.decode_steps")
            _REG.inc("sched.padded_decode_rows",
                     len(self.slots) - len(active))

        padded_rows = len(self.slots) - len(active) if decoded else 0
        if tick_sp:
            tick_sp.attrs.update(active=len(active), chunks=chunks_run,
                                 decoded=decoded)
        self.metrics.record_tick(
            active=len(active), slots=len(self.slots), decoded=decoded,
            chunks=chunks_run, padded_tokens=padded_tokens,
            padded_rows=padded_rows)
        self.metrics.finished_s = self.clock()

    def _admit(self) -> None:
        """Start prefilling the oldest queued request in a free slot:
        its padded prompt buffer, its chunk plan, and any cached prefix
        grafted into the prefill cache."""
        req = self.queue.popleft()
        slot = self.slots.acquire(req)
        if slot.stop_token is None:     # scheduler default, resolved
            slot.stop_token = self.cfg.stop_token   # on the slot —
        #                                 the Request is never mutated
        padded_len = self.buckets.padded_len(req.prompt_len)
        buf = np.zeros((1, padded_len), np.int32)
        buf[0, :req.prompt_len] = req.tokens
        chunks = self.buckets.plan_chunks(req.prompt_len)
        if self.prefix_cache is not None:
            # KV prefix reuse: a cached prefix of P tokens (always a
            # full-chunk boundary, always < prompt_len) is grafted
            # into the prefill cache and its chunks are skipped —
            # the remaining chunks read the grafted rows through
            # attention exactly as if they had just been prefilled
            # (KV at position i depends only on tokens <= i)
            hit = self.prefix_cache.lookup(req.tokens)
            if hit is not None:
                p, entry = hit
                self._prefill_cache = self.prefix_cache.graft(
                    self._prefill_cache, entry)
                chunks = [c for c in chunks
                          if c.start + c.width > p]
                _REG.inc("sched.prefix_tokens_reused", p)
        self._prefill = _Prefill(
            slot=slot, cache=self._prefill_cache,
            chunks=collections.deque(chunks),
            padded=buf)
        _REG.inc("sched.admitted")
        tr = get_tracer()
        if tr is not None:
            self._req_spans[req.req_id] = tr.start(
                "sched.request", detached=True, req_id=req.req_id,
                prompt_len=req.prompt_len,
                max_new_tokens=req.max_new_tokens)

    # ------------------------------------------------- speculative decode
    def _decode_spec(self, active: list[Slot]) -> list[Slot]:
        """One speculative decode round: a batched verify step over a
        (slots, spec_width) window — per active row the committed next
        token followed by spec_width - 1 drafted tokens — then per-row
        greedy acceptance.  The emitted tokens are the *target* model's
        own greedy continuations (greedy token j of the verify output is
        bit-identical to what width-1 decoding would produce after
        consuming window tokens 0..j); drafts only decide how many of
        them commit this round, so every stream stays byte-identical to
        width-1 decoding.  Rejected draft positions hold stale KV that
        per-row valid-length masking hides until the write frontier
        reclaims them — the same invariant that keeps recycled slot rows
        and bucket padding invisible.  Returns the surviving rows."""
        w = self.cfg.spec_width
        k = w - 1
        tokens = np.zeros((len(self.slots), w), np.int32)
        drafts: dict[int, list[int]] = {}
        for slot in active:
            ctx = list(slot.req.tokens) + slot.tokens
            d = [int(t) for t in self.drafter.propose(ctx, k)][:k]
            while len(d) < k:                 # short proposals padded —
                d.append(d[-1] if d else      # a wrong draft just stops
                         int(self._cur[slot.idx]))    # acceptance early
            drafts[slot.idx] = d
            tokens[slot.idx, 0] = self._cur[slot.idx]
            tokens[slot.idx, 1:] = d
        with _span("sched.verify_batch", rows=len(active), width=w,
                   slots=len(self.slots)):
            greedy, finite, self.slot_cache = self.engine.verify_step(
                self.slot_cache, tokens, self._pos)
            greedy = np.asarray(greedy)
            finite = np.array(finite)
            hit = inject("kernel.nan_row")
            if hit is not None:     # chaos: poison one active row — the
                finite[active[hit.index % len(active)].idx] = False
        now = self.clock()          # guard below must evict it
        for slot in [s for s in active if not finite[s.idx]]:
            self._evict_errored(slot, now)
        active = [s for s in active if finite[s.idx]]
        now = self.clock()
        for slot in active:
            idx = slot.idx
            m = 0
            while m < k and drafts[idx][m] == int(greedy[idx, m]):
                m += 1
            _REG.inc("sched.spec.rounds")
            _REG.inc("sched.spec.drafted", k)
            _REG.inc("sched.spec.accepted", m)
            for tok in greedy[idx, :m + 1]:
                self._pos[idx] += 1
                self._cur[idx] = int(tok)
                slot.next_token = int(tok)
                self._emit(slot, int(tok), now)
                if slot.free:
                    break           # stop token / budget hit mid-window
        _REG.inc("sched.decode_steps")
        _REG.inc("sched.padded_decode_rows",
                 len(self.slots) - len(active))
        return active

    # ------------------------------------------------------ fault isolation
    def _evict_not_finite(self, nxt: np.ndarray,
                          active: list[Slot]) -> list[Slot]:
        """Evict active slots whose logits row went NaN/Inf
        (``NOT_FINITE`` in ``nxt``, the picked tokens) — a poisoned row
        must never be emitted (Gumbel/argmax over NaN silently picks an
        arbitrary token).  Only the poisoned rows pay: slot rows are
        batch-independent, so the survivors' streams are untouched and
        stay token-identical to the fault-free oracle."""
        bad = [s for s in active if nxt[s.idx] == NOT_FINITE]
        if not bad:
            return active
        now = self.clock()
        for slot in bad:
            self._evict_errored(slot, now)
        return [s for s in active if nxt[s.idx] != NOT_FINITE]

    def _evict_errored(self, slot: Slot, now: float, *,
                       counter: str = "errors.sched.nan_row") -> None:
        """Terminal ERRORED eviction of one in-flight slot: the tokens
        streamed so far are kept, the slot is freed, the rest of the
        batch keeps decoding."""
        req = slot.req
        _REG.inc(counter)
        _REG.inc("sched.errored")
        res = RequestResult(
            req_id=req.req_id, tokens=list(slot.tokens),
            finish_reason=RequestState.ERRORED.value,
            prompt_len=req.prompt_len, arrival_s=req.arrival_s,
            first_token_s=slot.first_token_s if slot.emitted
            else float("nan"), finish_s=now)
        self.results.append(res)
        self.metrics.record_result(res)
        trace_event("sched.errored", req_id=req.req_id,
                    n_generated=res.n_generated)
        tr = get_tracer()
        rsp = self._req_spans.pop(req.req_id, None)
        if tr is not None and rsp is not None:
            tr.end(rsp, n_generated=res.n_generated,
                   finish_reason=res.finish_reason)
        if self.on_finish is not None:
            self.on_finish(res)
        self.slots.release(slot)

    def _activate(self, pf: _Prefill, logits, last_chunk: Chunk) -> None:
        """Last chunk done: sample the first token, graft the row into
        the slot cache, and join the decode batch."""
        slot, req = pf.slot, pf.slot.req
        row_logits = logits[0, last_chunk.n_real - 1]
        if not bool(np.isfinite(np.asarray(row_logits)).all()):
            # poisoned prefill output: evict before the row ever joins
            # the decode batch (no token was emitted for it yet)
            self._evict_errored(slot, self.clock())
            self._prefill_cache = pf.cache
            return
        tok = self._sample_one(row_logits, self._step_key(req, 0))
        self.slot_cache = self.engine.insert_row(
            self.slot_cache, pf.cache, slot.idx)
        self._prefill_cache = pf.cache   # next admission reuses it
        if self.prefix_cache is not None:
            # the completed prefill's rows are exact KV for this prompt:
            # offer its full-chunk prefix to future shared-prefix
            # admissions (the cache dedups / LRU-evicts internally)
            self.prefix_cache.insert(req.tokens, pf.cache)
        self._pos[slot.idx] = req.prompt_len
        self._cur[slot.idx] = tok
        slot.next_token = tok
        self._emit(slot, tok, self.clock(), first=True)

    def _emit(self, slot: Slot, tok: int, now: float,
              first: bool = False) -> None:
        req = slot.req
        tr = get_tracer()
        if first:
            slot.first_token_s = now
            if tr is not None:
                rsp = self._req_spans.get(req.req_id)
                if rsp is not None:
                    tr.event("sched.first_token", parent=rsp,
                             req_id=req.req_id)
        slot.emitted += 1
        slot.tokens.append(tok)
        _REG.inc("sched.tokens")
        if self.on_token is not None:
            self.on_token(req, tok)
        stopped = slot.stop_token is not None and tok == slot.stop_token
        if stopped or slot.emitted >= req.max_new_tokens:
            res = RequestResult(
                req_id=req.req_id, tokens=list(slot.tokens),
                finish_reason="stop" if stopped else "length",
                prompt_len=req.prompt_len, arrival_s=req.arrival_s,
                first_token_s=slot.first_token_s, finish_s=now)
            self.results.append(res)
            self.metrics.record_result(res)
            _REG.inc("sched.finished")
            rsp = self._req_spans.pop(req.req_id, None)
            if tr is not None and rsp is not None:
                tr.end(rsp, n_generated=res.n_generated,
                       finish_reason=res.finish_reason)
            if self.on_finish is not None:
                self.on_finish(res)
            self.slots.release(slot)

    # ----------------------------------------------------------- failover
    def evacuate(self) -> list[Request]:
        """Replica-failure drain (router failover): every *queued*
        request — nothing user-visible happened for those — is handed
        back for transparent re-routing, the in-flight prefill (no token
        emitted either) likewise, and decode slots that already streamed
        tokens are evicted as ERRORED with their streamed prefix kept
        (still oracle-identical — truncation, never divergence).  The
        scheduler is empty afterwards."""
        requeue = list(self.queue)
        self.queue.clear()
        if self._prefill is not None:
            pf, self._prefill = self._prefill, None
            req = pf.slot.req
            requeue.append(req)
            tr = get_tracer()
            rsp = self._req_spans.pop(req.req_id, None)
            if tr is not None and rsp is not None:
                tr.end(rsp, finish_reason="evacuated")
            self.slots.release(pf.slot)
        now = self.clock()
        for slot in self.slots.busy():
            self._evict_errored(slot, now,
                                counter="errors.sched.replica_down")
        _REG.inc("sched.evacuated", len(requeue))
        return requeue

    # ------------------------------------------------------------ sampling
    def _step_key(self, req: Request, token_idx: int):
        if self.cfg.temperature <= 0.0:
            return None
        req_key = jax.random.fold_in(self._base_key, req.req_id)
        return jax.random.fold_in(req_key, token_idx)

    def _sample_one(self, row, key) -> int:
        """Sample one token from a (V,) logits row under the scheduler's
        own temperature (the engine's temperature knob is not consulted
        anywhere in the continuous path)."""
        if self.cfg.temperature <= 0.0 or key is None:
            return int(jnp.argmax(row))
        return int(gumbel_argmax(row, self.cfg.temperature, key))

    def _pick_rows(self, logits, active: list[Slot]):
        """Dispatch the guard and sampling of a decode step's logits as
        one program (``Engine.pick_rows``); nothing is read back here.

        Greedy is batch-wide argmax (bit-identical to the oracle's).
        Temperature gives each active row the key of its (request, token
        index) — the same fold_in schedule as ``Engine.generate`` and
        ``_step_key`` — folded inside the program.  Every input has a
        fixed shape at the slot count, so the program compiles once."""
        if self.cfg.temperature <= 0.0:
            return self.engine.pick_rows(logits)
        req_ids = np.zeros((len(self.slots),), np.uint32)
        token_idx = np.zeros((len(self.slots),), np.uint32)
        for slot in active:
            req_ids[slot.idx] = slot.req.req_id
            token_idx[slot.idx] = slot.emitted
        return self.engine.pick_rows(logits, self.cfg.temperature,
                                     self._base_key, req_ids, token_idx)

    # ------------------------------------------------------------- driving
    def run(self, requests=None, *, max_steps: int = 1_000_000
            ) -> list[RequestResult]:
        """Submit `requests` (optional) and tick until fully drained."""
        for req in requests or ():
            self.submit(req)
        steps = 0
        while self.busy:
            self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError(f"scheduler not draining after "
                                   f"{max_steps} steps")
        return self.results
