"""Batched serving engine: prefill + greedy/temperature decode loop.

A deliberately small but real engine: jitted prefill and decode_step,
static-shape KV/state caches, batched requests with per-row lengths
(ragged prefill via right-padding + masked positions), and a
stop-token / max-token policy.  Used by examples/serve_lm.py and the
serving integration test.

Beyond the static ``generate`` loop, the engine exposes its *step-level*
primitives — ``new_cache`` / ``prefill_chunk`` / ``decode_slots`` /
``insert_row`` / ``sample`` — which the continuous-batching scheduler
(``serving.sched``) composes into an admission/prefill/decode loop.  All
of them run ``model.decode_step``, so the number of distinct compiled
programs is bounded by the number of chunk widths in use (see
sched.BucketSpec), not by traffic.  Each primitive jits it under its own
name (``jit_prefill_chunk``, ``jit_decode_slots``, ``jit_insert_row``,
``jit_verify_step``), so a profile tells their device time apart.
"""
from __future__ import annotations

import dataclasses
import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np

from ..models.model import Model
from ..obs.registry import get_registry
from ..obs.tracing import count_compiles

_REG = get_registry()
_LOG = logging.getLogger(__name__)


@dataclasses.dataclass
class ServeConfig:
    max_new_tokens: int = 32
    temperature: float = 0.0        # 0 = greedy
    stop_token: int | None = None
    cache_len: int = 512


def gumbel_argmax(logits, temperature: float, key):
    """Temperature sampling as Gumbel-max over the last axis — the one
    sampling implementation shared by the static engine and the
    continuous scheduler (token-identity depends on them agreeing)."""
    g = jax.random.gumbel(key, logits.shape)
    return jnp.argmax(logits / temperature + g, axis=-1).astype(jnp.int32)


def _named_jit(fn, name: str):
    """``jax.jit(fn)`` whose program is named ``jit_<name>``: the same
    computation, told apart from other jits of ``fn`` in a profile."""
    def named(*args):
        return fn(*args)
    named.__name__ = named.__qualname__ = name
    return jax.jit(named)


def insert_row(slot_cache, row_cache, slot):
    """Write a freshly prefilled B=1 cache row into slot `slot` of the
    slot-batched cache (batch is axis 1 of every KV leaf)."""
    return jax.tree.map(
        lambda big, small: jax.lax.dynamic_update_index_in_dim(
            big, small[:, 0].astype(big.dtype), slot, axis=1),
        slot_cache, row_cache)


class Engine:
    def __init__(self, model: Model, params, cfg: ServeConfig, *,
                 plan_store=None):
        self.model = model
        self.params = params
        self.cfg = cfg
        # optional GOMA plan database (repro.planner.PlanStore): serving
        # traffic consumes cached kernel tilings instead of solving inline
        self.plan_store = plan_store
        count_compiles()
        self._prefill = jax.jit(
            lambda p, b: model.prefill(p, b, max_len=cfg.cache_len))
        # chunk-capable, slot-indexable (see model.decode_step); one
        # compiled program per distinct (B, S) / index-rank signature,
        # jitted under the name of the primitive that runs it
        self._decode = jax.jit(model.decode_step)       # generate()
        self._prefill_chunk = _named_jit(model.decode_step, "prefill_chunk")
        self._decode_slots = _named_jit(model.decode_step, "decode_slots")
        self._insert = jax.jit(insert_row)

        # speculative-decoding verify: one decode_step over a whole
        # draft window, greedy-argmaxed *inside* the jit so only (B, W)
        # token ids and a (B,) finiteness mask cross to the host — never
        # the (B, W, V) logits (the verify loop is per-token otherwise)
        def verify_step(p, c, t, i):
            logits, cache = model.decode_step(p, c, t, i)
            greedy = jnp.argmax(
                jnp.where(jnp.isfinite(logits), logits, -jnp.inf),
                axis=-1).astype(jnp.int32)
            finite = jnp.all(jnp.isfinite(logits), axis=(1, 2))
            return greedy, finite, cache

        self._verify = jax.jit(verify_step)

    # ----------------------------------------------------- step-level API
    def new_cache(self, batch: int):
        """Fresh static cache for `batch` rows at cfg.cache_len."""
        return self.model.init_cache(batch, self.cfg.cache_len)

    def prefill_chunk(self, cache, tokens, index):
        """Run one prefill chunk (B, W) at scalar write position `index`
        against an existing cache; returns (logits (B, W, V), cache)."""
        return self._prefill_chunk(self.params, cache, jnp.asarray(tokens),
                                   jnp.asarray(index, jnp.int32))

    def decode_slots(self, cache, tokens, positions):
        """One decode step with per-row write positions (B,); rows are
        fully independent — inactive slots may carry garbage, their
        writes land below/at their own positions only."""
        return self._decode_slots(self.params, cache, jnp.asarray(tokens),
                                  jnp.asarray(positions, jnp.int32))

    def verify_step(self, cache, tokens, positions):
        """One speculative-verify step: decode ``tokens`` (B, W) — per
        row, the committed next token followed by W-1 draft tokens — at
        per-row write positions (B,), returning ``(greedy, finite,
        cache)`` where ``greedy`` (B, W) int32 is the target model's
        greedy continuation after each input token and ``finite`` (B,)
        flags rows whose logits stayed finite.  Greedy token j equals
        what width-1 decoding would have produced after consuming input
        tokens 0..j (chunked decode is bit-identical to sequential
        steps), so accepting drafts while they match ``greedy`` keeps
        the emitted stream byte-identical to target-only decoding."""
        return self._verify(self.params, cache, jnp.asarray(tokens),
                            jnp.asarray(positions, jnp.int32))

    def insert_row(self, slot_cache, row_cache, slot: int):
        """Graft a B=1 prefill cache into row `slot` of the slot cache."""
        return self._insert(slot_cache, row_cache,
                            jnp.asarray(slot, jnp.int32))

    def sample(self, logits, rng):
        """Greedy/temperature sampling (row-wise; rng may be None)."""
        return self._sample(logits, rng)

    def prewarm_plans(self, arch_id: str | None, batch: int,
                      prompt_len: int, *,
                      dtype_bytes: int | None = None,
                      source: str = "capture") -> int:
        """Pre-plan every GEMM tiling this deployment will hit (prefill at
        prompt_len + batched decode against the KV cache), through the
        plan database when one is installed.  After this, the serving loop
        never invokes the GOMA solver: every `kernels.ops.gemm` dispatch
        resolves its TpuTilePlan from cache.  Returns #shapes planned.

        ``source="capture"`` (default) reads the shape set off the
        engine's *own* jaxpr-traced prefill/decode programs
        (capture.plan) — the plans match what this model actually
        dispatches, smoke variants and frontend prefixes included, and
        ``arch_id`` is only documentation.  ``source="enumerated"``
        falls back to the hand-enumerated ``arch_id`` extraction tables.

        dtype_bytes defaults to the model's compute dtype — plan identity
        includes the dtype-rescaled VMEM capacity, so prewarming bf16
        plans for an f32 engine would all miss at dispatch time."""
        if source == "capture":
            from ..capture.plan import serving_capture_shapes
            shapes = serving_capture_shapes(self.model, batch, prompt_len,
                                            self.cfg.cache_len)
        else:
            if arch_id is None:
                raise ValueError(
                    "prewarm_plans(source='enumerated') needs an arch_id "
                    "to look up the extraction tables; only the capture "
                    "source reads everything off the model itself")
            from ..planner.batch import serving_plan_shapes
            shapes = serving_plan_shapes(arch_id, batch=batch,
                                         prompt_len=prompt_len,
                                         cache_len=self.cfg.cache_len)
        return self.prewarm_shapes(shapes, dtype_bytes=dtype_bytes)

    def prewarm_shapes(self, shapes, *,
                       dtype_bytes: int | None = None) -> int:
        """Plan an explicit (M, N, K) shape list through the installed
        store (or the in-process cache when none is).  Shared by
        ``prewarm_plans`` and the scheduler's bucketed prewarm.

        Best-effort: one unplannable shape is logged, counted under
        ``sched.prewarm_failures`` and skipped — it will solve cold at
        first dispatch instead of failing the whole prewarm.  Returns
        #shapes actually planned."""
        from ..planner.batch import prewarm_tpu_plans
        from ..planner.store import resolve_default_store
        if dtype_bytes is None:
            dtype_bytes = self.dispatch_dtype_bytes
        shapes = list(shapes)
        store = (self.plan_store if self.plan_store is not None
                 else resolve_default_store())
        planned = 0
        for s in shapes:
            try:
                if store is None:
                    from ..core.tpu_mapping import plan_gemm_tiling
                    plan_gemm_tiling(*s, dtype_bytes=dtype_bytes)
                    planned += 1
                else:
                    planned += prewarm_tpu_plans(
                        [s], store, dtype_bytes=dtype_bytes)
            except Exception as e:
                _REG.inc("sched.prewarm_failures")
                _LOG.warning("prewarm failed for GEMM shape %s (%s: %s); "
                             "it will solve at dispatch", s,
                             type(e).__name__, e)
        return planned

    def prewarm_chains(self, chains, *,
                       dtype_bytes: int | None = None) -> int:
        """Plan an explicit fused-MLP chain list ((M, FF, K, N2) shapes)
        through the installed store's fused section (or the in-process
        cache).  The fused counterpart of ``prewarm_shapes``: after this,
        a ``fused_mlp``-routed model resolves every chain plan from
        cache — zero chain solves in steady state.  Best-effort, like
        ``prewarm_shapes``."""
        from ..planner.batch import prewarm_fused_plans
        from ..planner.store import resolve_default_store
        if dtype_bytes is None:
            dtype_bytes = self.dispatch_dtype_bytes
        chains = list(chains)
        store = (self.plan_store if self.plan_store is not None
                 else resolve_default_store())
        planned = 0
        for c in chains:
            try:
                if store is None:
                    from ..core.tpu_mapping import plan_fused_mlp
                    plan_fused_mlp(*c, dtype_bytes=dtype_bytes)
                    planned += 1
                else:
                    planned += prewarm_fused_plans(
                        [c], store, dtype_bytes=dtype_bytes)
            except Exception as e:
                _REG.inc("sched.prewarm_failures")
                _LOG.warning("prewarm failed for fused chain %s (%s: %s); "
                             "it will solve at dispatch", c,
                             type(e).__name__, e)
        return planned

    def prewarm_sharded_shapes(self, shapes, *, n_chips: int,
                               dtype_bytes: int | None = None) -> int:
        """Plan an explicit (M, N, K) shape list through the installed
        store's *sharded* section: each shape gets a joint (mesh
        partition, per-chip tiling) plan for an ``n_chips`` mesh (see
        dist.mesh_solve).  The mesh counterpart of ``prewarm_shapes``;
        after this, a sharded deployment resolves every partition +
        tiling decision from cache — zero joint solves in steady state.

        Requires a store (sharded plans are deployment artifacts, not
        in-process caches): with none installed this is a counted no-op.
        Best-effort per shape, like ``prewarm_shapes``; failures count
        under ``dist.prewarm_failures``.  Returns #shapes planned."""
        from ..planner.batch import prewarm_sharded_plans
        from ..planner.store import resolve_default_store
        if dtype_bytes is None:
            dtype_bytes = self.dispatch_dtype_bytes
        store = (self.plan_store if self.plan_store is not None
                 else resolve_default_store())
        if store is None:
            _LOG.warning("prewarm_sharded_shapes needs a plan store; "
                         "skipping (install one via Engine(plan_store=...) "
                         "or $GOMA_PLAN_DB)")
            _REG.inc("dist.prewarm_skipped")
            return 0
        planned = 0
        for s in list(shapes):
            try:
                planned += prewarm_sharded_plans(
                    [s], store, n_chips=n_chips, dtype_bytes=dtype_bytes)
            except Exception as e:
                _REG.inc("dist.prewarm_failures")
                _LOG.warning("sharded prewarm failed for GEMM shape %s "
                             "(%s: %s); it will co-solve at first use", s,
                             type(e).__name__, e)
        _REG.inc("dist.prewarmed", planned)
        return planned

    def prewarm_pareto_shapes(self, shapes, *,
                              dtype_bytes: int | None = None,
                              max_points: int | None = 24) -> int:
        """Build certified (energy, delay) frontiers for an explicit
        (M, N, K) shape list into the installed store's pareto section
        (under the TPU dispatch identity).  The frontier counterpart of
        ``prewarm_shapes``: after this, latency-SLO point selection
        (``pareto_frontier`` + ``core.pareto.select_frontier_point``)
        never invokes the solver.

        Requires a store (frontiers are deployment artifacts): with none
        installed this is a counted no-op.  Best-effort per shape;
        failures count under ``sched.prewarm_failures``."""
        from ..planner.batch import prewarm_pareto_plans
        from ..planner.store import resolve_default_store
        if dtype_bytes is None:
            dtype_bytes = self.dispatch_dtype_bytes
        store = (self.plan_store if self.plan_store is not None
                 else resolve_default_store())
        if store is None:
            _LOG.warning("prewarm_pareto_shapes needs a plan store; "
                         "skipping (install one via Engine(plan_store=...) "
                         "or $GOMA_PLAN_DB)")
            _REG.inc("pareto.prewarm_skipped")
            return 0
        planned = 0
        for s in list(shapes):
            try:
                planned += prewarm_pareto_plans(
                    [s], store, dtype_bytes=dtype_bytes,
                    max_points=max_points)
            except Exception as e:
                _REG.inc("sched.prewarm_failures")
                _LOG.warning("pareto prewarm failed for GEMM shape %s "
                             "(%s: %s); skipping", s, type(e).__name__, e)
        _REG.inc("pareto.prewarmed", planned)
        return planned

    def pareto_frontier(self, M: int, N: int, K: int, *,
                        dtype_bytes: int | None = None,
                        max_points: int | None = 24):
        """The certified (energy, delay) frontier of one GEMM under its
        TPU dispatch identity, read through the installed store
        (``planner.batch.cached_solve_pareto``); a hit rehydrates the
        whole frontier with zero solver invocations."""
        from ..core import tpu_mapping
        from ..planner.batch import cached_solve_pareto
        from ..planner.store import resolve_default_store
        if dtype_bytes is None:
            dtype_bytes = self.dispatch_dtype_bytes
        gemm, hw, _ = tpu_mapping.tpu_problem(M, N, K,
                                              dtype_bytes=dtype_bytes)
        store = (self.plan_store if self.plan_store is not None
                 else resolve_default_store())
        return cached_solve_pareto(gemm, hw, store=store,
                                   max_points=max_points)

    @property
    def dispatch_dtype_bytes(self) -> int:
        """The dtype under which this engine's GEMMs dispatch (plan
        identity includes the dtype-rescaled VMEM capacity)."""
        return jnp.dtype(self.model.cfg.compute_dtype).itemsize

    def validate_capacity(self, prompt_len: int, max_new_tokens: int, *,
                          prefix_len: int = 0, lookahead: int = 0) -> None:
        """Fail fast instead of silently overflowing the static cache:
        every token of prompt + generation needs a cache position.
        ``lookahead`` reserves extra headroom past the last generated
        token — a speculative verify step writes up to spec_width - 1
        draft positions beyond the committed frontier, and those writes
        must land inside the cache even when every draft is rejected."""
        need = prefix_len + prompt_len + max_new_tokens + lookahead
        if need > self.cfg.cache_len:
            raise ValueError(
                f"request needs {need} cache positions (prefix "
                f"{prefix_len} + prompt {prompt_len} + max_new_tokens "
                f"{max_new_tokens} + lookahead {lookahead}) but "
                f"cache_len={self.cfg.cache_len}; shorten the request "
                f"or raise ServeConfig.cache_len")

    # With a stop token set, the all-rows-done early exit is checked only
    # every this many steps: each check is a device->host sync that
    # serializes the decode stream, so checking sparsely keeps the device
    # ahead of the host at the cost of <= STOP_CHECK_EVERY - 1 extra
    # (stop-token-padded) decode steps after the batch finishes.
    STOP_CHECK_EVERY = 4

    def generate(self, tokens: np.ndarray, *, extra_batch: dict | None
                 = None, rng: jax.Array | None = None) -> np.ndarray:
        """tokens: (B, S) right-padded prompt batch; returns (B, new).

        The decode loop keeps all bookkeeping (emitted tokens, per-row
        done flags) on device: no host sync happens per step — only the
        sparse stop-token early-exit check (see STOP_CHECK_EVERY) and
        one final transfer of the output buffer.  Rows that hit the stop
        token are padded with it; columns after the early exit are 0.

        With temperature > 0 the rng key is split per step
        (``fold_in(rng, t)``), so each sampled token draws fresh Gumbel
        noise; token t of a generation is reproducible from (rng, t)
        alone.
        """
        cfg = self.cfg
        B, S = tokens.shape
        prefix = 0
        for k in ("patches", "frames"):
            if extra_batch and k in extra_batch and \
                    self.model.cfg.family == "vlm":
                prefix = extra_batch[k].shape[1]
        self.validate_capacity(S, cfg.max_new_tokens, prefix_len=prefix)
        batch = {"tokens": jnp.asarray(tokens)}
        if extra_batch:
            batch.update(extra_batch)
        logits, cache = self._prefill(self.params, batch)
        out = jnp.zeros((B, cfg.max_new_tokens), jnp.int32)
        step_rng = (None if rng is None
                    else functools.partial(jax.random.fold_in, rng))
        cur = self._sample(logits[:, -1],
                           None if step_rng is None else step_rng(0))
        done = jnp.zeros((B,), bool)
        fill = jnp.int32(cfg.stop_token or 0)
        for t in range(cfg.max_new_tokens):
            out = out.at[:, t].set(jnp.where(done, fill, cur))
            if cfg.stop_token is not None:
                done = done | (cur == cfg.stop_token)
                last = t == cfg.max_new_tokens - 1
                if (t % self.STOP_CHECK_EVERY == self.STOP_CHECK_EVERY - 1
                        or last) and bool(done.all()):
                    break
            if t + 1 == cfg.max_new_tokens:
                break               # budget spent: the next step's token
            #                         would be discarded anyway
            idx = jnp.asarray(prefix + S + t, jnp.int32)
            logits, cache = self._decode(self.params, cache,
                                         cur[:, None], idx)
            cur = self._sample(logits[:, -1],
                               None if step_rng is None else step_rng(t + 1))
        return np.asarray(out)

    def _sample(self, logits, rng):
        if self.cfg.temperature <= 0.0 or rng is None:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return gumbel_argmax(logits, self.cfg.temperature, rng)
