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

import jax
import jax.numpy as jnp
import numpy as np

from ..core import tpu_mapping
from ..models.model import Model
from ..obs.tracing import count_compiles


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


def donate_default() -> bool:
    """Whether the engine's slot-decode and graft programs donate the
    slot cache they are given.  On an accelerator they do: the slot
    cache is most of the device's memory, and a step that updates it in
    place holds one copy of it, not two.  On the CPU backend (tests and
    rehearsals) they do not, so code that keeps a cache past a step
    still reads it (``chipbench``'s fault that leaves the decode state
    unchanged does); a test turns donation on there by patching this."""
    return jax.default_backend() != "cpu"


def _named_jit(fn, name: str, **jit_kw):
    """``jax.jit(fn, **jit_kw)`` whose program is named ``jit_<name>``:
    the same computation, told apart from other jits of ``fn`` in a
    profile."""
    def named(*args):
        return fn(*args)
    named.__name__ = named.__qualname__ = name
    return jax.jit(named, **jit_kw)


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
        # optional GOMA plan database (repro.planner.PlanStore),
        # installed for dispatch: a kernel traced into one of this
        # engine's programs resolves its tiling through it
        # (core.tpu_mapping), and jit caching keeps steady state free of
        # solves
        if plan_store is not None:
            tpu_mapping.set_plan_store(plan_store)
        count_compiles()
        self._prefill = jax.jit(
            lambda p, b: model.prefill(p, b, max_len=cfg.cache_len))
        # chunk-capable, slot-indexable (see model.decode_step); one
        # compiled program per distinct (B, S) / index-rank signature,
        # jitted under the name of the primitive that runs it.  Where the
        # model carries the slot cache through its layer scan
        # (``Model.carries_cache``), ``decode_slots`` donates it and XLA
        # updates it in place.  A scan that slices each layer out of a
        # donated argument copies the whole cache once more, so there,
        # in the verify step and in the prefill chunk (whose cache the
        # scheduler and the prefix cache keep) the cache is kept.  The
        # graft writes one row in place and always donates.
        donate = donate_default()
        self._decode = jax.jit(model.decode_step)       # generate()
        self._prefill_chunk = _named_jit(model.decode_step, "prefill_chunk")
        self._decode_slots_kept = _named_jit(model.decode_step,
                                             "decode_slots")
        self._decode_slots = (
            _named_jit(model.decode_step, "decode_slots", donate_argnums=1)
            if donate else self._decode_slots_kept)
        self._insert = jax.jit(insert_row,
                               donate_argnums=(0,) if donate else ())

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
        writes land below/at their own positions only.  Returns (logits,
        cache).  The step may consume the ``cache`` passed in (donated
        and updated in place where the model carries it, see
        ``donate_default``): use the one returned."""
        tokens = jnp.asarray(tokens)
        positions = jnp.asarray(positions, jnp.int32)
        step = (self._decode_slots if self.model.carries_cache(
            self.params, cache, tokens, positions)
            else self._decode_slots_kept)
        return step(self.params, cache, tokens, positions)

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
        """Graft a B=1 prefill cache into row `slot` of the slot cache.
        The graft consumes ``slot_cache`` (donated and written in place,
        see ``donate_default``): use the one returned.  ``row_cache``
        stays valid."""
        return self._insert(slot_cache, row_cache,
                            jnp.asarray(slot, jnp.int32))

    def sample(self, logits, rng):
        """Greedy/temperature sampling (row-wise; rng may be None)."""
        return self._sample(logits, rng)

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
