"""Pallas TPU kernel: one query token per row against its KV cache.

The decode step of every cache-resident model asks one new token of
each batch row to attend to that row's cache.  The flash-attention scan
in ``models/layers.py`` walks all ``T`` rows of every cache and masks
the invalid ones; this kernel reads only the blocks that hold a row's
valid keys, straight from the cache as it is stored.

Layout.  When ``hd`` does not fill whole 128-lane tiles, the TPU stores
a ``(B, T, KV, hd)`` cache with ``T`` as its minor dimension (the (KV,
hd) = (32, 64) pair would leave half of each tile empty), so its bytes
are those of a row-major ``(B, KV * hd, T)`` array.  The wrapper hands
the kernel that view; XLA compiles the transpose and reshape to a
bitcast, so no copy of the cache runs in front of the kernel.  A cache
whose ``hd`` is a multiple of 128 is stored row-major; the kernel would
need a copy of it, so ``stored_t_minor`` tells callers to keep the
flash scan there.

Every layer's cache, stacked ``(L, B, T, KV, hd)``, is read the same
way: its view is ``(L, B, KV * hd, T)`` and the layer to read arrives by
scalar prefetch, so a layer scan can carry the whole stacked cache and
write each layer's new rows into it in place (``write_rows`` with a
``layer``) with no slice of a layer taken out or stacked back.

Grid ``(B, T / tk)``; a block is ``(KV * hd, tk)``: every head, ``tk``
= 128 positions (one lane tile; fewer if the cache is shorter).
Per-row lengths arrive by scalar prefetch, and
the K/V ``index_map`` returns ``(layer, b, 0, min(j, last[b]))``, the
block index clamped to the row's last valid block: past it Pallas
starts no new DMA and ``pl.when`` skips the compute, so a row reads
``ceil(len / tk)`` blocks.

Arithmetic, per block:
  scores  on the MXU: a block-diagonal ``(H, KV * hd)`` query matrix
          (row ``h`` holds query head ``h`` in its KV head's columns)
          times the block, bf16 products exact in f32;
  softmax online, with f32 running max, sum and accumulator, masked by
          length, causal order, an optional window (switched per layer
          by a traced flag) and an optional tanh soft cap;
  PV      on the VPU in f32: each head's probabilities times its values,
          summed into a ``(hd, tk)`` accumulator, so the probabilities
          are never rounded below f32.
The last valid block turns each head's accumulator into its output row
(an MXU sum over the lanes at HIGHEST precision).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))      # x @ y.T
# positions per block: one lane tile.  On a v5e, at stablelm-1.6b's
# decode rows (24 x 1024, 32 heads of 64), 128 beat 256 and 512 on mixed
# lengths (205, 224, 247 us a call) and lost on full rows (325, 313,
# 289 us): a short row wastes less of its last block.
BLOCK = 128


def stored_t_minor(shape) -> bool:
    """Whether the TPU stores a (B, T, KV, hd) cache of this shape with
    T as its minor dimension: it does when hd leaves lanes empty (XLA's
    layouts on a v5e: (., ., 32, 64) and (., ., 32, 80) are T-minor,
    (., ., 8, 128) and (., ., 16, 128) row-major)."""
    return shape[-1] % 128 != 0


def _stored(x):
    """(..., T, KV, hd) -> (..., KV * hd, T): the bytes of a T-minor
    cache."""
    *lead, T, KV, hd = x.shape
    return jnp.moveaxis(x, len(lead), -1).reshape(*lead, KV * hd, T)


def _logical(x, kv_heads):
    """The inverse of ``_stored``."""
    *lead, D, T = x.shape
    return jnp.moveaxis(x.reshape(*lead, kv_heads, D // kv_heads, T),
                        -1, len(lead))


def write_rows(cache, new, index, layer=None):
    """``cache`` (B, T, KV, hd) with ``new`` (B, S, KV, hd) written at
    positions ``[index[b], index[b] + S)`` of each row ``b`` (``index``
    one int a row, or one for all).  With ``layer``, ``cache`` holds
    every layer's cache stacked, (L, B, T, KV, hd), and the rows go into
    layer ``layer``, in place.

    A T-minor cache is written through the view the kernel reads, so
    XLA keeps it in its stored layout (a write on the logical shape
    makes it relayout the whole cache before and after).
    """
    B, S, KV, hd = new.shape
    new = new.astype(cache.dtype)
    index = jnp.asarray(index, jnp.int32)
    if not stored_t_minor(cache.shape):
        # only a T-minor cache is stacked or written at a shared index
        assert layer is None and index.ndim == 1

        def row(c, u, i):
            return jax.lax.dynamic_update_slice(c, u, (i, 0, 0))
        return jax.vmap(row)(cache, new, index)
    out = _stored(cache if layer is not None else cache[None])
    rows = _stored(new)[None]                      # (1, B, KV * hd, S)
    lid = jnp.asarray(0 if layer is None else layer, jnp.int32)
    if index.ndim == 0:
        out = jax.lax.dynamic_update_slice(out, rows, (lid, 0, 0, index))
    else:
        # one in-place write a row: a vmapped write runs as a scatter
        # loop, 229 against 168 us for one layer's keys at 24 x 1024 on
        # a v5e
        for b in range(B):
            out = jax.lax.dynamic_update_slice(
                out, rows[:, b:b + 1], (lid, b, 0, index[b]))
    out = _logical(out, KV)
    return out if layer is not None else out[0]


def _kernel(last_ref, len_ref, qpos_ref, wact_ref, lid_ref,
            q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, tk: int, T: int, kv_heads: int,
            group: int, head_dim: int, scale: float, causal: bool,
            window: int | None, softcap: float | None):
    b, j = pl.program_id(0), pl.program_id(1)
    last = last_ref[b]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(j <= last)
    def _block():
        t = j * tk + jax.lax.broadcasted_iota(jnp.int32, (1, tk), 1)
        qpos = qpos_ref[b]
        ok = t < len_ref[b]
        if causal:
            ok = ok & (t <= qpos)
        if window is not None:
            ok = ok & ((qpos - t < window) | (wact_ref[0] == 0))
        qbd = q_ref[0]
        s = jax.lax.dot_general(
            qbd, k_ref[0].astype(qbd.dtype), (((1,), (0,)), ((), ())),
            precision=_HI if qbd.dtype == jnp.float32 else None,
            preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(ok, s, -jnp.inf)                  # (H, tk)
        m_prev = m_ref[...]                 # (H, tk), equal along lanes
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # rows with no valid key yet keep m = -inf; guard the exp
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(ok, jnp.exp(s - m_safe), 0.0)
        corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe),
                         0.0)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        for kh in range(kv_heads):
            v = v_ref[0, kh * head_dim:(kh + 1) * head_dim, :].astype(
                jnp.float32)                            # (hd, tk)
            if T % tk:
                # the last block runs past the cache: what lies there
                # is not data, and 0 * nan would poison the sum
                v = jnp.where(t < T, v, 0.0)
            for g in range(group):
                h = kh * group + g
                pv = v * p[h:h + 1, :]
                acc_ref[h] = acc_ref[h] * corr[h:h + 1, :] + pv

    @pl.when(j == last)
    def _finish():
        ones = jnp.ones((1, tk), jnp.float32)
        inv = 1.0 / jnp.maximum(l_ref[...][:, :1], 1e-20)   # (H, 1)
        for h in range(kv_heads * group):
            row = jax.lax.dot_general(ones, acc_ref[h], _NT, precision=_HI,
                                      preferred_element_type=jnp.float32)
            o_ref[0, h:h + 1, :] = (row * inv[h:h + 1, :]).astype(
                o_ref.dtype)


def decode_attention(q, k, v, *, kv_len, q_positions, causal: bool = True,
                     window: int | None = None, window_active=None,
                     softcap: float | None = None, layer=None,
                     interpret: bool = False):
    """Attention of one query token per row over that row's cache.

    q: (B, 1, H, hd); k/v: (B, T, KV, hd), the cache as stored (KV
    divides H: grouped-query heads; read in place where
    ``stored_t_minor``), or with ``layer`` (an int, traced or not) every
    layer's cache stacked, (L, B, T, KV, hd), of which layer ``layer``
    is read; kv_len, q_positions: ints, one per
    row or one for all.  Row ``b`` attends to cache positions
    ``t < kv_len[b]`` (and, when ``causal``, ``t <= q_positions[b]``;
    with ``window``, ``q_positions[b] - t < window`` unless the traced
    ``window_active`` is false).  Returns (B, 1, H, hd) in q's dtype, as
    ``models.layers.flash_attention`` does for the same arguments.
    """
    B, S, H, hd = q.shape
    if layer is None:
        k, v, layer = k[None], v[None], 0
    _, _, T, KV, _ = k.shape
    assert S == 1 and H % KV == 0, (q.shape, k.shape)
    G = H // KV
    tk = min(T, BLOCK)
    n_blk = pl.cdiv(T, tk)

    def rows(x):
        return jnp.broadcast_to(jnp.asarray(x, jnp.int32).reshape(-1), (B,))
    kv_len = jnp.minimum(rows(kv_len), T)
    qpos = rows(q_positions)
    last = jnp.clip((kv_len - 1) // tk, 0, n_blk - 1)
    wact = jnp.asarray(True if window_active is None else window_active,
                       jnp.int32).reshape(1)
    lid = jnp.asarray(layer, jnp.int32).reshape(1)
    # block-diagonal queries: row h = k*G + g holds q[h] in columns
    # [k*hd, (k+1)*hd), so one matmul scores every head against a block
    own = jnp.arange(H)[:, None] // G == jnp.arange(KV)[None, :]
    qbd = jnp.where(own[None, :, :, None], q[:, 0, :, None, :],
                    jnp.zeros((), q.dtype)).reshape(B, H, KV * hd)
    qbd = qbd.astype(jnp.promote_types(q.dtype, k.dtype))

    def kv_map(b, j, last_ref, len_ref, qpos_ref, wact_ref, lid_ref):
        return (lid_ref[0], b, 0, jnp.minimum(j, last_ref[b]))

    kernel = functools.partial(
        _kernel, tk=tk, T=T, kv_heads=KV, group=G, head_dim=hd,
        scale=1.0 / math.sqrt(hd), causal=causal, window=window,
        softcap=softcap)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B, n_blk),
            in_specs=[
                pl.BlockSpec((1, H, KV * hd), lambda b, j, *_: (b, 0, 0)),
                pl.BlockSpec((pl.squeezed, 1, KV * hd, tk), kv_map),
                pl.BlockSpec((pl.squeezed, 1, KV * hd, tk), kv_map),
            ],
            out_specs=pl.BlockSpec((1, H, hd), lambda b, j, *_: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((H, tk), jnp.float32),
                            pltpu.VMEM((H, tk), jnp.float32),
                            pltpu.VMEM((H, hd, tk), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="decode_attention",
    )(last, kv_len, qpos, wact, lid, qbd, _stored(k), _stored(v))
    return out.reshape(B, 1, H, hd)
