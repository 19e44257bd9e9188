"""Composable pure-JAX layer library (no flax).

Parameters are nested dicts of jnp arrays; every layer is an (init, apply)
pair of pure functions.  Attention is flash-style (KV-block scan with an
online softmax) so 32k-prefill and 500k-decode activations never
materialize the full score matrix — required for the dry-run memory
budgets (DESIGN.md §5).  A single-token step over a cache on one device
runs the Pallas decode kernel (``kernels/decode_attention.py``) instead.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from ..kernels.decode_attention import (decode_attention, stored_t_minor,
                                        write_rows)
from ..kernels.ops import interpret_default

Params = dict


def dtype_of(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype) -> Params:
    scale = 1.0 / math.sqrt(d_in)
    return {"w": (jax.random.normal(key, (d_in, d_out), jnp.float32)
                  * scale).astype(dtype)}


def dense(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    # params may be kept fp32 while activations run bf16: cast at use
    return x @ p["w"].astype(x.dtype)


def embed_init(key, vocab: int, d: int, dtype) -> Params:
    return {"e": (jax.random.normal(key, (vocab, d), jnp.float32)
                  * 0.02).astype(dtype)}


def embed(p: Params, tokens: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(p["e"], tokens, axis=0)


def norm_init(d: int, kind: str, dtype) -> Params:
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def apply_norm(p: Params, x: jnp.ndarray, kind: str,
               eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = ((xf - mu) * jax.lax.rsqrt(var + eps)
               * p["scale"].astype(jnp.float32)
               + p["bias"].astype(jnp.float32))
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------

def rope(x: jnp.ndarray, positions: jnp.ndarray,
         theta: float) -> jnp.ndarray:
    """x: (B, S, H, hd); positions: (B, S) or (S,)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-math.log(theta) *
                    jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B,S,half)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin,
                           xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# flash-style attention (KV-block scan, online softmax)
# ---------------------------------------------------------------------------

def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    q_positions: jnp.ndarray,
                    kv_positions: jnp.ndarray,
                    causal: bool = True,
                    window: int | None = None,
                    window_active: jnp.ndarray | None = None,
                    kv_len: jnp.ndarray | int | None = None,
                    softcap: float | None = None,
                    block: int = 512) -> jnp.ndarray:
    """q: (B,S,H,hd); k/v: (B,T,KV,hd).  GQA via head grouping.

    Memory per step is O(B*S*H*block) — the full (S,T) score matrix never
    exists.  ``kv_len`` masks the unwritten cache tail during decode.
    """
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, S, KV, G, hd).astype(jnp.float32) * scale
    block = min(block, T)
    n_blk = (T + block - 1) // block
    pad = n_blk * block - T
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, (0, pad),
                               constant_values=2**30)
    kb = k.reshape(B, n_blk, block, KV, hd).swapaxes(0, 1)
    vb = v.reshape(B, n_blk, block, KV, hd).swapaxes(0, 1)
    pb = kv_positions.reshape(n_blk, block)

    qpos = q_positions.astype(jnp.int32)          # (B,S) or (S,)
    if qpos.ndim == 1:
        qpos = jnp.broadcast_to(qpos[None], (B, S))
    limit = jnp.asarray(T if kv_len is None else kv_len, jnp.int32)

    m0 = jnp.full((B, S, KV, G), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, S, KV, G), jnp.float32)
    a0 = jnp.zeros((B, S, KV, G, hd), jnp.float32)

    def body(carry, blk):
        m, l, acc = carry
        kblk, vblk, posblk = blk                   # (B,block,KV,hd), (block,)
        s = jnp.einsum("bskgh,btkh->bskgt", qg,
                       kblk.astype(jnp.float32))
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        kpos = posblk.astype(jnp.int32)            # (block,)
        ok = kpos[None, None, :] < limit.reshape(
            (limit.shape[0] if limit.ndim else 1, 1, 1))
        if causal:
            ok = ok & (kpos[None, None, :] <= qpos[:, :, None])
        if window is not None:
            in_window = qpos[:, :, None] - kpos[None, None, :] < window
            if window_active is not None:
                # traced per-layer local/global switch (gemma2 alternation
                # under scan-over-layers): global layers ignore the window
                in_window = in_window | jnp.logical_not(window_active)
            ok = ok & in_window
        s = jnp.where(ok[:, :, None, None, :], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # rows with no valid key yet keep m = -inf; guard the exp
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(ok[:, :, None, None, :], p, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bskgt,btkh->bskgh", p, vblk.astype(jnp.float32))
        return (m_new, l, acc), None

    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kb, vb, pb))
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    return out.reshape(B, S, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention layer (self or cross), with optional KV cache
# ---------------------------------------------------------------------------

def _on_one_device(*xs) -> bool:
    """True unless an operand's type spans a mesh of several devices:
    the partitioner cannot split a Mosaic kernel, so a sharded program
    keeps the flash-attention scan."""
    return all(jax.typeof(x).sharding.mesh.size <= 1 for x in xs)


def attention_init(key, d_model: int, n_heads: int, kv_heads: int,
                   head_dim: int, dtype) -> Params:
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d_model, n_heads * head_dim, dtype),
        "wk": dense_init(ks[1], d_model, kv_heads * head_dim, dtype),
        "wv": dense_init(ks[2], d_model, kv_heads * head_dim, dtype),
        "wo": dense_init(ks[3], n_heads * head_dim, d_model, dtype),
    }


@jax.named_scope("attention")
def attention_apply(p: Params, x: jnp.ndarray, *,
                    n_heads: int, kv_heads: int, head_dim: int,
                    rope_theta: float | None,
                    q_positions: jnp.ndarray,
                    causal: bool = True,
                    window: int | None = None,
                    window_active: jnp.ndarray | None = None,
                    softcap: float | None = None,
                    xkv: jnp.ndarray | None = None,
                    kv_positions: jnp.ndarray | None = None,
                    cache: Params | None = None,
                    cache_index: jnp.ndarray | None = None,
                    static_cache: bool = False,
                    block: int = 512):
    """Returns (out, new_cache).  ``xkv`` switches to cross-attention.

    Cache layout: {"k": (B, T_max, KV, hd), "v": ...}; ``cache_index`` is
    the write position (decode step) — None means prefill writes [0, S).
    """
    B, S, _ = x.shape
    src = x if xkv is None else xkv
    q = dense(p["wq"], x).reshape(B, S, n_heads, head_dim)
    k = dense(p["wk"], src).reshape(B, src.shape[1], kv_heads, head_dim)
    v = dense(p["wv"], src).reshape(B, src.shape[1], kv_heads, head_dim)
    if kv_positions is None:
        kv_positions = (q_positions if xkv is None
                        else jnp.arange(src.shape[1]))
    if rope_theta is not None and xkv is None:
        q = rope(q, q_positions, rope_theta)
        k = rope(k, kv_positions if kv_positions.ndim == 1
                 else kv_positions, rope_theta)

    kv_len = None
    if cache is not None and static_cache:
        # cross-attention decode: reuse precomputed encoder K/V verbatim
        k, v = cache["k"], cache["v"]
        kv_positions = jnp.arange(k.shape[1])
        new_cache = cache
    elif cache is not None:
        if cache_index is not None and \
                getattr(cache_index, "ndim", 0) == 1:
            # slot-indexed write: each batch row has its own position
            # (continuous-batching decode, serving/sched), per-row
            # valid-length mask
            k_all = write_rows(cache["k"], k, cache_index)
            v_all = write_rows(cache["v"], v, cache_index)
            kv_len = cache_index + S                     # (B,)
        elif cache_index is not None:
            k_all = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype),
                (0, cache_index, 0, 0))
            v_all = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype),
                (0, cache_index, 0, 0))
            kv_len = cache_index + S
        else:
            k_all = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0))
            v_all = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0))
            kv_len = S
        new_cache = {"k": k_all, "v": v_all}
        k, v = k_all, v_all
        kv_positions = jnp.arange(k.shape[1])
    else:
        new_cache = None

    if (cache is not None and not static_cache and cache_index is not None
            and S == 1 and stored_t_minor(k.shape) and _on_one_device(q, k)):
        # one new token per row against its cache: the decode kernel
        # reads only each row's valid blocks, in the cache's own layout
        out = decode_attention(
            q, k, v, kv_len=kv_len, q_positions=q_positions, causal=causal,
            window=window, window_active=window_active, softcap=softcap,
            interpret=interpret_default())
    else:
        kv_len_arr = (None if kv_len is None
                      else jnp.asarray(kv_len, jnp.int32).reshape(-1))
        out = flash_attention(q, k, v, q_positions=q_positions,
                              kv_positions=kv_positions, causal=causal,
                              window=window, window_active=window_active,
                              kv_len=kv_len_arr, softcap=softcap,
                              block=block)
    out = dense(p["wo"], out.reshape(B, S, n_heads * head_dim))
    return out, new_cache


# ---------------------------------------------------------------------------
# gated MLP
# ---------------------------------------------------------------------------

def mlp_init(key, d_model: int, d_ff: int, dtype) -> Params:
    ks = jax.random.split(key, 3)
    return {"wg": dense_init(ks[0], d_model, d_ff, dtype),
            "wu": dense_init(ks[1], d_model, d_ff, dtype),
            "wd": dense_init(ks[2], d_ff, d_model, dtype)}


@jax.named_scope("mlp")
def mlp_apply(p: Params, x: jnp.ndarray, activation: str = "silu",
              use_fused: bool = False) -> jnp.ndarray:
    if use_fused:
        return fused_mlp_apply(p, x, activation=activation)
    g = dense(p["wg"], x)
    act = (jax.nn.silu if activation == "silu"
           else lambda t: jnp.square(jax.nn.relu(t))
           if activation == "sqrelu" else jax.nn.gelu)(g)
    return dense(p["wd"], act * dense(p["wu"], x))


def fused_mlp_apply(p: Params, x: jnp.ndarray,
                    activation: str = "silu") -> jnp.ndarray:
    """Gated MLP through the GOMA-chain-planned fused Pallas kernel.

    Token rows flatten to one (B*S, d) GEMM chain; the chain plan comes
    from the fused section of the plan database when one is installed
    (``core.tpu_mapping.plan_fused_mlp``).  Falls back internally to the
    per-GEMM composition when the chain's residency is infeasible."""
    from ..kernels.ops import fused_mlp
    *lead, d = x.shape
    x2 = x.reshape(-1, d)
    cdt = x.dtype
    out = fused_mlp(x2, p["wg"]["w"].astype(cdt), p["wu"]["w"].astype(cdt),
                    p["wd"]["w"].astype(cdt),
                    activation=f"{activation}_mul")
    return out.reshape(*lead, out.shape[-1])
