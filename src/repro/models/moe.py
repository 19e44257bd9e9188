"""Mixture-of-experts MLP: fine-grained routed experts + shared experts.

Deepseek-MoE style (2 shared + 64 routed, top-6) and Granite-MoE style
(32 routed, top-8).  Dispatch is dense one-hot einsum (Switch-style):
static shapes, GSPMD-friendly — experts shard over the "model" mesh axis
(expert parallelism reuses the TP axis; DESIGN.md §6).  An auxiliary
load-balancing loss is returned for training.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .layers import Params, dense_init


def moe_init(key, cfg, dtype) -> Params:
    ks = jax.random.split(key, 5)
    E, d, ff = cfg.n_experts, cfg.d_model, cfg.d_ff
    def bank(k, din, dout):
        scale = 1.0 / jnp.sqrt(din)
        return (jax.random.normal(k, (E, din, dout), jnp.float32)
                * scale).astype(dtype)
    p = {
        "router": dense_init(ks[0], d, E, jnp.float32),
        "wg": bank(ks[1], d, ff),
        "wu": bank(ks[2], d, ff),
        "wd": bank(ks[3], ff, d),
    }
    if cfg.shared_experts:
        ffs = ff * cfg.shared_experts
        kk = jax.random.split(ks[4], 3)
        p["shared"] = {
            "wg": dense_init(kk[0], d, ffs, dtype),
            "wu": dense_init(kk[1], d, ffs, dtype),
            "wd": dense_init(kk[2], ffs, d, dtype),
        }
    return p


@jax.named_scope("moe")
def moe_apply(p: Params, cfg, x: jnp.ndarray):
    """x: (B,S,d).  Returns (y, aux_loss); dispatch per cfg.moe_dispatch.

    "dense": every expert processes the full token set masked by its
    routing weight (one-hot combine) — static shapes, GSPMD-trivial, at
    the cost of E/top_k redundant compute.
    "gathered": capacity-bucketed sort-based dispatch (§Perf hillclimb
    B3) — experts process only their routed tokens (x capacity factor);
    overflow tokens drop (standard Switch semantics).
    """
    if getattr(cfg, "moe_dispatch", "dense") == "gathered":
        return moe_apply_gathered(p, cfg, x)
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    logits = (x.astype(jnp.float32) @ p["router"]["w"])      # (B,S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, k)                   # (B,S,k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    # combine weights as a dense (B,S,E) matrix
    combine = jnp.sum(jax.nn.one_hot(top_i, E, dtype=jnp.float32)
                      * top_w[..., None], axis=2)            # (B,S,E)

    xe = x.astype(jnp.float32)
    g = jnp.einsum("bsd,edf->bsef", xe, p["wg"].astype(jnp.float32))
    u = jnp.einsum("bsd,edf->bsef", xe, p["wu"].astype(jnp.float32))
    h = jax.nn.silu(g) * u
    y = jnp.einsum("bsef,efd,bse->bsd", h,
                   p["wd"].astype(jnp.float32), combine)

    if cfg.shared_experts:
        sh = p["shared"]
        hs = jax.nn.silu(xe @ sh["wg"]["w"].astype(jnp.float32)) \
            * (xe @ sh["wu"]["w"].astype(jnp.float32))
        y = y + hs @ sh["wd"]["w"].astype(jnp.float32)

    # load-balancing aux loss (Switch): E * sum_e f_e * P_e
    f = jnp.mean(jnp.sum(jax.nn.one_hot(top_i, E), axis=2), axis=(0, 1))
    pbar = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(f * pbar) / k
    return y.astype(x.dtype), aux


def moe_apply_gathered(p: Params, cfg, x: jnp.ndarray,
                       *, capacity_factor: float = 1.25):
    """Sort-based capacity-bucketed dispatch (§Perf hillclimb B3).

    Compute per expert shrinks from T tokens to C = cf*T*k/E tokens —
    an E/(k*cf) FLOP reduction vs dense dispatch (3.2x for granite-moe).
    Static shapes throughout: overflow beyond capacity drops (Switch
    semantics); a trash row absorbs dropped scatters."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    T = B * S
    xf = x.reshape(T, d).astype(jnp.float32)
    logits = xf @ p["router"]["w"]                     # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, k)             # (T, k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)

    C = max(1, int(capacity_factor * T * k / E))
    eid = top_i.reshape(-1)                            # (T*k,)
    w_flat = top_w.reshape(-1)
    order = jnp.argsort(eid, stable=True)
    sorted_eid = eid[order]
    starts = jnp.searchsorted(sorted_eid, jnp.arange(E))
    pos_in_expert = jnp.arange(T * k) - starts[sorted_eid]
    keep = pos_in_expert < C
    buf_idx = jnp.where(keep, sorted_eid * C + pos_in_expert, E * C)
    token_idx = order // k                             # source token

    # scatter tokens into (E*C [+1 trash], d) expert buffers
    xbuf = jnp.zeros((E * C + 1, d), jnp.float32).at[buf_idx].set(
        xf[token_idx])
    xe = xbuf[:E * C].reshape(E, C, d)
    g = jnp.einsum("ecd,edf->ecf", xe, p["wg"].astype(jnp.float32))
    u = jnp.einsum("ecd,edf->ecf", xe, p["wu"].astype(jnp.float32))
    ye = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u,
                    p["wd"].astype(jnp.float32))
    # gather back, weighted; dropped slots contribute zero
    contrib = (ye.reshape(E * C, d)[jnp.minimum(buf_idx, E * C - 1)]
               * (w_flat[order] * keep)[:, None])
    y = jnp.zeros((T, d), jnp.float32).at[token_idx].add(contrib)
    y = y.reshape(B, S, d)

    if cfg.shared_experts:
        sh = p["shared"]
        xs = x.astype(jnp.float32)
        hs = jax.nn.silu(xs @ sh["wg"]["w"].astype(jnp.float32)) \
            * (xs @ sh["wu"]["w"].astype(jnp.float32))
        y = y + hs @ sh["wd"]["w"].astype(jnp.float32)

    f = jnp.mean(jnp.sum(jax.nn.one_hot(top_i, E), axis=1), axis=0)
    pbar = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(f * pbar) / k
    return y.astype(x.dtype), aux
