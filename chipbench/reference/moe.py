"""Plain float32 reference of a mixture-of-experts decoder.

The router takes a softmax over all experts and keeps the
``num_experts_per_tok`` largest, renormalised to sum to one (the same
as a softmax over the kept logits).  Each expert's gated SiLU MLP is
evaluated in turn, and a token adds its output only where that expert
is one of its own: ``where(routed, weight * out, 0)``.  There is no
capacity and nothing is dropped.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common


def mlp(num: common.Numerics, h, lp, *, top_k: int):
    p = lp["moe"]
    logits = num.linear(h, p["router"]["w"])                  # (B, T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, top_k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    n_exp = logits.shape[-1]

    def expert(y, e):
        wg, wu, wd = p["wg"][e], p["wu"][e], p["wd"][e]
        out = num.linear(jax.nn.silu(num.linear(h, wg))
                         * num.linear(h, wu), wd)
        w = jnp.sum(jnp.where(top_i == e, top_w, 0.0), axis=-1)
        routed = jnp.any(top_i == e, axis=-1)
        return y + jnp.where(routed[..., None], w[..., None] * out, 0.0), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(n_exp))
    return y


def gap_fn(cfg: dict, precision: str = "float32"):
    top_k = cfg["num_experts_per_tok"]
    return common.gap_fn(
        cfg, precision, lambda num, h, lp: mlp(num, h, lp, top_k=top_k))
