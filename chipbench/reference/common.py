"""Pieces shared by the plain references: float32 matmuls at the highest
precision, norms, rotary embedding, causal attention and the logit gap.

The references take the configuration as run (the published
``config.json`` keys, with the departures the configuration file lists)
and the weights as a nested dict of arrays, stacked over layers on axis
0.  They have no cache, no batching across requests and no kernels: one
forward pass over whole sequences.

``precision`` is ``"float32"`` for the reference, or ``"int8"`` or
``"fp8"`` for the control: every matmul input is rounded -- weights per
output channel, activations, keys and values per row, each scaled to the
format's range -- as a W8A8 deployment with an 8-bit cache would hold
them (symmetric int8, or float8 e4m3), and then multiplied in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def int8_round(x, axis):
    """Symmetric int8 rounding of ``x`` with one scale per slice along
    ``axis`` (the reduction axis of the matmul it feeds)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def fp8_round(x, axis):
    """float8 e4m3 rounding of ``x`` with one scale per slice along
    ``axis``, mapping each slice's largest magnitude to 448."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


ROUNDING = {"float32": None, "int8": int8_round, "fp8": fp8_round}


class Numerics:
    def __init__(self, precision: str):
        if precision not in ROUNDING:
            raise ValueError(f"unknown precision {precision!r}")
        self.round = ROUNDING[precision]

    def weight(self, w):
        w = w.astype(jnp.float32)
        return w if self.round is None else self.round(w, -2)

    def act(self, x):
        return x if self.round is None else self.round(x, -1)

    def linear(self, x, w):
        """x (..., din) @ w (din, dout)."""
        return jnp.matmul(self.act(x), self.weight(w), precision=HIGHEST)


def norm(x, p, kind: str, eps: float):
    if kind == "layernorm":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        out = (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"]
        return out + p["bias"] if "bias" in p else out
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * p["scale"]


def rotary(x, theta: float, fraction: float):
    """Rotate-half rotary embedding on the first ``fraction`` of each
    head; x (B, T, H, hd) at positions 0 .. T - 1."""
    hd = x.shape[-1]
    rot = int(hd * fraction)
    half = rot // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def attention(num: Numerics, x, p, cfg: dict):
    """Causal self-attention of x (B, T, d) with weights p."""
    B, T, d = x.shape
    H = cfg["num_attention_heads"]
    KV = cfg.get("num_key_value_heads", H)
    hd = cfg.get("head_dim", d // H)
    q = num.linear(x, p["wq"]["w"]).reshape(B, T, H, hd)
    k = num.linear(x, p["wk"]["w"]).reshape(B, T, KV, hd)
    v = num.linear(x, p["wv"]["w"]).reshape(B, T, KV, hd)
    theta = cfg.get("rope_theta", 10000.0)
    frac = cfg.get("partial_rotary_factor", 1.0)
    q, k = rotary(q, theta, frac), rotary(k, theta, frac)
    k, v = num.act(k), num.act(v)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    scale = cfg.get("attention_multiplier", hd ** -0.5)
    s = jnp.einsum("bqhd,bkhd->bhqk", num.act(q), k,
                   precision=HIGHEST) * scale
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", num.act(pr), v, precision=HIGHEST)
    return num.linear(o.reshape(B, T, H * hd), p["wo"]["w"])


def norm_kind(cfg: dict) -> tuple[str, float]:
    if cfg.get("norm", "rmsnorm") == "layernorm":
        return "layernorm", cfg["layer_norm_eps"]
    return "rmsnorm", cfg["rms_norm_eps"]


def forward(num: Numerics, params, cfg: dict, tokens, mlp):
    """Logits (B, T, V) of the decoder; ``mlp(num, h, layer_params)``
    is the family's feed-forward block."""
    kind, eps = norm_kind(cfg)
    res = cfg.get("residual_multiplier", 1.0)
    x = params["embed"]["e"][tokens].astype(jnp.float32)
    x = x * cfg.get("embedding_multiplier", 1.0)

    def layer(x, lp):
        lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
        x = x + res * attention(num, norm(x, lp["ln1"], kind, eps),
                                lp["attn"], cfg)
        x = x + res * mlp(num, norm(x, lp["ln2"], kind, eps), lp)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = norm(x, jax.tree.map(lambda a: a.astype(jnp.float32),
                             params["final_norm"]), kind, eps)
    head = (params["embed"]["e"].T if cfg.get("tie_word_embeddings")
            else params["lm_head"]["w"])
    return num.linear(x, head) / cfg.get("logits_scaling", 1.0)


def gap_fn(cfg: dict, precision: str, mlp):
    """jit(params, tokens (B, T), cands (B, T)) -> (gap, top).

    ``gap[b, t]`` is how far the logit of ``cands[b, t]`` at position t
    lies below the best logit there, and ``top[b, t]`` the token this
    precision puts first.  Logits never leave the device."""
    num = Numerics(precision)

    @jax.jit
    def fn(params, tokens, cands):
        logits = forward(num, params, cfg, tokens, mlp)
        best = jnp.max(logits, axis=-1)
        pick = jnp.take_along_axis(logits, cands[..., None], axis=-1)[..., 0]
        return best - pick, jnp.argmax(logits, axis=-1).astype(jnp.int32)

    return fn
