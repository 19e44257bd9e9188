"""RWKV-6 "Finch" block in pure JAX — data-dependent decay WKV recurrence.

Per head (size P), with data-dependent per-channel decay w_t in (0,1),
bonus u, receptance r_t, key k_t, value v_t:

    S_t = diag(w_t) S_{t-1} + k_t v_t^T          (S in R^{P x P})
    y_t = r_t^T (S_{t-1} + diag(u) k_t v_t^T)

Training uses a chunked form (intra-chunk quadratic with decay products +
inter-chunk state scan) so 4k-training and 500k-decode both lower without
materializing O(S^2) tensors; decode is the O(1)-state step.  Token-shift
uses the Finch data-dependent linear interpolation (simplified: the
low-rank LoRA generators are folded into single dense maps — noted in
DESIGN.md as a modeling simplification that preserves shapes/FLOP
structure).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .layers import Params, dense, dense_init


def rwkv_dims(cfg):
    head_dim = 64
    return cfg.d_model // head_dim, head_dim


def rwkv_time_init(key, cfg, dtype) -> Params:
    d = cfg.d_model
    nh, hd = rwkv_dims(cfg)
    ks = jax.random.split(key, 8)
    return {
        "mix": jnp.full((5, d), 0.5, dtype),      # r,k,v,w,g shift mixes
        "wr": dense_init(ks[0], d, d, dtype),
        "wk": dense_init(ks[1], d, d, dtype),
        "wv": dense_init(ks[2], d, d, dtype),
        "wg": dense_init(ks[3], d, d, dtype),
        "ww": dense_init(ks[4], d, d, dtype),     # decay generator (folded LoRA)
        "wo": dense_init(ks[5], d, d, dtype),
        "u": jnp.zeros((nh, hd), jnp.float32),    # bonus
        "w_bias": jnp.full((d,), -6.0, jnp.float32),
    }


def rwkv_channel_init(key, cfg, dtype) -> Params:
    d = cfg.d_model
    ks = jax.random.split(key, 3)
    return {
        "mix": jnp.full((2, d), 0.5, dtype),
        "wk": dense_init(ks[0], d, cfg.d_ff, dtype),
        "wv": dense_init(ks[1], cfg.d_ff, d, dtype),
        "wr": dense_init(ks[2], d, d, dtype),
    }


def _token_shift(x: jnp.ndarray, prev: jnp.ndarray | None):
    """Shift right by one token; ``prev`` is the carry for decode."""
    if prev is None:
        prev = jnp.zeros_like(x[:, :1])
    shifted = jnp.concatenate([prev, x[:, :-1]], axis=1)
    return shifted, x[:, -1:]


def wkv_chunked(r, k, v, logw, u, *, chunk: int, init_state=None):
    """Chunked WKV6: r/k/v (B,S,H,P), logw (B,S,H,P) = log decay < 0.

    Returns (y, final_state) with state (B,H,P,P) mapping key-dim -> value-
    dim. Within a chunk the contribution of step s to step t>s is
    r_t . (prod_{s<j<=t-?} w) ... implemented with cumulative log-decays;
    the bonus-u diagonal handles the s == t term.
    """
    B, S, H, P = r.shape
    C = min(chunk, S)
    S_orig = S
    pad = (-S) % C
    if pad:
        # zero-contribution padding: logw=0 => w=1, k=v=r=0
        r = jnp.pad(r, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        logw = jnp.pad(logw, ((0, 0), (0, pad), (0, 0), (0, 0)))
        S = S + pad
    nc = S // C

    def resh(t):
        return t.reshape(B, nc, C, H, P).swapaxes(0, 1)  # (nc,B,C,H,P)

    rc, kc, vc, lwc = resh(r), resh(k), resh(v), resh(logw)
    cum = jnp.cumsum(lwc, axis=2)                        # (nc,B,C,H,P)
    total = cum[:, :, -1]                                # (nc,B,H,P)

    # intra-chunk: for t > s: y_t += r_t ⊙ exp(cum_{t-1} - cum_s) . k_s v_s
    # decay from s (exclusive) to t (exclusive of t's own w): cum[t-1]-cum[s]
    cum_tm1 = jnp.concatenate([jnp.zeros_like(cum[:, :, :1]),
                               cum[:, :, :-1]], axis=2)
    seg = cum_tm1[:, :, :, None] - cum[:, :, None, :]    # (nc,B,C,C,H,P)
    tri = jnp.tril(jnp.ones((C, C), bool), -1)           # strict lower
    decay = jnp.where(tri[None, None, :, :, None, None], jnp.exp(seg), 0.0)
    scores = jnp.einsum("nbthp,nbtshp,nbshp->nbtsh",
                        rc, decay, kc)                   # (nc,B,C,C,H)
    y_intra = jnp.einsum("nbtsh,nbshp->nbthp", scores, vc)
    # bonus diagonal term (s == t): (sum_p r_p u_p k_p) * v
    bonus = jnp.einsum("nbthp,hp,nbthp->nbth", rc, u, kc)
    y_intra += bonus[..., None] * vc

    # chunk-local suffix state: sum_s exp(total - cum_s) k_s v_s^T
    suffix = jnp.exp(total[:, :, None] - cum)            # (nc,B,C,H,P)
    chunk_state = jnp.einsum("nbshp,nbshq->nbhpq", kc * suffix, vc)

    if init_state is None:
        init_state = jnp.zeros((B, H, P, P), jnp.float32)

    def body(s_prev, inp):
        tot, st = inp
        s_new = s_prev * jnp.exp(tot)[..., None] + st
        return s_new, s_prev

    final_state, s_before = jax.lax.scan(body, init_state,
                                         (total, chunk_state))
    # inter-chunk: y_t += (r_t ⊙ exp(cum_{t-1})) . s_before
    y_inter = jnp.einsum("nbthp,nbhpq->nbthq", rc * jnp.exp(cum_tm1),
                         s_before)
    y = (y_intra + y_inter).swapaxes(0, 1).reshape(B, S, H, P)
    return y[:, :S_orig], final_state


def rwkv_time_apply(p: Params, cfg, x: jnp.ndarray, *,
                    state=None, shift=None, decode: bool = False):
    """Returns (y, (state, shift_carry))."""
    nh, hd = rwkv_dims(cfg)
    B, S, d = x.shape
    prev, new_shift = _token_shift(x, shift)
    mix = p["mix"].astype(x.dtype)
    xr = x + (prev - x) * mix[0]
    xk = x + (prev - x) * mix[1]
    xv = x + (prev - x) * mix[2]
    xw = x + (prev - x) * mix[3]
    xg = x + (prev - x) * mix[4]
    r = dense(p["wr"], xr).reshape(B, S, nh, hd).astype(jnp.float32)
    k = dense(p["wk"], xk).reshape(B, S, nh, hd).astype(jnp.float32)
    v = dense(p["wv"], xv).reshape(B, S, nh, hd).astype(jnp.float32)
    g = jax.nn.silu(dense(p["wg"], xg))
    logw = -jnp.exp((dense(p["ww"], xw).astype(jnp.float32)
                     + p["w_bias"]).reshape(B, S, nh, hd))  # < 0

    if decode:
        if state is None:
            state = jnp.zeros((B, nh, hd, hd), jnp.float32)
        w = jnp.exp(logw[:, 0])                           # (B,H,P)
        kv = jnp.einsum("bhp,bhq->bhpq", k[:, 0], v[:, 0])
        y = jnp.einsum("bhp,bhpq->bhq", r[:, 0],
                       state + p["u"][None, :, :, None] * kv)
        new_state = state * w[..., None] + kv
        y = y[:, None]
    elif getattr(cfg, "use_pallas_scan", False) and state is None:
        # Pallas kernel path (compiled; interpreted on the CPU)
        from ..kernels.ops import interpret_default
        from ..kernels.wkv6 import wkv6_pallas
        C = min(cfg.ssd_chunk, -(-S // 8) * 8)      # a multiple of 8
        pad = (-S) % C
        zpad = lambda t: jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
        y, new_state = wkv6_pallas(
            zpad(r), zpad(k), zpad(v), zpad(logw), p["u"].astype(jnp.float32),
            chunk=C, interpret=interpret_default())
        y = y[:, :S]
    else:
        y, new_state = wkv_chunked(r, k, v, logw, p["u"],
                                   chunk=cfg.ssd_chunk, init_state=state)
    y = y.reshape(B, S, d).astype(x.dtype) * g
    return dense(p["wo"], y), (new_state, new_shift)


def rwkv_channel_apply(p: Params, cfg, x: jnp.ndarray, *, shift=None):
    prev, new_shift = _token_shift(x, shift)
    mix = p["mix"].astype(x.dtype)
    xk = x + (prev - x) * mix[0]
    xr = x + (prev - x) * mix[1]
    k = jnp.square(jax.nn.relu(dense(p["wk"], xk)))
    return (jax.nn.sigmoid(dense(p["wr"], xr))
            * dense(p["wv"], k)), new_shift


def rwkv_time_ref(p: Params, cfg, x: jnp.ndarray):
    """Sequential O(S) reference for tests."""
    nh, hd = rwkv_dims(cfg)
    B = x.shape[0]

    def step(carry, xt):
        state, shift = carry
        y, (state, shift) = rwkv_time_apply(p, cfg, xt[:, None],
                                            state=state, shift=shift,
                                            decode=True)
        return (state, shift), y[:, 0]
    carry0 = (jnp.zeros((B, nh, hd, hd), jnp.float32),
              jnp.zeros((B, 1, cfg.d_model), x.dtype))
    _, ys = jax.lax.scan(step, carry0, x.swapaxes(0, 1))
    return ys.swapaxes(0, 1)
