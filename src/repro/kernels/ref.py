"""Pure-jnp oracles for every kernel in this package."""
from __future__ import annotations

import jax.numpy as jnp


def matmul_ref(a: jnp.ndarray, b: jnp.ndarray,
               out_dtype=None) -> jnp.ndarray:
    """fp32-accumulated matmul oracle."""
    out = jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    return out.astype(out_dtype or a.dtype)


def wkv6_ref(r, k, v, logw, u):
    """Sequential RWKV-6 WKV oracle; r/k/v/logw: (B,S,H,P), u: (H,P)."""
    import jax

    def step(state, inp):
        rt, kt, vt, lwt = inp
        kv = jnp.einsum("bhp,bhq->bhpq", kt, vt)
        y = jnp.einsum("bhp,bhpq->bhq", rt, state + u[None, :, :, None] * kv)
        state = state * jnp.exp(lwt)[..., None] + kv
        return state, y

    B, S, H, P = r.shape
    s0 = jnp.zeros((B, H, P, P), jnp.float32)
    xs = tuple(t.swapaxes(0, 1) for t in (r, k, v, logw))
    _, ys = jax.lax.scan(step, s0, xs)
    return ys.swapaxes(0, 1)


def ssd_ref(xh, dt, a_log, Bm, Cm, D):
    """Sequential Mamba2/SSD oracle; xh: (B,S,H,P), dt: (B,S,H),
    Bm/Cm: (B,S,N)."""
    import jax

    def step(state, inp):
        xt, dtt, bt, ct = inp
        a = jnp.exp(dtt * (-jnp.exp(a_log))[None, :])
        upd = jnp.einsum("bhp,bk->bhpk", xt * dtt[..., None], bt)
        state = state * a[..., None, None] + upd
        y = jnp.einsum("bhpk,bk->bhp", state, ct)
        return state, y

    B, S, H, P = xh.shape
    N = Bm.shape[-1]
    s0 = jnp.zeros((B, H, P, N), jnp.float32)
    xs = (xh.swapaxes(0, 1), dt.swapaxes(0, 1),
          Bm.swapaxes(0, 1), Cm.swapaxes(0, 1))
    _, ys = jax.lax.scan(step, s0, xs)
    ys = ys.swapaxes(0, 1)
    return ys + xh * D[None, None, :, None]


def decode_attention_ref(q, k, v, *, kv_len, q_positions, window=None,
                         window_active=True, softcap=None):
    """Plain float32 softmax oracle for one query token per row;
    q: (B,1,H,hd), k/v: (B,T,KV,hd), kv_len/q_positions: (B,)."""
    B, _, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    kf = jnp.repeat(k.astype(jnp.float32), G, axis=2)      # (B,T,H,hd)
    vf = jnp.repeat(v.astype(jnp.float32), G, axis=2)
    s = jnp.einsum("bhd,bthd->bht", q[:, 0].astype(jnp.float32), kf,
                   precision="highest") / jnp.sqrt(jnp.float32(hd))
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    t = jnp.arange(T)[None, :]
    qpos = jnp.asarray(q_positions)[:, None]
    ok = (t < jnp.asarray(kv_len)[:, None]) & (t <= qpos)
    if window is not None:
        ok = ok & ((qpos - t < window) | jnp.logical_not(window_active))
    s = jnp.where(ok[:, None, :], s, -jnp.inf)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bht,bthd->bhd", p, vf, precision="highest")
    return out[:, None]
