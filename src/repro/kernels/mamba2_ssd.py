"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

zamba2-2.7b's non-GEMM hot spot.  Same TPU shape as the WKV6 kernel: one
(batch, head) stream per grid row, chunk index innermost, the (P x N)
state carried in VMEM scratch across consecutive grid steps; all decay
factors are exps of non-positive log differences (numerically safe).

Like the WKV6 kernel it works head-major, so each block's last two dims
are (chunk, P) or (chunk, 1): the per-step scalars dt arrive as a
(B, H, S, 1) column and the per-head A = -exp(a_log) as SMEM scalars.  The
in-chunk prefix sums are matmuls against triangular masks (Mosaic has no
cumsum), at fp32 contraction precision.

Math (models/ssm.py): S_t = a_t S_{t-1} + dt_t x_t B_t^T,
y_t = C_t^T S_t  (the D skip term is applied by the caller).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))      # x @ y.T
_TN = (((0,), (0,)), ((), ()))      # x.T @ y


def _ssd_kernel(xh_ref, dt_ref, a_ref, b_ref, c_ref, o_ref, so_ref,
                state_ref, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    xh = xh_ref[0, 0].astype(jnp.float32)             # (C, P)
    dt = dt_ref[0, 0].astype(jnp.float32)             # (C, 1)
    Bm = b_ref[0].astype(jnp.float32)                 # (C, N)
    Cm = c_ref[0].astype(jnp.float32)                 # (C, N)
    state = state_ref[...]                            # (P, N)
    P, N = state.shape

    la = dt * a_ref[pl.program_id(1)]                 # (C, 1), <= 0
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # la_cols[j, s] = la[j] for every s.  Mosaic broadcasts a value along
    # lanes or sublanes but not both, and has no cumsum, so every prefix
    # sum below is a matmul of a 0/1 mask against such a broadcast:
    # cum_t[t, s] = cum[t], cum_s[t, s] = cum[s] (cum inclusive, <= 0)
    la_cols = jnp.broadcast_to(la, (chunk, chunk))
    cum_t = jnp.dot((col <= row).astype(jnp.float32), la_cols,
                    precision=_HI)
    cum_s = jnp.dot(jnp.ones((chunk, chunk), jnp.float32),
                    jnp.where(row <= col, la_cols, 0.0), precision=_HI)
    cum = cum_t[:, :1]                                # (C, 1)
    rest = jnp.dot((col > row).astype(jnp.float32), la_cols,
                   precision=_HI)[:, :1]              # total - cum
    total = jnp.dot(jnp.ones((P, chunk), jnp.float32),
                    jnp.broadcast_to(la, (chunk, N)),
                    precision=_HI)                    # (P, N), all total
    xdt = xh * dt                                     # (C, P)

    # intra-chunk: y[t] += sum_{s<=t} exp(cum[t]-cum[s]) (C_t.B_s) xdt[s]
    tri = row >= col
    decay = jnp.where(tri, jnp.exp(jnp.where(tri, cum_t - cum_s, 0.0)), 0.0)
    scores = jax.lax.dot_general(Cm, Bm, _NT, precision=_HI) * decay
    y = jnp.dot(scores, xdt, precision=_HI)           # (C, P)
    # inter-chunk: y[t] += exp(cum[t]) * C_t @ state^T
    y = y + jnp.exp(cum) * jax.lax.dot_general(Cm, state, _NT,
                                               precision=_HI)

    # state update: S <- exp(total) S + (xdt . exp(total-cum))^T B
    new_state = jnp.exp(total) * state + jax.lax.dot_general(
        xdt * jnp.exp(rest), Bm, _TN, precision=_HI)
    state_ref[...] = new_state
    so_ref[0, 0] = new_state          # final chunk's write survives
    o_ref[0, 0] = y.astype(o_ref.dtype)


def ssd_pallas(xh, dt, a_log, Bm, Cm, *, chunk: int = 64,
               interpret: bool = False):
    """xh: (B,S,H,P); dt: (B,S,H); a_log: (H,); Bm/Cm: (B,S,N).

    Returns (y: (B,S,H,P) WITHOUT the D*x skip term (caller adds it),
    final_state: (B,H,P,N))."""
    B, S, H, P = xh.shape
    N = Bm.shape[-1]
    assert S % chunk == 0, "pad sequence to the chunk size first"
    assert chunk % 8 == 0, "chunk is a block's sublane dim: a multiple of 8"
    grid = (B, H, S // chunk)

    x_spec = pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0))
    dt_spec = pl.BlockSpec((1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0))
    a_spec = pl.BlockSpec(memory_space=pltpu.SMEM)   # (H,) scalars
    bn_spec = pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0))
    s_spec = pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0))
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y, state = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[x_spec, dt_spec, a_spec, bn_spec, bn_spec],
        out_specs=(x_spec, s_spec),
        out_shape=(jax.ShapeDtypeStruct((B, H, S, P), xh.dtype),
                   jax.ShapeDtypeStruct((B, H, P, N), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xh.transpose(0, 2, 1, 3), dt.transpose(0, 2, 1)[..., None],
      -jnp.exp(a_log.astype(jnp.float32)), Bm, Cm)
    return y.transpose(0, 2, 1, 3), state
